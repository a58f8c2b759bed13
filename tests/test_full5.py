"""BASELINE configs[3] (`benchmark/configs/full5.json`): full ``Next`` with
DropMessage / DuplicateMessage at 5 servers under ``SYMMETRY Server``, on
the ``ddd`` engine, against the benchmark's plain reference
(``benchmark/reference``: states compared as states, no fingerprint).

At a small chunk on the CPU; the widths, the fan-out (84 actions a state),
the group (120 permutations), the four invariants and the bounds are the
configuration's own.  The first test is the one that found the fingerprint
scheme of PRs <= 25 merging distinct orbits here (936 counted of 937 at
level 6): ops/fingerprint.py has the account.
"""

import json
import os
import random
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import correct  # noqa: E402
from benchmark.reference import canon  # noqa: E402
from benchmark.reference import interp as rinterp  # noqa: E402
from benchmark.reference import spec as RS  # noqa: E402
from benchmark.reference.bounds import Bounds as RBounds  # noqa: E402
from raft_tla_tpu import ddd_engine as ddd_mod  # noqa: E402
from raft_tla_tpu.config import Bounds, CheckConfig  # noqa: E402
from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine  # noqa: E402
from raft_tla_tpu.models import interp  # noqa: E402
from raft_tla_tpu.ops import state as st  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "full5.json"),
          encoding="utf-8") as _f:
    FULL5 = json.load(_f)

CHUNK = 64
DEPTH = 7                  # 2,635 orbits; the frontier of level 7 is 7 blocks
FAMILIES = {"Restart", "Timeout", "RequestVote", "BecomeLeader",
            "ClientRequest", "AdvanceCommitIndex", "AppendEntries",
            "Receive", "DuplicateMessage", "DropMessage"}
RB = RBounds(**FULL5["bounds"])
RTABLE = RS.action_table(RB, FULL5["spec"])


def _engine(chunk=CHUNK, caps=None):
    """The configuration at chunk 64: ``seg_rows`` = 2 * chunk * A as
    check.py derives it past 2^19 (10,752 here, 688,128 at chunk 4096:
    neither a power of two), small blocks so that a level is many.  (On
    the chip the walked-states test is run once at the configuration's own
    chunk and capacities: PERF.md section 6, PR 26.)"""
    cfg = CheckConfig(bounds=Bounds(**FULL5["bounds"]), spec=FULL5["spec"],
                      invariants=tuple(FULL5["invariants"]),
                      symmetry=tuple(FULL5["symmetry"]), chunk=chunk)
    A = len(RTABLE)
    assert (A, st.Layout.of(cfg.bounds).width) == (84, 114)
    caps = caps or DDDCapacities(block=256, table=1 << 16,
                                 seg_rows=2 * chunk * A, flush=1 << 10,
                                 levels=64)
    assert caps.seg_rows & (caps.seg_rows - 1)
    return DDDEngine(cfg, caps)


@pytest.fixture(scope="module")
def eng():
    return _engine()


def _ref(s):
    return rinterp.PyState(**{f: getattr(s, f) for f in correct.STATE_FIELDS})


def _prog(s):
    return interp.PyState(**{f: getattr(s, f) for f in correct.STATE_FIELDS})


def _expand(eng, parents):
    """``parents`` (reference states) as one frontier block through the
    engine's compiled segment behind an empty filter: what the stream
    holds, in the shape ``correct.sample_checks`` takes (the states as
    reference states, in stream order), and the segment's stats."""
    import jax
    import jax.numpy as jnp
    P, block = eng.schema.P, eng.caps.block
    assert len(parents) <= block
    rows = np.zeros((block, P), np.int32)
    con = np.zeros((block,), bool)
    for k, s in enumerate(parents):
        rows[k] = eng.schema.pack(
            np.asarray(interp.to_vec(_prog(s), eng.bounds), np.int32), np)
        con[k] = rinterp.constraint_ok(s, RB)
    _fc, bufs, stats = eng._segment(
        eng._init_filter(), eng._make_bufs(), jnp.asarray(rows),
        jnp.asarray(con), jnp.int32(1 << 10), jnp.int32(len(parents)))
    bufs, stats = jax.device_get((bufs, stats))
    n = int(stats.cursor)
    states = [_ref(interp.from_struct(
        st.unpack(eng.schema.unpack(np.asarray(r), np), eng.lay, np),
        eng.bounds)) for r in bufs.orows[:n]]
    keys = (bufs.okey_hi[:n].astype(np.uint64) << np.uint64(32)) \
        | bufs.okey_lo[:n].astype(np.uint64)
    got = {"states": states, "keys": keys,
           "con": [bool(c) for c in bufs.ocon[:n]],
           "n_transitions": int(stats.n_valid), "done": bool(stats.done),
           "fail": int(stats.fail) | int(stats.viol_kind)}
    return got, stats


# ------------------------------------------------ level by level from Init

def _reference_search(depth):
    """The plain reference's own BFS to ``depth``: cumulative counts and
    the set of orbit representatives."""
    init = rinterp.init_state(RB)
    reps, frontier, cum = {canon.canonical(init)}, [init], [1]
    for _ in range(depth):
        nxt = []
        for s in frontier:
            if not rinterp.constraint_ok(s, RB):
                continue
            for _a, t in rinterp.successors(s, RB, RTABLE):
                k = canon.canonical(t)
                if k not in reps:
                    reps.add(k)
                    nxt.append(t)
        frontier = nxt
        cum.append(len(reps))
    return cum, reps


def test_ddd_equals_the_plain_reference_level_by_level(eng):
    """Counts at every level 0..7 and the set of orbit representatives:
    the engine's (its stored rows, canonicalised in plain Python) against
    the reference's own BFS.  Under the old fingerprint the engine merged
    two orbits at level 6 and four more by level 8."""
    want_cum, want = _reference_search(DEPTH)
    assert want_cum == FULL5["level_pins"][:DEPTH + 1]

    def stop_at_depth(rec):
        if rec["n_states"] >= want_cum[-1]:
            eng._sigint = True           # what the first SIGINT sets

    res = eng.check(on_progress=stop_at_depth, retain_store=True)
    host, constore, keystore, _n = eng.retained
    try:
        assert res.violation is None and not res.complete
        assert list(np.cumsum(res.levels))[:DEPTH + 1] == want_cum
        rows = host.read(0, want_cum[-1])
    finally:
        for store in (host, constore, keystore):
            store.close()
    got = {canon.canonical(_ref(interp.from_struct(
        st.unpack(eng.schema.unpack(r, np), eng.lay, np), eng.bounds)))
        for r in rows}
    assert got == want


# ------------------------- the compiled segment on states with leaders

def _walk_sample(seed, n_walks=300, steps=70):
    """States visited by seeded random walks of the reference from Init,
    through expandable states only.  Elections that complete are rare
    under a uniform choice (Restart and DropMessage undo them), so the
    choice is weighted by family; the sample keeps what the walks found of
    each kind of state."""
    weight = {"Restart": 0.03, "DropMessage": 0.15, "DuplicateMessage": 0.2,
              "Timeout": 0.6}
    rng = random.Random(seed)
    init = rinterp.init_state(RB)
    visited = {}
    for _ in range(n_walks):
        s = init
        for _ in range(steps):
            succ = [(a, t) for a, t in rinterp.successors(s, RB, RTABLE)
                    if rinterp.constraint_ok(t, RB)]
            if not succ:
                break
            a, s = rng.choices(
                succ, [weight.get(RTABLE[a].family, 1.0) for a, _t in succ])[0]
            visited.setdefault(canon.as_tuple(s), s)
    return list(visited.values())


def _has_ae_in_flight(s):
    from benchmark.reference import msgbits as mb
    sh, w = mb._HI_FIELDS["mtype"]
    return any(((hi >> sh) & ((1 << w) - 1)) == RS.M_AEREQ
               for (hi, _lo), _c in s.msgs)


def test_segment_equals_reference_successors_on_walked_states(eng):
    """What the benchmark's check (c) does, on parents a BFS prefix cannot
    give at 5 servers: states with a leader, with log entries, with
    AppendEntries requests in flight.  The transitions out of the sample
    cover all ten action families; the compiled segment's stream is
    exactly the reference's successor orbits, key <-> orbit one to one,
    constraint flags equal."""
    pool = _walk_sample(seed=26)
    rng = random.Random(2626)
    leaders = [s for s in pool if RS.LEADER in s.role]
    logs = [s for s in pool if any(s.log)]
    ae = [s for s in pool if _has_ae_in_flight(s)]
    assert leaders and logs and ae
    parents = []
    for group in (ae, logs, leaders, pool):
        for s in rng.sample(group, min(len(group), 48)):
            if s not in parents:
                parents.append(s)
    parents = parents[:eng.caps.block]
    fam = {RTABLE[a].family for s in parents
           for a, _t in rinterp.successors(s, RB, RTABLE)}
    assert fam == FAMILIES

    orbits, n_trans, con = canon.successor_orbits(parents, RB, FULL5["spec"],
                                                  True)
    got, _stats = _expand(eng, parents)
    ref = {"cumulative": [], "violations": 0, "orbits": orbits,
           "n_transitions": n_trans, "constraint": con, "sym": True}
    assert got["states"] and n_trans > len(orbits)
    for name, value, limit in correct.sample_checks(ref, got, pins=[]):
        assert value == limit == 0, name


# ------------------------------------------- a chunk of more than one slab

def test_a_chunk_past_one_slab_streams_the_reference_order(monkeypatch):
    """With the slab cut to 256 rows a 64-row chunk of level-7 states
    streams several slabs (``stream_slabs`` > ``steps``), and the stream is
    the reference's successors in discovery order: parents in block order,
    actions in table order, an orbit at its first occurrence.  (A later
    chunk may stream an orbit again when the lossy filter did not keep it;
    the host's exact dedup keeps the first, as here.)"""
    monkeypatch.setattr(ddd_mod, "_S_OUT", 256)
    eng = _engine()
    _cum, level, _v = canon.bfs_levels(
        RB, FULL5["spec"], True, tuple(FULL5["invariants"]),
        min_level_states=1000)
    parents = [s for s in level if rinterp.constraint_ok(s, RB)][:3 * CHUNK]
    want, seen = [], set()
    for s in parents:
        for _a, t in rinterp.successors(s, RB, RTABLE):
            k = canon.canonical(t)
            if k not in seen:
                seen.add(k)
                want.append(k)
    got, stats = _expand(eng, parents)
    assert int(stats.steps) == 3 and got["fail"] == 0
    assert int(stats.stream_peak) > 256
    assert int(stats.stream_slabs) > int(stats.steps)
    streamed = [canon.canonical(s) for s in got["states"]]
    first = list(dict.fromkeys(streamed))
    assert first == want
    assert len(streamed) - len(first) < len(want) // 20


# ------------------------------------------------------- the planted fault

def test_planted_two_leaders_is_reported_at_five_servers(eng):
    """The benchmark's check (d) at 5 servers: a reference state rewritten
    so that one BecomeLeader gives two leaders in one term, handed to the
    engine through ``check(init_override=...)``."""
    _cum, level, _v = canon.bfs_levels(
        RB, FULL5["spec"], True, tuple(FULL5["invariants"]),
        min_level_states=200)
    plant = correct.planted_fault(FULL5, level, seed=26)

    def stop_after_one_level(rec):
        if rec["level"] >= 1:
            eng._sigint = True

    res = eng.check(init_override=_prog(plant["parent"]),
                    on_progress=stop_after_one_level)
    v = res.violation
    assert v is not None
    names = plant["violators"].get(plant["key"](_ref(v.state)))
    assert names is not None and v.invariant in names
    assert "NoTwoLeaders" in names
