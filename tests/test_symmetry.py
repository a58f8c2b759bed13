"""Server-permutation symmetry reduction (TLC SYMMETRY analog).

Correctness anchors: the orbit key is permutation-invariant; the
symmetry-reduced oracle count equals the brute-force orbit count of the
full space; the device engine under symmetry reproduces the reduced oracle
exactly; violations still surface with replayable traces.
"""

import itertools

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.device_engine import Capacities, DeviceEngine
from raft_tla_tpu.models import interp, refbfs, spec as S
from raft_tla_tpu.ops import msgbits as mb
from raft_tla_tpu.ops import symmetry as sym

B2 = Bounds(n_servers=2, n_values=1, max_term=2, max_log=0, max_msgs=2)
B3 = Bounds(n_servers=3, n_values=1, max_term=2, max_log=0, max_msgs=1)


def bag(*ms):
    return tuple(sorted((m, 1) for m in ms))


def permute_py_state(s, p, bounds):
    """Reference permutation on the PyState view (independent impl)."""
    n = bounds.n_servers
    inv = [p.index(k) for k in range(n)]

    def vf(v):
        return 0 if v == 0 else p[v - 1] + 1

    def mask(m):
        out = 0
        for j in range(n):
            out |= ((m >> j) & 1) << p[j]
        return out

    msgs = []
    for (hi, lo), cnt in s.msgs:
        hi2 = mb.pack_hi(mb.mtype(hi), mb.mterm(hi), mb.fa(hi), mb.fb(hi),
                         p[mb.src(hi)], p[mb.dst(hi)])
        msgs.append(((hi2, lo), cnt))
    return s._replace(
        role=tuple(s.role[inv[k]] for k in range(n)),
        term=tuple(s.term[inv[k]] for k in range(n)),
        votedFor=tuple(vf(s.votedFor[inv[k]]) for k in range(n)),
        commitIndex=tuple(s.commitIndex[inv[k]] for k in range(n)),
        log=tuple(s.log[inv[k]] for k in range(n)),
        vResp=tuple(mask(s.vResp[inv[k]]) for k in range(n)),
        vGrant=tuple(mask(s.vGrant[inv[k]]) for k in range(n)),
        nextIndex=tuple(tuple(s.nextIndex[inv[k]][inv[j]] for j in range(n))
                        for k in range(n)),
        matchIndex=tuple(tuple(s.matchIndex[inv[k]][inv[j]]
                               for j in range(n)) for k in range(n)),
        msgs=tuple(sorted(msgs)))


def reachable_states(bounds, spec):
    table = S.action_table(bounds, spec)
    seen = {interp.init_state(bounds)}
    frontier = list(seen)
    while frontier:
        nxt = []
        for s in frontier:
            if not interp.constraint_ok(s, bounds):
                continue
            for _a, t in interp.successors(s, bounds, table):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    return seen


def test_orbit_key_is_permutation_invariant():
    states = list(reachable_states(B3, "election"))[:300]
    perms = list(itertools.permutations(range(3)))
    for s in states[:60]:
        keys = {sym.py_orbit_fingerprint(permute_py_state(s, p, B3), B3)
                for p in perms}
        assert len(keys) == 1


def test_oracle_orbit_count_matches_brute_force():
    cfg = CheckConfig(bounds=B2, spec="election", invariants=(),
                      symmetry=("Server",))
    reduced = refbfs.check(cfg)
    full = reachable_states(B2, "election")
    orbits = {sym.py_orbit_fingerprint(s, B2) for s in full}
    assert reduced.n_states == len(orbits) == 1514
    assert len(full) == 3014


def test_device_engine_symmetry_parity():
    cfg = CheckConfig(bounds=B3, spec="election",
                      invariants=("NoTwoLeaders",), symmetry=("Server",),
                      chunk=256)
    ref = refbfs.check(cfg)
    got = DeviceEngine(cfg, Capacities(n_states=1 << 16, levels=64)).check()
    assert got.n_states == ref.n_states
    assert got.diameter == ref.diameter
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert got.coverage == ref.coverage
    assert got.violation is None
    # sanity: it actually reduced (full space is 142538 with 2 values /
    # this config's unreduced count is strictly larger)
    unred = refbfs.check(CheckConfig(bounds=B3, spec="election",
                                     invariants=("NoTwoLeaders",)))
    assert ref.n_states < unred.n_states


def test_symmetry_violation_trace_replayable():
    bounds = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                    max_msgs=4, max_dup=1)
    cfg = CheckConfig(bounds=bounds, spec="election",
                      invariants=("NaiveNoTwoLeaders",),
                      symmetry=("Server",), chunk=256)
    start = interp.init_state(bounds)._replace(
        role=(S.LEADER, S.FOLLOWER, S.CANDIDATE),
        term=(2, 3, 3), votedFor=(1, 3, 0),
        vGrant=(0b011, 0, 0b100),
        msgs=bag(mb.rv_response(3, 1, 1, 2)))
    ref = refbfs.check(cfg, init_override=start)
    got = DeviceEngine(cfg, Capacities(n_states=1 << 15, levels=64)
                       ).check(init_override=start)
    assert ref.violation is not None and got.violation is not None
    assert got.violation.state == ref.violation.state
    trace = got.violation.trace
    for (_l, prev), (_label, cur) in zip(trace, trace[1:]):
        succs = [t for _i, t in interp.successors(prev, bounds,
                                                  spec="election")]
        assert cur in succs


def test_too_many_servers_is_loud():
    with pytest.raises(ValueError, match="symmetry"):
        sym.permutations(Bounds(n_servers=7, n_values=1, max_term=2,
                                max_log=0, max_msgs=1))


def test_host_engine_symmetry_parity():
    """Regression: the host-dedup engine must apply the same orbit keys
    (it once silently skipped the reduction while printing the banner)."""
    from raft_tla_tpu import engine
    cfg = CheckConfig(bounds=B2, spec="election", invariants=(),
                      symmetry=("Server",), chunk=64)
    ref = refbfs.check(cfg)
    got = engine.check(cfg)
    assert got.n_states == ref.n_states == 1514
    assert got.levels == ref.levels


def test_value_symmetry_orbit_counts():
    """Value permutations (TLC Permutations(Value)) quotient further:
    values enter only through ClientRequest and flow inertly, so
    Server x Value orbits < Server orbits < raw states, same diameter."""
    bp = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2)

    def run(axes):
        return refbfs.check(CheckConfig(bounds=bp, spec="full",
                                        invariants=(), symmetry=axes))
    base, s_only, v_only, sv = (run(()), run(("Server",)), run(("Value",)),
                                run(("Server", "Value")))
    assert base.n_states == 74897
    assert (s_only.n_states, v_only.n_states, sv.n_states) == \
        (37472, 50515, 25281)
    assert base.diameter == s_only.diameter == v_only.diameter == sv.diameter


def test_value_symmetry_engine_parity():
    from raft_tla_tpu import engine
    bp = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2)
    cfg = CheckConfig(bounds=bp, spec="full", invariants=("NoTwoLeaders",),
                      symmetry=("Server", "Value"), chunk=512)
    ref = refbfs.check(cfg)
    got = engine.check(cfg)
    assert (got.n_states, got.diameter) == (ref.n_states, ref.diameter)
    assert got.coverage == ref.coverage and got.violation is None


def test_value_symmetry_faithful_mode():
    """Rank-table remaps + bitwise allLogs permutation: faithful spaces
    quotient under Server x Value too, engines in exact agreement."""
    from raft_tla_tpu import engine
    bh = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2,
                history=True, max_elections=4)
    cf = CheckConfig(bounds=bh, spec="full",
                     invariants=("NoTwoLeaders", "ElectionSafetyHist"),
                     symmetry=("Server", "Value"), chunk=512)
    ref = refbfs.check(cf)
    got = engine.check(cf)
    assert (ref.n_states, ref.diameter) == (28121, 32)  # of 84572 states
    assert (got.n_states, got.diameter) == (28121, 32)
    assert ref.violation is None and got.violation is None


_B3S = Bounds(n_servers=3, n_values=2, max_term=2, max_log=1, max_msgs=2)
_BH2 = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2,
              history=True, max_elections=4)
# the benchmark's 5-server bounds (benchmark/configs/elect5.json, full5.json)
_ELECT5 = Bounds(n_servers=5, n_values=2, max_term=2, max_log=0, max_msgs=2,
                 max_dup=1)
_FULL5 = Bounds(n_servers=5, n_values=2, max_term=2, max_log=1, max_msgs=2,
                max_dup=1)
def _scan_case_states(bounds, spec, depth, lane_cap, cap, first=False):
    """A bag of reachable states: BFS prefix via the interpreter, keeping
    ``lane_cap`` successors a level — every k-th one (late ones carry the
    deeper histories), or with ``first`` the first ones (the low action
    ids: timeouts, vote requests and their replies, where servers still
    look alike) with the constraint ignored."""
    frontier = [interp.init_state(bounds)]
    seen = list(frontier)
    for _ in range(depth):
        nxt = []
        for s in frontier:
            # a state past the constraint is counted, not expanded
            if first or interp.constraint_ok(s, bounds):
                nxt += [t for _i, t in interp.successors(s, bounds,
                                                         spec=spec)]
        stride = 1 if first else max(1, len(nxt) // lane_cap)
        frontier = nxt[::stride][:lane_cap]
        seen += frontier
    return seen[:cap]


def _random_states(bounds, n, seed):
    from test_state import random_pystate
    rng = np.random.default_rng(seed)
    return [random_pystate(rng, bounds) for _ in range(n)]


def _all_distinct_state():
    """No two servers interchangeable: every one of the 6 permutations
    gives another orbit member, so the min really ranges over the group."""
    return interp.init_state(_B3S)._replace(
        role=(0, 1, 2), term=(1, 2, 2), votedFor=(0, 2, 3))


# name -> (bounds, axes, VIEW or None, states, at least this many)
_SCAN_CASES = {
    "3s-server": (_B3S, ("Server",), None,
                  lambda: _scan_case_states(_B3S, "full", 4, 40, 200), 100),
    "3s-value": (_B3S, ("Value",), None,
                 lambda: _scan_case_states(_B3S, "full", 4, 40, 200), 100),
    "3s-server-value": (
        _B3S, ("Server", "Value"), None,
        lambda: _scan_case_states(_B3S, "full", 4, 40, 200), 100),
    "2s-faithful-server-value": (
        _BH2, ("Server", "Value"), None,
        lambda: _scan_case_states(_BH2, "full", 4, 40, 200), 100),
    "2s-faithful-value": (
        _BH2, ("Value",), None,
        lambda: _scan_case_states(_BH2, "full", 6, 60, 300), 100),
    "elect5-server": (
        _ELECT5, ("Server",), None,
        lambda: _scan_case_states(_ELECT5, "election", 7, 60, 300), 300),
    "full5-server": (
        _FULL5, ("Server",), None,
        lambda: _scan_case_states(_FULL5, "full", 7, 60, 300), 300),
    # the poles of the orbit: every permutation ties / none does
    "5s-all-identical": (
        _ELECT5, ("Server",), None,
        lambda: [interp.init_state(_ELECT5)] * 4, 4),
    "3s-all-distinct": (
        _B3S, ("Server",), None, lambda: [_all_distinct_state()], 1),
    "3s-first-lanes-server-value": (
        _B3S, ("Server", "Value"), None,
        lambda: _scan_case_states(_B3S, "full", 3, 60, 150, first=True), 100),
    "2s-faithful-first-lanes-server-value": (
        _BH2, ("Server", "Value"), None,
        lambda: _scan_case_states(_BH2, "full", 4, 60, 150, first=True), 100),
    # the engines hand the scan the VIEWED struct; random bounded states,
    # because votes on a server that is no candidate (what the view
    # folds) are rare in a BFS prefix
    "3s-view-server": (_B3S, ("Server",), "deadvotes",
                       lambda: _random_states(_B3S, 120, seed=28), 120),
}


@pytest.mark.parametrize("case", list(_SCAN_CASES))
def test_scan_orbit_fp_bit_identical_to_loop(case):
    """The scan-compiled orbit pass (build_orbit_fp — ONE transform
    iterated over the group, keying each image from its fields) must
    produce bit-identical (hi, lo) keys to the reference unrolled loop
    (orbit_fingerprint, which packs the row): checkpointed runs resume
    across the upgrade only if the keys are unchanged.  Under a VIEW the
    scan sees the device view of the struct and the loop the host view
    of the state."""
    import jax
    import jax.numpy as jnp
    from raft_tla_tpu.models import views
    from raft_tla_tpu.ops import fingerprint as fpr
    from raft_tla_tpu.ops import state as st

    bounds, axes, view, make, at_least = _SCAN_CASES[case]
    lay = st.Layout.of(bounds)
    consts = fpr.lane_constants(lay.width)
    seen = make()
    assert len(seen) >= at_least, len(seen)
    vecs = np.stack([interp.to_vec(s, bounds) for s in seen])
    structs = jax.vmap(lambda v: st.unpack(v, lay, jnp))(jnp.asarray(vecs))
    if view:
        structs = jax.vmap(views.jnp_view(view, bounds))(structs)
        host_view = views.py_view(view)
        raw = vecs
        vecs = np.stack([interp.to_vec(host_view(s, bounds), bounds)
                         for s in seen])
        assert (vecs != raw).any(axis=1).sum() >= len(seen) // 2
    faithful = "allLogs" in lay.shapes
    fn = sym.build_orbit_fp(bounds, axes, jnp.asarray(consts), faithful)
    hi_s, lo_s = (np.asarray(a) for a in jax.jit(fn)(structs))
    for k, s in enumerate(seen):
        struct = st.unpack(vecs[k], lay, np)
        hi_l, lo_l = sym.orbit_fingerprint(struct, bounds, consts, np, axes)
        assert (int(hi_s[k]), int(lo_s[k])) == (int(hi_l), int(lo_l)), \
            (axes, k, s)
    if case == "3s-all-distinct":
        # the case can see: the identity's image is not the orbit's min
        ih, il = fpr.fingerprint(vecs, consts, np)
        assert (int(ih[0]), int(il[0])) != (int(hi_s[0]), int(lo_s[0]))


def test_scan_body_builds_no_packed_row():
    """The row must not come back: lowered at 5 servers, the orbit scan
    holds no ``[lanes, W]`` tensor at all, so no ``concatenate`` (nor
    ``reshape``) of one in its loop body — PR 27 took it out of the body,
    where it was written to HBM and read back 120 times a chunk step.
    The packed form of the same states does show it (the test can see)."""
    import re

    import jax
    import jax.numpy as jnp
    from raft_tla_tpu.ops import fingerprint as fpr
    from raft_tla_tpu.ops import state as st

    lanes = 24
    for bounds in (_ELECT5, _FULL5):
        lay = st.Layout.of(bounds)
        consts = jnp.asarray(fpr.lane_constants(lay.width))
        struct = {f: jax.ShapeDtypeStruct((lanes,) + tuple(shape), jnp.int32)
                  for f, shape in lay.shapes.items()}
        row = re.compile(rf"tensor<{lanes}x{lay.width}xu?i32>")
        fn = sym.build_orbit_fp(bounds, ("Server",), consts, False)
        text = jax.jit(fn).lower(struct).as_text()
        assert "stablehlo.while" in text
        assert not row.search(text), row.pattern
        packed = jax.jit(jax.vmap(lambda s: st.pack(s, jnp))) \
            .lower(struct).as_text()
        assert re.search(r"stablehlo\.concatenate.*" + row.pattern, packed)
