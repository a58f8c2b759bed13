"""Device-resident within-level fingerprint dedup (ops/devdedup.py).

The two-tier dedup's hot tier (ROADMAP item 5): an HBM-resident exact
set applied to segment output buffers before export, so within-level
duplicates never cross d2h.  Gates: hash-vs-sort backend equivalence
under adversarial streams (all-duplicate, all-unique, overflow-forcing
load factors), on/off BYTE-IDENTITY of discovery on the toy universe in
both retention modes (single-chip and the 4-device virtual mesh),
violation/deadlock trace identity, checkpoint resume across the gate in
both directions, and composition with the host-dedup and prefetch
gates.  The soundness invariant everywhere: a dropped lane is always an
exact duplicate of an earlier-streamed key — every lossy path (probe
overflow, capacity truncation, sentinel) widens the stream instead.
"""

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
from raft_tla_tpu.models import interp, refbfs
from raft_tla_tpu.ops import devdedup

# smoke tier: cross-section for mid-round changes (pytest -m smoke)
pytestmark = pytest.mark.smoke

CFG = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                max_log=0, max_msgs=2),
                  spec="election", invariants=("NoTwoLeaders",), chunk=32)
CAPS = DDDCapacities(block=256, table=1 << 14, flush=1 << 10, levels=64)


# -- backend unit gates -----------------------------------------------------

def _feed(backend, capacity, batches, oc=None):
    """Run a key-batch sequence through one backend; per-batch numpy
    (keep, idx, new_n, hits) plus the final set size."""
    import jax

    # jit like the engines do (_dedup_insert's probe loop is a
    # while_loop — it needs the traced path, not eager numpy)
    filt = jax.jit(devdedup.make_filter(backend))
    oc = oc or max(len(hi) for hi, _lo in batches)
    st = devdedup.init_set(capacity, backend)
    out = []
    for hi, lo in batches:
        n = len(hi)
        ph = np.zeros(oc, np.uint32)
        pl = np.zeros(oc, np.uint32)
        ph[:n], pl[:n] = hi, lo
        st, keep, idx, new_n, hits = filt(st, ph, pl, np.int32(n))
        out.append((np.asarray(keep), np.asarray(idx), int(new_n),
                    int(hits)))
    return out, st


def _batches(hi_lists):
    return [(np.asarray(h, np.uint32), np.asarray(h, np.uint32) ^ 0xABC)
            for h in hi_lists]


@pytest.mark.parametrize("stream", [
    [[1, 2, 3, 4, 5, 6, 7, 8]],                       # all unique
    [[9, 9, 9, 9, 9, 9, 9, 9]],                       # all duplicate
    [[1, 2, 1, 3, 2, 4, 1, 5]],                       # within-batch mix
    [[1, 2, 3, 4], [3, 4, 5, 6], [1, 6, 7, 7]],      # cross-batch mix
])
def test_backends_equivalent(stream):
    """With ample capacity the hash and sort backends make IDENTICAL
    keep decisions (the sort arm is the hash arm's parity oracle):
    exactly the first occurrence of each key this level survives, in
    stream order, and hits count the rest."""
    batches = _batches(stream)
    hout, _ = _feed("hash", 1 << 10, batches)
    sout, _ = _feed("sort", 1 << 10, batches)
    seen: set = set()
    for (hk, hi_, hn, hh), (sk, si, sn, sh), (bh, _bl) in zip(
            hout, sout, batches):
        n = len(bh)
        assert np.array_equal(hk[:n], sk[:n])
        assert (hn, hh) == (sn, sh)
        # oracle: keep iff first occurrence across the whole level
        expect = []
        for k in bh.tolist():
            expect.append(k not in seen)
            seen.add(k)
        assert hk[:n].tolist() == expect
        # compaction preserves stream order of the kept lanes
        kept_lanes = [i for i, e in enumerate(expect) if e]
        assert hi_[:hn].tolist() == kept_lanes
        assert si[:sn].tolist() == kept_lanes
        assert hn + hh == n                  # every lane accounted for


@pytest.mark.parametrize("backend", ["hash", "sort"])
def test_sentinel_always_streams(backend):
    """A genuine all-ones fingerprint aliases the empty-slot/padding
    key: it must stream every time (never dedup'd, never inserted) in
    BOTH backends — widening, not wrong answers."""
    s = 0xFFFFFFFF
    hi = np.asarray([s, 1, s, 1], np.uint32)
    lo = np.asarray([s, 1, s, 1], np.uint32)
    out, _ = _feed(backend, 1 << 6, [(hi, lo), (hi, lo)])
    # lane 3 is the only resolvable duplicate in batch 0; batch 1 keeps
    # only the sentinels (1 is now set-resident)
    assert out[0][0][:4].tolist() == [True, True, True, False]
    assert out[1][0][:4].tolist() == [True, False, True, False]


def test_hash_overflow_widens_not_drops():
    """Load factor > 1: a 32-slot table fed 64 unique keys must stream
    every unresolved lane (keep it) rather than drop it — and on a
    replay of the same keys, every DROPPED lane must be a key that
    streamed before (soundness), with kept + hits == n always."""
    keys = np.arange(1, 65, dtype=np.uint32)
    out, _ = _feed("hash", 32, _batches([keys.tolist(), keys.tolist()]))
    (k0, _i0, n0, h0), (k1, _i1, n1, h1) = out
    assert n0 == 64 and h0 == 0              # first sight: all stream
    assert n1 + h1 == 64                     # replay: all accounted
    assert h1 > 0                            # the table did hold SOME
    # soundness: a dropped lane in the replay is a key kept in pass 0
    dropped = keys[~k1[:64]]
    streamed_before = set(keys[k0[:64]].tolist())
    assert all(int(k) in streamed_before for k in dropped.tolist())


def test_sort_capacity_truncation_restreams():
    """Sort-set overflow keeps the smallest keys; overflowed keys simply
    re-stream on replay (hits bounded by capacity, never a drop of a
    first occurrence)."""
    keys = np.arange(1, 17, dtype=np.uint32)
    out, st = _feed("sort", 8, _batches([keys.tolist(), keys.tolist()]))
    (k0, _i0, n0, h0), (k1, _i1, n1, h1) = out
    assert n0 == 16 and h0 == 0              # first sight: all stream
    assert int(st.n) == 8                    # set clamped at capacity
    assert h1 == 8 and n1 == 8               # smallest 8 dedup'd
    # the dropped (dedup'd) keys are exactly the retained smallest 8
    assert sorted(keys[~k1[:16]].tolist()) == keys[:8].tolist()


# -- engine byte-identity ---------------------------------------------------

# Engine-level gates ride the slow tier (~17s of DDD toy run per cell —
# the 870s tier-1 box can't afford them every run); tier-1 keeps the
# pure-filter unit gates above, and tools/lint.sh smokes CLI-level
# on/off byte-identity on every lint.
@pytest.mark.slow
@pytest.mark.parametrize("backend,retention", [
    ("hash", "full"),
    ("hash", "frontier"),
    ("sort", "full"),
    ("sort", "frontier"),
])
def test_oracle_parity_both_backends_both_retentions(backend, retention,
                                                     monkeypatch):
    """The gate must not move a single byte of discovery: counts,
    levels, transition totals, and discovery-order coverage all match
    the oracle (and hence the gate-off run) in both retention modes."""
    monkeypatch.setenv("RAFT_TLA_DEVDEDUP", backend)
    ref = refbfs.check(CFG)
    caps = DDDCapacities(block=256, table=1 << 14, flush=1 << 10,
                         levels=64, retention=retention)
    got = DDDEngine(CFG, caps).check()
    assert got.n_states == ref.n_states == 3014
    assert got.diameter == ref.diameter == 17
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert got.coverage == ref.coverage      # identical discovery order
    assert got.violation is None and got.complete


@pytest.mark.slow
def test_parity_under_forced_filter_eviction(monkeypatch):
    """Device dedup composes with the lossy filter's eviction churn: a
    128-slot filter re-sights constantly; the exact set drops only true
    within-level re-sights and the host absorbs the rest.  (slow: the
    churn multiplies segments ~8x over the other toy runs)"""
    monkeypatch.setenv("RAFT_TLA_DEVDEDUP", "hash")
    ref = refbfs.check(CFG)
    caps = DDDCapacities(block=256, table=1 << 7, flush=1 << 9, levels=64)
    got = DDDEngine(CFG, caps).check()
    assert got.n_states == ref.n_states
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert got.coverage == ref.coverage


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["hash", "sort"])
def test_violation_trace_identity(backend, monkeypatch):
    """The counterexample is part of the byte-identity contract: same
    violating state, same invariant, same replayable trace, same
    truncation-exact n_states with the gate on."""
    from raft_tla_tpu.models import spec as S
    from raft_tla_tpu.ops import msgbits as mb

    bounds = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                    max_msgs=4, max_dup=1)
    cfg = CheckConfig(bounds=bounds, spec="election",
                      invariants=("NaiveNoTwoLeaders",), chunk=64)
    start = interp.init_state(bounds)._replace(
        role=(S.LEADER, S.FOLLOWER, S.CANDIDATE),
        term=(2, 3, 3),
        votedFor=(1, 3, 0),
        vGrant=(0b011, 0, 0b100),
        msgs=tuple(sorted((m, 1) for m in
                          (mb.rv_response(3, 1, 1, 2),))),
    )
    caps = DDDCapacities(block=1 << 12, table=1 << 17, flush=1 << 12,
                         levels=64)
    monkeypatch.setenv("RAFT_TLA_DEVDEDUP", "off")
    off = DDDEngine(cfg, caps).check(init_override=start)
    monkeypatch.setenv("RAFT_TLA_DEVDEDUP", backend)
    on = DDDEngine(cfg, caps).check(init_override=start)
    assert off.violation is not None and on.violation is not None
    assert on.violation.invariant == off.violation.invariant
    assert on.violation.state == off.violation.state
    assert on.violation.trace == off.violation.trace
    assert on.n_states == off.n_states


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["hash", "sort"])
def test_deadlock_identity(backend, monkeypatch):
    cfg = CheckConfig(bounds=Bounds(n_servers=1, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=(), chunk=16,
                      check_deadlock=True)
    caps = DDDCapacities(block=64, table=1 << 12, flush=1 << 8, levels=64)
    monkeypatch.setenv("RAFT_TLA_DEVDEDUP", "off")
    off = DDDEngine(cfg, caps).check()
    monkeypatch.setenv("RAFT_TLA_DEVDEDUP", backend)
    on = DDDEngine(cfg, caps).check()
    assert off.violation is not None and on.violation is not None
    assert on.violation.invariant == off.violation.invariant  # DEADLOCK
    assert on.violation.state == off.violation.state
    assert on.n_states == off.n_states


@pytest.mark.slow
def test_checkpoint_cross_gate(tmp_path, monkeypatch):
    """Checkpoints are gate-agnostic (the set is within-level and
    deliberately not part of the digest): written under either arm,
    resumable under the other, byte-identical finals both ways."""
    straight = DDDEngine(CFG, CAPS).check()
    for write, read in (("hash", "off"), ("off", "hash")):
        ck = str(tmp_path / f"ddd_dd_{write}_{read}.ckpt")
        monkeypatch.setenv("RAFT_TLA_DEVDEDUP", write)
        mid = DDDEngine(CFG, CAPS).check(checkpoint=ck,
                                         checkpoint_every_s=0.0)
        assert mid.n_states == straight.n_states
        monkeypatch.setenv("RAFT_TLA_DEVDEDUP", read)
        resumed = DDDEngine(CFG, CAPS).check(resume=ck)
        assert resumed.n_states == straight.n_states, (write, read)
        assert resumed.levels == straight.levels
        assert resumed.n_transitions == straight.n_transitions
        assert resumed.coverage == straight.coverage
        assert resumed.violation is None


@pytest.mark.slow
def test_composes_with_hostdedup_and_prefetch(monkeypatch):
    """All three gates at once — background host dedup, upload prefetch,
    device dedup — must still be byte-identical to the oracle."""
    monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", "on")
    monkeypatch.setenv("RAFT_TLA_PREFETCH", "on")
    monkeypatch.setenv("RAFT_TLA_DEVDEDUP", "hash")
    ref = refbfs.check(CFG)
    got = DDDEngine(CFG, CAPS).check()
    assert got.n_states == ref.n_states == 3014
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert got.coverage == ref.coverage
    assert got.violation is None and got.complete


@pytest.mark.slow
def test_observability_accounting(monkeypatch):
    """The schema-v9 counters close the books: with the gate on,
    export_rows + dev_dedup_hits equals the gate-off export_rows (every
    dropped row is a counted hit, nothing else moved)."""
    def run(mode):
        monkeypatch.setenv("RAFT_TLA_DEVDEDUP", mode)
        stats: list = []
        DDDEngine(CFG, CAPS).check(on_progress=stats.append)
        return stats

    off = run("off")
    on = run("hash")
    assert off and on and len(off) == len(on)
    assert [s["n_states"] for s in off] == [s["n_states"] for s in on]
    assert all("dev_dedup_hits" not in s for s in off)
    assert off[-1]["export_rows"] == (on[-1]["export_rows"]
                                      + on[-1]["dev_dedup_hits"])
    assert on[-1]["dev_dedup_hits"] > 0      # the toy HAS re-sights


# -- 4-device virtual mesh --------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("backend", ["hash", "sort"])
def test_mesh_4dev_parity(backend, monkeypatch):
    """Per-shard sets under shard_map: totals, violation-free finals and
    coverage sums identical to the oracle on the 4-device virtual mesh,
    canonical (level, window, shard) drain order untouched."""
    from raft_tla_tpu.parallel.ddd_shard_engine import (
        DDDShardCapacities, DDDShardEngine)
    from raft_tla_tpu.parallel.mesh import make_mesh

    caps = DDDShardCapacities(block=256, table=1 << 14, seg_rows=1 << 14,
                              flush=1 << 10, levels=64)
    ref = refbfs.check(CFG)
    monkeypatch.setenv("RAFT_TLA_DEVDEDUP", "off")
    off = DDDShardEngine(CFG, make_mesh(4), caps).check()
    monkeypatch.setenv("RAFT_TLA_DEVDEDUP", backend)
    got = DDDShardEngine(CFG, make_mesh(4), caps).check()
    for r in (off, got):
        assert r.n_states == ref.n_states == 3014
        assert r.diameter == ref.diameter == 17
        assert r.levels == ref.levels
        assert r.n_transitions == ref.n_transitions
    assert got.coverage == off.coverage
    assert got.violation is None and got.complete


@pytest.mark.slow
def test_mesh_4dev_violation_identity(monkeypatch):
    """Shard-engine counterexample identity: the violator survives the
    per-shard filter (an equal earlier candidate would have violated
    first) and the remapped viol_pos still points at it."""
    from raft_tla_tpu.models import spec as S
    from raft_tla_tpu.ops import msgbits as mb
    from raft_tla_tpu.parallel.ddd_shard_engine import (
        DDDShardCapacities, DDDShardEngine)
    from raft_tla_tpu.parallel.mesh import make_mesh

    bounds = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                    max_msgs=4, max_dup=1)
    cfg = CheckConfig(bounds=bounds, spec="election",
                      invariants=("NaiveNoTwoLeaders",), chunk=64)
    start = interp.init_state(bounds)._replace(
        role=(S.LEADER, S.FOLLOWER, S.CANDIDATE),
        term=(2, 3, 3),
        votedFor=(1, 3, 0),
        vGrant=(0b011, 0, 0b100),
        msgs=tuple(sorted((m, 1) for m in
                          (mb.rv_response(3, 1, 1, 2),))),
    )
    caps = DDDShardCapacities(block=1 << 12, table=1 << 17,
                              seg_rows=1 << 14, flush=1 << 12, levels=64)
    monkeypatch.setenv("RAFT_TLA_DEVDEDUP", "off")
    off = DDDShardEngine(cfg, make_mesh(4), caps).check(
        init_override=start)
    monkeypatch.setenv("RAFT_TLA_DEVDEDUP", "hash")
    on = DDDShardEngine(cfg, make_mesh(4), caps).check(
        init_override=start)
    assert off.violation is not None and on.violation is not None
    assert on.violation.invariant == off.violation.invariant
    assert on.violation.state == off.violation.state
    assert on.violation.trace == off.violation.trace
    assert on.n_states == off.n_states
