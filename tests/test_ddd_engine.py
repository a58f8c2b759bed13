"""Delayed-duplicate-detection engine (ddd_engine.py).

The engine exists because the exact device fingerprint table caps
distinct-state capacity at ~2^28 slots (the elect5 campaign measured into
that ceiling — RESULTS.md "capacity findings"); its gates: oracle-exact
parity with blocks/chunks small enough to cycle many times, IDENTICAL
results under forced filter-table eviction (the lossy filter must never
change a verdict or a count), refbfs-exact violation/deadlock stops,
trace replay, and block-boundary checkpoint/resume with exact counters.
"""

import functools
import time

import numpy as np
import pytest

from frontier_cases import frontier_block
from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
from raft_tla_tpu.models import interp, refbfs

# smoke tier: cross-section for mid-round changes (pytest -m smoke)
pytestmark = pytest.mark.smoke

CFG = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                max_log=0, max_msgs=2),
                  spec="election", invariants=("NoTwoLeaders",), chunk=32)
CAPS = DDDCapacities(block=256, table=1 << 14, flush=1 << 10, levels=64)


def test_parity_with_oracle_tiny_blocks_and_flushes():
    ref = refbfs.check(CFG)
    got = DDDEngine(CFG, CAPS).check()
    assert got.n_states == ref.n_states == 3014
    assert got.diameter == ref.diameter == 17
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert got.coverage == ref.coverage      # identical discovery order
    assert got.violation is None and got.complete


def test_parity_under_forced_eviction():
    """A 128-slot filter on a 3014-state space evicts constantly; the
    host dedup must absorb every false-new re-sight — identical counts,
    levels, coverage, discovery order."""
    ref = refbfs.check(CFG)
    caps = DDDCapacities(block=256, table=1 << 7, flush=1 << 9, levels=64)
    got = DDDEngine(CFG, caps).check()
    assert got.n_states == ref.n_states
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert got.coverage == ref.coverage


def test_capacity_past_device_table_scale():
    """The filter table is NOT a state-count ceiling: a space 8x larger
    than the filter completes exactly (the table engines would
    FAIL_PROBE here)."""
    cfg = CheckConfig(bounds=Bounds(n_servers=3, n_values=1, max_term=2,
                                    max_log=0, max_msgs=1),
                      spec="election", invariants=("NoTwoLeaders",),
                      chunk=64)
    caps = DDDCapacities(block=1 << 13, table=1 << 14, flush=1 << 14,
                         levels=64)
    got = DDDEngine(cfg, caps).check()
    assert got.n_states == 142538
    assert got.diameter == 31
    assert got.complete


@pytest.mark.parametrize("prefetch", ["on", "off"])
@pytest.mark.parametrize("host_dedup", ["on", "off"])
def test_violation_trace_replays_and_stops_exactly(host_dedup, prefetch,
                                                   monkeypatch):
    monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", host_dedup)
    monkeypatch.setenv("RAFT_TLA_PREFETCH", prefetch)
    from raft_tla_tpu.models import invariants as inv_mod
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
    ref = refbfs.check(cfg, init_override=start)
    caps = DDDCapacities(block=1 << 12, table=1 << 17, flush=1 << 12,
                         levels=64)
    got = DDDEngine(cfg, caps).check(init_override=start)
    assert got.violation is not None
    assert got.violation.invariant == "NaiveNoTwoLeaders"
    # device-side stream truncation makes the stop refbfs-exact
    assert got.n_states == ref.n_states
    trace = got.violation.trace
    assert trace[0][0] is None and trace[0][1] == start
    for (_l, prev), (_label, cur) in zip(trace, trace[1:]):
        succs = [t for _i, t in interp.successors(prev, bounds,
                                                  spec="election")]
        assert cur in succs
    assert not inv_mod.py_invariant("NaiveNoTwoLeaders")(
        got.violation.state, bounds)


def test_checkpoint_resume_bit_exact(tmp_path):
    ck = str(tmp_path / "ddd.ckpt")
    straight = DDDEngine(CFG, CAPS).check()
    res = DDDEngine(CFG, CAPS).check(checkpoint=ck,
                                     checkpoint_every_s=0.0)
    assert res.n_states == straight.n_states
    resumed = DDDEngine(CFG, CAPS).check(resume=ck)
    assert resumed.n_states == straight.n_states
    assert resumed.levels == straight.levels
    assert resumed.n_transitions == straight.n_transitions
    assert resumed.coverage == straight.coverage
    assert resumed.violation is None

    other = DDDEngine(CFG, DDDCapacities(block=512, table=1 << 14,
                                         flush=1 << 10, levels=64))
    with pytest.raises(ValueError, match="checkpoint"):
        other.check(resume=ck)


def test_symmetry_composes():
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=("NoTwoLeaders",),
                      symmetry=("Server",), chunk=32)
    ref = refbfs.check(cfg)
    got = DDDEngine(cfg, CAPS).check()
    assert got.n_states == ref.n_states == 1514
    assert got.diameter == ref.diameter
    assert got.coverage == ref.coverage


@pytest.mark.parametrize("prefetch", ["on", "off"])
@pytest.mark.parametrize("host_dedup", ["on", "off"])
def test_deadlock_detected(host_dedup, prefetch, monkeypatch):
    monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", host_dedup)
    monkeypatch.setenv("RAFT_TLA_PREFETCH", prefetch)
    cfg = CheckConfig(bounds=Bounds(n_servers=1, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=(), chunk=16,
                      check_deadlock=True)
    ref = refbfs.check(cfg)
    caps = DDDCapacities(block=64, table=1 << 12, flush=1 << 8, levels=64)
    got = DDDEngine(cfg, caps).check()
    assert ref.violation is not None and got.violation is not None
    assert got.violation.invariant == ref.violation.invariant  # DEADLOCK
    assert got.n_states == ref.n_states


def test_faithful_mode_parity():
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=1, max_msgs=2, history=True,
                                    max_elections=4),
                      spec="full",
                      invariants=("NoTwoLeaders", "ElectionSafetyHist",
                                  "AllLogsPrefixClosed"), chunk=512)
    ref = refbfs.check(cfg)
    assert (ref.n_states, ref.diameter) == (53398, 32)
    caps = DDDCapacities(block=1 << 13, table=1 << 18, flush=1 << 15,
                         levels=64)
    got = DDDEngine(cfg, caps).check()
    assert (got.n_states, got.diameter) == (ref.n_states, ref.diameter)
    assert got.levels == ref.levels
    assert got.coverage == ref.coverage
    assert got.violation is None


def test_masterkeys_unit():
    from raft_tla_tpu.utils.keyset import MasterKeys

    m = MasterKeys()
    m.seed(7)
    keys = np.array([9, 3, 9, 7, 3, 11], np.uint64)
    new = m.dedup(keys)
    # first occurrences of 9, 3, 11 (7 already present), stream order
    assert new.tolist() == [0, 1, 5]
    assert len(m) == 4
    assert m.contains(np.array([3, 4, 7, 9, 11], np.uint64)).tolist() == \
        [True, False, True, True, True]
    # second flush: all duplicates
    assert m.dedup(keys).size == 0
    # strictly-new flush merges in order
    assert m.dedup(np.array([2, 1, 2], np.uint64)).tolist() == [0, 1]
    assert m.array.tolist() == [1, 2, 3, 7, 9, 11]


def test_masterkeys_tiers_randomized():
    """LSM tiers must be observationally identical to a flat set: dedup
    indices per flush, contains, len, and the materialized array all
    match a reference dict over many random overlapping flushes."""
    from raft_tla_tpu.utils.keyset import MasterKeys, _RATIO

    rng = np.random.default_rng(20260731)
    m = MasterKeys()
    seen: set[int] = set()
    for _ in range(40):
        flush = rng.integers(0, 5000, size=rng.integers(1, 4000),
                             dtype=np.uint64)
        # reference first-occurrence semantics
        want, batch_seen = [], set()
        for i, k in enumerate(flush.tolist()):
            if k not in seen and k not in batch_seen:
                want.append(i)
                batch_seen.add(k)
        got = m.dedup(flush)
        assert got.tolist() == want
        seen |= batch_seen
        assert len(m) == len(seen)
        # geometric tier invariant: every older run > _RATIO x newer
        runs = m._runs
        assert all(runs[i].size > _RATIO * runs[i + 1].size
                   for i in range(len(runs) - 1))
        # runs stay mutually disjoint and individually sorted
        for r in runs:
            assert np.all(r[1:] > r[:-1])
    probe = np.arange(5000, dtype=np.uint64)
    assert m.contains(probe).tolist() == [k in seen for k in range(5000)]
    assert m.array.tolist() == sorted(seen)
    # tier count stays logarithmic
    assert m.n_runs <= 16


def test_masterkeys_resume_constructor():
    """The checkpoint-resume path hands a single sorted array; behavior
    must match a set grown flush-by-flush."""
    from raft_tla_tpu.utils.keyset import MasterKeys

    base = np.sort(np.unique(
        np.random.default_rng(7).integers(0, 10**6, 5000, dtype=np.uint64)))
    m = MasterKeys(base)
    assert len(m) == base.size and m.n_runs == 1
    flush = np.concatenate([base[:100], base[:100] + np.uint64(10**7)])
    new = m.dedup(flush)
    assert new.tolist() == list(range(100, 200))
    assert len(m) == base.size + 100
    bad = base.copy()
    bad[10] = bad[9]
    import pytest
    with pytest.raises(ValueError):
        MasterKeys(bad)


# -- RAFT_TLA_HOSTDEDUP gate (partitioned + background host dedup) ----------


@pytest.mark.parametrize("host_dedup", ["on", "off"])
def test_host_dedup_oracle_parity_both_arms(host_dedup, monkeypatch):
    """Explicit both-arm parity (the rest of this file runs under the
    auto policy): partitioned master keys + depth-1 background flush
    must not move a single byte of discovery."""
    monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", host_dedup)
    ref = refbfs.check(CFG)
    got = DDDEngine(CFG, CAPS).check()
    assert got.n_states == ref.n_states == 3014
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert got.coverage == ref.coverage
    assert got.violation is None and got.complete


def test_host_dedup_checkpoint_cross_gate(tmp_path, monkeypatch):
    """Checkpoints are gate-agnostic (the master set is rebuilt from the
    key log, and the gate is deliberately not part of the digest):
    written under either arm, resumable under the other, byte-identical
    finals both ways."""
    straight = DDDEngine(CFG, CAPS).check()
    for write, read in (("on", "off"), ("off", "on")):
        ck = str(tmp_path / f"ddd_{write}.ckpt")
        monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", write)
        mid = DDDEngine(CFG, CAPS).check(checkpoint=ck,
                                         checkpoint_every_s=0.0)
        assert mid.n_states == straight.n_states
        monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", read)
        resumed = DDDEngine(CFG, CAPS).check(resume=ck)
        assert resumed.n_states == straight.n_states, (write, read)
        assert resumed.levels == straight.levels
        assert resumed.n_transitions == straight.n_transitions
        assert resumed.coverage == straight.coverage
        assert resumed.violation is None


def test_host_dedup_lossless_deadline_stop_with_pending_flush(
        tmp_path, monkeypatch):
    """The lossless-stop contract under the async flush: a deadline
    lands while sealed batches may be in flight on the background
    worker; the stop path drains the queue before the snapshot, so
    resume completes byte-identical to an uninterrupted run."""
    monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", "on")
    cfg = CheckConfig(bounds=Bounds(n_servers=3, n_values=1, max_term=2,
                                    max_log=0, max_msgs=1),
                      spec="election", invariants=("NoTwoLeaders",),
                      chunk=64)
    caps = DDDCapacities(block=256, table=1 << 14, flush=1 << 9, levels=64)
    straight = DDDEngine(cfg, caps).check()
    ck = str(tmp_path / "dl.ckpt")
    got = DDDEngine(cfg, caps).check(deadline_s=0.5, checkpoint=ck,
                                     checkpoint_every_s=3600.0)
    assert not got.complete
    assert got.n_states < straight.n_states
    resumed = DDDEngine(cfg, caps).check(resume=ck)
    assert resumed.complete
    assert resumed.n_states == straight.n_states
    assert resumed.levels == straight.levels
    assert resumed.n_transitions == straight.n_transitions
    assert resumed.coverage == straight.coverage


# -- the hand-over to the flush worker at a harvest (PR 44) ------------------
#
# ``flush`` stays at its default 2^23, which no toy level reaches: every
# hand-over below is the harvest's own, taken because the level still has
# device work behind it.  The election engine's ``seg_rows`` is one chunk's
# candidates, so a segment runs one chunk and a level of k chunks makes k
# harvests; the two-phase engine's segments run four chunks (``seg_chunks``
# pinned, a buffer of four chunks' candidates), so its levels end in tails
# of one to four chunk steps.


@functools.lru_cache(maxsize=None)
def _handover_case(spec):
    """One compiled engine a spec for every test below, with a planted
    state whose violation lies several levels of several segments away
    (what a reachable state never is: a leader that voted for nobody; an
    aborted RM the TM holds for prepared) and one from which every level
    fits a chunk."""
    from raft_tla_tpu.frontend import resolve_model
    if spec == "election":
        from raft_tla_tpu.models import spec as S
        cfg = dataclasses.replace(CFG, chunk=8)
        init = interp.init_state(cfg.bounds)
        planted = init._replace(
            role=(S.LEADER, S.FOLLOWER), term=(2, 1), vResp=(0b11, 0),
            vGrant=(0b11, 0))
        narrow = init._replace(
            role=(S.LEADER, S.CANDIDATE), term=(2, 2), votedFor=(1, 2),
            vResp=(0b11, 0b10), vGrant=(0b11, 0b10))
        invariant = "NoTwoLeaders"
    else:
        from benchmark.families import twophase_ddd as fam
        from benchmark.reference import twophase as ref
        from test_ddd_twophase import toy_cfg
        n = 4
        cfg = fam.check_config(dict(toy_cfg(n), chunk=8))
        planted = fam.to_program(ref.State(
            (ref.ABORTED,) + (ref.WORKING,) * (n - 1), ref.TM_INIT, 1, 1))
        narrow = fam.to_program(ref.State(
            (ref.PREPARED,) * n, ref.TM_COMMITTED, (1 << n) - 1,
            (1 << n + 1) - 1))
        invariant = "TCConsistent"
    lanes = cfg.chunk * len(resolve_model(cfg.spec).action_table(cfg.bounds))
    per_seg = 1 if spec == "election" else 4
    caps = DDDCapacities(block=256, table=1 << 14, seg_rows=per_seg * lanes,
                         levels=64)
    assert caps.flush == 1 << 23
    eng = DDDEngine(cfg, caps, seg_chunks=per_seg)
    eng.SEG_MAX = max(per_seg, eng.SEG_MIN)  # the pacer keeps the budget
    return eng, planted, narrow, invariant


def _run_arm(eng, arm, monkeypatch, **kw):
    """``check()`` under ``RAFT_TLA_HOSTDEDUP=arm`` on the one compiled
    engine (the gate is read again, as the constructor reads it): the
    result, every byte the stores hold, the batches the worker got and
    when, on the pass ledger's clock."""
    from raft_tla_tpu.utils import flushq, keyset
    from test_upload_prefix import _stores
    monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", arm)
    eng._host_dedup = keyset.host_dedup_enabled()
    handed, handed_at = [], []
    submit = flushq.DedupWorker.submit

    def counted(self, batch, n_keys):
        handed.append(n_keys)
        handed_at.append(time.monotonic())
        submit(self, batch, n_keys)

    with monkeypatch.context() as patch:
        patch.setattr(flushq.DedupWorker, "submit", counted)
        res = eng.check(retain_store=True, **kw)
    return res, _stores(eng), handed, handed_at


def _same_discovery(got, want):
    (res, stored), (ref_res, ref_stored) = got[:2], want[:2]
    assert stored == ref_stored          # rows, trace links, flags, keys
    assert (res.n_states, res.levels, res.n_transitions, res.coverage,
            res.complete) == (ref_res.n_states, ref_res.levels,
                              ref_res.n_transitions, ref_res.coverage,
                              ref_res.complete)
    assert res.violation == ref_res.violation


@pytest.mark.parametrize("spec", ["election", "twophase"])
def test_harvest_handovers_leave_discovery_byte_identical(spec, monkeypatch):
    """Levels of several segments hand their stream to the worker harvest
    by harvest, far below ``flush``; states, trace links, level counts,
    coverage and a planted fault's trace equal the inline arm's."""
    eng, planted, _narrow, invariant = _handover_case(spec)
    on = _run_arm(eng, "on", monkeypatch)
    off = _run_arm(eng, "off", monkeypatch)
    _same_discovery(on, off)
    assert on[0].complete and on[0].violation is None
    assert max(on[0].levels) > eng.caps.block        # levels of two blocks
    assert len(on[2]) > len(on[0].levels) and max(on[2]) < 1 << 10
    assert on[0].level_log["threads"]["dedup@raft-tla-flush"] > 0
    assert off[2] == [] and \
        "dedup@raft-tla-flush" not in off[0].level_log["threads"]
    # the last harvest of a level hands nothing over: what the level close
    # merges inline is never the whole of a level of several segments
    assert sum(on[2]) < on[0].n_transitions

    von = _run_arm(eng, "on", monkeypatch, init_override=planted)
    voff = _run_arm(eng, "off", monkeypatch, init_override=planted)
    _same_discovery(von, voff)
    assert von[0].violation.invariant == invariant
    assert len(von[0].violation.trace) >= 8 and len(von[2]) >= 2


@pytest.mark.parametrize("spec", ["election", "twophase"])
def test_levels_of_one_segment_never_meet_the_worker(spec, monkeypatch):
    """The guard for the cells that must not move: where every level's
    first harvest finishes its block, the worker exists and is handed
    nothing, and the level close merges the stream inline as before."""
    eng, _planted, narrow, _invariant = _handover_case(spec)
    res, _stored, handed, _at = _run_arm(eng, "on", monkeypatch,
                                         init_override=narrow)
    assert res.complete and len(res.levels) >= 3
    assert max(res.levels) <= eng.config.chunk
    assert all(lv["steps"] <= 1 for lv in res.level_log["levels"])
    assert handed == []
    assert "dedup@raft-tla-flush" not in res.level_log["threads"]


def test_a_short_tail_of_the_level_is_not_worth_a_handover(monkeypatch):
    """A hand-over is hidden behind the chunk steps the level has left: with
    under a third of the steps that streamed the batch still to run (a
    segment of four, then a tail of one) nothing is handed over, though the
    level has two segments and the worker is free."""
    eng = _handover_case("twophase")[0]
    res, _stored, _handed, handed_at = _run_arm(eng, "on", monkeypatch)
    seen = set()
    for lv in res.level_log["levels"]:
        n = sum(lv["t0"] <= t < lv["t0"] + lv["wall_s"] for t in handed_at)
        if lv["blocks"] == 1 and lv["steps"] <= 5:
            assert n == 0, lv           # one segment; or four steps and one
            seen.add(min(lv["steps"], 5) // 5)
        elif lv["blocks"] == 1 and lv["steps"] >= 8:
            assert n >= 1, lv
            seen.add(2)
    assert seen == {0, 1, 2}


@pytest.mark.parametrize("spec", ["election", "twophase"])
def test_stop_between_two_handovers_is_lossless(spec, tmp_path, monkeypatch):
    """The SIGINT flag raised right after a hand-over, with more of the
    level to come: the stopped pass has flushed every streamed candidate
    (``n_states`` is the key log's distinct keys) and resumes to the
    uninterrupted run's totals."""
    from raft_tla_tpu.utils import flushq
    eng = _handover_case(spec)[0]
    straight, _stored, handed, _at = _run_arm(eng, "on", monkeypatch)
    stop_after = len(handed) // 2
    submit = flushq.DedupWorker.submit
    seen = []

    def stopping(self, batch, n_keys):
        submit(self, batch, n_keys)
        seen.append(n_keys)
        if len(seen) == stop_after:
            eng._sigint = True

    ck = str(tmp_path / "handover.ckpt")
    with monkeypatch.context() as patch:
        patch.setattr(flushq.DedupWorker, "submit", stopping)
        got = eng.check(checkpoint=ck, checkpoint_every_s=3600.0,
                        retain_store=True)
    host, constore, keystore, n = eng.retained
    keys = keystore.read(0, n).copy()
    for store in (host, constore, keystore):
        store.close()
    assert not got.complete and len(seen) == stop_after
    assert 1 < got.n_states < straight.n_states
    assert got.n_states == n == len(np.unique(keys.view(np.int64)))
    resumed = eng.check(resume=ck)
    assert resumed.complete
    assert (resumed.n_states, resumed.levels, resumed.n_transitions,
            resumed.coverage) == (straight.n_states, straight.levels,
                                  straight.n_transitions, straight.coverage)


def test_deadline_stops_cleanly():
    """A deadline expiry — including one landing between blocks with an
    empty pipeline — returns complete=False instead of crashing, and the
    partial counts stay self-consistent."""
    cfg = CheckConfig(bounds=Bounds(n_servers=3, n_values=1, max_term=2,
                                    max_log=0, max_msgs=1),
                      spec="election", invariants=("NoTwoLeaders",),
                      chunk=64)
    caps = DDDCapacities(block=256, table=1 << 14, flush=1 << 9, levels=64)
    got = DDDEngine(cfg, caps).check(deadline_s=0.5)
    assert not got.complete
    assert 1 <= got.n_states < 142538
    assert got.violation is None


def test_deadline_stopped_pass_reports_live_coverage():
    """The progress stream carries per-action coverage (TLC ``-coverage 1``
    analog) and a deadline stop is lossless: every record that closes a
    level (the host key set has merged all that streamed; inside a level
    the flush worker may be a batch ahead of the count) and the stopped
    pass's result credit each state found but Init to one action."""
    eng = _handover_case("election")[0]     # a segment is one 8-row chunk
    stats: list = []
    part = eng.check(deadline_s=0.25, on_progress=stats.append)
    assert not part.complete and 1 < part.n_states < 3014
    closes = [rec for rec, nxt in zip(stats, stats[1:])
              if nxt["level"] > rec["level"]]
    assert closes and closes[-1]["n_states"] <= part.n_states
    for rec in closes:
        assert sum(rec["coverage"].values()) == rec["n_states"] - 1
    assert "Timeout" in stats[-1]["coverage"]
    assert sum(part.coverage.values()) == part.n_states - 1


# -- RAFT_TLA_PREFETCH gate (double-buffered upload prefetch) ---------------


@pytest.mark.parametrize("retention", ["full", "frontier"])
@pytest.mark.parametrize("prefetch", ["on", "off"])
def test_prefetch_oracle_parity_both_arms(prefetch, retention,
                                          monkeypatch):
    """Explicit both-arm parity in both retention modes: swapping block
    uploads to prefetched, double-buffered staging must not move a
    single byte of discovery (hits and misses read the same rows)."""
    monkeypatch.setenv("RAFT_TLA_PREFETCH", prefetch)
    ref = refbfs.check(CFG)
    caps = DDDCapacities(block=256, table=1 << 14, flush=1 << 10,
                         levels=64, retention=retention)
    got = DDDEngine(CFG, caps).check()
    assert got.n_states == ref.n_states == 3014
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert got.coverage == ref.coverage
    assert got.violation is None and got.complete


def test_prefetch_checkpoint_cross_gate(tmp_path, monkeypatch):
    """Checkpoints are prefetch-agnostic (the gate is deliberately not
    part of the digest): written under either arm, resumable under the
    other, byte-identical finals both ways."""
    straight = DDDEngine(CFG, CAPS).check()
    for write, read in (("on", "off"), ("off", "on")):
        ck = str(tmp_path / f"ddd_pf_{write}.ckpt")
        monkeypatch.setenv("RAFT_TLA_PREFETCH", write)
        mid = DDDEngine(CFG, CAPS).check(checkpoint=ck,
                                         checkpoint_every_s=0.0)
        assert mid.n_states == straight.n_states
        monkeypatch.setenv("RAFT_TLA_PREFETCH", read)
        resumed = DDDEngine(CFG, CAPS).check(resume=ck)
        assert resumed.n_states == straight.n_states, (write, read)
        assert resumed.levels == straight.levels
        assert resumed.n_transitions == straight.n_transitions
        assert resumed.coverage == straight.coverage
        assert resumed.violation is None


def test_prefetch_lossless_deadline_stop_with_prefetch_in_flight(
        tmp_path, monkeypatch):
    """The lossless-stop contract with BOTH background threads live: a
    deadline lands while a flush may be in flight on the dedup worker
    AND a block prefetch may be staged or in flight; the stop path
    invalidates the prefetch and drains the queue before the snapshot,
    so resume completes byte-identical to an uninterrupted run."""
    monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", "on")
    monkeypatch.setenv("RAFT_TLA_PREFETCH", "on")
    cfg = CheckConfig(bounds=Bounds(n_servers=3, n_values=1, max_term=2,
                                    max_log=0, max_msgs=1),
                      spec="election", invariants=("NoTwoLeaders",),
                      chunk=64)
    caps = DDDCapacities(block=256, table=1 << 14, flush=1 << 9, levels=64)
    straight = DDDEngine(cfg, caps).check()
    ck = str(tmp_path / "pf_dl.ckpt")
    got = DDDEngine(cfg, caps).check(deadline_s=0.5, checkpoint=ck,
                                     checkpoint_every_s=3600.0)
    assert not got.complete
    assert got.n_states < straight.n_states
    resumed = DDDEngine(cfg, caps).check(resume=ck)
    assert resumed.complete
    assert resumed.n_states == straight.n_states
    assert resumed.levels == straight.levels
    assert resumed.n_transitions == straight.n_transitions
    assert resumed.coverage == straight.coverage


# -- EP-routed step (DDDCapacities.route_rows; SURVEY §2.9 EP row) ----------

import dataclasses


def _routed(caps, k):
    return dataclasses.replace(caps, route_rows=k)


def _n_lanes(cfg):
    from raft_tla_tpu.models import spec as S
    return cfg.chunk * len(S.action_table(cfg.bounds, cfg.spec))


def test_routed_parity_with_dense():
    """route_rows changes only where per-candidate work runs — counts,
    levels, coverage and discovery order are byte-identical.  K = N/2
    makes the slots genuinely contested (the realistic operating point:
    fewer slots than lanes, no overflow), not just a stable re-ordering
    of the full grid."""
    dense = DDDEngine(CFG, CAPS).check()
    for k in (_n_lanes(CFG), _n_lanes(CFG) // 2):
        got = DDDEngine(CFG, _routed(CAPS, k)).check()
        for f in ("n_states", "diameter", "levels", "n_transitions",
                  "coverage", "complete"):
            assert getattr(got, f) == getattr(dense, f), (k, f)
        assert got.violation is None


def test_routed_violation_truncation_exact():
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
    ref = DDDEngine(cfg, caps).check(init_override=start)
    got = DDDEngine(cfg, _routed(caps, _n_lanes(cfg))) \
        .check(init_override=start)
    assert got.violation is not None
    assert got.violation.invariant == ref.violation.invariant
    assert got.n_states == ref.n_states          # refbfs-exact stop
    assert got.n_transitions == ref.n_transitions
    assert got.violation.trace == ref.violation.trace


def test_routed_deadlock_and_symmetry():
    cfg = CheckConfig(bounds=Bounds(n_servers=1, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=(), chunk=16,
                      check_deadlock=True)
    caps = DDDCapacities(block=64, table=1 << 12, flush=1 << 8, levels=64)
    ref = DDDEngine(cfg, caps).check()
    got = DDDEngine(cfg, _routed(caps, _n_lanes(cfg))).check()
    assert got.violation is not None
    assert got.violation.invariant == ref.violation.invariant
    assert got.n_states == ref.n_states

    sym = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=("NoTwoLeaders",),
                      symmetry=("Server",), chunk=32)
    got = DDDEngine(sym, _routed(CAPS, _n_lanes(sym))).check()
    assert got.n_states == 1514      # refbfs-verified orbit count


def test_routed_faithful_mode():
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2, history=True,
                                    max_elections=4),
                      spec="election",
                      invariants=("NoTwoLeaders", "ElectionSafetyHist"),
                      chunk=64)
    caps = DDDCapacities(block=512, table=1 << 14, flush=1 << 11,
                         levels=64)
    dense = DDDEngine(cfg, caps).check()
    got = DDDEngine(cfg, _routed(caps, _n_lanes(cfg))).check()
    for f in ("n_states", "diameter", "levels", "n_transitions",
              "coverage"):
        assert getattr(got, f) == getattr(dense, f), f


def test_routed_checkpoint_crosses_step_switch(tmp_path):
    """route_rows stays out of the checkpoint digest: a dense snapshot
    resumes on the routed step (and vice versa) with identical results —
    the mid-campaign tuning DDDCapacities promises."""
    straight = DDDEngine(CFG, CAPS).check()
    ck = str(tmp_path / "ddd_route.ckpt")
    DDDEngine(CFG, CAPS).check(checkpoint=ck, checkpoint_every_s=0.0)
    resumed = DDDEngine(CFG, _routed(CAPS, _n_lanes(CFG))) \
        .check(resume=ck)
    assert resumed.n_states == straight.n_states
    assert resumed.n_transitions == straight.n_transitions
    assert resumed.coverage == straight.coverage


def test_routed_budget_overflow_aborts_loudly():
    with pytest.raises(RuntimeError, match="routing budget"):
        DDDEngine(CFG, _routed(CAPS, 8)).check()


def test_routed_violation_never_masked_by_budget():
    """Sweeping route_rows across the seeded-violation universe: every
    budget either aborts loudly (FAIL_ROUTE — candidates before the cut
    may be lost) or reports EXACTLY the dense engine's violation with
    dense-exact counts; a detected invariant violation outranks a
    routing overflow (the dropped lanes provably lie past the cut)."""
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
    ref = DDDEngine(cfg, caps).check(init_override=start)
    n_lanes = _n_lanes(cfg)
    reported = 0
    for k in (n_lanes // 16, n_lanes // 8, n_lanes // 4,
              n_lanes // 2, n_lanes):
        try:
            got = DDDEngine(cfg, _routed(caps, k)) \
                .check(init_override=start)
        except RuntimeError as e:
            assert "routing budget" in str(e)
            continue
        assert got.violation is not None
        assert got.violation.invariant == ref.violation.invariant
        assert got.n_states == ref.n_states
        assert got.n_transitions == ref.n_transitions
        assert got.violation.trace == ref.violation.trace
        reported += 1
    assert reported >= 1          # the sweep must exercise the report path


# -- slab writes of the candidate stream (ddd_engine._S_OUT) ----------------
#
# The segment program writes a chunk's streamed rows as contiguous slabs
# of _S_OUT rows in the filter's compaction order.  The slab size must be
# invisible: every streamed row lands, in order, at any size.

import jax
import jax.numpy as jnp

from raft_tla_tpu import ddd_engine as ddd_mod
from raft_tla_tpu.ops import kernels

_OLD_STATS = ("cursor", "n_valid", "fail", "viol_kind", "viol_inv",
              "dead_g", "steps", "done", "peak")
_FLAVOURS = ("dense", "routed")


def _flavour_caps(flavour, cfg=CFG, **kw):
    caps = dataclasses.replace(CAPS, **kw)
    return _routed(caps, _n_lanes(cfg) // 2) if flavour == "routed" \
        else caps


def _run_segment(eng, vecs, con, budget=1 << 10):
    """One dispatch of the engine's compiled segment over ``vecs`` as one
    block behind an empty filter: host copies of (bufs, stats)."""
    block = eng.caps.block
    rows = np.zeros((block, eng.schema.P), np.int32)
    rows[:len(vecs)] = eng.schema.pack(vecs, np)
    flags = np.zeros((block,), bool)
    flags[:len(vecs)] = con
    _fc, bufs, stats = eng._segment(
        eng._init_filter(), eng._make_bufs(), jnp.asarray(rows),
        jnp.asarray(flags), jnp.int32(budget), jnp.int32(len(vecs)))
    return jax.device_get(bufs), jax.device_get(stats)


def _slab_counts(per_chunk, slab):
    """NumPy replay of the two counters from the rows each chunk
    streamed: the peak, and one slab a chunk plus its overflow slabs."""
    per_chunk = np.asarray(per_chunk)
    return (int(per_chunk.max(initial=0)),
            int(np.maximum(-(-per_chunk // slab), 1).sum()))


@pytest.fixture(scope="module")
def whole_slab_runs():
    """What ``S >= N`` gives (the shipped _S_OUT dwarfs a toy chunk): the
    toy universe's check() and one deep block through the segment, per
    step flavour."""
    vecs, con = frontier_block(CFG, 9, 200)
    out = {}
    for flavour in _FLAVOURS:
        caps = _flavour_caps(flavour)
        assert ddd_mod._S_OUT >= _n_lanes(CFG)
        out[flavour] = (DDDEngine(CFG, caps).check(),
                        _run_segment(DDDEngine(CFG, caps), vecs, con))
    return vecs, con, out


@pytest.mark.parametrize("slab", [8, 7])      # 7 divides neither N nor K
@pytest.mark.parametrize("flavour", _FLAVOURS)
def test_slab_size_never_shows_in_check(flavour, slab, whole_slab_runs,
                                        monkeypatch):
    _vecs, _con, ref = whole_slab_runs
    monkeypatch.setattr(ddd_mod, "_S_OUT", slab)
    got = DDDEngine(CFG, _flavour_caps(flavour)).check()
    want = ref[flavour][0]
    for f in ("n_states", "diameter", "levels", "n_transitions",
              "coverage", "complete", "violation"):
        assert getattr(got, f) == getattr(want, f), f


@pytest.mark.parametrize("slab", [8, 7])
@pytest.mark.parametrize("flavour", _FLAVOURS)
def test_slab_size_never_shows_in_segment(flavour, slab, whole_slab_runs,
                                          monkeypatch):
    """SegBufs[:cursor] and the old SegStats fields are byte-identical to
    the whole-slab run's; the two new counters equal a NumPy replay of
    the stream (rows per chunk, read off the parents it carries)."""
    vecs, con, ref = whole_slab_runs
    want_bufs, want_stats = ref[flavour][1]
    monkeypatch.setattr(ddd_mod, "_S_OUT", slab)
    eng = DDDEngine(CFG, _flavour_caps(flavour))
    nk = eng.caps.route_rows or _n_lanes(CFG)
    assert eng._buf_rows == eng.caps.seg_rows + (-nk % slab)
    bufs, stats = _run_segment(eng, vecs, con)
    for f in _OLD_STATS:
        assert getattr(stats, f) == getattr(want_stats, f), f
    n = int(stats.cursor)
    assert n > 0 and int(stats.steps) == -(-len(vecs) // CFG.chunk)
    for f in bufs._fields:
        got, want = getattr(bufs, f)[:n], getattr(want_bufs, f)[:n]
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes(), f
    per_chunk = np.bincount(bufs.opar[:n] // CFG.chunk,
                            minlength=int(stats.steps))
    assert per_chunk.max() > slab          # the slab loop really ran
    assert (int(stats.stream_peak), int(stats.stream_slabs)) \
        == _slab_counts(per_chunk, slab)
    assert (int(want_stats.stream_peak), int(want_stats.stream_slabs)) \
        == _slab_counts(per_chunk, _n_lanes(CFG)) \
        == (per_chunk.max(), int(stats.steps))


class _PlannedStep:
    """Stand-in for kernels.build_step / build_step_routed: a chunk whose
    first row has term[0] == t enables exactly ``plan[t]`` lanes (a seeded
    choice), with keys, rows and flags that are functions of the lane —
    so the test knows, in NumPy, every row a chunk must stream."""

    def __init__(self, cfg, plan, seed=0):
        from raft_tla_tpu.models import spec as S
        from raft_tla_tpu.ops import state as st
        self.B = cfg.chunk
        self.A = len(S.action_table(cfg.bounds, cfg.spec))
        self.N = self.B * self.A
        self.n_inv = len(cfg.invariants)
        self.term0 = cfg.bounds.n_servers      # flat offset of term[0]
        self.counts = np.zeros((cfg.bounds.max_term + 1,), np.int32)
        for t, n in plan.items():
            self.counts[t] = n
        rng = np.random.default_rng(seed)
        self.rank = rng.permutation(self.N).astype(np.int32)
        tmpl, _con = frontier_block(cfg, 6, 40)
        self.tmpl = tmpl.astype(np.int32)
        assert self.tmpl.shape[1] == st.Layout.of(cfg.bounds).width

    def lanes(self, t):
        """Flat lanes a term-``t`` chunk enables, in stream order."""
        return np.flatnonzero(self.rank < self.counts[t])

    def expect(self, schema, t, r0):
        """The SegBufs rows a term-``t`` chunk at block row ``r0`` must
        stream, field by field."""
        ln = self.lanes(t)
        return {
            "okey_hi": ((np.uint32(t) << np.uint32(20))
                        | ln.astype(np.uint32)),
            "okey_lo": (ln.astype(np.uint64) * 2654435761
                        % (1 << 32)).astype(np.uint32),
            "orows": schema.pack(self.tmpl[ln % len(self.tmpl)], np),
            "opar": (r0 + ln // self.A).astype(np.int32),
            "olane": (ln % self.A).astype(np.int32),
            "ocon": ln % 3 != 0,
        }

    def dense(self, *_a, **_kw):
        B, A, N = self.B, self.A, self.N

        def step(vecs, row_ok=None):
            t = vecs[0, self.term0]
            lane = jnp.arange(N, dtype=jnp.int32)
            valid = jnp.asarray(self.rank) < jnp.asarray(self.counts)[t]
            tm = jnp.asarray(self.tmpl)
            return {
                "valid": valid.reshape(B, A),
                "overflow": jnp.zeros((B, A), bool),
                "fp_hi": ((t.astype(jnp.uint32) << 20)
                          | lane.astype(jnp.uint32)).reshape(B, A),
                "fp_lo": (lane.astype(jnp.uint32)
                          * jnp.uint32(2654435761)).reshape(B, A),
                "inv_ok": jnp.ones((B, A, self.n_inv), bool),
                "con_ok": (lane % 3 != 0).reshape(B, A),
                "svecs": tm[lane % tm.shape[0]].reshape(B, A, -1),
            }
        return step

    def routed(self, *_a, k_rows, **_kw):
        dense, N = self.dense(), self.N

        def step(vecs, row_ok):
            d = dense(vecs)
            live = (d["valid"] & row_ok[:, None]).reshape(-1)
            lane = jnp.arange(N, dtype=jnp.int32)
            cidx = jnp.sort(jnp.where(live, lane, N))[:k_rows]
            g = jnp.minimum(cidx, N - 1)
            n_en = jnp.sum(live.astype(jnp.int32))
            return {
                "valid": d["valid"], "overflow": d["overflow"],
                "cidx": cidx, "cvalid": cidx < N,
                "csvecs": d["svecs"].reshape(N, -1)[g],
                "cfp_hi": d["fp_hi"].reshape(-1)[g],
                "cfp_lo": d["fp_lo"].reshape(-1)[g],
                "cinv_ok": d["inv_ok"].reshape(N, -1)[g],
                "ccon_ok": d["con_ok"].reshape(-1)[g],
                "route_ovf": n_en > k_rows, "n_en": n_en}
        return step

    def install(self, monkeypatch):
        monkeypatch.setattr(kernels, "build_step", self.dense)
        monkeypatch.setattr(kernels, "build_step_routed", self.routed)


def _term_rows(cfg, terms):
    """One chunk of copies of Init per entry of ``terms``, with term[0]
    set to it (what _PlannedStep keys a chunk's count on)."""
    init = interp.init_state(cfg.bounds)
    vecs = [interp.to_vec(init._replace(
        term=(t,) + init.term[1:]), cfg.bounds)
        for t in terms for _ in range(cfg.chunk)]
    return np.stack(vecs), np.ones((len(vecs),), bool)


def _assert_streamed(bufs, lo, want):
    for f, w in want.items():
        got = getattr(bufs, f)[lo:lo + len(w)]
        assert got.dtype == w.dtype and np.array_equal(got, w), f


@pytest.mark.parametrize("n_rows", ["0", "S", "S+1", "NK"])
@pytest.mark.parametrize("flavour", _FLAVOURS)
def test_chunk_streaming_exactly(flavour, n_rows, monkeypatch):
    """One chunk that streams exactly 0, S, S+1 and NK rows: every row
    lands in order, and the counters read the count and its slabs."""
    slab = 8
    caps = _flavour_caps(flavour)
    nk = caps.route_rows or _n_lanes(CFG)
    n = {"0": 0, "S": slab, "S+1": slab + 1, "NK": nk}[n_rows]
    plan = _PlannedStep(CFG, {1: n})
    plan.install(monkeypatch)
    monkeypatch.setattr(ddd_mod, "_S_OUT", slab)
    eng = DDDEngine(CFG, caps)
    bufs, stats = _run_segment(eng, *_term_rows(CFG, [1]))
    assert (int(stats.cursor), int(stats.n_valid), int(stats.steps),
            int(stats.fail), bool(stats.done)) == (n, n, 1, 0, True)
    _assert_streamed(bufs, 0, plan.expect(eng.schema, 1, 0))
    assert (int(stats.stream_peak), int(stats.stream_slabs)) \
        == _slab_counts([n], slab) == (n, max(1, -(-n // slab)))


@pytest.mark.parametrize("flavour", _FLAVOURS)
def test_chunk_entered_at_the_last_admissible_cursor_is_not_clamped(
        flavour, monkeypatch):
    """A chunk may start with ``cursor + NK == seg_rows`` and stream NK
    rows; with a slab size that does not divide NK its last slab then
    reaches past ``seg_rows``.  The buffers' slack rows take that: a
    clamped dynamic_update_slice would shift the slab down over rows
    already streamed."""
    slab, n1 = 7, 5
    nk = _flavour_caps(flavour).route_rows or _n_lanes(CFG)
    assert nk % slab
    caps = _flavour_caps(flavour, seg_rows=n1 + nk)
    plan = _PlannedStep(CFG, {1: n1, 2: nk})
    plan.install(monkeypatch)
    monkeypatch.setattr(ddd_mod, "_S_OUT", slab)
    eng = DDDEngine(CFG, caps)
    assert eng._buf_rows == caps.seg_rows + slab - nk % slab
    bufs, stats = _run_segment(eng, *_term_rows(CFG, [1, 2]))
    assert (int(stats.cursor), int(stats.steps), int(stats.fail),
            bool(stats.done)) == (n1 + nk, 2, 0, True)
    assert len(bufs.okey_hi) == eng._buf_rows
    _assert_streamed(bufs, 0, plan.expect(eng.schema, 1, 0))
    _assert_streamed(bufs, n1, plan.expect(eng.schema, 2, CFG.chunk))
    assert (int(stats.stream_peak), int(stats.stream_slabs)) \
        == _slab_counts([n1, nk], slab)
