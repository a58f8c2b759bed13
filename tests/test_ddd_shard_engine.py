"""Mesh-sharded DDD engine (parallel/ddd_shard_engine.py).

The scale architecture's multi-chip composition: host-exact dedup
partitioned over the mesh's fingerprint-owner map, canonical
(level, window, shard) discovery order.  Gates: oracle-exact totals on
the 8-device virtual CPU mesh, ndev-invariance, IDENTITY with the
single-chip DDD engine on a 1-device mesh (order and checkpoint
included), parity under forced filter eviction, valid replayable
violation/deadlock counterexamples, window-boundary checkpoint/resume,
and checkpoint resharding across mesh sizes (including adopting a
single-chip campaign checkpoint onto a mesh).
"""

import dataclasses

import numpy as np
import pytest

# needs the virtual multi-device mesh — the slowest compiles on
# this 1-core host, excluded from the time-boxed tier-1 window
# (-m 'not slow'); the shard family stays exercised via -m smoke.  The
# harvest's tests at the end of the file (PR 45) are not marked: they are
# what tier-1 runs of this engine's d2h.
slow = pytest.mark.slow

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.models import interp, refbfs, spec as S
from raft_tla_tpu.ops import msgbits as mb
from raft_tla_tpu.parallel.ddd_shard_engine import (
    DDDShardCapacities, DDDShardEngine, reshard_ddd_checkpoint)
from raft_tla_tpu.parallel.mesh import make_mesh, make_slice_mesh

CFG = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                max_log=0, max_msgs=2),
                  spec="election", invariants=("NoTwoLeaders",), chunk=32)
CAPS = DDDShardCapacities(block=256, table=1 << 14, seg_rows=1 << 14,
                          flush=1 << 10, levels=64)


def assert_totals(got, ref):
    assert got.n_states == ref.n_states
    assert got.diameter == ref.diameter
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert sum(got.coverage.values()) == sum(ref.coverage.values())


@slow
@pytest.mark.parametrize("host_dedup", ["on", "off"])
def test_election_2server_parity_8dev(host_dedup, monkeypatch):
    monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", host_dedup)
    ref = refbfs.check(CFG)
    got = DDDShardEngine(CFG, make_mesh(8), CAPS).check()
    assert_totals(got, ref)
    assert got.n_states == 3014 and got.diameter == 17
    assert got.violation is None


@slow
def test_host_dedup_checkpoint_cross_gate_4dev(tmp_path, monkeypatch):
    """Per-shard partitioned masters rebuild from the same gate-agnostic
    key log: a snapshot written under either arm resumes under the
    other, byte-identical, with the canonical (level, window, shard)
    order untouched."""
    mesh = make_mesh(4)
    straight = DDDShardEngine(CFG, mesh, CAPS).check()
    for write, read in (("on", "off"), ("off", "on")):
        ck = str(tmp_path / f"shard_{write}.ckpt")
        monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", write)
        DDDShardEngine(CFG, mesh, CAPS).check(checkpoint=ck,
                                              checkpoint_every_s=0.0)
        monkeypatch.setenv("RAFT_TLA_HOSTDEDUP", read)
        resumed = DDDShardEngine(CFG, mesh, CAPS).check(resume=ck)
        assert_totals(resumed, straight)
        assert resumed.coverage == straight.coverage
        assert resumed.violation is None


@slow
def test_single_dev_mesh_equals_single_chip():
    """ndev=1: canonical order degenerates to the single-chip DDD
    engine's stream order — coverage attribution (order-dependent)
    must match refbfs exactly, not just in total."""
    ref = refbfs.check(CFG)
    got = DDDShardEngine(CFG, make_mesh(1), CAPS).check()
    assert_totals(got, ref)
    assert got.coverage == ref.coverage


@slow
def test_ndev_invariance():
    runs = {n: DDDShardEngine(CFG, make_mesh(n), CAPS).check()
            for n in (1, 2, 8)}
    base = runs[1]
    for n, r in runs.items():
        assert r.n_states == base.n_states, n
        assert r.levels == base.levels, n
        assert r.n_transitions == base.n_transitions, n


@slow
def test_multi_segment_windows_8dev():
    """Windows needing several device dispatches (tiny segment budget +
    near-full output buffers) must work: the first continuation call
    passes a committed-sharding chunk cursor, which retraces the pjit —
    a build-time-closure leak crashed exactly here (review regression).
    seg_rows is just past the one-chunk receivable bound, so buffer-full
    halts fire too."""
    import math

    ref = refbfs.check(CFG)
    nr = 8 * CFG.chunk * 11          # ndev * chunk * A upper bound
    caps = DDDShardCapacities(block=256, table=1 << 14,
                              seg_rows=1 << max(12, math.ceil(
                                  math.log2(nr + 1))),
                              flush=1 << 10, levels=64)
    eng = DDDShardEngine(CFG, make_mesh(8), caps, seg_chunks=4)
    got = eng.check()
    assert_totals(got, ref)


@slow
def test_parity_under_forced_eviction_8dev():
    """A 128-slot per-shard filter evicts constantly on a 3014-state
    space; the sharded host dedup must absorb every re-sight."""
    ref = refbfs.check(CFG)
    caps = DDDShardCapacities(block=256, table=1 << 7, seg_rows=1 << 14,
                              flush=1 << 9, levels=64)
    got = DDDShardEngine(CFG, make_mesh(8), caps).check()
    assert_totals(got, ref)


@slow
def test_slice_mesh_2x4_parity():
    ref = refbfs.check(CFG)
    got = DDDShardEngine(CFG, make_slice_mesh(2, 4), CAPS).check()
    assert_totals(got, ref)


@slow
def test_symmetry_composes_8dev():
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=("NoTwoLeaders",),
                      symmetry=("Server",), chunk=32)
    ref = refbfs.check(cfg)
    got = DDDShardEngine(cfg, make_mesh(8), CAPS).check()
    assert_totals(got, ref)
    assert got.n_states == 1514


VIOL_BOUNDS = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                     max_msgs=4, max_dup=1)
VIOL_CFG = CheckConfig(bounds=VIOL_BOUNDS, spec="election",
                       invariants=("NaiveNoTwoLeaders",), chunk=64)
VIOL_START = interp.init_state(VIOL_BOUNDS)._replace(
    role=(S.LEADER, S.FOLLOWER, S.CANDIDATE),
    term=(2, 3, 3),
    votedFor=(1, 3, 0),
    vGrant=(0b011, 0, 0b100),
    msgs=tuple(sorted((m, 1) for m in
                      (mb.rv_response(3, 1, 1, 2),))),
)
VIOL_CAPS = DDDShardCapacities(block=1 << 12, table=1 << 14,
                               seg_rows=1 << 15, flush=1 << 12, levels=64)


def assert_replayable_violation(got):
    from raft_tla_tpu.models import invariants as inv_mod

    assert got.violation is not None
    assert got.violation.invariant == "NaiveNoTwoLeaders"
    trace = got.violation.trace
    assert trace[0][0] is None and trace[0][1] == VIOL_START
    for (_l, prev), (_label, cur) in zip(trace, trace[1:]):
        succs = [t for _i, t in interp.successors(prev, VIOL_BOUNDS,
                                                  spec="election")]
        assert cur in succs
    assert not inv_mod.py_invariant("NaiveNoTwoLeaders")(
        got.violation.state, VIOL_BOUNDS)


@slow
def test_violation_trace_replayable_8dev():
    """Seeded NaiveNoTwoLeaders violation: the counterexample may be a
    different one than refbfs's (chunk-granular relaxed stop, as
    shard_engine), but must start at Init, follow real transitions, and
    violate the same invariant."""
    got = DDDShardEngine(VIOL_CFG, make_mesh(8), VIOL_CAPS).check(
        init_override=VIOL_START)
    assert_replayable_violation(got)


@slow
def test_deadlock_detected_8dev():
    cfg = CheckConfig(bounds=Bounds(n_servers=1, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=(), chunk=16,
                      check_deadlock=True)
    ref = refbfs.check(cfg)
    caps = DDDShardCapacities(block=64, table=1 << 7, seg_rows=1 << 12,
                              flush=1 << 8, levels=64)
    got = DDDShardEngine(cfg, make_mesh(8), caps).check()
    assert ref.violation is not None and got.violation is not None
    assert got.violation.invariant == ref.violation.invariant  # DEADLOCK
    # the dead state must genuinely have no successors
    dead = got.violation.state
    assert not list(interp.successors(dead, cfg.bounds, spec="election"))


@slow
def test_routing_overflow_is_loud():
    caps = DDDShardCapacities(block=256, table=1 << 14, seg_rows=1 << 14,
                              flush=1 << 10, levels=64, send=1)
    with pytest.raises(RuntimeError, match="routing budget"):
        DDDShardEngine(CFG, make_mesh(8), caps).check()


@slow
def test_checkpoint_resume_exact_8dev(tmp_path):
    ck = str(tmp_path / "dddsh.ckpt")
    mesh = make_mesh(8)
    straight = DDDShardEngine(CFG, mesh, CAPS).check()
    res = DDDShardEngine(CFG, mesh, CAPS).check(checkpoint=ck,
                                                checkpoint_every_s=0.0)
    assert res.n_states == straight.n_states
    resumed = DDDShardEngine(CFG, mesh, CAPS).check(resume=ck)
    assert resumed.n_states == straight.n_states
    assert resumed.levels == straight.levels
    assert resumed.n_transitions == straight.n_transitions
    assert resumed.coverage == res.coverage   # identical canonical order
    assert resumed.violation is None

    # a different mesh size must refuse the snapshot (owner map changed)
    with pytest.raises(ValueError, match="digest|different model"):
        DDDShardEngine(CFG, make_mesh(4), CAPS).check(resume=ck)


@slow
def test_reshard_across_mesh_sizes(tmp_path):
    """8 -> 2 devices with equal global window size (block scaled 4x):
    every window boundary is shared, the streams move verbatim, and the
    resumed run completes with oracle-exact totals."""
    ck8 = str(tmp_path / "m8.ckpt")
    ck2 = str(tmp_path / "m2.ckpt")
    DDDShardEngine(CFG, make_mesh(8), CAPS).check(
        checkpoint=ck8, checkpoint_every_s=0.0)
    caps2 = DDDShardCapacities(block=1024, table=1 << 14,
                               seg_rows=1 << 14, flush=1 << 10, levels=64)
    info = reshard_ddd_checkpoint(CFG, CAPS, ck8, ck2, ndev_src=8,
                                  ndev_dst=2, caps_dst=caps2)
    assert info["ndev_dst"] == 2
    ref = refbfs.check(CFG)
    got = DDDShardEngine(CFG, make_mesh(2), caps2).check(resume=ck2)
    assert_totals(got, ref)


@slow
def test_adopt_single_chip_checkpoint(tmp_path):
    """A single-chip DDD campaign checkpoint migrates onto the mesh:
    ndev_src=1 with the single-chip block inside caps_src (the stream
    formats are identical by design)."""
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine

    ck1 = str(tmp_path / "chip.ckpt")
    ckm = str(tmp_path / "mesh.ckpt")
    sc_caps = DDDCapacities(block=1024, table=1 << 14, flush=1 << 10,
                            levels=64)
    DDDEngine(CFG, sc_caps).check(checkpoint=ck1, checkpoint_every_s=0.0)
    caps_src = DDDShardCapacities(block=1024, table=1 << 14,
                                  seg_rows=1 << 14, flush=1 << 10,
                                  levels=64)
    caps_dst = DDDShardCapacities(block=256, table=1 << 14,
                                  seg_rows=1 << 14, flush=1 << 10,
                                  levels=64)
    reshard_ddd_checkpoint(CFG, caps_src, ck1, ckm, ndev_src=1,
                           ndev_dst=4, caps_dst=caps_dst)
    ref = refbfs.check(CFG)
    got = DDDShardEngine(CFG, make_mesh(4), caps_dst).check(resume=ckm)
    assert_totals(got, ref)


@slow
def test_cp_mode_parity_8dev():
    """CP mode (lane-sliced expansion over a replicated window) must
    explore the identical state graph: oracle-exact totals on an
    m4-heavy config where the bag lanes dominate the fan-out — the
    regime SURVEY §2.9's CP row targets."""
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=4, max_dup=2),
                      spec="election", invariants=("NoTwoLeaders",),
                      chunk=32)
    ref = refbfs.check(cfg)
    caps = DDDShardCapacities(block=256, table=1 << 12, seg_rows=1 << 15,
                              flush=1 << 10, levels=64, cp=True)
    got = DDDShardEngine(cfg, make_mesh(8), caps).check()
    assert_totals(got, ref)
    # every lane family still gets credited (lane ids are table-dense)
    assert got.coverage.keys() == ref.coverage.keys()


@slow
def test_cp_mode_deadlock_and_violation():
    """The cross-shard enabled-lane psum must not miss deadlocks, and
    violations carry valid traces (dense lane labels)."""
    from raft_tla_tpu.models import invariants as inv_mod

    dl = CheckConfig(bounds=Bounds(n_servers=1, n_values=1, max_term=2,
                                   max_log=0, max_msgs=2),
                     spec="election", invariants=(), chunk=16,
                     check_deadlock=True)
    caps = DDDShardCapacities(block=64, table=1 << 7, seg_rows=1 << 12,
                              flush=1 << 8, levels=64, cp=True)
    ref = refbfs.check(dl)
    got = DDDShardEngine(dl, make_mesh(8), caps).check()
    assert got.violation is not None
    assert got.violation.invariant == ref.violation.invariant
    assert not list(interp.successors(got.violation.state, dl.bounds,
                                      spec="election"))

    bounds = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                    max_msgs=4, max_dup=1)
    vcfg = CheckConfig(bounds=bounds, spec="election",
                       invariants=("NaiveNoTwoLeaders",), chunk=64)
    start = interp.init_state(bounds)._replace(
        role=(S.LEADER, S.FOLLOWER, S.CANDIDATE),
        term=(2, 3, 3), votedFor=(1, 3, 0),
        vGrant=(0b011, 0, 0b100),
        msgs=tuple(sorted((m, 1) for m in
                          (mb.rv_response(3, 1, 1, 2),))))
    caps_v = DDDShardCapacities(block=1 << 12, table=1 << 14,
                                seg_rows=1 << 16, flush=1 << 12,
                                levels=64, cp=True)
    gv = DDDShardEngine(vcfg, make_mesh(8), caps_v).check(
        init_override=start)
    assert gv.violation is not None
    assert gv.violation.invariant == "NaiveNoTwoLeaders"
    trace = gv.violation.trace
    for (_l, prev), (_label, cur) in zip(trace, trace[1:]):
        succs = [t for _i, t in interp.successors(prev, bounds,
                                                  spec="election")]
        assert cur in succs
    assert not inv_mod.py_invariant("NaiveNoTwoLeaders")(
        gv.violation.state, bounds)


@slow
def test_cp_mode_checkpoint_resume(tmp_path):
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=4, max_dup=2),
                      spec="election", invariants=("NoTwoLeaders",),
                      chunk=32)
    caps = DDDShardCapacities(block=256, table=1 << 12, seg_rows=1 << 15,
                              flush=1 << 10, levels=64, cp=True)
    ck = str(tmp_path / "cp.ckpt")
    mesh = make_mesh(8)
    straight = DDDShardEngine(cfg, mesh, caps).check()
    DDDShardEngine(cfg, mesh, caps).check(checkpoint=ck,
                                          checkpoint_every_s=0.0)
    resumed = DDDShardEngine(cfg, mesh, caps).check(resume=ck)
    assert resumed.n_states == straight.n_states
    assert resumed.levels == straight.levels
    # a dense-mode engine must refuse a CP snapshot (order differs)
    dense = dataclasses.replace(caps, cp=False)
    with pytest.raises(ValueError, match="digest|different model"):
        DDDShardEngine(cfg, mesh, dense).check(resume=ck)


@slow
def test_full_spec_small_parity_8dev():
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=1, max_msgs=2),
                      spec="full",
                      invariants=("NoTwoLeaders", "LogMatching",
                                  "CommittedWithinLog"),
                      chunk=128)
    ref = refbfs.check(cfg)
    caps = DDDShardCapacities(block=1 << 12, table=1 << 14,
                              seg_rows=1 << 15, flush=1 << 12, levels=64)
    got = DDDShardEngine(cfg, make_mesh(8), caps).check()
    assert_totals(got, ref)
    for fam in (S.RESTART, S.DUPLICATE, S.DROP):
        assert got.coverage[fam] > 0


@slow
def test_sigint_window_boundary_stop_and_resume(tmp_path):
    """ROADMAP item 8 leftover, chaos-tested in-process: the graceful
    SIGINT contract now reaches the ddd-shard child.  The flag is
    tripped mid-run (exactly what the installed handler does on the
    first Ctrl-C); the engine must stop at the next WINDOW boundary —
    the only point where the canonical shard-major stream is whole —
    snapshot there, return complete=False with no phantom violation,
    and the resumed run must land byte-identical to the uninterrupted
    one (states, levels, transitions, diameter, coverage)."""
    caps = DDDShardCapacities(block=32, table=1 << 14, seg_rows=1 << 14,
                              flush=1 << 10, levels=64)
    mesh = make_mesh(8)
    straight = DDDShardEngine(CFG, mesh, caps).check()
    ck = str(tmp_path / "sig.ck")
    eng = DDDShardEngine(CFG, mesh, caps)
    fired = {}

    def chaos(snap):
        if snap["n_states"] > 300 and not fired:
            fired["at"] = snap["n_states"]
            eng._sigint = True        # what the first SIGINT sets

    partial = eng.check(on_progress=chaos, checkpoint=ck,
                        checkpoint_every_s=1e9)
    assert fired, "chaos hook never fired — model too small"
    assert partial.complete is False
    assert partial.violation is None
    assert partial.n_states < straight.n_states
    resumed = DDDShardEngine(CFG, mesh, caps).check(resume=ck)
    assert resumed.complete is True
    assert_totals(resumed, straight)
    assert resumed.coverage == straight.coverage


# -- the harvest's d2h: the head of each shard's buffers (PR 45) -------------
#
# A harvest fetches the first ``head_rows(caps)`` rows of each shard's six
# output arrays, sliced by a program queued between segment k and k+1, and
# the whole ``seg_rows`` buffers only when a cursor outgrew the head.  The
# same rows have to reach the host in the same order either way: every case
# is held to the one-chip ``ddd`` engine on the same spec, the 1-device mesh
# down to the bytes of its checkpoint.

def _harvest_caps(case, ndev):
    kw = dict(block=1024 // ndev, table=1 << 14, seg_rows=1 << 14,
              flush=1 << 10, levels=64)
    if case == "whole":
        # H = 64 / 32 rows a shard; a level's stream outgrows it from level
        # 5 on (``send`` lowers the 4-device mesh's floor on seg_rows)
        kw.update(seg_rows=1024) if ndev == 1 else \
            kw.update(seg_rows=512, send=64)
    elif case == "devdedup":
        kw.update(table=1 << 7)      # the lossy filter leaks: the set drops
    elif case == "frontier":
        kw.update(retention="frontier")
    return DDDShardCapacities(**kw)


def _snapshot_digest(path):
    """A checkpoint as comparable values: the npz's fields and every
    stream file's size and hash."""
    import glob
    import hashlib
    import os

    out = {}
    for f in sorted(glob.glob(path + "*")):
        suffix = f[len(path):]
        if suffix in ("", ".npz"):
            with np.load(f) as z:
                out["npz"] = {k: np.asarray(z[k]).tolist() for k in z.files}
        else:
            with open(f, "rb") as fh:
                out[suffix] = (os.path.getsize(f),
                               hashlib.sha256(fh.read()).hexdigest())
    return out


@pytest.fixture(scope="module")
def harvest_runs(tmp_path_factory):
    """``run(case, ndev)``: one traced ``check()`` with a checkpoint, made
    once a module; ``ndev`` 0 is the one-chip ``ddd`` engine."""
    import json

    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine

    made = {}

    def run(case, ndev):
        if (case, ndev) in made:
            return made[case, ndev]
        tmp = tmp_path_factory.mktemp(f"harvest_{case}_{ndev}")
        ck, log = str(tmp / "run.ck"), str(tmp / "run.events")
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("RAFT_TLA_TRACE", "1")
            mp.setenv("RAFT_TLA_DEVDEDUP",
                      "hash" if case == "devdedup" and ndev else "off")
            if ndev:
                eng = DDDShardEngine(CFG, make_mesh(ndev),
                                     _harvest_caps(case, ndev))
            else:
                eng = DDDEngine(CFG, DDDCapacities(
                    block=1024, table=1 << 14, flush=1 << 10, levels=64,
                    retention="frontier" if case == "frontier" else "full"))
            res = eng.check(checkpoint=ck, checkpoint_every_s=0.0,
                            events=log)
        with open(log) as f:
            evs = [json.loads(line) for line in f]
        made[case, ndev] = {
            "result": res, "engine": eng, "digest": _snapshot_digest(ck),
            "d2h": [e for e in evs if e["event"] == "span"
                    and e["name"] == "d2h"],
            "dd_hits": max((e.get("dev_dedup_hits") or 0 for e in evs
                            if e["event"] == "segment"), default=0)}
        return made[case, ndev]

    return run


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("case", ["head", "whole", "devdedup", "frontier"])
def test_harvest_equals_single_chip(case, ndev, harvest_runs):
    """(i) every harvest takes the head, (ii) some cursors outgrow it and
    those harvests take the whole buffers, (iii) the device-dedup gate on:
    the head is sliced after the compaction, (iv) frontier retention."""
    ref = harvest_runs("frontier" if case == "frontier" else "head", 0)
    got = harvest_runs(case, ndev)
    assert got["result"].n_states == ref["result"].n_states == 3014
    assert got["result"].levels == ref["result"].levels
    assert got["result"].n_transitions == ref["result"].n_transitions
    assert got["result"].violation is None and got["result"].complete
    if ndev == 1:
        # the 1-device mesh IS the one-chip engine: digest, counters and
        # every stream, byte for byte
        assert got["digest"] == ref["digest"]
    # the case took the paths it is there for, and said what crossed
    eng = got["engine"]
    H, ocap = eng._head_rows, eng.caps.seg_rows
    row_bytes = eng.schema.P * 4 + 17
    paths = {sp["args"]["path"] for sp in got["d2h"]}
    assert paths == ({"head", "whole"} if case == "whole" else {"head"})
    for sp in got["d2h"]:
        rows = H if sp["args"]["path"] == "head" else ocap
        assert sp["args"]["bytes"] == ndev * rows * row_bytes
    if case == "devdedup":
        assert got["dd_hits"] > 0


def test_harvest_path_leaves_the_streams_alone(harvest_runs):
    """On the 4-device mesh no other engine has the same order, so the
    cases are held to each other: head, whole buffers and the compacted
    head give the same checkpoint, byte for byte."""
    base = harvest_runs("head", 4)["digest"]
    assert base["npz"]["n_states"] == 3014
    for case in ("whole", "devdedup"):
        assert harvest_runs(case, 4)["digest"] == base, case


def test_ledger_d2h_bytes_is_the_heads(harvest_runs):
    """The pass ledger sums what the harvests fetched: a one-step level
    moved ``ndev * H`` rows, not the buffers' ``ndev * seg_rows``."""
    got = harvest_runs("head", 4)
    eng = got["engine"]
    row_bytes = eng.schema.P * 4 + 17
    assert eng._head_rows == 1024 and eng.caps.seg_rows == 1 << 14
    levels = got["result"].level_log["levels"]
    first = levels[0]
    assert (first["level"], first["rows"], first["steps"]) == (1, 1, 1)
    assert first["d2h_bytes"] == 4 * 1024 * row_bytes
    assert sum(lv["d2h_bytes"] for lv in levels) == \
        sum(sp["args"]["bytes"] for sp in got["d2h"])
    assert levels[-1]["streamed_rows"] == 0 and levels[-1]["d2h_bytes"] == 0


def test_violation_in_a_head_segment_8dev(tmp_path, monkeypatch):
    """The violator's key is read out of the fetched head at the head's
    stride: the same replayable trace as with the whole buffers fetched
    (``head_rows`` = 1 sends every harvest of two rows down that path)."""
    import json

    from raft_tla_tpu.parallel import ddd_shard_engine as mod

    monkeypatch.setenv("RAFT_TLA_TRACE", "1")

    def run(name):
        log = str(tmp_path / name)
        got = DDDShardEngine(VIOL_CFG, make_mesh(8), VIOL_CAPS).check(
            init_override=VIOL_START, events=log)
        with open(log) as f:
            evs = [json.loads(line) for line in f]
        return got, [e["args"]["path"] for e in evs
                     if e["event"] == "span" and e["name"] == "d2h"]

    head, head_paths = run("head.events")
    monkeypatch.setattr(mod, "head_rows", lambda caps: 1)
    whole, whole_paths = run("whole.events")
    assert set(head_paths) == {"head"} and head_paths
    assert whole_paths[-1] == "whole"
    assert_replayable_violation(head)
    assert head.violation.trace == whole.violation.trace
    assert head.violation.state == whole.violation.state
    assert (head.n_states, head.levels) == (whole.n_states, whole.levels)
