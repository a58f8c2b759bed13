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

import raft_tla_tpu.ddd_engine as ddd_mod
from frontier_cases import frontier_block
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

SLAB = 24       # the "slabs" case's _S_OUT: divides no step's receivable rows


def _harvest_caps(case, ndev):
    kw = dict(block=1024 // ndev, table=1 << 14, seg_rows=1 << 14,
              flush=1 << 10, levels=64)
    if case == "whole":
        # H = 64 / 32 rows a shard; a level's stream outgrows it from level
        # 5 on (``send`` lowers the 4-device mesh's floor on seg_rows)
        kw.update(seg_rows=1024) if ndev == 1 else \
            kw.update(seg_rows=512, send=64)
    elif case == "slabs":
        # buffers that hold little more than one step can deliver (352 rows
        # on one device, 4 x 64 on four): ``full`` halts a segment as soon
        # as 48 / 44 rows streamed, so a window takes several
        kw.update(seg_rows=400) if ndev == 1 else \
            kw.update(seg_rows=300, send=64)
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
            if case == "slabs":
                # slabs of 24 rows under steps that can receive 352 (one
                # device) or 256 (four, ``send`` 64): a step streams
                # several, the buffers carry 8 slack rows, and with the
                # "whole" case's small buffers a window takes several
                # segments (the ``full`` halt)
                mp.setattr(ddd_mod, "_S_OUT", SLAB)
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
            "levels": [e["args"] for e in evs if e["event"] == "span"
                       and e["name"] == "level"],
            "dd_hits": max((e.get("dev_dedup_hits") or 0 for e in evs
                            if e["event"] == "segment"), default=0)}
        return made[case, ndev]

    return run


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("case", ["head", "whole", "devdedup", "frontier",
                                  "slabs"])
def test_harvest_equals_single_chip(case, ndev, harvest_runs):
    """(i) every harvest takes the head, (ii) some cursors outgrow it and
    those harvests take the whole buffers, (iii) the device-dedup gate on:
    the head is sliced after the compaction, (iv) frontier retention,
    (v) PR 48: steps that stream several slabs into buffers with slack
    rows, in windows of several segments."""
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
    H, ocap = eng._head_rows, eng._buf_rows
    row_bytes = eng.schema.P * 4 + 17
    paths = {sp["args"]["path"] for sp in got["d2h"]}
    assert paths == ({"head", "whole"} if case in ("whole", "slabs")
                     else {"head"})
    # the level spans' slab counts: one slab a lockstep step unless a
    # shard streamed more than a slab holds in one
    lv = got["levels"]
    assert all(a["stream_slabs"] >= a["steps"] for a in lv)
    assert max(a["stream_peak"] for a in lv) \
        <= max(a["streamed_rows"] for a in lv)
    if case == "slabs":
        assert ocap == eng.caps.seg_rows + 8
        assert max(a["stream_peak"] for a in lv) > SLAB
        assert sum(a["stream_slabs"] for a in lv) \
            > sum(a["steps"] for a in lv)
        # more harvests with rows than windows: ``full`` cut some window
        assert len(got["d2h"]) > sum(a["blocks"] for a in lv)
    else:
        assert ocap == eng.caps.seg_rows
        assert [a["stream_slabs"] for a in lv] == [a["steps"] for a in lv]
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
    for case in ("whole", "devdedup", "slabs"):
        assert harvest_runs(case, 4)["digest"] == base, case


# --------------------------- PR 48: the stream stage writes slabs at the cursor
#
# The mesh step lays its streamed candidates down as the one-chip step does
# (``ddd_engine._write_slabs``): gathered in the filter's compaction order, one
# ``dynamic_update_slice`` a buffer at the shard's cursor.  What may not move
# is ``[0, cursor)`` of the six buffers and every old field of ``MStats``.

def test_last_slab_of_a_full_step_lands_in_the_slack_rows():
    """``_write_slabs`` alone against NumPy: a step that streams every row
    it can receive, from the highest cursor ``full`` lets a step start at,
    writes its last slab into the buffers' slack rows and nothing is
    clamped; rows under the cursor are left as they were."""
    import jax
    import jax.numpy as jnp

    nk, seg_rows, slab = 100, 160, 24            # 100 = 4 slabs + 4 rows
    rng = np.random.default_rng(48)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ddd_mod, "_S_OUT", slab)
        assert ddd_mod._slab_plan(nk) == (slab, 20)
        cols = (rng.integers(0, 1 << 32, nk, dtype=np.uint32),
                rng.integers(0, 1 << 31, (nk, 3)).astype(np.int32),
                rng.random(nk) < 0.5)
        for n_stream, cursor in ((nk, seg_rows - nk), (nk, 0), (25, 7),
                                 (24, 7), (0, 5)):
            compact = rng.permutation(nk).astype(np.int32)
            old = (np.full(seg_rows + 20, 7, np.uint32),
                   np.full((seg_rows + 20, 3), 7, np.int32),
                   np.zeros(seg_rows + 20, bool))

            @jax.jit
            def run(bufs, cursor, n_stream, compact):
                return ddd_mod._write_slabs(
                    bufs, cursor, n_stream, compact, nk,
                    lambda sel: tuple(jnp.asarray(c)[sel] for c in cols))

            bufs, n_slabs, seen = run(old, jnp.int32(cursor),
                                      jnp.int32(n_stream),
                                      jnp.asarray(compact))
            assert int(n_slabs) == max(-(-n_stream // slab), 1)
            assert seen == ()
            for got, was, col in zip(bufs, old, cols):
                got = np.asarray(got)
                np.testing.assert_array_equal(got[:cursor], was[:cursor])
                np.testing.assert_array_equal(
                    got[cursor:cursor + n_stream],
                    col[compact[:n_stream]])


def _mesh_segment(eng, vecs, con):
    """One dispatch of a mesh engine's segment over ``vecs`` as one window
    (dealt block by block, as ``_upload_window`` deals it) behind an empty
    filter: host copies of (bufs, stats)."""
    import jax
    import jax.numpy as jnp

    nd, blk = eng.ndev, eng.caps.block
    rows = np.zeros((nd * blk, eng.schema.P), np.int32)
    rows[:len(vecs)] = eng.schema.pack(vecs, np)
    flags = np.zeros((nd * blk,), bool)
    flags[:len(vecs)] = con
    nrows = np.clip(len(vecs) - np.arange(nd) * blk, 0, blk).astype(np.int32)
    sh = eng._in_shardings
    _fc, bufs, stats = eng._segment(
        eng._init_filter(), eng._make_bufs(),
        jax.device_put(rows, sh[0]), jax.device_put(flags, sh[1]),
        jax.device_put(np.arange(nd * blk, dtype=np.int32), sh[2]),
        jax.device_put(nrows, sh[3]), jnp.int32(1 << 10),
        jnp.int32(-(-int(nrows.max()) // eng.config.chunk)))
    return jax.device_get(bufs), jax.device_get(stats)


_MESH_OLD_STATS = ("cursor", "n_valid", "fail", "viol_pos", "viol_inv",
                   "dead_g", "steps", "done")


@pytest.mark.parametrize("ndev", [1, 4])
def test_segment_streams_equal_whatever_the_slab(ndev, monkeypatch):
    """One segment over a deep frontier block, slab by slab: every shard's
    ``[0, cursor)`` of the six buffers and the old ``MStats`` fields are
    those of the whole-slab program, which on one device are the one-chip
    engine's ``SegBufs`` / ``SegStats`` byte for byte; the two new counters
    equal a NumPy replay of the rows each step streamed."""
    import jax
    import jax.numpy as jnp

    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine

    vecs, con = frontier_block(CFG, 9, 200)
    caps = DDDShardCapacities(block=256 // ndev, table=1 << 14,
                              seg_rows=1 << 13, flush=1 << 10, levels=64)
    want_bufs, want = _mesh_segment(
        DDDShardEngine(CFG, make_mesh(ndev), caps), vecs, con)
    monkeypatch.setattr(ddd_mod, "_S_OUT", 7)     # 352 = 50 slabs + 2 rows
    eng = DDDShardEngine(CFG, make_mesh(ndev), caps)
    nr = 32 * 11 * ndev
    assert eng._buf_rows == caps.seg_rows + (-nr % 7) > caps.seg_rows
    bufs, stats = _mesh_segment(eng, vecs, con)
    for f in _MESH_OLD_STATS:
        np.testing.assert_array_equal(getattr(stats, f), getattr(want, f), f)
    assert int(stats.steps) == -(-min(len(vecs), caps.block) // CFG.chunk)
    assert np.asarray(stats.cursor).sum() > 0
    for s in range(ndev):
        n = int(stats.cursor[s])
        for f in bufs._fields:
            got = getattr(bufs, f)[s * eng._buf_rows:][:n]
            ref = getattr(want_bufs, f)[s * caps.seg_rows:][:n]
            assert got.dtype == ref.dtype and got.tobytes() == ref.tobytes()
        # rows a step streamed to this shard, read off the window-relative
        # parents it carries (shard q expands rows [q*block, ...) in steps
        # of ``chunk``)
        par = bufs.opar[s * eng._buf_rows:][:n]
        per_step = np.bincount((par % caps.block) // CFG.chunk,
                               minlength=int(stats.steps))
        assert int(stats.stream_peak[s]) == per_step.max(initial=0)
        assert int(stats.stream_slabs[s]) \
            == int(np.maximum(-(-per_step // 7), 1).sum())
        assert int(want.stream_peak[s]) == per_step.max(initial=0)
        assert int(want.stream_slabs[s]) == int(stats.steps)
    assert int(np.max(stats.stream_slabs)) > int(stats.steps)
    if ndev > 1:
        return
    # the 1-device mesh against the one-chip engine's own segment
    one = DDDEngine(CFG, DDDCapacities(block=256, table=1 << 14,
                                       seg_rows=1 << 13, flush=1 << 10,
                                       levels=64))
    rows = np.zeros((256, one.schema.P), np.int32)
    rows[:len(vecs)] = one.schema.pack(vecs, np)
    flags = np.zeros((256,), bool)
    flags[:len(vecs)] = con
    _fc, obufs, ostats = jax.device_get(one._segment(
        one._init_filter(), one._make_bufs(), jnp.asarray(rows),
        jnp.asarray(flags), jnp.int32(1 << 10), jnp.int32(len(vecs))))
    n = int(ostats.cursor)
    assert n == int(stats.cursor[0])
    for f in ("n_valid", "fail", "steps", "stream_peak", "stream_slabs"):
        assert int(np.asarray(getattr(ostats, f))) \
            == int(np.asarray(getattr(stats, f)).reshape(-1)[0]), f
    for f in bufs._fields:
        got, ref = getattr(bufs, f)[:n], getattr(obufs, f)[:n]
        assert got.dtype == ref.dtype and got.tobytes() == ref.tobytes(), f


@pytest.mark.parametrize("ndev", [4, 8])
def test_violation_in_a_later_slab(ndev, monkeypatch):
    """The first violating streamed candidate is found in the slab that
    holds it: with slabs of three rows it lies in the second slab or later
    of its step (one step a segment, so ``viol_pos`` is the offset into
    that step's stream), ``viol_pos`` is the same buffer slot as with one
    slab a step, and the same trace replays."""
    import jax

    def run(slab):
        if slab:
            monkeypatch.setattr(ddd_mod, "_S_OUT", slab)
        eng = DDDShardEngine(VIOL_CFG, make_mesh(ndev), VIOL_CAPS,
                             seg_chunks=1)
        eng.SEG_MIN = eng.SEG_MAX = 1           # a segment is one step
        hits, seg = [], eng._segment

        def recording(*a):
            out = seg(*a)
            st = jax.device_get(out[2])
            if (np.asarray(st.viol_pos) >= 0).any():
                hits.append((np.asarray(st.viol_pos).tolist(),
                             np.asarray(st.viol_inv).tolist(),
                             np.asarray(st.cursor).tolist(),
                             np.asarray(st.stream_slabs).tolist()))
            return out

        eng._segment = recording
        return eng.check(init_override=VIOL_START), hits, eng

    whole, whole_hits, _ = run(None)
    got, hits, eng = run(3)
    # a step can deliver ndev x 64 x 20 rows: 5,120 / 10,240, not whole slabs
    assert eng._buf_rows == VIOL_CAPS.seg_rows + (-(ndev * 1280) % 3) \
        > VIOL_CAPS.seg_rows
    assert_replayable_violation(got)
    assert got.violation.trace == whole.violation.trace
    assert (got.n_states, got.levels, got.n_transitions) \
        == (whole.n_states, whole.levels, whole.n_transitions)
    assert len(hits) == 1
    vpos, vinv, cursors, slabs = hits[0]
    assert (vpos, vinv, cursors) == whole_hits[0][:3]
    shard = int(np.argmax(np.asarray(vpos) >= 0))
    assert 3 <= vpos[shard] < cursors[shard]
    assert slabs[shard] == -(-cursors[shard] // 3) >= 2
    assert whole_hits[0][3] == [1] * ndev


def test_cp_mode_streams_slabs(tmp_path, monkeypatch):
    """CP mode (every shard expands the same rows over its lane slice) runs
    the same ``stream`` stage: oracle-exact totals with slabs of five rows
    under steps that receive up to 4 x 32 x ``cp_lane_count``, and the same
    run as with one slab a step."""
    import json

    monkeypatch.setenv("RAFT_TLA_TRACE", "1")
    caps = DDDShardCapacities(block=256, table=1 << 12, seg_rows=1 << 12,
                              flush=1 << 10, levels=64, cp=True)

    def run(name):
        log = str(tmp_path / name)
        got = DDDShardEngine(CFG, make_mesh(4), caps).check(events=log)
        with open(log) as f:
            evs = [json.loads(line) for line in f]
        return got, [e["args"] for e in evs
                     if e["event"] == "span" and e["name"] == "level"]

    ref = refbfs.check(CFG)
    whole, whole_lv = run("whole.events")
    monkeypatch.setattr(ddd_mod, "_S_OUT", 5)
    got, lv = run("slabs.events")
    assert_totals(got, ref)
    assert got.coverage == whole.coverage
    assert [a["stream_slabs"] for a in whole_lv] \
        == [a["steps"] for a in whole_lv]
    assert sum(a["stream_slabs"] for a in lv) > sum(a["steps"] for a in lv)
    for key in ("steps", "streamed_rows", "new_states", "stream_peak"):
        assert [a[key] for a in lv] == [a[key] for a in whole_lv], key


def _scatters(jaxpr, stack=""):
    """``(scope path, updates shape)`` of every scatter equation of a jaxpr,
    sub-jaxprs included (an inner equation's name stack is relative to the
    equation that holds it)."""
    import jax

    out = []
    for eqn in jaxpr.eqns:
        path = stack + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name.startswith("scatter"):
            out.append((path, eqn.invars[2].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_scatters(sub, path))
    return out


def test_mesh_segment_scatters_nothing_in_stream(monkeypatch):
    """The traced mesh segment: no scatter under the ``stream`` scope (PR
    47's program had six over every received lane), none under ``exchange``
    (PR 48's had six over every lane of the chunk: the send blocks are
    gathered slabs now), and none under ``filter_insert`` wider than the
    insert budget (where the walk is seen to read scopes)."""
    import jax

    monkeypatch.setattr(ddd_mod, "_S_INS", 16)
    eng = DDDShardEngine(CFG, make_mesh(4), CAPS)
    sh = eng._in_shardings
    nd, blk = eng.ndev, eng.caps.block
    S = jax.ShapeDtypeStruct
    i32 = S((), np.int32)
    closed = jax.make_jaxpr(eng._segment)(
        jax.eval_shape(eng._init_filter), jax.eval_shape(eng._make_bufs),
        S((nd * blk, eng.schema.P), np.int32, sharding=sh[0]),
        S((nd * blk,), np.bool_, sharding=sh[1]),
        S((nd * blk,), np.int32, sharding=sh[2]),
        S((nd,), np.int32, sharding=sh[3]), i32, i32)
    found = _scatters(closed.jaxpr)
    scoped = [(p.split("/"), shape) for p, shape in found]
    assert not [p for p, _ in scoped if "stream" in p or "exchange" in p]
    in_filter = [shape for p, shape in scoped if "filter_insert" in p]
    assert in_filter and all(shape[0] <= 16 for shape in in_filter)


def test_level_spans_count_what_the_exchange_packed(tmp_path, monkeypatch):
    """PR 49: a 4-device run's ``level`` spans carry ``route_peak`` (the
    most live lanes one shard's exchange packed in a step) and
    ``exchange_slabs`` (the trips of its gather loop, the most of any shard
    a segment, summed), and both are what a plain count of each window's
    destinations gives: a lane is addressed to an owner exactly when the
    interpreter finds its action enabled on a row the constraint admits."""
    import json

    import jax

    from raft_tla_tpu.ops import state as st

    monkeypatch.setenv("RAFT_TLA_TRACE", "1")
    monkeypatch.setattr(ddd_mod, "_S_OUT", 5)     # 352 lanes: 70 slabs + 2
    log = str(tmp_path / "run.events")
    eng = DDDShardEngine(CFG, make_mesh(4), _harvest_caps("head", 4))
    B, blk, seg = CFG.chunk, eng.caps.block, eng._segment
    enabled = {}                                  # packed row -> live lanes

    def live_lanes(row):
        if row.tobytes() not in enabled:
            s = interp.from_struct(
                st.unpack(eng.schema.unpack(row[None], np)[0], eng.lay, np),
                CFG.bounds)
            enabled[row.tobytes()] = sum(
                1 for _ in interp.successors(s, CFG.bounds, spec=CFG.spec))
        return enabled[row.tobytes()]

    counted = []                # a segment: (route_peak, exchange_slabs)

    def recording(fc, bufs, fbuf, fcon, fpar, nrows, budget, n_chunks):
        c0 = int(fc.c)                            # read before the donation
        rows, con, nr = (np.asarray(jax.device_get(a))
                         for a in (fbuf, fcon, nrows))
        out = seg(fc, bufs, fbuf, fcon, fpar, nrows, budget, n_chunks)
        stats = jax.device_get(out[2])
        peak, trips = [], []
        for q in range(4):
            live = [sum(live_lanes(rows[q * blk + r])
                        for r in range(k * B, min((k + 1) * B, int(nr[q])))
                        if con[q * blk + r])
                    for k in range(c0, c0 + int(stats.steps))]
            peak.append(max(live, default=0))
            trips.append(sum(max(-(-n // 5), 1) for n in live))
        assert np.asarray(stats.route_peak).tolist() == peak
        assert np.asarray(stats.exchange_slabs).tolist() == trips
        counted.append((max(peak), max(trips)))
        return out

    eng._segment = recording
    res = eng.check(events=log)
    assert (res.n_states, res.n_transitions) == (3014, 5274)
    with open(log) as f:
        levels = [e["args"] for e in map(json.loads, f)
                  if e["event"] == "span" and e["name"] == "level"]
    assert sum(a["segments"] for a in levels) == len(counted)
    for a in levels:
        mine, counted = counted[:a["segments"]], counted[a["segments"]:]
        assert a["route_peak"] == max((p for p, _ in mine), default=0)
        assert a["exchange_slabs"] == sum(t for _, t in mine)
    # the run packed more than a slab in a step, and less in others
    assert max(a["route_peak"] for a in levels) > 5
    assert sum(a["exchange_slabs"] for a in levels) \
        > sum(a["steps"] for a in levels) > 0


# What the parent of PR 49 (2e397bc: the exchange's six scatters) wrote for
# the same 4-device runs — ``_snapshot_digest`` there: the level table and
# every stream's (bytes, sha256).  ``.keys`` is the discovery order itself.
_PARENT_KEYS = (
    24128, "ffa92ec19d39cbdf503617cb9d7728a489743f88adf213a0c4f4b0d6ac2bd0e7")
_PARENT_STREAMS = {
    "frontier": {
        ".keys": _PARENT_KEYS,
        ".conL18": (112, "73a098f974bf66e7968a8fc0f40adc94"
                         "ea6b729a89ca0cc963e81585cfbac9d9"),
        ".conL19": (16, "9d34149fbd1fe777eb238799054c8cbf"
                        "bce372255f219f8740838def9bfd02db"),
        ".rowsL18": (592, "9c64fbf88e8a36ac6bb7f4f5228d45c0"
                          "d01326fc5d15a01ae58b605dc778bb07"),
        ".rowsL19": (16, "931c13477d08fd5ad0741bd095218457"
                         "ea8aaf60f175328f6532bcb49c1a49cd")},
    "head": {
        ".keys": _PARENT_KEYS,
        ".con": (12072, "752a33784be92d0fd61e9eac2800bfc1"
                        "744230f299433669e6b1f562cb4b4229"),
        ".links": (36184, "1ddcc382cb67448d1af4145fe2d38c28"
                          "bbf2cf13873fcfaa95624f4fa61c88a2"),
        ".rows": (72352, "932851b8f04e8e4e5bc6953289729bbd"
                         "d475b7a7f9f6988203bb898098abd87c")}}
_PARENT_LEVELS = [1, 2, 7, 20, 44, 88, 140, 156, 220, 384, 306, 294, 472, 340,
                  194, 210, 112, 24]


@pytest.mark.parametrize("case", ["frontier", "head"])
def test_four_device_streams_are_the_parents(case, harvest_runs):
    """The exchange delivers what it delivered, so a 4-device run discovers
    what it discovered in the order it did: the level counts and every
    checkpoint stream — frontier retention's level files and the full
    retention's rows, links and constraint flags — are byte for byte what
    the parent's tree wrote (the cases of one tree are held to each other
    above; this holds them across the change of the exchange)."""
    got = harvest_runs(case, 4)
    assert list(got["result"].levels) == _PARENT_LEVELS
    digest = dict(got["digest"])
    npz = digest.pop("npz")
    assert (npz["n_states"], npz["n_trans"]) == (3014, 5274)
    assert npz["level_ends"] == np.cumsum(_PARENT_LEVELS).tolist()
    assert {k: tuple(v) for k, v in digest.items()} == _PARENT_STREAMS[case]


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
