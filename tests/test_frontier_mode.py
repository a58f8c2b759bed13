"""Frontier-retention DDD mode (the TLC-regime campaign mode).

Retention changes WHERE rows live (disk level files, no trace links),
never WHAT is discovered: counts, levels, coverage and verdicts must
be identical to full retention, checkpoints must resume in place, and
a full-format snapshot must migrate on first frontier resume.
"""

import glob
import os

import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
from raft_tla_tpu.models import refbfs

ELECTION = CheckConfig(
    bounds=Bounds(n_servers=2, n_values=1, max_term=2, max_log=0,
                  max_msgs=2),
    spec="election", invariants=("NoTwoLeaders",), chunk=256)

FULL = CheckConfig(
    bounds=Bounds(n_servers=2, n_values=2, max_term=2, max_log=1,
                  max_msgs=2, max_dup=1),
    spec="full",
    invariants=("NoTwoLeaders", "LogMatching", "CommittedWithinLog"),
    chunk=256)


def _caps(**kw):
    base = dict(block=1 << 12, table=1 << 10, seg_rows=1 << 15,
                flush=1 << 12, levels=64, retention="frontier")
    base.update(kw)
    return DDDCapacities(**base)


def assert_totals(got, ref):
    assert got.n_states == ref.n_states
    assert got.diameter == ref.diameter
    assert got.n_transitions == ref.n_transitions
    assert got.levels == ref.levels
    assert got.coverage == ref.coverage


def test_frontier_parity_election():
    ref = refbfs.check(ELECTION)
    got = DDDEngine(ELECTION, _caps()).check()
    assert_totals(got, ref)
    assert got.violation is None


def test_frontier_parity_full_spec():
    ref = refbfs.check(FULL)
    got = DDDEngine(FULL, _caps()).check()
    assert_totals(got, ref)


def test_frontier_violation_reports_state_without_trace():
    # 3 servers: a deposed leader coexists with a new-term leader (at 2
    # servers quorum forces the step-down first, Naive is unreachable)
    cfg = CheckConfig(
        bounds=Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                      max_msgs=1),
        spec="election", invariants=("NaiveNoTwoLeaders",), chunk=256)
    ref = refbfs.check(cfg)
    assert ref.violation is not None
    got = DDDEngine(cfg, _caps()).check()
    assert got.violation is not None
    assert got.violation.invariant == "NaiveNoTwoLeaders"
    # the same violating state the full-retention engine stops at;
    # only the path is absent (TLC -noTrace equivalence)
    full = DDDEngine(cfg, _caps(retention="full")).check()
    assert got.violation.state == full.violation.state
    assert len(got.violation.trace) == 1
    assert got.n_states == full.n_states


def test_frontier_deadlock():
    cfg = CheckConfig(
        bounds=Bounds(n_servers=1, n_values=1, max_term=2, max_log=0,
                      max_msgs=2),
        spec="election", invariants=(), check_deadlock=True, chunk=64)
    ref = refbfs.check(cfg)
    got = DDDEngine(cfg, _caps(block=1 << 8)).check()
    assert got.violation is not None
    assert got.violation.invariant == ref.violation.invariant
    assert got.n_states == ref.n_states


def test_frontier_checkpoint_resume(tmp_path):
    ck = str(tmp_path / "f.ckpt")
    ref = refbfs.check(FULL)
    eng = DDDEngine(FULL, _caps())
    part = eng.check(checkpoint=ck, checkpoint_every_s=0.0,
                     deadline_s=1.0)
    assert not part.complete
    assert part.n_states < ref.n_states
    assert os.path.exists(ck)       # at least one boundary snapshot
    got = DDDEngine(FULL, _caps()).check(resume=ck, checkpoint=ck,
                                         checkpoint_every_s=0.0)
    assert_totals(got, ref)
    # pre-frontier level files were cleaned at snapshots
    idxs = sorted(int(p.rsplit("L", 1)[1])
                  for p in glob.glob(ck + ".rowsL*"))
    assert len(idxs) <= 3


def test_full_snapshot_migrates_to_frontier(tmp_path):
    """A full-format checkpoint (the elect5 campaign's situation)
    resumes under retention='frontier': the retained window slices out
    of the old streams, the dead prefix and .links are removed."""
    ck = str(tmp_path / "m.ckpt")
    ref = refbfs.check(FULL)
    full_caps = _caps(retention="full")
    part = DDDEngine(FULL, full_caps).check(
        checkpoint=ck, checkpoint_every_s=0.0, deadline_s=1.0)
    assert not part.complete
    assert os.path.exists(ck + ".rows") and os.path.exists(ck + ".links")
    got = DDDEngine(FULL, _caps()).check(resume=ck, checkpoint=ck,
                                         checkpoint_every_s=0.0)
    assert_totals(got, ref)
    assert not os.path.exists(ck + ".rows")       # migrated + removed
    assert not os.path.exists(ck + ".links")


def test_frontier_rejects_retain_store():
    with pytest.raises(ValueError, match="retain_store"):
        DDDEngine(ELECTION, _caps()).check(retain_store=True)


def test_filestore_torn_append_discarded(tmp_path):
    """Rows appended after the last sync() are discarded on reopen —
    the crash contract snapshots rely on."""
    from raft_tla_tpu.utils import native

    p = str(tmp_path / "s.stream")
    fs = native.FileStore(p, 3, base=5, reset=True)
    fs.append([[1, 2, 3], [4, 5, 6]])
    fs.sync()                        # commits rows 5..6
    fs.append([[7, 8, 9]])           # torn: never synced
    fs._f.flush()                    # bytes on disk, header not updated
    fs.close()

    fs2 = native.FileStore(p, 3, base=5)
    assert len(fs2) == 7             # base 5 + 2 committed rows
    assert fs2.read(5, 2).tolist() == [[1, 2, 3], [4, 5, 6]]
    # appends continue exactly at the committed point
    fs2.append([[9, 9, 9]])
    fs2.sync()
    assert fs2.read(7, 1).tolist() == [[9, 9, 9]]
    fs2.close()


def test_levelstore_rotation_and_trim(tmp_path):
    from raft_tla_tpu.utils import native

    ls = native.LevelStore(str(tmp_path / "r"), 2, 1, 0, 1, reset=True)
    ls.cur.append([[0, 0]])                  # the init row
    ls.append([[1, 1], [2, 2]])              # level 2 discoveries
    ls.sync()
    ls.rotate()                              # level boundary
    assert ls.cur.base == 1 and len(ls.cur) == 3
    assert ls.nxt.base == 3
    ls.append([[3, 3], [4, 4]])
    ls.trim_next(4)                          # npz said only 4 states
    assert len(ls) == 4
    assert ls.read(3, 1).tolist() == [[3, 3]]
    assert ls.read(1, 2).tolist() == [[1, 1], [2, 2]]   # cur routing
    ls.close()


# -- mesh (ddd-shard) frontier mode ----------------------------------------

def _mesh_caps(**kw):
    from raft_tla_tpu.parallel.ddd_shard_engine import DDDShardCapacities

    base = dict(block=256, table=1 << 10, seg_rows=1 << 16,
                flush=1 << 10, levels=64, retention="frontier")
    base.update(kw)
    return DDDShardCapacities(**base)


@pytest.mark.slow      # virtual-mesh test (see test_shard_engine)
def test_mesh_frontier_parity_8dev():
    from raft_tla_tpu.parallel.ddd_shard_engine import DDDShardEngine
    from raft_tla_tpu.parallel.mesh import make_mesh

    ref = refbfs.check(ELECTION)
    got = DDDShardEngine(ELECTION, make_mesh(8), _mesh_caps()).check()
    assert got.n_states == ref.n_states
    assert got.diameter == ref.diameter
    assert got.n_transitions == ref.n_transitions
    assert got.levels == ref.levels


@pytest.mark.slow      # virtual-mesh test (see test_shard_engine)
def test_mesh_frontier_checkpoint_resume_and_reshard(tmp_path):
    """Mesh frontier: snapshot, resume in place, and reshard the
    frontier snapshot 8 -> 2 (keys + level files move verbatim)."""
    from raft_tla_tpu.parallel.ddd_shard_engine import (
        DDDShardEngine, reshard_ddd_checkpoint)
    from raft_tla_tpu.parallel.mesh import make_mesh

    ck = str(tmp_path / "m.ckpt")
    ck2 = str(tmp_path / "m2.ckpt")
    ref = refbfs.check(FULL)
    DDDShardEngine(FULL, make_mesh(8), _mesh_caps()).check(
        checkpoint=ck, checkpoint_every_s=0.0)
    got = DDDShardEngine(FULL, make_mesh(8), _mesh_caps()).check(
        resume=ck)
    assert got.n_states == ref.n_states
    assert got.diameter == ref.diameter

    caps2 = _mesh_caps(block=1024, seg_rows=1 << 16)
    reshard_ddd_checkpoint(FULL, _mesh_caps(), ck, ck2, ndev_src=8,
                           ndev_dst=2, caps_dst=caps2)
    from raft_tla_tpu.parallel.mesh import make_mesh as mm
    got2 = DDDShardEngine(FULL, mm(2), caps2).check(resume=ck2)
    assert got2.n_states == ref.n_states
    assert got2.diameter == ref.diameter
    assert got2.n_transitions == ref.n_transitions

# -- keep_levels: TLC's states/-dir regime -> full traces ----------------

VIOL_CFG = CheckConfig(
    bounds=Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                  max_msgs=1),
    spec="election", invariants=("NaiveNoTwoLeaders",), chunk=256)


def _assert_replayable(trace, cfg):
    """Every edge of the reconstructed trace must be a real interpreter
    transition with the claimed action label (no-symmetry configs)."""
    from raft_tla_tpu.models import interp, spec as S
    table = S.action_table(cfg.bounds, cfg.spec)
    assert trace[0][0] is None
    assert trace[0][1] == interp.init_state(cfg.bounds)
    for (_, prev), (label, cur) in zip(trace, trace[1:]):
        succ = [(table[i].label(), n)
                for i, n in interp.successors(prev, cfg.bounds, table,
                                              cfg.spec)]
        assert (label, cur) in succ


def test_frontier_keep_levels_full_violation_trace():
    got = DDDEngine(VIOL_CFG, _caps(keep_levels=True)).check()
    assert got.violation is not None
    full = DDDEngine(VIOL_CFG, _caps(retention="full")).check()
    # same violating endpoint, same (shortest) trace length as the
    # link-following full-retention trace, every edge replayable
    assert got.violation.state == full.violation.state
    assert len(got.violation.trace) == len(full.violation.trace)
    assert got.violation.trace[-1][1] == got.violation.state
    _assert_replayable(got.violation.trace, VIOL_CFG)


def test_frontier_keep_levels_trace_with_checkpointing(tmp_path):
    # snapshots must not garbage-collect the retained level files
    ck = str(tmp_path / "run")
    got = DDDEngine(VIOL_CFG, _caps(keep_levels=True)).check(
        checkpoint=ck, checkpoint_every_s=0.0)
    assert got.violation is not None
    assert len(got.violation.trace) > 1
    _assert_replayable(got.violation.trace, VIOL_CFG)
    # every level file from L1 up survives on disk
    n_levels = len(glob.glob(ck + ".rowsL*"))
    assert n_levels >= len(got.violation.trace)


def test_frontier_keep_levels_deadlock_trace():
    cfg = CheckConfig(
        bounds=Bounds(n_servers=1, n_values=1, max_term=2, max_log=0,
                      max_msgs=2),
        spec="election", invariants=(), check_deadlock=True, chunk=64)
    got = DDDEngine(cfg, _caps(block=1 << 8, keep_levels=True)).check()
    ref = refbfs.check(cfg)
    assert got.violation is not None
    assert got.violation.invariant == ref.violation.invariant
    assert len(got.violation.trace) == len(ref.violation.trace)
    _assert_replayable(got.violation.trace, cfg)


@pytest.mark.slow      # virtual-mesh test (see test_shard_engine)
def test_frontier_keep_levels_shard_trace():
    from raft_tla_tpu.parallel.ddd_shard_engine import (
        DDDShardCapacities, DDDShardEngine)
    from raft_tla_tpu.parallel.mesh import make_mesh
    caps = DDDShardCapacities(block=256, table=1 << 14,
                              seg_rows=1 << 14, flush=1 << 12,
                              levels=64, retention="frontier",
                              keep_levels=True)
    got = DDDShardEngine(VIOL_CFG, make_mesh(2), caps).check()
    assert got.violation is not None
    full = DDDEngine(VIOL_CFG, _caps(retention="full")).check()
    assert len(got.violation.trace) == len(full.violation.trace)
    assert got.violation.trace[-1][1] == got.violation.state
    _assert_replayable(got.violation.trace, VIOL_CFG)


def test_frontier_keep_levels_trace_composes_with_symmetry():
    cfg = CheckConfig(
        bounds=VIOL_CFG.bounds, spec="election",
        invariants=("NaiveNoTwoLeaders",), symmetry=("Server",),
        chunk=256)
    got = DDDEngine(cfg, _caps(keep_levels=True)).check()
    full = DDDEngine(cfg, _caps(retention="full")).check()
    assert got.violation is not None and full.violation is not None
    # states are canonical orbit representatives; the trace matches the
    # full-retention link trace in endpoint and (shortest) length
    assert got.violation.state == full.violation.state
    assert len(got.violation.trace) == len(full.violation.trace)
    assert got.violation.trace[-1][1] == got.violation.state
    assert got.violation.trace[0][0] is None
    assert all(lbl is not None for lbl, _ in got.violation.trace[1:])
