"""Sharded multi-device engine ≡ oracle (SURVEY §4.3-§4.4).

Runs on the 8-device virtual CPU mesh (conftest.py) — the checker's
"multi-node without a cluster" story.  Exploration metrics (state counts,
per-level counts, diameter, transition counts, verdicts) must match refbfs
exactly; per-action coverage matches in total (attribution is interleaving-
dependent — see shard_engine.py module docstring).
"""

import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.models import interp, refbfs, spec as S
from raft_tla_tpu.ops import msgbits as mb
from raft_tla_tpu.parallel import ShardCapacities, ShardEngine, make_mesh

# smoke tier: cross-section for mid-round changes (pytest -m smoke)
pytestmark = [pytest.mark.smoke, pytest.mark.slow]

CAPS = ShardCapacities(n_states=1 << 12, levels=64)


def bag(*ms):
    return tuple(sorted((m, 1) for m in ms))


def assert_parity(cfg, ndev=8, caps=CAPS, **kw):
    ref = refbfs.check(cfg, **kw)
    got = ShardEngine(cfg, make_mesh(ndev), caps).check(**kw)
    assert got.n_states == ref.n_states
    assert got.diameter == ref.diameter
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert sum(got.coverage.values()) == sum(ref.coverage.values())
    assert (got.violation is None) == (ref.violation is None)
    return ref, got


def test_election_2server_parity_8dev():
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=("NoTwoLeaders",), chunk=64)
    _, got = assert_parity(cfg)
    assert got.violation is None and got.n_states > 1000


def test_full_spec_small_parity_8dev():
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=1, max_msgs=2),
                      spec="full",
                      invariants=("NoTwoLeaders", "LogMatching",
                                  "CommittedWithinLog"),
                      chunk=128)
    _, got = assert_parity(cfg, caps=ShardCapacities(n_states=1 << 14,
                                                     levels=64))
    assert got.violation is None
    for fam in (S.RESTART, S.DUPLICATE, S.DROP):
        assert got.coverage[fam] > 0


def test_ndev_invariance():
    """1-, 2- and 8-chip meshes explore the identical state graph."""
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=("NoTwoLeaders",), chunk=32)
    runs = {n: ShardEngine(cfg, make_mesh(n), CAPS).check()
            for n in (1, 2, 8)}
    base = runs[1]
    for n, r in runs.items():
        assert r.n_states == base.n_states, n
        assert r.levels == base.levels, n
        assert r.n_transitions == base.n_transitions, n


def test_violation_trace_replayable_8dev():
    """Seeded NaiveNoTwoLeaders violation: the cross-chip trace must replay.

    The trace may be a different counterexample than refbfs's (discovery
    interleaving), but it must start at Init, follow real transitions, and
    end in a state violating the same invariant.
    """
    bounds = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                    max_msgs=4, max_dup=1)
    cfg = CheckConfig(bounds=bounds, spec="election",
                      invariants=("NaiveNoTwoLeaders",), chunk=256)
    start = interp.init_state(bounds)._replace(
        role=(S.LEADER, S.FOLLOWER, S.CANDIDATE),
        term=(2, 3, 3),
        votedFor=(1, 3, 0),
        vGrant=(0b011, 0, 0b100),
        msgs=bag(mb.rv_response(3, 1, 1, 2)),
    )
    got = ShardEngine(cfg, make_mesh(8), CAPS).check(init_override=start)
    assert got.violation is not None
    assert got.violation.invariant == "NaiveNoTwoLeaders"
    trace = got.violation.trace
    assert trace[0][0] is None and trace[0][1] == start
    for (_l, prev), (_label, cur) in zip(trace, trace[1:]):
        succs = [t for _i, t in interp.successors(prev, bounds,
                                                  spec="election")]
        assert cur in succs
    from raft_tla_tpu.models import invariants as inv_mod
    assert not inv_mod.py_invariant("NaiveNoTwoLeaders")(
        got.violation.state, bounds)


def test_routing_overflow_is_loud():
    """A send buffer too small for one owner's share must abort, not clamp."""
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=(), chunk=64)
    caps = ShardCapacities(n_states=1 << 12, levels=64, send=1)
    with pytest.raises(RuntimeError, match="routing budget"):
        ShardEngine(cfg, make_mesh(8), caps).check()


def test_slice_mesh_2x4_parity():
    """2-D (dcn, ici) mesh with the hierarchical two-stage exchange
    explores the identical state graph: same counts, levels, transitions,
    verdicts as the oracle and (by test_ndev_invariance) the 1-D mesh."""
    from raft_tla_tpu.parallel.mesh import make_slice_mesh

    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=("NoTwoLeaders",),
                      chunk=64)
    ref = refbfs.check(cfg)
    got = ShardEngine(cfg, make_slice_mesh(2, 4), CAPS).check()
    assert got.n_states == ref.n_states
    assert got.diameter == ref.diameter
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert sum(got.coverage.values()) == sum(ref.coverage.values())
    assert got.violation is None


def test_slice_mesh_checkpoint_portable_from_1d(tmp_path):
    """FP ownership is by FLAT device id, so a 1-D 8-mesh checkpoint
    resumes on a 2x4 slice mesh (same total size) and finishes with
    identical counts."""
    from raft_tla_tpu.parallel.mesh import make_slice_mesh

    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=("NoTwoLeaders",),
                      chunk=64)
    straight = ShardEngine(cfg, make_mesh(8), CAPS).check()
    ck = str(tmp_path / "flat.ckpt")
    ShardEngine(cfg, make_mesh(8), CAPS, seg_chunks=8).check(
        checkpoint=ck, checkpoint_every_s=0.0)
    got = ShardEngine(cfg, make_slice_mesh(2, 4), CAPS).check(resume=ck)
    assert got.n_states == straight.n_states
    assert got.levels == straight.levels
    assert got.n_transitions == straight.n_transitions


def test_reshard_checkpoint_across_mesh_sizes(tmp_path):
    """A mid-run 2-device snapshot resharded to 4, 1, and (with grown
    caps) 8 devices resumes with oracle-exact results — a pod-size
    change no longer discards a run.  Also exercises the mid-level
    promotion (expanded window prefix moves to the done region)."""
    from raft_tla_tpu.parallel.shard_engine import reshard_checkpoint

    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=("NoTwoLeaders",),
                      chunk=64)
    ref = refbfs.check(cfg)
    ck = str(tmp_path / "m2.ckpt")
    ShardEngine(cfg, make_mesh(2), CAPS, seg_chunks=8).check(
        checkpoint=ck, checkpoint_every_s=0.0)
    for nd in (4, 1):
        out = str(tmp_path / f"m{nd}.ckpt")
        info = reshard_checkpoint(cfg, CAPS, ck, out, nd)
        assert info["ndev_src"] == 2 and info["ndev_dst"] == nd
        got = ShardEngine(cfg, make_mesh(nd), CAPS).check(resume=out)
        assert got.n_states == ref.n_states
        assert got.levels == ref.levels
        assert got.n_transitions == ref.n_transitions
        assert sum(got.coverage.values()) == sum(ref.coverage.values())
        assert got.violation is None
    big = ShardCapacities(n_states=1 << 13, levels=96)  # grown store AND
    out = str(tmp_path / "m8big.ckpt")                  # levels array
    reshard_checkpoint(cfg, CAPS, ck, out, 8, caps_dst=big)
    got = ShardEngine(cfg, make_mesh(8), big).check(resume=out)
    assert got.n_states == ref.n_states
    assert got.levels == ref.levels


def test_reshard_symmetric_run(tmp_path):
    """Resharding recomputes ORBIT keys when the run has SYMMETRY; the
    resumed orbit counts must stay exact."""
    from raft_tla_tpu.parallel.shard_engine import reshard_checkpoint

    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=("NoTwoLeaders",),
                      symmetry=("Server",), chunk=64)
    ref = refbfs.check(cfg)
    assert ref.n_states == 1514
    ck = str(tmp_path / "sym2.ckpt")
    ShardEngine(cfg, make_mesh(2), CAPS, seg_chunks=8).check(
        checkpoint=ck, checkpoint_every_s=0.0)
    out = str(tmp_path / "sym8.ckpt")
    reshard_checkpoint(cfg, CAPS, ck, out, 8)
    got = ShardEngine(cfg, make_mesh(8), CAPS).check(resume=out)
    assert got.n_states == ref.n_states
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions


def test_reshard_refuses_finished_and_wrong_digest(tmp_path):
    from raft_tla_tpu.parallel.shard_engine import reshard_checkpoint

    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=2),
                      spec="election", invariants=("NoTwoLeaders",),
                      chunk=64)
    ck = str(tmp_path / "m2.ckpt")
    ShardEngine(cfg, make_mesh(2), CAPS, seg_chunks=8).check(
        checkpoint=ck, checkpoint_every_s=0.0)
    other = CheckConfig(bounds=cfg.bounds, spec="election",
                        invariants=(), chunk=64)
    with pytest.raises(ValueError, match="digest"):
        reshard_checkpoint(other, CAPS, ck, str(tmp_path / "x.ckpt"), 4)
    tiny = ShardCapacities(n_states=1 << 4, levels=64)
    with pytest.raises(ValueError, match="n_states"):
        reshard_checkpoint(cfg, CAPS, ck, str(tmp_path / "y.ckpt"), 1,
                           caps_dst=tiny)
