"""Deadlock checking (TLC's default check; CLI --deadlock, exit 11).

A deadlock is a reachable, expanded state with no successor at all
(stuttering excluded; CONSTRAINT gates exploration, not enabledness).
The full ``Next`` can never deadlock — ``Restart`` is always enabled
(raft.tla:167-175, an unconditioned disjunct raft.tla:454) — so the
interesting cases are sub-specs:

- 1-server election: the server elects itself (quorum of one), consumes
  the vote round-trip, and the sole Leader with an empty bag has no
  enabled action.
- replication sub-spec from Init: no leader exists and every disjunct
  needs one, so Init itself deadlocks.
"""

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.models import interp, refbfs, spec as S
from raft_tla_tpu import engine
from raft_tla_tpu.engine import DEADLOCK

B1 = Bounds(n_servers=1, n_values=1, max_term=2, max_log=0, max_msgs=1)
CFG1 = CheckConfig(bounds=B1, spec="election", invariants=("NoTwoLeaders",),
                   chunk=64, check_deadlock=True)


def _assert_deadlock(res, ref):
    assert res.violation is not None
    assert res.violation.invariant == DEADLOCK
    assert (res.n_states, res.diameter) == (ref.n_states, ref.diameter)
    assert res.violation.state == ref.violation.state
    assert len(res.violation.trace) == len(ref.violation.trace)


def test_refbfs_finds_election_deadlock():
    ref = refbfs.check(CFG1)
    assert ref.violation is not None and ref.violation.invariant == DEADLOCK
    final = ref.violation.state
    assert final.role == (S.LEADER,) and final.msgs == ()
    # the trace replays action by action through the interpreter
    cur = ref.violation.trace[0][1]
    table = S.action_table(B1, "election")
    for _label, nxt in ref.violation.trace[1:]:
        assert nxt in {t for _a, t in interp.successors(cur, B1, table)}
        cur = nxt
    # and the final state genuinely has no successors
    assert not list(interp.successors(cur, B1, table))


def test_refbfs_no_deadlock_when_flag_off():
    ref = refbfs.check(CheckConfig(bounds=B1, spec="election",
                                   invariants=("NoTwoLeaders",), chunk=64))
    assert ref.violation is None


def test_host_engine_deadlock_parity():
    ref = refbfs.check(CFG1)
    _assert_deadlock(engine.check(CFG1), ref)


def test_device_engine_deadlock_parity():
    from raft_tla_tpu.device_engine import Capacities, DeviceEngine
    ref = refbfs.check(CFG1)
    got = DeviceEngine(CFG1, Capacities(n_states=1 << 12, levels=32)).check()
    _assert_deadlock(got, ref)


@pytest.mark.slow      # virtual-mesh test (see test_shard_engine)
def test_shard_engine_deadlock():
    """Like violation traces, deadlock reporting in the sharded engine is
    interleaving-dependent in its level accounting (module docstring); the
    verdict, state count and deadlocked state itself must still agree."""
    from raft_tla_tpu.parallel.shard_engine import (ShardCapacities,
                                                    ShardEngine, make_mesh)
    ref = refbfs.check(CFG1)
    got = ShardEngine(CFG1, make_mesh(2),
                      ShardCapacities(n_states=1 << 12, levels=32)).check()
    assert got.violation is not None
    assert got.violation.invariant == DEADLOCK
    assert got.n_states == ref.n_states
    assert got.violation.state == ref.violation.state


def test_replication_spec_init_deadlocks_immediately():
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=1, max_msgs=2),
                      spec="replication", invariants=(), chunk=64,
                      check_deadlock=True)
    ref = refbfs.check(cfg)
    assert ref.violation is not None and ref.violation.invariant == DEADLOCK
    assert ref.n_states == 1 and len(ref.violation.trace) == 1
    got = engine.check(cfg)
    assert got.violation is not None and got.violation.invariant == DEADLOCK
    assert got.n_states == 1


def test_full_spec_cannot_deadlock():
    cfg = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                    max_log=0, max_msgs=1),
                      spec="full", invariants=(), chunk=128,
                      check_deadlock=True)
    assert refbfs.check(cfg).violation is None


def test_cli_deadlock_exit_code(tmp_path):
    from test_cli import run_cli, write_cfg
    from raft_tla_tpu import check as cli
    cfg = write_cfg(tmp_path / "d.cfg", servers="s1")
    code, out = run_cli(cfg, "--engine", "ref", "--spec", "election",
                        "--deadlock", "--max-term", "2", "--max-log", "0",
                        "--max-msgs", "1", "--no-trace")
    assert code == cli.EXIT_DEADLOCK == 11
    assert "Deadlock reached." in out
    # with the trace enabled, the TLC-style header names the deadlock too
    code, out = run_cli(cfg, "--engine", "ref", "--spec", "election",
                        "--deadlock", "--max-term", "2", "--max-log", "0",
                        "--max-msgs", "1")
    assert code == 11 and "Error: Deadlock reached." in out
    assert "State 1: <Initial predicate>" in out
