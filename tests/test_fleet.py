"""Mesh-sharded walker fleets (fleet/): device-count-invariant results,
coverage steering, fault-weight scenarios, and fleet telemetry.

The load-bearing contract: a fixed (seed, walkers, depth,
steps_per_dispatch) reproduces the SAME walks bit for bit at any device
count — sharding is a throughput decision, never a semantics decision.
"""

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.fleet import FleetSimulator, Scenario, fault_matrix, \
    run_matrix
from raft_tla_tpu.fleet.scenario import FAULT_FAMILIES
from raft_tla_tpu.models import interp, spec as S
from raft_tla_tpu.ops import msgbits as mb
from raft_tla_tpu.parallel.mesh import make_mesh

B3 = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0, max_msgs=4)
CV = CheckConfig(bounds=B3, spec="election",
                 invariants=("NaiveNoTwoLeaders",))
CLEAN = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                  max_log=1, max_msgs=2),
                    spec="full", invariants=("NoTwoLeaders",))


def bag(*ms):
    return tuple(sorted((m, 1) for m in ms))


def seeded_start():
    """Two steps from a NaiveNoTwoLeaders violation (engine-test seed)."""
    return interp.init_state(B3)._replace(
        role=(S.LEADER, S.FOLLOWER, S.CANDIDATE),
        term=(2, 3, 3), votedFor=(1, 3, 0),
        vGrant=(0b011, 0, 0b100), msgs=bag(mb.rv_response(3, 1, 1, 2)))


def fleet(config, ndev, **kw):
    kw.setdefault("walkers", 64)
    kw.setdefault("depth", 24)
    kw.setdefault("steps_per_dispatch", 12)
    kw.setdefault("seed", 11)
    return FleetSimulator(config, mesh=make_mesh(ndev), **kw)


def test_device_count_invariance_bit_for_bit():
    """Same (seed, walkers, depth) -> identical walks at 1 vs 2 devices,
    down to the recorded per-walker lane histories."""
    r1 = fleet(CLEAN, 1).run(300, snapshot_walks=True)
    r2 = fleet(CLEAN, 2).run(300, snapshot_walks=True)
    assert (r1.n_behaviors, r1.n_states, r1.max_depth_seen) == \
        (r2.n_behaviors, r2.n_states, r2.max_depth_seen)
    assert r1.coverage == r2.coverage
    assert r1.coverage_entropy == r2.coverage_entropy
    assert np.array_equal(r1.walks[0], r2.walks[0])     # lane histories
    assert np.array_equal(r1.walks[1], r2.walks[1])     # walk lengths
    assert r1.device_states == [r1.n_states]
    assert sum(r2.device_states) == r2.n_states and \
        len(r2.device_states) == 2


@pytest.mark.slow
def test_violation_parity_and_replay_across_meshes():
    traces = []
    for nd in (1, 2):
        r = fleet(CV, nd, walkers=128, depth=20, steps_per_dispatch=10,
                  seed=3).run(100000, init_override=seeded_start())
        assert r.violation is not None
        assert r.violation.invariant == "NaiveNoTwoLeaders"
        traces.append(r.violation.trace)
    assert traces[0] == traces[1]
    tab = S.action_table(B3, "election")
    cur = traces[0][0][1]
    for label, nxt in traces[0][1:]:
        assert nxt in {t for _a, t in interp.successors(cur, B3, tab)}, \
            label
        cur = nxt
    assert sum(1 for x in cur.role if x == S.LEADER) >= 2


@pytest.mark.slow
def test_steering_shifts_coverage():
    """Coverage steering flattens the per-action histogram: normalized
    entropy rises, while the run still checks the same invariants over
    the same universe."""
    base = fleet(CV, 2, walkers=128, seed=5).run(400)
    steered = fleet(CV, 2, walkers=128, seed=5, steer_tau=2.0).run(400)
    assert steered.coverage_entropy > base.coverage_entropy
    assert steered.violation is None and base.violation is None
    assert sum(steered.coverage.values()) > 0


@pytest.mark.slow
def test_fault_weight_matrix_shifts_sampling():
    """One compiled fleet sweeps the fault-intensity matrix (weights are
    a traced input): weight 0 starves the fault lanes, weight 2 feeds
    them — without touching enabledness."""
    cc = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                   max_log=1, max_msgs=2, max_dup=1),
                     spec="full", invariants=("NoTwoLeaders",))
    sim = fleet(cc, 2, walkers=128, depth=30, steps_per_dispatch=15)
    cells = run_matrix(sim, fault_matrix((0.0, 2.0)), 300)
    by_name = {sc.name: r for sc, r in cells}
    visits = lambda r: sum(r.coverage.get(f, 0) for f in FAULT_FAMILIES)
    assert visits(by_name["faults-x0"]) == 0
    assert visits(by_name["faults-x2"]) > visits(by_name["uniform"]) > 0


@pytest.mark.slow
def test_zero_weight_never_false_deadlocks():
    """When weight-0 lanes are the only enabled ones the sampler falls
    back to uniform-over-valid: from Raft init only Timeout is enabled,
    and starving it must not strand the fleet."""
    r = fleet(CV, 2, fault_weights={"Timeout": 0.0}).run(100)
    assert r.violation is None and r.n_behaviors >= 100
    assert r.coverage["Timeout"] > 0          # fallback sampled it


def test_fleet_rejects_bad_shapes_and_weights():
    with pytest.raises(ValueError, match="divide evenly"):
        fleet(CV, 2, walkers=63)
    with pytest.raises(ValueError, match="unknown action families"):
        fleet(CV, 1, fault_weights={"Restart": 1.0})   # not in election
    with pytest.raises(ValueError, match="negative"):
        fleet(CV, 1, fault_weights={"Timeout": -1.0})
    with pytest.raises(ValueError, match="SYMMETRY"):
        FleetSimulator(CheckConfig(bounds=B3, spec="election",
                                   invariants=(), symmetry=("Server",)))


def test_twophase_fleet_violation_replays():
    cc = CheckConfig(bounds=Bounds(n_servers=2, n_values=1),
                     spec="twophase", invariants=("~(msgCommit = 1)",))
    r = fleet(cc, 2, depth=20).run(200)
    assert r.violation is not None
    assert r.violation.invariant == "~(msgCommit = 1)"
    assert r.violation.trace[-1][1] == r.violation.state
    assert len(r.violation.trace) >= 5        # prepare/prepare/rcv/commit
    from raft_tla_tpu.frontend import resolve_model
    txt = resolve_model("twophase").render_trace(r.violation, cc.bounds)
    assert "TMCommit" in txt and "Initial predicate" in txt


def test_fleet_emits_conformant_events(tmp_path):
    """fleet speaks RunTelemetry v3: per-device segment rates and a
    run_end carrying the statistical-confidence payload."""
    import json

    from raft_tla_tpu.obs import validate_event

    path = str(tmp_path / "fleet.events")
    r = fleet(CLEAN, 2).run(300, events=path)
    assert r.violation is None
    events = [json.loads(l) for l in open(path)]
    assert not [e for d in events for e in validate_event(d)]
    assert events[0]["event"] == "run_start"
    assert events[0]["engine"] == "fleet"
    segs = [d for d in events if d["event"] == "segment"]
    assert segs and all(len(d["device_rates"]) == 2 for d in segs)
    end = events[-1]
    assert end["event"] == "run_end" and end["outcome"] == "ok"
    sim = end["sim"]
    assert sim["behaviors"] == r.n_behaviors
    assert sim["sampled_transitions"] == r.n_states
    assert sim["n_devices"] == 2 and sim["walkers"] == 64
    assert sim["per_invariant"] == {"NoTwoLeaders": r.n_states}
    # the run_end payload IS the result's confidence report
    conf = r.confidence(CLEAN.invariants)
    assert sim == {**conf, "behaviors": r.n_behaviors}
    assert 0.0 <= conf["coverage_entropy"] <= 1.0
    assert r.states_per_sec > 0


def test_scenario_matrix_helpers():
    ms = fault_matrix((0.0, 0.5, 1.0, 2.0))
    assert [s.name for s in ms] == ["uniform", "faults-x0", "faults-x0.5",
                                    "faults-x2"]      # x1 == uniform
    assert ms[0].describe() == "uniform: uniform"
    assert "Restart=2" in ms[-1].describe()
    assert Scenario("x", {"Restart": 0.5}).fault_weights == \
        {"Restart": 0.5}


def test_cli_fleet_smoke(tmp_path):
    from test_cli import run_cli, write_cfg
    from raft_tla_tpu import check as cli
    cfg = write_cfg(tmp_path / "f.cfg")
    code, out = run_cli(cfg, "--engine", "ref", "--spec", "election",
                        "--max-term", "2", "--max-log", "0",
                        "--max-msgs", "2", "--simulate", "200",
                        "--depth", "20", "--walkers", "64", "--seed", "5",
                        "--fleet", "--devices", "2")
    assert code == cli.EXIT_OK
    assert "behaviors generated" in out and "not exhaustive" in out
    assert "Fleet: 2 devices x 32 walkers" in out
    assert "held on" in out                 # confidence lines


def test_cli_fleet_flag_validation(tmp_path):
    from test_cli import run_cli, write_cfg
    cfg = write_cfg(tmp_path / "v.cfg")
    for extra in (["--fleet"],                          # no --simulate
                  ["--simulate", "10", "--steer", "1"],  # steer sans fleet
                  ["--simulate", "10", "--fault-weights", "Restart=2"]):
        with pytest.raises(SystemExit):
            run_cli(cfg, "--engine", "ref", "--spec", "election",
                    "--max-term", "2", "--max-log", "0",
                    "--max-msgs", "2", *extra)
