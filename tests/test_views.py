"""The deadvotes VIEW (models/views.py) — TLC VIEW analog.

The soundness of the quotient rests on view-equivalence being a
bisimulation; ``test_deadvotes_bisimulation`` checks that mechanically
against THIS implementation's action semantics (not just the raft.tla
reading): states differing only in non-Candidate vote sets must enable
identical actions, produce view-identical successors, and agree on
every registered invariant and the constraint.  The remaining tests
pin the quotient's exactness (same verdicts, violations still found)
and the engine/oracle/digest plumbing.
"""

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.models import interp, invariants as inv_mod, refbfs
from raft_tla_tpu.models import spec as S
from raft_tla_tpu.models.views import py_view
from raft_tla_tpu.ops import msgbits as mb

BOUNDS = Bounds(n_servers=3, n_values=1, max_term=2, max_log=0,
                max_msgs=1)
CFG = CheckConfig(bounds=BOUNDS, spec="election",
                  invariants=("NoTwoLeaders",), chunk=64,
                  view="deadvotes")
PLAIN = CheckConfig(bounds=BOUNDS, spec="election",
                    invariants=("NoTwoLeaders",), chunk=64)


def bag(*ms):
    return tuple(sorted((m, 1) for m in ms))


def test_unknown_view_rejected():
    with pytest.raises(ValueError, match="unknown view"):
        CheckConfig(bounds=BOUNDS, view="nope")


def _bisim_walk(bounds, spec, inv_names, min_checked, seed=7):
    """For reachable states s, scrambling the dead vote sets must not
    change: enabled action lanes, viewed successors per lane, any
    registered invariant, or the constraint."""
    rng = np.random.default_rng(seed)
    view = py_view("deadvotes")
    full_mask = (1 << bounds.n_servers) - 1
    invs = [inv_mod.py_invariant(nm) for nm in inv_names]

    # sample reachable states by random walk
    states = [interp.init_state(bounds)]
    cur = states[0]
    for _ in range(400):
        succ = list(interp.successors(cur, bounds, spec=spec))
        if not succ:
            cur = states[0]
            continue
        cur = succ[rng.integers(len(succ))][1]
        states.append(cur)

    # every walk state plus each state's one-step successors: the walk
    # saturates into all-Candidate regions fast, so the successor fringe
    # supplies most of the states that still have a non-Candidate
    fringe = [t for s in states[::8]
              for _a, t in interp.successors(s, bounds, spec=spec)]
    checked = 0
    for s in states + fringe:
        dead = [i for i, r in enumerate(s.role) if r != S.CANDIDATE]
        if not dead:
            continue
        vr, vg = list(s.vResp), list(s.vGrant)
        for i in dead:
            vr[i] = int(rng.integers(full_mask + 1))
            vg[i] = int(rng.integers(full_mask + 1))
        s2 = s._replace(vResp=tuple(vr), vGrant=tuple(vg))
        assert view(s, bounds) == view(s2, bounds)
        su1 = list(interp.successors(s, bounds, spec=spec))
        su2 = list(interp.successors(s2, bounds, spec=spec))
        assert [a for a, _ in su1] == [a for a, _ in su2]
        for (a1, t1), (a2, t2) in zip(su1, su2):
            assert view(t1, bounds) == view(t2, bounds), (a1, s)
        for f in invs:
            assert f(s, bounds) == f(s2, bounds)
        assert interp.constraint_ok(s, bounds) == \
            interp.constraint_ok(s2, bounds)
        checked += 1
    assert checked >= min_checked     # the walk must exercise dead sets


def test_deadvotes_bisimulation():
    _bisim_walk(BOUNDS, "election",
                ("NoTwoLeaders", "ElectionSafety", "NaiveNoTwoLeaders"),
                min_checked=40)


def test_deadvotes_bisimulation_full_spec():
    """The soundness claim covers every full-spec action (Restart,
    Duplicate/Drop, AppendEntries, ClientRequest, AdvanceCommitIndex
    included), not just the election subset."""
    _bisim_walk(Bounds(n_servers=3, n_values=1, max_term=2, max_log=1,
                       max_msgs=2, max_dup=1), "full",
                ("NoTwoLeaders", "LogMatching", "CommittedWithinLog"),
                min_checked=40)


def test_deadvotes_bisimulation_faithful():
    """Faithful mode: history variables (elections/allLogs/voterLog)
    join state identity; the view must stay a bisimulation there too
    (the elections record is only written by BecomeLeader — a Candidate,
    where the view is the identity)."""
    _bisim_walk(Bounds(n_servers=2, n_values=1, max_term=2, max_log=1,
                       max_msgs=2, history=True, max_elections=4), "full",
                ("NoTwoLeaders", "ElectionSafetyHist"),
                min_checked=15)


def test_refbfs_quotient_is_smaller_and_safe():
    plain = refbfs.check(PLAIN)
    viewed = refbfs.check(CFG)
    assert viewed.violation is None and plain.violation is None
    assert viewed.n_states < plain.n_states
    assert viewed.diameter <= plain.diameter
    # the quotient must still reach every viewed state: counts are
    # reproducible constants worth pinning (3s election t2/m1; the
    # measured reduction is ~9.4% here — RESULTS.md "deadvotes VIEW")
    assert plain.n_states == 142538
    assert viewed.n_states == 129134


def test_violation_still_found_under_view():
    bounds = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                    max_msgs=4, max_dup=1)
    start = interp.init_state(bounds)._replace(
        role=(S.LEADER, S.FOLLOWER, S.CANDIDATE),
        term=(2, 3, 3),
        votedFor=(1, 3, 0),
        vGrant=(0b011, 0, 0b100),
        msgs=bag(mb.rv_response(3, 1, 1, 2)),
    )
    for view in (None, "deadvotes"):
        cfg = CheckConfig(bounds=bounds, spec="election",
                          invariants=("NaiveNoTwoLeaders",), chunk=64,
                          view=view)
        got = refbfs.check(cfg, init_override=start)
        assert got.violation is not None
        assert got.violation.invariant == "NaiveNoTwoLeaders"
        assert not inv_mod.py_invariant("NaiveNoTwoLeaders")(
            got.violation.state, bounds)


def test_engine_parity_under_view():
    """Device pipeline (jnp view) == oracle (py view), exact discovery
    order: counts, levels, coverage."""
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine

    ref = refbfs.check(CFG)
    caps = DDDCapacities(block=1 << 12, table=1 << 14, flush=1 << 12,
                         levels=64)
    got = DDDEngine(CFG, caps).check()
    assert got.n_states == ref.n_states
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert got.coverage == ref.coverage


def test_view_composes_with_symmetry():
    cfg_sv = CheckConfig(bounds=BOUNDS, spec="election",
                         invariants=("NoTwoLeaders",), chunk=64,
                         symmetry=("Server",), view="deadvotes")
    cfg_s = CheckConfig(bounds=BOUNDS, spec="election",
                        invariants=("NoTwoLeaders",), chunk=64,
                        symmetry=("Server",))
    ref_sv = refbfs.check(cfg_sv)
    ref_s = refbfs.check(cfg_s)
    assert ref_sv.n_states < ref_s.n_states
    assert ref_sv.violation is None

    from raft_tla_tpu.engine import Engine
    got = Engine(cfg_sv).check()
    assert got.n_states == ref_sv.n_states
    assert got.coverage == ref_sv.coverage


def test_view_joins_checkpoint_digest(tmp_path):
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine

    caps = DDDCapacities(block=1 << 12, table=1 << 14, flush=1 << 12,
                         levels=64)
    ck = str(tmp_path / "v.ckpt")
    DDDEngine(PLAIN, caps).check(checkpoint=ck, checkpoint_every_s=0.0)
    with pytest.raises(ValueError, match="different model"):
        DDDEngine(CFG, caps).check(resume=ck)


def test_tlc_export_carries_view():
    """--emit-tlc under a view must emit a MATCHING TLC VIEW — a twin
    artifact that silently explored the unquotiented space would
    disagree with the run's printed totals."""
    from raft_tla_tpu.models import tla_export

    t = tla_export.emit_module(BOUNDS, ("NoTwoLeaders",), True, False,
                               "deadvotes")
    assert "DeadVotes(votesResponded)" in t
    assert "DeadVotes(votesGranted)" in t
    c = tla_export.emit_cfg(BOUNDS, ("NoTwoLeaders",), True, False,
                            "deadvotes")
    assert "VIEW ParityView" in c
    # faithful mode keeps history vars in the identity, masks votes only
    fb = Bounds(n_servers=2, n_values=1, max_term=2, max_log=1,
                max_msgs=2, history=True, max_elections=4)
    t2 = tla_export.emit_module(fb, ("NoTwoLeaders",), False, False,
                                "deadvotes")
    assert "DeadVotesView" in t2 and "voterLog" in t2
    assert "VIEW DeadVotesView" in tla_export.emit_cfg(
        fb, ("NoTwoLeaders",), False, False, "deadvotes")


@pytest.mark.slow      # virtual-mesh test (see test_shard_engine)
def test_mesh_engine_under_view():
    from raft_tla_tpu.parallel.ddd_shard_engine import (
        DDDShardCapacities, DDDShardEngine)
    from raft_tla_tpu.parallel.mesh import make_mesh

    ref = refbfs.check(CFG)
    caps = DDDShardCapacities(block=1 << 12, table=1 << 12,
                              seg_rows=1 << 15, flush=1 << 12, levels=64)
    got = DDDShardEngine(CFG, make_mesh(8), caps).check()
    assert got.n_states == ref.n_states
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions

def test_predicates_view_invariant():
    """The PREDICATES registry's obligation #2 (models/liveness.py):
    every registered temporal predicate must read only view-preserved
    fields, for every registered view — pred(s) == pred(view(s)) over a
    reachable full-spec corpus.  A future predicate that reads vote
    sets (legal for symmetry, unsound under deadvotes) fails here
    loudly instead of silently mis-evaluating on the quotient."""
    from raft_tla_tpu.models import interp, liveness, views

    b = Bounds(n_servers=2, n_values=1, max_term=2, max_log=0,
               max_msgs=2)
    cfg = CheckConfig(bounds=b, spec="full", invariants=())
    # reachable corpus: the whole bounded 2-server full-spec space
    seen = {interp.init_state(b)}
    frontier = [interp.init_state(b)]
    while frontier:
        nxt = []
        for s in frontier:
            if not interp.constraint_ok(s, b):
                continue
            for _i, t in interp.successors(s, b, spec="full"):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    assert len(seen) > 20000            # the corpus is the real space
    for vname in views.REGISTRY:
        vw = views.py_view(vname)
        # the view must move SOME state or the check is vacuous
        assert any(vw(s, b) != s for s in seen)
        for pname, (pred, _struct, _tla) in liveness.PREDICATES.items():
            bad = [s for s in seen if pred(s, b) != pred(vw(s, b), b)]
            assert not bad, (vname, pname, bad[:1])
