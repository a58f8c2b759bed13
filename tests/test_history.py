"""Faithful mode (SURVEY §7.0.3b): history variables as real state.

Covers the bounded-log universe (ops/loguniv.py), the history encodings in
the tensor schema, lane-exact kernel/interpreter differentials with history
on, engine parity, and the history-based invariants — including a seeded
ElectionSafetyHist violation that only history can see (the state-level
NoTwoLeaders reading holds while the history records two leaders for one
term... which cannot happen in Raft, so the seeded case uses a doctored
initial state).
"""

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.models import interp, invariants as inv_mod, refbfs
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops.loguniv import LogUniverse

from test_state import random_pystate
from test_kernels import _diff_on_states

BH = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2,
            history=True, max_elections=4)


def test_universe_roundtrip_and_prefix():
    uni = LogUniverse.of(BH)
    assert uni.size == 43            # R=6 (3 terms x 2 values), lengths 0..2
    for r in range(uni.size):
        t = uni.tuple_of_id(r)
        assert uni.id_of_tuple(t) == r
        if t:
            assert uni.id_of_tuple(t[:-1]) == int(uni.prefix_id(np.asarray(r), np))
    # empty log is rank 0 (parity-mode messages encode g = 0)
    assert uni.id_of_tuple(()) == 0


def test_universe_vectorized_matches_scalar():
    uni = LogUniverse.of(BH)
    rng = np.random.default_rng(7)
    for _ in range(100):
        ln = int(rng.integers(0, uni.L + 1))
        log = tuple((int(rng.integers(1, uni.T + 1)),
                     int(rng.integers(1, uni.V + 1))) for _ in range(ln))
        lt = np.zeros(uni.L, np.int32)
        lv = np.zeros(uni.L, np.int32)
        for k, (t, v) in enumerate(log):
            lt[k], lv[k] = t, v
        assert int(uni.log_id(lt, lv, np.int32(ln), np)) == uni.id_of_tuple(log)
        et, ev, eln = uni.decode(np.asarray(uni.id_of_tuple(log)), np)
        assert int(eln) == ln
        assert tuple((int(et[..., k]), int(ev[..., k]))
                     for k in range(ln)) == log


def test_layout_and_struct_roundtrip():
    lay = st.Layout.of(BH)
    assert lay.history and lay.E == 4 and lay.Wa == 2
    rng = np.random.default_rng(3)
    for _ in range(50):
        s = random_pystate(rng, BH)
        assert interp.from_struct(interp.to_struct(s, BH), BH) == s


def test_config_gates():
    with pytest.raises(ValueError, match="faithful"):
        CheckConfig(invariants=("ElectionSafetyHist",))
    with pytest.raises(ValueError, match="universe"):
        Bounds(history=True, max_term=6, max_log=4, n_values=2)


def test_differential_random_history_states():
    rng = np.random.default_rng(11)
    states = [random_pystate(rng, BH) for _ in range(48)]
    _diff_on_states(states, BH)


def test_differential_reachable_history_prefix():
    cc = CheckConfig(bounds=BH, spec="full", invariants=())
    frontier = [interp.init_state(BH)]
    seen = set(frontier)
    for _lvl in range(3):
        nxt = []
        for s in frontier:
            for _ai, t in interp.successors(s, BH):
                if t not in seen and interp.constraint_ok(s, BH):
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt[:64]
    _diff_on_states(list(seen)[:128], BH)
    assert cc.bounds.history


def test_faithful_refines_parity_full_spec():
    """History splits parity-equal states (e.g. post-crash states differing
    only in what was ever elected); counts must only grow."""
    bp = Bounds(n_servers=2, n_values=1, max_term=2, max_log=1, max_msgs=2)
    bh = Bounds(n_servers=2, n_values=1, max_term=2, max_log=1, max_msgs=2,
                history=True, max_elections=4)
    rp = refbfs.check(CheckConfig(bounds=bp, spec="full",
                                  invariants=("NoTwoLeaders",)))
    rh = refbfs.check(CheckConfig(
        bounds=bh, spec="full",
        invariants=("NoTwoLeaders", "ElectionSafetyHist",
                    "LeaderCompletenessHist", "AllLogsPrefixClosed")))
    assert rh.violation is None
    assert rh.n_states > rp.n_states        # 53398 vs 48041
    assert rh.diameter == rp.diameter == 32


def test_engine_parity_faithful():
    """Device-path BFS (engine.py, per-chunk jit) must agree with the
    interpreter BFS exactly in faithful mode."""
    from raft_tla_tpu import engine
    cc = CheckConfig(bounds=BH, spec="election",
                     invariants=("NoTwoLeaders", "ElectionSafetyHist"),
                     chunk=256)
    r_ref = refbfs.check(cc)
    r_eng = engine.check(cc)
    assert (r_eng.n_states, r_eng.diameter) == (r_ref.n_states, r_ref.diameter)
    assert r_eng.violation is None and r_ref.violation is None
    assert r_eng.coverage == r_ref.coverage


def test_election_safety_hist_seeded_violation():
    """Two same-term elections with different leaders in the history: the
    state-level NoTwoLeaders reading cannot see it (neither is in office),
    but ElectionSafetyHist must flag it — on both predicate faces."""
    n = BH.n_servers
    s = interp.init_state(BH)
    bad = s._replace(elections=tuple(sorted(
        [(2, 0, (), 0b11, ((), ())), (2, 1, (), 0b11, ((), ()))],
        key=interp._election_key)))
    assert inv_mod.py_invariant("NoTwoLeaders")(bad, BH)
    assert not inv_mod.py_invariant("ElectionSafetyHist")(bad, BH)
    import jax.numpy as jnp
    struct = {k: jnp.asarray(v) for k, v in interp.to_struct(bad, BH).items()}
    assert not bool(inv_mod.jnp_invariant("ElectionSafetyHist", BH)(struct))
    assert bool(inv_mod.jnp_invariant("LeaderCompletenessHist", BH)(struct))


def test_all_logs_prefix_closed_seeded():
    s = interp.init_state(BH)
    # ((1,1),(1,2)) present without its prefix ((1,1),)
    bad = s._replace(allLogs=tuple(sorted([(), ((1, 1), (1, 2))],
                                          key=interp._log_key)))
    ok = s._replace(allLogs=tuple(sorted([(), ((1, 1),), ((1, 1), (1, 2))],
                                         key=interp._log_key)))
    assert not inv_mod.py_invariant("AllLogsPrefixClosed")(bad, BH)
    assert inv_mod.py_invariant("AllLogsPrefixClosed")(ok, BH)
    import jax.numpy as jnp
    for s_, want in ((bad, False), (ok, True)):
        struct = {k: jnp.asarray(v)
                  for k, v in interp.to_struct(s_, BH).items()}
        assert bool(inv_mod.jnp_invariant("AllLogsPrefixClosed", BH)(struct)) \
            is want


def test_leader_completeness_hist_seeded_violation():
    """A committed entry missing from a later-term election's elog."""
    s = interp.init_state(BH)
    ent = (1, 1)
    bad = s._replace(
        log=((ent,), ()), commitIndex=(1, 0), term=(1, 1),
        elections=((2, 1, (), 0b11, ((), ())),))
    assert not inv_mod.py_invariant("LeaderCompletenessHist")(bad, BH)
    good = bad._replace(elections=((2, 1, (ent,), 0b11, ((), ())),))
    assert inv_mod.py_invariant("LeaderCompletenessHist")(good, BH)
    import jax.numpy as jnp
    for s_, want in ((bad, False), (good, True)):
        struct = {k: jnp.asarray(v)
                  for k, v in interp.to_struct(s_, BH).items()}
        assert bool(inv_mod.jnp_invariant(
            "LeaderCompletenessHist", BH)(struct)) is want


def test_liveness_composes_with_faithful_mode():
    """The liveness graph builds on interp.successors, so history state
    flows through: EventuallyLeader holds under WF(Next) on the faithful
    election universe and is stutter-refuted with no fairness, exactly as
    in parity mode."""
    from raft_tla_tpu.models import liveness
    ch = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                   max_log=0, max_msgs=2, history=True,
                                   max_elections=4),
                     spec="election", invariants=())
    g = liveness.explore_graph(ch)
    assert liveness.check(ch, "EventuallyLeader", wf=("Next",),
                          graph=g).holds
    refuted = liveness.check(ch, "EventuallyLeader", wf=(), graph=g)
    assert not refuted.holds and refuted.violation is not None


def test_symmetry_composes_with_faithful_mode():
    """History is Server-equivariant (log ranks carry no server ids;
    voterLog/eLeader/eVotes/eVLog permute), so SYMMETRY quotients faithful
    spaces too.  On the election universe faithful equals parity state for
    state, so the orbit count must be the known parity figure."""
    from raft_tla_tpu import engine
    bh = Bounds(n_servers=2, n_values=1, max_term=2, max_log=0, max_msgs=2,
                history=True, max_elections=4)
    cc = CheckConfig(bounds=bh, spec="election",
                     invariants=("NoTwoLeaders",), symmetry=("Server",),
                     chunk=256)
    r = refbfs.check(cc)
    assert (r.n_states, r.diameter) == (1514, 17)     # 3014 states / 2 = ...
    e = engine.check(cc)
    assert (e.n_states, e.diameter) == (1514, 17)
    assert e.coverage == r.coverage

    bf = Bounds(n_servers=2, n_values=1, max_term=2, max_log=1, max_msgs=2,
                history=True, max_elections=4)
    cf = CheckConfig(bounds=bf, spec="full",
                     invariants=("NoTwoLeaders", "ElectionSafetyHist"),
                     symmetry=("Server",), chunk=512)
    rf = refbfs.check(cf)
    assert (rf.n_states, rf.diameter) == (26723, 32)  # orbits of the 53398
    assert rf.violation is None
    ef = engine.check(cf)
    assert (ef.n_states, ef.diameter) == (26723, 32)


def test_the_pass_says_how_many_fields_the_orbit_scan_moves(tmp_path,
                                                            capsys):
    """``scan_moved_fields`` on the ``pass`` span, in the pass ledger and in
    the CLI's faithful-mode line: 0 in faithful mode under SYMMETRY Server
    (the history rides the key table, a sum taken once and packed record
    keys), 1 under a Value symmetry (``logVal``), nothing with no
    SYMMETRY (no scan)."""
    from raft_tla_tpu import check as cli
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    from test_cli import write_cfg

    tiny = ("--max-term", "2", "--max-log", "0", "--max-msgs", "1",
            "--spec", "election", "--engine", "ddd", "--chunk", "32",
            "--cpu")
    cfg = write_cfg(tmp_path / "h.cfg", extra="SYMMETRY Server\n")
    assert cli.main([cfg, "--faithful", *tiny]) == cli.EXIT_OK
    said = capsys.readouterr().out
    assert "elections peak 1 of 6 slots; the orbit scan moves 0 field(s) " \
           "an image." in said
    cfg = write_cfg(tmp_path / "p.cfg")
    assert cli.main([cfg, "--faithful", *tiny]) == cli.EXIT_OK
    assert "elections peak 1 of 6 slots." in capsys.readouterr().out

    cc = CheckConfig(bounds=Bounds(n_servers=2, n_values=2, max_term=2,
                                   max_log=1, max_msgs=1),
                     spec="election", invariants=("NoTwoLeaders",),
                     symmetry=("Server", "Value"), chunk=32)
    rec = DDDEngine(cc, DDDCapacities(block=1 << 10, table=1 << 12,
                                      seg_rows=1 << 9, levels=64)
                    ).check().level_log
    assert rec["scan_moved_fields"] == 1 and "elections_peak" not in rec


def test_device_engine_faithful_parity():
    """The device engine runs faithful mode too: its HBM store rows carry
    the history fields (``ddd``'s bit-packed rows:
    tests/test_ddd_engine.py::test_faithful_mode_parity)."""
    from raft_tla_tpu.device_engine import Capacities, DeviceEngine
    cc = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                   max_log=1, max_msgs=2, history=True,
                                   max_elections=4),
                     spec="full",
                     invariants=("NoTwoLeaders", "ElectionSafetyHist",
                                 "AllLogsPrefixClosed"), chunk=512)
    ref = refbfs.check(cc)
    assert (ref.n_states, ref.diameter) == (53398, 32)
    dev = DeviceEngine(cc, Capacities(n_states=1 << 16, levels=64)).check()
    assert (dev.n_states, dev.diameter) == (ref.n_states, ref.diameter)
    assert dev.levels == ref.levels and dev.coverage == ref.coverage


def test_bitpack_roundtrip_history_fields():
    """Bit-packed rows preserve every faithful-mode field exactly,
    including the 32-bit allLogs words (sign bit included)."""
    from raft_tla_tpu.ops import bitpack
    rng = np.random.default_rng(5)
    sch = bitpack.BitSchema(BH)
    vecs = np.stack([
        interp.to_vec(random_pystate(rng, BH), BH) for _ in range(64)])
    # force sign-bit patterns into the allLogs words
    lay = st.Layout.of(BH)
    off = sum(int(np.prod(lay.shapes[f])) for f in st.STATE_FIELDS)
    vecs[0, off] = -2147483648
    vecs[1, off] = -1
    packed = sch.pack(vecs, np)
    assert packed.shape[-1] == sch.P < vecs.shape[-1]
    assert (sch.unpack(packed, np) == vecs).all()
