"""The ddd engine on a third spec: Lamport's single-decree Paxos, taken from
the model registry (``--spec paxos --engine ddd``), held to the benchmark's
plain reference (``benchmark/reference/paxos.py``: the TLA+ text transcribed
by hand, nothing of the program) at 3 acceptors, 2 values and ballots 0..1
(3,921 states, state for state) and 0..2 (185,369 states, level for level).

One engine per configuration for the whole module (``_engine``), with a block
smaller than most levels, so every level loop here crosses block boundaries.
The states cross through the benchmark family's own codec
(``benchmark/families/paxos_ddd.py``), which the cell ``paxos3b4.passes`` runs
on the chip.  ``ddd_engine.py`` itself was not edited for this spec: the
engine takes layout, action table, step, packed row, Init and row codec from
``registry.resolve_model``, as it does for TwoPhase.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import paxos_ddd as fam
from benchmark.reference import paxos as ref
from raft_tla_tpu import check as cli
from raft_tla_tpu import engine as host_engine
from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine

TOTALS = {1: (3_921, 17, 22_994), 2: (185_369, 25, 1_316_583)}
CHUNK, BLOCK = 32, 128
PAIRS = (("a1", "a2"), ("a1", "a3"), ("a2", "a3"))
FAMILIES = {"Phase1a", "Phase1b", "Phase2a", "Phase2b"}


def toy_cfg(max_ballot: int = 1, quorums=PAIRS, chunk: int = CHUNK) -> dict:
    sets = ", ".join("{" + ", ".join(q) + "}" for q in quorums)
    return {"name": f"toy_paxos_b{max_ballot}", "family": "paxos_ddd",
            "bounds": {"n_acceptors": 3, "n_values": 2,
                       "max_ballot": max_ballot},
            "quorums": [list(q) for q in quorums], "symmetry": [],
            "chunk": chunk, "invariants": ["TypeOK", "Consistency"],
            "cfg_text": ("CONSTANTS\n  Acceptor = {a1, a2, a3}\n"
                         "  Value = {v1, v2}\n"
                         f"  Quorum = {{{sets}}}\n  None = None\n"
                         "  Ballot <- MCBallot\nSPECIFICATION Spec\n"
                         "INVARIANTS TypeOK Consistency\n")}


def caps(block: int = BLOCK, **kw) -> DDDCapacities:
    return DDDCapacities(block=block, table=1 << 12, seg_rows=1 << 12,
                         levels=64, **kw)


@functools.lru_cache(maxsize=None)
def _engine(quorums=PAIRS) -> DDDEngine:
    return DDDEngine(fam.check_config(toy_cfg(1, quorums)), caps())


@functools.lru_cache(maxsize=None)
def _levels(quorums=PAIRS) -> tuple:
    """The reference's BFS at ballots 0..1, level by level."""
    m = fam.bounds(toy_cfg(1, quorums))
    init = ref.init_state(m)
    seen, levels = {init}, [[init]]
    while True:
        nxt = []
        for s in levels[-1]:
            for _a, t in ref.successors(s, m):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        if not nxt:
            return tuple(levels)
        levels.append(nxt)


def test_check_from_init_counts_what_the_reference_counts():
    eng = _engine()
    cum, _last, viol, trans = ref.bfs_levels(fam.bounds(toy_cfg(1)))
    assert (cum[-1], len(cum), trans) == TOTALS[1] and viol == 0
    got = eng.check()
    assert got.violation is None and got.complete is True
    assert list(np.cumsum(got.levels)) == cum           # every level
    assert (got.n_states, got.diameter + 1, got.n_transitions) == TOTALS[1]
    assert max(got.levels) > BLOCK          # expanded block after block
    # coverage by the spec's four action families; every state but Init
    # was first found by one of them
    assert set(got.coverage) == FAMILIES
    assert sum(got.coverage.values()) == TOTALS[1][0] - 1


def test_the_ddd_engine_and_the_host_engine_agree():
    config = fam.check_config(toy_cfg(1))
    ddd, host = _engine().check(), host_engine.check(config)
    assert (ddd.levels, ddd.n_states, ddd.n_transitions, ddd.diameter) \
        == (host.levels, host.n_states, host.n_transitions, host.diameter)
    assert sum(ddd.coverage.values()) == sum(host.coverage.values())


def test_ballots_0_to_2_level_for_level_through_the_cli(tmp_path, capsys):
    """The acceptance run: ``check --spec paxos --engine ddd`` on the
    configuration's own cfg text, the normal path, at ballots 0..2: the
    plain reference's 185,369 states, 25 levels, 1,316,583 transitions."""
    cum, _last, viol, trans = ref.bfs_levels(fam.bounds(toy_cfg(2)))
    assert (cum[-1], len(cum), trans) == TOTALS[2] and viol == 0
    # the engine itself, every level against the reference's
    eng = DDDEngine(fam.check_config(toy_cfg(2, chunk=1024)),
                    DDDCapacities(block=1 << 14, table=1 << 16,
                                  seg_rows=1 << 16, levels=64))
    got = eng.check()
    assert got.violation is None and got.complete is True
    assert list(np.cumsum(got.levels)) == cum
    assert got.n_transitions == trans
    # ... and the same through the command line
    from benchmark.harness import manifest as mf
    cfg = tmp_path / "MCPaxos.cfg"
    cfg.write_text(mf.read_json("configs", "paxos3b4.json")["cfg_text"])
    rc = cli.main([str(cfg), "--spec", "paxos", "--engine", "ddd",
                   "--max-term", "2", "--cpu", "--chunk", "1024"])
    said = capsys.readouterr().out
    assert rc == 0
    assert "185369 distinct states found, diameter 24, 1316583 " \
        "transitions" in said
    assert "No error has been found" in said


def test_the_compiled_segment_streams_the_references_successors():
    """Every state of every level through ``eng._segment``, a block at a
    time behind an empty filter: the stream, decoded, is the reference's
    successor set, states against states, steps that change nothing
    included (the spec enables them; they count as transitions)."""
    eng, cfg = _engine(), toy_cfg(1)
    total = 0
    for level in _levels():
        want, n_trans, _con = fam.successor_orbits(list(level), cfg)
        got, got_trans = set(), 0
        for at in range(0, len(level), BLOCK):
            part = list(level[at:at + BLOCK])
            rows, con = fam.pack_rows(eng, part)
            brows = np.zeros((BLOCK, eng.schema.P), np.int32)
            bcon = np.zeros((BLOCK,), bool)
            brows[:len(part)], bcon[:len(part)] = rows, con
            _fc, bufs, stats = eng._segment(
                eng._init_filter(), eng._make_bufs(), jnp.asarray(brows),
                jnp.asarray(bcon), jnp.int32(-(-len(part) // CHUNK)),
                jnp.int32(len(part)))
            st_h, bufs_h = jax.device_get((stats, bufs))
            assert bool(st_h.done) and int(st_h.fail) == 0 \
                and int(st_h.viol_kind) == 0
            got_trans += int(st_h.n_valid)
            got.update(fam.decode_rows(
                eng, bufs_h.orows[:int(st_h.cursor)]))
        assert got == want
        assert got_trans == n_trans
        total += got_trans
    assert total == TOTALS[1][2]


def test_a_block_boundary_inside_a_level_changes_nothing():
    small = _engine().check()
    whole = DDDEngine(fam.check_config(toy_cfg(1)), caps(block=1 << 10))
    big = whole.check()
    assert max(big.levels) < 1 << 10 and max(small.levels) > BLOCK
    assert (small.levels, small.n_states, small.n_transitions,
            small.coverage) == (big.levels, big.n_states,
                                big.n_transitions, big.coverage)


@pytest.mark.parametrize("quorums", [
    (("a1", "a2"),),
    (("a1", "a2", "a3"), ("a1", "a2")),
], ids=["one_pair", "all_three_and_a_pair"])
def test_the_quorum_table_is_the_cfgs_not_the_majorities(quorums):
    """A ``Quorum`` that is not the majorities gives the reference's counts
    for that ``Quorum`` (fewer states: fewer sets enable ``Phase2a``), which
    a popcount shortcut would miss."""
    cfg = toy_cfg(1, quorums)
    cum, _last, viol, trans = ref.bfs_levels(fam.bounds(cfg))
    assert viol == 0 and cum[-1] < TOTALS[1][0]
    got = _engine(quorums).check()
    assert got.violation is None and got.complete is True
    assert list(np.cumsum(got.levels)) == cum
    assert got.n_transitions == trans
    rows = dict(_engine(quorums).bounds.constants)["Quorum"]
    assert rows == tuple(tuple(int(f"a{k + 1}" in q) for k in range(3))
                         for q in quorums)


@pytest.mark.parametrize("seed", [7, 2_147_483_659, 3_000_000_019])
def test_the_planted_fault_is_named_with_consistency(seed):
    eng, cfg = _engine(), toy_cfg(1)
    level = list(_levels()[6])
    plant = fam.planted_fault(cfg, level, seed=seed)
    assert fam.holds(plant["parent"], cfg) == []
    m = fam.bounds(cfg)
    assert len(ref.chosen(plant["parent"], m)) == 1
    got = eng.check(init_override=fam.to_program(plant["parent"]))
    assert got.violation is not None
    assert got.violation.invariant == "Consistency"
    named = fam.from_program(got.violation.state)
    assert "Consistency" in plant["violators"][named]
    assert len(ref.chosen(named, m)) == 2
    # the trace runs from the planted parent to the state named
    assert fam.from_program(got.violation.trace[0][1]) == plant["parent"]
    assert got.violation.trace[-1][0].startswith("Phase2b(")


def test_a_clean_state_is_not_flagged():
    """From reachable states of a middle level (no fault planted) the engine
    finds what the reference finds: nothing."""
    eng, cfg = _engine(), toy_cfg(1)
    for s in _levels()[9][::60]:
        assert fam.holds(s, cfg) == []
        got = eng.check(init_override=fam.to_program(s))
        assert got.violation is None and got.complete is True


def test_the_schema_derived_row_packs_every_reachable_state_one_to_one():
    eng = _engine()
    from raft_tla_tpu.frontend import paxos as ppx
    states = [s for level in _levels() for s in level]
    assert len(states) == TOTALS[1][0]
    vecs = np.stack([ppx.to_vec(fam.to_program(s), eng.bounds)
                     for s in states])
    # ballots 0..1: 2 + 3*2*5 + 4 + 12 = 48 flags, 9 fields of 2 bits
    assert (eng.schema.W, eng.schema.total_bits, eng.schema.P) \
        == (57, 66, 3)
    packed = eng.schema.pack(vecs, np)
    assert packed.shape == (len(states), 3)
    assert len({p.tobytes() for p in packed}) == len(states)
    assert np.array_equal(eng.schema.unpack(packed, np), vecs)
    assert np.array_equal(
        np.asarray(eng.schema.unpack(jnp.asarray(packed), jnp)), vecs)
    assert fam.decode_rows(eng, packed[::50]) == states[::50]


def _real_cfg() -> dict:
    from benchmark.harness import manifest as mf
    return mf.read_json("configs", "paxos3b4.json")


def test_a_row_wider_than_a_lane_tile_packs_to_the_same_bits():
    """At the benchmark's own bounds a row is 153 words, past one 128-lane
    tile, and ``BitSchema.pack`` goes row-wise (``_pack_rows``): the same
    bits as the column form on random rows and on reachable states, in
    NumPy and under jit, and back through ``unpack``; every narrower row
    (each accepted configuration's) still takes the column form."""
    from raft_tla_tpu.config import Bounds
    from raft_tla_tpu.frontend import paxos as ppx
    from raft_tla_tpu.ops import bitpack
    cfg = _real_cfg()
    config = fam.check_config(cfg)
    schema = bitpack.BitSchema.of_schema(ppx.SCHEMA, config.bounds)
    assert (schema.W, schema.P) == (153, 6) and schema.W > bitpack._LANE_TILE

    def column_form(sch, v, xp):
        tile, bitpack._LANE_TILE = bitpack._LANE_TILE, 1 << 30
        try:
            return sch.pack(v, xp)
        finally:
            bitpack._LANE_TILE = tile

    rng = np.random.default_rng(43)
    rand = np.stack([rng.integers(0, 1 << int(b), size=3000)
                     for b in schema.bits], -1).astype(np.int32)
    _cum, level, _viol, _trans = ref.bfs_levels(fam.bounds(cfg), (), 2000)
    reach = np.stack([ppx.to_vec(fam.to_program(s), config.bounds)
                      for s in level])
    for vecs in (rand, reach):
        want = column_form(schema, vecs, np)
        assert np.array_equal(schema.pack(vecs, np), want)
        assert np.array_equal(
            np.asarray(jax.jit(lambda v: schema.pack(v, jnp))(vecs)), want)
        assert np.array_equal(schema.unpack(want, np), vecs)
    # a Raft row with eleven straddling fields and 29-bit ones, forced
    # through the row form: the same bits again
    raft = bitpack.BitSchema(Bounds(n_servers=5, n_values=2, max_term=2,
                                    max_log=1, max_msgs=2))
    wide = np.stack([rng.integers(0, 1 << int(b), size=2000, dtype=np.int64)
                     for b in raft.bits], -1).astype(np.uint32) \
        .astype(np.int32)
    assert int(((raft.start % 32 + raft.bits) > 32).sum()) >= 5
    assert np.array_equal(raft._pack_rows(wide, np), raft.pack(wide, np))
    for name in ("elect5", "flagship3", "full5", "repl3", "twophase10"):
        from benchmark.harness import manifest as mf
        other = mf.read_json("configs", name + ".json")
        oc = mf.family(other).check_config(other)
        from raft_tla_tpu.frontend import resolve_model
        assert resolve_model(oc.spec).bit_schema(oc.bounds).W \
            <= bitpack._LANE_TILE


def test_the_benchmarks_own_bounds_level_for_level_to_level_8():
    """Ballots 0..3 on the engine here, through the wide row, stopped at the
    pin of level 8 (10,635 states): every level's count is the plain
    reference's, which re-derives them in this test."""
    import signal
    cfg = dict(_real_cfg(), chunk=256)
    cum = ref.bfs_levels(fam.bounds(cfg), tuple(cfg["invariants"]), 4096)[0]
    assert cum == cfg["level_pins"][:9]
    eng = DDDEngine(fam.check_config(cfg),
                    DDDCapacities(block=1 << 13, table=1 << 15,
                                  seg_rows=1 << 14, levels=64))
    assert (eng.A, eng.lay.width, eng.schema.P) == (48, 153, 6)

    def stop_at_level_8(rec):
        # by count: a level of several segments also reports from inside
        # itself, after each hand-over to the flush worker (PR 44)
        if rec["n_states"] >= cum[8]:
            signal.raise_signal(signal.SIGINT)

    got = eng.check(on_progress=stop_at_level_8)
    assert got.violation is None and got.complete is False
    assert list(np.cumsum(got.levels))[:9] == cum


def test_the_routed_step_and_the_mesh_engine_refuse_the_spec_by_name():
    from raft_tla_tpu.parallel.ddd_shard_engine import DDDShardEngine
    config = fam.check_config(toy_cfg(1))
    with pytest.raises(ValueError, match="routed step .* is Raft's; spec "
                                         "'paxos'"):
        DDDEngine(config, caps(route_rows=256))
    with pytest.raises(ValueError, match="ddd-shard engine does not run "
                                         "spec 'paxos'"):
        DDDShardEngine(config)


def test_a_snapshot_taken_under_one_quorum_table_refuses_another(tmp_path):
    """The constant table is part of the run's identity: a checkpoint
    written under the source's three pairs does not resume under one."""
    path = str(tmp_path / "snap")
    eng = _engine()
    import signal

    def stop_at_level_5(rec):
        if rec["level"] >= 5:
            signal.raise_signal(signal.SIGINT)

    stopped = eng.check(on_progress=stop_at_level_5, checkpoint=path,
                        checkpoint_every_s=float("inf"))
    assert stopped.complete is False and stopped.violation is None
    done = eng.check(resume=path)
    assert (done.n_states, done.complete) == (TOTALS[1][0], True)
    with pytest.raises(ValueError, match="digest|config|mismatch"):
        _engine((("a1", "a2"),)).check(resume=path)
