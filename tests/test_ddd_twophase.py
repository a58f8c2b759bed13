"""The ddd engine on a second spec: Lamport's two-phase commit, taken from the
model registry (``--spec twophase --engine ddd``), held to the benchmark's
plain reference (``benchmark/reference/twophase.py``: the TLA+ text
transcribed by hand, nothing of the program) at n = 2..5 resource managers.

One engine per n for the whole module (``_engine``), with a block smaller
than most levels, so every level loop here crosses block boundaries; the one
extra engine is the large-block twin that shows a boundary changes nothing.
The states cross through the benchmark family's own codec
(``benchmark/families/twophase_ddd.py``), which the cell ``twophase10.passes``
runs on the chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import twophase_ddd as fam
from benchmark.reference import twophase as ref
from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine

NS = (2, 3, 4, 5)
TOTALS = {2: 56, 3: 288, 4: 1568, 5: 8832}
CHUNK, BLOCK = 32, 64
FAMILIES = {"TMRcvPrepared", "TMCommit", "TMAbort", "RMPrepare",
            "RMChooseToAbort", "RMRcvCommitMsg", "RMRcvAbortMsg"}


def toy_cfg(n: int) -> dict:
    rms = ", ".join(f"r{k + 1}" for k in range(n))
    return {"name": f"toy_twophase{n}", "family": "twophase_ddd",
            "bounds": {"n_rms": n}, "symmetry": [], "chunk": CHUNK,
            "invariants": ["TPTypeOK", "TCConsistent"],
            "cfg_text": (f"CONSTANT RM = {{{rms}}}\n"
                         "INVARIANTS TPTypeOK TCConsistent\n"
                         "SPECIFICATION TPSpec\n")}


def caps(block: int = BLOCK) -> DDDCapacities:
    return DDDCapacities(block=block, table=1 << 12, seg_rows=1 << 11,
                         levels=64)


@functools.lru_cache(maxsize=None)
def _engine(n: int) -> DDDEngine:
    return DDDEngine(fam.check_config(toy_cfg(n)), caps())


@functools.lru_cache(maxsize=None)
def _levels(n: int) -> tuple:
    """The reference's BFS, level by level: ``[states of level k]``."""
    init = ref.init_state(n)
    seen, levels = {init}, [[init]]
    while True:
        nxt = []
        for s in levels[-1]:
            for _a, t in ref.successors(s):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        if not nxt:
            return tuple(levels)
        levels.append(nxt)


@pytest.mark.parametrize("n", NS)
def test_check_from_tpinit_counts_what_the_reference_counts(n):
    eng = _engine(n)
    cum, _last, viol, trans = ref.bfs_levels(n)
    assert cum[-1] == TOTALS[n] and viol == 0
    got = eng.check()
    assert got.violation is None and got.complete is True
    assert list(np.cumsum(got.levels)) == cum           # every level
    assert got.n_states == TOTALS[n]
    assert got.diameter == 3 * n + 1 == len(cum) - 1
    assert got.n_transitions == trans
    # from four RMs on, levels wider than a block: expanded block after block
    assert (max(got.levels) > BLOCK) == (n >= 4)
    # coverage by the spec's seven action families; every state but Init
    # was first found by one of them
    assert set(got.coverage) == FAMILIES
    assert sum(got.coverage.values()) == TOTALS[n] - 1


@pytest.mark.parametrize("n", NS)
def test_the_compiled_segment_streams_the_references_successors(n):
    """Every state of every level through ``eng._segment``, a block at a
    time behind an empty filter: the stream, decoded, is the reference's
    successor set, states against states, steps that change nothing
    included (the spec enables them; they count as transitions)."""
    eng = _engine(n)
    for level in _levels(n):
        want, n_trans, _con = fam.successor_orbits(list(level), toy_cfg(n))
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


def test_a_block_boundary_inside_a_level_changes_nothing():
    n = 4
    small = _engine(n).check()
    whole = DDDEngine(fam.check_config(toy_cfg(n)), caps(block=1 << 10))
    big = whole.check()
    assert max(big.levels) < 1 << 10 and max(small.levels) > BLOCK
    assert (small.levels, small.n_states, small.n_transitions,
            small.coverage) == (big.levels, big.n_states,
                                big.n_transitions, big.coverage)


@pytest.mark.parametrize("n", NS)
def test_the_planted_fault_is_named_with_tcconsistent(n):
    eng, cfg = _engine(n), toy_cfg(n)
    level = list(_levels(n)[n])
    plant = fam.planted_fault(cfg, level, seed=7 + n)
    assert fam.holds(plant["parent"], cfg) == []
    got = eng.check(init_override=fam.to_program(plant["parent"]))
    assert got.violation is not None
    assert got.violation.invariant == "TCConsistent"
    named = fam.from_program(got.violation.state)
    assert "TCConsistent" in plant["violators"][named]
    # the trace runs from the planted parent to the state named
    assert fam.from_program(got.violation.trace[0][1]) == plant["parent"]
    assert got.violation.trace[-1][0].startswith("RMRcvCommitMsg(")


def test_the_schema_derived_row_packs_every_reachable_state_one_to_one():
    n = 4
    eng = _engine(n)
    from raft_tla_tpu.frontend import twophase as ptp
    states = [s for level in _levels(n) for s in level]
    assert len(states) == TOTALS[n]
    vecs = np.stack([ptp.to_vec(fam.to_program(s), eng.bounds)
                     for s in states])
    # 2 bits an RM state and the TM's, one a flag: 4n + 4 bits, one word
    assert (eng.schema.W, eng.schema.total_bits, eng.schema.P) \
        == (3 * n + 3, 4 * n + 4, 1)
    packed = eng.schema.pack(vecs, np)
    assert packed.shape == (len(states), 1)
    assert len({int(p) for p in packed[:, 0]}) == len(states)
    assert np.array_equal(eng.schema.unpack(packed, np), vecs)
    assert np.array_equal(
        np.asarray(eng.schema.unpack(jnp.asarray(packed), jnp)), vecs)
    assert fam.decode_rows(eng, packed) == states


def test_the_published_shape_is_two_words_and_fifty_two_lanes():
    from raft_tla_tpu.config import Bounds
    from raft_tla_tpu.frontend.registry import resolve_model
    model, b = resolve_model("twophase"), Bounds(n_servers=10, n_values=1)
    schema = model.bit_schema(b)
    assert (schema.W, schema.total_bits, schema.P) == (33, 44, 2)
    assert len(model.action_table(b)) == 52


def test_a_field_that_may_be_negative_has_no_packed_row():
    from raft_tla_tpu.config import Bounds
    from raft_tla_tpu.frontend.schema import Field, Schema
    from raft_tla_tpu.ops.bitpack import BitSchema
    bad = Schema("bad", (Field("x", ("n",), lo=-1, hi=2),))
    with pytest.raises(ValueError, match="field 'x' declares"):
        BitSchema.of_schema(bad, Bounds(n_servers=2))


def test_the_routed_step_and_the_mesh_engine_refuse_the_spec_by_name():
    from raft_tla_tpu.parallel.ddd_shard_engine import DDDShardEngine
    config = fam.check_config(toy_cfg(2))
    with pytest.raises(ValueError, match="routed step .* is Raft's; spec "
                                         "'twophase'"):
        DDDEngine(config, DDDCapacities(block=BLOCK, table=1 << 12,
                                        seg_rows=1 << 11, route_rows=256))
    with pytest.raises(ValueError, match="ddd-shard engine does not run "
                                         "spec 'twophase'"):
        DDDShardEngine(config)


@pytest.mark.parametrize("keep_levels", [False, True])
def test_frontier_retention_names_the_state_and_rebuilds_the_trace(
        keep_levels):
    """Frontier retention keeps no trace links: with the level files kept
    the counterexample is rebuilt by a backward search through the spec's
    own step (``frontier_backtrace``), without them the state alone is
    named; both as full retention finds them."""
    import dataclasses
    config = dataclasses.replace(fam.check_config(toy_cfg(3)),
                                 invariants=("~any(rmState = 2)",))
    full = DDDEngine(config, caps()).check()
    got = DDDEngine(config, dataclasses.replace(
        caps(), retention="frontier", keep_levels=keep_levels)).check()
    assert got.violation.invariant == "~any(rmState = 2)"
    assert got.violation.state == full.violation.state
    assert got.n_states == full.n_states
    labels = [a for a, _s in full.violation.trace]
    assert labels[0] is None and labels[-2:] == ["TMCommit",
                                                 "RMRcvCommitMsg(r1)"]
    if keep_levels:
        assert got.violation.trace == full.violation.trace
    else:
        assert got.violation.trace == [(None, full.violation.state)]


# ---------------------------------------- Raft through the same registry

ACCEPTED = ("elect5", "flagship3", "full5", "elect5_mesh4", "repl3",
            "flagship3_m1")


@pytest.mark.parametrize("name", ACCEPTED)
def test_an_accepted_configurations_step_is_built_as_it_was(name,
                                                            monkeypatch):
    """The adapter's ``build_step`` is ``kernels.build_step`` with the
    arguments ``_build_segment`` gave it before the engine took its spec
    from the registry, the row is ``BitSchema(bounds)`` and the layout and
    action table are Raft's: the accepted cells' programs do not change
    (their StableHLO digests, parent against change, are in PERF.md)."""
    from benchmark.families import raft
    from benchmark.harness import manifest as mf
    from raft_tla_tpu.models import spec as S
    from raft_tla_tpu.ops import bitpack, kernels
    from raft_tla_tpu.ops import state as st
    cfg = mf.read_json("configs", name + ".json")
    config = raft.check_config(cfg)
    sig = kernels.step_signature(config.bounds, config.spec,
                                 config.invariants, config.symmetry, None)
    assert sig[:5] == (config.bounds, cfg["spec"], tuple(cfg["invariants"]),
                       tuple(cfg["symmetry"]), None)
    assert [k for k, _v in sig[5:]] == ["prescan", "devdedup"]
    if cfg.get("engine", "ddd") != "ddd":
        return      # the mesh engine builds its own segment, untouched
    calls, real = [], kernels.build_step
    monkeypatch.setattr(
        kernels, "build_step",
        lambda *a, **kw: calls.append((a, kw)) or real(*a, **kw))
    eng = DDDEngine(config, DDDCapacities(**cfg["engine_caps"]["ddd"]))
    assert calls == [((config.bounds, cfg["spec"], tuple(cfg["invariants"]),
                       tuple(cfg["symmetry"])),
                      {"view": None, "family_kernels": None})]
    plain = bitpack.BitSchema(config.bounds)
    assert type(eng.schema) is bitpack.BitSchema
    assert np.array_equal(eng.schema.bits, plain.bits) \
        and (eng.schema.W, eng.schema.P) == (plain.W, plain.P)
    assert eng.lay == st.Layout.of(config.bounds)
    assert eng.table == S.action_table(config.bounds, cfg["spec"])
