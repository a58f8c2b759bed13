"""frontend/paxos: the third bundled spec and the first with quorums, end to
end on the host engine.

Lamport's single-decree Paxos declared as frontend schema + IR (a constant
table for ``Quorum``, ``\\E Q \\in Quorum`` guards, message flags at computed
indices), held to the benchmark's plain reference
(``benchmark/reference/paxos.py``: the TLA+ text transcribed by hand, nothing
of the program): the compiled step state for state at ballots 0..1, the host
engine level for level at 0..2, the quorum table taken from the cfg as
written, the new IR nodes' interval transfers against brute force, and the
cfg parser's nested sets and ``<-``.
"""

import functools
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import paxos as ref
from raft_tla_tpu import check as cli
from raft_tla_tpu import engine
from raft_tla_tpu.analysis import intervals as iv
from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.frontend import expr as E
from raft_tla_tpu.frontend import paxos as px
from raft_tla_tpu.frontend import predicate, widthgen
from raft_tla_tpu.frontend.predicate import compile_predicate
from raft_tla_tpu.frontend.registry import PaxosModel, resolve_model
from raft_tla_tpu.frontend.schema import (Const, Field, Schema,
                                          const_envelope)
from raft_tla_tpu.serve.jobs import JobOptions, resolve_check_config
from raft_tla_tpu.utils import cfgparse

PAIRS = ({0, 1}, {0, 2}, {1, 2})
CFG = ("CONSTANTS\n"
       "  Acceptor = {a1, a2, a3}\n"
       "  Value = {v1, v2}\n"
       "  Quorum = {{a1, a2}, {a1, a3}, {a2, a3}}\n"
       "  None = None\n"
       "  Ballot <- MCBallot\n"
       "SPECIFICATION Spec\n"
       "INVARIANTS TypeOK Consistency\n")
# the plain reference's own totals (PR 43): states, levels, transitions
TOTALS = {1: (3_921, 17, 22_994), 2: (185_369, 25, 1_316_583)}


def _bounds(max_ballot, quorums=PAIRS, n=3, n_values=2):
    return Bounds(n_servers=n, n_values=n_values, max_term=max_ballot,
                  constants=(("Quorum", px.quorum_rows(quorums, n)),))


def _config(max_ballot, quorums=PAIRS, invariants=("TypeOK", "Consistency"),
            **kw):
    return CheckConfig(bounds=_bounds(max_ballot, quorums), spec="paxos",
                       invariants=invariants, chunk=1024, **kw)


def _to_program(s):
    return px.PaxosState(s.maxBal, s.maxVBal, s.maxVal, s.msgs)


def _to_reference(p):
    return ref.State(p.maxBal, p.maxVBal, p.maxVal, p.msgs)


@functools.lru_cache(maxsize=None)
def _ref_levels(max_ballot, quorums=PAIRS):
    """The reference's BFS, level by level: ``[states of level k]``."""
    m = ref.model(3, 2, max_ballot, quorums)
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
            return m, tuple(levels)
        levels.append(nxt)


# -- the compiled step against the plain reference ----------------------------

def test_the_published_shape_is_six_words_and_forty_eight_lanes():
    model, b = resolve_model("paxos"), _bounds(3)
    assert isinstance(model, PaxosModel) and "ddd" in model.engines
    schema = model.bit_schema(b)
    # 144 message flags and 9 small fields of 2-3 bits
    assert (schema.W, schema.total_bits, schema.P) == (153, 168, 6)
    shapes = model.layout(b).shapes
    assert sum(int(np.prod(shapes[f])) for f in shapes
               if f.startswith("msg")) == 144
    table = model.action_table(b)
    assert len(table) == 48
    by = {f: sum(a.family == f for a in table) for f in px.ALL_FAMILIES}
    assert by == {"Phase1a": 4, "Phase1b": 12, "Phase2a": 8, "Phase2b": 24}
    assert model.check_widths(b) == []


def test_a_state_crosses_the_codec_and_the_schema_layout_one_to_one():
    m, levels = _ref_levels(1)
    b = _bounds(1)
    lay = px.SCHEMA.layout(b)
    states = [s for level in levels for s in level]
    vecs = np.stack([px.to_vec(_to_program(s), b) for s in states])
    assert vecs.shape == (TOTALS[1][0], lay.width)
    assert len({v.tobytes() for v in vecs}) == len(states)
    for s, v in zip(states[::37], vecs[::37]):
        assert _to_reference(px.from_vec(v, b)) == s
        assert np.array_equal(lay.pack(lay.unpack(v, np), np), v)
    # Init as the schema declares it is Init as the codec packs it
    assert np.array_equal(lay.pack(lay.init_struct(), np),
                          px.to_vec(px.init_state(b), b))
    with pytest.raises(ValueError, match="has no flag"):
        px.to_vec(px.PaxosState((0,) * 3, (-1,) * 3, (None,) * 3,
                                frozenset({("1b", 0, 0, -1, 1)})), b)


def test_the_ir_compiled_step_is_the_reference_state_for_state():
    """Every one of the 3,921 states at ballots 0..1 through the compiled
    step: lane by lane the enabled actions, and the successor of each, are
    the reference's (a step that changes nothing included)."""
    m, levels = _ref_levels(1)
    b = _bounds(1)
    model = resolve_model("paxos")
    table = model.action_table(b)
    step = jax.jit(model.build_step(_config(1)))
    states = [s for level in levels for s in level]
    assert (len(states), len(levels)) == TOTALS[1][:2]
    vecs = np.stack([px.to_vec(_to_program(s), b) for s in states])
    out = jax.device_get(step(jnp.asarray(vecs)))
    # (a disabled lane still carries its writes: only enabled ones count)
    assert not out["overflow"].any() and out["inv_ok"][out["valid"]].all()
    n_trans = 0
    for k, s in enumerate(states):
        want = {(a, args): t for (a, args), t in ref.successors(s, m)}
        lanes = np.flatnonzero(out["valid"][k])
        got = {}
        for ln in lanes:
            inst = table[ln]
            args = {"Phase1a": (inst.b,), "Phase1b": (inst.a, inst.b),
                    "Phase2a": (inst.b, inst.v),
                    "Phase2b": (inst.a, inst.b, inst.v)}[inst.family]
            got[(inst.family, args)] = _to_reference(
                px.from_vec(out["svecs"][k, ln], b))
        assert got == want
        n_trans += len(lanes)
    assert n_trans == TOTALS[1][2]


@pytest.mark.parametrize("max_ballot", [1, 2])
def test_the_host_engine_counts_what_the_reference_counts(max_ballot):
    m = ref.model(3, 2, max_ballot)
    cum, _last, viol, trans = ref.bfs_levels(m)
    assert (cum[-1], len(cum), trans) == TOTALS[max_ballot] and viol == 0
    got = engine.check(_config(max_ballot))
    assert got.violation is None
    assert list(np.cumsum(got.levels)) == cum           # level for level
    assert (got.n_states, got.diameter + 1, got.n_transitions) \
        == TOTALS[max_ballot]
    assert set(got.coverage) == set(px.ALL_FAMILIES)
    assert sum(got.coverage.values()) == cum[-1] - 1


@pytest.mark.parametrize("quorums", [
    ({0, 1},),                         # one quorum: not the majorities
    ({0}, {1, 2}),                     # a one-acceptor quorum beside a pair
    ({0, 1}, {0, 2}, {1, 2}, {0, 1, 2}),       # every majority
], ids=["one_pair", "singleton_and_pair", "all_majorities"])
def test_the_quorum_table_is_the_cfgs_not_the_majorities(quorums):
    """The reference's counts for the ``Quorum`` the cfg writes, which a
    popcount shortcut ("more than half") would fail.  The second table's
    quorums do not even intersect, so ``Consistency`` is violated and has to
    be found; the fourth reaches what the source's three pairs reach."""
    m = ref.model(3, 2, 1, quorums)
    cum, _last, viol, trans = ref.bfs_levels(m, ("TypeOK",))
    assert viol == 0
    if quorums == ({0}, {1, 2}):
        bad = ref.bfs_levels(m)[2]
        assert bad > 0
        got = engine.check(_config(1, quorums))
        assert got.violation is not None
        assert got.violation.invariant == "Consistency"
        assert not ref.consistency(_to_reference(got.violation.state), m)
        return
    got = engine.check(_config(1, quorums))
    assert got.violation is None
    assert list(np.cumsum(got.levels)) == cum
    assert got.n_transitions == trans
    pairs = TOTALS[1]
    assert ((cum[-1], len(cum), trans) == pairs) \
        == (len(quorums) == 4)         # one pair alone reaches less


# -- the cfg: a set of sets, a substitution, the refusals ----------------------

def test_the_cfg_binds_the_quorum_table_and_records_the_substitution(
        tmp_path):
    tlc = cfgparse.parse_cfg(CFG)
    assert tlc.constants["Quorum"] == [["a1", "a2"], ["a1", "a3"],
                                       ["a2", "a3"]]
    assert tlc.substitutions == {"Ballot": "MCBallot"}
    assert tlc.line_of("constant", "Ballot") == 6
    assert "Ballot" not in tlc.constants
    config, props = resolve_check_config(
        tlc, JobOptions(spec="paxos", max_term=2, chunk=64))
    assert props == () and config.spec == "paxos"
    b = config.bounds
    assert (b.n_servers, b.n_values, b.max_term) == (3, 2, 2)
    assert dict(b.constants) == {"Quorum": ((1, 1, 0), (1, 0, 1), (0, 1, 1))}
    assert config.invariants == ("TypeOK", "Consistency")
    # the constants are part of a checkpoint's identity
    from raft_tla_tpu.utils import ckpt
    other = resolve_check_config(
        cfgparse.parse_cfg(CFG.replace("{a2, a3}}", "{a2, a3}, {a1}}")),
        JobOptions(spec="paxos", max_term=2, chunk=64))[0]
    assert ckpt.config_digest(config, b, ()) \
        != ckpt.config_digest(other, other.bounds, ())
    # MaxBallot, where the cfg binds it (the emitted twin does), wins
    capped = resolve_check_config(
        cfgparse.parse_cfg(CFG + "CONSTANT MaxBallot = 1\n"),
        JobOptions(spec="paxos", max_term=3, chunk=64))[0]
    assert capped.bounds.max_term == 1


@pytest.mark.parametrize("edit, said", [
    (("{a2, a3}}", "{a2, a9}}"), r"line 4: Quorum element \{a2, a9\}: a9 is "
                                 r"not in Acceptor = \{a1, a2, a3\}"),
    (("{a2, a3}}", "{}}"), "line 4: Quorum holds the empty set"),
    (("{{a1, a2}, {a1, a3}, {a2, a3}}", "{a1, a2}"),
     "line 4: Quorum has to be a nonempty set of sets over Acceptor"),
    (("{{a1, a2}, {a1, a3}, {a2, a3}}", "{}"),
     "line 4: Quorum has to be a nonempty set of sets"),
    (("  Quorum = {{a1, a2}, {a1, a3}, {a2, a3}}\n", ""),
     "Quorum has to be a nonempty set of sets"),
    (("{a2, a3}}", "{a2, a3}"), "line 4: unbalanced braces"),
    (("INVARIANTS", "PROPERTY V!Spec\nINVARIANTS"),
     "temporal properties are not supported for paxos"),
    (("Consistency", "Agreement"), "unknown paxos invariant 'Agreement'"),
    (("SPECIFICATION Spec", "SPECIFICATION TPSpec"),
     "paxos checks SPECIFICATION Spec only"),
    (("  Value = {v1, v2}\n", ""), "paxos needs CONSTANT Value"),
], ids=["stranger", "empty_quorum", "flat_set", "empty_set", "unbound",
        "unbalanced", "property", "invariant", "specification", "no_value"])
def test_a_malformed_cfg_is_refused_with_its_line(edit, said):
    with pytest.raises(ValueError, match=said):
        resolve_check_config(cfgparse.parse_cfg(CFG.replace(*edit)),
                             JobOptions(spec="paxos", max_term=1, chunk=64),
                             path="MCPaxos.cfg")


def test_nested_set_literals_parse_to_any_depth():
    assert cfgparse._parse_set("{}") == []
    assert cfgparse._parse_set("{ a , b }") == ["a", "b"]
    assert cfgparse._parse_set("{{a, b}, {c}}") == [["a", "b"], ["c"]]
    assert cfgparse._parse_set("{{}, {{a}}, b}") == [[], [["a"]], "b"]
    for bad in ("{a,, b}", "{{a}, }", "{a}}", "{{a}"):
        with pytest.raises(ValueError):
            cfgparse._parse_set(bad)
    tlc = cfgparse.parse_cfg("CONSTANTS\n  Server <- MCServer\n"
                             "  Value = {v1}\n  Nil = Nil\n")
    assert tlc.substitutions == {"Server": "MCServer"}
    assert tlc.constants == {"Value": ["v1"], "Nil": "Nil"}
    rows = cfgparse.set_of_subsets(
        cfgparse.parse_cfg("CONSTANT S = {x, y, z}\nCONSTANT Q = {{z}, "
                           "{y, x}}\n"), "Q", "S")
    assert rows == [(0, 0, 1), (1, 1, 0)]


# -- the CLI: the normal path, the TLC twin ------------------------------------

def test_the_cli_runs_the_spec_and_emits_the_tlc_twin(tmp_path, capsys):
    cfg = tmp_path / "MCPaxos.cfg"
    cfg.write_text(CFG)
    out_dir = tmp_path / "tlc"
    rc = cli.main([str(cfg), "--spec", "paxos", "--engine", "host",
                   "--max-term", "1", "--cpu", "--chunk", "256",
                   "--coverage", "--emit-tlc", str(out_dir)])
    said = capsys.readouterr().out
    assert rc == 0
    assert "3 acceptors, 2 values, ballots 0..1, 3 quorums" in said
    assert "3921 distinct states found, diameter 16, 22994 transitions" \
        in said
    assert "Phase2a: 320 new states" in said
    tla = (out_dir / "MCPaxos.tla").read_text()
    twin = (out_dir / "MCPaxos.cfg").read_text()
    assert "\\E Q \\in Quorum :" in tla and "Consistency ==" in tla
    assert "Quorum = {{a1, a2}, {a1, a3}, {a2, a3}}" in twin
    assert "MaxBallot = 1" in twin and "INVARIANT Consistency" in twin
    # the twin's cfg is this program's cfg too: the same model comes back
    back = resolve_check_config(cfgparse.parse_cfg(twin),
                                JobOptions(spec="paxos", max_term=3))[0]
    assert back.bounds == _bounds(1)
    with pytest.raises(ValueError, match="cannot emit invariant expression"):
        px.emit_tla(str(out_dir), _bounds(1), ("any(msg1a = 1)",))


def test_a_violation_renders_as_a_paxos_trace():
    got = engine.check(_config(1, invariants=("~any(msg2b = 1)",)))
    assert got.violation is not None
    labels = [a for a, _s in got.violation.trace]
    assert labels[0] is None and labels[-1].startswith("Phase2b(")
    assert [x.split("(")[0] for x in labels[1:]] \
        == ["Phase1a", "Phase1b", "Phase1b", "Phase2a", "Phase2b"]
    text = resolve_model("paxos").render_trace(got.violation, _bounds(1))
    assert '[type |-> "2b", acc |-> a' in text
    assert "/\\ maxVal = (a1 :> " in text and "mval |-> None" in text


def test_engines_that_assume_the_raft_row_refuse_the_spec(tmp_path):
    cfg = tmp_path / "MCPaxos.cfg"
    cfg.write_text(CFG)
    with pytest.raises(SystemExit):
        cli.main([str(cfg), "--spec", "paxos", "--engine", "ddd-shard",
                  "--cpu"])
    with pytest.raises(SystemExit):
        cli.main([str(cfg), "--spec", "paxos", "--simulate", "5", "--cpu",
                  "--engine", "host"])


# -- the invariants in the predicate language ----------------------------------

def test_consistency_is_the_references_chosen_over_the_quorum_table():
    m, levels = _ref_levels(1)
    b = _bounds(1)
    model = resolve_model("paxos")
    lay = px.SCHEMA.layout(b)
    ok = model.py_invariant("Consistency")
    rng = np.random.default_rng(5)
    states = [s for level in levels for s in level]
    for s in [states[k] for k in rng.choice(len(states), 40, False)]:
        # reachable states choose at most one value; plant votes to make two
        assert ok(_to_program(s), b) and ref.consistency(s, m)
        for q1, q2 in itertools.product(m.quorums, repeat=2):
            votes = {("2b", a, 0, 0) for a in q1} \
                | {("2b", a, 1, 1) for a in q2}
            t = s._replace(msgs=frozenset(
                {x for x in s.msgs if x[0] != "2b"} | votes))
            assert not ref.consistency(t, m)
            assert not ok(_to_program(t), b)
            # one acceptor short of the second quorum: still consistent
            short = t._replace(msgs=t.msgs - {("2b", min(q2), 1, 1)})
            assert ok(_to_program(short), b) == ref.consistency(short, m)
    struct = lay.unpack(px.to_vec(_to_program(states[-1]), b), np)
    assert model.py_invariant("TypeOK")(_to_program(states[-1]), b)
    bad = dict(struct, maxVal=np.asarray([3, 0, 0]))
    assert not model._predicate("TypeOK", b).ev(bad, np)


def test_the_predicate_language_reads_a_table_folds_an_axis_and_contracts():
    q = np.asarray([[1, 1, 0], [0, 1, 1]])
    x = np.arange(24).reshape(3, 4, 2) % 3
    st = {"x": x}
    consts = {"Q": q}

    def ev(text):
        return predicate.parse(text, ("x",), consts).ev(st, np)

    assert np.array_equal(ev("dot(Q, x)"), np.tensordot(q, x, 1))
    assert np.array_equal(ev("max(x, 0)"), x.max(0))
    assert np.array_equal(ev("min(x, -1)"), x.min(-1))
    assert np.array_equal(ev("count(x = 1, 1)"), (x == 1).sum(1))
    assert np.array_equal(ev("any(all(x >= 1, 2), 0)"),
                          (x >= 1).all(2).any(0))
    assert ev("count(x = 1)") == (x == 1).sum()
    assert np.array_equal(np.asarray(predicate.parse(
        "dot(Q, x)", ("x",), consts).ev({"x": jnp.asarray(x)}, jnp)),
        np.tensordot(q, x, 1))
    assert compile_predicate("all(Q >= 0)", ("x",), consts).reads \
        == frozenset()
    for text, said in (("any(x = 1, x)", "axis is an integer literal"),
                       ("dot(x = 1, x)", "dot needs an integer operand"),
                       ("dot(x)", "expected ','"),
                       ("all(R >= 0)", "unknown field 'R'")):
        with pytest.raises(ValueError, match=said):
            compile_predicate(text, ("x",), consts)


# -- the new IR nodes: concrete evaluation and interval transfers --------------

TOY = Schema("toy", (Field("m", ("n", 3), 0, 1),
                     Field("c", ("n",), 0, 4)),
             consts=(Const("T", ("*", "n"), 0, 1),))


def _brute(node, bounds, consts, params=None, n_samples=400, seed=0):
    """``node`` on random structs inside the declared envelope: every
    concrete value has to lie inside the interval the node abstracts to."""
    from raft_tla_tpu.frontend.schema import envelope
    env = envelope(TOY, bounds)
    lay = TOY.layout(bounds)
    rng = np.random.default_rng(seed)
    ictx = E.IvCtx(bounds, env, {}, {k: iv.Interval(lo, hi)
                                     for k, (lo, hi) in (params or {}).items()},
                   None, const_envelope(TOY, bounds))
    got = node.iv(ictx)
    for _ in range(n_samples):
        s = {f: rng.integers(env[f].lo, env[f].hi + 1, lay.shapes[f])
             for f in lay.shapes}
        ps = {k: int(rng.integers(lo, hi + 1))
              for k, (lo, hi) in (params or {}).items()}
        v = np.asarray(node.ev(E.Ctx(bounds, s, ps, np, consts)))
        assert got.lo <= v.min() and v.max() <= got.hi, (node, v, got)
    return got


NODES = {
    "sel_row": (E.Sel("m", (E.Param("i"), None)), (0, 1)),
    "sel_cell": (E.Sel("c", (E.Param("i"),)), (0, 4)),
    "iota": (E.Iota(E.Lit(5)), (0, 4)),
    "lift": (E.Bin("+", E.Lift(E.Sel("c", (None,))),
                   E.Sel("m", (None, None))), (0, 5)),
    "max": (E.Reduce("max", E.Sel("c", (None,))), (0, 4)),
    "min_axis": (E.Reduce("min", E.Sel("m", (None, None)), axis=1), (0, 1)),
    "any": (E.Reduce("any", E.Bin("==", E.Sel("m", (None, None)),
                                  E.Lit(1))), (0, 1)),
    "floordiv": (E.Bin("//", E.Sel("c", (None,)), E.Lit(2)), (0, 2)),
    "mod": (E.Bin("%", E.Bin("+", E.Sel("c", (None,)), E.Lit(3)),
                  E.Lit(3)), (0, 2)),
    "table": (E.ConstTab("T"), (0, 1)),
    "exists": (E.Exists("r", E.ConstTab("T"), E.Reduce("all", E.Bin(
        "or", E.Bin("==", E.Param("r"), E.Lit(0)),
        E.Bin(">=", E.Sel("c", (None,)), E.Lit(2))))), (0, 1)),
    "scope": (E.Scope("quorum", E.Reduce("max", E.Where(
        E.Bin("==", E.Sel("m", (None, E.Param("i"))), E.Lit(1)),
        E.Sel("c", (None,)), E.Lit(-1)))), (-1, 4)),
    "pair_index": (px._PAIR, None),
}


@pytest.mark.parametrize("name", sorted(NODES))
def test_a_new_nodes_interval_holds_every_concrete_value(name):
    node, want = NODES[name]
    b = Bounds(n_servers=3, n_values=2, max_term=3)
    consts = TOY.bind_consts(b, {"T": [[1, 0, 1], [0, 1, 1]]})
    if name == "pair_index":
        # Paxos' own computed index, on Paxos' own schema: provably inside
        # the pair axis, which is what check_schema_writes asks of it
        pb = _bounds(3)
        assert px.n_pairs(pb) == 9
        from raft_tla_tpu.frontend.schema import envelope
        ictx = E.IvCtx(pb, envelope(px.SCHEMA, pb), {},
                       {"a": iv.Interval(0, 2)})
        assert node.iv(ictx) == iv.Interval(0, 8)
        lay = px.SCHEMA.layout(pb)
        seen = set()
        for vb, vl in itertools.product(range(5), range(3)):
            s = lay.init_struct()
            s["maxVBal"], s["maxVal"] = np.full(3, vb), np.full(3, vl)
            seen.add(int(node.ev(E.Ctx(pb, s, {"a": 1}, np))))
        assert seen == set(range(9))
        return
    got = _brute(node, b, consts, {"i": (0, 2)})
    assert (got.lo, got.hi) == want


def test_the_division_transfer_refuses_what_it_cannot_bound():
    b = Bounds(n_servers=3)
    ictx = E.IvCtx(b, {"c": iv.Interval(-1, 4)}, {}, {})
    for op in ("//", "%"):
        with pytest.raises(ValueError, match="non-negative dividend"):
            E.Bin(op, E.Get("c"), E.Lit(2)).iv(ictx)
    with pytest.raises(ValueError, match="unknown reducer"):
        E.Reduce("sum", E.Lit(0))


def test_a_write_outside_the_declaration_is_a_finding():
    b = Bounds(n_servers=3)
    wide = E.ActionDef("Wide", ("i",), E.Lit(True), (E.Branch(updates=(
        E.SetAt("c", (E.Param("i"),), E.Bin("+", E.Sel("c", (E.Param("i"),)),
                                            E.Lit(1))),
        E.SetAt("m", (E.Param("i"), E.Sel("c", (E.Param("i"),))),
                E.Lit(1)))),),
        param_iv=(("i", lambda bb: iv.Interval(0, bb.n_servers - 1)),))
    rules = [f.code for f in widthgen.check_schema_writes(TOY, (wide,), b)]
    assert rules == ["schema-write-range", "schema-index-range"]
    assert widthgen.check_schema_writes(px.SCHEMA, px.ACTIONS,
                                        _bounds(3)) == []


def test_set_at_writes_one_cell_at_any_rank():
    from raft_tla_tpu.frontend.actions import _set_at
    a = jnp.zeros((3, 4, 2), jnp.int32)
    got = np.asarray(jax.jit(_set_at)(a, [jnp.int32(2), jnp.int32(1),
                                          jnp.int32(0)], 7))
    want = np.zeros((3, 4, 2), np.int32)
    want[2, 1, 0] = 7
    assert np.array_equal(got, want)
    with pytest.raises(ValueError, match="2 indices for 3 axes"):
        _set_at(a, [0, 1], 1)
    s = {"m": np.arange(9).reshape(3, 3) % 2, "c": np.arange(3)}
    ctx = E.Ctx(Bounds(n_servers=3), s, {"i": 1}, np)
    assert np.array_equal(E.Sel("m", (None, E.Param("i"))).ev(ctx),
                          s["m"][:, 1])
    with pytest.raises(ValueError, match="1 indices for 2 axes"):
        E.Sel("m", (None,)).ev(ctx)


def test_a_constant_table_is_held_to_its_declaration():
    b = Bounds(n_servers=3)
    assert TOY.bind_consts(b, {"T": [[1, 0, 1]]})["T"].shape == (1, 3)
    for values, said in (({}, "constant 'T' is not bound"),
                         ({"T": [[1, 0]]}, r"has shape \(1, 2\)"),
                         ({"T": [[1, 0, 2]]}, r"holds 0..2, declared"),
                         ({"T": [[1, 0, 1]], "U": [1]},
                          "declares no constant 'U'"),
                         ({"T": []}, "has shape")):
        with pytest.raises(ValueError, match=said):
            TOY.bind_consts(b, values)
    with pytest.raises(ValueError, match="duplicate field 'c'"):
        Schema("bad", (Field("c"),), consts=(Const("c"),))


def test_the_quorum_guard_lowers_under_its_scope_inside_expand():
    step = jax.jit(resolve_model("paxos").build_step(_config(1)))
    lay = px.SCHEMA.layout(_bounds(1))
    text = step.lower(jax.ShapeDtypeStruct((8, lay.width), jnp.int32)) \
        .as_text(debug_info=True)
    import re
    paths = set(re.findall(r'"(jit\(step\)/[^"]*)"', text))
    scoped = [p for p in paths if "quorum" in p]
    assert scoped and all("/expand/" in p for p in scoped)
    from benchmark.harness import quorumred
    assert all(quorumred.in_scope(p) for p in scoped)
    assert not any(quorumred.in_scope(p) for p in paths - set(scoped))


def test_the_spec_is_listed_beside_the_others():
    from raft_tla_tpu.frontend.registry import known_specs
    assert "paxos" in known_specs()
    with pytest.raises(ValueError, match="did you mean: paxos"):
        resolve_model("paxo")
    assert os.path.isfile(os.path.join(
        os.path.dirname(px.__file__), "paxos.py"))
