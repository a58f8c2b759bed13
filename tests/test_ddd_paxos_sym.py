"""``--spec paxos`` under its model's SYMMETRY (``SYMMETRY Acceptor Value``):
the ``ddd`` engine and the host engine count orbits, level for level what the
benchmark's plain reference counts by sorting (``benchmark/reference/
paxos_sym.py``: nothing of the program, no fingerprint) — 443 orbits of 3,921
states at three acceptors and ballots 0..1, 17,153 of 185,369 at 0..2, 5,811 of
701,505 at five acceptors and ballots 0..1 (|G| = 240) — and with SYMMETRY off
the same runs count states.  The front refuses by name what it cannot reduce
soundly; a planted ``Consistency`` violation is found and its trace is a
concrete behaviour of ``benchmark/reference/paxos.py``.
"""

import functools
import itertools
import json
import signal

import numpy as np
import pytest

from benchmark.families import paxos_ddd, paxos_sym as fam
from benchmark.reference import paxos as ref
from benchmark.reference import paxos_sym as sref
from raft_tla_tpu import check as cli
from raft_tla_tpu import engine as host_engine
from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
from raft_tla_tpu.frontend import registry
from raft_tla_tpu.serve.jobs import JobOptions, resolve_check_config
from raft_tla_tpu.utils import cfgparse

# ISSUE 47's table (a separate transcription): (states unreduced, orbits,
# levels, transitions out of orbits) by (acceptors, max ballot)
TABLE = {(3, 1): (3_921, 443, 17, 2_577),
         (3, 2): (185_369, 17_153, 25, 121_880),
         (5, 1): (701_505, 5_811, 25, 49_300)}
BOTH = ["Acceptor", "Value"]


def cfg_text(n: int, quorums=None, symmetry="Acceptor Value") -> str:
    accs = [f"a{k + 1}" for k in range(n)]
    qs = quorums or list(itertools.combinations(accs, n // 2 + 1))
    sets = ", ".join("{" + ", ".join(q) + "}" for q in qs)
    return ("CONSTANTS\n  Acceptor = {" + ", ".join(accs) + "}\n"
            "  Value = {v1, v2}\n"
            f"  Quorum = {{{sets}}}\n  None = None\n"
            "  Ballot <- MCBallot\nSPECIFICATION Spec\n"
            "INVARIANTS TypeOK Consistency\n"
            + (f"SYMMETRY {symmetry}\n" if symmetry else ""))


def toy_cfg(n: int, max_ballot: int, chunk: int, symmetry=True) -> dict:
    accs = [f"a{k + 1}" for k in range(n)]
    return {"name": f"toy_paxos{n}_b{max_ballot}",
            "family": "paxos_sym" if symmetry else "paxos_ddd",
            "bounds": {"n_acceptors": n, "n_values": 2,
                       "max_ballot": max_ballot},
            "quorums": [list(q) for q in
                        itertools.combinations(accs, n // 2 + 1)],
            "symmetry": BOTH if symmetry else [], "chunk": chunk,
            "invariants": ["TypeOK", "Consistency"],
            "cfg_text": cfg_text(n, symmetry="Acceptor Value"
                                 if symmetry else None)}


@functools.lru_cache(maxsize=None)
def _engine(n: int, max_ballot: int, symmetry: bool = True) -> DDDEngine:
    chunk = 32 if (n, max_ballot) == (3, 1) else 256
    family = fam if symmetry else paxos_ddd
    return DDDEngine(
        family.check_config(toy_cfg(n, max_ballot, chunk, symmetry)),
        DDDCapacities(block=1 << 12, table=1 << 15, seg_rows=1 << 15,
                      levels=64))


@functools.lru_cache(maxsize=None)
def _reference(n: int, max_ballot: int) -> tuple:
    cum, _last, viol, trans = sref.bfs_orbit_levels(
        ref.model(n, 2, max_ballot))
    assert viol == 0
    return tuple(cum), trans


def _resolve(text: str, **opts):
    return resolve_check_config(cfgparse.parse_cfg(text),
                                JobOptions(spec="paxos", **opts))


# ------------------------------------------------------ counts, both engines

@pytest.mark.parametrize("n, max_ballot", sorted(TABLE))
def test_the_ddd_engine_counts_the_references_orbits_at_every_level(
        n, max_ballot):
    _states, orbits, levels, trans = TABLE[(n, max_ballot)]
    cum, ref_trans = _reference(n, max_ballot)
    assert (cum[-1], len(cum), ref_trans) == (orbits, levels, trans)
    got = _engine(n, max_ballot).check()
    assert got.violation is None and got.complete is True
    assert tuple(np.cumsum(got.levels)) == cum
    assert (got.n_states, got.diameter + 1, got.n_transitions) \
        == (orbits, levels, trans)
    assert sum(got.coverage.values()) == orbits - 1


@pytest.mark.parametrize("n, max_ballot", sorted(TABLE))
def test_the_host_engine_counts_the_same_orbits(n, max_ballot):
    cum, trans = _reference(n, max_ballot)
    got = host_engine.check(_engine(n, max_ballot).config)
    assert got.violation is None and got.complete is True
    assert tuple(np.cumsum(got.levels)) == cum
    assert got.n_transitions == trans


def test_with_symmetry_off_the_same_run_counts_states():
    states, _orbits, levels, _trans = TABLE[(3, 1)]
    got = _engine(3, 1, symmetry=False).check()
    assert (got.n_states, len(got.levels), got.n_transitions) \
        == (states, levels, 22_994)
    assert tuple(np.cumsum(got.levels)) \
        == tuple(ref.bfs_levels(ref.model(3, 2, 1))[0])


def test_with_symmetry_off_paxos3b4s_pins_hold():
    """``paxos3b4``'s own configuration, as the accepted cell runs it (no
    SYMMETRY), to the pin of level 8: the step a schema that now declares
    sorts compiles for a run that names none counts what it counted."""
    from benchmark.harness import manifest as mf
    cfg = dict(mf.read_json("configs", "paxos3b4.json"), chunk=256)
    config = paxos_ddd.check_config(cfg)
    assert config.symmetry == ()
    eng = DDDEngine(config, DDDCapacities(block=1 << 13, table=1 << 15,
                                          seg_rows=1 << 14, levels=64))
    pins = cfg["level_pins"]

    def stop_at_level_8(rec):
        if rec["n_states"] >= pins[8]:
            signal.raise_signal(signal.SIGINT)

    got = eng.check(on_progress=stop_at_level_8)
    assert got.violation is None and got.complete is False
    assert list(np.cumsum(got.levels))[:9] == pins[:9]


def test_one_sort_alone_reduces_by_its_own_group():
    """``SYMMETRY Value`` alone halves (nearly) what ``SYMMETRY Acceptor``
    alone cuts to a sixth: the counts lie between the states and the orbits
    of the whole group, and a brute-force orbit count of the reference's
    states says which."""
    m = ref.model(3, 2, 1)
    states = _all_states(m)
    ident_v, ident_a = (0, 1), (0, 1, 2)
    for sorts, images in (
            (("Value",), [(ident_a, s)
                          for s in itertools.permutations(range(2))]),
            (("Acceptor",), [(p, ident_v)
                             for p in itertools.permutations(range(3))])):
        orbits = {min(repr(sref._order(sref.permute(s, p, q)))
                      for p, q in images) for s in states}
        config, _props = _resolve(cfg_text(3, symmetry=" ".join(sorts)),
                                  max_term=1, chunk=64)
        assert config.symmetry == sorts
        got = host_engine.check(config)
        assert got.n_states == len(orbits)
        assert TABLE[(3, 1)][1] < got.n_states < TABLE[(3, 1)][0]


@functools.lru_cache(maxsize=None)
def _all_states(m) -> tuple:
    init = ref.init_state(m)
    seen, frontier = {init}, [init]
    while frontier:
        nxt = []
        for s in frontier:
            for _a, t in ref.successors(s, m):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    return tuple(seen)


# ---------------------------------------------------------------- the front

def test_the_stanza_maps_to_the_schemas_sorts_in_its_order():
    config, props = _resolve(cfg_text(3, symmetry="Value Acceptor"),
                             max_term=1)
    assert config.symmetry == ("Acceptor", "Value") and props == ()
    assert _resolve(cfg_text(3, symmetry=None), max_term=1)[0].symmetry == ()
    # --symmetry: every sort the schema declares
    assert _resolve(cfg_text(3, symmetry=None), max_term=1,
                    symmetry=True)[0].symmetry == ("Acceptor", "Value")
    # the emitted twin's own name reads back
    assert _resolve(cfg_text(3, symmetry="SymAcceptorValue"),
                    max_term=1)[0].symmetry == ("Acceptor", "Value")
    assert _resolve(cfg_text(3, symmetry="SymValue"),
                    max_term=1)[0].symmetry == ("Value",)
    model = registry.resolve_model("paxos")
    assert model.sorts == ("Acceptor", "Value")
    assert registry.resolve_model("twophase").sorts == ()
    assert model.group_order(config) == 12
    assert model.group_order(_resolve(cfg_text(5), max_term=1)[0]) == 240
    assert registry.resolve_model("election").group_order(
        registry.CheckConfig(symmetry=("Server",))) == 6


def test_a_quorum_table_that_is_not_invariant_is_refused_by_name():
    lopsided = [("a1", "a2"), ("a1", "a3")]
    with pytest.raises(ValueError, match="SYMMETRY Acceptor is unsound "
                       "here: the constant Quorum is not invariant under "
                       "the permutations of Acceptor"):
        _resolve(cfg_text(3, lopsided, symmetry="Acceptor"), max_term=1)
    with pytest.raises(ValueError, match="SYMMETRY Acceptor is unsound"):
        _resolve(cfg_text(3, lopsided), max_term=1)
    # Value does not index the table: reducing by it alone is sound
    config, _ = _resolve(cfg_text(3, lopsided, symmetry="Value"), max_term=1)
    assert config.symmetry == ("Value",)
    assert _resolve(cfg_text(3, lopsided, symmetry=None),
                    max_term=1)[0].symmetry == ()
    # the plain reference refuses the same table
    with pytest.raises(ValueError, match="Quorum is not invariant"):
        sref.bfs_orbit_levels(ref.model(3, 2, 1, [{0, 1}, {0, 2}]))


@pytest.mark.parametrize("stanza, said", [
    ("Server", "SYMMETRY Server not supported: paxos declares the symmetric "
               "sorts Acceptor, Value"),
    ("Acceptor Ballot", "SYMMETRY Ballot not supported"),
    ("MCSymmetry", "SYMMETRY MCSymmetry not supported")])
def test_an_unknown_sort_is_refused_by_name(stanza, said):
    with pytest.raises(ValueError, match=said):
        _resolve(cfg_text(3, symmetry=stanza), max_term=1)


def test_view_faithful_mode_and_twophases_symmetry_stay_refused():
    with pytest.raises(ValueError, match="views are not supported for "
                                         "paxos"):
        _resolve(cfg_text(3), max_term=1, view="deadvotes")
    with pytest.raises(ValueError, match="faithful mode .* is "
                                         "Raft-specific"):
        _resolve(cfg_text(3), max_term=1, faithful=True)
    two = cfgparse.parse_cfg("CONSTANTS\n  RM = {r1, r2}\nSYMMETRY RM\n")
    with pytest.raises(ValueError, match="symmetry reduction is not "
                                         "supported for twophase"):
        resolve_check_config(two, JobOptions(spec="twophase"))


# ------------------------------------------------- the acceptance run (CLI)

def _returns_the_cli_env(monkeypatch, events):
    """``check.main`` writes ``--events`` / ``--trace`` into ``os.environ``
    for the engines it builds; in-process that outlives the test and turns
    tracing on for every later test of the worker (``run_end.compiles`` then
    differs between two arms that tests/test_serve_sched.py compares).
    Setting them through ``monkeypatch`` first gives the originals back."""
    monkeypatch.setenv("RAFT_TLA_EVENTS", str(events))
    monkeypatch.setenv("RAFT_TLA_TRACE", "1")


def test_five_acceptors_through_the_command_line(tmp_path, capsys,
                                                 monkeypatch):
    """``check --spec paxos --engine ddd`` on the configuration's own cfg
    text, the normal path, at ballots 0..1: the ``Symmetry:`` line, 5,811
    orbits in 25 levels, complete."""
    from benchmark.harness import manifest as mf
    text = mf.read_json("configs", "paxos5sym.json")["cfg_text"]
    assert text == cfg_text(5)
    cfg = tmp_path / "MCPaxos.cfg"
    cfg.write_text(text)
    events = tmp_path / "run.events"
    _returns_the_cli_env(monkeypatch, events)
    rc = cli.main([str(cfg), "--spec", "paxos", "--engine", "ddd",
                   "--max-term", "1", "--cpu", "--chunk", "256",
                   "--events", str(events), "--trace",
                   "--emit-tlc", str(tmp_path / "twin")])
    said = capsys.readouterr().out
    assert rc == 0
    assert "Universe: 5 acceptors, 2 values, ballots 0..1, 10 quorums" in said
    assert "Symmetry: Acceptor x Value permutations, |G| = 240 (counting " \
        "orbits)" in said
    assert "5811 distinct states found, diameter 24, 49300 transitions" \
        in said
    assert "No error has been found" in said
    # the twin carries the SYMMETRY, and its cfg reads back to the same run
    twin = (tmp_path / "twin" / "MCPaxos.tla").read_text()
    assert "EXTENDS Integers, TLC" in twin
    assert "SymAcceptorValue == Permutations(Acceptor) \\cup " \
        "Permutations(Value)" in twin
    twin_cfg = (tmp_path / "twin" / "MCPaxos.cfg").read_text()
    assert "SYMMETRY SymAcceptorValue" in twin_cfg
    config, _ = _resolve(twin_cfg)
    assert config.symmetry == ("Acceptor", "Value")
    assert config.bounds.max_term == 1
    # run_start names the group; every segment span carries group and
    # images = group x the lanes of its steps
    with open(events, encoding="utf-8") as f:
        evs = [json.loads(ln) for ln in f]
    (start,) = [e for e in evs if e["event"] == "run_start"]
    assert start["group"] == 240 and start["symmetry"] == BOTH
    segs = [e["args"] for e in evs
            if e["event"] == "span" and e["name"] == "segment"]
    assert segs and all(a["group"] == 240 for a in segs)
    assert all(a["images"] == 240 * a["lanes"] == 240 * a["steps"] * 256 * 36
               for a in segs)


def test_without_symmetry_the_twin_and_the_spans_say_so(tmp_path, capsys,
                                                        monkeypatch):
    cfg = tmp_path / "MCPaxos.cfg"
    cfg.write_text(cfg_text(3, symmetry=None))
    events = tmp_path / "run.events"
    _returns_the_cli_env(monkeypatch, events)
    rc = cli.main([str(cfg), "--spec", "paxos", "--engine", "ddd",
                   "--max-term", "1", "--cpu", "--chunk", "64",
                   "--events", str(events), "--trace",
                   "--emit-tlc", str(tmp_path / "twin")])
    said = capsys.readouterr().out
    assert rc == 0 and "Symmetry:" not in said
    assert "3921 distinct states found" in said
    twin = (tmp_path / "twin" / "MCPaxos.tla").read_text()
    assert "EXTENDS Integers\n" in twin and "Permutations" not in twin
    assert "SYMMETRY" not in (tmp_path / "twin" / "MCPaxos.cfg").read_text()
    with open(events, encoding="utf-8") as f:
        evs = [json.loads(ln) for ln in f]
    (start,) = [e for e in evs if e["event"] == "run_start"]
    assert start["group"] == 1 and "symmetry" not in start
    segs = [e["args"] for e in evs
            if e["event"] == "span" and e["name"] == "segment"]
    assert segs and all(a["group"] == 1 and a["images"] == a["lanes"]
                        for a in segs)


# ----------------------------------------------------- a violation's trace

def _label(action: str, args: tuple) -> str:
    """A reference step as the program's trace labels it."""
    acc, val = (lambda a: f"a{a + 1}"), (lambda v: f"v{v + 1}")
    return {"Phase1a": lambda b: f"Phase1a({b})",
            "Phase1b": lambda a, b: f"Phase1b({acc(a)}, {b})",
            "Phase2a": lambda b, v: f"Phase2a({b}, {val(v)})",
            "Phase2b": lambda a, b, v: f"Phase2b({acc(a)}, {b}, {val(v)})",
            }[action](*args)


def _replays(trace, m) -> bool:
    """Every step of ``trace`` (``[(label, reference state)]``) is an
    enabled step of the plain reference, under the label's own action and
    arguments, leading exactly to the next state listed."""
    for (_l0, s), (label, t) in zip(trace, trace[1:]):
        steps = {_label(a, args): nxt
                 for (a, args), nxt in ref.successors(s, m)}
        if steps.get(label) != t:
            return False
    return True


@pytest.mark.parametrize("seed", [11, 3_000_000_019])
def test_a_planted_violation_is_found_and_named_on_orbits(seed):
    eng, cfg = _engine(3, 1), toy_cfg(3, 1, 32)
    m = fam.bounds(cfg)
    _cum, level, _viol, _trans = sref.bfs_orbit_levels(m, (), 32)
    plant = fam.planted_fault(cfg, level, seed)
    parent = plant["parent"]
    assert fam.holds(parent, cfg) == [] and len(ref.chosen(parent, m)) == 1
    got = eng.check(init_override=fam.to_program(parent))
    v = got.violation
    assert v is not None and v.invariant == "Consistency"
    named = fam.from_program(v.state)
    assert len(ref.chosen(named, m)) == 2
    # judged on orbits, the state named is one of the planted violators,
    # and it is a successor of the parent itself, not of a renamed one
    assert "Consistency" in plant["violators"].get(plant["key"](named), [])
    trace = [(label, fam.from_program(s)) for label, s in v.trace]
    assert trace[0][1] == parent and trace[-1][1] == named
    assert trace[-1][0].startswith("Phase2b(") and _replays(trace, m)


def test_a_trace_under_symmetry_is_a_concrete_behaviour():
    """An invariant reachable states break (no "2b" message is ever sent):
    the engine stores the member of an orbit it found first and links it to
    the stored member it is a successor of, so the counterexample replays
    step by step through the plain reference from Init, though every
    state on it stands for up to twelve."""
    import dataclasses
    base = _engine(3, 1)
    config = dataclasses.replace(base.config,
                                 invariants=("~any(msg2b = 1)",))
    m = fam.bounds(toy_cfg(3, 1, 32))
    for got in (DDDEngine(config, base.caps).check(),
                host_engine.check(config)):
        v = got.violation
        assert v is not None and v.invariant == "~any(msg2b = 1)"
        trace = [(label, fam.from_program(s)) for label, s in v.trace]
        assert trace[0][1] == ref.init_state(m)
        assert len(trace) == 6      # 1a, 1b, 1b, 2a, 2b: the shortest way
        assert [lb.split("(")[0] for lb, _s in trace[1:]] \
            == ["Phase1a", "Phase1b", "Phase1b", "Phase2a", "Phase2b"]
        assert _replays(trace, m)
        assert any(x[0] == "2b" for x in trace[-1][1].msgs)


def test_the_trace_labels_are_the_programs():
    from raft_tla_tpu.frontend.paxos import PaxosInstance
    assert PaxosInstance("Phase2b", a=2, b=1, v=0).label() \
        == _label("Phase2b", (2, 1, 0)) == "Phase2b(a3, 1, v1)"
    assert PaxosInstance("Phase1a", b=1).label() \
        == _label("Phase1a", (1,)) == "Phase1a(1)"
    assert PaxosInstance("Phase1b", a=0, b=1).label() \
        == _label("Phase1b", (0, 1))
    assert PaxosInstance("Phase2a", b=0, v=1).label() \
        == _label("Phase2a", (0, 1))


def test_a_checkpoint_written_under_symmetry_refuses_a_run_without(
        tmp_path):
    path = str(tmp_path / "snap")
    eng = _engine(3, 1)

    def stop_early(rec):
        if rec["n_states"] >= 100:
            signal.raise_signal(signal.SIGINT)

    got = eng.check(on_progress=stop_early, checkpoint=path,
                    checkpoint_every_s=float("inf"))
    assert got.complete is False
    again = eng.check(resume=path)
    assert again.complete is True and again.n_states == TABLE[(3, 1)][1]
    with pytest.raises(ValueError):
        _engine(3, 1, symmetry=False).check(resume=path)
