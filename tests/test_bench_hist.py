"""The benchmark's family with history (``benchmark/families/raft_hist.py``),
the configuration ``faithful3`` and the cell ``faithful3.passes``: the
manifest's new entries; the plain reference's canonical form over states with
history held to its all-permutations twin; the program held to the reference
through the ``ddd`` engine in faithful mode, level by level and state by
state, at a size that completes; the history invariants against the
program's Python twins; the planted fault in ``elections``; the two scopes
faithful mode opens in the lowered segment; a CPU rehearsal of the whole run
at toy size through the harness (``run.execute(rehearsal=True)``) and one
control; the four new readers on a recorded excerpt.  Nothing here is a
measurement.
"""

import dataclasses
import itertools
import json
import os
import random

import numpy as np
import pytest

from benchmark import run
from benchmark.families import raft, raft_hist as fam
from benchmark.harness import breakers, histred, stagered
from benchmark.harness import manifest as mf
from benchmark.reference import (canon, canon_hist, hist_pins, interp,
                                 invariants_hist)
from benchmark.reference import spec as S
from benchmark.reference.bounds import Bounds

CELL = "faithful3.passes"
NEW_METRICS = ("stage_history_ms", "stage_orbit_moved_ms",
               "history_step_share_pct", "row_words")
HISTORY_INVARIANTS = ("ElectionSafetyHist", "LeaderCompletenessHist",
                      "AllLogsPrefixClosed")


def toy_cell() -> dict:
    """``faithful3``'s own space at a chunk of 64, passes stopped at level
    8: the cell at toy size."""
    return {"name": "toy_hist.rehearsal", "config": "toy_hist3",
            "traffic": "toy_hist_traffic", "chips": 1,
            "config_data": mf.read_json("testdata", "toy_hist3.json"),
            "traffic_data": mf.read_json("testdata",
                                         "toy_hist_traffic.json")}


def two_server_cfg() -> dict:
    """Two servers, one value, one message in flight: 2,581 orbits of full
    states in 29 levels, a space that completes."""
    cfg = dict(mf.read_json("testdata", "toy_hist3.json"))
    cfg.update(
        name="toy_hist2", chunk=64,
        bounds={"n_servers": 2, "n_values": 1, "max_term": 2, "max_log": 1,
                "max_msgs": 1, "max_dup": 1, "history": True,
                "max_elections": 4},
        cfg_text=cfg["cfg_text"].replace("{s1, s2, s3}", "{s1, s2}")
        .replace("{v1, v2}", "{v1}"))
    del cfg["level_pins"]
    return cfg


def rename(s, p):
    """The state ``s`` with server j renamed p[j], as a state: the image
    ``canon_hist.permute`` names, rebuilt."""
    (role, term, voted, commit, log, vresp, vgrant, nxt, match, msgs,
     all_logs, vlog, elections) = canon_hist.permute(s, p)

    def opt(t):
        return t[0] if t else None

    return interp.PyState(
        role=role, term=term, votedFor=voted, commitIndex=commit, log=log,
        vResp=vresp, vGrant=vgrant, nextIndex=nxt, matchIndex=match,
        msgs=msgs, allLogs=tuple(sorted(all_logs, key=interp._log_key)),
        vLog=tuple(tuple(opt(x) for x in row) for row in vlog),
        elections=tuple(sorted(
            ((t, l, elog, v, tuple(opt(x) for x in evl))
             for t, l, elog, v, evl in elections),
            key=interp._election_key)))


@pytest.fixture(scope="module")
def reachable3():
    """The reference's level sets 0..9 of ``faithful3``'s space (7,595
    orbits of full states, three servers)."""
    cfg = mf.read_json("configs", "faithful3.json")
    levels = {}
    cum, _last, viol = canon_hist.bfs_levels(
        fam.bounds(cfg), cfg["spec"], cfg["symmetry"],
        {nm: invariants_hist.REGISTRY[nm] for nm in cfg["invariants"]},
        4096, on_level=lambda k, states: levels.__setitem__(k, list(states)))
    return cfg, cum, levels, viol


@pytest.fixture(scope="module")
def complete2():
    """The two-server space run to its end by the ``ddd`` engine in
    faithful mode under SYMMETRY Server, its store kept, beside the
    reference's level sets of the same space."""
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    cfg = two_server_cfg()
    levels = {}
    cum, _last, viol = canon_hist.bfs_levels(
        fam.bounds(cfg), cfg["spec"], cfg["symmetry"],
        {nm: invariants_hist.REGISTRY[nm] for nm in cfg["invariants"]},
        10 ** 9, on_level=lambda k, states: levels.__setitem__(k, list(states)))
    eng = DDDEngine(fam.check_config(cfg),
                    DDDCapacities(**cfg["engine_caps"]["ddd"]))
    res = eng.check(retain_store=True)
    yield cfg, cum, levels, viol, eng, res
    for store in eng.retained[:3]:
        store.close()


# ------------------------------------------------------------- the manifest

def test_manifest_gains_the_configuration_the_cell_and_four_readers():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    assert len(manifest["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2
    config = manifest["configs"][9]
    assert config["name"] == "faithful3" and config["reduced"] == ["depth"]
    assert config["file"] == "benchmark/configs/faithful3.json"
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    cell = manifest["workloads"][10]
    assert cell == {"name": CELL, "config": "faithful3",
                    "traffic": "passes_l15_l17_hist", "chips": 1,
                    "why": cell["why"]}
    assert 1 <= len(cell["why"]) <= 200
    # the 72 entries PR 49 left, then these four (later PRs append after)
    readers = manifest["per_layer"][72:76]
    assert tuple(m["name"] for m in readers) == NEW_METRICS
    for m in readers:
        assert m["workloads"] == [CELL] and m["moves"] == "orbits_per_s"
    assert [(m["unit"], m["source"], m["layer"]) for m in readers] == [
        ("ms/step", "device_trace", "fused step"),
        ("ms/step", "device_trace", "fused step"),
        ("%", "device_trace", "fused step"),
        ("words/row", "program_span", "d2h export and host key set")]
    names = mf.metric_names(manifest, CELL, "per_layer")
    assert names[-4:] == list(NEW_METRICS)
    assert mf.metric_names(manifest, CELL, "end_to_end") \
        == ["orbits_per_s", "setup_s"]
    # no accepted metric's list was touched: the cell is in its own four
    assert [m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", ())] == list(NEW_METRICS)


def test_the_configuration_is_flagship3_with_every_variable_of_vars():
    cell = mf.cell(mf.load(), CELL)
    cfg, t = cell["config_data"], cell["traffic_data"]
    flag = mf.read_json("configs", "flagship3.json")
    assert mf.family(cfg) is fam and mf.engine_of(cfg, 1) == ("ddd", 1)
    assert mf.end_of(t, cfg, cell["traffic"]) == "pin"
    assert cfg["bounds"] == {**flag["bounds"], "history": True,
                             "max_elections": 6}
    assert cfg["invariants"] == flag["invariants"] + list(HISTORY_INVARIANTS)
    assert (cfg["symmetry"], cfg["chunk"], cfg["engine_caps"], cfg["spec"]) \
        == (flag["symmetry"], flag["chunk"], flag["engine_caps"], "full")
    assert set(cfg["reduced"]) == {"depth"} and "init" not in cfg
    with open(os.path.join(mf.ROOT, "runs", "MC3s2v_faithful.cfg"),
              encoding="utf-8") as f:
        assert f.read() == cfg["cfg_text"]
    assert "two states that differ in a history variable alone are two " \
        "states" in cfg["guarantees"]["search"]
    # B is the first level whose pinned count reaches 900,000, A = B - 2
    pins = cfg["level_pins"]
    b = next(k for k, c in enumerate(pins) if c >= 900_000)
    assert (t["start_level"], t["end_level"], t["min_passes"]) \
        == (b - 2, b, 3) == (15, 17, 3)
    assert (pins[15], pins[17]) == (t["count_at_start"], t["count_at_end"]) \
        == (353_863, 1_011_645) and len(pins) >= b + 2
    # nowhere fewer orbits than parity counts, one block a level
    assert all(h >= p for h, p in zip(pins, flag["level_pins"]))
    assert max(y - x for x, y in zip(pins, pins[1:b + 1])) < 1 << 20
    config = fam.check_config(cfg)
    assert config.bounds.history and config.bounds.max_elections == 6
    assert (config.chunk, config.symmetry, len(config.invariants)) \
        == (4096, ("Server",), 7)


def test_the_pins_are_the_references_and_part_from_paritys_at_level_1(
        reachable3):
    cfg, cum, levels, viol = reachable3
    assert cum == cfg["level_pins"][:len(cum)] and len(cum) == 10
    assert viol == 0
    # the first step turns allLogs from {} into {<<>>}: two orbits at level
    # 1 where parity has one, so a program that keys the parity fields alone
    # counts 2 at level 1 where the pins say 3
    flag = mf.read_json("configs", "flagship3.json")
    assert (cfg["level_pins"][1], flag["level_pins"][1]) == (3, 2)
    keyed_without = {canon.canonical(canon_hist.drop_history(s))
                     for k in (0, 1) for s in levels[k]}
    assert len(keyed_without) == 2
    a, b = levels[1]
    assert {a.allLogs, b.allLogs} == {((),)} != {levels[0][0].allLogs}


# ------------------------------------------------- the reference's own form

@pytest.mark.parametrize("seed", range(4))
def test_canonical_form_equals_its_all_permutations_twin(reachable3, seed):
    _cfg, _cum, levels, _viol = reachable3
    rng = random.Random(f"twin/{seed}")
    pool = levels[9] + levels[8]
    with_elections = [s for s in pool if s.elections]
    assert with_elections
    perms = list(itertools.permutations(range(3)))
    picked = rng.sample(pool, 60) + rng.sample(
        with_elections, min(10, len(with_elections)))
    pairs = set()
    for s in picked:
        want = canon_hist.canonical_all_perms(s)
        short = canon_hist.canonical(s)
        pairs.add((short, want))
        # whatever the servers are called, the orbit's name is the same
        image = rename(s, rng.choice(perms))
        assert canon_hist.canonical(image) == short
        assert canon_hist.canonical_all_perms(image) == want
        # both name a member of the orbit
        images = {canon_hist.permute(s, p) for p in perms}
        assert {short, want, canon_hist.as_tuple(image)} <= images
    # the shortcut partitions states exactly as the definition does
    assert len({a for a, _b in pairs}) == len({b for _a, b in pairs}) \
        == len(pairs)


def test_states_that_differ_in_a_history_variable_alone_are_two_states(
        reachable3):
    _cfg, _cum, levels, _viol = reachable3
    s = next(s for s in levels[9] if s.elections)
    (eterm, leader, elog, votes, evlog), = s.elections
    other = s._replace(elections=((eterm, leader, elog, votes | 0b111,
                                   evlog),))
    assert other != s and votes != 0b111
    assert canon_hist.canonical(other) != canon_hist.canonical(s)
    assert canon.canonical(canon_hist.drop_history(other)) \
        == canon.canonical(canon_hist.drop_history(s))
    # eleader is relabelled, evotes and evoterLog re-indexed with it
    p = (1, 2, 0)
    (_t, leader2, _l, votes2, evlog2), = rename(s, p).elections
    assert leader2 == p[leader]
    assert votes2 == sum(1 << p[j] for j in range(3) if votes >> j & 1)
    assert all(evlog2[p[j]] == evlog[j] for j in range(3))
    # parity mode is the other family's: neither takes the other's state
    with pytest.raises(ValueError, match="parity mode"):
        canon_hist.canonical(canon_hist.drop_history(s))
    with pytest.raises(ValueError, match="parity mode only"):
        canon.canonical(s)


def test_the_family_refuses_what_it_does_not_cover():
    cfg = mf.read_json("testdata", "toy_hist3.json")
    parity = {**cfg, "bounds": {**cfg["bounds"], "history": False}}
    with pytest.raises(ValueError, match="needs bounds.history"):
        fam.bounds(parity)
    with pytest.raises(ValueError, match="starts from the spec's Init"):
        fam.stated_init({**cfg, "init": {"role": ["Leader"] * 3}})
    assert fam.stated_init(cfg) is None
    with pytest.raises(ValueError, match="reduces over no axis or over Serv"):
        fam.orbit_key({**cfg, "symmetry": ["Server", "Value"]})
    # a parent of this PR has no families/raft_hist.py: manifest.family
    # refuses the name before any device, as it does an unknown one here
    with pytest.raises(ValueError, match="unknown family 'raft_hist_v2'"):
        mf.family({**cfg, "family": "raft_hist_v2"})
    assert "raft_hist" in str(pytest.raises(
        ValueError, mf.family, {"name": "x", "family": "nope"}).value)
    # the program half is the Raft family's where the program is the same
    assert (fam.check_config, fam.gates, fam.scan_words) \
        == (raft.check_config, raft.gates, raft.scan_words)
    assert fam.STATE_FIELDS[:len(raft.STATE_FIELDS)] == raft.STATE_FIELDS


# ------------------------------ the program against the reference, complete

def test_the_engine_counts_the_references_levels_in_faithful_mode(complete2):
    _cfg, cum, _levels, viol, _eng, res = complete2
    assert viol == 0 and res.violation is None and res.complete is True
    assert list(itertools.accumulate(res.levels)) == cum
    assert (cum[-1], len(cum)) == (2581, 29)


def test_the_admitted_rows_are_the_references_level_sets_as_states(
        complete2):
    cfg, cum, levels, _viol, eng, res = complete2
    host, _con, _keys, n = eng.retained
    assert n == cum[-1]
    key = fam.orbit_key(cfg)
    lo = 0
    for k, rows in enumerate(res.levels):
        got = fam.decode_rows(eng, host.read(lo, rows))
        lo += rows
        # states with their history, canonicalised in plain Python
        assert {key(s) for s in got} == {key(s) for s in levels[k]}
        assert all(s.allLogs is not None for s in got)
    assert any(s.elections for s in got) or any(
        s.elections for lv in levels.values() for s in lv)


def test_the_pass_ledger_says_row_words_and_elections_peak(complete2):
    cfg, _cum, levels, _viol, eng, res = complete2
    rec = res.level_log
    peak = max(len(s.elections) for lv in levels.values() for s in lv)
    assert rec["elections_peak"] == peak == 1
    assert {lv["row_words"] for lv in rec["levels"]} == {eng.schema.P}
    # parity's row is narrower and its ledger holds no elections_peak
    from raft_tla_tpu.ops import bitpack
    parity = dataclasses.replace(fam.check_config(cfg).bounds, history=False)
    assert bitpack.BitSchema(parity).P < eng.schema.P
    want = hist_pins.bfs_counts(cfg["bounds"], cfg["spec"], cfg["symmetry"],
                                tuple(cfg["invariants"]), end_level=99,
                                out=lambda _m: None)
    assert want["elections_peak"] == peak
    assert want["cumulative"] == list(itertools.accumulate(res.levels))


def test_a_state_crosses_to_the_program_and_back_with_its_history(
        complete2):
    _cfg, _cum, levels, _viol, eng, _res = complete2
    states = [s for lv in levels.values() for s in lv]
    picked = random.Random("cross").sample(states, 50) \
        + [s for s in states if s.elections][:10]
    assert [fam.from_program(fam.to_program(s)) for s in picked] == picked
    rows, con = fam.pack_rows(eng, picked)
    assert rows.shape == (60, eng.schema.P) and rows.dtype == np.int32
    assert fam.decode_rows(eng, rows) == picked
    assert list(con) == [interp.constraint_ok(s, eng.bounds) for s in picked]


def test_the_planted_fault_is_found_with_a_trace(complete2):
    cfg, _cum, levels, _viol, eng, _res = complete2
    plant = fam.planted_fault(cfg, levels[12], 7)
    parent = plant["parent"]
    assert fam.holds(parent, cfg) == []
    assert list(plant["violators"].values()) == [["ElectionSafetyHist"]]
    res = eng.check(init_override=fam.to_program(parent))
    v = res.violation
    assert v is not None and v.invariant == "ElectionSafetyHist"
    state = fam.from_program(v.state)
    assert plant["key"](state) in plant["violators"]
    assert fam.holds(state, cfg) == ["ElectionSafetyHist"]
    # a second record of a term that has one, with another leader
    terms = [(r[0], r[1]) for r in state.elections]
    assert len({t for t, _l in terms}) < len(set(terms))
    # the trace: the planted parent, then BecomeLeader
    assert len(v.trace) == 2 and v.trace[0][0] is None
    assert fam.from_program(v.trace[0][1]) == parent
    assert "BecomeLeader" in v.trace[1][0]


def test_a_level_set_with_its_history_dropped_lies_inside_paritys_space(
        complete2):
    cfg, _cum, levels, _viol, _eng, _res = complete2
    b = Bounds(**{k: v for k, v in cfg["bounds"].items()
                  if k not in ("history", "max_elections")})
    table = S.action_table(b, "full")
    seen = {canon.canonical(interp.init_state(b))}
    frontier = [interp.init_state(b)]
    while frontier:
        nxt = []
        for s in frontier:
            if interp.constraint_ok(s, b):
                for _a, t in interp.successors(s, b, table):
                    k = canon.canonical(t)
                    if k not in seen:
                        seen.add(k)
                        nxt.append(t)
        frontier = nxt
    dropped = {canon.canonical(canon_hist.drop_history(s))
               for lv in levels.values() for s in lv}
    assert dropped <= seen and len(dropped) < sum(map(len, levels.values()))


# ------------------------------------------------------------ the invariants

def _broken(s, name, rng):
    """``s`` rewritten so that it breaks ``name`` (None where it cannot)."""
    n = len(s.role)
    if name == "ElectionSafetyHist":
        if not s.elections:
            return None
        t, leader, elog, votes, evlog = s.elections[0]
        extra = (t, (leader + 1) % n, elog, votes, evlog)
        return s._replace(elections=tuple(sorted(
            set(s.elections) | {extra}, key=interp._election_key)))
    if name == "LeaderCompletenessHist":
        j = rng.randrange(n)
        entry = (s.term[j], 1)
        later = (s.term[j] + 1, (j + 1) % n, (), 0b011, (None,) * n)
        return s._replace(
            log=s.log[:j] + ((entry,),) + s.log[j + 1:],
            commitIndex=s.commitIndex[:j] + (1,) + s.commitIndex[j + 1:],
            elections=tuple(sorted(set(s.elections) | {later},
                                   key=interp._election_key)))
    # a log of two entries whose first entry alone was never recorded
    orphan = ((2, 1 + rng.randrange(2)), (2, 1))
    return s._replace(allLogs=tuple(sorted(
        set(s.allLogs) - {orphan[:1]} | {orphan}, key=interp._log_key)))


@pytest.mark.parametrize("name", HISTORY_INVARIANTS)
def test_history_invariants_agree_with_the_programs_python_twins(
        reachable3, name):
    from raft_tla_tpu.config import Bounds as PBounds
    from raft_tla_tpu.models import invariants as pinv
    cfg, _cum, levels, _viol = reachable3
    b, pb = fam.bounds(cfg), PBounds(**cfg["bounds"])
    mine, theirs = invariants_hist.HISTORY[name], pinv.py_invariant(name)
    rng = random.Random(f"inv/{name}")
    pool = levels[9] + levels[8]
    picked = rng.sample(pool, 150) + [s for s in pool if s.elections][:50]
    verdicts = set()
    for s in picked:
        for t in (s, _broken(s, name, rng)):
            if t is not None:
                ok = mine(t, b)
                assert ok == theirs(fam.to_program(t), pb)
                verdicts.add(ok)
        assert mine(s, b)                   # reachable states hold it
    assert verdicts == {True, False}        # and the broken ones break it


# ------------------------------------------------------- the lowered segment

def _lowered(cfg):
    import jax
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    eng = DDDEngine(fam.check_config(cfg),
                    DDDCapacities(**cfg["engine_caps"]["ddd"]))
    i32 = jax.ShapeDtypeStruct((), np.int32)
    return eng._segment.lower(
        jax.eval_shape(eng._init_filter), jax.eval_shape(eng._make_bufs),
        jax.ShapeDtypeStruct((eng.caps.block, eng.schema.P), np.int32),
        jax.ShapeDtypeStruct((eng.caps.block,), np.bool_), i32, i32)


@pytest.fixture(scope="module")
def toy_paths():
    """The toy configuration's compiled segment module's op names: what a
    device trace's events carry."""
    import re
    cfg = mf.read_json("testdata", "toy_hist3.json")
    return cfg, set(re.findall(r'op_name="(jit\(segment\)/[^"]*)"',
                               _lowered(cfg).compile().as_text()))


def test_faithful_mode_opens_two_scopes_inside_two_stages(toy_paths):
    import re
    from raft_tla_tpu.ops import kernels
    # the stage list is the accepted reducer's, letter for letter; the two
    # new names stand beside it, and are the new reducer's
    assert kernels.STAGE_SCOPES == stagered.STAGES
    assert kernels.NESTED_SCOPES == histred.SCOPES \
        == (kernels.HISTORY_SCOPE, kernels.ORBIT_MOVED_SCOPE)
    assert not set(kernels.NESTED_SCOPES) & set(kernels.STAGE_SCOPES)
    cfg, paths = toy_paths
    hist = [p for p in paths if histred.scope_of(p) == "history"]
    moved = [p for p in paths if histred.scope_of(p) == "orbit_moved"]
    assert hist and moved
    # each inside its stage, which stays the op's stage
    assert {stagered.stage_of(p) for p in hist} == {"expand"}
    assert {stagered.stage_of(p) for p in moved} == {"orbit_scan"}
    assert all(re.search(r"/expand/.*history", p) for p in hist)
    assert all(re.search(r"/orbit_scan/.*orbit_moved", p) for p in moved)
    # parity mode under SYMMETRY Server opens neither
    parity = {**cfg, "family": "raft",
              "bounds": {k: v for k, v in cfg["bounds"].items()
                         if k not in ("history", "max_elections")},
              "invariants": cfg["invariants"][:4],
              "cfg_text": cfg["cfg_text"].replace(
                  " ElectionSafetyHist LeaderCompletenessHist "
                  "AllLogsPrefixClosed", "")}
    ptext = _lowered(parity).as_text(debug_info=True)
    assert "orbit_scan" in ptext and "expand" in ptext
    assert not re.search("|".join(kernels.NESTED_SCOPES), ptext)


def test_the_scan_of_the_toy_moves_no_history(toy_paths):
    """PR 51: under SYMMETRY Server the faithful scan moves no field
    (``scan_forms``), and what the history adds to an image's key still
    lowers under ``orbit_moved`` inside ``orbit_scan`` — once a step in
    front of the scan (the slot-major record words, ``allLogs``' sums) and
    in the images' vmapped body — with no loop of its own: the parent's
    ``lax.map`` over the images put every op of the scope inside a second
    ``while`` under the scan's."""
    from raft_tla_tpu.ops import symmetry as sym
    cfg, paths = toy_paths
    forms = sym.scan_forms(fam.check_config(cfg).bounds,
                           tuple(cfg["symmetry"]))
    assert forms["moved"] == () and forms["once"] == ("allLogs",)
    assert "vLog" in forms["table"] and "eVLog" in forms["ranked"]
    moved = [p.split("/orbit_scan/", 1)[1] for p in paths
             if histred.scope_of(p) == "orbit_moved"]
    assert any(p.startswith("orbit_moved/") for p in moved)
    assert any("vmap(orbit_moved)" in p for p in moved)
    assert max(p.count("while") for p in moved) <= 1
    assert not any(re_op in p for p in moved
                   for re_op in ("gather", "scatter", "dynamic_update_slice",
                                 "sort"))


# ----------------------------------------- the whole run, at toy size (CPU)

def test_rehearsal_of_one_run_is_correct_and_writes_no_metric(capsys):
    res = run.execute(toy_cell(), mf.load(), 3_000_000_019, 0.0, False,
                      rehearsal=True)
    said = capsys.readouterr().out
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 3
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert "planted fault: the engine reported ElectionSafetyHist" in said
    assert "levels [1, 3, 8, 24, 79, 243, 678, 1591, 3452]" in said


@pytest.mark.parametrize("control, broken", [
    ("invariants_off", "planted_violation_missed"),
    ("filter_only", "pass_level_mismatches"),
    # the cell's own control is key32, which 1,011,645 orbits collide under
    # and the toy's 3,452 do not: sixteen bits at this size
    ("key16", "pass_level_mismatches")])
def test_a_control_at_toy_size_comes_out_not_correct(control, broken):
    cut = breakers.short_keys(16) if control == "key16" \
        else breakers.CONTROLS[control]()
    with cut:
        res = run.execute(toy_cell(), mf.load(), 11, 0.0, False,
                          rehearsal=True)
    assert res["correct"] is False
    assert res["checks"][broken]["value"] >= 1
    if control == "invariants_off":
        # the search itself is sound: only the planted election is missed
        assert [k for k, c in res["checks"].items()
                if c["value"] > c["limit"]] == [broken]


# ------------------------------------- the readers, on a recorded excerpt

@pytest.fixture(scope="module")
def excerpt():
    with open(os.path.join(mf.BENCH, "testdata", "hist_trace_small.json"),
              encoding="utf-8") as f:
        return json.load(f)


class _Pass:
    traced, index = True, 2
    trace_dir = anchor = None


def _evidence(excerpt, tmp_path):
    """Evidence as ``run.execute`` builds it: the excerpt's spans as the
    traced pass's event log, its capture's reduction in place of the
    capture (``histred.of`` loads an ``.xplane.pb``; the excerpt is the
    plain dict ``scope_times`` reads)."""
    from benchmark.harness import spanred
    p = _Pass()
    p.events = str(tmp_path / "run.events")
    with open(p.events, "w", encoding="utf-8") as f:
        for sp in excerpt["spans"]:
            f.write(json.dumps(sp) + "\n")
    p.t_a, p.t_trace_end = (excerpt["pass"][k] for k in ("t_a", "t_trace_end"))
    seg_s = excerpt["expected"]["stages"]["module_ns"] / 1e9
    ev = {"passes": [p], "work": {"steps": excerpt["pass"]["steps"]},
          "trace": {"segment_device_s": seg_s}}
    ev["histred"] = {
        "row_words": histred.window_row_words(
            spanred.load(p.events), p.t_a, p.t_trace_end),
        "scopes": histred.scope_times(excerpt["trace"],
                                      *excerpt["window_ns"])}
    return ev


def test_scope_times_partition_the_recorded_window(excerpt):
    w0, w1 = excerpt["window_ns"]
    red = histred.scope_times(excerpt["trace"], w0, w1)
    st = stagered.stage_times(excerpt["trace"], w0, w1)
    assert red == excerpt["expected"]["scopes"]
    assert st["stage_ns"] == excerpt["expected"]["stages"]["stage_ns"]
    # one partition of the same events; each scope inside its stage
    assert red["devices"] == 1 and red["total_ns"] == st["total_ns"]
    hist, moved = (red["scope_ns"][s] for s in histred.SCOPES)
    assert 0 < hist < st["stage_ns"]["expand"]
    assert 0 < moved < st["stage_ns"]["orbit_scan"]
    assert {scope for _n, scope, _ns in red["top_ops"]} \
        == set(histred.SCOPES)
    # the whole traced level, as the run's log had it
    whole = excerpt["whole_window"]
    assert whole["scopes"]["total_ns"] == whole["stages"]["total_ns"]
    assert whole["scopes"]["scope_ns"]["orbit_moved"] \
        < whole["stages"]["stage_ns"]["orbit_scan"]
    assert histred.scope_of("jit(segment)/while/body/expand/vmap(vmap("
                            "history))/mul") == "history"
    assert histred.scope_of("jit(segment)/while/body/orbit_scan/while/body/"
                            "closed_call/orbit_moved/reduce_sum") \
        == "orbit_moved"
    assert histred.scope_of("jit(segment)/while/body/expand/add") is None
    assert histred.scope_of("jit(segment)/prehistory/add") is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_reader_on_the_recorded_excerpt(excerpt, tmp_path, name):
    ev = _evidence(excerpt, tmp_path)
    value = mf.metric_reader(name)(ev)
    red = ev["histred"]
    hist, moved = (red["scopes"]["scope_ns"][s] for s in histred.SCOPES)
    want = {
        "stage_history_ms": hist / 1e6 / excerpt["pass"]["steps"],
        "stage_orbit_moved_ms": moved / 1e6 / excerpt["pass"]["steps"],
        "history_step_share_pct":
            100 * (hist + moved) / 1e9 / ev["trace"]["segment_device_s"],
        "row_words": 19}[name]
    assert value == pytest.approx(want) and value > 0
    if name == "history_step_share_pct":
        assert value < 100
    # a parity-mode program, or one older than the scopes and the count:
    # nothing to read, and nothing raised
    bare = {**ev, "histred": {"row_words": None, "scopes": {
        "scope_ns": dict.fromkeys(histred.SCOPES, 0.0)}}}
    assert mf.metric_reader(name)(bare) is None
    assert mf.metric_reader(name)({**ev, "histred": None}) is None
    untraced = {"passes": [], "work": {"steps": 1}, "trace": None}
    assert mf.metric_reader(name)(untraced) is None
