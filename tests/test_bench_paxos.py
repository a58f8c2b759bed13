"""The benchmark's third family (``benchmark/families/paxos_ddd.py``), the
configuration ``paxos3b4`` and the cell ``paxos3b4.passes``: the manifest's new
entries, the plain reference, the family's two crossings, a CPU rehearsal of
the whole run at toy size through the harness (``run.execute(rehearsal=True)``:
passes, pins, the reference's BFS prefix and sample through the run's own
compiled segment, the planted fault), the ``filter_only`` control at that
size, and the three new readers on hand-made evidence.  Nothing here is a
measurement.
"""

import ast
import os
import subprocess
import sys
import types

import pytest

from benchmark import run
from benchmark.families import paxos_ddd as fam
from benchmark.harness import breakers, quorumred
from benchmark.harness import manifest as mf
from benchmark.harness import passes
from benchmark.reference import paxos as ref

NEW_METRICS = ("stage_quorum_ms", "quorum_step_share_pct",
               "phase2_states_share_pct")
CELL = "paxos3b4.passes"
CFG_TEXT = ("CONSTANTS\n  Acceptor = {a1, a2, a3}\n  Value = {v1, v2}\n"
            "  Quorum = {{a1, a2}, {a1, a3}, {a2, a3}}\n  None = None\n"
            "  Ballot <- MCBallot\nSPECIFICATION Spec\n"
            "INVARIANTS TypeOK Consistency\n")
# ISSUE 43's table (a separate transcription): new states a level, 0..32
ISSUE_LEVELS = (
    1, 4, 18, 52, 155, 428, 1116, 2728, 6133, 12936, 25800, 48572, 86299,
    144148, 226302, 335444, 469857, 619616, 765362, 881404, 945762, 947384,
    878806, 734316, 531158, 320020, 155530, 59280, 17198, 3652, 534, 48, 2)


def toy_cell(max_ballot: int = 1) -> dict:
    """Ballots 0..1 (3,921 states, 17 levels), a block smaller than the
    levels of the clocked span and a filter smaller than the space: what the
    cell is for, at toy size."""
    cum = ref.bfs_levels(ref.model(3, 2, max_ballot))[0]
    cfg = {"name": "toy_paxos", "family": "paxos_ddd", "spec": "paxos",
           "bounds": {"n_acceptors": 3, "n_values": 2,
                      "max_ballot": max_ballot},
           "quorums": [["a1", "a2"], ["a1", "a3"], ["a2", "a3"]],
           "symmetry": [], "invariants": ["TypeOK", "Consistency"],
           "chunk": 32, "cfg_text": CFG_TEXT,
           "engine_caps": {"ddd": {"block": 256, "table": 1024,
                                   "seg_rows": 2048, "levels": 64,
                                   "retention": "full"}},
           "sample_min_level_states": 64, "level_pins": cum}
    traffic = {"start": "init", "end": "pin", "start_level": 5,
               "end_level": 9, "min_passes": 3, "count_at_start": cum[5],
               "count_at_end": cum[9], "why": "rehearsal only"}
    return {"name": "toy.paxos", "config": cfg["name"],
            "traffic": "toy_paxos_traffic", "chips": 1,
            "config_data": cfg, "traffic_data": traffic}


# ------------------------------------------------------------- the manifest

def test_manifest_gains_the_configuration_the_cell_and_three_readers():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    (config,) = [c for c in manifest["configs"] if c["name"] == "paxos3b4"]
    assert config["reduced"] == ["depth"]
    assert config["file"] == "benchmark/configs/paxos3b4.json"
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": "paxos3b4",
                    "traffic": "passes_l14_l24", "chips": 1,
                    "why": cell["why"]}
    assert 1 <= len(cell["why"]) <= 200
    readers = [m for m in manifest["per_layer"] if m["name"] in NEW_METRICS]
    assert tuple(m["name"] for m in readers) == NEW_METRICS
    for m in readers:
        assert m["workloads"] == [CELL] and m["moves"] == "orbits_per_s"
        assert m["layer"] == "fused step"
    # the cell reports the readers that carry no list, the three new ones
    # and, since PR 44, flush_offload_pct
    names = mf.metric_names(manifest, CELL, "per_layer")
    assert len(names) == 18 and set(NEW_METRICS) <= set(names)
    assert {"scan_words_per_s", "step_hbm_share", "device_idle_share",
            "pass_median_rate"} <= set(names)
    assert mf.metric_names(manifest, CELL, "end_to_end") \
        == ["orbits_per_s", "setup_s"]
    # nothing an accepted metric lists was touched: the cell is in no list
    # but its own three and the one PR 44 brought with it
    assert [m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", ())] \
        == list(NEW_METRICS) + ["flush_offload_pct"]


def test_the_configuration_is_the_sources_deployment_at_ballots_0_to_3():
    cell = mf.cell(mf.load(), CELL)
    cfg, t = cell["config_data"], cell["traffic_data"]
    assert mf.family(cfg) is fam and mf.engine_of(cfg, 1) == ("ddd", 1)
    assert mf.end_of(t, cfg, cell["traffic"]) == "pin"
    assert cfg["bounds"] == {"n_acceptors": 3, "n_values": 2,
                             "max_ballot": 3}
    assert cfg["quorums"] == [["a1", "a2"], ["a1", "a3"], ["a2", "a3"]]
    assert cfg["cfg_text"] == CFG_TEXT and cfg["chunk"] == 4096
    assert cfg["invariants"] == ["TypeOK", "Consistency"]
    assert cfg["symmetry"] == [] and "init" not in cfg
    assert set(cfg["reduced"]) == {"depth"}
    assert set(cfg["guarantees"]) == {"search", "dedup", "invariants",
                                      "symmetry", "stop"}
    assert cfg["engine_caps"]["ddd"] == {
        "block": 1 << 20, "table": 1 << 22, "seg_rows": 1 << 19,
        "levels": 256, "retention": "full"}
    pins = cfg["level_pins"]
    # the plain reference's own BFS re-derives the first levels here; the
    # whole table is the reference's too (pins_source) and equals ISSUE 43's
    assert len(pins) == 25
    assert pins[:9] == ref.bfs_levels(fam.bounds(cfg), (), 4096)[0]
    rows = [b - a for a, b in zip([0] + pins, pins)]
    assert tuple(rows) == ISSUE_LEVELS[:25]
    assert (t["start_level"], t["end_level"], t["min_passes"]) == (14, 24, 3)
    assert (pins[14], pins[24]) == (t["count_at_start"], t["count_at_end"]) \
        == (554_692, 7_663_801)
    assert sum(ISSUE_LEVELS) == 8_220_065
    # the clocked span admits the ten widest levels of the space (15..24)
    # out of frontiers 14..23, every one in one 2^20-row block
    assert sorted(ISSUE_LEVELS)[-10:] == sorted(rows[15:25])
    assert min(rows[14:24]) == 226_302 and max(rows) == 947_384 < 1 << 20
    assert t["count_at_end"] - t["count_at_start"] == 7_109_109
    config = fam.check_config(cfg)
    assert (config.spec, config.bounds.n_servers, config.bounds.n_values,
            config.bounds.max_term, config.chunk, config.invariants,
            config.symmetry) \
        == ("paxos", 3, 2, 3, 4096, ("TypeOK", "Consistency"), ())
    assert dict(config.bounds.constants)["Quorum"] \
        == ((1, 1, 0), (1, 0, 1), (0, 1, 1))
    # a chunk's candidate lanes fit the segment's rows (the engine's own
    # check, here at the real size without building the engine)
    assert cfg["chunk"] * 48 <= cfg["engine_caps"]["ddd"]["seg_rows"]


def test_cfg_text_is_held_to_the_fields_beside_it():
    cfg = toy_cell()["config_data"]
    for edit, said in (
            ({"bounds": dict(cfg["bounds"], n_values=3)},
             "cfg_text says (3, 2,"),
            ({"quorums": [["a1", "a2"]]}, "the fields say (3, 2, [(1, 1, 0)]"),
            ({"invariants": ["Consistency"]}, "['Consistency'], [])"),
            ({"cfg_text": CFG_TEXT.replace("Spec\n", "MCSpec\n")},
             "SPECIFICATION 'MCSpec'")):
        with pytest.raises(ValueError, match=said.replace("(", r"\(")
                           .replace("[", r"\[").replace(")", r"\)")
                           .replace("]", r"\]")):
            fam.check_config(dict(cfg, **edit))
    with pytest.raises(ValueError, match="no SYMMETRY"):
        fam.check_config(dict(cfg, symmetry=["Acceptor"]))
    with pytest.raises(ValueError, match="states an Init"):
        fam.check_config(dict(cfg, init={}))
    with pytest.raises(ValueError, match="quorum .* is empty or names"):
        fam.check_config(dict(cfg, quorums=[["a1", "a7"]]))
    with pytest.raises(ValueError, match="a9 is not in Acceptor"):
        fam.check_config(dict(cfg, cfg_text=CFG_TEXT.replace(
            "{a2, a3}}", "{a2, a9}}")))


@pytest.mark.parametrize("how", ["unknown_spec", "no_ddd"])
def test_a_program_without_the_spec_or_the_engine_is_refused_by_name(
        how, monkeypatch):
    # what the parent of this PR answers: at once, out of check_config,
    # before any engine is built
    from raft_tla_tpu.frontend import registry
    if how == "no_ddd":
        monkeypatch.setattr(registry.PaxosModel, "engines", ("host",))
        said = "runs spec 'paxos' on host only"
    else:
        real = registry.resolve_model

        def parent(spec):
            if spec == "paxos":
                raise ValueError("unknown spec 'paxos'; known: full")
            return real(spec)

        monkeypatch.setattr(registry, "resolve_model", parent)
        said = r"this program has no spec 'paxos' \(unknown spec 'paxos'\)"
    with pytest.raises(fam.NoDeviceEngine, match=said):
        fam.check_config(toy_cell()["config_data"])


def test_a_state_crosses_to_the_program_and_back():
    m = ref.model(3, 2, 1)
    _cum, level, _viol, _trans = ref.bfs_levels(m, (), 200)
    assert len(level) >= 200
    for s in level:
        p = fam.to_program(s)
        assert fam.from_program(p) == s
        assert type(p).__name__ == "PaxosState" and p.msgs == s.msgs
    # the fourteen names families/raft.py lists
    from benchmark.families import raft
    public = {k for k, v in vars(raft).items() if not k.startswith("_")
              and callable(v) and not isinstance(v, (type, types.ModuleType))
              and getattr(v, "__module__", "") == raft.__name__}
    assert len(public) == 14 and public <= set(vars(fam))


# ------------------------------------------------------ the plain reference

def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(mf.BENCH, "reference", "paxos.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            mods.add((node.module or "").split(".")[0])
    assert mods == {"__future__", "itertools", "sys", "time", "typing"}


def test_the_reference_counts_a_whole_space_from_the_command_line():
    out = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH, "reference", "paxos.py"),
         "3", "2", "1"], capture_output=True, text=True, check=True).stdout
    assert "3921 states, 17 levels (diameter 16)" in out
    assert "22994 transitions, 0 violations" in out
    assert "quorums=[[0, 1], [0, 2], [1, 2]]" in out


def test_the_reference_judges_both_invariants():
    m = ref.model(3, 2, 1)
    s = ref.init_state(m)
    assert ref.type_ok(s, m) and ref.consistency(s, m)
    two = s._replace(msgs=frozenset(
        {("2b", 0, 0, 0), ("2b", 1, 0, 0), ("2b", 1, 1, 1), ("2b", 2, 1, 1)}))
    assert ref.chosen(two, m) == {0, 1} and not ref.consistency(two, m)
    one_short = two._replace(msgs=two.msgs - {("2b", 2, 1, 1)})
    assert ref.chosen(one_short, m) == {0} and ref.consistency(one_short, m)
    assert not ref.type_ok(s._replace(maxBal=(2, -1, -1)), m)
    assert not ref.type_ok(s._replace(msgs=frozenset({("1b", 0, 0, 2, 0)})),
                           m)
    assert not ref.type_ok(s._replace(msgs=frozenset({("3a", 0)})), m)
    with pytest.raises(ValueError, match="is empty or names an acceptor"):
        ref.model(3, 2, 1, [set()])
    # all majorities reach what the three pairs reach (ISSUE 43's remark)
    assert ref.bfs_levels(ref.model(3, 2, 1, ref.majorities(3)))[0] \
        == ref.bfs_levels(m)[0]
    assert ref.minimal_majorities(3) == m.quorums
    # the packing a long search keeps in ``seen`` is one to one
    _cum, level, _v, _t = ref.bfs_levels(m, (), 300)
    pack = ref.packer(m)
    assert len({pack(x) for x in level}) == len(set(level)) == len(level)


@pytest.mark.parametrize("seed", [1, 2_147_483_659, 4_000_000_007])
def test_the_planted_fault_is_one_phase2b_short_of_two_chosen_values(seed):
    cfg = toy_cell()["config_data"]
    m = fam.bounds(cfg)
    _cum, level, _viol, _trans = ref.bfs_levels(m, (), 64)
    plant = fam.planted_fault(cfg, level, seed)
    parent = plant["parent"]
    assert fam.holds(parent, cfg) == [] and len(ref.chosen(parent, m)) == 1
    assert plant["violators"]
    for nxt, broken in plant["violators"].items():
        assert broken == ["Consistency"] and len(ref.chosen(nxt, m)) == 2
        (action,) = [a for a, t in ref.successors(parent, m) if t == nxt]
        assert action[0] == "Phase2b"
    assert fam.planted_fault(cfg, level, seed)["parent"] == parent
    with pytest.raises(ValueError, match="two values and two ballots"):
        fam.planted_fault(dict(cfg, bounds=dict(cfg["bounds"], n_values=1)),
                          level, seed)


# ------------------------------------------------- the run, rehearsed here

@pytest.fixture(scope="module")
def rehearsal():
    return run.execute(toy_cell(), mf.load(), 3_000_000_041, 0.0, False,
                       rehearsal=True)


def test_a_rehearsal_of_the_cell_at_toy_size_is_correct(rehearsal):
    res = rehearsal
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["correct"] is True
    assert res["attempted"] >= 3 and res["failed"] == 0
    checks = res["checks"]
    assert all(c["value"] <= c["limit"] for c in checks.values())
    assert {"pass_level_mismatches", "violations", "warm_pass_problems",
            "ref_bfs_level_mismatches", "sample_orbits_missing",
            "sample_orbits_extra", "sample_key_orbit_conflicts",
            "sample_transitions_diff", "sample_segment_flags",
            "planted_violation_missed", "planted_violation_misnamed"} \
        <= set(checks)


def test_the_filter_alone_does_not_dedup_the_toy():
    with breakers.filter_only_dedup():
        res = run.execute(toy_cell(), mf.load(), 12, 0.0, False,
                          rehearsal=True)
    assert res["correct"] is False


# ------------------------------------------ the new readers, by hand

PATH = "jit(segment)/while/body/"


def _trace(quorum_ns: int = 100) -> dict:
    """One device that ran the segment module for 1,000 ns: a ``while`` that
    holds three fusions, one of them under the scope."""
    ops = [["while.1", 0, 1000, "jit(segment)/while"],
           ["fusion.1", 100, quorum_ns,
            PATH + "expand/vmap(vmap(quorum))/reduce_max"],
           ["fusion.2", 300, 200, PATH + "expand/vmap(vmap())/select_n"],
           ["fusion.3", 600, 50, PATH + "pack/shift_left"],
           ["fusion.9", 2000, 500,
            PATH + "expand/vmap(vmap(quorum))/eq"]]      # past the window
    return {"devices": {"/device:TPU:0": {
        "XLA Modules": [["jit_segment(1)", 0, 1000]], "XLA Ops": ops}},
        "host": [], "anchor": ["a", 0]}


def test_the_scope_is_found_inside_the_transforms_it_was_traced_under():
    assert quorumred.in_scope(PATH + "expand/vmap(vmap(quorum))/eq")
    assert quorumred.in_scope(PATH + "expand/quorum/eq")
    assert quorumred.in_scope("jit(step)/expand/vmap(quorum)/jit(_where)/x")
    assert not quorumred.in_scope(PATH + "expand/vmap(vmap())/eq")
    assert not quorumred.in_scope(PATH + "expand/quorums/eq")
    assert not quorumred.in_scope("")
    red = quorumred.scope_times(_trace(), 0, 1000)
    assert red["devices"] == 1 and red["ops"] == 1
    assert red["scope_ns"] == 100 and red["total_ns"] == 1000
    assert red["top_ops"] == [["fusion.1", 100]]
    # a program without the scope reads 0 there, and no device no reduction
    assert quorumred.scope_times(_trace(0), 0, 1000)["scope_ns"] == 0
    assert quorumred.scope_times(_trace(), 5000, 6000) is None


def test_the_quorum_readers_read_the_scope_a_step_and_as_a_share():
    ms, share = (mf.metric_reader(n) for n in NEW_METRICS[:2])
    ev = {"quorumred": quorumred.scope_times(_trace(), 0, 1000),
          "work": {"steps": 4}, "trace": {"segment_device_s": 1e-6}}
    assert ms(ev) == pytest.approx(100 / 1e6 / 4)
    assert share(ev) == pytest.approx(10.0)
    # a program whose step has no such scope: nothing, and no error
    ev["quorumred"] = quorumred.scope_times(_trace(0), 0, 1000)
    assert ms(ev) is None and share(ev) is None
    ev["quorumred"] = None                      # an untraced run
    assert ms(ev) is None and share(ev) is None
    untraced = {"passes": [passes.Pass(index=0, t_call=0.0)], "trace": None,
                "work": {"steps": 4}}
    assert ms(untraced) is None and share(untraced) is None


def test_phase2_states_share_reads_the_coverage_of_the_sound_passes():
    read = mf.metric_reader("phase2_states_share_pct")

    def p(cov, **kw):
        q = passes.Pass(index=0, t_call=0.0, **kw)
        q.coverage = cov
        return q

    cov = {"Phase1a": 3, "Phase1b": 525, "Phase2a": 320, "Phase2b": 3072}
    ev = {"passes": [p(cov), p(cov), p({"Phase2b": 9}, traced=True)]}
    assert read(ev) == pytest.approx(100.0 * 3392 / 3920)
    ev["passes"][1].problem = "stopped short"
    assert read(ev) == pytest.approx(100.0 * 3392 / 3920)
    assert read({"passes": [p(None)]}) is None
    assert read({"passes": [p({"Timeout": 5})]}) == 0.0


def test_the_new_readers_are_files_beside_the_old_ones():
    for name in NEW_METRICS:
        assert os.path.isfile(os.path.join(mf.BENCH, "metrics",
                                           name + ".py"))
    assert os.path.isfile(os.path.join(mf.BENCH, "harness", "quorumred.py"))
    assert os.path.isfile(os.path.join(mf.BENCH, "traffic",
                                       "passes_l14_l24.json"))
