"""The benchmark's fourth family (``benchmark/families/paxos_sym.py``), the
configuration ``paxos5sym`` and the cell ``paxos5sym.passes``: the manifest's
new entries, the plain reference's sort-based orbit name held to the
brute-force one, the family's refusals (a parent of this PR fails at once, by
name), a CPU rehearsal of the whole run at toy size through the harness
(``run.execute(rehearsal=True)``), a program whose key table has one row of a
wrong permutation failing it, the ``filter_only`` control at that size, and
the four new readers on hand-made evidence.  Nothing here is a measurement.
"""

import ast
import itertools
import os
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.families import paxos_ddd, paxos_sym as fam
from benchmark.harness import breakers, symred
from benchmark.harness import manifest as mf
from benchmark.harness import passes
from benchmark.reference import paxos as ref
from benchmark.reference import paxos_sym as sref

NEW_METRICS = ("symscan_ms", "symscan_step_share_pct",
               "symscan_ns_per_image", "symscan_roofline_pct")
CELL = "paxos5sym.passes"
# ISSUE 47's table (a separate transcription): new orbits a level, 0..36
ISSUE_LEVELS = (
    1, 3, 6, 10, 18, 33, 65, 129, 246, 456, 840, 1548, 2874, 5301, 9618,
    17078, 29129, 46751, 70585, 102930, 147956, 205822, 264206, 299439,
    291730, 241271, 168666, 99649, 49884, 21250, 7749, 2442, 673, 164, 36,
    6, 1)
CFG_TEXT = ("CONSTANTS\n  Acceptor = {a1, a2, a3}\n  Value = {v1, v2}\n"
            "  Quorum = {{a1, a2}, {a1, a3}, {a2, a3}}\n  None = None\n"
            "  Ballot <- MCBallot\nSPECIFICATION Spec\n"
            "INVARIANTS TypeOK Consistency\nSYMMETRY Acceptor Value\n")


def toy_cell(max_ballot: int = 1) -> dict:
    """Three acceptors, ballots 0..1 (443 orbits of 3,921 states, 17
    levels, |G| = 12): the cell at toy size."""
    cum = sref.bfs_orbit_levels(ref.model(3, 2, max_ballot))[0]
    cfg = {"name": "toy_paxos_sym", "family": "paxos_sym", "spec": "paxos",
           "bounds": {"n_acceptors": 3, "n_values": 2,
                      "max_ballot": max_ballot},
           "quorums": [["a1", "a2"], ["a1", "a3"], ["a2", "a3"]],
           "symmetry": ["Acceptor", "Value"],
           "invariants": ["TypeOK", "Consistency"],
           "chunk": 32, "cfg_text": CFG_TEXT,
           "engine_caps": {"ddd": {"block": 256, "table": 1024,
                                   "seg_rows": 2048, "levels": 64,
                                   "retention": "full"}},
           "sample_min_level_states": 32, "level_pins": cum}
    traffic = {"start": "init", "end": "pin", "start_level": 5,
               "end_level": 11, "min_passes": 3, "count_at_start": cum[5],
               "count_at_end": cum[11], "why": "rehearsal only"}
    return {"name": "toy.paxos_sym", "config": cfg["name"],
            "traffic": "toy_paxos_sym_traffic", "chips": 1,
            "config_data": cfg, "traffic_data": traffic}


# ------------------------------------------------------------- the manifest

def test_manifest_gains_the_configuration_the_cell_and_four_readers():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    # ten cells and nine configurations with this PR's (later PRs append)
    assert len(manifest["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2
    config = manifest["configs"][8]
    assert config["name"] == "paxos5sym" and config["reduced"] == ["depth"]
    assert config["file"] == "benchmark/configs/paxos5sym.json"
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    cell = manifest["workloads"][9]
    assert cell == {"name": CELL, "config": "paxos5sym",
                    "traffic": "passes_l18_l25", "chips": 1,
                    "why": cell["why"]}
    assert 1 <= len(cell["why"]) <= 200
    # the 65 entries PR 45 left, then these four (later PRs append after)
    readers = manifest["per_layer"][65:69]
    assert tuple(m["name"] for m in readers) == NEW_METRICS
    for m in readers:
        assert m["workloads"] == [CELL] and m["moves"] == "orbits_per_s"
        assert m["layer"] == "fused step" and m["source"] == "device_trace"
    assert [m["unit"] for m in readers] == ["ms/step", "%", "ns", "%"]
    # the cell reports the fourteen readers that carry no list and its four
    names = mf.metric_names(manifest, CELL, "per_layer")
    assert len(names) == 18 and names[-4:] == list(NEW_METRICS)
    assert mf.metric_names(manifest, CELL, "end_to_end") \
        == ["orbits_per_s", "setup_s"]
    # no accepted metric's list was touched: the cell is in its own four
    assert [m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", ())] == list(NEW_METRICS)


def test_the_configuration_is_the_sources_model_at_five_acceptors():
    cell = mf.cell(mf.load(), CELL)
    cfg, t = cell["config_data"], cell["traffic_data"]
    assert mf.family(cfg) is fam and mf.engine_of(cfg, 1) == ("ddd", 1)
    assert mf.end_of(t, cfg, cell["traffic"]) == "pin"
    assert cfg["bounds"] == {"n_acceptors": 5, "n_values": 2,
                             "max_ballot": 2}
    accs = ["a1", "a2", "a3", "a4", "a5"]
    assert cfg["quorums"] == [list(q)
                              for q in itertools.combinations(accs, 3)]
    assert cfg["symmetry"] == ["Acceptor", "Value"] and "init" not in cfg
    assert cfg["cfg_text"].endswith("SYMMETRY Acceptor Value\n")
    assert cfg["invariants"] == ["TypeOK", "Consistency"]
    assert set(cfg["reduced"]) == {"depth"} and cfg["chunk"] == 4096
    assert set(cfg["guarantees"]) == {"search", "dedup", "invariants",
                                      "symmetry", "stop"}
    assert "Quorum is checked invariant" in cfg["guarantees"]["symmetry"]
    # paxos3b4's capacities, what check.py gives a user
    assert cfg["engine_caps"] == mf.read_json(
        "configs", "paxos3b4.json")["engine_caps"]
    # the pins are the plain reference's own and equal ISSUE 47's table,
    # the whole space: 2,088,565 orbits in 37 levels
    pins = cfg["level_pins"]
    rows = [b - a for a, b in zip([0] + pins, pins)]
    assert tuple(rows) == ISSUE_LEVELS and pins[-1] == 2_088_565
    assert pins[:8] == sref.bfs_orbit_levels(fam.bounds(cfg), (), 100)[0]
    assert (t["start_level"], t["end_level"], t["min_passes"]) == (18, 25, 3)
    assert (pins[18], pins[25]) == (t["count_at_start"], t["count_at_end"]) \
        == (184_691, 1_738_045)
    # every level of the clocked span is one block, the widest level in it
    assert max(rows) == rows[23] == 299_439 < 1 << 20
    assert min(rows[18:25]) == 70_585
    config = fam.check_config(cfg)
    assert (config.spec, config.bounds.n_servers, config.bounds.n_values,
            config.bounds.max_term, config.chunk, config.invariants,
            config.symmetry) \
        == ("paxos", 5, 2, 2, 4096, ("TypeOK", "Consistency"),
            ("Acceptor", "Value"))
    assert len(dict(config.bounds.constants)["Quorum"]) == 10
    # the scan's work from the declared shapes alone: ISSUE 47's sizes
    shapes = fam.scan_shapes(cfg)
    assert shapes == {"F": 164, "N": 221_184, "G": 240, "actions": 54,
                      "row_words": 159}
    assert fam.scan_ops(cfg) == 2 * 8 * 164 * 221_184 * 240
    assert fam.scan_bytes(cfg) == 164 * 221_184 * 30 + 240 * 8 * 164
    assert cfg["chunk"] * 54 <= cfg["engine_caps"]["ddd"]["seg_rows"]
    # ... and the program's own shapes agree (not what the reader uses)
    from raft_tla_tpu.frontend import paxos as ppx
    lay = ppx.SCHEMA.layout(config.bounds)
    assert (lay.width, len(ppx.action_table(config.bounds))) == (159, 54)


def test_cfg_text_and_the_symmetry_are_held_to_the_fields_beside_them():
    cfg = toy_cell()["config_data"]
    assert fam.check_config(cfg).symmetry == ("Acceptor", "Value")
    with pytest.raises(ValueError, match="SYMMETRY Acceptor Value and "
                                         "nothing less"):
        fam.check_config(dict(cfg, symmetry=["Acceptor"]))
    with pytest.raises(ValueError, match="nothing less"):
        fam.check_config(dict(cfg, symmetry=[]))
    with pytest.raises(ValueError, match=r"cfg_text says SYMMETRY \[\]"):
        fam.check_config(dict(cfg, cfg_text=CFG_TEXT.replace(
            "SYMMETRY Acceptor Value\n", "")))
    with pytest.raises(ValueError, match="Quorum is not mapped onto itself"):
        fam.check_config(dict(cfg, quorums=[["a1", "a2"], ["a1", "a3"]]))
    with pytest.raises(ValueError, match="cfg_text says"):
        fam.check_config(dict(cfg, invariants=["Consistency"]))
    with pytest.raises(ValueError, match="states an Init"):
        fam.check_config(dict(cfg, init={}))
    # the unreduced family still refuses a SYMMETRY
    with pytest.raises(ValueError, match="no SYMMETRY"):
        paxos_ddd.check_config(cfg)


@pytest.mark.parametrize("how", ["parent", "other_sorts", "unknown_spec"])
def test_a_program_that_does_not_reduce_by_the_sorts_is_refused_by_name(
        how, monkeypatch):
    # what the parent of this PR answers: at once, out of check_config,
    # before any engine is built, and never a search of the unreduced space
    from raft_tla_tpu.frontend import registry
    if how == "parent":         # PR 46's PaxosModel: no such attribute
        monkeypatch.delattr(registry.SchemaModel, "sorts")
        said = (r"this program reduces spec 'paxos' by no symmetric sort "
                r"'Acceptor' \(it names none\); the configuration's "
                "SYMMETRY is Acceptor Value")
    elif how == "other_sorts":
        monkeypatch.setattr(registry.SchemaModel, "sorts", ("Acceptor",))
        said = r"no symmetric sort 'Value' \(it names Acceptor\)"
    else:
        real = registry.resolve_model

        def older(spec):
            if spec == "paxos":
                raise ValueError("unknown spec 'paxos'; known: full")
            return real(spec)

        monkeypatch.setattr(registry, "resolve_model", older)
        said = r"this program has no spec 'paxos' \(unknown spec 'paxos'\)"
    with pytest.raises(fam.NoDeviceEngine, match=said):
        fam.check_config(toy_cell()["config_data"])


# ------------------------------------------------------ the plain reference

def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(mf.BENCH, "reference", "paxos_sym.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            mods.add((node.module or "").split(".")[0])
    assert mods == {"__future__", "itertools", "multiprocessing", "os",
                    "sys", "time", "benchmark"}
    assert "raft_tla_tpu" not in open(path, encoding="utf-8").read()


@pytest.fixture(scope="module")
def space():
    """Every state of the 3-acceptor, ballots 0..1 space, by the unreduced
    reference."""
    m = ref.model(3, 2, 1)
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
    assert len(seen) == 3_921
    return m, seen


def test_canonical_is_brute_canonical_on_every_state_of_a_space(space):
    """The sort-based name and the least image over all twelve agree on
    which states are one orbit, on every state; each is a member of the
    orbit it names; and the space falls into the table's 443 orbits."""
    m, states = space
    by_sort, by_brute = {}, {}
    for s in states:
        c, b = sref.canonical(s, m), sref.brute_canonical(s, m)
        by_sort.setdefault(c, set()).add(s)
        by_brute.setdefault(b, set()).add(s)
        assert sref.canonical(c, m) == c and sref.brute_canonical(b, m) == b
        assert sref.brute_canonical(c, m) == b      # c is in s's orbit
    assert len(by_sort) == len(by_brute) == 443
    assert sorted(map(sorted, by_sort.values())) \
        == sorted(map(sorted, by_brute.values()))
    # an orbit has at most |G| members, and the sizes divide it
    assert {len(v) for v in by_sort.values()} <= {1, 2, 3, 4, 6, 12}


def test_an_image_has_its_states_successors_images(space):
    m, states = space
    pi, sigma = (2, 0, 1), (1, 0)
    for s in sorted(states)[::97]:
        img = sref.permute(s, pi, sigma)
        assert img in states and sref.canonical(img, m) \
            == sref.canonical(s, m)
        assert {sref.permute(t, pi, sigma)
                for _a, t in ref.successors(s, m)} \
            == {t for _a, t in ref.successors(img, m)}
        assert ref.consistency(img, m) == ref.consistency(s, m)
        assert ref.type_ok(img, m)
    assert sref.invariant_quorums(m)
    assert not sref.invariant_quorums(ref.model(3, 2, 1, [{0, 1}, {0, 2}]))


def test_the_orbit_bfs_counts_issue_47s_table_at_small_sizes():
    for (n, b), (orbits, levels, trans) in {
            (3, 1): (443, 17, 2_577), (3, 2): (17_153, 25, 121_880)}.items():
        cum, last, viol, got = sref.bfs_orbit_levels(ref.model(n, 2, b))
        assert (cum[-1], len(cum), got, viol) == (orbits, levels, trans, 0)
        assert len(last) == cum[-1] - cum[-2]
    # two worker processes count what one counts
    one = sref.bfs_orbit_levels(ref.model(3, 2, 1))
    two = sref.bfs_orbit_levels(ref.model(3, 2, 1), workers=2)
    assert (one[0], one[2], one[3]) == (two[0], two[2], two[3])
    # stopped at a level's size and at a level, like paxos.bfs_levels
    cum, level, _v, _t = sref.bfs_orbit_levels(ref.model(3, 2, 1), (), 32)
    assert len(level) >= 32 > cum[-2] - cum[-3]
    assert len(sref.bfs_orbit_levels(ref.model(3, 2, 1),
                                     max_level=4)[0]) == 5


def test_the_reference_counts_a_whole_space_from_the_command_line():
    out = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH, "reference", "paxos_sym.py"),
         "5", "2", "1", "2"], capture_output=True, text=True,
        check=True).stdout
    assert "|G|=240: 5811 orbits, 25 levels (diameter 24)" in out
    assert "49300 transitions, 0 violations" in out


@pytest.mark.parametrize("seed", [1, 2_147_483_659, 4_000_000_007])
def test_the_planted_fault_is_judged_on_orbits(seed):
    cfg = toy_cell()["config_data"]
    m = fam.bounds(cfg)
    _cum, level, _viol, _trans = sref.bfs_orbit_levels(m, (), 32)
    plant = fam.planted_fault(cfg, level, seed)
    parent = plant["parent"]
    assert fam.holds(parent, cfg) == [] and len(ref.chosen(parent, m)) == 1
    assert plant["violators"]
    found = 0
    for _a, nxt in ref.successors(parent, m):
        names = plant["violators"].get(plant["key"](nxt))
        if fam.holds(nxt, cfg):
            assert names == ["Consistency"]
            found += 1
        # a member of a violating orbit under another name is judged so too
        if names:
            assert plant["violators"][plant["key"](
                sref.permute(nxt, (1, 2, 0), (1, 0)))] == names
    assert found >= 1
    assert fam.planted_fault(cfg, level, seed)["parent"] == parent


# ------------------------------------------------- the run, rehearsed here

@pytest.fixture(scope="module")
def rehearsal():
    return run.execute(toy_cell(), mf.load(), 3_000_000_047, 0.0, False,
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


def test_one_wrong_row_of_the_key_table_fails_the_rehearsal(monkeypatch):
    """A program whose table of permuted constants holds, for one group
    element, the row of another permutation than the one it stands for (the
    identity's): two members of an orbit then miss their common key.  The
    pins or the sample's key <-> orbit correspondence refuse it."""
    from raft_tla_tpu.ops import symmetry as sym
    real = sym._schema_key_table

    def one_row_wrong(lay, consts, group):
        table = real(lay, consts, group).copy()
        table[1] = real(lay, consts, (group[5],))[0]
        return table

    monkeypatch.setattr(sym, "_schema_key_table", one_row_wrong)
    res = run.execute(toy_cell(), mf.load(), 3_000_000_047, 0.0, False,
                      rehearsal=True)
    assert res["correct"] is False
    bad = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert bad & {"sample_key_orbit_conflicts", "pass_level_mismatches"}


def test_the_filter_alone_does_not_dedup_the_toy():
    with breakers.filter_only_dedup():
        res = run.execute(toy_cell(), mf.load(), 12, 0.0, False,
                          rehearsal=True)
    assert res["correct"] is False


# ------------------------------------------ the new readers, by hand

PATH = "jit(segment)/while/body/"


def _trace(scan_ns: int = 600) -> dict:
    """One device that ran the segment module for 1,000 ns: a ``while`` that
    holds three fusions, one of them the scan's."""
    ops = [["while.1", 0, 1000, "jit(segment)/while"],
           ["fusion.1", 100, scan_ns, PATH + "orbit_scan/while/body/dot"],
           ["fusion.2", 750, 100, PATH + "expand/vmap(vmap(quorum))/eq"],
           ["fusion.3", 900, 50, PATH + "pack/shift_left"],
           ["fusion.9", 2000, 500, PATH + "orbit_scan/while/body/dot"]]
    return {"devices": {"/device:TPU:0": {
        "XLA Modules": [["jit_segment(1)", 0, 1000]], "XLA Ops": ops}},
        "host": [], "anchor": ["a", 0]}


def _evidence(tmp_path, scan_ns=600, images=True, cell_dir=CELL) -> dict:
    """Evidence as ``run.execute`` hands it to a reader, by hand: the stage
    table of ``_trace`` and an event log with two segments in the window."""
    from benchmark.harness import stagered
    pdir = tmp_path / cell_dir / "pass1"
    pdir.mkdir(parents=True, exist_ok=True)
    events = pdir / "run.events"
    lanes = 2 * 221_184

    def seg(t0, **extra):
        args = {"steps": 2, "lanes": lanes, "streamed_rows": 7, **extra}
        return ('{"v": 15, "event": "span", "ts": 0.0, "name": "segment", '
                '"thread": "segments", "t0": %r, "dur": 0.1, "span_id": 1, '
                '"args": %s}\n' % (t0, __import__("json").dumps(args)))

    extra = {"group": 240, "images": 240 * lanes} if images else {}
    events.write_text(seg(1.0, **extra) + seg(1.2, **extra)
                      + seg(5.0, **extra))       # the last: past the window
    p = passes.Pass(index=1, t_call=0.0, traced=True)
    p.events, p.t_a, p.t_trace_end = str(events), 0.9, 1.4
    p.trace_dir, p.anchor = str(pdir), (0, "a")
    return {"passes": [p], "work": {"steps": 4},
            "trace": {"segment_device_s": 1e-6},
            "stagered": {"stages": stagered.stage_times(
                _trace(scan_ns), 0, 1000)},
            "peaks": mf.peaks("TPU v5 lite")}


def test_the_four_readers_read_the_scope_the_images_and_the_roofline(
        tmp_path):
    ms, share, per_image, roof = (mf.metric_reader(n) for n in NEW_METRICS)
    ev = _evidence(tmp_path)
    red = symred.of(ev)
    assert red["scope_ns"] == 600 and red["total_ns"] == 1000
    assert red["window"] == {"segments": 2, "steps": 4,
                             "lanes": 4 * 221_184, "group": 240,
                             "images": 240 * 4 * 221_184}
    cfg = mf.read_json("configs", "paxos5sym.json")
    assert red["work"] == {"ops": fam.scan_ops(cfg),
                           "bytes": fam.scan_bytes(cfg)}
    assert ms(ev) == pytest.approx(600 / 1e6 / 4)
    assert share(ev) == pytest.approx(60.0)
    assert per_image(ev) == pytest.approx(600 / (240 * 4 * 221_184))
    pk = ev["peaks"]
    allowed = max(fam.scan_ops(cfg) / pk["int8_ops_per_s"],
                  fam.scan_bytes(cfg) / pk["hbm_bytes_per_s"])
    assert allowed == pytest.approx(fam.scan_bytes(cfg)
                                    / pk["hbm_bytes_per_s"])  # bytes bind
    assert roof(ev) == pytest.approx(100.0 * allowed / (600e-9 / 4))


def test_a_program_without_the_scope_or_the_counts_reads_nothing(tmp_path):
    ms, share, per_image, roof = (mf.metric_reader(n) for n in NEW_METRICS)
    # the parent's program: no op under orbit_scan, no images on its spans
    ev = _evidence(tmp_path, scan_ns=0, images=False)
    assert [r(ev) for r in (ms, share, per_image, roof)] == [None] * 4
    # the scope but spans older than the count: time, no time an image
    ev = _evidence(tmp_path, images=False)
    assert ms(ev) is not None and per_image(ev) is None
    assert symred.of(ev)["window"]["images"] is None
    # a cell the manifest does not know (a rehearsal's toy): no roofline
    ev = _evidence(tmp_path, cell_dir="toy.paxos_sym")
    assert symred.of(ev)["work"] is None
    assert roof(ev) is None and ms(ev) is not None
    # a family that counts no scan (an accepted cell's): no roofline either
    ev = _evidence(tmp_path, cell_dir="paxos3b4.passes")
    assert symred.of(ev)["work"] is None and roof(ev) is None
    # an untraced run
    untraced = {"passes": [passes.Pass(index=0, t_call=0.0)], "trace": None,
                "work": {"steps": 4}}
    assert [r(untraced) for r in (ms, share, per_image, roof)] == [None] * 4


def test_the_new_files_lie_beside_the_old_ones():
    for name in NEW_METRICS:
        assert os.path.isfile(os.path.join(mf.BENCH, "metrics",
                                           name + ".py"))
    for rel in (("harness", "symred.py"), ("families", "paxos_sym.py"),
                ("reference", "paxos_sym.py"),
                ("traffic", "passes_l18_l25.json"),
                ("configs", "paxos5sym.json")):
        assert os.path.isfile(os.path.join(mf.BENCH, *rel))
