#!/usr/bin/env python3
"""The benchmark's own tests; no chip needed:

    JAX_PLATFORMS=cpu python3 benchmark/selftest.py [-k substring]

Covers: the manifest's names and units; every cell's configuration, traffic
and metric files resolving by name; the window-rate and span-median
arithmetic and the pass clock, from Init and resumed from a snapshot; the
trace reduction on the recorded trace; the readers of a pass at depth on a
recorded span log; the plain reference against its own definition and the
planted fault (two leaders in a term, or, where the action table has no
BecomeLeader, a commit that a later leader lacks); a configuration's family
found by name (stated or absent, unknown, the second family's reference half
through the harness, what the harness may import); a toy-size REHEARSAL of one
whole run from Init, of one whose passes resume from a level-pinned snapshot,
of one on the mesh engine over four host devices, of one from a stated
Init under SYMMETRY Server and Value and of one whose passes run to the
space's own end (labelled as such, write no metric); the clock, the refusals
and the readers of a pass that ends by itself; and the controls — the
same rehearsals with one guarantee or the timed path broken underneath (the
exchange between shards misrouted, on the mesh; the last level dropped, or
the pass stopped at its total, where it should end by itself) have to come
out ``correct: false``.
"""

from __future__ import annotations

import itertools
import json
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.harness import manifest as mf          # noqa: E402
from benchmark.harness import passes, tracered, work  # noqa: E402


def toy_cell() -> dict:
    return {"name": "toy.rehearsal", "config": "toy_elect3",
            "traffic": "toy_traffic", "chips": 1,
            "config_data": mf.read_json("testdata", "toy_config.json"),
            "traffic_data": mf.read_json("testdata", "toy_traffic.json")}


def toy_resume_cell() -> dict:
    """The toy under a traffic whose passes resume from a snapshot of level
    12; full retention, as a resumed pass needs it (the toy's own frontier
    retention resumes in place)."""
    cell = toy_cell()
    cell.update(name="toy.resume", traffic="toy_resume",
                traffic_data=mf.read_json("testdata", "toy_resume.json"))
    cell["config_data"]["engine_caps"]["ddd"]["retention"] = "full"
    return cell


def toy_mesh_cell() -> dict:
    """The toy on the mesh engine: four shards (host devices here)."""
    cell = toy_cell()
    cell.update(name="toy.mesh4", config="toy_elect3_mesh4", chips=4,
                config_data=mf.read_json("testdata", "toy_mesh4.json"))
    return cell


def toy_repl_cell() -> dict:
    """The log-replication toy: its Init stated by the configuration (s1
    leads term 2), SYMMETRY over Server and Value."""
    return {"name": "toy.repl", "config": "toy_repl3",
            "traffic": "toy_repl_traffic", "chips": 1,
            "config_data": mf.read_json("testdata", "toy_repl3.json"),
            "traffic_data": mf.read_json("testdata",
                                         "toy_repl_traffic.json")}


def toy_verdict_cell() -> dict:
    """The replication toy under a traffic whose passes run to the space's
    own end (2,137 orbits, 24 levels past the stated Init)."""
    cell = toy_repl_cell()
    cell.update(name="toy.verdict", traffic="toy_repl_fixpoint",
                traffic_data=mf.read_json("testdata",
                                          "toy_repl_fixpoint.json"))
    return cell


def four_devices() -> None:
    """The mesh rehearsals need four devices: on the CPU four host devices,
    which have to be asked for before JAX opens its backend (``main`` does;
    a caller whose process already holds fewer gets the case in a child)."""
    import jax
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        try:
            jax.config.update("jax_num_cpu_devices", 4)
        except RuntimeError:
            pass                        # the backend is open already


def on_four_devices(test_name: str) -> bool:
    """True where this process can run a mesh case itself; otherwise the
    case is run by name in a child with four host devices and has to pass
    there."""
    import subprocess
    import jax
    if jax.device_count() >= 4:
        return True
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, os.path.abspath(__file__), "-k",
                           test_name], env=env, stdout=subprocess.PIPE,
                          stderr=subprocess.DEVNULL, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:]
    return False


RESUME_LOG = os.path.join(mf.BENCH, "testdata", "spans_resume_small.jsonl")
FIXPOINT_LOG = os.path.join(mf.BENCH, "testdata",
                            "spans_fixpoint_small.jsonl")


# ------------------------------------------------------------ the manifest

def test_manifest_meets_the_checkable_contract():
    assert mf.problems(mf.load()) == []


def test_every_cell_resolves_its_files_by_name():
    m = mf.load()
    for w in m["workloads"]:
        c = mf.cell(m, w["name"])
        pins = c["config_data"]["level_pins"]
        t = c["traffic_data"]
        assert pins[t["start_level"]] == t["count_at_start"]
        assert pins[t["end_level"]] == t["count_at_end"]
        assert t["min_passes"] >= 2
        for kind in ("end_to_end", "per_layer"):
            assert mf.metric_names(m, w["name"], kind)
    for metric in m["per_layer"]:
        assert callable(mf.metric_reader(metric["name"]))
    assert mf.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        mf.peaks("TPU v9")
        raise AssertionError("an unknown device kind got peaks")
    except KeyError:
        pass


def test_a_reader_with_nothing_to_read_returns_nothing():
    ev = {"trace": None, "summary": None, "passes": [], "hbm_peak_bytes": 0,
          "device": {"count": 1}}
    for name in ("scan_words_per_s", "step_hbm_share", "device_idle_share",
                 "export_wall_s", "dedup_exposed_s", "upload_wait_ms",
                 "pass_spread_pct", "peak_hbm_mb"):
        assert mf.metric_reader(name)(ev) is None, name
    ev = {"summary": passes.summarise([3.0, 1.0, 2.0]),
          "window": {"rate": 1.5, "ramp_share_pct": 40.0},
          "clocks": {"setup_s": 18.5, "open_s": 11.0, "compile_s": 7.0}}
    assert mf.metric_reader("orbits_per_s")(ev) == 1.5
    assert mf.metric_reader("pass_median_rate")(ev) == 2.0
    assert mf.metric_reader("ramp_share_pct")(ev) == 40.0
    assert mf.metric_reader("setup_s")(ev) == 18.5
    assert mf.metric_reader("pass_spread_pct")(ev) == 100.0


def test_a_cell_names_an_engine_it_can_hold():
    cfg = mf.read_json("testdata", "toy_mesh4.json")
    assert mf.engine_of(cfg, 4) == ("ddd-shard", 4)
    assert mf.engine_of(mf.read_json("testdata", "toy_config.json"),
                           1) == ("ddd", 1)        # no key: the ddd engine
    for change, chips, word in (({}, 1, "holds 1 chip"),
                                ({"engine": "bfs9"}, 4, "unknown engine"),
                                ({"engine": "ddd"}, 4, "on one device"),
                                ({"engine_caps": {}}, 4, "no engine_caps")):
        try:
            mf.engine_of(dict(cfg, **change), chips)
            raise AssertionError(f"{change} on {chips} chip(s) was accepted")
        except ValueError as e:
            assert word in str(e) and cfg["name"] in str(e), e
    # ... and the manifest's own check names the cell
    m = mf.load()
    cell = next(w for w in m["workloads"] if w["name"] == "elect5.shard4")
    assert cell["chips"] == 4
    cell["chips"] = 1
    assert any("elect5.shard4" in b and "holds 1 chip" in b
               for b in mf.problems(m))
    # a run of such a cell ends before it opens a device, by name
    from benchmark import run
    try:
        run.execute(dict(toy_mesh_cell(), chips=1), m, 1, 0.0, False,
                    rehearsal=True)
        raise AssertionError("a one-chip cell ran a four-device mesh")
    except SystemExit as e:
        assert "toy_elect3_mesh4 spans 4 devices" in str(e)


# ------------------------------------------------ a configuration's family

def _said(cell, seed):
    """The lines one untraced rehearsal of ``cell`` prints through
    ``run.say``, in order."""
    from benchmark import run
    lines, real = [], run.say
    run.say = lambda msg: (lines.append(msg), real(msg))
    try:
        res = run.execute(cell, mf.load(), seed, 0.0, False, rehearsal=True)
    finally:
        run.say = real
    assert res["correct"] is True
    return lines


def _check_lines(cell, seed):
    """The ``check <name>=<value> limit=<limit>`` lines of that rehearsal."""
    return [ln for ln in _said(cell, seed) if ln.startswith("check ")]


def test_a_family_stated_or_absent_is_the_same_run():
    from benchmark.families import raft
    cell = toy_cell()
    assert "family" not in cell["config_data"]
    assert mf.family(cell["config_data"]) is raft \
        is mf.family({"family": "raft"})
    absent = _check_lines(cell, 41)
    cell = toy_cell()
    cell["config_data"]["family"] = "raft"
    stated = _check_lines(cell, 41)
    assert absent == stated and len(absent) >= 8
    # every accepted configuration is the default family's
    m = mf.load()
    assert all("family" not in mf.cell(m, w["name"])["config_data"]
               for w in m["workloads"])


def test_an_untraced_run_prints_the_pass_ledgers_account():
    # the reducer of the program's pass ledger is asked once after the
    # window whatever --trace is: its line (and a stall's, where a pass
    # stalled) stands in an untraced run's log, after the verdict on correct
    import contextlib
    import io
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        _said(toy_cell(), 42)
    printed = out.getvalue().splitlines()
    account = [k for k, ln in enumerate(printed)
               if ln.startswith("pass ledger, untraced passes: ")]
    assert len(account) == 1
    red = json.loads(printed[account[0]].split(": ", 1)[1])
    assert red["passes"] >= 3 and red["ramp_by_seam_ms"] \
        and red["span_by_seam_ms"] and red["stall_s"] >= 0.0
    decided = next(k for k, ln in enumerate(printed)
                   if ln.startswith("correct=True decided"))
    assert decided < account[0]


def test_an_unknown_family_is_refused_by_name_before_any_device():
    from benchmark import run
    from benchmark.harness import drive
    for name in ("paxos", "../reference/canon", "_hidden", 7):
        try:
            mf.family({"name": "toy", "family": name})
            raise AssertionError(f"family {name!r} was found")
        except ValueError as e:
            assert "toy" in str(e) and repr(name) in str(e) \
                and "known: raft, twophase" in str(e), e
    opened, real = [], drive.open_device
    drive.open_device = lambda *a, **kw: opened.append(a) or real(*a, **kw)
    cell = toy_cell()
    cell["config_data"]["family"] = "paxos"
    try:
        run.execute(cell, mf.load(), 1, 0.0, False, rehearsal=True)
        raise AssertionError("a run of an unknown family")
    except SystemExit as e:
        assert "unknown family 'paxos'" in str(e) and "toy_elect3" in str(e)
    finally:
        drive.open_device = real
    assert opened == []
    # ... and the manifest's own check names the cell
    import tempfile
    m = mf.load()
    entry = next(c for c in m["configs"] if c["name"] == "elect5")
    cfg = dict(mf.read_json("configs", "elect5.json"), family="paxos")
    with tempfile.NamedTemporaryFile("w", suffix=".json") as f:
        json.dump(cfg, f)
        f.flush()
        entry["file"] = f.name
        assert any("elect5.passes" in b and "unknown family 'paxos'" in b
                   for b in mf.problems(m))


# what a family is for: the harness names nothing of a spec.  breakers.py is
# the one exception, by design: its controls patch the program underneath
# the timed path, and these are the symbols it patches.
_SPEC_MODULES = ("benchmark.reference", "models.interp", "ops.state",
                 "ops.kernels", "cfgparse")
_BREAKERS_PATCH = {"raft_tla_tpu.ops.kernels": "build_step",
                   "raft_tla_tpu.utils.keyset": "new_master, "
                                                "master_from_keys",
                   "raft_tla_tpu.parallel.ddd_shard_engine": "exchange",
                   "benchmark.harness.drive": "build_engine"}


def _imports(path: str) -> set:
    """Every module name ``path`` imports, ``from X import y`` as ``X`` and
    ``X.y``."""
    import ast
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            found.add(node.module)
            found.update(f"{node.module}.{a.name}" for a in node.names)
    return found


def test_the_harness_names_no_module_of_a_spec():
    harness = os.path.join(mf.BENCH, "harness")
    files = sorted(os.path.join(harness, f) for f in os.listdir(harness)
                   if f.endswith(".py"))
    files += [os.path.join(mf.BENCH, "run.py"),
              os.path.join(mf.BENCH, "control.py")]
    assert len(files) >= 18
    for path in files:
        names = _imports(path)
        with open(path, encoding="utf-8") as f:
            text = f.read()
        if path.endswith("breakers.py"):
            assert {n for n in names if n.startswith(("raft_tla_tpu.",
                                                      "benchmark."))} \
                == set(_BREAKERS_PATCH) | {
                    m.rsplit(".", 1)[0] for m in _BREAKERS_PATCH}, names
            continue
        for spec in _SPEC_MODULES:
            assert not any(spec in n for n in names), (path, spec)
            assert spec not in text, (path, spec)
    # the guard sees what it is there to see
    raft = _imports(os.path.join(mf.BENCH, "families", "raft.py"))
    assert all(any(spec in n for n in raft) for spec in _SPEC_MODULES)


def test_twophase_reference_counts_what_tlc_counts():
    from benchmark.reference import twophase as tp
    for n, total in ((2, 56), (3, 288), (4, 1568), (5, 8832)):
        cum, last, viol, _trans = tp.bfs_levels(n)
        assert cum[-1] == total and len(cum) == 3 * n + 2 and viol == 0
        assert len(last) == 2       # all committed, or all aborted
        assert {s.rmState for s in last} == {(tp.COMMITTED,) * n,
                                             (tp.ABORTED,) * n}
    cum, _last, viol, trans = tp.bfs_levels(3)
    assert [b - a for a, b in zip([0] + cum, cum)] \
        == [1, 7, 21, 38, 50, 54, 49, 36, 21, 9, 2]
    assert trans == 1145 and viol == 0
    # a search cut at a level's size stops there, unexpanded
    cum4, level, _v, _t = tp.bfs_levels(4, min_level_states=200)
    assert cum4 == [1, 10, 46, 134, 288, 504] and len(level) == 216
    # the packed form is one to one; the invariants see what they should
    states, frontier = {tp.init_state(3)}, {tp.init_state(3)}
    while frontier:
        frontier = {t for s in frontier for _a, t in tp.successors(s)} \
            - states
        states |= frontier
    assert len(states) == 288 == len({tp.pack(s) for s in states})
    taken = {a for s in states for (a, _rm), _t in tp.successors(s)}
    assert taken == set(tp.ACTIONS)
    bad = tp.init_state(3)._replace(
        rmState=(tp.ABORTED, tp.COMMITTED, tp.WORKING))
    assert tp.tp_type_ok(bad) and not tp.tc_consistent(bad)
    assert not tp.tp_type_ok(bad._replace(msgs=1 << 5))
    # it is the benchmark's own: nothing of the program, nor of its oracle
    names = _imports(tp.__file__)
    assert not any(n.startswith(("raft_tla_tpu", "frontend")) or
                   "frontend" in n for n in names), names


def _toy_twophase(n: int = 4) -> dict:
    return {"name": f"toy_twophase{n}", "family": "twophase",
            "bounds": {"n_rms": n}, "symmetry": [],
            "invariants": ["TPTypeOK", "TCConsistent"],
            "sample_min_level_states": 200}


def test_the_second_familys_reference_half_goes_through_the_harness():
    import numpy as np
    from benchmark.families import twophase as fam
    from benchmark.harness import correct
    from benchmark.reference import twophase as tp
    cfg = _toy_twophase(4)
    assert mf.family(cfg) is fam
    full = tp.bfs_levels(4)[0]
    plants = []
    for seed in (1, 2, 2_147_483_659):
        ref = correct.reference_sample(cfg, seed)
        assert ref["cumulative"] == full[:6] and ref["violations"] == 0
        assert len(ref["level"]) == 216 and len(ref["parents"]) == 216
        assert not any(fam.holds(s, cfg) for s in ref["level"])
        # the stream a sound engine would give: every successor, as a state,
        # under a key of its own
        states = [t for s in ref["parents"] for _a, t in tp.successors(s)]
        got = {"states": states, "con": [True] * len(states),
               "keys": np.asarray([tp.pack(t) for t in states], np.uint64),
               "n_transitions": len(states), "fail": 0, "done": True}
        checks = correct.sample_checks(ref, got, full)
        assert [v for _n, v, _l in checks] == [0] * 8, checks
        # ... and one that loses a successor, or names two states alike
        short = dict(got, states=states[1:], con=got["con"][1:],
                     keys=got["keys"][1:], n_transitions=len(states) - 1)
        failed = {n for n, v, lim in correct.sample_checks(ref, short, full)
                  if v > lim}
        assert "sample_transitions_diff" in failed
        alike = dict(got, keys=np.zeros(len(states), np.uint64))
        assert {n for n, v, lim in correct.sample_checks(ref, alike, full)
                if v > lim} == {"sample_key_orbit_conflicts"}
        plant = correct.planted_fault(cfg, ref["level"], seed)
        plants.append(plant)
        parent = plant["parent"]
        assert fam.holds(parent, cfg) == []
        assert parent.tmState == tp.TM_COMMITTED and parent.msgs & 1 << 4
        assert tp.PREPARED in parent.rmState and tp.ABORTED in parent.rmState
        assert plant["violators"] and all(
            v == ["TCConsistent"] for v in plant["violators"].values())
        by = {a for (a, _rm), t in tp.successors(parent)
              if plant["key"](t) in plant["violators"]}
        assert by == {"RMRcvCommitMsg"}
        hit = next(iter(plant["violators"]))
        assert [v for _n, v, _l in correct.planted_checks(
            plant, {"invariant": "TCConsistent", "state": hit})] == [0, 0]
        assert [v for _n, v, _l in correct.planted_checks(
            plant, {"invariant": "TPTypeOK", "state": hit})] == [0, 1]
        assert [v for _n, v, _l in correct.planted_checks(
            plant, {"invariant": None, "state": None})] == [1, 0]
    assert len({p["parent"] for p in plants}) > 1      # the seed draws it
    # what a configuration of this family may not say
    for change, word in (({"symmetry": ["RM"]}, "no SYMMETRY"),
                         ({"init": {}}, "starts from TPInit"),
                         ({"bounds": {"n_rms": 0}}, "n_rms"),
                         ({"invariants": ["TPTypeOK"]},
                          "lists no invariant it breaks")):
        try:
            bad = dict(cfg, **change)
            correct.planted_fault(bad, correct.reference_sample(
                bad, 1)["level"], 1)
            raise AssertionError(f"{change} was accepted")
        except ValueError as e:
            assert word in str(e), e


def test_the_second_familys_program_half_says_what_it_waits_for():
    from benchmark.families import raft, twophase as fam
    cfg = _toy_twophase(4)
    s = fam.bfs_levels(cfg, 10)[1][0]
    for call in (lambda: fam.check_config(cfg), lambda: fam.to_program(s),
                 lambda: fam.from_program(s),
                 lambda: fam.pack_rows(None, [s]),
                 lambda: fam.decode_rows(None, []),
                 lambda: fam.gates(None, cfg), lambda: fam.scan_words(None)):
        try:
            call()
            raise AssertionError("the TwoPhase family drove a device engine")
        except fam.NoDeviceEngine as e:
            assert "no device engine runs this family yet " \
                "(ROADMAP queue 2 A.1)" in str(e)
    # the two families answer to the same names
    def surface(mod):
        return {n for n, f in vars(mod).items() if not n.startswith("_")
                and callable(f) and f.__module__ == mod.__name__}
    assert surface(raft) == surface(fam) - {"NoDeviceEngine"} == {
        "check_config", "bounds", "stated_init", "to_program",
        "from_program", "bfs_levels", "successor_orbits", "orbit_key",
        "holds", "pack_rows", "decode_rows", "planted_fault", "scan_words",
        "gates"}


# ------------------------------------------------- the reading's arithmetic

def test_the_window_rate_keeps_a_stall_that_the_span_median_drops():
    def made(ramps, spans):
        t, out = 0.0, []
        for k, (r, sp) in enumerate(zip(ramps, spans)):
            out.append(passes.Pass(index=k, t_call=t, t_a=t + r,
                                   t_b=t + r + sp, t_return=t + r + sp + 0.1))
            t += r + sp + 0.1
        return out, t
    # four passes of 1000 orbits to B, 600 of them in the clocked span
    steady, w0 = made([2.0] * 4, [4.0] * 4)
    stalled, w1 = made([2.0, 2.0, 15.0, 2.0], [4.0, 4.0, 4.0, 5.0])
    a = passes.window_rate(steady, 1000, w0)
    b = passes.window_rate(stalled, 1000, w1)
    assert abs(a["rate"] - 4000 / 24.4) < 1e-9 and a["orbits"] == 4000
    assert abs(b["rate"] - 4000 / 38.4) < 1e-9          # both stalls count
    assert abs(a["ramp_share_pct"] - 100 * 8 / 24.4) < 1e-9
    stalled[2].traced = True                 # read on untraced passes only
    assert abs(passes.window_rate(stalled, 1000, w1)["ramp_share_pct"]
               - 100 * 6 / 19.3) < 1e-9
    stalled[2].traced = False
    stalled[1].problem = "short of B"        # a failed pass: time, no work
    assert passes.window_rate(stalled, 1000, w1)["orbits"] == 3000
    # the span median sees neither the ramp stall nor one slow span
    s = passes.summarise([p.rate(600) for p in stalled])
    assert s["median"] == 150.0
    assert abs(s["spread_pct"] - 100 * (150.0 - 120.0) / 150.0) < 1e-9
    assert passes.summarise([5.0, 1.0, 3.0])["median"] == 3.0


def test_whole_passes_until_the_window_is_used_at_least_three():
    assert passes.room_for_another(31.0, 10.0, 40.0, 2, 3)       # minimum
    assert passes.room_for_another(29.0, 10.0, 40.0, 3, 3)       # fits
    assert not passes.room_for_another(31.0, 10.0, 40.0, 3, 3)   # would spill
    assert passes.room_for_another(99.0, 10.0, 0.0, 1, 3)        # --seconds 0


def test_span_clock_stamps_at_the_pinned_counts_only():
    import signal
    hits = []
    old = signal.signal(signal.SIGINT, lambda *_: hits.append(1))
    try:
        pins = [1, 50, 100, 250, 400, 900]
        p = passes.Pass(index=0, t_call=time.monotonic())
        clock = passes.SpanClock(p, pins, 2, 4)
        # (count, level): 100 shows up early as a mid-level upper bound and
        # must not start the clock; the boundary record of level 2 does
        for n, lvl in ((50, 1), (100, 1), (100, 2), (250, 3), (400, 4)):
            assert p.t_a is None or lvl > 1
            clock({"n_states": n, "level": lvl})
        assert p.reached and p.t_b >= p.t_a and hits == [1]

        class R:
            levels = [1, 49, 50, 150, 150]
            violation = None
            n_states = 400
            coverage = {}
        passes.finish(p, R, pins, 4)
        assert p.problem is None and p.levels == [1, 50, 100, 250, 400]
        assert abs(p.rate(300) - 300 / (p.t_b - p.t_a)) < 1e-6
        # past B with no boundary record at the pin: stopped, failed
        q = passes.Pass(index=1, t_call=time.monotonic())
        clock = passes.SpanClock(q, pins, 2, 4)
        for n, lvl in ((100, 2), (401, 4), (950, 5)):
            clock({"n_states": n, "level": lvl})
        assert not q.reached and "no level-4 boundary" in q.problem
        assert hits == [1, 1]
        # a level table off the pins
        r = passes.Pass(index=2, t_call=0.0, t_a=1.0, t_b=2.0)
        R.levels = [1, 49, 50, 151, 149]
        passes.finish(r, R, [1, 50, 100, 250, 400], 4)
        assert "level 3" in r.problem
    finally:
        signal.signal(signal.SIGINT, old)


def test_the_mesh_engines_boundary_record_runs_one_level_ahead():
    import signal
    hits, closed = [], []
    old = signal.signal(signal.SIGINT, lambda *_: hits.append(1))
    try:
        pins = [1, 50, 100, 250, 400, 900]
        p = passes.Pass(index=0, t_call=time.monotonic())
        clock = passes.SpanClock(p, pins, 2, 4, None, closed.append,
                                 level_ahead=1)
        # the mesh engine: in-window records carry the count of the last
        # drain, the boundary record the level about to open; both repeat
        for n, lvl in ((50, 2), (50, 2), (100, 3), (100, 3), (100, 3),
                       (250, 4), (250, 4), (400, 5)):
            clock({"n_states": n, "level": lvl})
            assert (p.t_a is None) == (n < 100)
        assert p.reached and hits == [1] and len(closed) == 1
        clock({"n_states": 400, "level": 5})     # from the overshoot window
        assert hits == [1]                        # one SIGINT, never two
        # the ddd engine's pair (level 2, 100) is no boundary here
        q = passes.Pass(index=1, t_call=0.0)
        passes.SpanClock(q, pins, 2, 4, level_ahead=1)(
            {"n_states": 100, "level": 2})
        assert q.t_a is None
    finally:
        signal.signal(signal.SIGINT, old)


def test_chunk_steps_and_words_from_shapes():
    pins = [1, 2, 6, 5000, 9000]
    # level 2 -> 3: frontier of 4 rows = 1 step; level 3 -> 4: 4994 rows in
    # blocks of 4096 = ceil(4096/1024) + ceil(898/1024) = 4 + 1
    assert work.chunk_steps(pins, 2, 4, 4096, 1024) == 1 + 5
    # four shards, a window of 4 x 2048 rows dealt block by block: shard 0
    # takes 2048 of the 4994, and the window runs as long as it does
    assert work.chunk_steps(pins, 2, 4, 2048, 1024, 4) == 1 + 2
    assert work.chunk_steps(pins, 2, 4, 1024, 1024, 4) == 1 + 1 + 1
    # one shard's bytes a step: its chunk in, a bucket for every lane the
    # four shards send it
    assert work.step_bytes(4096, 38, 11) == 4096 * 11 * 4 + 4096 * 38 * 8 * 8
    assert work.step_bytes(4096, 38, 11, 4) \
        == 4096 * 11 * 4 + 4 * 4096 * 38 * 8 * 8
    assert work.step_bytes(4096, 38, 11, 4, send=1000) \
        == 4096 * 11 * 4 + 4 * 1000 * 8 * 8
    assert work.scan_words(4096, 38, 5, 104, True) == 4096 * 38 * 120 * 104
    assert work.scan_words(4096, 38, 5, 104, False) == 4096 * 38 * 104


def test_a_resumed_pass_is_clocked_from_its_run_start_and_counts_less():
    import signal
    hits = []
    old = signal.signal(signal.SIGINT, lambda *_: hits.append(1))
    try:
        pins = [1, 50, 100, 250, 400, 900]
        # the recorded log of a resumed toy pass: its run_start holds the
        # engine's clock and first count
        began = passes.run_start(RESUME_LOG)
        assert began == {"mono": 1956.552841, "n_states": 2325}
        p = passes.Pass(index=0, t_call=began["mono"] - 0.5, resumed=True,
                        events=RESUME_LOG)
        clock = passes.SpanClock(p, pins, 2, 4)
        # resumed from level 2: the first boundary record is level 3's
        for n, lvl in ((180, 3), (250, 3), (400, 4)):
            clock({"n_states": n, "level": lvl})
        assert p.t_a is None and p.t_b is not None and hits == [1]

        class R:
            levels = [1, 49, 50, 150, 150]
            violation = None
            n_states = 400
            coverage = {}
        passes.finish(p, R, pins, 4)
        assert p.problem is None and p.reached and p.n_states == 400
        assert p.t_a == began["mono"] and p.start_keys == 2325
        assert abs(p.ramp_s - 0.5) < 1e-9              # the resume
        # a pass from Init is never stamped B before A
        q = passes.Pass(index=1, t_call=0.0)
        passes.SpanClock(q, pins, 2, 4)({"n_states": 400, "level": 4})
        assert q.t_b is None and hits == [1]
    finally:
        signal.signal(signal.SIGINT, old)
    # the window: pins[B] less the snapshot's keys for every sound pass
    made = [passes.Pass(index=k, t_call=10.0 * k, t_a=10.0 * k + 1,
                        t_b=10.0 * k + 9, t_return=10.0 * k + 9.5,
                        resumed=True) for k in range(3)]
    made[1].problem = "short of B"
    win = passes.window_rate(made, 400 - 102, 30.0)
    assert win["orbits"] == 2 * 298 and abs(win["rate"] - 596 / 30.0) < 1e-9


def test_snapshot_checks_hold_the_snapshot_to_the_pin_and_the_passes_to_it():
    from benchmark.harness import correct
    pins = [1, 50, 100, 250, 400, 900]
    snap = {"level": 2, "keys": 102, "problem": None}
    made = [passes.Pass(index=k, t_call=0.0, resumed=True, start_keys=102)
            for k in range(2)]
    got = dict((n, (v, lim)) for n, v, lim in
               correct.snapshot_checks(snap, made, pins, 4))
    assert got == {"snapshot_pass_problems": (0, 0),
                   "snapshot_overshoot_orbits": (2, 6),     # 2 % of 300
                   "snapshot_keys_diff": (0, 0)}
    made[1].start_keys = 101            # a key went missing on the way
    made.append(passes.Pass(index=2, t_call=0.0, resumed=True))  # no log
    snap.update(keys=93, problem="level table differs")
    got = dict((n, v) for n, v, _l in
               correct.snapshot_checks(snap, made, pins, 4))
    assert got == {"snapshot_pass_problems": 1,
                   "snapshot_overshoot_orbits": 7,
                   "snapshot_keys_diff": 9 + 8 + 93}


def test_the_capture_of_a_resumed_pass_closes_by_steps():
    from benchmark.harness import drive
    stops = []
    p = passes.Pass(index=0, t_call=0.0, resumed=True, events=RESUME_LOG)
    closer = drive.CaptureCloser(p, 40, lambda: stops.append(1))
    closer.start()
    closer.finish()
    # levels 13, 14, 15 hold 13 + 7 + 19 = 39 steps; the 29-step segment of
    # level 16 brings them past 40, and its harvest ends the window
    assert stops == [1] and abs(p.t_trace_end - 1956.769046) < 1e-6
    q = passes.Pass(index=1, t_call=0.0, resumed=True, events=RESUME_LOG)
    closer = drive.CaptureCloser(q, 1000, lambda: stops.append(1))
    closer.start()
    closer.finish()                      # the pass ended first
    assert stops == [1] and q.t_trace_end is None


class _FakeEngine:
    """Stands in for the program: records how check() was called, feeds the
    clock one boundary record a level and stops at the first SIGINT."""

    seg_chunks = 7
    fault: dict = {}      # what a broken engine would get wrong at its end

    def __init__(self, pins):
        self.pins, self.calls, self.stop = pins, [], False

    def check(self, on_progress, **kw):
        self.calls.append(kw)
        first = 13 if "resume" in kw else 0
        if "events" in kw:
            with open(kw["events"], "w", encoding="utf-8") as f:
                f.write(json.dumps({"event": "run_start", "n_states":
                                    self.pins[first - 1] if first else 1,
                                    "anchor": {"mono": time.monotonic()}})
                        + "\n")
        self.stop, n = False, first
        for n in range(first, len(self.pins)):
            on_progress({"level": n, "n_states": self.pins[n]})
            if self.stop:
                break
        else:
            # nobody stopped it: the last frontier expands to nothing
            on_progress({"level": n + 1, "n_states": self.pins[n]})
        fault = self.fault

        class Result:
            levels = [self.pins[0]] + [b - a for a, b in zip(
                self.pins, self.pins[1:n + 1])] + fault.get("more", [])
            violation = None
            n_states = self.pins[n] + fault.get("count_off", 0)
            coverage = {"Timeout": self.pins[n] - 1}
            complete = not self.stop and fault.get("complete", True)
        return Result


def _drive_fake(cell, fault=None):
    import signal
    import tempfile
    from benchmark.harness import drive
    eng = _FakeEngine(cell["config_data"]["level_pins"])
    eng.fault = fault or {}
    real = drive.build_engine
    drive.build_engine = lambda cfg: eng
    old = signal.signal(signal.SIGINT,
                        lambda *_: setattr(eng, "stop", True))
    try:
        with tempfile.TemporaryDirectory() as scratch:
            drv = drive.Driver(cell, scratch)
            warm = drv.run_pass(end_level=3, start_level=1)
            snap = drv.build_snapshot()
            made = [drv.timed_pass(), drv.timed_pass()]
            calls = [dict(c, events=os.path.relpath(c["events"], scratch))
                     if "events" in c else c for c in eng.calls]
            return drv, warm, snap, made, calls
    finally:
        signal.signal(signal.SIGINT, old)
        drive.build_engine = real


def test_a_traffic_with_no_start_drives_the_calls_it_always_drove():
    # from Init: check(on_progress=) and nothing else, no snapshot pass
    drv, warm, snap, made, calls = _drive_fake(toy_cell())
    assert snap is None and drv.snapshot is None and calls == [{}, {}, {}]
    assert warm.problem is None and all(p.problem is None for p in made)
    assert not any(p.resumed or p.events for p in made)
    assert drv.pass_orbits == 6652 and drv.orbits == 6652 - 2325
    cell = toy_cell()
    cell["traffic_data"]["start"] = "init"
    assert _drive_fake(cell)[4] == [{}, {}, {}]
    assert all(p.coverage == {"Timeout": 6651} for p in made)
    # a configuration that states its Init: every pass, the warm one too, is
    # handed it as init_override (the program's own state class), and
    # nothing else joins the call
    drv, warm, snap, made, calls = _drive_fake(toy_repl_cell())
    assert snap is None and [sorted(c) for c in calls] \
        == [["init_override"]] * 3
    start = calls[0]["init_override"]
    assert all(c["init_override"] is start for c in calls)
    assert type(start).__module__ == "raft_tla_tpu.models.interp"
    assert (start.role, start.term, start.votedFor, start.log, start.msgs) \
        == ((2, 0, 0), (2, 2, 2), (1, 1, 1), ((), (), ()), ())
    assert warm.problem is None and all(p.problem is None for p in made)
    # ... and it cannot yet be combined with passes resumed from a snapshot
    cell = toy_repl_cell()
    cell.update(traffic_data=dict(cell["traffic_data"],
                                  start={"snapshot_level": 12}))
    try:
        _drive_fake(cell)
        raise AssertionError("a stated Init took a snapshot traffic")
    except ValueError as e:
        assert "states its Init" in str(e)
    # from a snapshot of level 12: one pass from Init writes it through the
    # public checkpoint arguments, every timed pass resumes from it
    drv, warm, snap, made, calls = _drive_fake(toy_resume_cell())
    path = drv.snapshot["path"]
    assert path.endswith(os.path.join("snap", "run"))
    assert calls == [
        {},
        {"checkpoint": path, "checkpoint_every_s": float("inf"),
         "events": os.path.join("pass1", "run.events")},
        {"resume": path, "events": os.path.join("pass2", "run.events")},
        {"resume": path, "events": os.path.join("pass3", "run.events")}]
    assert snap.problem is None and drv.snapshot["keys"] == 2325
    assert drv.pass_orbits == drv.orbits == 6652 - 2325
    assert all(p.problem is None and p.resumed and p.start_keys == 2325
               and p.t_call <= p.t_a <= p.t_b for p in made)
    # a configuration that resumes in place cannot share one snapshot
    cell = toy_resume_cell()
    cell["config_data"]["engine_caps"]["ddd"]["retention"] = "frontier"
    try:
        _drive_fake(cell)
        raise AssertionError("frontier retention took a snapshot traffic")
    except ValueError as e:
        assert "one copy a pass" in str(e)
    cell = toy_resume_cell()
    cell["traffic_data"]["start_level"] = 11
    try:
        _drive_fake(cell)
        raise AssertionError("a span that starts off its snapshot")
    except ValueError as e:
        assert "starts at its snapshot" in str(e)


# ---------------------------------------- a pass that runs to its own end

def test_a_fixpoint_traffic_is_refused_where_it_cannot_end():
    cell = toy_verdict_cell()
    cfg, t = cell["config_data"], cell["traffic_data"]
    assert mf.end_of(t, cfg, "toy_repl_fixpoint") == "fixpoint"
    assert mf.end_of(toy_cell()["traffic_data"], cfg, "toy_traffic") == "pin"
    assert mf.end_of(dict(t, end="pin"), cfg, "x") == "pin"
    mesh = dict(mf.read_json("testdata", "toy_mesh4.json"))
    for traffic, conf, word in (
            (dict(t, end_level=23, count_at_end=2129), cfg,
             "has to be the last level configuration toy_repl3 pins, 24"),
            (dict(t, start={"snapshot_level": 12}), cfg,
             "starts from a snapshot"),
            (dict(t, end_level=31), mesh, "names the engine 'ddd-shard'"),
            (dict(t, end="never"), cfg, "unknown end 'never'")):
        try:
            mf.end_of(traffic, conf, "toy_repl_fixpoint")
            raise AssertionError(f"{word!r}: accepted")
        except ValueError as e:
            assert word in str(e) and "toy_repl_fixpoint" in str(e), e
    # the Driver refuses it too, and a run ends before it opens a device
    from benchmark import run
    bad = toy_verdict_cell()
    bad["traffic_data"].update(end_level=23, count_at_end=2129)
    try:
        _drive_fake(bad)
        raise AssertionError("a fixpoint traffic that ends short was driven")
    except ValueError as e:
        assert "last level" in str(e)
    from benchmark.harness import drive
    real = drive.open_device
    drive.open_device = lambda *a, **kw: (_ for _ in ()).throw(
        AssertionError("a device was opened"))
    try:
        run.execute(bad, mf.load(), 1, 0.0, False, rehearsal=True)
        raise AssertionError("a fixpoint traffic that ends short ran")
    except SystemExit as e:
        assert "toy_repl_fixpoint runs to the fixpoint" in str(e)
    finally:
        drive.open_device = real


def test_the_clock_lets_a_fixpoint_pass_end_by_itself():
    import signal
    hits, closed = [], []
    old = signal.signal(signal.SIGINT, lambda *_: hits.append(1))
    try:
        pins = [1, 50, 100, 250, 400, 410]
        p = passes.Pass(index=0, t_call=time.monotonic())
        clock = passes.SpanClock(p, pins, 2, 5, None, closed.append,
                                 fixpoint=True)
        # every boundary, an in-level record at the total, the boundary of
        # the last level and the empty expansion past it: nothing is raised
        for n, lvl in ((50, 1), (100, 2), (250, 3), (400, 4), (410, 5),
                       (410, 5), (410, 6)):
            clock({"n_states": n, "level": lvl})
        assert hits == [] and len(closed) == 1      # the traced level A+1
        assert p.t_a is not None and p.t_last is not None and p.t_b is None
        assert p.problem is None

        class R:
            levels = [1, 49, 50, 150, 150, 10]
            violation = None
            n_states = 410
            coverage = {}
            complete = True
        passes.finish(p, R, pins, 5, fixpoint=True)
        assert p.problem is None and p.reached and p.fixpoint
        assert p.t_b == p.t_return and p.levels == pins
        assert p.verdict_s == p.t_return - p.t_call
        assert 0.0 <= p.close_s <= p.verdict_s and p.overshoot_s == 0.0
        # a pass stopped at a pin has no verdict to clock
        q = passes.Pass(index=1, t_call=0.0, t_a=1.0, t_b=2.0)
        passes.finish(q, R, pins, 5)
        assert q.problem is None and q.verdict_s is None and not q.fixpoint
        # a record past the total stops the pass, failed, once
        r = passes.Pass(index=2, t_call=time.monotonic())
        clock = passes.SpanClock(r, pins, 2, 5, fixpoint=True)
        for n, lvl in ((100, 2), (410, 5), (411, 6), (450, 6)):
            clock({"n_states": n, "level": lvl})
        assert hits == [1] and "ran past the last pin 410" in r.problem
        # what finish holds a fixpoint pass to, fault by fault
        for change, word in (
                ({"complete": False}, "complete = False"),
                ({"n_states": 409}, "409 orbits, the last pin is 410"),
                ({"levels": R.levels + [1], "n_states": 411},
                 "overshoot levels [411] exceed the pins []"),
                ({"levels": R.levels[:-1], "n_states": 400},
                 "400 orbits, the last pin is 410")):
            f = passes.Pass(index=3, t_call=0.0, t_a=1.0, t_last=2.0)
            passes.finish(f, type("F", (R,), change), pins, 5, fixpoint=True)
            assert f.problem and word in f.problem, (change, f.problem)
    finally:
        signal.signal(signal.SIGINT, old)


def test_a_fixpoint_traffic_is_stopped_by_nothing_and_held_to_the_verdict():
    from benchmark.harness import correct
    cell = toy_verdict_cell()
    pins = cell["config_data"]["level_pins"]
    drv, warm, snap, made, calls = _drive_fake(cell)
    # the calls a stated Init always made; the warm pass stops at level 3
    # by SIGINT, a timed pass is stopped by nothing and returns complete
    assert snap is None and [sorted(c) for c in calls] \
        == [["init_override"]] * 3 and drv.fixpoint
    assert warm.problem is None and not warm.fixpoint \
        and warm.complete is False and len(warm.levels) == 4
    assert (drv.a, drv.b, drv.pass_orbits, drv.orbits) \
        == (12, 24, 2137, 2137 - 565)
    for p in made:
        assert p.problem is None and p.fixpoint and p.complete is True
        assert p.levels == pins and p.overshoot_levels == []
        assert p.t_call <= p.t_a <= p.t_last <= p.t_b == p.t_return
    sound = {n: v for n, v, _l in correct.pass_checks(made, pins, 24)
             + correct.fixpoint_checks(made, pins)}
    assert set(sound.values()) == {0} and {
        "passes_incomplete", "fixpoint_total_diff"} <= set(sound)
    ev = {"passes": made}
    assert mf.metric_reader("verdict_wall_s")(ev) > 0.0
    assert mf.metric_reader("fixpoint_close_s")(ev) >= 0.0
    assert mf.metric_reader("levels_per_pass")(ev) == 25
    # an engine that gets its end wrong fails the check that names it
    for fault, wrong in (
            ({"complete": False}, {"passes_incomplete": 2}),
            ({"count_off": -8}, {"fixpoint_total_diff": 16}),
            # a state past the last pin, in a result that still says 2,137
            ({"more": [1]}, {"fixpoint_total_diff": 2})):
        _d, _w, _s, broken, _c = _drive_fake(toy_verdict_cell(), fault)
        assert all(p.problem is not None for p in broken), fault
        got = {n: v for n, v, _l in correct.pass_checks(broken, pins, 24)
               + correct.fixpoint_checks(broken, pins) if v}
        assert got == wrong, (fault, got)
        for n in ("verdict_wall_s", "fixpoint_close_s", "levels_per_pass"):
            assert mf.metric_reader(n)({"passes": broken}) is None, n
    # a pass stopped at a pin reads none of the three
    _d, _w, _s, pinned, _c = _drive_fake(toy_repl_cell())
    assert all(p.problem is None and p.complete is False for p in pinned)
    for n in ("verdict_wall_s", "fixpoint_close_s", "levels_per_pass"):
        assert mf.metric_reader(n)({"passes": pinned}) is None, n


def test_readers_of_a_pass_that_ends_by_itself_on_the_recorded_toy_pass():
    from benchmark.harness import spanred, tailred
    spans = spanred.load(FIXPOINT_LOG)
    # the replication toy to its end, chunk 64: the most rows any level
    # expands are level 18's 234; after it, levels 23-25 admit 30, 8 and 0
    red = tailred.reduce(spans, 64)
    wall = red.pop("tail_wall_s")
    assert red == {"levels": 25, "peak_level": 18, "peak_frontier_rows": 234,
                   "tail_levels": [23, 24, 25],
                   "tail_new_states": [30, 8, 0]}
    assert abs(wall - (0.012846 + 0.007973 + 0.005527)) < 1e-9
    # under a smaller chunk only the empty expansion is the tail
    assert tailred.reduce(spans, 8)["tail_levels"] == [25]
    assert tailred.reduce([s for s in spans if s["name"] != "level"],
                          64) is None
    traced = passes.Pass(index=2, t_call=0.0, t_a=1.0, t_b=2.0,
                         t_trace_end=1.5, traced=True, fixpoint=True,
                         events=FIXPOINT_LOG)
    ev = {"passes": [traced], "work": {"chunk": 64}}
    assert mf.metric_reader("peak_frontier_rows")(ev) == 234
    assert abs(mf.metric_reader("tail_levels_s")(ev) - wall) < 1e-12
    # nothing to read: an untraced run; a pass whose levels never fall
    # under a chunk after the peak (a pass cut on the way up)
    bare = {"passes": [passes.Pass(index=0, t_call=0.0, t_a=1.0, t_b=2.0)],
            "work": {"chunk": 64}}
    for n in ("peak_frontier_rows", "tail_levels_s"):
        assert mf.metric_reader(n)(bare) is None, n
    rising = [s for s in spans if s["name"] != "level"
              or s["args"]["level"] <= 18]
    assert tailred.reduce(rising, 64)["tail_wall_s"] is None


def rehearse_verdict(seed: int, trace: bool = False) -> dict:
    from benchmark import run
    return run.execute(toy_verdict_cell(), mf.load(), seed, 0.0, trace,
                       rehearsal=True)


def test_rehearsal_of_passes_that_end_by_themselves_is_correct():
    res = rehearse_verdict(3_000_000_041, trace=True)
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["correct"] is True
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert res["checks"]["passes_incomplete"] == {"value": 0, "limit": 0}
    assert res["checks"]["fixpoint_total_diff"] == {"value": 0, "limit": 0}
    # a pass stopped at a pin compares every number but those two
    assert set(res["checks"]) - set(rehearse_repl(43)["checks"]) \
        == {"passes_incomplete", "fixpoint_total_diff"}


def test_a_dropped_last_level_comes_out_not_correct():
    # the timed path broken underneath: the frontier that bears the last
    # level is handed to the compiled segment empty, so the engine returns
    # complete = True a level early, 8 orbits short a pass
    from benchmark.harness import breakers
    with breakers.drops_last_level():
        res = rehearse_verdict(44)
        assert rehearse_repl(45)["correct"] is True    # stopped at a pin
    assert res["correct"] is False and res["failed"] == res["attempted"]
    wrong = {n: c["value"] for n, c in res["checks"].items()
             if c["value"] > c["limit"]}
    assert wrong == {"fixpoint_total_diff": 8 * res["attempted"],
                     "pass_level_mismatches": res["attempted"],
                     "passes_short_of_B": res["attempted"]}


def test_a_pass_stopped_at_the_total_has_no_verdict():
    # what every older cell does, done to this one: the pass is stopped at
    # the record of the last pin.  Every count holds and every older number
    # reads 0; the empty last expansion was never made, so the engine says
    # complete = False, and the one new number sees it
    import signal
    from benchmark.harness import drive
    real = drive.build_engine
    total = toy_verdict_cell()["config_data"]["level_pins"][-1]

    def build(cfg):
        eng = real(cfg)
        check = eng.check

        def stopped(on_progress=None, **kw):
            hit = []

            def cb(rec):
                on_progress(rec)
                if rec["n_states"] == total and not hit:
                    hit.append(1)
                    signal.raise_signal(signal.SIGINT)
            return check(on_progress=cb, **kw)

        eng.check = stopped
        return eng

    drive.build_engine = build
    try:
        res = rehearse_verdict(46)
    finally:
        drive.build_engine = real
    assert res["correct"] is False and res["failed"] == res["attempted"]
    assert {n: c["value"] for n, c in res["checks"].items()
            if c["value"] > c["limit"]} \
        == {"passes_incomplete": res["attempted"]}


# ------------------------------------------------------ the trace reduction

def test_interval_arithmetic():
    assert tracered.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tracered.gaps_ns([(5, 10), (8, 20), (30, 50)], 0, 40) == \
        [(0, 5), (20, 30)]
    # a while holding its body: self time goes to the body's ops
    ev = [["while.1", 0, 100], ["fusion.2", 10, 30], ["fusion.3", 50, 40],
          ["copy.4", 120, 5]]
    assert tracered.self_times(ev) == {"while.1": 30, "fusion.2": 30,
                                       "fusion.3": 40, "copy.4": 5}
    spans = [["upload", "MainThread", 0, 50], ["export", "MainThread", 60, 80],
             ["dedup_wait", "MainThread", 62, 70]]
    assert tracered.covering_kind(spans, 40, 75) == \
        {"upload": 10, "dedup": 8, "export": 7, "unattributed": 10}


def test_reduction_of_the_recorded_trace():
    rec = mf.read_json("testdata", "trace_small.json")
    got = tracered.reduce(rec["trace"], rec["spans"], rec["anchor_mono_ns"],
                          rec["t_a_s"], rec["t_b_s"])
    want = rec["expected"]
    for k in ("devices", "window_s", "busy_s", "segment_device_s"):
        assert abs(got[k] - want[k]) <= 1e-9 * max(1.0, abs(want[k])), k
    assert [n for n, _ in got["device_ops"]] == \
        [n for n, _ in want["device_ops"]]
    assert dict(got["idle_gaps"]).keys() == dict(want["idle_gaps"]).keys()
    assert 0.0 < got["busy_s"] <= got["window_s"]


def test_readers_of_a_pass_at_depth_on_the_recorded_resumed_pass():
    from benchmark.harness import depthred, spanred
    spans = spanred.load(RESUME_LOG)
    red = depthred.reduce(spans, 12, 16)
    inline = red.pop("dedup_inline_s")
    assert abs(inline - (0.002747 + 0.008381 + 0.0071 + 0.009088)) < 1e-9
    assert red == {"levels": 4, "streamed_rows": 4736, "new_states": 4327,
                   "blocks": 4, "uploads": 4, "prefetch_hits": 4,
                   "flush_submits": 0, "flush_backlog_max": None}
    assert depthred.reduce(spans, 16, 16) is None
    assert depthred.reduce([s for s in spans if s["name"] != "level"],
                           12, 16) is None
    # the step-bounded window: the segments harvested inside it
    t_a = passes.run_start(RESUME_LOG)["mono"]
    assert depthred.window_segments(spans, t_a, 1956.769046) == \
        {"segments": 7, "steps": 68, "streamed_rows": 4736}
    assert depthred.window_segments(spans, t_a, 1956.62) == \
        {"segments": 3, "steps": 20, "streamed_rows": 1856}
    traced = passes.Pass(index=1, t_call=t_a - 0.014, t_a=t_a,
                         t_b=1956.7815, t_trace_end=1956.769046,
                         traced=True, resumed=True, events=RESUME_LOG)
    plain = [passes.Pass(index=k, t_call=10.0 * k, t_a=10.0 * k + r,
                         t_b=10.0 * k + 9, resumed=True)
             for k, r in ((0, 0.25), (2, 0.75), (3, 0.5))]
    ev = {"passes": [plain[0], traced] + plain[1:], "span_levels": [12, 16],
          "snapshot": {"build_s": 12.5}}
    read = {n: mf.metric_reader(n)(ev) for n in (
        "host_dedup_hit_pct", "blocks_per_level", "prefetch_hit_pct",
        "flush_backlog_keys", "resume_s", "snapshot_build_s",
        "dedup_inline_s")}
    assert abs(read["host_dedup_hit_pct"] - 100 * 409 / 4736) < 1e-9
    assert read["blocks_per_level"] == 1.0
    assert read["prefetch_hit_pct"] == 100.0
    assert read["flush_backlog_keys"] is None     # no batch was handed over
    assert read["resume_s"] == 0.5 and read["snapshot_build_s"] == 12.5
    assert abs(read["dedup_inline_s"] - inline) < 1e-12
    # a hand-over with keys behind it is what the backlog reader reads
    submit = {"name": "dedup_submit", "thread": "MainThread", "t0": 1956.6,
              "dur": 0.001, "id": 9001, "args": {"keys": 7, "backlog": 3},
              "parent": next(s["id"] for s in spans if s["name"] == "level"
                             and s["args"]["level"] == 14)}
    assert depthred.reduce(spans + [submit], 12, 16)[
        "flush_backlog_max"] == 3
    # nothing to read: an untraced run, a run from Init, a log with no level
    bare = {"passes": [passes.Pass(index=0, t_call=0.0, t_a=1.0, t_b=2.0)],
            "span_levels": [12, 16], "snapshot": None}
    for n in read:
        assert mf.metric_reader(n)(bare) is None, n


# ------------------------------------------------------ the plain reference

def test_deep_pins_count_what_the_plain_bfs_counts():
    from benchmark.reference import deep_pins
    cfg = mf.read_json("testdata", "toy_config.json")
    for workers in (1, 2):
        cum, viol = deep_pins.bfs_counts(
            cfg["bounds"], cfg["spec"], cfg["symmetry"],
            tuple(cfg["invariants"]), 40, workers, out=lambda _m: None)
        assert cum == cfg["level_pins"] and viol == 0
    cum, _v = deep_pins.bfs_counts(
        cfg["bounds"], cfg["spec"], cfg["symmetry"],
        tuple(cfg["invariants"]), 5, 1, out=lambda _m: None)
    assert cum == cfg["level_pins"][:6]


def test_deep_pins_start_from_the_stated_init_under_both_axes():
    from benchmark.reference import canon, deep_pins
    from benchmark.reference.bounds import Bounds
    cfg = mf.read_json("testdata", "toy_repl3.json")
    init = canon.stated_init(Bounds(**cfg["bounds"]), cfg["init"],
                             cfg["invariants"])
    for workers in (1, 2):
        cum, viol = deep_pins.bfs_counts(
            cfg["bounds"], cfg["spec"], cfg["symmetry"],
            tuple(cfg["invariants"]), 40, workers, out=lambda _m: None,
            init=init)
        assert cum == cfg["level_pins"] and viol == 0     # the space ends
    # from the spec's own Init the sub-spec has nothing to do
    cum, _v = deep_pins.bfs_counts(
        cfg["bounds"], cfg["spec"], cfg["symmetry"],
        tuple(cfg["invariants"]), 40, 1, out=lambda _m: None)
    assert cum == [1]


def test_a_stated_init_is_held_to_the_bounds_and_the_invariants():
    from benchmark.reference import canon, interp
    from benchmark.reference.bounds import Bounds
    b = Bounds(n_servers=3, n_values=2, max_term=2, max_log=2, max_msgs=1,
               max_dup=1)
    assert canon.stated_init(b, None) == interp.init_state(b)
    s = canon.stated_init(b, {
        "role": ["Follower", "Leader", "Candidate"], "term": [1, 2, 2],
        "votedFor": ["Nil", "s2", "s3"], "log": [[], [[1, 2], [2, 1]], []],
        "vGrant": [[], [], ["s3", "s1"]],
        "matchIndex": [[0, 0, 0], [1, 0, 2], [0, 0, 0]]})
    assert (s.role, s.term, s.votedFor) == ((0, 2, 1), (1, 2, 2), (0, 2, 3))
    assert s.log == ((), ((1, 2), (2, 1)), ()) and s.vGrant == (0, 0, 0b101)
    assert s.matchIndex[1] == (1, 0, 2) and s.nextIndex == ((1, 1, 1),) * 3
    for bad, word in (
            ({"term": [3, 1, 1]}, "outside the bounds"),
            ({"term": [1, 1]}, "each of the 3 servers"),
            ({"log": [[[1, 3]], [], []]}, "outside the bounds"),
            ({"votedFor": ["s4", "Nil", "Nil"]}, "no server"),
            ({"msgs": []}, "it may state"),
            ({"role": ["Leader", "Leader", "Follower"]}, "NoTwoLeaders")):
        try:
            canon.stated_init(b, bad, ("NoTwoLeaders",))
            raise AssertionError(f"{bad} was accepted")
        except ValueError as e:
            assert word in str(e), e


def test_reference_orbit_representative_is_renaming_invariant():
    from benchmark.reference import canon, interp
    from benchmark.reference.bounds import Bounds
    b = Bounds(n_servers=3, n_values=2, max_term=2, max_log=1, max_msgs=2,
               max_dup=1)
    cum, level, viol = canon.bfs_levels(
        b, "full", True, ("NoTwoLeaders", "LogMatching"), 600)
    flagship = mf.read_json("configs", "flagship3.json")["level_pins"]
    assert cum == flagship[:len(cum)] and viol == 0
    rng = random.Random(5)
    perms = list(itertools.permutations(range(3)))
    for s in rng.sample(level, 100):
        twin = interp.PyState(*canon.permute(s, rng.choice(perms)))
        assert canon.canonical(s) == canon.canonical(twin)
        assert canon.canonical_all_perms(s) == canon.canonical_all_perms(twin)
    # the shortcut partitions states exactly as the definition does
    some = rng.sample(level, 300)
    assert len({canon.canonical(s) for s in some}) == \
        len({canon.canonical_all_perms(s) for s in some})
    # over Server and Value: the replication toy from its stated Init,
    # logs that differ and entries in flight in the sample
    cfg = mf.read_json("testdata", "toy_repl3.json")
    b = Bounds(**cfg["bounds"])
    init = canon.stated_init(b, cfg["init"], cfg["invariants"])
    assert canon.bfs_levels(b, cfg["spec"], ["Server"], (), 10**9,
                            init=init)[0][-1] > cfg["level_pins"][-1]
    _cum, level, _v = canon.bfs_levels(b, cfg["spec"], [], (), 500,
                                       init=init)
    assert any(len(set(s.log)) == 3 for s in level) and any(
        canon.mb.fc(lo) for s in level for (_hi, lo), _c in s.msgs)
    values = list(itertools.permutations(range(2)))
    for s in rng.sample(level, 200):
        twin = interp.PyState(*canon.permute(s, rng.choice(perms),
                                             rng.choice(values)))
        assert canon.canonical(s, 2) == canon.canonical(twin, 2) \
            == canon.canonical_all_perms(s, 2) \
            == canon.canonical_all_perms(twin, 2)
        assert canon.canonical(s, 2) <= canon.canonical(s)
    # a renaming of the values moves the state and keeps the orbit
    moved = [s for s in level
             if canon.permute(s, (0, 1, 2), (1, 0)) != canon.as_tuple(s)]
    assert len(moved) > len(level) // 2
    key = canon.orbit_key(cfg["symmetry"], 2)
    assert key(moved[0]) == canon.canonical(moved[0], 2)
    assert canon.orbit_key(True, 2) is canon.orbit_key(["Server"], 2) \
        is canon.canonical and canon.orbit_key([], 2) is canon.as_tuple
    for axes in (["Value"], ["Server", "Term"]):
        try:
            canon.orbit_key(axes, 2)
            raise AssertionError(f"SYMMETRY {axes} was accepted")
        except ValueError as e:
            assert str(axes) in str(e)


# ------------------------------------------- a whole run, toy size, the CPU

def rehearse(seed: int, trace: bool = False) -> dict:
    from benchmark import run
    return run.execute(toy_cell(), mf.load(), seed, 0.0, trace,
                       rehearsal=True)


def test_rehearsal_of_one_run_is_correct_and_writes_no_metric():
    res = rehearse(3_000_000_019, trace=True)     # more than 32 signed bits
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["correct"] is True
    assert res["attempted"] >= 3 and res["failed"] == 0
    # every number compared stands beside its limit, under the last key
    assert list(res)[-1] == "checks" and len(res["checks"]) >= 8
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    json.dumps(res)


def rehearse_repl(seed: int, trace: bool = False) -> dict:
    from benchmark import run
    return run.execute(toy_repl_cell(), mf.load(), seed, 0.0, trace,
                       rehearsal=True)


def test_rehearsal_from_a_stated_init_under_both_axes_is_correct():
    res = rehearse_repl(3_000_000_033, trace=True)
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["correct"] is True
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def test_the_log_fault_is_found_and_invariants_off_misses_it():
    # the replication table has no BecomeLeader: check (d) plants a commit
    # that a later leader's log lacks, and only the invariant pass sees it
    from benchmark.harness import breakers
    with breakers.invariants_off():
        res = rehearse_repl(34)
    assert res["correct"] is False and res["failed"] == 0
    assert {n for n, c in res["checks"].items() if c["value"] > c["limit"]} \
        == {"planted_violation_missed"}


def test_short_keys_from_a_stated_init_come_out_not_correct():
    from benchmark.harness import breakers
    with breakers.short_keys(10):
        res = rehearse_repl(35)
    assert res["correct"] is False


def rehearse_resumed(seed: int, trace: bool = False) -> dict:
    from benchmark import run
    return run.execute(toy_resume_cell(), mf.load(), seed, 0.0, trace,
                       rehearsal=True)


def test_rehearsal_of_resumed_passes_is_correct_and_writes_no_metric():
    res = rehearse_resumed(3_000_000_029, trace=True)
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["correct"] is True
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert {"snapshot_overshoot_orbits", "snapshot_keys_diff"} <= set(
        res["checks"])


def test_a_snapshot_with_one_key_removed_comes_out_not_correct():
    # the snapshot damaged between set-up and the window: its metadata (an
    # npz beside the four streams; the layout is the program's, known to
    # this test alone) rewritten to end one key early
    import numpy as np
    from benchmark.harness import drive
    real = drive.Driver.build_snapshot

    def build_then_damage(self):
        p = real(self)
        path = self.snapshot["path"]
        with np.load(path) as z:
            meta = {k: z[k] for k in z.files if k != "content_sha"}
        meta["n_states"] = meta["n_states"] - 1
        meta["level_ends"] = meta["level_ends"].copy()
        meta["level_ends"][-1] -= 1
        with open(path, "wb") as f:
            np.savez(f, **meta)
        return p

    drive.Driver.build_snapshot = build_then_damage
    try:
        res = rehearse_resumed(15)
    finally:
        drive.Driver.build_snapshot = real
    assert res["correct"] is False and res["failed"] == res["attempted"]


def test_short_keys_under_resumed_passes_come_out_not_correct():
    from benchmark.harness import breakers
    with breakers.short_keys(16):
        res = rehearse_resumed(16)
    assert res["correct"] is False


def test_filter_only_dedup_under_resumed_passes_comes_out_not_correct():
    # the resume rebuilds the key set from the snapshot's keys: that one
    # forgets too
    from benchmark.harness import breakers
    with breakers.filter_only_dedup():
        res = rehearse_resumed(17)
    assert res["correct"] is False


# ------------------------------------------------ the mesh, toy size, the CPU

def rehearse_mesh(seed: int, trace: bool = False) -> dict:
    from benchmark import run
    return run.execute(toy_mesh_cell(), mf.load(), seed, 0.0, trace,
                       rehearsal=True)


def test_mesh_rehearsal_is_correct_and_every_key_is_on_its_owner():
    if not on_four_devices(
            "test_mesh_rehearsal_is_correct_and_every_key_is_on_its_owner"):
        return
    res = rehearse_mesh(3_000_000_031, trace=True)
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert res["correct"] is True and res["device"]["count"] >= 4
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert res["checks"]["owner_misrouted"] == {"value": 0, "limit": 0}
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    # the one-chip rehearsal compares the same numbers but that one
    assert set(res["checks"]) - set(rehearse(21)["checks"]) \
        == {"owner_misrouted"}


def test_a_misrouted_exchange_comes_out_not_correct():
    # the exchange between chips broken underneath: every candidate goes to
    # the shard after its owner.  All duplicates of a key still meet, so
    # every count holds; the one number that the mesh adds sees it
    if not on_four_devices(
            "test_a_misrouted_exchange_comes_out_not_correct"):
        return
    from benchmark.harness import breakers
    with breakers.misrouted_exchange():
        res = rehearse_mesh(22)
    assert res["correct"] is False and res["failed"] == 0
    wrong = {n for n, c in res["checks"].items() if c["value"] > c["limit"]}
    assert wrong == {"owner_misrouted"}
    assert res["checks"]["owner_misrouted"]["value"] > 100
    # a one-chip engine has no exchange: the control passes it
    with breakers.misrouted_exchange():
        assert rehearse(23)["correct"] is True


def test_short_keys_on_the_mesh_come_out_not_correct():
    if not on_four_devices(
            "test_short_keys_on_the_mesh_come_out_not_correct"):
        return
    from benchmark.harness import breakers
    with breakers.short_keys(16):
        res = rehearse_mesh(24)
    assert res["correct"] is False


def test_exchange_time_counts_nested_ops_and_busy_skew_is_of_the_chips():
    from benchmark.harness import meshred, stagered
    seg = "jit(segment)/while/body/"
    ops = [["while.1", 0, 1000, "jit(segment)/while"],
           ["fusion.2", 10, 200, seg + "filter_insert/gather"],
           ["all-to-all.3", 300, 400, seg + "exchange/all_to_all"],
           # nested under the collective: its own self time is counted too
           ["copy.4", 350, 100, seg + "exchange/all_to_all/copy"],
           ["scatter.5", 720, 80, seg + "exchange/scatter"],
           ["fusion.6", 820, 50, seg + "exchanged/not_the_scope"],
           ["fusion.7", 2000, 50, seg + "exchange/outside_the_module"]]
    plane = {"XLA Ops": ops, "XLA Modules": [["jit_segment(1)", 0, 1000]]}
    slow = {"XLA Ops": [[n, 2 * s, 2 * d, path] for n, s, d, path in ops],
            "XLA Modules": [["jit_segment(1)", 0, 2000]]}
    idle = {"XLA Ops": [], "XLA Modules": []}
    trace = {"devices": {"/device:TPU:0": plane, "/device:TPU:1": slow,
                         "/device:TPU:9": idle}}
    red = meshred.scope_times(trace, 0, 3000)
    assert red["devices"] == 2
    assert [p["scope_ns"] for p in red["planes"]] == [480, 960]
    assert red["scope_ns"] == 720 and red["scope_ns_max"] == 960
    by_dev = stagered.stage_times(trace, 0, 3000)["stage_ns_by_device"]
    assert [d["filter_insert"] for d in by_dev] == [200, 400]
    assert red["planes"][0]["top"][0] == ("all-to-all.3", 300)
    assert meshred.scope_times({"devices": {"/device:TPU:9": idle}},
                               0, 1500) is None
    ev = {"meshred": red, "work": {"steps": 3}, "passes": []}
    assert abs(mf.metric_reader("stage_exchange_ms")(ev)
               - 720 / 1e6 / 3) < 1e-15
    # no op under the scope: nothing to read, never 0.0
    ev["meshred"] = dict(red, scope_ns=0)
    assert mf.metric_reader("stage_exchange_ms")(ev) is None
    assert meshred.busy_skew_pct([2.0, 1.5, 1.9, 2.0]) == 25.0
    assert meshred.busy_skew_pct([2.0]) is None
    tr = {"busy_by_device_s": [0.8, 1.0, 1.0, 0.9]}
    assert abs(mf.metric_reader("chip_busy_skew_pct")({"trace": tr})
               - 20.0) < 1e-9
    assert mf.metric_reader("chip_busy_skew_pct")({"trace": None}) is None


def test_overshoot_is_the_median_of_the_sound_untraced_passes():
    made = [passes.Pass(index=k, t_call=0.0, t_a=1.0, t_b=2.0,
                        t_return=2.0 + over, traced=k == 1)
            for k, over in enumerate((0.05, 9.0, 0.07, 0.50))]
    made[3].problem = "short of B"
    assert abs(mf.metric_reader("overshoot_s")({"passes": made})
               - 0.06) < 1e-9
    assert mf.metric_reader("overshoot_s")({"passes": []}) is None


def test_log_share_is_of_the_sound_untraced_passes_coverage():
    read = mf.metric_reader("log_transitions_share_pct")
    cov = {"ClientRequest": 10, "AppendEntries": 20, "Receive": 60,
           "AdvanceCommitIndex": 10}
    made = [passes.Pass(index=k, t_call=0.0, traced=k == 1, coverage=c)
            for k, c in enumerate((cov, {"Receive": 1}, cov,
                                   {"ClientRequest": 5}))]
    made[3].problem = "short of B"
    assert abs(read({"passes": made}) - 40.0) < 1e-9
    assert read({"passes": [passes.Pass(index=0, t_call=0.0,
                                        coverage={"Timeout": 9})]}) == 0.0
    assert read({"passes": made[1:2]}) is None and \
        read({"passes": []}) is None


def test_the_planted_fault_breaks_an_invariant_in_one_step_only():
    from benchmark.harness import correct
    from benchmark.reference import canon, interp
    from benchmark.reference.bounds import Bounds
    for cfg in (mf.read_json("testdata", "toy_config.json"),
                mf.read_json("configs", "elect5.json"),
                mf.read_json("configs", "flagship3.json")):
        b = Bounds(**cfg["bounds"])
        _cum, level, _v = canon.bfs_levels(
            b, cfg["spec"], True, tuple(cfg["invariants"]), 200)
        plants = [correct.planted_fault(cfg, level, seed)
                  for seed in (1, 2, 2_147_483_659)]
        assert len({p["parent"] for p in plants}) > 1     # the seed draws it
        for p in plants:
            assert interp.constraint_ok(p["parent"], b)
            assert mf.family(cfg).holds(p["parent"], cfg) == []
            assert p["violators"] and all(
                "NoTwoLeaders" in v for v in p["violators"].values())
    # a table with no BecomeLeader takes the log fault: AdvanceCommitIndex
    # commits an entry that the leader of the later term lacks
    from benchmark.reference import spec as S
    cfg = mf.read_json("testdata", "toy_repl3.json")
    b = Bounds(**cfg["bounds"])
    _cum, level, _v = canon.bfs_levels(
        b, cfg["spec"], cfg["symmetry"], tuple(cfg["invariants"]), 60,
        init=canon.stated_init(b, cfg["init"]))
    logs = [correct.planted_fault(cfg, level, seed)
            for seed in (1, 2, 2_147_483_659)]
    assert len({p["parent"] for p in logs}) > 1
    table = S.action_table(b, cfg["spec"])
    for p in logs:
        assert interp.constraint_ok(p["parent"], b)
        assert mf.family(cfg).holds(p["parent"], cfg) == []
        assert p["violators"] and all(
            v == ["LeaderCompleteness"] for v in p["violators"].values())
        by = {table[a].family for a, t in interp.successors(
            p["parent"], b, table) if p["key"](t) in p["violators"]}
        assert by == {S.ADVANCECOMMIT}
    try:
        correct.planted_fault(dict(cfg, invariants=["LogMatching"]), level, 1)
        raise AssertionError("a fault with no invariant to break")
    except ValueError as e:
        assert "lists no invariant it breaks" in str(e)
    # a sound engine names the planted state; a blind or wrong one fails
    p = plants[0]
    orbit, names = next(iter(p["violators"].items()))

    def Hit(t):
        return interp.PyState(*t)
    # tests/test_full5.py still reads the Raft family's fields off correct
    from benchmark.families import raft
    assert correct.STATE_FIELDS is raft.STATE_FIELDS
    ok = correct.planted_checks(p, {"invariant": names[0],
                                    "state": Hit(orbit)})
    assert [v for _n, v, _l in ok] == [0, 0]
    blind = correct.planted_checks(p, {"invariant": None, "state": None})
    assert [v for _n, v, _l in blind] == [1, 0]
    other = correct.planted_checks(p, {"invariant": "LogMatching",
                                       "state": Hit(orbit)})
    assert [v for _n, v, _l in other] == [0, 1]
    elsewhere = correct.planted_checks(
        p, {"invariant": names[0], "state": p["parent"]})
    assert [v for _n, v, _l in elsewhere] == [0, 1]


def test_short_keys_come_out_not_correct():
    # 32 bits collide ~16 and ~105 times at the cells' 3.7e5 and 9.5e5 keys
    # (control.py shows that on the chip); the toy's 6.6e3 keys need 16 bits
    # for collisions as sure
    from benchmark.harness import breakers
    with breakers.short_keys(16):
        res = rehearse(11)
    assert res["correct"] is False


def test_filter_only_dedup_comes_out_not_correct():
    from benchmark.harness import breakers
    with breakers.filter_only_dedup():
        res = rehearse(12)
    assert res["correct"] is False


def test_invariants_switched_off_come_out_not_correct():
    # no sound run's counts depend on the invariant pass; only the planted
    # fault (check d) sees it gone
    from benchmark.harness import breakers
    with breakers.invariants_off():
        res = rehearse(14)
    assert res["correct"] is False and res["failed"] == 0


def test_a_pass_cut_short_comes_out_not_correct():
    # the timed path broken underneath: an engine that stops one level early
    import signal
    from benchmark.harness import drive
    real = drive.build_engine
    cell = toy_cell()
    stop_at = cell["config_data"]["level_pins"][
        cell["traffic_data"]["end_level"] - 1]

    def build(cfg):
        eng = real(cfg)
        check = eng.check

        def short(on_progress=None, **kw):
            def cb(rec):
                on_progress(rec)
                if rec["n_states"] == stop_at:
                    signal.raise_signal(signal.SIGINT)
            return check(on_progress=cb, **kw)

        eng.check = short
        return eng

    drive.build_engine = build
    try:
        res = rehearse(13)
    finally:
        drive.build_engine = real
    assert res["correct"] is False and res["failed"] == res["attempted"]


def main(argv) -> int:
    four_devices()
    pick = argv[argv.index("-k") + 1] if "-k" in argv else ""
    tests = [(n, f) for n, f in sorted(globals().items())
             if n.startswith("test_") and pick in n]
    failed = 0
    for name, fn in tests:
        t0 = time.monotonic()
        try:
            fn()
            print(f"PASS {name} ({time.monotonic() - t0:.1f}s)", flush=True)
        except Exception:
            import traceback
            failed += 1
            traceback.print_exc()
            print(f"FAIL {name}", flush=True)
    print(json.dumps({"selftest": "ok" if not failed else "failed",
                      "ran": len(tests), "failed": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
