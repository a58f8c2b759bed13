"""The benchmark's readers of the program's span tree, stage scopes and
compile ledger (PR 24): each new ``benchmark/metrics/<name>.py`` and the
reducers behind them, on small recorded data under ``benchmark/testdata``
(one traced pass of ``flagship3.passes`` on the v5e: its span log, and its
capture cut to one chunk step with every op's scope path).

No chip here: nothing in this file is a measurement, only the arithmetic
that turns a capture into numbers.
"""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import (lanered, ledgerred, passes, spanred,  # noqa: E402
                               stagered)
from benchmark.harness import manifest as mf  # noqa: E402

TESTDATA = os.path.join(ROOT, "benchmark", "testdata")
SPAN_LOG = os.path.join(TESTDATA, "spans_small.jsonl")

SPAN_METRICS = ("ramp_level_ms", "ramp_upload_ms", "ramp_segment_ms",
                "level_close_ms", "ramp_self_ms", "segment_wait_s",
                "export_d2h_s", "flush_busy_s")
STAGE_METRICS = ("stage_expand_ms", "stage_orbit_ms", "stage_check_ms",
                 "stage_filter_ms", "stage_stream_ms",
                 "stage_unscoped_share_pct")
LEDGER_METRICS = ("setup_trace_s", "setup_backend_s", "setup_programs")
OTHER_METRICS = ("clock_skew_us", "span_overhead_pct")
NEW_METRICS = SPAN_METRICS + STAGE_METRICS + LEDGER_METRICS + OTHER_METRICS
# PR 26: the lane counts on the ``level`` spans (benchmark/harness/lanered.py)
LANE_METRICS = ("lane_fill_pct", "slabs_per_step", "route_peak_rows")
LANE_LOG = os.path.join(TESTDATA, "lanes_small.jsonl")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(TESTDATA, "scoped_trace_small.json"),
              encoding="utf-8") as f:
        return json.load(f)


def _pass(recorded, **kw):
    m = recorded["pass"]
    return passes.Pass(index=2, t_call=m["t_a"] - 2.7, t_a=m["t_a"],
                       t_trace_end=m["t_trace_end"], traced=True,
                       events=SPAN_LOG,
                       anchor=(m["anchor_mono_ns"], m["anchor_name"]), **kw)


def _evidence(recorded):
    """What ``run.execute`` hands a reader, as far as the new ones look:
    an untraced first pass, the recorded traced pass, an untraced third."""
    m = recorded["pass"]
    plain = [passes.Pass(index=k, t_call=100.0 * k, t_a=100.0 * k + ramp,
                         t_b=100.0 * k + ramp + 3.2)
             for k, ramp in ((1, 2.70), (3, 2.60))]
    return {"passes": [plain[0], _pass(recorded), plain[1]],
            "work": {"traced_levels": [m["level_a"], m["level_a"] + 1],
                     "steps": m["steps"]},
            "trace": {"segment_device_s": 1.153}}


# ------------------------------------------------------------ the manifest

def test_manifest_gains_the_nineteen_readers_and_nothing_else():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    names = [m["name"] for m in manifest["per_layer"]]
    # found by name, in the order PR 24 appended them (later PRs append
    # their own readers after these)
    at = [names.index(n) for n in NEW_METRICS]
    assert at == list(range(at[0], at[0] + 19))
    new = {m["name"]: m for m in manifest["per_layer"]
           if m["name"] in NEW_METRICS}
    assert {m["moves"] for n, m in new.items() if n in LEDGER_METRICS} \
        == {"setup_s"}
    assert {m["moves"] for n, m in new.items()
            if n not in LEDGER_METRICS} == {"orbits_per_s"}
    # each lists the cells its reader finds something to read in: the two
    # of PR 24 first, cells of later PRs appended
    cells = [w["name"] for w in manifest["workloads"]]
    for m in new.values():
        assert m["workloads"][:2] == ["elect5.passes", "flagship3.passes"]
        assert set(m["workloads"]) <= set(cells)
    assert {new[n]["source"] for n in SPAN_METRICS} == {"program_span"}
    assert {new[n]["source"] for n in STAGE_METRICS} == {"device_trace"}


def test_manifest_gains_the_three_lane_readers_and_the_cell_full5():
    """PR 26: one configuration, one cell, three readers of the ``level``
    spans' lane counts, each listing every cell; the nineteen of PR 24 gain
    the cell and nothing else."""
    manifest = mf.load()
    names = [m["name"] for m in manifest["per_layer"]]
    assert tuple(names[names.index(NEW_METRICS[-1]) + 1:][:3]) == LANE_METRICS
    # the first three cells first; cells of later PRs may be appended
    # (ROADMAP queue 2 B.1: a benchmark PR appends the five later ones)
    cells = [w["name"] for w in manifest["workloads"]]
    for m in manifest["per_layer"]:
        if m["name"] in LANE_METRICS:
            assert (m["layer"], m["moves"], m["source"]) \
                == ("fused step", "orbits_per_s", "program_span")
        if m["name"] in LANE_METRICS or m["name"] in NEW_METRICS:
            assert m["workloads"][:3] == ["elect5.passes",
                                          "flagship3.passes", "full5.passes"]
            assert set(m["workloads"]) <= set(cells) \
                and len(set(m["workloads"])) == len(m["workloads"])
    cell = mf.cell(manifest, "full5.passes")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("full5", "passes_l10_l12", 1)
    pins, t = cell["config_data"]["level_pins"], cell["traffic_data"]
    assert (pins[t["start_level"]], pins[t["end_level"]]) \
        == (t["count_at_start"], t["count_at_end"]) == (45236, 261844)
    # the cfg the configuration names is the one under runs/, word for word
    with open(os.path.join(ROOT, "runs", "MC5s2v_full.cfg"),
              encoding="utf-8") as f:
        assert f.read() == cell["config_data"]["cfg_text"]


# ----------------------------------------------------- the span reduction

def test_span_reduction_of_the_recorded_log(recorded):
    m = recorded["pass"]
    red = spanred.reduce(spanred.load(SPAN_LOG), m["level_a"], m["t_a"],
                         m["t_trace_end"])
    want = recorded["expected"]["spans"]
    assert red.keys() == want.keys()
    for k, v in want.items():
        assert red[k] == pytest.approx(v), k
    # the issue's account of the old export span: nearly all of it is the
    # wait for the segment, a few ms are transfer
    assert red["export_d2h_s"] < 0.01 < 1.0 < red["segment_wait_s"]


def test_level_rows_partition_each_level_exactly():
    spans = spanred.load(SPAN_LOG)
    rows = spanred.level_rows(spans)
    assert [r["level"] for r in rows] == list(range(1, len(rows) + 1))
    kids = spanred.children(spans)
    by_id = {s["id"]: s for s in spans}
    for r in rows:
        lvl = next(s for s in spans if s["name"] == "level"
                   and s["args"]["level"] == r["level"])
        direct = sum(c["dur"] for c in kids.get(lvl["id"], ()))
        assert r["self_s"] + direct == pytest.approx(r["wall_s"], abs=1e-4)
        assert r["by_name_s"].get("segment_wait", 0.0) \
            <= r["by_name_s"].get("export", 0.0) + 1e-9
    # one tree: every parent is in the log, one root on the main thread
    main = [s for s in spans if s["thread"] == spanred.MAIN]
    assert all(s["parent"] in by_id for s in main if s["parent"])
    assert [s["name"] for s in main if s["parent"] is None] == ["pass"]


def test_clipping_follows_the_traced_window():
    spans = [{"name": "d2h", "thread": "MainThread", "t0": 9.0, "dur": 2.0,
              "id": 1, "parent": None, "args": {}},
             {"name": "d2h", "thread": "raft-tla-flush", "t0": 10.0,
              "dur": 1.0, "id": 2, "parent": None, "args": {}}]
    assert spanred.clipped_wall(spans, "d2h", "MainThread", 10.0, 20.0) \
        == pytest.approx(1.0)
    assert spanred.clipped_wall(spans, "d2h", "MainThread", 0.0, 9.5) \
        == pytest.approx(0.5)
    assert spanred.clipped_wall(spans, "upload", "MainThread", 0, 99) == 0.0
    assert spanred.reduce(spans, 3, 0.0, 99.0) is None    # no level span


# ---------------------------------------------------- the stage reduction

def test_stage_of_takes_the_innermost_scope():
    assert stagered.stage_of(
        "jit(segment)/while/body/stream/scatter") == "stream"
    assert stagered.stage_of(
        "jit(segment)/while/body/prescan/cond/branch_0_fun/orbit_scan/"
        "while/body/closed_call/add") == "orbit_scan"
    assert stagered.stage_of("jit(segment)/while/body/jit(expand)/mul") \
        is None                                  # a name, not a scope
    assert stagered.stage_of("jit(segment)/while") is None
    assert stagered.stage_of("") is None


def test_stage_reduction_of_the_recorded_excerpt(recorded):
    w0, w1 = recorded["window_ns"]
    got = stagered.stage_times(recorded["trace"], w0, w1)
    want = recorded["expected"]["stages"]
    assert got["stage_ns"] == want["stage_ns"]
    assert got["unscoped_ns"] == want["unscoped_ns"]
    assert [list(x) for x in got["top_unscoped"]] == want["top_unscoped"]
    assert [list(x) for x in got["top_ops"]] == want["top_ops"]
    assert got["top_ops"][0][:2] == ["fusion.927", "stream"]
    # one partition of the same events: stages + unscoped == total, and
    # the total is the ops' self time inside the segment module
    assert sum(got["stage_ns"].values()) + got["unscoped_ns"] \
        == got["total_ns"] <= got["module_ns"]
    assert got["scoped"] and got["stage_ns"]["filter_insert"] \
        > got["stage_ns"]["orbit_scan"] > 0      # |G| = 6: the scan is cheap
    # outside the segment module's intervals nothing is counted
    assert stagered.stage_times(recorded["trace"], w1 + 10**12,
                                w1 + 2 * 10**12) is None


def test_ops_without_paths_read_as_unscoped_not_as_scoped(recorded):
    bare = json.loads(json.dumps(recorded["trace"]))
    for lines in bare["devices"].values():
        for op in lines.get("XLA Ops", []):
            op[3] = ""
    w0, w1 = recorded["window_ns"]
    st = stagered.stage_times(bare, w0, w1)
    assert st["scoped"] is False
    assert st["unscoped_ns"] == st["total_ns"] \
        == recorded["expected"]["stages"]["total_ns"]


def test_op_paths_reads_tf_op_off_the_wire():
    """A hand-built XSpace: one device plane whose event metadata carries
    ``tf_op`` (and another stat to skip), one host plane to ignore."""
    def varint(x):
        out = bytearray()
        while True:
            out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
            x >>= 7
            if not x:
                return bytes(out)

    def field(no, payload):
        if isinstance(payload, int):
            return varint(no << 3) + varint(payload)
        return varint(no << 3 | 2) + varint(len(payload)) + payload

    def entry(key, msg):
        return field(1, key) + field(2, msg)

    stat_md = (field(5, entry(7, field(1, 7) + field(2, b"flops")))
               + field(5, entry(9, field(1, 9) + field(2, b"tf_op"))))
    ev = field(2, b"%fusion.927 = s32[8] fusion(...)") \
        + field(5, field(1, 7) + field(3, 12)) \
        + field(5, field(1, 9)
                + field(5, b"jit(segment)/while/body/stream/scatter:"))
    bare = field(2, b"%copy.1 = s32[8] copy(...)")
    device = field(2, b"/device:TPU:0") + field(3, b"\x08\x01") + stat_md \
        + field(4, entry(1, field(1, 1) + ev)) \
        + field(4, entry(2, field(1, 2) + bare))
    host = field(2, b"/host:CPU") + stat_md \
        + field(4, entry(1, field(1, 1) + ev))
    xspace = field(1, device) + field(1, host)
    assert stagered.op_paths(xspace) == {"/device:TPU:0": {
        "%fusion.927 = s32[8] fusion(...)":
            "jit(segment)/while/body/stream/scatter"}}


def test_clock_skew_of_the_recorded_annotations(recorded):
    m = recorded["pass"]
    spans = spanred.load(SPAN_LOG)
    got = stagered.clock_skew(recorded["trace"], spans, m["anchor_mono_ns"])
    assert got == pytest.approx(recorded["expected"]["skew"])
    assert got["n"] >= 10 and got["median_us"] < 100
    assert stagered.anchor_check_ms(
        recorded["trace"], spans, m["anchor_mono_ns"],
        recorded["window_ns"][0]) \
        == pytest.approx(recorded["expected"]["anchor_check_ms"])
    # a capture of a program without annotations has nothing to compare
    bare = dict(recorded["trace"], host=[])
    assert stagered.clock_skew(bare, spans, m["anchor_mono_ns"]) is None


# ------------------------------------------------------ the compile ledger

def test_ledger_reduction_counts_what_began_before_the_first_pass():
    recs = [{"t0": 1.0, "kind": "trace", "dur_s": 2.4, "fun": "segment"},
            {"t0": 3.5, "kind": "lower", "dur_s": 0.7, "fun": "jit(segment)"},
            {"t0": 4.2, "kind": "cache_load", "dur_s": 0.9, "fun": None},
            {"t0": 4.2, "kind": "backend", "dur_s": 1.0,
             "fun": "jit(segment)"},
            {"t0": 9.0, "kind": "backend", "dur_s": 5.0, "fun": "jit(match)"}]
    red = ledgerred.reduce(recs, t_call=8.0)
    assert red["setup_trace_s"] == pytest.approx(3.1)
    assert red["setup_backend_s"] == pytest.approx(1.0)   # holds the load
    assert red["setup_programs"] == 1 and red["cache_loads"] == 1
    assert red["cache_load_s"] == pytest.approx(0.9)


# ------------------------------------------------------------- the readers

def test_each_reader_on_the_recorded_pass(recorded, capsys):
    ev = _evidence(recorded)
    # the capture itself is not in the repo: hand the readers the excerpt's
    # reduction where stagered.of() would have loaded the xplane
    w0, w1 = recorded["window_ns"]
    ev["stagered"] = {
        "stages": stagered.stage_times(recorded["trace"], w0, w1),
        "skew": stagered.clock_skew(recorded["trace"],
                                    spanred.load(SPAN_LOG),
                                    recorded["pass"]["anchor_mono_ns"]),
        "anchor_check_ms": None}
    got = {n: mf.metric_reader(n)(ev) for n in NEW_METRICS}
    want = recorded["expected"]
    for n in SPAN_METRICS:
        assert got[n] == pytest.approx(want["spans"][n]), n
    st = want["stages"]
    steps = recorded["pass"]["steps"]
    for n, group in (("stage_expand_ms", "expand"),
                     ("stage_orbit_ms", "orbit"),
                     ("stage_check_ms", "check"),
                     ("stage_filter_ms", "filter"),
                     ("stage_stream_ms", "stream")):
        ns = sum(st["stage_ns"][s] for s in stagered.GROUPS[group])
        assert got[n] == pytest.approx(ns / 1e6 / steps), n
    assert got["stage_unscoped_share_pct"] == pytest.approx(
        100.0 * st["unscoped_ns"] / st["total_ns"])
    # the five stage metrics x steps + the unscoped time partition the total
    assert sum(got[n] for n in STAGE_METRICS[:5]) * steps * 1e6 \
        + st["unscoped_ns"] == pytest.approx(st["total_ns"])
    assert got["clock_skew_us"] == pytest.approx(want["skew"]["median_us"])
    # the traced pass's ramp (2.7 s) over the untraced median (2.65 s)
    assert got["span_overhead_pct"] == pytest.approx(
        100.0 * (2.7 / 2.65 - 1.0))
    # one printed line a reducer, however many readers ask
    out = capsys.readouterr().out
    assert out.count("span tree pass 2: ") == 1
    assert segment_wait_le_export(ev)


def segment_wait_le_export(ev) -> bool:
    red = spanred.of(ev)
    spans = spanred.load(SPAN_LOG)
    p = spanred.traced_pass(ev)
    export = spanred.clipped_wall(spans, "export", spanred.MAIN, p.t_a,
                                  p.t_trace_end)
    return red["segment_wait_s"] + red["export_d2h_s"] <= export + 1e-9


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_with_nothing_to_read_returns_nothing(name, capsys):
    """An untraced run (or a program older than the span tree: no ``level``
    span, no scope, no annotation) leaves the metric out and does not
    raise.  The ledger's three read a counter, not the traced pass."""
    plain = [passes.Pass(index=k, t_call=10.0 * k, t_a=10.0 * k + 2.7,
                         t_b=10.0 * k + 6.0) for k in (1, 2, 3)]
    ev = {"passes": plain, "work": {"traced_levels": [13, 14], "steps": 12},
          "trace": None}
    value = mf.metric_reader(name)(ev)
    if name in LEDGER_METRICS:
        assert value is not None and value >= 0
    else:
        assert value is None
    assert "span tree" not in capsys.readouterr().out


def test_a_log_of_the_flat_spans_reads_as_nothing(tmp_path, recorded):
    """The parent's traced pass: the old flat spans, no tree."""
    log = tmp_path / "flat.events"
    with open(SPAN_LOG, encoding="utf-8") as f, open(log, "w") as out:
        for line in f:
            ev = json.loads(line)
            if ev["event"] == "span" and ev["name"] in (
                    "upload", "expand", "export", "dedup", "dedup_wait",
                    "take", "prefetch"):
                ev.pop("parent_id", None)
                out.write(json.dumps(ev) + "\n")
    ev = _evidence(recorded)
    ev["passes"][1].events = str(log)
    ev["stagered"] = None
    for n in SPAN_METRICS + STAGE_METRICS + ("clock_skew_us",):
        assert mf.metric_reader(n)(ev) is None, n


# ------------------------------------------- the lane counts (PR 26)

def _lane_evidence(log=LANE_LOG):
    traced = passes.Pass(index=2, t_call=10.0, t_a=13.0, t_trace_end=14.7,
                         traced=True, events=log)
    return {"passes": [traced], "work": {"traced_levels": [10, 11]}}


def test_lane_reduction_of_the_recorded_log():
    """``lanes_small.jsonl``: the ``pass`` / ``level`` / ``segment`` spans
    of one traced pass of ``full5.passes`` on the v5e (PR 26).  The level
    spans' sums against the segments' own, and against the shapes: 37
    chunk steps of 4,096 rows x 84 actions."""
    spans = spanred.load(LANE_LOG)
    red = lanered.reduce(spans)
    segs = [s["args"] for s in spans if s["name"] == "segment"]
    assert red == {"levels": 13, "steps": 37, "lanes": 37 * 4096 * 84,
                   "n_valid": 721572, "stream_slabs": 37,
                   "route_peak": 38892}
    assert red["steps"] == sum(a["steps"] for a in segs)
    assert red["lanes"] == sum(a["lanes"] for a in segs)
    assert red["n_valid"] == sum(a["n_valid"] for a in segs)
    assert red["route_peak"] == max(a["route_peak"] for a in segs)
    assert lanered.reduce([s for s in spans if s["name"] != "level"]) is None


def test_each_lane_reader_on_the_recorded_pass(capsys):
    ev = _lane_evidence()
    got = {n: mf.metric_reader(n)(ev) for n in LANE_METRICS}
    assert got["lane_fill_pct"] == pytest.approx(100 * 721572 / 12730368)
    assert got["slabs_per_step"] == 1.0
    assert got["route_peak_rows"] == 38892
    assert capsys.readouterr().out.count("lane counts pass 2: ") == 1


@pytest.mark.parametrize("name", LANE_METRICS)
def test_a_lane_reader_with_nothing_to_read_returns_nothing(name):
    plain = [passes.Pass(index=k, t_call=10.0 * k, t_a=10.0 * k + 2.7,
                         t_b=10.0 * k + 6.0) for k in (1, 2, 3)]
    assert mf.metric_reader(name)({"passes": plain, "work": {}}) is None
    # PR 24's log: level spans, but from before any of the counts
    assert mf.metric_reader(name)(_lane_evidence(SPAN_LOG)) is None


def test_the_parents_level_spans_give_the_slab_ratio_alone(tmp_path):
    """PR 25's program: ``steps`` and ``stream_slabs`` on the level spans,
    no ``lanes``, ``n_valid`` or ``route_peak`` — the one reader that has
    its counts reports, the other two leave their metric out."""
    log = tmp_path / "pr25.events"
    with open(LANE_LOG, encoding="utf-8") as f, open(log, "w") as out:
        for line in f:
            ev = json.loads(line)
            for k in ("lanes", "n_valid", "route_peak"):
                if ev.get("name") == "level":
                    ev["args"].pop(k, None)
            out.write(json.dumps(ev) + "\n")
    ev = _lane_evidence(str(log))
    assert mf.metric_reader("slabs_per_step")(ev) == 1.0
    assert mf.metric_reader("lane_fill_pct")(ev) is None
    assert mf.metric_reader("route_peak_rows")(ev) is None


# ------------------------------------------------- the scopes in the step

@pytest.mark.parametrize("symmetry,scopes", [
    (("Server",), ("unpack", "expand", "pack", "prescan", "orbit_scan",
                   "invariants", "constraint", "filter_insert", "stream")),
    ((), ("unpack", "expand", "pack", "plain_fp", "invariants",
          "constraint", "filter_insert", "stream")),
])
def test_the_lowered_toy_step_names_every_stage_scope(symmetry, scopes):
    """``jax.named_scope`` lands in the lowered module's locations (from
    where XLA copies it into each op's ``op_name``, the trace's ``tf_op``):
    every stage the benchmark reads is named in the toy segment's text,
    under the segment's ``while`` body."""
    import jax.numpy as jnp

    from raft_tla_tpu.config import Bounds, CheckConfig
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    from raft_tla_tpu.ops import kernels
    cfg = CheckConfig(
        bounds=Bounds(n_servers=2, n_values=1, max_term=2, max_log=0,
                      max_msgs=2),
        spec="election", invariants=("NoTwoLeaders",), chunk=32,
        symmetry=symmetry)
    eng = DDDEngine(cfg, DDDCapacities(block=256, table=1 << 14,
                                       flush=1 << 10, levels=64))
    text = eng._segment.lower(
        eng._init_filter(), eng._make_bufs(),
        jnp.zeros((256, eng.schema.P), jnp.int32), jnp.zeros((256,), bool),
        jnp.int32(4), jnp.int32(3)).as_text(debug_info=True)
    assert set(scopes) <= set(kernels.STAGE_SCOPES) \
        == set(stagered.STAGES)       # the program's names = the reader's
    for scope in scopes:
        assert re.search(r'jit\(segment\)/while/body/(?:[^"]*/)?%s[/"]'
                         % scope, text), scope
    if symmetry:
        assert re.search(r'/prescan/[^"]*/orbit_scan/', text)
