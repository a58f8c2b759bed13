"""Trace layer: spans, clock alignment, Perfetto export, attribution.

The contract under test is PR 17's tentpole: with ``--trace`` on, every
process in a run (engines, scheduler, pool supervisor) emits schema-v8
``span`` events into its own log, each log carries a wall/monotonic
anchor, and the collector merges them onto ONE wall axis with the skew
bounded by the recorded anchor error; with tracing off (the default),
every instrumentation site touches one shared null handle and the logs
are byte-compatible with v7 consumers.
"""

import json
import os
import threading
import time

import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.obs import collect as obs_collect
from raft_tla_tpu.obs import perfetto as obs_perfetto
from raft_tla_tpu.obs.events import append_event, validate_event
from raft_tla_tpu.obs.phases import PhaseTimers
from raft_tla_tpu.obs.trace import (NULL_TRACER, SpanTracer, clock_anchor,
                                    trace_enabled, tracer_for)

CFG = CheckConfig(
    bounds=Bounds(n_servers=2, n_values=1, max_term=2, max_log=0,
                  max_msgs=2),
    spec="election", invariants=("NoTwoLeaders",), chunk=32)
N_TOY = 3014


# --------------------------------------------------------------------------
# span model


def _capture_tracer():
    rows = []
    tr = SpanTracer(lambda event, **f: rows.append({"event": event, **f}))
    return tr, rows


def test_span_nesting_parent_ids_and_set():
    tr, rows = _capture_tracer()
    with tr.span("outer", a=1):
        assert tr.current_id() == 1
        with tr.span("inner") as sp:
            assert tr.current_id() == 2
            sp.set(rows=256)
    assert tr.current_id() is None
    # inner emitted first (exit order), parented to outer
    inner, outer = rows
    assert inner["name"] == "inner" and inner["parent_id"] == 1
    assert inner["args"] == {"rows": 256}
    assert outer["name"] == "outer" and "parent_id" not in outer
    assert outer["args"] == {"a": 1}
    assert outer["t0"] <= inner["t0"]
    assert inner["dur"] <= outer["dur"]


def test_open_close_handle_nests_like_the_context_manager():
    """``open``/``close`` push and pop the same per-thread parent stack
    as ``with``: a region whose body is too long to re-indent parents
    what opens inside it, and closing twice emits once."""
    tr, rows = _capture_tracer()
    outer = tr.open("level", level=3)
    assert tr.current_id() == 1
    with tr.span("upload"):
        pass
    inner = tr.open("level_close")
    inner.close()
    outer.set(segments=2).close()
    outer.set(segments=99).close()           # idempotent: nothing more
    assert tr.current_id() is None
    assert [r["name"] for r in rows] == ["upload", "level_close", "level"]
    up, lc, lvl = rows
    assert up["parent_id"] == lc["parent_id"] == lvl["span_id"] == 1
    assert "parent_id" not in lvl
    assert lvl["args"]["level"] == 3 and lvl["args"]["segments"] == 2


def test_spans_open_an_annotation_of_the_same_name_and_id():
    """One clock: a tracer given an annotation factory (the engines hand
    in ``jax.profiler.TraceAnnotation``) opens each span a second time
    under its own name and ``span_id``, entered and left once each, on
    both the ``with`` and the ``open``/``close`` path; manual spans
    (``emit_span``) are in the past and open none."""
    log = []

    class Ann:
        def __init__(self, name, **kw):
            self.key = (name, kw["span_id"])

        def __enter__(self):
            log.append(("enter",) + self.key)

        def __exit__(self, *exc):
            log.append(("exit",) + self.key)

    rows = []
    tr = SpanTracer(lambda event, **f: rows.append(f), annotate=Ann)
    with tr.span("export"):
        h = tr.open("segment_wait")
        h.close()
        h.close()
    tr.emit_span("segment", 0.0, 1.0, thread="segments")
    assert log == [("enter", "export", 1), ("enter", "segment_wait", 2),
                   ("exit", "segment_wait", 2), ("exit", "export", 1)]
    assert [r["name"] for r in rows] == ["segment_wait", "export",
                                         "segment"]


def test_span_thread_attribution_is_per_thread():
    tr, rows = _capture_tracer()

    def work():
        with tr.span("bg"):
            # a fresh thread has its own stack: no parent inherited
            # from the main thread's open span
            assert tr.current_id() is not None

    with tr.span("main_work"):
        t = threading.Thread(target=work, name="bg-thread")
        t.start()
        t.join()
    by = {r["name"]: r for r in rows}
    assert by["bg"]["thread"] == "bg-thread"
    assert "parent_id" not in by["bg"]
    assert by["main_work"]["thread"] == threading.current_thread().name


def test_manual_spans_ride_synthetic_tracks():
    tr, rows = _capture_tracer()
    t0 = time.monotonic()
    tr.emit_span("ticket", t0, 0.5, thread="tickets", bin="b0")
    tr.emit_span("worker", t0, -1.0, thread="workers")  # clamped
    assert rows[0]["thread"] == "tickets"
    assert rows[0]["args"] == {"bin": "b0"}
    assert rows[1]["dur"] == 0.0
    assert rows[0]["span_id"] != rows[1]["span_id"]


def test_spans_validate_at_schema_v8(tmp_path):
    log = str(tmp_path / "t.events")
    tr = tracer_for(log)
    with tr.span("expand", rows=4):
        pass
    d = json.loads(open(log).read())
    assert d["event"] == "span" and validate_event(d) == []


# --------------------------------------------------------------------------
# off path


def test_off_path_is_one_shared_handle():
    assert not trace_enabled("")
    assert not trace_enabled("off")
    assert trace_enabled("1") and trace_enabled("on")
    s1 = NULL_TRACER.span("a", x=1)
    s2 = NULL_TRACER.span("b")
    assert s1 is s2                      # no per-call allocation
    with s1 as sp:
        assert sp.set(y=2) is sp
    # the explicit handle of the level loop is the same shared object
    assert NULL_TRACER.open("level", level=1) is s1
    assert s1.set(segments=2) is s1 and s1.close() is None
    assert NULL_TRACER.current_id() is None
    NULL_TRACER.emit_span("x", 0.0, 1.0)  # no-op, nothing to observe


def _ok_result():
    from types import SimpleNamespace
    return SimpleNamespace(n_states=1, n_transitions=1, complete=True,
                           violation=None, diameter=1, levels=[1],
                           wall_s=0.1)


def test_untraced_run_emits_no_spans_and_null_tracer(tmp_path,
                                                     monkeypatch):
    monkeypatch.delenv("RAFT_TLA_TRACE", raising=False)
    from raft_tla_tpu.obs.events import RunTelemetry
    tel = RunTelemetry("ddd", config=CFG,
                       events=str(tmp_path / "off.events"))
    assert tel.trace is NULL_TRACER
    tel.run_start()
    with tel.phases.phase("expand"):
        pass
    tel.run_end(_ok_result())
    tel.close()
    evs = [json.loads(l) for l in open(tmp_path / "off.events")]
    assert [e["event"] for e in evs] == ["run_start", "run_end"]
    # the anchor and the host context ride run_start unconditionally:
    # any log is alignable, and any log says where its run executed
    assert "anchor" in evs[0] and evs[0]["host"]["nproc"] >= 1


def test_traced_telemetry_attaches_tracer(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFT_TLA_TRACE", "1")
    from raft_tla_tpu.obs.events import RunTelemetry
    tel = RunTelemetry("ddd", config=CFG,
                       events=str(tmp_path / "on.events"))
    assert tel.trace.enabled and tel.phases.tracer is tel.trace
    tel.run_start()
    with tel.phases.phase("expand"):
        pass
    tel.run_end(_ok_result())
    tel.close()
    evs = [json.loads(l) for l in open(tmp_path / "on.events")]
    assert [e["event"] for e in evs] \
        == ["run_start", "span", "run_end"]
    assert "host" in evs[0]
    assert evs[1]["name"] == "expand"
    assert all(validate_event(e) == [] for e in evs)


# --------------------------------------------------------------------------
# PhaseTimers thread attribution (the v8 bugfix)


def test_phase_timers_background_thread_buckets():
    """Work timed on a non-owner thread lands in its own
    ``{phase}@{thread}`` bucket instead of silently racing the owner's
    accumulator — and the snapshot drains both."""
    pt = PhaseTimers(enabled=True)

    def work():
        with pt.phase("dedup"):
            time.sleep(0.01)

    with pt.phase("dedup"):
        time.sleep(0.01)
    t = threading.Thread(target=work, name="raft-tla-flush")
    t.start()
    t.join()
    snap = pt.snapshot()
    assert set(snap) == {"dedup", "dedup@raft-tla-flush"}
    assert snap["dedup"] > 0 and snap["dedup@raft-tla-flush"] > 0


def test_phase_timers_trace_only_emits_spans_without_sync():
    """A tracer on a DISABLED PhaseTimers still opens spans (trace-only
    mode) but never syncs or accumulates — dispatch pipelining stays
    intact and ``phase_s`` stays empty.  With both layers off the
    handle is the shared null singleton."""
    pt = PhaseTimers(enabled=False)
    tr, rows = _capture_tracer()
    pt.tracer = tr
    with pt.phase("expand") as ph:
        # sync() marks a value to block on — with timers disabled the
        # exit path must never touch it (no jax sync in trace-only mode)
        ph.sync(object())
    assert [r["name"] for r in rows] == ["expand"]
    assert pt.snapshot() == {}
    pt.tracer = NULL_TRACER
    assert pt.phase("expand") is pt.phase("upload")  # shared null handle
    # work counts ride the phase's span; the null handle swallows them
    assert pt.phase("upload").set(rows=4) is pt.phase("upload")
    pt.tracer = tr
    with pt.phase("upload") as ph:
        ph.set(rows=4, padded_rows=256)
    assert rows[-1]["args"] == {"rows": 4, "padded_rows": 256}
    assert pt.snapshot() == {}               # counts are not seconds


# --------------------------------------------------------------------------
# collector: clock alignment


def _synthetic_log(path, engine, pid, wall0, mono0, spans,
                   err_s=1e-6):
    """A minimal anchored log: run_start + spans with process-local
    monotonic t0 values (mono0 + offset)."""
    append_event(path, "run_start", engine=engine, universe={},
                 spec="", invariants=[], resumed=False, pid=pid,
                 anchor={"wall": wall0, "mono": mono0, "err_s": err_s},
                 host={"nproc": 1})
    for i, (name, off, dur, thread) in enumerate(spans, 1):
        append_event(path, "span", name=name, span_id=i,
                     t0=mono0 + off, dur=dur, thread=thread)


def test_two_process_clock_alignment(tmp_path):
    """Two processes whose monotonic clocks started at wildly different
    points record the SAME wall-time story; the collector aligns them
    through their anchors to within the recorded error bound."""
    a = str(tmp_path / "a.events")
    b = str(tmp_path / "b.events")
    wall = 1_700_000_000.0
    # process a: mono started 50s ago; process b: 9000s ago
    _synthetic_log(a, "ddd", 100, wall, 50.0,
                   [("expand", 1.0, 0.5, "MainThread")])
    _synthetic_log(b, "sched", 200, wall, 9000.0,
                   [("dispatch", 1.0, 0.5, "MainThread")])
    col = obs_collect.collect([a, b])
    assert len(col["processes"]) == 2
    sa, sb = col["spans"]
    # both spans happened at wall+1.0 despite disjoint monotonic bases
    assert abs(sa["ts"] - (wall + 1.0)) <= 1e-6
    assert abs(sa["ts"] - sb["ts"]) <= 2 * col["skew_bound_s"] + 1e-9
    assert col["skew_bound_s"] == 1e-6


def test_collector_anchorless_fallback_and_mixed_versions(tmp_path):
    """A log with no anchor (pre-v8 producer) degrades to the span's
    append stamp minus duration — still placed, flagged unanchored —
    and non-span/v7 rows in the mix are passed through as instants."""
    log = str(tmp_path / "old.events")
    append_event(log, "run_start", engine="ddd", universe={},
                 spec="", invariants=[], resumed=False, pid=7)
    append_event(log, "span", name="expand", span_id=1, t0=123.0,
                 dur=0.25, thread="MainThread")
    append_event(log, "worker_spawn", worker="w0", pid=9)
    d = [json.loads(l) for l in open(log)]
    col = obs_collect.collect([log])
    (proc,) = col["processes"]
    assert proc["anchored"] is False and proc["skew_bound_s"] is None
    (span,) = col["spans"]
    assert abs(span["ts"] - (d[1]["ts"] - 0.25)) <= 1e-9
    assert [i["name"] for i in col["instants"]] == ["worker_spawn"]


# --------------------------------------------------------------------------
# Perfetto export


def test_perfetto_export_structure(tmp_path):
    a = str(tmp_path / "a.events")
    wall = 1_700_000_000.0
    _synthetic_log(a, "ddd", 100, wall, 50.0,
                   [("expand", 1.0, 0.5, "MainThread"),
                    ("prefetch", 1.1, 0.2, "raft-tla-prefetch")])
    append_event(a, "segment", wall_s=2.0, n_states=10, level=1,
                 n_transitions=20, dedup_hit_rate=0.5,
                 states_per_sec=5.0, inc_states_per_sec=5.0,
                 since_resume=False)
    append_event(a, "run_end", outcome="ok", n_states=10,
                 n_transitions=20, complete=True)
    col = obs_collect.collect([a])
    out = str(tmp_path / "trace.json")
    n = obs_perfetto.export(col, out)
    doc = json.loads(open(out).read())
    evs = doc["traceEvents"]
    assert len(evs) == n
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["name"]: e for e in meta}
    assert "process_name" in names
    tthreads = {e["args"]["name"]: e["tid"] for e in meta
                if e["name"] == "thread_name"}
    assert tthreads["MainThread"] == 1          # main track first
    assert tthreads["raft-tla-prefetch"] == 2
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert xs["expand"]["dur"] == 0.5e6
    # rebased to t_min: the earliest stamp in the collection is 0
    assert min(e["ts"] for e in evs if e["ph"] != "M") == 0.0
    assert [e for e in evs if e["ph"] == "C"]   # the rate counter
    assert [e for e in evs if e["ph"] == "i"]   # run_end instant


# --------------------------------------------------------------------------
# end-to-end: traced engine run, report attribution, CLI


_TOY_CAPS = dict(block=256, table=1 << 14, flush=1 << 10, levels=64)


@pytest.fixture(scope="module")
def traced_toy(tmp_path_factory):
    """One traced toy ddd run (host dedup + prefetch on) shared by the
    tests below: ``(result, events, log directory)``."""
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    env = {"RAFT_TLA_TRACE": "1", "RAFT_TLA_HOSTDEDUP": "on",
           "RAFT_TLA_PREFETCH": "on"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        d = tmp_path_factory.mktemp("traced_toy")
        log = str(d / "ddd.events")
        res = DDDEngine(CFG, DDDCapacities(**_TOY_CAPS)).check(events=log)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return res, [json.loads(l) for l in open(log)], str(d)


def _main_spans(evs):
    return [e for e in evs if e["event"] == "span"
            and e["thread"] == "MainThread"]


@pytest.mark.smoke
def test_traced_ddd_run_report_attribution(traced_toy):
    """The acceptance bar on one process: a traced toy ddd run collects
    into a timeline whose main thread is one ``pass`` tree, attributed
    by SELF time (the root reads as what its children left uncovered,
    not as 100 %), with the prefetch thread on its own track — and the
    traced result equals the untraced oracle."""
    res, evs, d = traced_toy
    assert res.n_states == N_TOY
    assert all(validate_event(e) == [] for e in evs)
    spans = [e for e in evs if e["event"] == "span"]
    assert {s["name"] for s in spans} >= {"expand", "upload", "dedup"}
    assert "raft-tla-prefetch" in {s["thread"] for s in spans}

    col = obs_collect.collect(obs_collect.find_logs(d))
    rep = obs_collect.report(col)
    (proc,) = rep["processes"]
    main = proc["threads"]["MainThread"]
    assert main["attributed_frac"] >= 0.95
    assert abs(main["attributed_frac"] + main["gap_frac"] - 1.0) < 1e-9
    # self times partition the root: they sum to the pass's wall, and the
    # root's own row is the small remainder, not the whole run
    ph = main["phases"]
    assert abs(sum(p["total_s"] for p in ph.values())
               - main["attributed_s"]) < 1e-3   # t0, dur: 6 decimals
    assert ph["pass"]["span_s"] == pytest.approx(main["wall_s"])
    assert ph["pass"]["total_s"] < 0.5 * ph["pass"]["span_s"]
    assert ph["expand"]["total_s"] == pytest.approx(ph["expand"]["span_s"])
    assert proc["levels"], "level spans should yield one row a level"
    text = obs_collect.render_report(rep)
    assert "MainThread" in text and "expand" in text

    # the CLI over the same directory: collect, export, report
    from raft_tla_tpu.obs.tracecli import main as trace_main
    out = os.path.join(d, "trace.json")
    assert trace_main(["export", d, "-o", out]) == 0
    doc = json.loads(open(out).read())
    assert any(e["ph"] == "X" for e in doc["traceEvents"])
    assert trace_main(["collect", d]) == 0
    assert trace_main(["report", d, "--json"]) == 0


@pytest.mark.smoke
def test_traced_ddd_spans_form_one_tree(traced_toy):
    """pass > level > upload / expand / export > {segment_wait, d2h} /
    level_close, by ``parent_id`` alone."""
    res, evs, _d = traced_toy
    main = _main_spans(evs)
    by_id = {s["span_id"]: s for s in main}
    roots = [s for s in main if "parent_id" not in s]
    assert [s["name"] for s in roots] == ["pass"]
    parent_of = {s["span_id"]: by_id[s["parent_id"]]["name"]
                 for s in main if "parent_id" in s}
    names = {}
    for s in main:
        if "parent_id" in s:
            names.setdefault(s["name"], set()).add(parent_of[s["span_id"]])
    assert names["level"] == {"pass"}
    for child in ("upload", "expand", "export", "level_close"):
        assert names[child] == {"level"}, child
    assert names["segment_wait"] == names["d2h"] == {"export"}
    assert names["take"] == {"upload"}
    # the last drain runs after the levels, under the pass itself
    assert names["dedup_wait"] <= {"level_close", "pass"}
    assert "level_close" in names["dedup_wait"]
    p = roots[0]["args"]
    assert p["engine"] == "ddd" and p["resumed"] is False
    assert p["prescan"] is False             # CFG has no SYMMETRY
    assert p["n_states"] == N_TOY and p["stopped_by"] is None
    assert p["levels"] == len(res.levels)


@pytest.mark.smoke
def test_level_spans_carry_their_work_and_match_the_segments_track(
        traced_toy):
    res, evs, _d = traced_toy
    spans = [e for e in evs if e["event"] == "span"]
    levels = sorted((s for s in spans if s["name"] == "level"),
                    key=lambda s: s["t0"])
    assert [s["args"]["level"] for s in levels] \
        == list(range(1, len(res.levels) + 1))
    for s in levels:
        assert {"level", "rows", "blocks", "segments", "steps",
                "streamed_rows", "new_states"} <= set(s["args"])
    # a level's frontier is what the previous level discovered
    assert [s["args"]["rows"] for s in levels] == res.levels
    assert [s["args"]["new_states"] for s in levels[:-1]] \
        == res.levels[1:]
    segs = [s for s in spans if s["name"] == "segment"]
    assert segs and {s["thread"] for s in segs} == {"segments"}
    assert all("parent_id" not in s for s in segs)
    for lv in levels:
        mine = [s for s in segs
                if s["args"]["level"] == lv["args"]["level"]]
        assert len(mine) == lv["args"]["segments"]
        assert sum(s["args"]["steps"] for s in mine) \
            == lv["args"]["steps"]
        assert sum(s["args"]["streamed_rows"] for s in mine
                   if not s["args"]["dropped"]) \
            == lv["args"]["streamed_rows"]
    assert sum(s["args"]["n_valid"] for s in segs) == res.n_transitions
    # one segment_wait per harvested segment; d2h only where it streamed
    waits = [s for s in spans if s["name"] == "segment_wait"]
    d2h = [s for s in spans if s["name"] == "d2h"]
    assert len(waits) == len(segs)
    assert len(d2h) == sum(1 for s in segs if s["args"]["streamed_rows"])
    assert all(s["args"]["rows"] > 0 and s["args"]["bytes"] > 0
               for s in d2h)
    # an upload says what it sent: the live prefix rounded to pieces of
    # ``_up_rows`` rows, or the whole block past ``_up_whole`` of them
    from raft_tla_tpu.ddd_engine import _upload_plan
    from raft_tla_tpu.ops.bitpack import BitSchema
    block = _TOY_CAPS["block"]
    piece, whole_above = _upload_plan(block, CFG.chunk)
    row_bytes = 4 * BitSchema(CFG.bounds).P + 1
    ups = [s["args"] for s in spans if s["name"] == "upload"]
    assert ups and all(0 < a["rows"] <= a["padded_rows"] <= block
                       and a["bytes"] == a["padded_rows"] * row_bytes
                       and "prefetch_hit" in a for a in ups)
    for a in ups:
        n = -(-a["rows"] // piece)
        assert (a["pieces"], a["padded_rows"]) == (
            (n, n * piece) if n * piece <= whole_above else (1, block))
    flush = [s for s in spans if s["name"] == "dedup"]
    assert flush and all("keys" in s["args"] for s in flush)


@pytest.mark.smoke
def test_segment_spans_carry_the_slab_counters(traced_toy):
    """``stream_peak`` / ``stream_slabs`` (the segment program's slab
    writes, SegStats) ride the ``segment`` spans beside
    ``streamed_rows``; the ``level`` span holds its segments' maximum
    and total.  A toy chunk never overflows one slab, so a segment
    writes one slab a step and its peak bounds what it streamed."""
    _res, evs, _d = traced_toy
    spans = [e for e in evs if e["event"] == "span"]
    segs = [s for s in spans if s["name"] == "segment"]
    assert segs
    for s in segs:
        a = s["args"]
        assert {"stream_peak", "stream_slabs"} <= set(a)
        assert a["stream_slabs"] == a["steps"]
        assert a["stream_peak"] <= a["streamed_rows"] \
            <= a["stream_peak"] * max(a["steps"], 1)
    for lv in (s for s in spans if s["name"] == "level"):
        mine = [s["args"] for s in segs
                if s["args"]["level"] == lv["args"]["level"]]
        assert lv["args"]["stream_slabs"] \
            == sum(a["stream_slabs"] for a in mine)
        assert lv["args"]["stream_peak"] \
            == max(a["stream_peak"] for a in mine)
    last = [e for e in evs if e["event"] == "segment"][-1]
    assert last["stream_slabs"] == sum(s["args"]["stream_slabs"]
                                       for s in segs)
    assert last["stream_peak"] == max(s["args"]["stream_peak"]
                                      for s in segs)


@pytest.mark.smoke
def test_segment_and_level_spans_carry_the_lane_counters(traced_toy):
    """``lanes`` (chunk steps x chunk x A: what the dense step computed),
    ``n_valid`` and ``route_peak`` (SegStats.n_valid / .peak: how many of
    those lanes were enabled, in all and at most in one step) ride the
    ``segment`` spans; the ``level`` span holds its segments' sums and
    maximum; the pass's last ``segment`` record holds the same peak."""
    from raft_tla_tpu.models import spec as S
    res, evs, _d = traced_toy
    n_lanes = CFG.chunk * len(S.action_table(CFG.bounds, CFG.spec))
    spans = [e for e in evs if e["event"] == "span"]
    segs = [s["args"] for s in spans if s["name"] == "segment"]
    assert segs
    for a in segs:
        assert a["lanes"] == a["steps"] * n_lanes
        assert a["route_peak"] <= a["n_valid"] \
            <= a["route_peak"] * max(a["steps"], 1) <= max(a["lanes"], 0)
    levels = [s["args"] for s in spans if s["name"] == "level"]
    for lv in levels:
        mine = [a for a in segs if a["level"] == lv["level"]]
        assert lv["lanes"] == sum(a["lanes"] for a in mine) \
            == lv["steps"] * n_lanes
        assert lv["n_valid"] == sum(a["n_valid"] for a in mine)
        assert lv["route_peak"] == max(a["route_peak"] for a in mine)
    assert sum(lv["n_valid"] for lv in levels) == res.n_transitions
    last = [e for e in evs if e["event"] == "segment"][-1]
    assert last["route_peak"] == max(lv["route_peak"] for lv in levels) > 0


@pytest.mark.smoke
def test_segment_spans_carry_the_probe_tiles(traced_toy):
    """``probe_tiles`` (the filter probe's tiles, SegStats) rides the
    ``segment`` spans beside ``stream_slabs``; the ``level`` span holds its
    segments' total and the last segment record the pass's.  A toy chunk
    is narrower than the shipped tile, so a step takes one tile, or none
    when no lane of it is live."""
    _res, evs, _d = traced_toy
    spans = [e for e in evs if e["event"] == "span"]
    segs = [s for s in spans if s["name"] == "segment"]
    assert segs
    for s in segs:
        a = s["args"]
        assert (a["n_valid"] > 0) <= a["probe_tiles"] <= a["steps"]
    for lv in (s for s in spans if s["name"] == "level"):
        mine = [s["args"] for s in segs
                if s["args"]["level"] == lv["args"]["level"]]
        assert lv["args"]["probe_tiles"] \
            == sum(a["probe_tiles"] for a in mine)
    last = [e for e in evs if e["event"] == "segment"][-1]
    assert last["probe_tiles"] == sum(s["args"]["probe_tiles"]
                                      for s in segs) > 0


@pytest.mark.parametrize("tile", [8, 50])
def test_probe_tiles_are_the_live_lanes_over_the_tile(
        tile, tmp_path, monkeypatch):
    """One chunk step a segment, a tile far under the chunk's lanes: every
    ``segment`` span's ``probe_tiles`` is ``ceil(n_valid / T)`` of its one
    step, so the pass's total is that sum — and the verdict is the one
    the shipped tile gives."""
    import raft_tla_tpu.ddd_engine as ddd_mod
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    monkeypatch.setenv("RAFT_TLA_TRACE", "1")
    monkeypatch.setattr(ddd_mod, "_T_PROBE", tile)
    eng = DDDEngine(CFG, DDDCapacities(**_TOY_CAPS), seg_chunks=1)
    eng.SEG_MIN = eng.SEG_MAX = 1          # the pacer may not widen it
    log = str(tmp_path / "tiles.events")
    res = eng.check(events=log)
    assert res.n_states == N_TOY
    evs = [json.loads(l) for l in open(log)]
    assert all(validate_event(e) == [] for e in evs)
    segs = [e["args"] for e in evs
            if e["event"] == "span" and e["name"] == "segment"]
    assert segs and all(a["steps"] <= 1 for a in segs)
    want = [-(-a["n_valid"] // tile) for a in segs]
    assert [a["probe_tiles"] for a in segs] == want
    assert max(want) > 1                   # some step took several tiles
    last = [e for e in evs if e["event"] == "segment"][-1]
    assert last["probe_tiles"] == sum(want)
    levels = [e["args"] for e in evs
              if e["event"] == "span" and e["name"] == "level"]
    assert sum(a["probe_tiles"] for a in levels) == sum(want)


@pytest.mark.smoke
def test_self_times_over_the_tree_sum_to_the_pass_wall(traced_toy):
    _res, evs, d = traced_toy
    col = obs_collect.collect(obs_collect.find_logs(d))
    main = [s for s in col["spans"] if s["thread"] == "MainThread"]
    self_s, kids, roots = obs_collect.self_times(main)
    (root,) = roots
    assert root["name"] == "pass"
    assert sum(self_s.values()) == pytest.approx(root["dur"], abs=1e-4)
    # and level by level: a level's self time plus its children's walls
    for s in main:
        if s["name"] == "level":
            covered = sum(c["dur"] for c in kids[s["span_id"]])
            assert self_s[id(s)] + covered \
                == pytest.approx(s["dur"], abs=1e-4)


@pytest.mark.smoke
def test_trace_report_prints_level_rows_from_the_level_spans(
        traced_toy, capsys):
    res, _evs, d = traced_toy
    assert not hasattr(obs_collect, "_level_critical_path")
    from raft_tla_tpu.obs.tracecli import main as trace_main
    assert trace_main(["report", d]) == 0
    text = capsys.readouterr().out
    rows = [l for l in text.splitlines() if l.startswith("  L")]
    assert len(rows) == len(res.levels)
    assert rows[0].startswith("  L1: ") and "1 rows" in rows[0]
    assert all("segments" in r and "steps" in r and "self " in r
               and " slabs (peak " in r and " probe tiles, " in r
               and "most in: " in r
               and " lanes enabled (peak " in r for r in rows)
    assert " self " in text and "in spans)" in text
    rep = obs_collect.report(obs_collect.collect(
        obs_collect.find_logs(d)))
    (proc,) = rep["processes"]
    assert [r["level"] for r in proc["levels"]] \
        == list(range(1, len(res.levels) + 1))
    assert sum(r["steps"] for r in proc["levels"]) > 0
    assert {r["dominant_child"] for r in proc["levels"]} \
        <= {"upload", "expand", "export", "level_close", "dedup_submit",
            "dedup", "devdedup", "snapshot"}


@pytest.mark.smoke
def test_perfetto_exports_the_segments_and_compiles_tracks(traced_toy):
    _res, _evs, d = traced_toy
    col = obs_collect.collect(obs_collect.find_logs(d))
    evs = obs_perfetto.to_trace_events(col)
    tracks = {e["args"]["name"]: e["tid"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tracks["MainThread"] == 1
    assert {"segments", "compiles", "raft-tla-prefetch"} <= set(tracks)
    on = {}
    for e in evs:
        if e["ph"] == "X":
            on.setdefault(e["name"], set()).add(e["tid"])
    assert on["segment"] == {tracks["segments"]}
    assert on["compile"] == {tracks["compiles"]}
    seg = next(e for e in evs if e["ph"] == "X" and e["name"] == "segment")
    assert {"level", "block", "budget", "steps"} <= set(seg["args"])


@pytest.mark.smoke
def test_untraced_ddd_run_emits_no_span(tmp_path, monkeypatch):
    """The off path: the log of an untraced run holds no span and the
    run's tracer emits nothing (``enabled`` is False).  Since PR 38 the
    sites the pass ledger reads (obs/passlog: pass, level, the seams) are
    timed for it alone; every other site (export, take, snapshot, the
    segments track) still goes through the one shared null handle."""
    monkeypatch.delenv("RAFT_TLA_TRACE", raising=False)
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    from raft_tla_tpu.obs import passlog
    from raft_tla_tpu.obs import trace as obs_trace
    opened, live, null, manual = [], set(), set(), []
    real_open = obs_trace.SpanTracer.open
    real_span = obs_trace.SpanTracer.span
    real_emit = obs_trace.SpanTracer.emit_span

    def spy_open(self, name, **a):
        assert not self.enabled
        opened.append(name)
        return real_open(self, name, **a)

    def spy_span(self, name, **a):
        sp = real_span(self, name, **a)
        (null if sp is obs_trace._NULL_SPAN else live).add(name)
        return sp

    def spy_emit(self, name, *a, **kw):
        manual.append(name)
        return real_emit(self, name, *a, **kw)

    monkeypatch.setattr(obs_trace.SpanTracer, "open", spy_open)
    monkeypatch.setattr(obs_trace.SpanTracer, "span", spy_span)
    monkeypatch.setattr(obs_trace.SpanTracer, "emit_span", spy_emit)
    log = str(tmp_path / "off.events")
    res = DDDEngine(CFG, DDDCapacities(**_TOY_CAPS)).check(events=log)
    assert res.n_states == N_TOY
    evs = [json.loads(l) for l in open(log)]
    assert not [e for e in evs if e["event"] == "span"]
    assert opened[0] == "pass" and set(opened[1:]) == {"level"}
    assert len(opened) == 1 + len(res.levels)
    assert live and live <= passlog.NAMES and not (null & passlog.NAMES)
    assert {"segment_wait", "d2h", "level_close"} <= live
    assert not manual                    # emit_span sits behind tr.enabled
    assert evs[-1]["event"] == "run_end"


def test_compile_ledger_counts_a_fresh_jit_once():
    """The program's own compile counter: a fresh jit is one trace, one
    lowering and one backend compile; its second call is nothing."""
    import jax
    import jax.numpy as jnp

    from raft_tla_tpu.obs import compiles
    compiles.install()
    assert compiles.install() is False       # idempotent
    x = jnp.arange(8)

    @jax.jit
    def fresh_for_the_ledger(v):
        return jnp.where(v > 3, v * 2, v).sum()  # nested jits inside

    before = compiles.LEDGER.totals()
    fresh_for_the_ledger(x).block_until_ready()
    once = compiles.totals_since(before, compiles.LEDGER.totals())
    assert once["trace"][0] == once["lower"][0] == once["backend"][0] == 1
    assert all(once[k][1] > 0 for k in ("trace", "lower", "backend"))
    mid = compiles.LEDGER.totals()
    fresh_for_the_ledger(x).block_until_ready()
    assert compiles.totals_since(mid, compiles.LEDGER.totals()) == {}
    recs = [r for r in compiles.snapshot()["records"]
            if r["fun"] and "fresh_for_the_ledger" in r["fun"]]
    assert sorted(r["kind"] for r in recs) == ["backend", "lower", "trace"]


def test_compile_ledger_keeps_outermost_traces_and_feeds_the_tracer():
    """Driven without JAX: a trace that opens inside another is held by
    the outer one's duration and not recorded again; every record is a
    ``compile`` span on the ``compiles`` track while a tracer is
    attached; the list is bounded and says what it dropped."""
    from raft_tla_tpu.obs.compiles import CompileLedger, totals_since
    tr, rows = _capture_tracer()
    led = CompileLedger(max_records=3)
    led.attach(tr)
    ev = "/jax/core/compile/jaxpr_trace_duration"
    led.on_scalar(ev, 0.0, fun_name="segment")
    led.on_scalar(ev, 0.0, fun_name="_where")
    led.on_duration(ev, 0.001, fun_name="_where")      # nested: dropped
    led.on_duration(ev, 0.5, fun_name="segment")
    led.on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration",
                    0.2, fun_name="jit(segment)")
    led.on_event("/jax/compilation_cache/cache_hits")
    led.on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                    0.1)
    led.on_duration("/jax/core/compile/backend_compile_duration", 0.3,
                    fun_name="jit(segment)")
    led.on_duration("/jax/unrelated", 9.0)
    snap = led.snapshot()
    assert snap["totals"] == {"trace": [1, 0.5], "lower": [1, 0.2],
                              "cache_hits": 1, "cache_load": [1, 0.1],
                              "backend": [1, 0.3]}
    assert [r["kind"] for r in snap["records"]] \
        == ["lower", "cache_load", "backend"] and snap["dropped"] == 1
    assert [(r["name"], r["thread"], r["args"]["kind"]) for r in rows] \
        == [("compile", "compiles", k)
            for k in ("trace", "lower", "cache_load", "backend")]
    assert rows[0]["args"]["fun"] == "segment" and rows[0]["dur"] == 0.5
    led.detach(tr)
    led.on_duration("/jax/core/compile/backend_compile_duration", 0.3)
    assert len(rows) == 4
    assert totals_since(snap["totals"], led.totals()) \
        == {"backend": [1, 0.3]}


def test_run_end_carries_the_runs_compile_totals(traced_toy):
    _res, evs, _d = traced_toy
    end = evs[-1]
    assert end["event"] == "run_end" and validate_event(end) == []
    comp = [e for e in evs if e["event"] == "span"
            and e["name"] == "compile"]
    assert {e["thread"] for e in comp} == {"compiles"}
    for kind in ("trace", "lower", "backend"):
        n = sum(1 for e in comp if e["args"]["kind"] == kind)
        assert n >= 1 and end["compiles"][kind][0] == n
    assert any(e["args"].get("fun") == "jit(segment)" for e in comp)


@pytest.mark.smoke
def test_pool_run_merges_into_one_timeline(tmp_path, monkeypatch):
    """The multi-process acceptance bar: a traced --workers 2 pool run
    leaves logs that collect into ONE timeline — pool supervisor with
    worker-lifetime spans, each worker's scheduler with dispatch/
    harvest/ticket spans, each tenant engine with phase spans — all
    anchored, distinct pids, Perfetto-exportable."""
    monkeypatch.setenv("RAFT_TLA_TRACE", "1")
    from test_cli import write_cfg

    from raft_tla_tpu.serve.jobs import CheckJob, JobOptions
    from raft_tla_tpu.serve.pool import run_pool
    from raft_tla_tpu.serve.supervise import PoolPolicy
    cfg = write_cfg(tmp_path / "toy.cfg")
    opts = JobOptions(spec="election", max_term=2, max_log=0, max_msgs=1)
    opts_sym = JobOptions(spec="election", max_term=2, max_log=0,
                          max_msgs=1, symmetry=True)
    jobs = [CheckJob("j0", opts, cfg_path=str(cfg)),
            CheckJob("j1", opts_sym, cfg_path=str(cfg))]
    out = str(tmp_path / "out")
    recs = run_pool(jobs, out, workers=2, chunk=256, cpu=True,
                    quiet=True,
                    policy=PoolPolicy(poll_s=0.02, backoff_base_s=0.05,
                                      backoff_cap_s=0.2,
                                      backoff_jitter_seed=7))
    assert all(r["status"] == "completed" for r in recs)

    logs = obs_collect.find_logs(out)
    assert any(p.endswith("pool.events") for p in logs)
    assert sum("sched-" in os.path.basename(p) for p in logs) == 2
    col = obs_collect.collect(logs)
    by_engine = {}
    for p in col["processes"]:
        by_engine.setdefault(p["engine"], []).append(p)
    assert len(by_engine["pool"]) == 1
    assert len(by_engine["sched"]) == 2
    assert len(by_engine["serve"]) == 2          # tenant logs
    assert all(p["anchored"] for p in col["processes"])
    assert col["skew_bound_s"] is not None
    # >= 3 distinct OS processes: the supervisor + 2 workers (each
    # worker contributes a sched row AND its tenant rows, same os_pid)
    assert len({p["os_pid"] for p in col["processes"]}) >= 3
    sched_os = {p["os_pid"] for p in by_engine["sched"]}
    serve_os = {p["os_pid"] for p in by_engine["serve"]}
    assert serve_os <= sched_os         # tenants ran inside the workers

    sup = by_engine["pool"][0]
    sup_spans = [s for s in col["spans"] if s["pid"] == sup["pid"]]
    assert {s["name"] for s in sup_spans} >= {"worker"}
    assert {s["thread"] for s in sup_spans} == {"workers"}
    sched_spans = [s for s in col["spans"]
                   if s["pid"] in {p["pid"] for p in by_engine["sched"]}]
    assert {s["name"] for s in sched_spans} >= {"dispatch", "harvest",
                                                "ticket", "compile"}
    assert "tickets" in {s["thread"] for s in sched_spans}

    rep = obs_collect.report(col)
    assert len(rep["processes"]) == len(col["processes"])
    out_json = str(tmp_path / "pool_trace.json")
    n = obs_perfetto.export(col, out_json)
    assert n > 0
    doc = json.loads(open(out_json).read())
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert len(pids) >= 3                        # distinct tracks
