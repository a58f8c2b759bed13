"""The pass ledger (obs/passlog.py, PR 38): every ``check()`` of the ddd
engines keeps its own level-by-level account, traced or not, through the
sites the span tracer already had.

Under test: an untraced ``check()`` with no ``events`` and no environment
variable leaves one record in ``passlog.snapshot()`` and on the result, one
entry a level, tiling the pass's wall; a traced pass of the same engine object
leaves the same record with the same counts, each entry's wall being its
``level`` span's; on ``ddd`` and on ``ddd-shard`` (four host devices).  Then
the ring, the thread attribution, the sink-only tracer, the phase seams and
the operator's view (``run_end.level_log``, ``raft-tla-trace report``).
"""

import gc
import json
import threading
import time

import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.obs import passlog
from raft_tla_tpu.obs.events import RunTelemetry, validate_event
from raft_tla_tpu.obs.phases import _NULL as NULL_PHASE
from raft_tla_tpu.obs.phases import PhaseTimers
from raft_tla_tpu.obs.trace import _NULL_SPAN, SpanTracer

CFG = CheckConfig(
    bounds=Bounds(n_servers=2, n_values=1, max_term=2, max_log=0,
                  max_msgs=2),
    spec="election", invariants=("NoTwoLeaders",), chunk=32)
N_TOY = 3014
SEAMS = ("upload_s", "expand_s", "wait_s", "d2h_s", "dedup_s", "close_s")
FIELDS = {"level", "t0", "gap_s", "wall_s", "rows", "row_words", "blocks",
          "segments", "steps", "streamed_rows", "new_states", "upload_s", "uploads",
          "upload_bytes", "upload_pieces", "d2h_bytes", "expand_s", "wait_s",
          "d2h_s", "dedup_s", "close_s", "cpu_s", "gc_s", "majflt", "nivcsw"}


def _build(kind):
    if kind == "ddd":
        from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
        return DDDEngine(CFG, DDDCapacities(block=256, table=1 << 14,
                                            flush=1 << 10, levels=64))
    from raft_tla_tpu.parallel.ddd_shard_engine import (DDDShardCapacities,
                                                        DDDShardEngine)
    from raft_tla_tpu.parallel.mesh import make_mesh
    return DDDShardEngine(
        CFG, make_mesh(4),
        DDDShardCapacities(block=256, table=1 << 14, seg_rows=1 << 14,
                           flush=1 << 10, levels=64))


@pytest.fixture(scope="module", params=["ddd", "ddd-shard"])
def two_passes(request, tmp_path_factory):
    """One engine object, two passes of a small complete space: untraced
    (no ``events``, no ``RAFT_TLA_TRACE``), then traced.  The worker threads
    are forced on so that their seams exist."""
    mp = pytest.MonkeyPatch()
    mp.setenv("RAFT_TLA_HOSTDEDUP", "on")
    mp.setenv("RAFT_TLA_PREFETCH", "on")
    mp.delenv("RAFT_TLA_TRACE", raising=False)
    mp.delenv("RAFT_TLA_EVENTS", raising=False)
    mp.delenv("RAFT_TLA_PHASE_TIMERS", raising=False)
    try:
        eng = _build(request.param)
        before = {id(r) for r in passlog.LEDGER._records}
        plain = eng.check()
        fresh = [r for r in passlog.snapshot()["records"]
                 if r["t0"] == plain.level_log["t0"]]
        n_new = sum(id(r) not in before for r in passlog.LEDGER._records)
        log = str(tmp_path_factory.mktemp("passlog") / "traced.events")
        mp.setenv("RAFT_TLA_TRACE", "1")
        traced = eng.check(events=log)
    finally:
        mp.undo()
    evs = [json.loads(line) for line in open(log)]
    return {"kind": request.param, "plain": plain, "traced": traced,
            "snapshot_hits": fresh, "n_new": n_new, "events": evs,
            "log": log,
            "levels": sorted((e for e in evs if e["event"] == "span"
                              and e["name"] == "level"),
                             key=lambda e: e["t0"])}


def test_untraced_check_leaves_one_record(two_passes):
    res, kind = two_passes["plain"], two_passes["kind"]
    rec = res.level_log
    assert res.n_states == N_TOY and res.complete
    # one record, in the ledger and on the result, the same one
    assert two_passes["n_new"] == 1 and len(two_passes["snapshot_hits"]) == 1
    snap = two_passes["snapshot_hits"][0]
    assert snap == rec and snap is not rec and snap["levels"] is not \
        rec["levels"]
    assert (rec["engine"], rec["resumed"], rec["stopped_by"],
            rec["n_states"]) == (kind, False, None, N_TOY)
    # one entry a level: every frontier expanded, the empty last one too
    entries = rec["levels"]
    assert [lv["level"] for lv in entries] == \
        list(range(1, len(res.levels) + 1))
    assert [lv["new_states"] for lv in entries] == res.levels[1:] + [0]
    assert all(set(lv) == FIELDS for lv in entries)
    # the levels and the gaps between them tile the pass between its head
    # and its tail, by construction (one chain of stamps): however loaded
    # the host, nothing of the wall is outside the account
    tiled = sum(lv["gap_s"] + lv["wall_s"] for lv in entries) \
        + rec["head_s"] + rec["tail_s"]
    assert tiled == pytest.approx(rec["wall_s"], abs=1e-6)
    assert entries[0]["gap_s"] == 0.0 and rec["head_s"] > 0
    assert all(lv["gap_s"] > 0 for lv in entries[1:])
    assert isinstance(rec["stalls"], list)
    assert 0 < rec["wall_s"] <= res.wall_s
    assert entries[0]["t0"] == pytest.approx(rec["t0"] + rec["head_s"])
    for lv in entries:
        assert lv["wall_s"] > 0 and lv["cpu_s"] >= 0 and lv["gc_s"] >= 0
        assert lv["uploads"] == lv["blocks"] >= 1 and lv["segments"] >= 1
        for seam in SEAMS:
            assert 0 <= lv[seam] <= lv["wall_s"] + 1e-6, (seam, lv)
        assert lv["wait_s"] > 0 and lv["upload_s"] > 0
    assert sum(lv["streamed_rows"] for lv in entries) >= N_TOY - 1
    # the prefetcher staged the blocks on its own thread
    assert rec["threads"].get("prefetch@raft-tla-prefetch", 0) > 0
    assert all("@" in k for k in rec["threads"])


def test_untraced_counts_equal_the_traced_level_spans(two_passes):
    """Same engine object, same space: what the ledger counted with
    tracing off is what the ``level`` spans carry with it on (on the mesh
    too, whose spans gained ``streamed_rows`` with this ledger)."""
    entries = two_passes["plain"].level_log["levels"]
    spans = two_passes["levels"]
    assert len(spans) == len(entries)
    for lv, sp in zip(entries, spans):
        for key in ("level", "rows", "blocks", "steps", "streamed_rows",
                    "new_states"):
            assert lv[key] == sp["args"][key], (key, lv["level"])


def test_traced_pass_leaves_the_same_record_from_the_same_sites(two_passes):
    """With spans on the ledger is a second sink of the same handles: each
    entry's ``t0`` and ``wall_s`` are its ``level`` span's."""
    rec = two_passes["traced"].level_log
    plain = two_passes["plain"].level_log
    spans = two_passes["levels"]
    assert two_passes["traced"].n_states == N_TOY
    assert len(rec["levels"]) == len(spans) == len(plain["levels"])
    for lv, sp in zip(rec["levels"], spans):
        assert abs(lv["wall_s"] - sp["dur"]) < 1e-3
        assert abs(lv["t0"] - sp["t0"]) < 1e-3
        assert lv["segments"] == sp["args"]["segments"]
    for key in ("level", "rows", "blocks", "steps", "streamed_rows",
                "new_states", "uploads"):
        assert [lv[key] for lv in rec["levels"]] == \
            [lv[key] for lv in plain["levels"]], key
    # the pass span closes the record: its end is the span's
    pass_sp = next(e for e in two_passes["events"]
                   if e["event"] == "span" and e["name"] == "pass")
    assert abs(rec["t0"] + rec["wall_s"]
               - (pass_sp["t0"] + pass_sp["dur"])) < 1e-3
    # ... and the log's run_end carries it (schema v14), rounded
    end = two_passes["events"][-1]
    assert end["event"] == "run_end" and validate_event(end) == []
    assert end["level_log"] == passlog.rounded(rec)


def test_level_spans_carry_the_slab_counts_on_both_engines(two_passes):
    """``stream_slabs`` / ``stream_peak`` on every ``level`` span (ISSUE 48
    gave the mesh step the one-chip step's slab writes and with them the
    counts: the most any shard wrote, summed over the level's segments, and
    the most rows any shard streamed in one step).  At a toy's size a step
    streams less than a slab: one slab a step.  The report's ``L<k>`` rows
    print them; the ledger's entries keep to their own fields."""
    from raft_tla_tpu.obs import collect

    spans = two_passes["levels"]
    assert spans
    for sp in spans:
        a = sp["args"]
        assert a["stream_slabs"] == a["steps"]
        assert 0 <= a["stream_peak"] <= a["streamed_rows"]
        assert (a["stream_peak"] > 0) == (a["streamed_rows"] > 0)
    assert max(sp["args"]["stream_peak"] for sp in spans) > 0
    assert not {"stream_slabs", "stream_peak"} \
        & set(two_passes["traced"].level_log["levels"][0])
    rep = collect.report(collect.collect([two_passes["log"]]))
    rows = rep["processes"][0]["levels"]
    assert [(r["stream_slabs"], r["stream_peak"]) for r in rows] \
        == [(sp["args"]["stream_slabs"], sp["args"]["stream_peak"])
            for sp in spans]


def test_upload_bytes_and_pieces_are_the_sums_of_the_upload_spans(two_passes):
    """What a level's uploads sent (ISSUE 39): ``upload_bytes`` and
    ``upload_pieces`` of an entry are the sums of ``bytes`` and ``pieces``
    over the ``upload`` spans under that level's span, traced pass and
    untraced pass of one engine object alike.  The mesh engine's spans do
    not say, and its entries read 0."""
    spans = [e for e in two_passes["events"] if e["event"] == "span"]
    for key in ("upload_bytes", "upload_pieces"):
        assert [lv[key] for lv in two_passes["traced"].level_log["levels"]] \
            == [lv[key] for lv in two_passes["plain"].level_log["levels"]]
    for lv, sp in zip(two_passes["traced"].level_log["levels"],
                      two_passes["levels"]):
        ups = [u.get("args", {}) for u in spans if u["name"] == "upload"
               and u.get("parent_id") == sp["span_id"]]
        assert len(ups) == lv["uploads"]
        assert lv["upload_bytes"] == sum(u.get("bytes", 0) for u in ups)
        assert lv["upload_pieces"] == sum(u.get("pieces", 0) for u in ups)
        if two_passes["kind"] == "ddd":
            assert lv["upload_pieces"] >= lv["uploads"]
            assert lv["upload_bytes"] >= lv["rows"] * 4
        else:
            assert (lv["upload_bytes"], lv["upload_pieces"]) == (0, 0)


def test_d2h_bytes_is_the_sum_of_the_d2h_spans(two_passes):
    """What a level's harvests fetched (ISSUE 45): ``d2h_bytes`` of an entry
    is the sum of ``bytes`` over the ``d2h`` spans inside that level's span,
    traced pass and untraced pass alike, on both engines: the one-chip
    engine's whole buffer set, the mesh's head of each shard's buffers."""
    traced = two_passes["traced"].level_log["levels"]
    assert [lv["d2h_bytes"] for lv in traced] == \
        [lv["d2h_bytes"] for lv in two_passes["plain"].level_log["levels"]]
    d2h = [e for e in two_passes["events"]
           if e["event"] == "span" and e["name"] == "d2h"]
    assert d2h and all(e["args"]["bytes"] > 0 for e in d2h)
    for lv, sp in zip(traced, two_passes["levels"]):
        inside = [e["args"]["bytes"] for e in d2h
                  if sp["t0"] <= e["t0"] < sp["t0"] + sp["dur"]]
        assert lv["d2h_bytes"] == sum(inside)
        assert (lv["d2h_bytes"] > 0) == (lv["streamed_rows"] > 0)
    if two_passes["kind"] == "ddd-shard":
        assert {e["args"]["path"] for e in d2h} == {"head"}


def _drive(plog, levels=1):
    """An empty pass through a sink-only tracer, as the engines drive it."""
    tr = SpanTracer(None, sink=plog)
    pass_sp = tr.open("pass", engine="ddd")
    for k in range(levels):
        sp = tr.open("level", level=k + 1, rows=1, blocks=1)
        with tr.span("upload"):
            pass
        sp.set(segments=1, steps=1, streamed_rows=0, new_states=0).close()
    pass_sp.set(levels=levels, n_states=7, stopped_by="sigint").close()
    pass_sp.close()                      # the cleanup's second close
    return tr


def test_ring_keeps_64_and_counts_dropped():
    ring = passlog.PassLedger()
    assert ring.snapshot() == {"records": [], "dropped": 0}
    for k in range(70):
        _drive(passlog.PassLog("ddd", False, float(k), ledger=ring))
    snap = ring.snapshot()
    assert passlog.KEEP == 64 and len(snap["records"]) == 64
    assert snap["dropped"] == 6
    assert [r["t0"] for r in snap["records"]] == \
        [float(k) for k in range(6, 70)]            # oldest first
    rec = snap["records"][-1]
    assert (rec["stopped_by"], rec["n_states"]) == ("sigint", 7)
    assert len(rec["levels"]) == 1 and rec["levels"][0]["uploads"] == 1
    # a snapshot is a copy: changing it changes nothing the ledger holds
    rec["levels"].clear()
    assert len(ring.snapshot()["records"][-1]["levels"]) == 1


def test_worker_thread_seam_lands_under_name_at_thread():
    ring = passlog.PassLedger()
    plog = passlog.PassLog("ddd", False, time.monotonic(), ledger=ring)
    tr = SpanTracer(None, sink=plog)
    pass_sp = tr.open("pass")
    level_sp = tr.open("level", level=1)

    def flush():
        with tr.span("dedup"):                       # a real span ...
            pass
        plog.closed("dedup", 0.0, 5.0, {})           # ... and 5 s by hand

    t = threading.Thread(target=flush, name="raft-tla-flush")
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    with tr.span("dedup_wait"):
        pass
    plog.closed("dedup_submit", 0.0, 0.25, {})       # the main thread's
    level_sp.close()
    pass_sp.close()
    late = threading.Thread(target=flush, name="raft-tla-flush")
    late.start()                         # after the record closed: dropped
    late.join(timeout=30)
    assert not late.is_alive()
    rec = ring.snapshot()["records"][0]
    assert set(rec["threads"]) == {"dedup@raft-tla-flush"}
    assert 5.0 <= rec["threads"]["dedup@raft-tla-flush"] < 10.0  # one 5 s
    assert 0.25 <= rec["levels"][0]["dedup_s"] < 5.0     # the main's alone


def test_worker_seams_from_many_threads_lose_no_update():
    """More workers than cores, a short switch interval: the ``threads``
    bucket is read-modify-write under the log's lock, so every close
    lands (whole seconds add exactly)."""
    import sys
    ring = passlog.PassLedger()
    plog = passlog.PassLog("ddd", False, 0.0, ledger=ring)
    n_threads, n_each = 32, 500

    def work():
        for _ in range(n_each):
            plog.closed("dedup", 0.0, 1.0, {})

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=work, name="raft-tla-flush")
                   for _ in range(n_threads)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in workers)
    assert plog.record["threads"] == {
        "dedup@raft-tla-flush": float(n_threads * n_each)}


def test_sink_only_tracer_emits_nothing_and_nulls_other_sites():
    ring = passlog.PassLedger()
    tr = _drive(passlog.PassLog("ddd", False, 0.0, ledger=ring), levels=0)
    assert not tr.enabled and tr.current_id() is None
    assert tr.wants("segment_wait") and not tr.wants("export")
    assert tr.span("export") is _NULL_SPAN and tr.open("x") is _NULL_SPAN
    tr.emit_span("segment", 0.0, 1.0, thread="segments")   # a no-op
    rec = ring.snapshot()["records"][0]
    # no level opened: the whole pass is head
    assert rec["levels"] == [] and rec["head_s"] == rec["wall_s"]
    assert rec["tail_s"] == 0.0


def test_phase_seams_feed_the_ledger_without_flag_or_sync():
    """``PhaseTimers`` off (no ``RAFT_TLA_PHASE_TIMERS``): the phases the
    ledger reads are live and never sync, the others stay the null handle,
    and nothing accumulates in ``phase_s``."""
    tel = RunTelemetry("ddd", level_log=True)
    assert not tel.phases.enabled and not tel.trace.enabled
    assert not tel.active
    tr = tel.trace
    pass_sp = tr.open("pass")
    level_sp = tr.open("level", level=1)

    class NeverBlock:
        def block_until_ready(self):     # pragma: no cover - must not run
            raise AssertionError("a ledger seam synced")

    with tel.phases.phase("upload") as ph:
        assert ph is not NULL_PHASE
        ph.sync(NeverBlock())
        ph.set(rows=1)
        time.sleep(0.002)
    assert tel.phases.phase("export") is NULL_PHASE
    assert tel.phases.phase("snapshot") is NULL_PHASE
    level_sp.close()
    pass_sp.close()
    tel.close()
    lv = tel.passlog.record["levels"][0]
    assert lv["upload_s"] >= 0.002 and lv["uploads"] == 1
    assert tel.phases.snapshot() == {}
    assert PhaseTimers().phase("upload") is NULL_PHASE     # no tracer


def test_level_counts_the_collector_and_the_threads_cpu():
    ring = passlog.PassLedger()
    plog = passlog.PassLog("ddd", False, time.monotonic(), ledger=ring)
    tr = SpanTracer(None, sink=plog)
    pass_sp = tr.open("pass")
    quiet = tr.open("level", level=1)
    time.sleep(0.01)
    quiet.close()
    busy = tr.open("level", level=2)
    junk = [[k] for k in range(20000)]
    gc.collect()
    t_end = time.thread_time() + 0.01
    while time.thread_time() < t_end:
        pass
    busy.close()
    pass_sp.close()
    del junk
    lv1, lv2 = ring.snapshot()["records"][0]["levels"]
    assert lv1["cpu_s"] < 0.005 <= lv1["wall_s"]     # blocked, not computing
    assert lv2["gc_s"] > 0 and lv2["cpu_s"] >= 0.009
    assert lv2["gc_s"] <= lv2["wall_s"]


def _timed(ring, walls, rows=10, engine="ddd", resumed=False, head=0.01):
    """A pass whose level k+1 took ``walls[k]`` seconds (0.1 s of it in
    ``upload``), stamped by hand through the sink's own calls."""
    t = 1000.0 * (1 + ring.snapshot()["dropped"] + len(ring._records))
    plog = passlog.PassLog(engine, resumed, t, ledger=ring)
    t += head
    for k, wall in enumerate(walls):
        plog.opened("level", t)
        plog.closed("upload", t, min(wall, 0.1), {})
        plog.closed("level", t, wall, {"level": k + 1, "rows": rows * (k + 1),
                                       "blocks": 1, "segments": 1})
        t += wall + 0.001
    plog.closed("pass", plog.record["t0"], t + 0.004 - plog.record["t0"],
                {"n_states": 7})
    return plog.record


def test_levels_and_gaps_tile_the_pass_by_construction():
    rec = _timed(passlog.PassLedger(), [0.5, 0.25, 0.125])
    assert [lv["gap_s"] for lv in rec["levels"]] == \
        pytest.approx([0.0, 0.001, 0.001])
    assert (rec["head_s"], rec["tail_s"]) == pytest.approx((0.01, 0.005))
    assert rec["wall_s"] == pytest.approx(0.01 + 0.875 + 0.002 + 0.005)
    assert rec["head_s"] + rec["tail_s"] + sum(
        lv["gap_s"] + lv["wall_s"] for lv in rec["levels"]) \
        == pytest.approx(rec["wall_s"], abs=1e-9)


@pytest.mark.parametrize("case", ["level", "head", "under_the_floor",
                                  "first_pass", "other_rows",
                                  "other_engine", "other_start"])
def test_a_stall_is_told_as_the_pass_returns(case, capsys):
    """The ledger holds a closing pass against the passes before it: a
    level (or the head) that ran longer than the median of the same level
    there by more than max(0.25 s, median) is in ``record["stalls"]`` and
    is one line on stderr — with tracing off, where every stall so far
    fell."""
    ring = passlog.PassLedger()
    walls = [0.02, 0.3, 0.4]
    if case != "first_pass":
        for _ in range(3):
            assert _timed(ring, walls)["stalls"] == []
    assert capsys.readouterr().err == ""
    late = {"level": dict(walls=[0.02, 2.3, 0.4]),
            "head": dict(walls=walls, head=1.5),
            # +0.24 s on 0.02: twelve times the median and no stall
            "under_the_floor": dict(walls=[0.26, 0.3, 0.4]),
            "first_pass": dict(walls=[0.02, 2.3, 0.4]),
            "other_rows": dict(walls=[0.02, 2.3, 0.4], rows=11),
            "other_engine": dict(walls=[0.02, 2.3, 0.4],
                                 engine="ddd-shard"),
            "other_start": dict(walls=[0.02, 2.3, 0.4], resumed=True)}[case]
    rec = _timed(ring, **late)
    err = capsys.readouterr().err
    if case not in ("level", "head"):
        assert rec["stalls"] == [] and err == ""
        return
    (st,) = rec["stalls"]
    assert err.count("\n") == 1 and err == passlog.stall_line(rec, st) + "\n"
    assert st["passes"] == 3
    if case == "head":
        assert st == {"level": "head", "wall_s": pytest.approx(1.5),
                      "median_s": pytest.approx(0.01), "passes": 3}
        assert ", head: wall 1.500s against a median of 0.010s over the " \
            "last 3 passes" in err
        return
    assert (st["level"], st["uploads"]) == (2, 1)
    assert (st["wall_s"], st["median_s"], st["upload_s"]) == \
        pytest.approx((2.3, 0.3, 0.1))
    assert err.startswith("raft-tla pass ledger: stall in the ddd pass at "
                          "t0=4000.000, level 2: wall 2.300s against a "
                          "median of 0.300s over the last 3 passes: "
                          "upload_s 0.100 expand_s 0.000 wait_s 0.000 ")
    for key in ("d2h_s", "dedup_s", "close_s", "uploads 1", "cpu_s", "gc_s",
                "majflt", "nivcsw"):
        assert f" {key}" in err
    # the copies a reader gets hold it too, and an event line rounds it
    snap = ring.snapshot()["records"][-1]
    assert snap["stalls"] == [st] and snap["stalls"][0] is not st
    assert passlog.rounded(rec)["stalls"][0]["wall_s"] == 2.3
    # a stalled pass is one of eight: the next sound one is still sound
    assert _timed(ring, walls)["stalls"] == []


def test_events_alone_carry_the_level_log_and_the_report_prints_it(
        tmp_path, monkeypatch, capsys):
    """An operator's view: ``--events`` alone (no ``--trace``) leaves the
    record in ``run_end``, and ``raft-tla-trace report`` prints the level
    table from it when the log holds no spans."""
    from raft_tla_tpu.obs import tracecli
    monkeypatch.delenv("RAFT_TLA_TRACE", raising=False)
    log = str(tmp_path / "plain.events")
    res = _build("ddd").check(events=log)
    evs = [json.loads(line) for line in open(log)]
    assert all(validate_event(e) == [] for e in evs)
    assert not [e for e in evs if e["event"] == "span"]
    end = evs[-1]
    assert end["event"] == "run_end" and end["v"] >= 14
    assert end["level_log"] == passlog.rounded(res.level_log)
    assert tracecli.main(["report", log]) == 0
    out = capsys.readouterr().out
    assert "pass ledger (no spans in this log): ddd wall" in out
    for lv in res.level_log["levels"]:
        assert f"  L{lv['level']}: " in out
    assert "upload " in out and "nivcsw " in out
    assert "prefetch@raft-tla-prefetch" in out or \
        not res.level_log["threads"]
    assert tracecli.main(["report", "--json", log]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["processes"][0]["level_log"] == end["level_log"]


def test_engines_that_keep_no_ledger_leave_level_log_none():
    from raft_tla_tpu import engine
    from raft_tla_tpu.obs.trace import NULL_TRACER
    before = passlog.snapshot()
    res = engine.check(CFG)
    assert res.n_states == N_TOY and res.level_log is None
    after = passlog.snapshot()
    assert [r["t0"] for r in after["records"]] == \
        [r["t0"] for r in before["records"]]
    # ... and their telemetry stays on the null tracer
    tel = RunTelemetry("device")
    assert tel.trace is NULL_TRACER and tel.passlog is None
    tel.close()
    # the field takes no part in comparing or printing a result
    import dataclasses
    twin = dataclasses.replace(res, level_log={"wall_s": 1.0})
    assert twin == res and "level_log" not in repr(twin)
