"""The benchmark's readers of the program's pass ledger (PR 38):
``benchmark/harness/levelred.py`` and the five ``benchmark/metrics/<name>.py``
behind it, on a small recorded shape (``benchmark/testdata/
passlog_small.json``: a warm pass, passes 1, 2 (traced), 3, and pass 4 with a
2.0 s stall planted in level 7, blocked in ``upload``).  Every value below is
computed by hand from that file's numbers.

No chip here: nothing in this file is a measurement, only the arithmetic that
turns a ledger into numbers.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import levelred, passes  # noqa: E402
from benchmark.harness import manifest as mf  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "testdata", "passlog_small.json")
METRICS = ("level_host_ms", "level_cpu_share_pct", "host_exposed_s",
           "upload_untraced_ms", "stall_s")
SIX = ["elect5.passes", "flagship3.passes", "full5.passes", "repl3.passes",
       "flagship3_m1.verdict", "elect5.shard4"]

# by hand, from the file.  Ramp levels 1..4 of the three untraced passes:
# wall 40 / 42 / 44 / 46 ms less 10 ms of wait each = 30 / 32 / 34 / 36 ms of
# host, three times over: the median of the twelve is 33.  Their cpu_s is
# 16.5 ms each: 12 x 16.5 = 198 ms over 3 x 132 = 396 ms of host = 50 %.
# A pass: head 10 ms + levels (172 + 5 x 100 ms) + tail 5 ms = 0.687 s, of
# which 4 x 10 + 5 x 60 = 340 ms are wait: 0.347 s exposed; the stalled pass
# 2.347 s; the median of (0.347, 0.347, 2.347) is 0.347.  Levels 6..8 hold
# 1 + 1 + 2 uploads of 20 ms each, and the stall adds 2.0 s to one of them:
# (3 x 80 ms + 2.0 s) / 12 uploads = 186.67 ms.  Level 7 reads 0.1, 0.1 and
# 2.1 s: low median 0.1, excess 2.0 > max(0.25, 0.1).
BY_HAND = {"level_host_ms": 33.0, "level_cpu_share_pct": 50.0,
           "host_exposed_s": 0.347, "upload_untraced_ms": 2240.0 / 12,
           "stall_s": 2.0}


@pytest.fixture()
def recorded():
    with open(DATA, encoding="utf-8") as f:
        return json.load(f)


def _evidence(recorded, monkeypatch, snapshot=None):
    """What ``run.execute`` hands a reader, as far as these look, with the
    program's ``passlog.snapshot`` standing on the recorded one."""
    from raft_tla_tpu.obs import passlog
    monkeypatch.setattr(passlog, "snapshot",
                        lambda: snapshot or recorded["snapshot"])
    made = [passes.Pass(index=k + 1, t_call=p["t_call"],
                        t_return=p["t_return"], traced=p["traced"],
                        problem=p["problem"])
            for k, p in enumerate(recorded["passes"])]
    return {"passes": made, "span_levels": recorded["span_levels"]}


@pytest.mark.parametrize("name", METRICS)
def test_reader_gives_the_value_computed_by_hand(recorded, monkeypatch,
                                                 capsys, name):
    ev = _evidence(recorded, monkeypatch)
    assert mf.metric_reader(name)(ev) == pytest.approx(BY_HAND[name])
    red = ev["levelred"]
    assert (red["passes"], red["ramp_levels"], red["dropped"]) == (3, 12, 0)
    # the untraced level table of the run's log: medians by seam, in ms
    assert red["ramp_by_seam_ms"] == pytest.approx(
        {"wall": 43.0, "upload": 20.0, "expand": 2.0, "wait": 10.0,
         "d2h": 1.0, "dedup": 3.0, "close": 4.0, "cpu": 16.5})
    assert red["span_by_seam_ms"]["wall"] == pytest.approx(100.0)
    assert red["span_by_seam_ms"]["wait"] == pytest.approx(60.0)
    # once a run: a second reader prints nothing more
    first = capsys.readouterr().out
    assert first.count("pass ledger, untraced passes: ") == 1
    mf.metric_reader("stall_s")(ev)
    assert capsys.readouterr().out == ""


def test_the_stall_gets_its_line_with_every_seam(recorded, monkeypatch,
                                                 capsys):
    ev = _evidence(recorded, monkeypatch)
    assert mf.metric_reader("stall_s")(ev) == pytest.approx(2.0)
    out = capsys.readouterr().out.splitlines()
    stalls = [line for line in out if line.startswith("stall ")]
    assert len(stalls) == 1 and stalls == ev["levelred"]["stalls"]
    line = stalls[0]
    # pass 4 of the window (as run.py numbers its ``pass N`` lines)
    assert line.startswith("stall pass 4 level 7: wall 2.100000s against "
                           "the run's median 0.100000s (+2.000000s): ")
    assert "upload_s 2.020000" in line and "cpu_s 0.031000" in line
    for key in ("expand_s", "wait_s", "d2h_s", "dedup_s", "close_s", "gc_s",
                "uploads", "majflt", "nivcsw 1"):
        assert key in line
    # ... and the traced pass's extra head is found where it was planted
    cost = json.loads(next(
        line for line in out if line.startswith("tracing's own cost")
    ).split(": ", 1)[1])
    assert cost["head_s"] == pytest.approx(0.05)
    assert cost["pre_s"] == pytest.approx(0.001)
    assert cost["ramp_wall_s"] == pytest.approx(0.0)
    assert cost["level_excess_max_s"] == pytest.approx(0.0)


def test_stalls_of_head_tail_and_of_a_pair_of_passes(recorded):
    recs = recorded["snapshot"]["records"]
    slow_head = {**recs[1], "head_s": 1.010}
    found = levelred.stalls([(1, slow_head), (2, recs[3])])
    # two passes: the low median is the faster one, so a pair can tell
    assert [(s["pass"], s["level"]) for s in found] == [(1, "head")]
    assert found[0]["excess_s"] == pytest.approx(1.0)
    assert levelred.stall_line(found[0]) == (
        "stall pass 1 head: wall 1.010000s against the run's median "
        "0.010000s (+1.000000s)")
    # under the floor of 0.25 s nothing is a stall, whatever the ratio
    quick = {**recs[1], "tail_s": 0.205}
    assert levelred.stalls([(1, quick), (2, recs[3])]) == []
    assert levelred.reduce([(1, recs[1]), (2, recs[3])], 5, 8)["stall_s"] \
        == 0.0


def test_the_programs_own_rule_names_the_same_level(recorded):
    """The ledger holds each pass against the ones before it as it closes
    (``record["stalls"]``, one line on stderr: the only witness in a run
    the benchmark does not trace); on this shape it finds what the reader
    finds over the whole run, and the file holds what it found."""
    from raft_tla_tpu.obs import passlog
    recs = recorded["snapshot"]["records"]
    for k, rec in enumerate(recs):
        assert passlog._stalls(rec, recs[:k]) == rec["stalls"]
    assert [len(r["stalls"]) for r in recs] == [0, 0, 0, 0, 1]
    st = recs[4]["stalls"][0]
    assert (st["level"], st["wall_s"], st["median_s"], st["passes"]) == \
        (7, 2.1, 0.1, 3)
    assert st["upload_s"] == pytest.approx(2.02)
    assert passlog.stall_line(recs[4], st).startswith(
        "raft-tla pass ledger: stall in the ddd pass at t0=130.001, level "
        "7: wall 2.100s against a median of 0.100s over the last 3 passes: "
        "upload_s 2.020 ")


def test_failed_and_traced_passes_are_left_out(recorded, monkeypatch):
    ev = _evidence(recorded, monkeypatch)
    ev["passes"][3].problem = "level table differs from the pins"
    assert mf.metric_reader("stall_s")(ev) == 0.0
    assert ev["levelred"]["passes"] == 2
    assert mf.metric_reader("upload_untraced_ms")(ev) == pytest.approx(20.0)
    assert mf.metric_reader("host_exposed_s")(ev) == pytest.approx(0.347)


def test_a_resumed_pass_has_no_ramp_and_still_reads_the_rest(recorded):
    recs = recorded["snapshot"]["records"]
    red = levelred.reduce([(1, recs[1])], 0, 8)
    assert red["level_host_ms"] is None and red["ramp_by_seam_ms"] is None
    assert red["level_cpu_share_pct"] is None
    assert red["upload_untraced_ms"] == pytest.approx(20.0)
    assert red["host_exposed_s"] == pytest.approx(0.347)


@pytest.mark.parametrize("name", METRICS)
def test_without_records_every_reader_gives_none(recorded, monkeypatch,
                                                 capsys, name):
    ev = _evidence(recorded, monkeypatch,
                   snapshot={"records": [], "dropped": 0})
    assert mf.metric_reader(name)(ev) is None
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", METRICS)
def test_a_ring_that_dropped_a_timed_pass_gives_none_and_says_so(
        recorded, monkeypatch, capsys, name):
    """The ring keeps 64 passes and the checks after the window make more:
    once a sound untraced pass of the run has no record left, no reader
    reduces over the rest."""
    snap = {"records": recorded["snapshot"]["records"][2:], "dropped": 2}
    ev = _evidence(recorded, monkeypatch, snapshot=snap)
    assert mf.metric_reader(name)(ev) is None
    out = capsys.readouterr().out
    assert out == ("PASS LEDGER: 1 sound untraced passes of this run have "
                   "no record (the ledger dropped 2): no reading from it\n")
    assert mf.metric_reader("stall_s")(ev) is None        # said once
    assert capsys.readouterr().out == ""


def test_a_program_without_the_ledger_gives_none(recorded, monkeypatch):
    """The parent commit has no ``obs/passlog``: the import fails, the
    reduction is ``None`` and nothing raises."""
    import raft_tla_tpu.obs
    ev = _evidence(recorded, monkeypatch)
    monkeypatch.delattr(raft_tla_tpu.obs, "passlog")
    monkeypatch.setitem(sys.modules, "raft_tla_tpu.obs.passlog", None)
    for name in METRICS:
        assert mf.metric_reader(name)(ev) is None
    assert ev["levelred"] is None


def test_manifest_gains_the_five_readers_at_the_end_and_nothing_else():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    # the seven cells PR 38 found; it adds none
    cells = [w["name"] for w in manifest["workloads"]][:7]
    names = [m["name"] for m in manifest["per_layer"]]
    # the last five of the list as PR 38 leaves it, after PR 35's 51 (later
    # PRs append their own after these)
    assert [names.index(n) for n in METRICS] == list(range(51, 56))
    last = manifest["per_layer"][51:56]
    for m in last:
        assert (m["source"], m["moves"]) == ("program_counter",
                                             "orbits_per_s")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m["workloads"][:7]) <= set(cells)
    by = {m["name"]: m for m in last}
    assert by["level_host_ms"]["workloads"][:6] == SIX
    assert by["level_cpu_share_pct"]["workloads"][:6] == SIX
    for name in ("host_exposed_s", "upload_untraced_ms", "stall_s"):
        assert by[name]["workloads"][:7] == cells
    assert by["upload_untraced_ms"]["layer"] == "store read and h2d upload"
    assert {by[n]["layer"] for n in METRICS if n != "upload_untraced_ms"} \
        == {"level loop"}
    assert [by[n]["unit"] for n in METRICS] == ["ms", "%", "s", "ms", "s"]


# --------------------------------- PR 45: the two readers of the mesh harvest

MESH = "elect5.shard4"


def test_ramp_d2h_ms_is_the_ramps_d2h_seam(recorded, monkeypatch):
    """``levelred`` has reduced the ramp's seams since PR 38; the reader
    hands out the ``d2h`` one.  By hand: every ramp level of the file spends
    1 ms there."""
    read = mf.metric_reader("ramp_d2h_ms")
    assert read(_evidence(recorded, monkeypatch)) == pytest.approx(1.0)
    # a resumed pass has no ramp; a program without records has no reading
    assert read({"levelred": {"ramp_by_seam_ms": None}}) is None
    assert read(_evidence(recorded, monkeypatch,
                          snapshot={"records": [], "dropped": 0})) is None


def _span(name, thread, t0, **args):
    return json.dumps({"event": "span", "name": name, "thread": thread,
                       "t0": t0, "dur": 0.005, "span_id": int(t0 * 1e3),
                       "parent_id": None, "args": args})


def test_harvest_head_pct_counts_the_clocked_spans_d2h_by_path(tmp_path):
    log = tmp_path / "run.events"
    p = passes.Pass(index=2, t_call=0.0, t_a=10.0, t_b=20.0, traced=True,
                    events=str(log), t_trace_end=12.0)
    ev = {"passes": [p]}
    read = mf.metric_reader("harvest_head_pct")
    log.write_text("\n".join([
        _span("d2h", "MainThread", 9.0, rows=19, path="whole"),  # before A
        _span("d2h", "MainThread", 10.5, rows=900, path="head"),
        _span("d2h", "MainThread", 12.0, rows=70000, path="whole"),
        _span("d2h", "MainThread", 15.0, rows=4000, path="head"),
        _span("d2h", "MainThread", 19.0, rows=100, path="head"),
        _span("d2h", "raft-tla-flush", 16.0, rows=1, path="whole"),
        _span("d2h", "MainThread", 20.5, rows=5, path="whole"),  # past B
    ]) + "\n")
    assert read(ev) == pytest.approx(75.0)
    # the parent's spans carry no path: nothing to read, and nothing raised
    log.write_text(_span("d2h", "MainThread", 10.5, rows=900) + "\n")
    assert read(ev) is None
    log.write_text(_span("level", "MainThread", 10.0, level=13) + "\n")
    assert read(ev) is None                     # no harvest inside the span
    log.write_text(json.dumps({"event": "run_start"}) + "\n")
    assert read(ev) is None                     # a program without spans
    p.t_b = None
    assert read(ev) is None                     # a pass that met no B
    assert read({"passes": []}) is None         # an untraced run


def test_manifest_gains_the_two_harvest_readers_for_the_mesh_cell_alone():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    # the 63 entries PR 44 left, then these two (later PRs append after)
    assert manifest["per_layer"][63:65] == [
        {"name": "ramp_d2h_ms", "unit": "ms", "better": "lower",
         "source": "program_counter",
         "layer": "d2h export and host key set", "moves": "orbits_per_s",
         "workloads": [MESH]},
        {"name": "harvest_head_pct", "unit": "%", "better": "higher",
         "source": "program_span",
         "layer": "d2h export and host key set", "moves": "orbits_per_s",
         "workloads": [MESH]}]
    names = mf.metric_names(manifest, MESH, "per_layer")
    assert {"ramp_d2h_ms", "harvest_head_pct", "level_host_ms",
            "host_exposed_s"} <= set(names)
    for name in ("ramp_d2h_ms", "harvest_head_pct"):
        assert os.path.isfile(os.path.join(mf.BENCH, "metrics", name + ".py"))


# ------------------------- PR 48: the two readers of the mesh step's stream

def _mesh_capture(stream_op=True, exchange_op=True):
    """A capture as ``stagered.load_xplane`` gives it: two chips, three
    lockstep steps in one segment module, the second chip half as fast."""
    seg = "jit(segment)/shard_map/while/body/"
    ops = [["while.1", 0, 3000, "jit(segment)/shard_map/while"],
           ["fusion.2", 10, 200, seg + "filter_insert/sort"]]
    if exchange_op:
        ops.append(["all-to-all.3", 300, 400, seg + "exchange/all_to_all"])
    if stream_op:
        # the slab loop's ops nest under the scope's own ``while``
        ops += [["while.4", 1000, 900, seg + "stream/while"],
                ["fusion.5", 1010, 300, seg + "stream/while/body/gather"],
                ["fusion.6", 1400, 450,
                 seg + "stream/while/body/dynamic_update_slice"]]
    ops.append(["fusion.7", 2000, 50, seg + "streams/not_the_scope"])
    plane = {"XLA Ops": ops, "XLA Modules": [["jit_segment(1)", 0, 3000]]}
    slow = {"XLA Ops": [[n, 2 * s, 2 * d, path] for n, s, d, path in ops],
            "XLA Modules": [["jit_segment(1)", 0, 6000]]}
    return {"devices": {"/device:TPU:0": plane, "/device:TPU:1": slow}}


def _stream_evidence(trace, steps=3):
    from benchmark.harness import meshred, stagered
    return {"meshred": meshred.scope_times(trace, 0, 9000),
            "stagered": {"stages": stagered.stage_times(trace, 0, 9000)},
            "work": {"steps": steps}, "passes": []}


def test_stage_mesh_stream_ms_is_the_stream_scope_of_a_mesh_capture():
    """By hand: under ``stream`` chip 0 spends the loop's own 150 ns (900
    less its body's 750) + 300 + 450 = 900 ns, chip 1 twice that; the mean
    is 1,350 ns over three steps."""
    read = mf.metric_reader("stage_mesh_stream_ms")
    ev = _stream_evidence(_mesh_capture())
    assert ev["stagered"]["stages"]["stage_ns"]["stream"] == 1350
    assert read(ev) == pytest.approx(1350 / 1e6 / 3)
    # the one-chip cells' reader gives the same stage from the same capture
    assert mf.metric_reader("stage_stream_ms")(ev) == read(ev)
    # a one-chip capture names no exchange: this reader is the mesh's alone
    one = _stream_evidence(_mesh_capture(exchange_op=False))
    assert mf.metric_reader("stage_stream_ms")(one) is not None
    assert read(one) is None
    # a mesh capture that names no stream op: nothing to read, never 0.0
    assert read(_stream_evidence(_mesh_capture(stream_op=False))) is None
    # an untraced run, and a traced one whose capture no plane ran in
    assert read({"passes": []}) is None
    assert read({"meshred": None, "passes": []}) is None


def _levels_log(tmp_path, levels):
    log = tmp_path / "run.events"
    log.write_text("\n".join(
        _span("level", "MainThread", 10.0 + k, level=k + 1, **args)
        for k, args in enumerate(levels)) + "\n")
    p = passes.Pass(index=2, t_call=0.0, t_a=10.0, t_b=20.0, traced=True,
                    events=str(log), t_trace_end=12.0)
    return {"passes": [p]}


def test_mesh_slabs_per_step_is_the_level_spans_slabs_over_steps(tmp_path):
    """Over the whole traced pass's ``level`` spans: (1 + 6 + 9) slabs over
    (1 + 6 + 6) lockstep steps."""
    read = mf.metric_reader("mesh_slabs_per_step")
    ev = _levels_log(tmp_path, [
        dict(steps=1, stream_slabs=1, stream_peak=2, streamed_rows=2),
        dict(steps=6, stream_slabs=6, stream_peak=9000, streamed_rows=30000),
        dict(steps=6, stream_slabs=9, stream_peak=40000,
             streamed_rows=150000)])
    assert read(ev) == pytest.approx(16 / 13)
    # the parent's mesh spans count steps and no slabs: nothing to read
    assert read(_levels_log(tmp_path, [
        dict(steps=1, streamed_rows=2), dict(steps=6, streamed_rows=30000)
    ])) is None
    assert read(_levels_log(tmp_path, [])) is None      # no level span
    assert read({"passes": []}) is None                 # an untraced run


def test_manifest_gains_the_two_stream_readers_for_the_mesh_cell_alone():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    # the 69 entries PR 47 left, then these two
    assert manifest["per_layer"][69:71] == [
        {"name": "stage_mesh_stream_ms", "unit": "ms/step",
         "better": "lower", "source": "device_trace",
         "layer": "d2h export and host key set", "moves": "orbits_per_s",
         "workloads": [MESH]},
        {"name": "mesh_slabs_per_step", "unit": "slabs/step",
         "better": "lower", "source": "program_span",
         "layer": "d2h export and host key set", "moves": "orbits_per_s",
         "workloads": [MESH]}]
    names = mf.metric_names(manifest, MESH, "per_layer")
    assert {"stage_mesh_stream_ms", "mesh_slabs_per_step",
            "stage_exchange_ms"} <= set(names)
    # the one-chip cells' lists are as they were
    assert MESH not in next(m for m in manifest["per_layer"]
                            if m["name"] == "stage_stream_ms")["workloads"]
    for name in ("stage_mesh_stream_ms", "mesh_slabs_per_step"):
        assert os.path.isfile(os.path.join(mf.BENCH, "metrics", name + ".py"))


# ---------------------- PR 49: the mesh exchange's gather loop, counted by
# the program (``exchange_slabs`` on the mesh engine's ``level`` spans)

def test_exchange_slabs_per_step_is_the_level_spans_trips_over_steps(
        tmp_path):
    """Over the whole traced pass's ``level`` spans: (1 + 6 + 15) trips of
    the exchange's gather loop over (1 + 6 + 6) lockstep steps."""
    read = mf.metric_reader("exchange_slabs_per_step")
    ev = _levels_log(tmp_path, [
        dict(steps=1, exchange_slabs=1, route_peak=2, stream_slabs=1),
        dict(steps=6, exchange_slabs=6, route_peak=11000, stream_slabs=6),
        dict(steps=6, exchange_slabs=15, route_peak=40000, stream_slabs=6)])
    assert read(ev) == pytest.approx(22 / 13)
    # a level of a program that counts no trips adds neither sum
    assert read(_levels_log(tmp_path, [
        dict(steps=4, exchange_slabs=6), dict(steps=9, stream_slabs=9)
    ])) == pytest.approx(6 / 4)
    # the parent's mesh spans and a one-chip program's count steps and
    # slabs of the stream, no trips of an exchange: nothing to read
    assert read(_levels_log(tmp_path, [
        dict(steps=1, stream_slabs=1), dict(steps=6, stream_slabs=6)
    ])) is None
    assert read(_levels_log(tmp_path, [])) is None      # no level span
    assert read({"passes": []}) is None                 # an untraced run


def test_manifest_gains_the_exchange_reader_for_the_mesh_cell_alone():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    # the 71 entries PR 48 left, then this one, beside the stage it counts
    # (later PRs append after it)
    assert manifest["per_layer"][71:72] == [
        {"name": "exchange_slabs_per_step", "unit": "slabs/step",
         "better": "lower", "source": "program_span",
         "layer": "mesh exchange", "moves": "orbits_per_s",
         "workloads": [MESH]}]
    assert next(m for m in manifest["per_layer"]
                if m["name"] == "stage_exchange_ms")["layer"] \
        == "mesh exchange"
    for w in manifest["workloads"]:
        listed = "exchange_slabs_per_step" in mf.metric_names(
            manifest, w["name"], "per_layer")
        assert listed == (w["name"] == MESH)
    assert os.path.isfile(os.path.join(
        mf.BENCH, "metrics", "exchange_slabs_per_step.py"))
