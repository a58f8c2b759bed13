"""The benchmark's second family on the device engine
(``benchmark/families/twophase_ddd.py``), the configuration ``twophase10`` and
the cell ``twophase10.passes``: the manifest's new entries, the family's two
crossings, a CPU rehearsal of the whole run at toy size through the harness
(``run.execute(rehearsal=True)``: passes, pins, the reference's BFS prefix and
sample through the run's own compiled segment, the planted fault), the
``filter_only`` control at that size, and the three new readers on hand-made
evidence.  Nothing here is a measurement.
"""

import json
import os

import pytest

from benchmark import run
from benchmark.families import twophase as ref_fam
from benchmark.families import twophase_ddd as fam
from benchmark.harness import breakers
from benchmark.harness import manifest as mf
from benchmark.harness import passes
from benchmark.reference import twophase as ref

NEW_METRICS = ("stage_plainfp_ms", "flush_span_busy_s", "level_blocks_max")
CELL = "twophase10.passes"


def toy_cell(n: int = 5) -> dict:
    """Five resource managers (8,832 states, 17 levels), a block smaller
    than the levels of the clocked span and a filter smaller than the
    space: what the cell is for, at toy size."""
    rms = ", ".join(f"r{k + 1}" for k in range(n))
    cum = ref.bfs_levels(n)[0]
    cfg = {"name": f"toy_twophase{n}", "family": "twophase_ddd",
           "spec": "twophase", "bounds": {"n_rms": n}, "symmetry": [],
           "invariants": ["TPTypeOK", "TCConsistent"], "chunk": 32,
           "cfg_text": (f"CONSTANT RM = {{{rms}}}\n"
                        "INVARIANTS TPTypeOK TCConsistent\n"
                        "SPECIFICATION TPSpec\n"),
           "engine_caps": {"ddd": {"block": 256, "table": 1024,
                                   "seg_rows": 2048, "levels": 64,
                                   "retention": "full"}},
           "sample_min_level_states": 64, "level_pins": cum}
    traffic = {"start": "init", "end": "pin", "start_level": 5,
               "end_level": 9, "min_passes": 3, "count_at_start": cum[5],
               "count_at_end": cum[9], "why": "rehearsal only"}
    return {"name": "toy.twophase", "config": cfg["name"],
            "traffic": "toy_twophase_traffic", "chips": 1,
            "config_data": cfg, "traffic_data": traffic}


# ------------------------------------------------------------- the manifest

def test_manifest_gains_the_configuration_the_cell_and_three_readers():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    # found by name: a later PR's entries go after these
    (config,) = [c for c in manifest["configs"] if c["name"] == "twophase10"]
    assert config["reduced"] == ["depth"]
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {
        "name": CELL, "config": "twophase10", "traffic": "passes_l7_l12",
        "chips": 1, "why": cell["why"]}
    readers = [m for m in manifest["per_layer"] if m["name"] in NEW_METRICS]
    assert tuple(m["name"] for m in readers) == NEW_METRICS
    for m in readers:
        assert m["workloads"] == [CELL] and m["moves"] == "orbits_per_s"
    # the cell reports the readers that carry no list, the three new ones
    # and, since PR 44, flush_offload_pct
    names = mf.metric_names(manifest, CELL, "per_layer")
    assert len(names) == 18 and set(NEW_METRICS) <= set(names)
    # flush_backlog_keys (its reader is PR 30's) stays unlisted until a
    # benchmark PR lists it: since PR 44 this cell hands the flush worker
    # batches, so its reader has something to read here (ROADMAP queue 2)
    assert "flush_backlog_keys" not in {m["name"]
                                        for m in manifest["per_layer"]}
    assert {"scan_words_per_s", "step_hbm_share"} <= set(names)
    assert mf.metric_names(manifest, CELL, "end_to_end") \
        == ["orbits_per_s", "setup_s"]


def test_the_configuration_is_the_sources_deployment_at_ten_rms():
    cell = mf.cell(mf.load(), CELL)
    cfg, t = cell["config_data"], cell["traffic_data"]
    assert mf.family(cfg) is fam and mf.engine_of(cfg, 1) == ("ddd", 1)
    assert mf.end_of(t, cfg, cell["traffic"]) == "pin"
    assert cfg["bounds"] == {"n_rms": 10} and cfg["chunk"] == 4096
    assert cfg["invariants"] == ["TPTypeOK", "TCConsistent"]
    assert cfg["symmetry"] == [] and "init" not in cfg
    assert set(cfg["reduced"]) == {"depth"}
    assert set(cfg["guarantees"]) == {"search", "dedup", "invariants",
                                      "symmetry", "stop"}
    assert cfg["engine_caps"]["ddd"] == {
        "block": 1 << 20, "table": 1 << 22, "seg_rows": 1 << 19,
        "levels": 256, "retention": "full"}
    pins = cfg["level_pins"]
    assert len(pins) == 13 and pins[:5] == ref.bfs_levels(10, (), 4096)[0]
    assert (pins[t["start_level"]], pins[t["end_level"]]) \
        == (t["count_at_start"], t["count_at_end"]) == (256_660, 8_430_484)
    # levels 10 and 11 are expanded in 2 and 3 blocks of 2^20 rows
    rows = [b - a for a, b in zip([0] + pins, pins)]
    assert [-(-r // (1 << 20)) for r in rows[9:12]] == [1, 2, 3]
    config = fam.check_config(cfg)
    assert (config.spec, config.bounds.n_servers, config.chunk,
            config.invariants, config.symmetry) \
        == ("twophase", 10, 4096, ("TPTypeOK", "TCConsistent"), ())


def test_cfg_text_is_held_to_the_fields_beside_it():
    cfg = toy_cell(3)["config_data"]
    for edit, said in (
            ({"bounds": {"n_rms": 4}}, "cfg_text says (3,"),
            ({"invariants": ["TCConsistent"]}, "the fields say (3, ['TCCons"),
            ({"cfg_text": cfg["cfg_text"].replace("TPSpec", "Spec")},
             "SPECIFICATION 'Spec'")):
        with pytest.raises(ValueError, match=said.replace("(", r"\(")
                           .replace("[", r"\[")):
            fam.check_config(dict(cfg, **edit))
    with pytest.raises(ValueError, match="no SYMMETRY"):
        fam.check_config(dict(cfg, symmetry=["Server"]))
    with pytest.raises(ValueError, match="states an Init"):
        fam.check_config(dict(cfg, init={}))


def test_a_program_without_the_device_engine_is_refused_by_name(monkeypatch):
    # what the parent of the PR that brought the engine answers: at once,
    # out of check_config, before any engine is built
    from raft_tla_tpu.frontend.registry import TwoPhaseModel
    monkeypatch.setattr(TwoPhaseModel, "engines", ("host", "simulate"))
    with pytest.raises(ref_fam.NoDeviceEngine,
                       match="runs spec 'twophase' on host, simulate only"):
        fam.check_config(toy_cell(3)["config_data"])


def test_a_state_crosses_to_the_program_and_back():
    n = 4
    cum, level, _viol, _trans = ref.bfs_levels(n, (), 100)
    assert len(level) >= 100
    for s in level:
        p = fam.to_program(s)
        assert fam.from_program(p) == s
        assert len(p.tmPrepared) == len(p.msgPrepared) == n
    s = ref.State((ref.PREPARED, ref.ABORTED, ref.WORKING, ref.COMMITTED),
                  ref.TM_COMMITTED, 0b0101, 0b01_0011)
    p = fam.to_program(s)
    assert (p.rmState, p.tmState, p.tmPrepared, p.msgPrepared, p.msgCommit,
            p.msgAbort) == ((1, 3, 0, 2), 1, (1, 0, 1, 0), (1, 1, 0, 0), 1, 0)
    # the two families answer to the same names
    import types
    public = {k for k, v in vars(ref_fam).items() if not k.startswith("_")
              and not isinstance(v, types.ModuleType)}
    assert len(public) >= 16 and public <= set(vars(fam))


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
    # (a) (b) the passes; (c) the reference's BFS prefix and the sample
    # through the run's own segment; (d) the planted fault
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

def _span(name, thread, t0, dur, sid, **args):
    return json.dumps({"event": "span", "name": name, "thread": thread,
                       "t0": t0, "dur": dur, "span_id": sid,
                       "parent_id": None, "args": args})


@pytest.fixture()
def traced_evidence(tmp_path):
    log = tmp_path / "run.events"
    log.write_text("\n".join([
        _span("level", "MainThread", 9.0, 1.0, 1, level=7, blocks=1),
        _span("level", "MainThread", 10.0, 1.0, 2, level=8, blocks=1),
        _span("level", "MainThread", 11.0, 2.0, 3, level=11, blocks=2),
        _span("level", "MainThread", 13.0, 4.0, 4, level=12, blocks=3),
        _span("level", "MainThread", 17.0, 4.0, 5, level=13, blocks=4),
        _span("dedup", "MainThread", 12.5, 0.25, 6),
        _span("dedup", "raft-tla-flush", 9.5, 1.0, 7),     # half before A
        _span("dedup", "raft-tla-flush", 14.0, 2.0, 8),
        _span("dedup", "raft-tla-flush", 16.5, 1.0, 9),    # half after B
        _span("dedup_submit", "MainThread", 13.5, 0.001, 10, backlog=0),
    ]) + "\n")
    p = passes.Pass(index=1, t_call=0.0, t_a=10.0, t_b=17.0, traced=True,
                    events=str(log), t_trace_end=11.0)
    return {"passes": [p], "span_levels": [7, 12], "work": {"steps": 40}}


def test_level_blocks_max_reads_the_widest_level_of_the_clocked_span(
        traced_evidence):
    assert mf.metric_reader("level_blocks_max")(traced_evidence) == 3
    traced_evidence["span_levels"] = [7, 8]
    assert mf.metric_reader("level_blocks_max")(traced_evidence) == 1
    traced_evidence["passes"][0].traced = False
    assert mf.metric_reader("level_blocks_max")(traced_evidence) is None


def test_flush_span_busy_s_is_the_workers_wall_over_the_whole_span(
        traced_evidence, tmp_path):
    read = mf.metric_reader("flush_span_busy_s")
    assert read(traced_evidence) == pytest.approx(0.5 + 2.0 + 0.5)
    # the traced level alone is what flush_busy_s reads: here, half a second
    quiet = tmp_path / "quiet.events"
    quiet.write_text(_span("dedup", "MainThread", 12.5, 0.25, 6) + "\n")
    traced_evidence["passes"][0].events = str(quiet)
    assert read(traced_evidence) == 0.0         # the worker had no batch
    quiet.write_text(json.dumps({"event": "run_start"}) + "\n")
    assert read(traced_evidence) is None        # a program without spans


def test_flush_offload_pct_is_the_workers_share_of_the_spans_merge(
        traced_evidence, tmp_path):
    """PR 44: the worker's ``dedup`` wall over that plus the main thread's,
    both clipped to the clocked span A->B."""
    (entry,) = [m for m in mf.load()["per_layer"]
                if m["name"] == "flush_offload_pct"]
    assert entry == {
        "name": "flush_offload_pct", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "d2h export and host key set",
        "moves": "orbits_per_s",
        "workloads": [CELL, "paxos3b4.passes"]}
    read = mf.metric_reader("flush_offload_pct")
    assert read(traced_evidence) == pytest.approx(100 * 3.0 / 3.25)
    quiet = tmp_path / "quiet.events"
    quiet.write_text("\n".join([
        _span("dedup", "MainThread", 9.9, 0.2, 6),         # half before A
        _span("dedup", "MainThread", 12.5, 0.25, 7),
        _span("dedup", "raft-tla-flush", 18.0, 1.0, 8),    # past B
    ]) + "\n")
    traced_evidence["passes"][0].events = str(quiet)
    assert read(traced_evidence) == 0.0         # every merge ran inline
    quiet.write_text(_span("level", "MainThread", 10.0, 1.0, 2, level=8)
                     + "\n")
    assert read(traced_evidence) is None        # no dedup inside the span
    quiet.write_text(json.dumps({"event": "run_start"}) + "\n")
    assert read(traced_evidence) is None        # a program without spans
    traced_evidence["passes"][0].t_b = None
    assert read(traced_evidence) is None        # a pass that met no B


def test_stage_plainfp_ms_is_the_scopes_self_time_a_step(traced_evidence):
    read = mf.metric_reader("stage_plainfp_ms")
    traced_evidence["stagered"] = {"stages": {
        "scoped": True, "stage_ns": {"plain_fp": 8.0e6, "expand": 4.0e7}}}
    assert read(traced_evidence) == pytest.approx(0.2)
    # a program whose key is the orbit scan's has nothing under the scope
    traced_evidence["stagered"]["stages"]["stage_ns"]["plain_fp"] = 0
    assert read(traced_evidence) is None
    traced_evidence["stagered"] = None          # an untraced run
    assert read(traced_evidence) is None


def test_the_new_readers_are_files_beside_the_old_ones():
    for name in NEW_METRICS:
        assert os.path.isfile(os.path.join(mf.BENCH, "metrics",
                                           name + ".py"))
