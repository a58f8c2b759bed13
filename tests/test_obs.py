"""Cross-engine run-event conformance + obs unit tests.

The conformance tests are the contract the obs/ package exists for:
every engine family emits the SAME versioned event schema, so one
monitor (and one campaign-projection client) reads all of them.  Each
engine runs the tiny election universe, the resulting log is validated
line by line against the strict schema, and the final ``run_end`` count
must agree with the ``EngineResult`` — and across engines.
"""

import json
import subprocess
import sys
import time

import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.obs import monitor
from raft_tla_tpu.obs.events import (
    SCHEMA_VERSION, EventLog, ProgressTracker, append_event, validate_event)
from raft_tla_tpu.obs.phases import PhaseTimers

CFG = CheckConfig(
    bounds=Bounds(n_servers=2, n_values=1, max_term=2, max_log=0,
                  max_msgs=2),
    spec="election", invariants=("NoTwoLeaders",), chunk=32)
N_TOY = 3014            # distinct states of the toy universe (oracle)


def _read_log(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh]


def _assert_conformant(evs, engine):
    """The schema contract: valid lines, run_start first, run_end last,
    segments carrying the shared ProgressRecord fields."""
    errs = [(e["event"], err) for e in evs for err in validate_event(e)]
    assert not errs, errs[:5]
    assert evs[0]["event"] == "run_start"
    assert evs[0]["engine"] == engine
    assert evs[0]["universe"] == {"servers": 2, "values": 1}
    assert evs[-1]["event"] == "run_end"
    assert evs[-1]["outcome"] == "ok" and evs[-1]["complete"]
    segs = [e for e in evs if e["event"] == "segment"]
    assert segs, f"{engine}: no segment events"
    for s in segs:
        assert s["v"] == SCHEMA_VERSION
        assert s["since_resume"] is True
        # per-invariant evaluation counts (TLC -coverage 1 analogue):
        # every generated state was checked against every invariant
        assert s["inv_evals"] == {"NoTwoLeaders": s["n_transitions"]}
    # level_end events appear whenever a level transition is observed
    # between segments (always for the ddd family, pacing-dependent for
    # table engines whose budget can cross several levels per segment)
    ends = [e["level"] for e in evs if e["event"] == "level_end"]
    assert ends == sorted(ends)
    return evs[-1]["n_states"]


def _run_engine(name, events, on_progress=None):
    if name == "device":
        from raft_tla_tpu.device_engine import Capacities, DeviceEngine
        eng = DeviceEngine(CFG, Capacities(n_states=1 << 15, levels=64))
    elif name == "ddd":
        from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
        eng = DDDEngine(CFG, DDDCapacities(block=256, table=1 << 14,
                                           flush=1 << 10, levels=64))
    elif name == "shard":
        from raft_tla_tpu.parallel import (ShardCapacities, ShardEngine,
                                           make_mesh)
        eng = ShardEngine(CFG, make_mesh(8),
                          ShardCapacities(n_states=1 << 12, levels=64))
    else:
        from raft_tla_tpu.parallel.ddd_shard_engine import (
            DDDShardCapacities, DDDShardEngine)
        eng = DDDShardEngine(CFG, caps=DDDShardCapacities(
            block=256, table=1 << 14, flush=1 << 10, levels=64))
    return eng.check(events=events, on_progress=on_progress)


@pytest.mark.smoke
@pytest.mark.parametrize("engine", ["device", "ddd"])
def test_event_conformance_single_device(engine, tmp_path):
    path = str(tmp_path / f"{engine}.events")
    lines = []
    res = _run_engine(engine, path, on_progress=lines.append)
    evs = _read_log(path)
    n = _assert_conformant(evs, engine)
    assert n == res.n_states == N_TOY
    if engine == "ddd":  # boundary-exact level accounting
        assert [e["level"] for e in evs if e["event"] == "level_end"]
    # on_progress receives the same records the log's segments carry
    segs = [e for e in evs if e["event"] == "segment"]
    assert len(lines) == len(segs)
    for cb, seg in zip(lines, segs):
        assert cb["n_states"] == seg["n_states"]
        assert cb["inc_states_per_sec"] == seg["inc_states_per_sec"]


@pytest.mark.smoke
@pytest.mark.slow
@pytest.mark.parametrize("engine", ["shard", "ddd-shard"])
def test_event_conformance_sharded(engine, tmp_path):
    path = str(tmp_path / "shard.events")
    res = _run_engine(engine, path)
    evs = _read_log(path)
    n = _assert_conformant(evs, engine)
    assert n == res.n_states == N_TOY
    assert evs[0]["n_devices"] >= 1


# --------------------------------------------------------------------------
# schema unit tests


def test_validate_rejects_unknowns_and_type_drift():
    ok = {"v": 1, "event": "level_end", "ts": 0.0, "level": 3,
          "n_states": 10}
    assert validate_event(ok) == []
    assert validate_event({**ok, "event": "levelend"})      # unknown event
    assert validate_event({**ok, "extra": 1})               # unknown field
    assert validate_event({**ok, "level": "3"})             # type drift
    assert validate_event({**ok, "level": True})            # bool is not int
    assert validate_event({**ok, "v": 2}) == []             # v2 superset
    assert validate_event({**ok, "v": 3}) == []             # v3 superset
    assert validate_event({**ok, "v": 4}) == []             # v4 superset
    assert validate_event({**ok, "v": 5}) == []             # v5 superset
    assert validate_event({**ok, "v": 6}) == []             # v6 superset
    assert validate_event({**ok, "v": 7}) == []             # v7 superset
    assert validate_event({**ok, "v": 8}) == []             # v8 superset
    assert validate_event({**ok, "v": 9}) == []             # v9 superset
    assert validate_event({**ok, "v": 10}) == []            # v10 superset
    assert validate_event({**ok, "v": 11}) == []            # v11 superset
    assert validate_event({**ok, "v": 12}) == []            # v12 superset
    assert validate_event({**ok, "v": 13}) == []            # v13 superset
    assert validate_event({**ok, "v": 14}) == []            # v14 superset
    assert validate_event({**ok, "v": 15}) == []            # v15 superset
    assert validate_event({**ok, "v": 16})                  # future version
    assert validate_event({"v": 1, "event": "level_end", "ts": 0.0,
                           "level": 3})                     # missing field


def test_validate_v2_supervisor_events():
    ok = {"v": 2, "event": "preempt", "ts": 0.0, "reason": "stale"}
    assert validate_event(ok) == []
    assert validate_event({**ok, "stale_s": 12.5, "pid": 7}) == []
    assert validate_event({**ok, "v": 1})      # v2-only type on a v1 line
    assert validate_event({"v": 2, "event": "reshard", "ts": 0.0,
                           "ndev_src": 8, "ndev_dst": 2,
                           "n_states": 3014}) == []
    assert validate_event({"v": 2, "event": "reshard", "ts": 0.0,
                           "ndev_src": 8})     # missing ndev_dst
    assert validate_event({"v": 2, "event": "resume_attempt", "ts": 0.0,
                           "attempt": 1, "backoff_s": 0.5,
                           "quarantined": "x.ckpt"}) == []
    assert validate_event({"v": 2, "event": "resume_attempt", "ts": 0.0,
                           "attempt": 1, "surprise": 1})    # unknown field


def test_validate_v4_serve_segment_fields():
    """The serve scheduler's per-bin attribution (``bin``/``inflight``
    on segment events) exists only from schema v4 — field-gated exactly
    like the v3 fleet fields, so a v3 consumer never sees them."""
    seg = {"v": 4, "event": "segment", "ts": 0.0, "wall_s": 0.1,
           "n_states": 10, "level": 1, "n_transitions": 20,
           "dedup_hit_rate": 0.5, "since_resume": False,
           "states_per_sec": 100.0, "inc_states_per_sec": 100.0,
           "bin": "bin0", "inflight": 2}
    assert validate_event(seg) == []
    errs = validate_event({**seg, "v": 3})   # v4-only fields, v3 line
    assert errs and all("requires schema version >= 4" in e
                        for e in errs)
    assert validate_event({**seg, "bin": 0})         # type drift
    assert validate_event({**seg, "inflight": 1.5})  # type drift


def test_validate_v5_hostdedup_segment_field():
    """The ddd background host-dedup attribution (``flush_backlog`` on
    segment events) exists only from schema v5 — field-gated exactly
    like the v3/v4 fields, so a v4 consumer never sees it."""
    seg = {"v": 5, "event": "segment", "ts": 0.0, "wall_s": 0.1,
           "n_states": 10, "level": 1, "n_transitions": 20,
           "dedup_hit_rate": 0.5, "since_resume": False,
           "states_per_sec": 100.0, "inc_states_per_sec": 100.0,
           "flush_backlog": 1}
    assert validate_event(seg) == []
    errs = validate_event({**seg, "v": 4})   # v5-only field, v4 line
    assert errs and all("requires schema version >= 5" in e
                        for e in errs)
    assert validate_event({**seg, "flush_backlog": 0.5})  # type drift
    assert validate_event({**seg, "flush_backlog": True})  # bool ≠ int


def test_validate_v7_pool_supervision_events():
    """The serve worker-pool lifecycle (worker_spawn / worker_lost /
    job_retry / quarantine) exists only from schema v7 — event-type
    gated exactly like the v2 campaign-supervisor types, so a v6
    consumer never sees them."""
    spawn = {"v": 7, "event": "worker_spawn", "ts": 0.0, "worker": "w0",
             "pid": 1234}
    assert validate_event(spawn) == []
    assert validate_event({**spawn, "jobs": ["a", "b"], "bins": 1,
                           "chunk": 256, "respawn": True,
                           "attempt": 2}) == []
    errs = validate_event({**spawn, "v": 6})  # v7-only type on a v6 line
    assert errs and all("requires schema version >= 7" in e for e in errs)
    assert validate_event({**spawn, "chunk": "256"})      # type drift
    assert validate_event({"v": 7, "event": "worker_spawn", "ts": 0.0,
                           "worker": "w0"})               # missing pid

    lost = {"v": 7, "event": "worker_lost", "ts": 0.0, "worker": "w0",
            "kind": "killed"}
    assert validate_event(lost) == []
    assert validate_event({**lost, "pid": 9, "exit_code": -9,
                           "jobs": ["a"], "detail": "signal 9"}) == []
    assert validate_event({**lost, "v": 1})
    assert validate_event({"v": 7, "event": "worker_lost", "ts": 0.0,
                           "worker": "w0"})               # missing kind

    retry = {"v": 7, "event": "job_retry", "ts": 0.0, "job_id": "a",
             "attempt": 1}
    assert validate_event(retry) == []
    assert validate_event({**retry, "worker": "w1", "backoff_s": 0.7,
                           "reason": "killed"}) == []
    assert validate_event({**retry, "attempt": True})     # bool ≠ int

    quar = {"v": 7, "event": "quarantine", "ts": 0.0, "job_id": "a",
            "reason": "poison-job"}
    assert validate_event(quar) == []
    assert validate_event({**quar, "deaths": 3, "worker": "w2",
                           "detail": "killed its worker 3x"}) == []
    assert validate_event({**quar, "v": 6})
    assert validate_event({**quar, "surprise": 1})        # unknown field


def test_validate_v8_span_events():
    """Trace spans (obs/trace.py) exist only from schema v8 — event-type
    gated like the v7 pool lifecycle; the ``run_start`` clock anchor and
    host context are field-gated like the v3..v6 additions, so a v7
    consumer never sees any of it."""
    span = {"v": 8, "event": "span", "ts": 0.0, "name": "expand",
            "span_id": 3, "t0": 12.25, "dur": 0.125,
            "thread": "MainThread"}
    assert validate_event(span) == []
    assert validate_event({**span, "parent_id": 1,
                           "args": {"rows": 256}}) == []
    errs = validate_event({**span, "v": 7})  # v8-only type on a v7 line
    assert errs and all("requires schema version >= 8" in e for e in errs)
    assert validate_event({**span, "span_id": "3"})       # type drift
    assert validate_event({**span, "span_id": True})      # bool ≠ int
    assert validate_event({**span, "dur": "fast"})        # type drift
    assert validate_event({**span, "surprise": 1})        # unknown field
    assert validate_event({"v": 8, "event": "span", "ts": 0.0,
                           "name": "expand", "span_id": 3,
                           "t0": 1.0, "dur": 0.1})        # missing thread

    start = {"v": 8, "event": "run_start", "ts": 0.0, "engine": "ddd",
             "universe": {}, "spec": "election", "invariants": [],
             "resumed": False,
             "anchor": {"wall": 1.0, "mono": 2.0, "err_s": 1e-6},
             "host": {"nproc": 4}}
    assert validate_event(start) == []
    errs = validate_event({**start, "v": 7})  # v8-only fields, v7 line
    assert errs and all("requires schema version >= 8" in e for e in errs)
    assert validate_event({**start, "anchor": [1.0]})     # type drift


def test_validate_v9_devdedup_segment_fields():
    """The ddd device-dedup attribution (``export_rows`` /
    ``dev_dedup_hits`` on segment events) exists only from schema v9 —
    field-gated exactly like the v5 ``flush_backlog``, so a v8 consumer
    never sees it."""
    seg = {"v": 9, "event": "segment", "ts": 0.0, "wall_s": 0.1,
           "n_states": 10, "level": 1, "n_transitions": 20,
           "dedup_hit_rate": 0.5, "since_resume": False,
           "states_per_sec": 100.0, "inc_states_per_sec": 100.0,
           "export_rows": 8, "dev_dedup_hits": 2}
    assert validate_event(seg) == []
    # the off arm of an A/B emits export_rows without dev_dedup_hits
    off = dict(seg)
    del off["dev_dedup_hits"]
    assert validate_event(off) == []
    errs = validate_event({**seg, "v": 8})   # v9-only fields, v8 line
    assert errs and all("requires schema version >= 9" in e
                        for e in errs)
    assert validate_event({**seg, "export_rows": 0.5})     # type drift
    assert validate_event({**seg, "dev_dedup_hits": True})  # bool ≠ int


def test_validate_v10_metrics_snapshot():
    """The metrics layer's periodic exposition dump (one flat dict of
    series, written by obs/openmetrics.py's snapshot loop) exists only
    from schema v10 — event-type gated exactly like the v7/v8 types, so
    a v9 consumer never sees it."""
    snap = {"v": 10, "event": "metrics_snapshot", "ts": 0.0,
            "metrics": {"raft_tla_queue_depth": 2.0,
                        'raft_tla_latency_seconds{tenant="a",'
                        'quantile="0.99"}': 1.5}}
    assert validate_event(snap) == []
    assert validate_event({**snap, "port": 9108, "root": "/tmp/x"}) == []
    errs = validate_event({**snap, "v": 9})  # v10-only type on a v9 line
    assert errs and all("requires schema version >= 10" in e for e in errs)
    assert validate_event({**snap, "metrics": [1, 2]})    # type drift
    assert validate_event({**snap, "port": "9108"})       # type drift
    assert validate_event({**snap, "surprise": 1})        # unknown field
    assert validate_event({"v": 10, "event": "metrics_snapshot",
                           "ts": 0.0})                    # missing metrics


def test_validate_v11_run_end_compiles():
    """The compile ledger's per-run totals (``run_end.compiles``,
    obs/compiles.py) exist only from schema v11 — field-gated like the
    v9 segment fields, so a v10 consumer never sees them."""
    end = {"v": 11, "event": "run_end", "ts": 0.0, "n_states": 10,
           "n_transitions": 20, "complete": True, "outcome": "ok",
           "compiles": {"trace": [2, 0.8], "lower": [2, 0.2],
                        "backend": [2, 1.5], "cache_misses": 2}}
    assert validate_event(end) == []
    assert validate_event({**end, "compiles": {}}) == []
    errs = validate_event({**end, "v": 10})  # v11-only field, v10 line
    assert errs and all("requires schema version >= 11" in e
                        for e in errs)
    assert validate_event({**end, "compiles": [1, 2]})     # type drift


def test_validate_v12_segment_slab_counters():
    """The ddd segment program's slab-write counters (``stream_peak``,
    ``stream_slabs``) exist only from schema v12, field-gated like the
    v9 segment fields."""
    seg = {"v": 12, "event": "segment", "ts": 0.0, "wall_s": 1.0,
           "n_states": 10, "level": 2, "n_transitions": 20,
           "dedup_hit_rate": 0.5, "states_per_sec": 10.0,
           "inc_states_per_sec": 10.0, "since_resume": True,
           "stream_peak": 7, "stream_slabs": 3}
    assert validate_event(seg) == []
    errs = validate_event({**seg, "v": 11})  # v12-only fields, v11 line
    assert len(errs) == 2 and all("requires schema version >= 12" in e
                                  for e in errs)
    assert validate_event({**seg, "stream_peak": 7.5})     # type drift


def test_validate_v13_segment_probe_tiles():
    """The ddd filter probe's tile counter (``probe_tiles``) exists only
    from schema v13, field-gated like the v12 segment fields; a v12 line as
    PR 25's program wrote it still reads."""
    v12 = {"v": 12, "event": "segment", "ts": 0.0, "wall_s": 1.0,
           "n_states": 10, "level": 2, "n_transitions": 20,
           "dedup_hit_rate": 0.5, "states_per_sec": 10.0,
           "inc_states_per_sec": 10.0, "since_resume": True,
           "stream_peak": 7, "stream_slabs": 3}
    seg = {**v12, "v": 13, "probe_tiles": 5}
    assert validate_event(v12) == [] and validate_event(seg) == []
    errs = validate_event({**seg, "v": 12})  # v13-only field, v12 line
    assert len(errs) == 1 and "requires schema version >= 13" in errs[0]
    assert validate_event({**seg, "probe_tiles": 5.5})     # type drift
    assert json.loads(json.dumps(seg)) == seg              # round trip


def test_validate_v14_run_end_level_log():
    """The pass ledger's record (``run_end.level_log``) exists only from
    schema v14, field-gated like ``run_end.compiles``; a v13 ``run_end``
    as PR 35's program wrote it still reads."""
    v13 = {"v": 13, "event": "run_end", "ts": 0.0, "n_states": 10,
           "n_transitions": 20, "complete": True, "outcome": "ok"}
    end = {**v13, "v": 14, "level_log": {"wall_s": 1.0, "levels": []}}
    assert validate_event(v13) == [] and validate_event(end) == []
    errs = validate_event({**end, "v": 13})  # v14-only field, v13 line
    assert len(errs) == 1 and "requires schema version >= 14" in errs[0]
    assert validate_event({**end, "level_log": []})        # type drift
    assert json.loads(json.dumps(end)) == end              # round trip


def test_validate_v15_run_start_group():
    """The order of the symmetry group a ddd run reduces by
    (``run_start.group``) exists only from schema v15, field-gated like
    ``run_end.level_log``; a v14 ``run_start`` still reads."""
    v14 = {"v": 14, "event": "run_start", "ts": 0.0, "engine": "ddd",
           "universe": {"servers": 5, "values": 2}, "spec": "paxos",
           "invariants": ["Consistency"], "resumed": False}
    start = {**v14, "v": 15, "group": 240}
    assert validate_event(v14) == [] and validate_event(start) == []
    errs = validate_event({**start, "v": 14})  # v15-only field, v14 line
    assert len(errs) == 1 and "requires schema version >= 15" in errs[0]
    assert validate_event({**start, "group": 240.0})       # type drift
    assert json.loads(json.dumps(start)) == start          # round trip


def test_git_sha_is_read_from_the_checkouts_files(tmp_path, monkeypatch):
    """``run_start.git_sha`` comes from ``.git``'s own files (loose ref,
    packed ref, detached HEAD; a worktree's ``.git`` file gives no sha),
    never from a child process: the first logged run stamps it on a clocked
    path."""
    from raft_tla_tpu.obs import events
    sha = "0123456789abcdef0123456789abcdef01234567"
    other = "f" * 40

    def checkout(name, head, files=()):
        root = tmp_path / name
        (root / ".git" / "refs" / "heads").mkdir(parents=True)
        (root / ".git" / "HEAD").write_text(head + "\n")
        for rel, text in files:
            (root / ".git" / rel).write_text(text)
        return str(root)

    loose = checkout("loose", "ref: refs/heads/main",
                     [("refs/heads/main", sha + "\n")])
    packed = checkout("packed", "ref: refs/heads/main", [(
        "packed-refs", f"# pack-refs with: peeled\n{other} refs/heads/x\n"
                       f"{sha} refs/heads/main\n")])
    detached = checkout("detached", sha)
    unborn = checkout("unborn", "ref: refs/heads/main",
                      [("packed-refs", "")])
    assert events._read_head(loose) == sha
    assert events._read_head(packed) == sha
    assert events._read_head(detached) == sha
    assert events._read_head(unborn) is None
    # a worktree is not followed out of the checkout: no sha, no error
    tree = tmp_path / "tree"
    tree.mkdir()
    (tree / ".git").write_text(f"gitdir: {loose}/.git/worktrees/tree\n")
    for root in (tree, tmp_path / "nowhere"):
        with pytest.raises(OSError):
            events._read_head(str(root))
        monkeypatch.setattr(events, "_GIT_SHA_CACHE", [])
        monkeypatch.setattr(events.os.path, "abspath",
                            lambda _p, r=root: str(r / "a" / "b" / "c.py"))
        assert events.git_sha() is None
    monkeypatch.undo()
    # the cached face: 12 hex digits or None, and no import of subprocess
    monkeypatch.setattr(events, "_GIT_SHA_CACHE", [])
    got = events.git_sha()
    assert got is None or (len(got) == 12 and set(got) <= events._HEX)
    assert events._GIT_SHA_CACHE == [got]
    assert not hasattr(events, "subprocess")


def test_ddd_segment_records_carry_the_slab_counters(tmp_path):
    """RunTelemetry keeps the pass's running maximum and total: the
    segment records (log and on_progress alike) carry ``stream_peak``
    non-decreasing and ``stream_slabs`` cumulative, and the last record
    equals what the ``segments`` track sums to."""
    path = str(tmp_path / "ddd.events")
    recs = []
    res = _run_engine("ddd", path, on_progress=recs.append)
    segs = [e for e in _read_log(path) if e["event"] == "segment"]
    assert res.n_states == N_TOY and segs and len(recs) == len(segs)
    for key in ("stream_peak", "stream_slabs", "probe_tiles"):
        vals = [s[key] for s in segs]
        assert vals == sorted(vals) and vals[-1] > 0
        assert vals == [r[key] for r in recs]
    # every chunk of the toy space fits one slab: one slab write a step
    assert 0 < segs[-1]["stream_peak"] <= 32 * 11


def test_monitor_pool_attribution_rows(tmp_path):
    """A pool.events supervision log (no segments at all) renders a
    pool-lifecycle heartbeat; a tenant log with pool events alongside
    segments gets the pool row appended."""
    from raft_tla_tpu.obs.monitor import heartbeat, load_stream, summarize

    p = str(tmp_path / "pool.events")
    append_event(p, "worker_spawn", worker="w0", pid=11,
                 jobs=["a", "b"], chunk=256)
    append_event(p, "worker_lost", worker="w0", kind="killed",
                 exit_code=-9, jobs=["b"])
    append_event(p, "job_retry", job_id="b", attempt=1, worker="w1",
                 backoff_s=0.4)
    append_event(p, "worker_spawn", worker="w1", pid=12, respawn=True)
    append_event(p, "quarantine", job_id="b", reason="poison-job",
                 deaths=3)
    s = summarize(load_stream(p))
    assert s["pool_only"] and s["pool"]["spawns"] == 2
    assert s["pool"]["losses"] == 1 and s["pool"]["retries"] == 1
    assert s["pool"]["last_loss_kind"] == "killed"
    assert s["pool"]["quarantined"] == ["b"]
    line = heartbeat(s)
    assert "2 spawn(s)" in line and "1 lost" in line
    assert "last loss: killed" in line and "QUARANTINED b" in line
    # an empty/eventless stream still reports "no segments yet"
    q = str(tmp_path / "empty.events")
    open(q, "w").close()
    assert heartbeat(summarize(load_stream(q))) == "obs: no segments yet"


def test_append_event_validates(tmp_path):
    p = str(tmp_path / "x.events")
    append_event(p, "stop_requested", reason="clean-stop", source="test")
    with pytest.raises(ValueError):
        append_event(p, "stop_requested", source="test")  # missing reason
    with pytest.raises(ValueError):
        append_event(p, "no_such_event", reason="x")
    evs = _read_log(p)
    assert len(evs) == 1 and validate_event(evs[0]) == []


def test_tracker_incremental_rate_immune_to_resume():
    """Satellite (a): cumulative states/s inflated after a resume
    (prior-process states over this-process wall); the incremental rate
    and the since_resume tag carry the honest signal."""
    tr = ProgressTracker(t0=time.monotonic() - 100.0,  # 100s in already
                         n0=1, resumed=True)
    tr.anchor(1_000_000)                  # checkpoint-restored count
    rec = tr.record(n_states=1_000_050, level=7, n_transitions=2_000_000)
    assert rec.since_resume is False      # cumulative fields span processes
    assert rec.states_per_sec > 5_000     # the inflated wart, tagged...
    assert rec.inc_states_per_sec < 10    # ...while inc stays honest
    # rollback-monotone anchor: an inclusive count below the running max
    # never yields a negative rate
    rec2 = tr.record(n_states=999_000, level=7, n_transitions=2_000_001,
                     n_incl=999_500)
    assert rec2.inc_states_per_sec == 0.0


def test_tracker_unknown_baseline_first_record_anchors():
    tr = ProgressTracker(t0=time.monotonic() - 10.0,
                         n0=None)             # table-engine resume
    rec = tr.record(n_states=500, level=3, n_transitions=900)
    assert rec.inc_states_per_sec == 0.0      # anchor, not a fabricated rate
    rec2 = tr.record(n_states=700, level=3, n_transitions=1300)
    assert rec2.inc_states_per_sec > 0.0


def test_event_log_round_trips(tmp_path):
    p = str(tmp_path / "log.events")
    log = EventLog(p)
    for k in range(100):
        log.emit("level_end", level=k, n_states=k * 10)
    log.close()
    evs = _read_log(p)
    assert [e["level"] for e in evs] == list(range(100))
    assert all(validate_event(e) == [] for e in evs)
    log.close()                                   # idempotent


def test_concurrent_event_log_writers_do_not_corrupt(tmp_path):
    """Serving-mode write pattern: two jobs' EventLogs appending to
    separate logs concurrently, plus an external one-shot emitter
    (``python -m raft_tla_tpu.obs emit``) interleaving whole lines into
    one of them mid-run.  Every line must still parse and validate —
    append-mode line-at-a-time writes never interleave partial lines."""
    import threading

    pa = str(tmp_path / "a.events")
    pb = str(tmp_path / "b.events")
    la, lb = EventLog(pa), EventLog(pb)
    n_each = 400

    def pump(log, tag):
        for k in range(n_each):
            log.emit("level_end", level=k, n_states=k * 10 + tag)

    ta = threading.Thread(target=pump, args=(la, 1))
    tb = threading.Thread(target=pump, args=(lb, 2))
    ta.start(), tb.start()
    # External one-shot emitters racing the live background writer on
    # log A (the campaign_stop.sh pattern, now also the service's
    # rejected-tenant path).
    for k in range(3):
        r = subprocess.run(
            [sys.executable, "-m", "raft_tla_tpu.obs", "emit", pa,
             "stop_requested", "--reason", f"external-{k}",
             "--source", "test"],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
    ta.join(), tb.join()
    la.close(), lb.close()

    evs_a, evs_b = _read_log(pa), _read_log(pb)     # json.loads = no torn lines
    for evs in (evs_a, evs_b):
        assert all(validate_event(e) == [] for e in evs)
    # nothing lost, nothing duplicated, no cross-log bleed
    assert len(evs_a) == n_each + 3
    assert len(evs_b) == n_each
    lv_a = [e["level"] for e in evs_a if e["event"] == "level_end"]
    assert sorted(lv_a) == list(range(n_each))
    assert [e["n_states"] % 10 for e in evs_a
            if e["event"] == "level_end"] == [1] * n_each
    assert [e["n_states"] % 10 for e in evs_b] == [2] * n_each
    exts = [e for e in evs_a if e["event"] == "stop_requested"]
    assert sorted(e["reason"] for e in exts) == [
        f"external-{k}" for k in range(3)]


def test_phase_timers_disabled_is_inert_enabled_accumulates():
    off = PhaseTimers(enabled=False)
    with off.phase("expand") as ph:
        assert ph.sync(123) == 123                # pass-through
    assert off.snapshot() == {}
    on = PhaseTimers(enabled=True)
    with on.phase("expand") as ph:
        ph.sync((1, 2))
    with on.phase("expand"):
        pass
    snap = on.snapshot()
    assert set(snap) == {"expand"} and snap["expand"] >= 0.0
    assert on.snapshot() == {}                    # snapshot(reset=True)


# --------------------------------------------------------------------------
# monitor


def test_load_stream_lifts_legacy_and_rebases_walls():
    stream = monitor.load_stream("runs/elect5ddd_r5a.stats")
    assert stream["legacy"] and not stream["invalid"]
    segs = stream["segments"]
    assert segs
    cum = [s["cum_wall_s"] for s in segs]
    assert cum == sorted(cum)                     # one monotone clock
    ns = [s["n_states"] for s in segs]
    assert ns == sorted(ns)                       # rollbacks dropped
    hb = monitor.heartbeat(monitor.summarize(stream))
    assert hb.startswith("L") and "inc" in hb


def test_monitor_reads_v1_log_end_to_end(tmp_path):
    p = str(tmp_path / "run.events")
    _run_engine("ddd", p)
    stream = monitor.load_stream(p)
    assert not stream["legacy"] and not stream["invalid"]
    s = monitor.summarize(stream)
    assert s["status"] == "ok" and s["n_states"] == N_TOY
    assert s["level_sizes"]                       # from level_end events
    assert sum(s["level_sizes"].values()) <= N_TOY
    assert "ok" in monitor.heartbeat(s)
    assert monitor.main([p]) == 0                 # CLI one-shot


def test_obs_emit_cli_interleaves_with_log(tmp_path):
    p = str(tmp_path / "x.events")
    append_event(p, "checkpoint", path="ck.npz", n_states=5)
    r = subprocess.run(
        [sys.executable, "-m", "raft_tla_tpu.obs", "emit", p,
         "stop_requested", "--reason", "clean-stop",
         "--source", "campaign_stop.sh", "--pid", "42"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    evs = _read_log(p)
    assert [e["event"] for e in evs] == ["checkpoint", "stop_requested"]
    assert evs[-1]["pid"] == 42
    bad = subprocess.run(
        [sys.executable, "-m", "raft_tla_tpu.obs", "emit", p, "bogus"],
        capture_output=True, text=True)
    assert bad.returncode != 0 and len(_read_log(p)) == 2


# -- monitor end-state attribution (campaign supervision satellite) ---------
# One test per status path in monitor.summarize: the supervisor's
# health verdicts and the operator's heartbeat must agree on what a
# quiet log means.


def _seg(path, ts, n_states, level=1):
    append_event(path, "segment", ts=ts, wall_s=ts, n_states=n_states,
                 level=level, n_transitions=2 * n_states,
                 dedup_hit_rate=0.5, states_per_sec=10.0,
                 inc_states_per_sec=10.0, since_resume=True)


def _summary(path, now, stale_after_s=None):
    return monitor.summarize(monitor.load_stream(path), now=now,
                             stale_after_s=stale_after_s)


def test_monitor_attribution_run_end_wins(tmp_path):
    p = str(tmp_path / "e")
    _seg(p, 10.0, 100)
    append_event(p, "run_end", ts=11.0, n_states=3014,
                 n_transitions=5274, complete=True, outcome="ok")
    # a finished run is never "presumed-crashed", however old the log
    s = _summary(p, now=11.0 + 9999.0)
    assert s["status"] == "ok"


def test_monitor_attribution_presumed_crashed(tmp_path):
    p = str(tmp_path / "e")
    # 5s cadence -> auto threshold 10x = 50s (clamped to [30s, 1h])
    for t in range(0, 30, 5):
        _seg(p, float(t), 10 * (t + 1))
    assert _summary(p, now=25.0 + 49.0)["status"] == "live"
    s = _summary(p, now=25.0 + 51.0)
    assert s["stale"] is True
    assert s["status"].startswith("presumed-crashed (last event 51s ago")
    assert "cadence ~5s" in s["status"]


def test_monitor_attribution_explicit_threshold_overrides(tmp_path):
    p = str(tmp_path / "e")
    for t in range(0, 30, 5):
        _seg(p, float(t), 10 * (t + 1))
    # 49s of silence: live under the cadence rule, crashed at 10s policy
    assert _summary(p, now=74.0)["status"] == "live"
    s = _summary(p, now=74.0, stale_after_s=10.0)
    assert s["status"].startswith("presumed-crashed")


def test_monitor_attribution_stop_requested_live(tmp_path):
    p = str(tmp_path / "e")
    _seg(p, 10.0, 100)
    append_event(p, "stop_requested", ts=11.0, reason="preempt",
                 source="supervisor")
    s = _summary(p, now=12.0)
    assert s["status"] == "live (stop requested (preempt))"


def test_monitor_attribution_violation_live(tmp_path):
    p = str(tmp_path / "e")
    _seg(p, 10.0, 100)
    append_event(p, "violation", ts=11.0, invariant="NoTwoLeaders")
    s = _summary(p, now=12.0)
    assert s["status"] == "live (VIOLATION NoTwoLeaders)"


def test_monitor_attribution_timestampless_is_unjudged(tmp_path):
    p = str(tmp_path / "e")
    with open(p, "w") as fh:        # legacy .stats line: no ts anywhere
        fh.write(json.dumps({"n_states": 100, "wall_s": 1.0,
                             "level": 1}) + "\n")
    s = _summary(p, now=9999.0)
    assert s["stale"] is None
    assert s["status"] == "live?"   # no timestamps: no crash verdict
