"""Fault-isolated serving: worker pool, supervision, chaos parity.

The pool's contract is run_service's contract survived: SIGKILL a
worker mid-dispatch and every job still completes with results records
and tenant event logs canonically identical to an unsupervised solo
pass; ride a poison job and the pool bisects to it, quarantines it in
<= K worker deaths, and never runs it again; OOM a worker and it
respawns at half dispatch width without blaming anyone.  Plus the
crash-safety satellites: torn results tails, restart dedup, per-job
wall budgets, and the _LogTail live-log behaviors the supervisor
leans on.
"""

import json
import os
import threading
import time

import pytest

from test_cli import write_cfg

from raft_tla_tpu.campaign.supervisor import _LogTail
from raft_tla_tpu.obs import append_event
from raft_tla_tpu.serve import supervise
from raft_tla_tpu.serve.chaos import (PoolChaos, canon_events,
                                      canon_record, last_records)
from raft_tla_tpu.serve.jobs import CheckJob, JobOptions, admit
from raft_tla_tpu.serve.pool import _partition, run_pool
from raft_tla_tpu.serve.service import (read_results, record_is_terminal,
                                        run_daemon, run_service)
from raft_tla_tpu.serve.supervise import PoolPolicy, classify_death

# 524-state election universe (max_msgs=1): the cheapest real check,
# ~2s per worker process on CPU — pool tests spawn several.
OPTS = JobOptions(spec="election", max_term=2, max_log=0, max_msgs=1)
OPTS_SYM = JobOptions(spec="election", max_term=2, max_log=0,
                      max_msgs=1, symmetry=True)

FAST = PoolPolicy(poll_s=0.02, backoff_base_s=0.05, backoff_cap_s=0.2,
                  backoff_jitter_seed=7)


def _jobs(cfg, ids, alternate=True):
    """Jobs over one cfg; ``alternate`` flips symmetry on odd indices
    so the batch spans two step-signature bins."""
    return [CheckJob(j, OPTS_SYM if alternate and i % 2 else OPTS,
                     cfg_path=str(cfg))
            for i, j in enumerate(ids)]


# --------------------------------------------------------------------------
# host-only units: death classification, partitioning, budgets, torn tails


def test_classify_death_kinds():
    assert classify_death(-9)[0] == "killed"
    assert classify_death(-11)[0] == "segfault"
    assert classify_death(-15)[0] == "signal"
    assert classify_death(1)[0] == "crashed"
    assert classify_death(2, "usage: ...")[0] == "crashed"
    # the output scan wins over the returncode — an uncaught
    # MemoryError exits 1, a TPU RESOURCE_EXHAUSTED dies on a signal
    assert classify_death(1, "MemoryError: ...")[0] == "oom"
    assert classify_death(-6, "RESOURCE_EXHAUSTED: hbm")[0] == "oom"
    assert classify_death(134, "std::bad_alloc")[0] == "oom"
    # a worker that never opened its device died of its environment
    assert classify_death(
        1, "Error: device unavailable: no TPU found")[0] == "backend"
    assert classify_death(
        1, "RuntimeError: Unable to initialize backend 'tpu': "
           "UNAVAILABLE: TPU is already in use")[0] == "backend"


def test_partition_keeps_bins_together_and_splits_when_needed(tmp_path):
    cfg = write_cfg(tmp_path / "toy.cfg")
    jobs = _jobs(cfg, ["a", "b", "c", "d"])      # 2 bins x 2 jobs
    admitted = [(j, admit(j), {}) for j in jobs]
    assert all(a.admitted for _, a, _ in admitted)
    groups = _partition(admitted, workers=2)
    assert sorted(sorted(pj.job_id for pj in g) for g in groups) \
        == [["a", "c"], ["b", "d"]]              # bin-mates share a worker
    # fewer bins than workers: the single bin splits so the pool is
    # actually a pool (fault isolation over compile sharing)
    solo_bin = [(j, admit(j), {})
                for j in _jobs(cfg, ["x", "y", "z"], alternate=False)]
    groups = _partition(solo_bin, workers=2)
    assert len(groups) == 2
    assert sorted(len(g) for g in groups) == [1, 2]


def test_budget_invalid_rejected_at_admission(tmp_path):
    cfg = write_cfg(tmp_path / "toy.cfg")
    for bad in (0, -5, "3s", True):
        job = CheckJob("b", JobOptions(spec="election", max_term=2,
                                       max_log=0, max_msgs=1,
                                       wall_s=bad),
                       cfg_path=str(cfg))
        adm = admit(job)
        assert not adm.admitted and adm.reason == "budget-invalid"
        assert any("wall_s" in t for t in adm.findings_text())


def test_read_results_tolerates_torn_tail(tmp_path):
    out = tmp_path / "out"
    out.mkdir()
    good = {"job_id": "a", "status": "completed", "digest": "d1"}
    with open(out / "results.jsonl", "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write("garbage not json\n")
        f.write(json.dumps({"no_job_id": True}) + "\n")
        f.write('{"job_id": "torn", "status": "comp')   # SIGKILL here
    recs = read_results(str(out))
    assert recs == [good]
    assert read_results(str(tmp_path / "missing")) == []


def test_record_is_terminal_statuses():
    for st in ("completed", "violation", "deadlock", "rejected",
               "quarantined"):
        assert record_is_terminal({"status": st})
    assert not record_is_terminal({"status": "stopped"})
    assert not record_is_terminal({"status": "stopped",
                                   "error": "stop requested (drain)"})
    assert record_is_terminal({"status": "stopped",
                               "error": "budget-exceeded: wall 1.2s"})
    assert record_is_terminal({"status": "stopped",
                               "error": "state count exceeded 10"})


# --------------------------------------------------------------------------
# _LogTail over a live serve tenant log (satellite: the supervisor's
# eyes must survive torn lines, truncation/rotation, and a concurrent
# writer thread)


def test_logtail_live_torn_line_and_rotation(tmp_path):
    path = str(tmp_path / "t.events")
    tail = _LogTail(path)
    assert tail.poll() == []                     # not created yet
    line1 = json.dumps({"event": "segment", "n_states": 10}) + "\n"
    line2 = json.dumps({"event": "segment", "n_states": 20}) + "\n"
    with open(path, "a") as f:
        f.write(line1)
        f.flush()
        assert [e["n_states"] for e in tail.poll()] == [10]
        f.write(line2[:9])                       # torn mid-line
        f.flush()
        assert tail.poll() == []                 # buffered, not garbled
        f.write(line2[9:])
        f.flush()
        assert [e["n_states"] for e in tail.poll()] == [20]
    # rotation: requeue moves the log aside and a fresh (shorter) one
    # appears — the tail must re-anchor, not sleep at a stale offset
    os.replace(path, path + ".retry1")
    with open(path, "w") as f:
        f.write(json.dumps({"event": "run_start", "attempt": 2}) + "\n")
    assert [e["event"] for e in tail.poll()] == ["run_start"]


def test_logtail_concurrent_writer_thread(tmp_path):
    """A live serve-style log: a writer thread appends real validated
    events while the supervisor-side tail polls — every event arrives
    exactly once, in order."""
    path = str(tmp_path / "live.events")
    n = 60

    def writer():
        for i in range(n):
            append_event(path, "segment", wall_s=0.01 * i, n_states=i,
                         level=i, n_transitions=i, dedup_hit_rate=0.0,
                         states_per_sec=1.0, inc_states_per_sec=1.0,
                         since_resume=False)
    t = threading.Thread(target=writer)
    t.start()
    tail = _LogTail(path)
    seen = []
    deadline = time.monotonic() + 20.0
    while len(seen) < n and time.monotonic() < deadline:
        seen.extend(e["n_states"] for e in tail.poll()
                    if e.get("event") == "segment")
        time.sleep(0.002)
    t.join()
    seen.extend(e["n_states"] for e in tail.poll()
                if e.get("event") == "segment")
    assert seen == list(range(n))


# --------------------------------------------------------------------------
# pool end-to-end: parity under SIGKILL, poison quarantine, OOM
# degradation, drain, budgets, restart dedup


def test_pool_parity_under_worker_sigkill(tmp_path):
    """The acceptance bar: SIGKILL a worker mid-dispatch; every job
    still completes and both the results records and tenant event logs
    are canonically identical to an unsupervised solo run_service."""
    cfg = write_cfg(tmp_path / "toy.cfg")
    jobs = _jobs(cfg, ["j0", "j1", "j2", "j3"])
    ref = {r["job_id"]: r
           for r in run_service(jobs, str(tmp_path / "ref"),
                                chunk=256, quiet=True)}
    chaos = PoolChaos(kill_after_events=2)
    recs = run_pool(jobs, str(tmp_path / "pool"), workers=2, chunk=256,
                    cpu=True, quiet=True, policy=FAST,
                    spawn_hook=chaos.spawn_hook)
    assert chaos.kills and chaos.kills[0][1] == "kill-after-events"
    by = {r["job_id"]: r for r in recs}
    for job in jobs:
        jid = job.job_id
        assert by[jid]["status"] == "completed"
        assert canon_record(ref[jid]) == canon_record(by[jid])
        assert canon_events(str(tmp_path / "ref" / f"{jid}.events")) \
            == canon_events(str(tmp_path / "pool" / f"{jid}.events"))
    # supervision telemetry: a spawn per worker, one loss, retries
    pool_events = [json.loads(l) for l in
                   open(tmp_path / "pool" / "pool.events")]
    kinds = [e["event"] for e in pool_events]
    assert kinds.count("worker_lost") >= 1
    assert "job_retry" in kinds and "quarantine" not in kinds


def test_pool_poison_bisection_quarantine(tmp_path):
    """A job that kills every worker it rides is bisected to, blamed,
    and quarantined after <= K deaths — with attributed quarantine
    records — while its innocent cellmates complete normally."""
    cfg = write_cfg(tmp_path / "toy.cfg")
    jobs = _jobs(cfg, ["i0", "poison", "i2"], alternate=False)  # one bin
    out = str(tmp_path / "out")
    K = 2
    chaos = PoolChaos(poison="poison")
    recs = run_pool(jobs, out, workers=2, chunk=256, cpu=True,
                    quiet=True,
                    policy=PoolPolicy(poll_s=0.02, backoff_base_s=0.05,
                                      backoff_cap_s=0.2,
                                      backoff_jitter_seed=7,
                                      max_job_deaths=K),
                    spawn_hook=chaos.spawn_hook)
    by = {r["job_id"]: r for r in recs}
    assert by["poison"]["status"] == "quarantined"
    assert by["poison"]["reason"] == "poison-job"
    assert by["poison"]["deaths"] <= K
    assert record_is_terminal(by["poison"])      # never re-run, ever
    assert by["i0"]["status"] == by["i2"]["status"] == "completed"
    assert by["i0"]["n_states"] == by["i2"]["n_states"] == 524
    # the poison died exactly K times and was never dispatched after
    # its quarantine
    assert len(chaos.kills) == K
    pool_events = [json.loads(l) for l in open(os.path.join(
        out, "pool.events"))]
    q = [e for e in pool_events if e["event"] == "quarantine"]
    assert len(q) == 1 and q[0]["job_id"] == "poison"
    spawns_with_poison = [e for e in pool_events
                          if e["event"] == "worker_spawn"
                          and "poison" in e["jobs"]]
    assert len(spawns_with_poison) == K
    q_idx = pool_events.index(q[0])
    assert all(pool_events.index(e) < q_idx for e in spawns_with_poison)
    # tenant-log attribution: the quarantined tenant's log ends with
    # an explicit stop + quarantined outcome, not silence
    ev = [json.loads(l) for l in open(os.path.join(out,
                                                   "poison.events"))]
    assert ev[-1]["event"] == "run_end"
    assert ev[-1]["outcome"] == "quarantined"
    assert any(e["event"] == "stop_requested"
               and "quarantined" in e["reason"] for e in ev)


def test_pool_oom_respawns_with_halved_chunk(tmp_path, monkeypatch):
    """An OOM-classified death takes no blame: the same group respawns
    at half dispatch width and completes."""
    cfg = write_cfg(tmp_path / "toy.cfg")
    jobs = _jobs(cfg, ["a", "b"], alternate=False)
    out = str(tmp_path / "out")
    monkeypatch.setattr(supervise, "classify_death",
                        lambda rc, out_text="": ("oom", "simulated"))
    killed = []

    def hook(w):
        if not killed:
            killed.append(w.wid)
            w.proc.kill()
    # max_job_deaths=1 proves no blame was assigned: one blamed death
    # would quarantine immediately
    recs = run_pool(jobs, out, workers=1, chunk=256, cpu=True,
                    quiet=True,
                    policy=PoolPolicy(poll_s=0.02, backoff_base_s=0.05,
                                      backoff_cap_s=0.2,
                                      backoff_jitter_seed=7,
                                      max_job_deaths=1, min_chunk=32),
                    spawn_hook=hook)
    by = {r["job_id"]: r for r in recs}
    assert by["a"]["status"] == by["b"]["status"] == "completed"
    pool_events = [json.loads(l) for l in open(os.path.join(
        out, "pool.events"))]
    spawns = [e for e in pool_events if e["event"] == "worker_spawn"]
    assert [e["chunk"] for e in spawns] == [256, 128]    # degraded
    assert sorted(spawns[0]["jobs"]) == sorted(spawns[1]["jobs"])
    assert not [e for e in pool_events if e["event"] == "quarantine"]
    retries = [e for e in pool_events if e["event"] == "job_retry"]
    assert retries and all(e["reason"] == "oom" for e in retries)


def test_pool_backend_death_blames_no_job(tmp_path, monkeypatch):
    """Workers that die opening their backend (here for real: the
    children are told to use a TPU this sandbox does not have) are an
    environment failure.  max_job_deaths=1 proves no job was blamed —
    one blamed death would write a terminal quarantined/poison-job
    record for a healthy job, which is a wrong answer."""
    cfg = write_cfg(tmp_path / "toy.cfg")
    jobs = _jobs(cfg, ["healthy"], alternate=False)
    out = str(tmp_path / "out")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")       # inherited by workers
    recs = run_pool(jobs, out, workers=1, chunk=256, quiet=True,
                    policy=PoolPolicy(poll_s=0.02, backoff_base_s=0.02,
                                      backoff_cap_s=0.05,
                                      backoff_jitter_seed=7,
                                      max_job_deaths=1, max_respawns=1))
    assert recs[0]["status"] == "stopped"
    assert "pool gave up" in recs[0]["error"]
    assert "backend" in recs[0]["error"]
    assert not record_is_terminal(recs[0])           # a restart may retry
    pool_events = [json.loads(l) for l in open(os.path.join(
        out, "pool.events"))]
    lost = [e for e in pool_events if e["event"] == "worker_lost"]
    assert lost and all(e["kind"] == "backend" for e in lost)
    assert not [e for e in pool_events if e["event"] == "quarantine"]


def test_more_workers_than_chips_is_refused(tmp_path, monkeypatch, capsys):
    """A chip serves one process: asking for more workers than chips is
    a loud error, from the library call and from the CLI (whose parent
    learns the count from a probe child, never from its own backend)."""
    cfg = write_cfg(tmp_path / "toy.cfg")
    with pytest.raises(ValueError, match="3 workers need 3 TPU chips"):
        run_pool(_jobs(cfg, ["a"]), str(tmp_path / "o"), workers=3,
                 chips=2)
    from raft_tla_tpu.serve import service
    from raft_tla_tpu.utils import device
    monkeypatch.setattr(device, "probe_devices", lambda cpu=False: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    manifest = tmp_path / "m.jsonl"
    manifest.write_text(json.dumps({"id": "a", "cfg": str(cfg)}) + "\n")
    with pytest.raises(SystemExit) as e:
        service.main([str(manifest), "--out", str(tmp_path / "o2"),
                      "--workers", "2"])
    assert e.value.code == 2
    assert "exceeds the 1 TPU chip(s)" in capsys.readouterr().err


def test_pool_drain_attributes_undispatched_jobs(tmp_path):
    """stop() truthy before any spawn: no workers start, every admitted
    job gets an attributed stopped record and a non-silent event log."""
    cfg = write_cfg(tmp_path / "toy.cfg")
    jobs = _jobs(cfg, ["a", "b"])
    out = str(tmp_path / "out")
    recs = run_pool(jobs, out, workers=2, cpu=True, quiet=True,
                    policy=FAST, stop=lambda: True)
    assert len(recs) == 2
    for r in recs:
        assert r["status"] == "stopped"
        assert "never reached a worker" in r["error"]
        assert not record_is_terminal(r)         # a restart may retry
        ev = [json.loads(l) for l in open(r["events"])]
        assert ev[-1]["event"] == "run_end"
        assert ev[-1]["outcome"] == "stopped"


def test_pool_gives_up_when_respawn_budget_exhausts(tmp_path,
                                                    monkeypatch):
    """A systematically dying fleet must exhaust the bounded respawn
    budget and stop with attribution, not retry forever."""
    cfg = write_cfg(tmp_path / "toy.cfg")
    jobs = _jobs(cfg, ["a"], alternate=False)
    out = str(tmp_path / "out")

    def hook(w):                                 # every worker dies
        w.proc.kill()
    recs = run_pool(jobs, out, workers=1, chunk=256, cpu=True,
                    quiet=True,
                    policy=PoolPolicy(poll_s=0.02, backoff_base_s=0.02,
                                      backoff_cap_s=0.05,
                                      backoff_jitter_seed=7,
                                      max_job_deaths=99,
                                      max_respawns=2),
                    spawn_hook=hook)
    assert recs[0]["status"] == "stopped"
    assert "pool gave up" in recs[0]["error"]
    pool_events = [json.loads(l) for l in open(os.path.join(
        out, "pool.events"))]
    spawns = [e for e in pool_events if e["event"] == "worker_spawn"]
    assert len(spawns) == 3                      # initial + 2 respawns


def test_wall_budget_stops_lane_losslessly(tmp_path):
    """wall_s -> a terminal budget-exceeded stop at a level boundary;
    the cellmate lane is untouched."""
    cfg = write_cfg(tmp_path / "toy.cfg")
    jobs = [CheckJob("fast", OPTS, cfg_path=str(cfg)),
            CheckJob("capped", JobOptions(spec="election", max_term=2,
                                          max_log=0, max_msgs=1,
                                          wall_s=1e-4),
                     cfg_path=str(cfg))]
    recs = run_service(jobs, str(tmp_path / "out"), chunk=256,
                       quiet=True)
    by = {r["job_id"]: r for r in recs}
    assert by["fast"]["status"] == "completed"
    assert by["fast"]["n_states"] == 524
    assert by["capped"]["status"] == "stopped"
    assert by["capped"]["error"].startswith("budget-exceeded")
    assert record_is_terminal(by["capped"])      # restart will NOT rerun


def test_daemon_restart_skips_terminal_digests(tmp_path):
    """Daemon restart dedup: a queue job whose content digest already
    has a terminal record is not re-run (and not re-billed)."""
    q = tmp_path / "q"
    q.mkdir()
    write_cfg(q / "toy.cfg")
    (q / "001-a.json").write_text(json.dumps(
        {"id": "a", "cfg": "toy.cfg", "spec": "election",
         "max_term": 2, "max_log": 0, "max_msgs": 1}))
    out = str(tmp_path / "out")
    assert run_daemon(str(q), out, chunk=256, quiet=True, poll_s=0.05,
                      max_idle_polls=2) == 0
    first = read_results(out)
    assert [r["status"] for r in first] == ["completed"]
    # restart: same queue, same digest -> zero new records
    assert run_daemon(str(q), out, chunk=256, quiet=True, poll_s=0.05,
                      max_idle_polls=2) == 0
    assert read_results(out) == first
