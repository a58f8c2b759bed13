"""Pipelined dispatch scheduler (serve/sched.py).

The async scheduler must change WHEN work runs, never WHAT it computes:
depth=2 interleaving with async compiles must leave every completing
lane's results and per-tenant event stream identical to the depth=1
sequential baseline (the PR 6 Engine-verbatim invariant, extended).
Fair-share packing must bound starvation under oversubscription, the
background compile must actually run off-thread, and the daemon's
drain hook must give every accepted lane an attributed terminal record.
"""

import json
import os
import signal
import subprocess
import sys
import time
import types

import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.engine import Engine
from raft_tla_tpu.serve import CheckJob, JobOptions
from raft_tla_tpu.serve.batch import BatchExecutor
from raft_tla_tpu.serve.sched import DispatchScheduler
from raft_tla_tpu.serve.service import run_service

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(**kw):
    b = dict(n_servers=2, n_values=1, max_term=2, max_log=0, max_msgs=2)
    sym = kw.pop("symmetry", ())
    b.update(kw)
    return CheckConfig(bounds=Bounds(**b), spec="election",
                       invariants=("NoTwoLeaders",), symmetry=sym,
                       chunk=256)


TOY_M1 = _cfg(max_msgs=1)               # 524 states
TOY_M1S = _cfg(max_msgs=1, symmetry=("Server",))
TOY = _cfg()                            # 3,014 states
TOY_SYM = _cfg(symmetry=("Server",))


# --------------------------------------------------------------------------
# interleaved vs sequential: byte-identical tenant artifacts

_CFG_TEXT = """SPECIFICATION Spec
INVARIANT NoTwoLeaders
CONSTANTS
    Server = {s1, s2}
    Value = {v1}
    Follower = "Follower"
    Candidate = "Candidate"
    Leader = "Leader"
    Nil = "Nil"
    RequestVoteRequest = "RequestVoteRequest"
    RequestVoteResponse = "RequestVoteResponse"
    AppendEntriesRequest = "AppendEntriesRequest"
    AppendEntriesResponse = "AppendEntriesResponse"
"""

# 16 jobs over 4 step-signature bins, all completing (all-completing is
# what makes full byte-parity well-defined: a lane that *violates* mid-
# pipeline changes later slice boundaries — its guarantee is verdict and
# trace, covered by test_serve.py).
_MANIFEST = ([(f"m1-{i}", dict(max_msgs=1)) for i in range(6)]
             + [(f"m1s-{i}", dict(max_msgs=1, symmetry=True))
                for i in range(4)]
             + [(f"m2-{i}", dict()) for i in range(4)]
             + [(f"m2s-{i}", dict(symmetry=True)) for i in range(2)])

# Everything that varies run-to-run without changing WHAT was computed:
# wall-clock, rates, and the pipeline-occupancy annotation itself.
_VOLATILE = frozenset({"ts", "wall_s", "states_per_sec",
                       "inc_states_per_sec", "admission_s", "inflight",
                       "phase_s", "pid", "git_sha", "anchor"})


def _jobs():
    return [CheckJob(jid, JobOptions(spec="election", max_term=2,
                                     max_log=0,
                                     max_msgs=kw.get("max_msgs", 2),
                                     symmetry=kw.get("symmetry", False)),
                     cfg_text=_CFG_TEXT)
            for jid, kw in _MANIFEST]


def _scrub(d):
    return {k: v for k, v in d.items() if k not in _VOLATILE}


@pytest.mark.smoke
def test_interleaved_matches_sequential_byte_for_byte(tmp_path):
    """The tentpole invariant: depth=2 + async compiles vs the depth=1
    sequential baseline on the 16-job/4-bin manifest — every tenant's
    results.jsonl record and full event stream identical modulo
    timing-only fields."""
    out_seq = run_service(_jobs(), str(tmp_path / "seq"), chunk=256,
                          quiet=True, depth=1, compile_async=False)
    out_int = run_service(_jobs(), str(tmp_path / "int"), chunk=256,
                          quiet=True, depth=2, compile_async=True)
    seq = {r["job_id"]: r for r in out_seq}
    inter = {r["job_id"]: r for r in out_int}
    assert set(seq) == set(inter) == {jid for jid, _ in _MANIFEST}
    for jid in seq:
        a, b = dict(seq[jid]), dict(inter[jid])
        ea, eb = a.pop("events"), b.pop("events")
        assert _scrub(a) == _scrub(b), jid
        assert a["status"] == "completed", jid
        evs_a = [_scrub(json.loads(l)) for l in open(ea)]
        evs_b = [_scrub(json.loads(l)) for l in open(eb)]
        assert evs_a == evs_b, jid

    # and the depth=2 arm really pipelined + compiled off-thread
    # (scheduler stats ride on the records only via the event logs, so
    # re-run one executor directly to read them; chunk 64 makes the
    # 3,014-state levels span several dispatches, so the speculative
    # same-bin path must fill the pipeline)
    ex = BatchExecutor(chunk=64, depth=2, compile_async=True)
    out = ex.run([("a", TOY), ("b", TOY_SYM)])
    assert all(oc.status == "completed" for oc in out.values())
    assert ex.last_stats["peak_inflight"] >= 2
    assert ex.last_stats["async_compiles"] == 2


def test_executor_parity_vs_solo_all_depths():
    """Counts parity vs solo Engine at depth 1, 2 and 3 — the per-lane
    chunk semantics must be depth-invariant, not just depth-2-correct."""
    solo = {jid: Engine(cfg).check()
            for jid, cfg in [("a", TOY_M1), ("s", TOY_M1S)]}
    for depth in (1, 2, 3):
        out = BatchExecutor(chunk=128, depth=depth).run(
            [("a", TOY_M1), ("s", TOY_M1S)])
        for jid, ref in solo.items():
            got = out[jid].result
            assert out[jid].status == "completed", (depth, jid)
            assert got.n_states == ref.n_states, (depth, jid)
            assert got.diameter == ref.diameter, (depth, jid)
            assert got.n_transitions == ref.n_transitions, (depth, jid)
            assert list(got.levels) == list(ref.levels), (depth, jid)
            assert dict(got.coverage) == dict(ref.coverage), (depth, jid)


def test_aot_compile_failure_stops_the_bin_with_the_compilers_message(
        monkeypatch):
    """An AOT compile failure used to degrade to a lazy jit on the
    dispatch path; now it surfaces: the bin's lanes stop, attributed
    with the compiler's own words, and the other bins serve on."""
    from raft_tla_tpu.serve import batch
    orig = batch._Bin.__init__

    def init(self, key, config, tag="bin"):
        orig(self, key, config, tag)
        if config.symmetry:
            def refuse(vecs):
                raise ValueError("mosaic says no")
            self.step_fn = refuse
    monkeypatch.setattr(batch._Bin, "__init__", init)
    out = BatchExecutor(chunk=128).run([("a", TOY_M1), ("s", TOY_M1S)])
    assert out["a"].status == "completed" and out["a"].result.n_states == 524
    assert out["s"].status == "stopped"
    assert "step compile failed" in out["s"].error
    assert "mosaic says no" in out["s"].error


_CACHE_PROBE = """
import json, os, sys
import jax
touched = []
_update = jax.config.update
def spy(name, value):
    touched.append(name)
    return _update(name, value)
jax.config.update = spy
from raft_tla_tpu.serve.sched import enable_compile_cache
got = enable_compile_cache(sys.argv[1] or None, platform=sys.argv[2])
print(json.dumps({"returned": got, "config": jax.config.jax_compilation_cache_dir,
                  "touched": touched,
                  "min_s": jax.config.jax_persistent_cache_min_compile_time_secs}))
"""


def _cache_probe(flag, platform="tpu", **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR",
                         "RAFT_TLA_COMPILE_CACHE")}
    full.update(env)
    p = subprocess.run([sys.executable, "-c", _CACHE_PROBE, flag, platform],
                       cwd=REPO, env=full, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself; the flag and
    the gate are ignored and NO code path updates the directory."""
    outside, flag, gate = (str(tmp_path / n) for n in ("x", "flag", "gate"))
    got = _cache_probe(flag, JAX_COMPILATION_CACHE_DIR=outside,
                       RAFT_TLA_COMPILE_CACHE=gate)
    assert got["returned"] == got["config"] == outside
    assert "jax_compilation_cache_dir" not in got["touched"]
    assert got["min_s"] == 0.0           # small programs still cached
    assert not os.path.exists(flag) and not os.path.exists(gate)


def test_compile_cache_default_is_the_fixed_checkout_directory(tmp_path):
    """Unset: the flag if given, else the gate, else <checkout>/.jax_cache
    — fixed, never a temp name — on an accelerator.  (That a second
    process then reuses the entries is asserted by
    tests/test_zz_chip_smoke.py's warm phase.)"""
    fixed = os.path.join(REPO, ".jax_cache")
    got = _cache_probe("")
    assert got["returned"] == got["config"] == fixed
    flag, gate = str(tmp_path / "flag"), str(tmp_path / "gate")
    assert _cache_probe("", RAFT_TLA_COMPILE_CACHE=gate)["config"] == gate
    assert _cache_probe(flag, RAFT_TLA_COMPILE_CACHE=gate)["config"] == flag
    # the one exception: on the CPU the default directory is not used
    # (jaxlib's XLA:CPU loader floods stderr on warm loads), only a
    # placed one
    got = _cache_probe("", platform="cpu")
    assert got["returned"] is None and got["config"] is None
    assert _cache_probe(flag, platform="cpu")["config"] == flag


def test_depth_validation():
    with pytest.raises(ValueError, match="depth"):
        DispatchScheduler(chunk=64, depth=0)


# --------------------------------------------------------------------------
# fair-share deficit round robin: starvation bound


class _StubLane:
    def __init__(self, jid, pending):
        self.job_id = jid
        self._pending = pending

    def pending_rows(self):
        return self._pending


def _drive(chunk, lanes, dispatches):
    """Run _plan_takes repeatedly, applying takes; returns per-dispatch
    served-lane sets."""
    sched = DispatchScheduler(chunk=chunk, depth=1, compile_async=False)
    st = types.SimpleNamespace(rr=0, deficit={})
    served = []
    for _ in range(dispatches):
        live = [ln for ln in lanes if ln.pending_rows() > 0]
        if not live:
            break
        plan = sched._plan_takes(st, live)
        assert sum(t for _ln, t in plan) <= chunk
        for ln, t in plan:
            assert 0 < t <= ln.pending_rows()
            ln._pending -= t
        served.append({ln.job_id for ln, _t in plan})
    return served


def test_drr_starvation_bound_oversubscribed():
    """16 lanes on an 4-row chunk: every pending lane must ride within
    any ceil(n/B) = 4 consecutive dispatches, and every dispatch must
    be full (work-conserving) while work remains."""
    B, n = 4, 16
    lanes = [_StubLane(f"l{i}", 40) for i in range(n)]
    served = _drive(B, lanes, 40)
    window = -(-n // B)
    for w0 in range(len(served) - window + 1):
        rode = set().union(*served[w0:w0 + window])
        assert rode == {f"l{i}" for i in range(n)}, \
            f"lane starved in window starting at dispatch {w0}"
    # full chunks while every lane still had pending rows
    assert all(len(s) == B for s in served[:n // B * 2])


def test_drr_undersubscribed_every_lane_every_dispatch():
    """B >= n: every pending lane rides every dispatch and leftover
    space backfills to the deeper frontiers (chunk stays full)."""
    B = 64
    lanes = [_StubLane("big", 1000), _StubLane("small", 3),
             _StubLane("mid", 100)]
    served = _drive(B, lanes, 1)
    assert served[0] == {"big", "small", "mid"}
    # 3 quantum-21 grants cover small's 3 rows; backfill fills the rest
    taken = 1000 + 3 + 100 - sum(ln.pending_rows() for ln in lanes)
    assert taken == B


def test_drr_skips_exhausted_lane_without_deficit_leak():
    """A lane with no pending rows accrues no deficit and is skipped;
    when it refills it gets the normal quantum, not a hoarded burst."""
    sched = DispatchScheduler(chunk=8, depth=1, compile_async=False)
    st = types.SimpleNamespace(rr=0, deficit={})
    idle = _StubLane("idle", 0)
    busy = _StubLane("busy", 100)
    for _ in range(5):
        plan = sched._plan_takes(st, [busy])
        for ln, t in plan:
            ln._pending -= t
    assert st.deficit.get("idle", 0) == 0
    idle._pending = 100
    plan = dict((ln.job_id, t)
                for ln, t in sched._plan_takes(st, [idle, busy]))
    assert plan["idle"] <= 8              # quantum+backfill, no hoard


# --------------------------------------------------------------------------
# daemon drain: every accepted lane reaches an attributed record


def test_executor_stop_drains_with_attribution(tmp_path):
    """The daemon's stop hook: a stop signal that turns on mid-run must
    leave every lane either completed or failed with the drain
    attribution — never silent."""
    calls = {"n": 0}

    def stop():
        calls["n"] += 1
        return calls["n"] > 4            # trip after a few dispatches

    ex = BatchExecutor(chunk=64, depth=2, stop=stop)
    out = ex.run([("a", TOY), ("b", TOY_SYM)])
    assert set(out) == {"a", "b"}
    for oc in out.values():
        assert oc.status in ("completed", "stopped")
        if oc.status == "stopped":
            assert "stop requested (drain)" in oc.error
            assert oc.result.complete is False
    assert any(oc.status == "stopped" for oc in out.values())


@pytest.mark.smoke
def test_daemon_watch_sigint_drain(tmp_path):
    """End-to-end daemon: file intake from a watched queue dir, results
    appear while the daemon stays up, SIGINT exits 0 (lossless drain),
    and a duplicate job id is rejected without touching the original
    tenant's artifacts."""
    qdir, out = tmp_path / "q", tmp_path / "out"
    qdir.mkdir()
    job = {"id": "watched", "cfg_text": _CFG_TEXT, "spec": "election",
           "max_term": 2, "max_log": 0, "max_msgs": 1}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-m", "raft_tla_tpu.serve", str(qdir),
         "--watch", "--out", str(out), "--chunk", "64", "--poll", "0.2",
         "--cpu", "--quiet"],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        (qdir / "001-a.json").write_text(json.dumps(job))

        def records():
            p = out / "results.jsonl"
            if not p.exists():
                return []
            return [json.loads(l) for l in p.read_text().splitlines()]

        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if any(r["job_id"] == "watched" for r in records()):
                break
            assert proc.poll() is None, proc.communicate()
            time.sleep(0.3)
        recs = {r["job_id"]: r for r in records()}
        assert recs["watched"]["status"] == "completed"
        assert recs["watched"]["n_states"] == 524

        # duplicate id in a NEW file: rejected, original artifacts intact
        (qdir / "002-dup.json").write_text(json.dumps(job))
        while time.monotonic() < deadline:
            recs = [r for r in records() if r["job_id"] == "watched"]
            if len(recs) == 2:
                break
            time.sleep(0.3)
        dups = [r for r in records()
                if r["job_id"] == "watched" and r["status"] == "rejected"]
        assert dups and dups[0]["reason"] == "duplicate-id"
        done = [r for r in records()
                if r["job_id"] == "watched" and r["status"] == "completed"]
        assert len(done) == 1            # the original record, untouched

        proc.send_signal(signal.SIGINT)
        code = proc.wait(timeout=60)
        assert code == 0, proc.communicate()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
