#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the checker still starts on the chip.

Drives the three user entry points (``python -m raft_tla_tpu.check``,
``python -m raft_tla_tpu.serve``, ``python -m raft_tla_tpu.campaign``) once
each on one TPU chip, at the size users call real (the flagship window: a
host key set in the millions behind a 2^22-slot device filter), and checks
what comes out by the repo's own means: the flagship's level table, the
pure-Python oracle (``--engine ref``) on two complete spaces, a
counterexample replayed through ``models/interp.py``, the pinned served
counts, the campaign's verdict line.

One process per chip: this parent never imports JAX (nor the package); each
phase is its own child, run one after another, invoked as a user would — no
``--cpu``.  It takes no option.  It reads only tracked files, writes only
under ``chiprun_out/chip_smoke/`` (the program itself keeps its compile
cache where serve/sched.enable_compile_cache puts it), exits non-zero if
any phase failed or the platform is not ``tpu``, and on success prints as
its last line ``{"ok": true, "device": {...}}``.

tests/test_zz_chip_smoke.py rehearses every phase function at toy size on the
CPU, so chip time is not spent debugging this script.
"""

import collections
import itertools
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
FLAGSHIP_CFG = os.path.join("runs", "MC3s2v.cfg")      # tracked

# Cumulative distinct orbits after each completed BFS level of the flagship
# (reference raft.cfg universe, 3s/2v full Next, SYMMETRY Server, t2 l1 m2
# d1).  Source: runs/flagship_r2_ddd.stats (the round-2 complete run); the
# first 17 re-derived on the CPU under JAX 0.9.0 (ISSUE 21).
FLAGSHIP_LEVELS = (
    1, 2, 7, 23, 78, 242, 677, 1590, 3451, 7594, 16437, 31691, 57841,
    112436, 206931, 342847, 573746, 949932, 1468275, 2213667, 3260322,
    4596761, 6331246, 8486397, 10984294, 13950986, 17419585, 21235646,
    25369177, 29988771, 35046500)
FLAGSHIP_BOUNDS = ("--max-term", "2", "--max-log", "1", "--max-msgs", "2",
                   "--max-dup", "1")

_HEAD = "SPECIFICATION Spec\n"
CFG_TOY = _HEAD + ("INVARIANT NoTwoLeaders\n"
                   "CONSTANTS\n    Server = {s1, s2}\n    Value = {v1}\n")
CFG_ELECT3 = _HEAD + ("INVARIANT NoTwoLeaders\n"
                      "CONSTANTS\n    Server = {s1, s2, s3}\n"
                      "    Value = {v1}\n")
CFG_ELECT3_SYM = CFG_ELECT3 + "SYMMETRY Server\n"
CFG_NAIVE3 = CFG_ELECT3.replace("NoTwoLeaders", "NaiveNoTwoLeaders")
CFG_FULL2 = _HEAD + ("INVARIANTS NoTwoLeaders LogMatching "
                     "CommittedWithinLog\n"
                     "CONSTANTS\n    Server = {s1, s2}\n"
                     "    Value = {v1, v2}\n")
CFG_VACUOUS = CFG_TOY.replace("NoTwoLeaders", "LogMatching")

TOY_FLAGS = ("--spec", "election", "--max-term", "2", "--max-log", "0",
             "--max-msgs", "2")
ELECT3_FLAGS = ("--spec", "election", "--max-term", "2", "--max-log", "0",
                "--max-msgs", "1")

# (name, cfg text, bounds flags, pinned (states, diameter))
COMPLETE_SPACES = (
    ("elect3", CFG_ELECT3, ELECT3_FLAGS, (142538, 31)),
    ("elect3-sym", CFG_ELECT3_SYM, ELECT3_FLAGS, (23902, 31)),
)
COUNTEREXAMPLE = ("naive3", CFG_NAIVE3,
                  ("--spec", "election", "--max-term", "3", "--max-log", "0",
                   "--max-msgs", "1"),
                  {"n_servers": 3, "n_values": 1, "max_term": 3,
                   "max_log": 0, "max_msgs": 1, "max_dup": 1},
                  "election", "NaiveNoTwoLeaders")
_ELECT2 = {"spec": "election", "max_log": 0, "max_msgs": 2}
SERVE_JOBS = (                  # (job dict, pinned record fields)
    ({"id": "elect2-t2", "cfg_text": CFG_TOY, "max_term": 2, **_ELECT2},
     {"status": "completed", "n_states": 3014, "diameter": 17}),
    ({"id": "elect2-t3", "cfg_text": CFG_TOY, "max_term": 3, **_ELECT2},
     {"status": "completed", "n_states": 44765, "diameter": 25}),
    ({"id": "full2s2v", "cfg_text": CFG_FULL2, "spec": "full",
      "max_term": 2, "max_log": 1, "max_msgs": 2},
     {"status": "completed", "n_states": 74897, "diameter": 32}),
    ({"id": "vacuous", "cfg_text": CFG_VACUOUS, "max_term": 2, **_ELECT2},
     {"status": "rejected"}),
)

_RESULT_RE = re.compile(r"(\d+) distinct states found, diameter (\d+), "
                        r"(\d+) transitions")
_OK_LINE = "Model checking completed. No error has been found."


class Failed(Exception):
    """A phase did not pass."""


def need(cond, msg: str) -> None:
    if not cond:
        raise Failed(msg)


class Ctx:
    """Where a smoke run writes and what it demands of every phase."""

    def __init__(self, out: str = OUT, platform: str = "tpu"):
        self.out = out
        self.platform = platform        # every phase must report this
        self.device = None              # filled by phase_probe
        os.makedirs(out, exist_ok=True)

    def path(self, *parts: str) -> str:
        return os.path.join(self.out, *parts)

    def write(self, name: str, text: str) -> str:
        p = self.path(name)
        with open(p, "w", encoding="utf-8") as f:
            f.write(text)
        return p


# ---------------------------------------------------------------- children


Done = collections.namedtuple("Done", "rc out err wall t_spawn")


def run(ctx: Ctx, name: str, argv: list, *, env: dict | None = None,
        timeout: float = 900.0, sigint_when=None) -> Done:
    """Run one child to its end (stdout/stderr kept under the output
    directory).  ``sigint_when()`` turning true sends the child ONE
    SIGINT (the engines' lossless-stop request).  On timeout the child's
    whole process group is killed — nothing this script starts outlives
    it."""
    outp, errp = ctx.path(name + ".out"), ctx.path(name + ".err")
    t_spawn = time.time()
    t0 = time.monotonic()
    with open(outp, "wb") as fo, open(errp, "wb") as fe:
        proc = subprocess.Popen(argv, cwd=ROOT, stdout=fo, stderr=fe,
                                stdin=subprocess.DEVNULL, env=env,
                                start_new_session=True)
        try:
            while proc.poll() is None:
                if time.monotonic() - t0 > timeout:
                    raise Failed(f"{name}: no exit after {timeout:.0f}s")
                if sigint_when is not None and sigint_when():
                    proc.send_signal(signal.SIGINT)
                    sigint_when = None
                time.sleep(0.2)
        finally:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
    with open(outp, encoding="utf-8", errors="replace") as f:
        out = f.read()
    with open(errp, encoding="utf-8", errors="replace") as f:
        err = f.read()
    return Done(proc.returncode, out, err, time.monotonic() - t0, t_spawn)


def run_check(ctx, name, cfg, flags, **kw) -> Done:
    return run(ctx, name, [sys.executable, "-m", "raft_tla_tpu.check", cfg,
                           *flags], **kw)


def run_snippet(ctx, name, code, args=(), **kw) -> dict:
    """A ``python -c`` child that prints one JSON object last."""
    d = run(ctx, name, [sys.executable, "-c", code, *args], **kw)
    need(d.rc == 0, f"{name}: exit {d.rc}: {tail(d.err)}")
    try:
        return json.loads(d.out.strip().splitlines()[-1])
    except (ValueError, IndexError):
        raise Failed(f"{name}: no JSON on stdout: {tail(d.out)}") from None


def cpu_env() -> dict:
    """For the reference children: the plain oracle runs on the CPU."""
    return dict(os.environ, JAX_PLATFORMS="cpu")


def tail(text: str, n: int = 600) -> str:
    return text.strip()[-n:]


def jsonl(path: str) -> list:
    try:
        with open(path, encoding="utf-8") as f:
            return [json.loads(ln) for ln in f if ln.strip()]
    except OSError:
        return []


def last(events: list, kind: str) -> dict | None:
    hits = [e for e in events if e.get("event") == kind]
    return hits[-1] if hits else None


def need_platform(ctx: Ctx, name: str, events: list) -> None:
    """Every run states where it executed (run_start.host); every phase
    is held to the platform the smoke demands."""
    start = last(events, "run_start")
    need(start is not None, f"{name}: no run_start event")
    got = (start.get("host") or {}).get("platform")
    need(got == ctx.platform,
         f"{name}: ran on platform {got!r}, not {ctx.platform!r}")


def parse_result(name: str, d: Done) -> tuple:
    m = _RESULT_RE.search(d.out)
    need(m is not None, f"{name}: no result line: {tail(d.out + d.err)}")
    return tuple(int(x) for x in m.groups())


def check_levels(name: str, levels: list, complete: bool) -> int:
    """Every COMPLETED level's cumulative count equals the table (a
    stopped run's last entry is the level it was inside: bounded, not
    equal).  Returns how many levels were held to the table."""
    cum = list(itertools.accumulate(levels))
    done = cum if complete else cum[:-1]
    need(len(cum) <= len(FLAGSHIP_LEVELS),
         f"{name}: ran past the embedded level table ({len(cum)} levels)")
    for lvl, (got, want) in enumerate(zip(done, FLAGSHIP_LEVELS)):
        need(got == want, f"{name}: level {lvl} cumulative count {got}, "
                          f"table says {want}")
    need(cum[-1] <= FLAGSHIP_LEVELS[len(cum) - 1],
         f"{name}: partial level {len(cum) - 1} already holds {cum[-1]} "
         f"> its complete count {FLAGSHIP_LEVELS[len(cum) - 1]}")
    return len(done)


# ------------------------------------------------------------------ phases

_PROBE = r"""
import json, os
from raft_tla_tpu.utils import device
dev = device.select_device()         # no --cpu: a TPU, or what JAX_PLATFORMS names
import jax
from raft_tla_tpu.config import Bounds
from raft_tla_tpu.ops import kernels
from raft_tla_tpu.utils import keyset, native, prefetch
sig = kernels.step_signature(
    Bounds(n_servers=3, n_values=2, max_term=2, max_log=1, max_msgs=2,
           max_dup=1), "full", (), ("Server",), None)
gates = {k: v for k, v in sig[5:]}
gates["host_dedup"] = keyset.host_dedup_enabled()
gates["prefetch"] = prefetch.prefetch_enabled()
print(json.dumps({"device": dev, "jax": jax.__version__,
                  "nproc": os.cpu_count(), "gates": gates,
                  "has_native": native.HAS_NATIVE}))
"""


def phase_probe(ctx: Ctx) -> dict:
    """Where are we, and which program will the defaults select there?"""
    info = run_snippet(ctx, "probe", _PROBE, timeout=300)
    ctx.device = info["device"]
    need(info["device"]["platform"] == ctx.platform,
         f"platform is {info['device']['platform']!r}, "
         f"not {ctx.platform!r}")
    need(info["has_native"], "utils.native.HAS_NATIVE is false")
    return info


def phase_flagship(ctx: Ctx, deadline_s: float = 120.0, chunk: int = 4096,
                   min_orbits: int = 1_000_000) -> dict:
    """The flagship window: the reference universe, full Next, SYMMETRY
    Server, four invariants, a 2^22-slot filter in front of the host
    key set, stopped losslessly at the deadline."""
    ev = ctx.path("flagship.events")
    d = run_check(ctx, "flagship", FLAGSHIP_CFG,
                  ["--engine", "ddd", "--chunk", str(chunk),
                   *FLAGSHIP_BOUNDS, "--cap", str(1 << 21),
                   "--deadline", str(deadline_s), "--no-trace",
                   "--events", ev],
                  timeout=deadline_s + 600)
    need(d.rc == 14, f"flagship: exit {d.rc}, want 14 (stopped): "
                     f"{tail(d.out + d.err)}")
    events = jsonl(ev)
    need_platform(ctx, "flagship", events)
    end = last(events, "run_end")
    need(end is not None and end["complete"] is False
         and end["outcome"] == "stopped",
         f"flagship: run_end is {end}")
    need(last(events, "violation") is None, "flagship: a violation event")
    need(end["n_states"] >= min_orbits,
         f"flagship: {end['n_states']} orbits < {min_orbits}")
    n_lvl = check_levels("flagship", end["levels"], complete=False)
    first_seg = next(e for e in events if e["event"] == "segment")
    return {"orbits": end["n_states"], "levels_checked": n_lvl,
            "setup_s": round(first_seg["ts"] - d.t_spawn, 1),
            "wall_s": round(d.wall, 1)}


def cache_dir() -> str:
    """Where the children keep their compile cache on an accelerator
    (serve/sched.enable_compile_cache's rule, restated; the CPU
    rehearsal places it through the variable)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.environ.get("RAFT_TLA_COMPILE_CACHE") \
        or os.path.join(ROOT, ".jax_cache")


def _n_cache_entries() -> int:
    try:
        return len(os.listdir(cache_dir()))
    except OSError:
        return 0


def _ddd_complete(ctx: Ctx, name: str, cfg_path: str, flags, tag: str):
    """One complete ``--engine ddd`` run; returns ``(Done, set-up
    seconds from spawn to the first segment, cache entries added)``."""
    ev = ctx.path(f"{name}.{tag}.events")
    if os.path.exists(ev):
        os.remove(ev)
    before = _n_cache_entries()
    d = run_check(ctx, f"{name}.{tag}", cfg_path,
                  ["--engine", "ddd", *flags, "--events", ev])
    need(d.rc == 0, f"{name} ({tag}): exit {d.rc}: {tail(d.out + d.err)}")
    events = jsonl(ev)
    need_platform(ctx, name, events)
    first_seg = next(e for e in events if e["event"] == "segment")
    return (d, round(first_seg["ts"] - d.t_spawn, 1),
            _n_cache_entries() - before)


def phase_complete(ctx: Ctx, spaces=COMPLETE_SPACES) -> dict:
    """Complete spaces against the plain reference: the device engine on
    the chip and the pure-Python oracle on the CPU must print the same
    states, diameter, transitions and verdict line."""
    got = {}
    for name, cfg_text, flags, pin in spaces:
        cfg = ctx.write(name + ".cfg", cfg_text)
        d, setup_s, grew = _ddd_complete(ctx, name, cfg, flags, "cold")
        ref = run_check(ctx, name + ".ref", cfg, ["--engine", "ref", *flags],
                        env=cpu_env())
        need(ref.rc == 0, f"{name}: reference exit {ref.rc}: "
                          f"{tail(ref.out + ref.err)}")
        a, b = parse_result(name, d), parse_result(name + ".ref", ref)
        need(a == b, f"{name}: ddd {a} != ref {b}")
        need(a[:2] == pin, f"{name}: {a[:2]} != pinned {pin}")
        need(_OK_LINE in d.out and _OK_LINE in ref.out,
             f"{name}: verdict lines differ")
        got[name] = {"states": a[0], "diameter": a[1], "transitions": a[2],
                     "setup_s": setup_s, "wall_s": round(d.wall, 1),
                     "cache_added": grew}
    return got


def phase_warm(ctx: Ctx, cold: dict, spaces=COMPLETE_SPACES) -> dict:
    """Second process, warm cache: the complete-space runs again.  The
    cache directory gains no entry, and set-up (spawn to first segment)
    drops against ``cold`` = phase_complete's readings — unless the cold
    run added nothing either (a cache placed from outside came warm)."""
    got = {}
    for name, _cfg_text, flags, _pin in spaces:
        need(_n_cache_entries() > 0,
             f"no compile cache entries under {cache_dir()}")
        d, setup_s, grew = _ddd_complete(ctx, name, ctx.path(name + ".cfg"),
                                         flags, "warm")
        need(grew == 0, f"{name}: warm run added {grew} cache entries "
                        f"under {cache_dir()}")
        was = cold[name]
        if was["cache_added"]:
            need(setup_s < was["setup_s"],
                 f"{name}: warm set-up {setup_s}s did not drop below the "
                 f"cold {was['setup_s']}s")
        got[name] = {"cold_setup_s": was["setup_s"], "warm_setup_s": setup_s,
                     "cold_wall_s": was["wall_s"],
                     "warm_wall_s": round(d.wall, 1),
                     "cold_cache_added": was["cache_added"]}
    return got


_REPLAY = r"""
import json, re, sys
from raft_tla_tpu.config import Bounds
from raft_tla_tpu.models import interp, invariants
from raft_tla_tpu.utils.render import render_state
out_path, bounds_json, spec, inv = sys.argv[1:5]
bounds = Bounds(**json.loads(bounds_json))
text = open(out_path, encoding="utf-8").read()
blocks = re.split(r"^State \d+: <.*>$", text, flags=re.M)[1:]
blocks = [b.strip("\n").split("\n\n")[0] for b in blocks]
cur = interp.init_state(bounds)
assert blocks and render_state(cur, bounds) == blocks[0], "State 1 is not Init"
for k, want in enumerate(blocks[1:], start=2):
    nxt = [t for _i, t in interp.successors(cur, bounds, spec=spec)
           if render_state(t, bounds) == want]
    assert nxt, f"State {k} is not a successor of State {k - 1}"
    cur = nxt[0]
assert not invariants.py_invariant(inv)(cur, bounds), "last state satisfies " + inv
print(json.dumps({"steps": len(blocks)}))
"""


def phase_counterexample(ctx: Ctx, case=COUNTEREXAMPLE) -> dict:
    """A violation found on the chip, its rendered trace replayed step
    by step through the interpreter (tests/test_ddd_engine's check)."""
    name, cfg_text, flags, bounds, spec, inv = case
    cfg = ctx.write(name + ".cfg", cfg_text)
    ev = ctx.path(name + ".events")
    d = run_check(ctx, name, cfg, ["--engine", "ddd", *flags, "--events", ev])
    need(d.rc == 12, f"{name}: exit {d.rc}, want 12 (violation): "
                     f"{tail(d.out + d.err)}")
    need_platform(ctx, name, jsonl(ev))
    need(f"Error: Invariant {inv} is violated." in d.out,
         f"{name}: no violation line")
    rep = run_snippet(ctx, name + ".replay", _REPLAY,
                      [ctx.path(name + ".out"), json.dumps(bounds), spec,
                       inv], env=cpu_env())
    return {"trace_states": rep["steps"], "wall_s": round(d.wall, 1)}


def phase_serve(ctx: Ctx, jobs=SERVE_JOBS, chunk: int = 1024) -> dict:
    """A mixed manifest through the serve front: lane-packed bins, one
    admission reject, results.jsonl equal to the pins."""
    manifest = ctx.write("serve.jobs.jsonl", "".join(
        json.dumps(job) + "\n" for job, _pin in jobs))
    out_dir = ctx.path("serve-out")
    res = os.path.join(out_dir, "results.jsonl")
    if os.path.exists(res):
        os.remove(res)
    d = run(ctx, "serve", [sys.executable, "-m", "raft_tla_tpu.serve",
                           manifest, "--out", out_dir,
                           "--chunk", str(chunk)])
    need(d.rc == 0, f"serve: exit {d.rc}: {tail(d.out + d.err)}")
    recs = {r["job_id"]: r for r in jsonl(res)}
    for job, pin in jobs:
        rec = recs.get(job["id"])
        need(rec is not None, f"serve: no record for {job['id']}")
        for k, v in pin.items():
            need(rec.get(k) == v,
                 f"serve: {job['id']}.{k} = {rec.get(k)!r}, pinned {v!r}")
        if pin["status"] == "completed":
            need_platform(ctx, "serve:" + job["id"],
                          jsonl(rec["events"]))
    return {"wall_s": round(d.wall, 1)}


def phase_campaign(ctx: Ctx, extra=()) -> dict:
    """The toy election under the campaign supervisor with the DEFAULT
    mesh plan: the supervisor must learn the device count without
    holding the device its child needs."""
    cfg = ctx.write("toy.cfg", CFG_TOY)
    work = ctx.path("campaign")
    shutil.rmtree(work, ignore_errors=True)
    d = run(ctx, "campaign",
            [sys.executable, "-m", "raft_tla_tpu.campaign", cfg,
             "--workdir", work, *TOY_FLAGS, "--window", "128",
             "--chunk", "32", "--checkpoint-every", "0", *extra])
    need(d.rc == 0, f"campaign: exit {d.rc}: {tail(d.out + d.err)}")
    need("campaign ok: 3014 states across 1 attempt(s)" in d.out,
         f"campaign: verdict line missing: {tail(d.out)}")
    need_platform(ctx, "campaign",
                  jsonl(os.path.join(work, "run.events")))
    return {"wall_s": round(d.wall, 1)}


_KERNELS = r"""
import json, sys
import numpy as np
from raft_tla_tpu.utils import device
dev = device.select_device()
import jax, jax.numpy as jnp
from raft_tla_tpu.config import Bounds
from raft_tla_tpu.models import interp
from raft_tla_tpu.ops import fingerprint as fpr
from raft_tla_tpu.ops import pallas_fp
chunk, interpret = int(sys.argv[1]), sys.argv[2] == "interpret"
bounds = Bounds(n_servers=3, n_values=2, max_term=2, max_log=1, max_msgs=2,
                max_dup=1)
spec = "full"
pool, frontier, seen = [], [interp.init_state(bounds)], set()
for _ in range(2):                      # reachable rows, depth <= 2
    nxt = []
    for s in frontier:
        for _i, t in interp.successors(s, bounds, spec=spec):
            if t not in seen and interp.constraint_ok(t, bounds):
                seen.add(t)
                nxt.append(t)
    frontier = nxt
    pool += nxt
rows = np.stack([interp.to_vec(s, bounds) for s in pool])
vecs = jnp.asarray(np.tile(rows, (-(-chunk // len(rows)), 1))[:chunk])
out = {"device": dev}

def attempt(name, build, reference):
    try:
        got = jax.device_get(build())
    except Exception as e:              # the compiler's verdict IS the result
        out[name] = {"mosaic_ok": False,
                     "error": f"{type(e).__name__}: {e}"[:1500]}
        return
    ref = jax.device_get(reference())
    same = jax.tree.all(jax.tree.map(np.array_equal, got, ref))
    out[name] = {"mosaic_ok": True, "bit_equal": bool(same)}

attempt("pallas_fp",
        lambda: pallas_fp.fingerprint_rows(vecs, interpret=interpret),
        lambda: fpr.fingerprint(vecs, jnp.asarray(
            fpr.lane_constants(vecs.shape[1])), jnp))
print(json.dumps(out))
"""


def phase_kernels(ctx: Ctx, chunk: int = 4096,
                  interpret: bool = False) -> dict:
    """Does the Pallas fingerprint kernel compile for this chip?  Mosaic
    taking it obliges it to be bit-equal to its XLA twin on one flagship-
    shaped chunk.  Either verdict passes and is printed."""
    got = run_snippet(ctx, "kernels", _KERNELS,
                      [str(chunk), "interpret" if interpret else "mosaic"],
                      timeout=900)
    need(got["device"]["platform"] == ctx.platform,
         f"kernels: ran on {got['device']['platform']!r}")
    if got["pallas_fp"]["mosaic_ok"]:
        need(got["pallas_fp"]["bit_equal"],
             "pallas_fp: Mosaic build is not bit-equal to its XLA twin")
    return got


def phase_multichip(ctx: Ctx, n_devices: int = 4, chunk: int = 4096,
                    stop_at: int = 200_000, extra=()) -> dict:
    """``--engine ddd-shard`` on a short flagship window: stopped by one
    SIGINT at a window boundary, every completed level equal to the
    single-chip table, the carry spread over ``n_devices`` distinct
    devices (the engine refuses to start otherwise and states the count
    in run_start)."""
    have = ctx.device["count"]          # what the probe child saw
    if have < n_devices:
        print(f"multichip: skipped ({have} device)")
        return {"skipped": True, "devices": have}
    ev = ctx.path("multichip.events")
    if os.path.exists(ev):
        os.remove(ev)

    def reached():
        seg = last(jsonl(ev), "segment")
        return seg is not None and seg["n_states"] >= stop_at

    d = run_check(ctx, "multichip", FLAGSHIP_CFG,
                  ["--engine", "ddd-shard", "--devices", str(n_devices),
                   "--chunk", str(chunk), *FLAGSHIP_BOUNDS,
                   "--cap", str(1 << 21), "--no-trace", "--events", ev,
                   *extra], sigint_when=reached)
    need(d.rc == 14, f"multichip: exit {d.rc}, want 14 (stopped): "
                     f"{tail(d.out + d.err)}")
    events = jsonl(ev)
    need_platform(ctx, "multichip", events)
    start, end = last(events, "run_start"), last(events, "run_end")
    need(start.get("n_devices") == n_devices,
         f"multichip: run_start.n_devices = {start.get('n_devices')}")
    need(end is not None and end["complete"] is False,
         f"multichip: run_end is {end}")
    n_lvl = check_levels("multichip", end["levels"], complete=False)
    return {"devices": n_devices, "orbits": end["n_states"],
            "levels_checked": n_lvl, "wall_s": round(d.wall, 1)}


# -------------------------------------------------------------------- main


def main() -> int:
    ctx = Ctx()
    readings: dict = {}
    failed: list = []

    def phase(name, fn, *args):
        t0 = time.monotonic()
        try:
            readings[name] = fn(ctx, *args)
        except Failed as e:
            failed.append(name)
            print(f"[{name}] FAILED after {time.monotonic() - t0:.1f}s: "
                  f"{e}", flush=True)
            return None
        print(f"[{name}] ok {time.monotonic() - t0:.1f}s "
              f"{json.dumps(readings[name])}", flush=True)
        return readings[name]

    if phase("probe", phase_probe) is None:
        return 1                        # no chip: nothing else can pass
    # complete-space first: its programs must meet a cold cache for the
    # warm phase to measure anything
    cold = phase("complete", phase_complete)
    phase("flagship", phase_flagship)
    phase("counterexample", phase_counterexample)
    phase("serve", phase_serve)
    phase("campaign", phase_campaign)
    if cold is not None:
        phase("warm", phase_warm, cold)
    phase("kernels", phase_kernels)
    phase("multichip", phase_multichip)
    ctx.write("summary.json", json.dumps(
        {"device": ctx.device, "failed": failed, "readings": readings},
        indent=1, sort_keys=True) + "\n")
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": ctx.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
