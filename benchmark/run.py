#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

A run opens the TPU (no chip, no run), builds the cell's engine, warms its
shapes with a short pass and, where the traffic starts at depth, builds the
snapshot its passes resume from (all of that is ``setup_s``), then makes whole
passes (Init, or the snapshot, to the cell's pinned level B) for S seconds of
run time, at least the traffic's ``min_passes``.  ``orbits_per_s`` is every
orbit those passes admitted over the whole window; every pass's own numbers
(ramp or resume, the clocked A->B span, overshoot) are printed on earlier
lines.  Where the traffic says ``"end": "fixpoint"`` nothing stops a pass:
``check()`` runs to the level that admits nothing and returns its verdict, and
``verdict_wall_s`` is the median call -> return of the sound untraced passes.
After the window it decides ``correct`` and prints the contract's JSON object
last.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def process_age_s() -> float:
    """Seconds since this process was started (interpreter start-up and
    imports included), from /proc; 0 where that cannot be read."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_START = time.monotonic() - process_age_s()
WARM_END_LEVEL = 3


def say(msg: str) -> None:
    print(msg, flush=True)


def rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def execute(cell: dict, manifest: dict, seed: int, seconds: float,
            trace: bool, rehearsal: bool = False) -> dict:
    """The run.  Returns the result object (and prints the lines before it).
    ``rehearsal`` skips the look for a chip and nothing else."""
    from benchmark.harness import (correct, drive, levelred, passes,
                                   tracered, work)
    from benchmark.harness import manifest as mf

    # the family and the engine the configuration names, refused by name
    # before any device work where there is none or the cell cannot hold it
    try:
        mf.family(cell["config_data"])
        engine_name, ndev = mf.engine_of(cell["config_data"],
                                            cell["chips"])
        mf.end_of(cell["traffic_data"], cell["config_data"],
                  cell["traffic"])
    except ValueError as e:
        raise SystemExit(f"benchmark: {e}") from None
    scratch = drive.scratch_dir(cell["name"])
    dev = drive.open_device(cell["chips"], rehearsal=rehearsal)
    t_open = time.monotonic()
    cache = drive.enable_cache(dev["platform"])
    say(f"device platform={dev['platform']} kind={dev['kind']!r} "
        f"count={dev['count']} compile_cache={cache} scratch={scratch} "
        f"peak_rss_mb={rss_mb():.0f}")
    drv = drive.Driver(cell, scratch)
    traffic = drv.traffic
    say(f"engine {engine_name} devices={ndev} "
        f"caps={json.dumps(drv.cfg['engine_caps'][engine_name])}")
    say(f"gates {json.dumps(drv.family.gates(drv.engine, drv.cfg))} "
        f"host_dedup={getattr(drv.engine, '_host_dedup', None)} "
        f"prefetch={getattr(drv.engine, '_prefetch', None)} "
        f"nproc={os.cpu_count()}")
    # warm-up: a short pass compiles (or loads) every program a pass uses
    # (every level runs the same padded shapes, so three levels do)
    warm = drv.run_pass(end_level=WARM_END_LEVEL, start_level=1)
    say(f"warm pass to level {WARM_END_LEVEL}: "
        f"{warm.t_return - warm.t_call:.3f}s compiles={warm.compiles}"
        f" peak_rss_mb={rss_mb():.0f}"
        + (f" PROBLEM {warm.problem}" if warm.problem else ""))
    t_ready = time.monotonic()
    snap = drv.build_snapshot()
    if snap is not None:
        sn = drv.snapshot
        say(f"snapshot at level {sn['level']}: keys={sn['keys']} "
            f"pinned={drv.pins[sn['level']]} built in {sn['build_s']:.3f}s "
            f"({sn['bytes'] / 1e6:.1f} MB under {os.path.dirname(sn['path'])})"
            f" compiles={snap.compiles}; a resumed pass admits "
            f"{drv.pass_orbits} orbits peak_rss_mb={rss_mb():.0f}"
            + (f" PROBLEM {snap.problem}" if snap.problem else ""))

    made = []
    t_first = time.monotonic()
    while True:
        p = drv.timed_pass(trace=trace and len(made) == 1)
        made.append(p)
        rate = p.rate(drv.orbits)
        say(f"pass {len(made)} "
            + (f"rate={rate:.3f} orbits/s " if rate else "rate=none ")
            + f"{'resume_s' if p.resumed else 'ramp_s'}={_f(p.ramp_s)} "
            f"span_s={_f(p.span_s)} "
            f"overshoot_s={_f(p.overshoot_s)} compiles={p.compiles} "
            f"peak_rss_mb={rss_mb():.0f}"
            + (f" verdict_s={_f(p.verdict_s)} close_s={_f(p.close_s)} "
               f"complete={p.complete} levels={len(p.levels)}"
               if p.fixpoint else "")
            + (" traced" if p.traced else "")
            + (f" FAILED: {p.problem}" if p.problem else ""))
        if not passes.room_for_another(
                time.monotonic() - t_first, p.t_return - p.t_call, seconds,
                len(made), traffic["min_passes"]):
            break
    window_s = time.monotonic() - t_first
    win = passes.window_rate(made, drv.pass_orbits, window_s)
    say(f"window {window_s:.3f}s of --seconds {seconds:g}: {len(made)} passes "
        f"to level {drv.b} ({drv.pass_orbits} orbits each, {drv.orbits} of "
        f"them in the clocked span {drv.a}..{drv.b}) rate="
        + (f"{win['rate']:.3f}" if win["rate"] else "none")
        + f" orbits/s ramp_share_pct={_f(win['ramp_share_pct'])}")
    say(f"levels {json.dumps(made[-1].levels)}")

    # -- correct: outside the timed passes, on what they produced ---------
    t_chk = time.monotonic()
    # the warm pass stops early by design and was held to its own prefix
    checks = correct.pass_checks(made, drv.pins, drv.b) + [
        ("warm_pass_problems", int(warm.problem is not None), 0)]
    if snap is not None:
        checks += correct.snapshot_checks(drv.snapshot, made, drv.pins,
                                          drv.b)
    if drv.fixpoint:
        checks += correct.fixpoint_checks(made, drv.pins)
    ref = correct.reference_sample(drv.cfg, seed)
    t_smp = time.monotonic()
    got = drv.expand_sample(ref["parents"])
    say(f"sample: {len(ref['parents'])} reference states through the run's "
        f"segment on {got.get('shards', 1)} shard(s): {len(got['states'])} "
        f"rows streamed in {time.monotonic() - t_smp:.3f}s "
        f"compiles={got['compiles']}")
    checks += correct.sample_checks(ref, got, drv.pins)
    plant = correct.planted_fault(drv.cfg, ref["level"], seed)
    t_plant, n0 = time.monotonic(), drv.compiles.n
    flagged = drv.planted_violation(plant["parent"])
    say(f"planted fault: the engine reported {flagged['invariant']} after "
        f"levels {flagged['levels']} in {time.monotonic() - t_plant:.3f}s "
        f"compiles={drv.compiles.n - n0}")
    checks += correct.planted_checks(plant, flagged)
    is_correct = correct.decide(checks, out=say)
    say(f"correct={is_correct} decided in {time.monotonic() - t_chk:.3f}s "
        "(outside setup_s and outside the passes)")

    sound = [p for p in made if p.problem is None and not p.traced]
    rates = [p.rate(drv.orbits) for p in sound]
    clocks = {"open_s": t_open - T_START, "compile_s": t_ready - t_open,
              "setup_s": t_first - T_START}
    evidence = {
        "clocks": clocks, "passes": made, "rates": rates, "window": win,
        "summary": passes.summarise(rates) if rates else None,
        "orbits": drv.orbits, "trace": None, "device": dev,
        "rss_mb": rss_mb(),
        "hbm_peak_bytes": drive.memory_peak_bytes(),
        "work": _work(drv, work, made),
        "span_levels": [drv.a, drv.b], "snapshot": drv.snapshot,
    }
    # the pass ledger's account of the untraced passes (its level table and,
    # where a pass stalled, the stall's line), whatever --trace is; the five
    # metrics read from it stay in the traced run's result line
    levelred.of(evidence)
    if drv.fixpoint:
        # what a user waits for: set-up, then one check() to its verdict
        verdict = mf.metric_reader("verdict_wall_s")(evidence)
        if verdict is not None:
            say(f"a user's whole wait: setup_s {clocks['setup_s']:.3f} + "
                f"verdict_wall_s {verdict:.3f} = "
                f"{clocks['setup_s'] + verdict:.3f}s (the sum is no metric)")
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": evidence["hbm_peak_bytes"]}
    result = {"correct": is_correct, "attempted": len(made),
              "failed": sum(p.problem is not None for p in made),
              "metrics": {}, "device": device}
    # every number compared, beside its limit: the result line's last key
    compared = {name: {"value": _num(value), "limit": _num(limit)}
                for name, value, limit in checks}
    if rehearsal:
        # a CPU rehearsal writes no number under a device metric's name
        result["rehearsal"] = True
        say("REHEARSAL: not a measurement; no metric is written")
        result["checks"] = compared
        return result
    evidence["peaks"] = mf.peaks(dev["kind"])
    if trace:
        tp = next((p for p in made if p.traced and p.t_trace_end), None)
        if tp is None:
            raise SystemExit("benchmark: the traced pass did not complete")
        red = tracered.reduce(
            tracered.load_xplane(tp.trace_dir, tp.anchor[1]),
            tracered.load_spans(tp.events), tp.anchor[0], tp.t_a,
            tp.t_trace_end)
        evidence["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        say(f"traced pass {tp.index} levels {evidence['work']['traced_levels']}"
            f" steps={evidence['work']['steps']} "
            f"streamed_or_admitted={evidence['work']['traced_orbits']}: "
            f"anchor_mono_ns={tp.anchor[0]} t_a={tp.t_a:.6f} "
            f"t_end={tp.t_trace_end:.6f} window_s={red['window_s']:.6f} "
            f"busy_s={red['busy_s']:.6f} span walls "
            f"{json.dumps(red['span_wall_s'])}")
    kind = "per_layer" if trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in manifest[kind]}
    for name in mf.metric_names(manifest, cell["name"], kind):
        value = mf.metric_reader(name)(evidence)
        if value is not None:
            result["metrics"][name] = {"value": value, "unit": units[name]}
    result["checks"] = compared
    return result


def _f(x) -> str:
    return "none" if x is None else f"{x:.3f}"


def _num(x):
    return x if isinstance(x, (int, float)) else float(x)


def _work(drv, work, made: list) -> dict:
    """Analytic work of the TRACED part of the span: its first level, whose
    chunk steps follow from the pins; of a resumed pass the segments
    harvested inside the step-bounded window, as the program's own
    ``segment`` spans count them (steps, and rows streamed for export)."""
    from benchmark.harness import depthred, spanred
    eng = drv.engine
    te = drv.a + 1
    # a mesh: one SHARD's work a lockstep step (every shard runs the whole
    # dense step on its chunk, then filters what all the shards send it),
    # and a shard's share of the exported stream (keys are owned evenly),
    # to stand over one chip's segment time and one chip's peak
    shards = drv.ndev
    steps = work.chunk_steps(drv.pins, drv.a, te, eng.caps.block,
                             eng.config.chunk, shards)
    exported = (drv.pins[te] - drv.pins[drv.a]) // shards
    tp = next((p for p in made if p.traced and p.resumed
               and p.t_a is not None and p.t_trace_end is not None), None)
    if tp is not None:
        seen = depthred.window_segments(spanred.load(tp.events), tp.t_a,
                                        tp.t_trace_end)
        steps, exported = seen["steps"], seen["streamed_rows"]
    return {
        "traced_levels": [drv.a, te],
        "traced_orbits": exported,
        "steps": steps,
        "words_per_step": drv.family.scan_words(eng),
        "bytes_per_step": work.step_bytes(
            eng.config.chunk, eng.A, eng.schema.P, shards,
            getattr(eng.caps, "send", None)),
        "packed_words": eng.schema.P,
        "shards": shards,
        "chunk": eng.config.chunk,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import raft_tla_tpu  # noqa: F401  (the system under test)
    except ImportError:
        print("benchmark: the program (raft_tla_tpu/) is not in this "
              "checkout; nothing to measure", file=sys.stderr)
        return 3
    from benchmark.harness import manifest as mf
    manifest = mf.load()
    cell = mf.cell(manifest, args.workload)
    result = execute(cell, manifest, args.seed, args.seconds,
                     bool(args.trace))
    say(json.dumps(result))
    # ... and as the last lines on standard error
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr)
    print(f"correct={result['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
