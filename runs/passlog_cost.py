"""What the pass ledger costs a level, on the host (ISSUE 38).

The ddd level loop's sites, driven empty: per level one ``tr.open("level")``,
one ``upload`` phase, two segments (``expand`` phase, ``export`` phase >
``segment_wait`` and ``d2h`` spans), one ``level_close`` span around a
``dedup`` phase, and ``end_level()``'s ``set(...).close()`` — once through a
``RunTelemetry`` that keeps the ledger (what every ddd pass now does with
tracing off) and once through one that keeps none (the null handles: what the
sites cost before).  The difference is the ledger: its clock reads, one
``getrusage(RUSAGE_THREAD)`` at each end of a level and one small dict.
Also printed: the mean ``gap_s`` (one level's close -> the next one's open,
which holds the close's own bookkeeping), what one ``getrusage`` and one
``time.monotonic()`` cost on this host, and the smallest step the thread's
CPU clock shows (10 ms on the v5e machines' host).

Usage: python runs/passlog_cost.py [levels]   (default 100000; no JAX, no chip)
"""

import json
import os
import resource
import sys
import time
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from raft_tla_tpu.obs.events import RunTelemetry

SEGMENTS = 2


def drive(levels: int, level_log: bool) -> tuple:
    """``(seconds, record or None)`` of ``levels`` empty levels."""
    tel = RunTelemetry("ddd", level_log=level_log, t0=time.monotonic())
    tr, phases = tel.trace, tel.phases
    pass_sp = tr.open("pass", engine="ddd", resumed=False)
    t0 = time.perf_counter()
    for k in range(levels):
        level_sp = tr.open("level", level=k + 1, rows=1, blocks=1)
        with phases.phase("upload") as ph:
            ph.set(rows=1, padded_rows=1, bytes=1)
        for _ in range(SEGMENTS):
            with phases.phase("expand") as ph:
                ph.sync(None)
            with phases.phase("export"):
                with tr.span("segment_wait"):
                    pass
                with tr.span("d2h", rows=1, bytes=1):
                    pass
        with tr.span("level_close"):
            with phases.phase("dedup") as ph:
                if tr.enabled:
                    ph.set(keys=0)
        level_sp.set(segments=SEGMENTS, steps=1, streamed_rows=1,
                     new_states=1).close()
    dt = time.perf_counter() - t0
    pass_sp.set(levels=levels, n_states=levels).close()
    tel.close()
    return dt, tel.passlog.record if level_log else None


def main() -> int:
    levels = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    drive(1000, True)                   # warm both paths
    drive(1000, False)
    off, _ = drive(levels, False)
    on, rec = drive(levels, True)
    tiled = sum(lv["gap_s"] + lv["wall_s"] for lv in rec["levels"]) \
        + rec["head_s"] + rec["tail_s"]
    who = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)

    def cpu():
        ru = resource.getrusage(who)
        return ru.ru_utime + ru.ru_stime

    ticks, t_end = {cpu()}, time.monotonic() + 0.2
    while time.monotonic() < t_end:
        ticks.add(cpu())
    ticks = sorted(ticks)
    print(json.dumps({
        "levels": levels, "segments_a_level": SEGMENTS,
        "null_us_a_level": 1e6 * off / levels,
        "ledger_us_a_level": 1e6 * on / levels,
        "ledger_cost_us_a_level": 1e6 * (on - off) / levels,
        "gap_us_a_level": 1e6 * sum(lv["gap_s"] for lv in rec["levels"])
        / levels,
        "untiled_s": rec["wall_s"] - tiled,
        "getrusage_us": 1e6 * timeit.timeit(
            lambda: resource.getrusage(who), number=20000) / 20000,
        "monotonic_us": 1e6 * timeit.timeit(time.monotonic,
                                            number=20000) / 20000,
        "cpu_clock_step_us": 1e6 * min(
            (b - a for a, b in zip(ticks, ticks[1:])), default=0.0),
        "nproc": os.cpu_count()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
