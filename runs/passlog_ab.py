"""What the pass ledger costs a pass, inside one process (ISSUE 38).

Two runs of one program differ by 1-2 % in their host side whatever the code
(the run-level mode, PERF.md section 6, PR 30), so parent against change, run
against run, cannot see a cost of 60 us a level.  This drives the benchmark's
own ``Driver`` (one engine object, the cell's own untraced passes) and
alternates the passes A B B A between two arms:

- ``ledger``: the program as it is: every ``check()`` keeps its record;
- ``none``: the same program with a sink that reads no span, so that every
  site the ledger times is the null handle again (what the parent's untraced
  pass ran) and ``EngineResult.level_log`` is ``None``.

Printed: one JSON line with each arm's passes, the median and quartiles of
the pass's wall (call -> return) and of its ramp, and ledger over none.

Usage (on the chip, through the chip tool):

    python3 runs/passlog_ab.py WORKLOAD SECONDS

``WORKLOAD`` is a cell of BENCHMARK.json; ``toy`` is the selftest's toy cell
on whatever JAX_PLATFORMS names (a rehearsal of this script, no measurement).
"""

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class NoLog:
    """A sink that reads nothing: ``SpanTracer.wants`` is False at every
    site, and the result carries no record."""

    NAMES = frozenset()
    record = None

    def __init__(self, *_a, **_kw):
        pass


def _arm(ps: list) -> dict:
    walls = [p.t_return - p.t_call for p in ps]
    ramps = [p.ramp_s for p in ps if p.ramp_s is not None]
    out = {"passes": len(ps), "wall_med": statistics.median(walls),
           "wall_q": statistics.quantiles(walls, n=4)}
    if len(ramps) > 1:
        out.update(ramp_med=statistics.median(ramps),
                   ramp_q=statistics.quantiles(ramps, n=4))
    return out


def main(argv) -> int:
    workload, seconds = argv[1], float(argv[2])
    from benchmark import run, selftest
    from benchmark.harness import drive
    from benchmark.harness import manifest as mf
    from raft_tla_tpu.obs import passlog
    toy = workload == "toy"
    cell = selftest.toy_cell() if toy else mf.cell(mf.load(), workload)
    dev = drive.open_device(cell["chips"], rehearsal=toy)
    drive.enable_cache(dev["platform"])
    drv = drive.Driver(cell, drive.scratch_dir(cell["name"]))
    warm = drv.run_pass(end_level=run.WARM_END_LEVEL, start_level=1)
    drv.build_snapshot()
    print(f"device {dev['kind']!r} warm {warm.t_return - warm.t_call:.3f}s "
          f"problem={warm.problem}", flush=True)
    arms = {"ledger": passlog.PassLog, "none": NoLog}
    made = {name: [] for name in arms}
    t_end = time.monotonic() + seconds
    try:
        while time.monotonic() < t_end or len(made["none"]) < 2:
            for name in ("ledger", "none", "none", "ledger"):
                passlog.PassLog = arms[name]
                p = drv.timed_pass()
                if p.problem is not None:
                    print(f"pass {p.index} ({name}) FAILED: {p.problem}",
                          flush=True)
                    return 1
                made[name].append(p)
    finally:
        passlog.PassLog = arms["ledger"]
    out = {"cell": cell["name"], "device": dev["kind"],
           **{name: _arm(ps) for name, ps in made.items()}}
    out["wall_ledger_over_none_pct"] = 100.0 * (
        out["ledger"]["wall_med"] / out["none"]["wall_med"] - 1.0)
    if "ramp_med" in out["ledger"]:
        out["ramp_ledger_over_none_ms"] = 1e3 * (
            out["ledger"]["ramp_med"] - out["none"]["ramp_med"])
    held = passlog.snapshot()
    out["records_held"] = len(held["records"]) + held["dropped"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
