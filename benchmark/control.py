#!/usr/bin/env python3
"""Run a cell with one guarantee broken and see ``correct`` come out false.

    python3 benchmark/control.py --workload NAME --seeds 1,2,3 [--seconds 0] \
        --control key32|filter_only|invariants_off|misroute|drops_last_level

Not part of a benchmark run.  ``misroute`` is a mesh cell's control (a cell
on one chip has no exchange to misroute, and passes it); ``drops_last_level``
is the control of a cell whose passes run to their own end (a pass stopped at
a pin never reaches the level it drops, and passes it).  One process, one
run per seed at the cell's own size (the traffic's minimum number of passes
when --seconds is 0).
Exits 0 when every seed's run was refused, 1 when one passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.harness import breakers
    from benchmark.harness import manifest as mf
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True, choices=sorted(breakers.CONTROLS))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    manifest = mf.load()
    cell = mf.cell(manifest, args.workload)
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        with breakers.CONTROLS[args.control]():
            result = run.execute(cell, manifest, seed, args.seconds, False)
        print(f"control {args.control} workload {args.workload} seed {seed}: "
              f"correct={result['correct']} {json.dumps(result['metrics'])}",
              flush=True)
        passed += bool(result["correct"])
    print(f"control {args.control}: {passed} run(s) passed that should not "
          "have" if passed else f"control {args.control}: every run refused")
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
