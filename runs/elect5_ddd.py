"""BASELINE config #2 campaign, round-2 DDD attempt: 5-server election,
t2/m2, SYMMETRY Server — exhaustive, with no fingerprint-table ceiling.

The streamed-engine v3 run reached 131.3M orbits into level 26 before the
2^28 device-table ceiling (and a wedged chip) ended it; its checkpoint
did not survive the environment reset.  This restarts the space on the
DDD engine, whose exact dedup lives in host RAM (~15B-state capacity).

Usage: python runs/elect5_ddd.py [resume] [--seg-rows E] [--route K] [--cpu]
(--seg-rows E sets DDDCapacities.seg_rows = 2**E -- checkpoint-compatible.)
Checkpoints at runs/elect5ddd.ckpt every 15 min; stats stream appended to
runs/elect5ddd.stats (one JSON line per flush/level); run-event log
appended to runs/elect5ddd.events (tail it live with raft-tla-monitor).  ``--route K``
switches to the EP-routed step (DDDCapacities.route_rows=K) —
checkpoint-compatible either way (tests/test_ddd_engine.py::
test_routed_checkpoint_crosses_step_switch).
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine

RUNS = os.path.dirname(os.path.abspath(__file__))
CKPT = os.path.join(RUNS, "elect5ddd.ckpt")
STATS = os.path.join(RUNS, "elect5ddd.stats")
EVENTS = os.path.join(RUNS, "elect5ddd.events")

CFG = CheckConfig(
    bounds=Bounds(n_servers=5, n_values=2, max_term=2, max_log=0,
                  max_msgs=2, max_dup=1),
    spec="election",
    invariants=("NoTwoLeaders", "CommittedWithinLog"),
    symmetry=("Server",), chunk=4096)

# retention="frontier" (round 4): master keys in RAM (8 B/orbit), rows
# in disk-backed current+next level files, no trace links — the TLC
# campaign regime.  Lifts the ~1.5e9 RAM/disk ceilings the full-
# retention resume was dying under (73 GB RSS at 983M orbits) to ~7e9.
CAPS = DDDCapacities(block=1 << 20, table=1 << 22, seg_rows=1 << 19,
                     flush=1 << 23, levels=1 << 12, retention="frontier")


def main():
    args = sys.argv[1:]
    if "--cpu" in args:          # resume-path validation without a chip
        import argparse

        from raft_tla_tpu.check import _force_cpu
        _force_cpu(argparse.Namespace(cpu=True, devices=0))
        args.remove("--cpu")
    if "--seg-rows" in args:     # checkpoint-compatible dispatch sizing
        k = args.index("--seg-rows")
        if k + 1 >= len(args) or not args[k + 1].isdigit() \
                or not 15 <= int(args[k + 1]) <= 26:
            sys.exit("usage: elect5_ddd.py [resume] [--seg-rows E] "
                     "[--route K] [--cpu]  (E = log2 of the segment row "
                     "budget, 15-26; default 19)")
        global CAPS
        CAPS = dataclasses.replace(CAPS, seg_rows=1 << int(args[k + 1]))
        del args[k:k + 2]
    route = 0
    if "--route" in args:
        k = args.index("--route")
        if k + 1 >= len(args) or not args[k + 1].isdigit():
            sys.exit("usage: elect5_ddd.py [resume] [--route K] [--cpu]  "
                     "(K = routed candidate slots per chunk, integer)")
        route = int(args[k + 1])
        del args[k:k + 2]
    caps = dataclasses.replace(CAPS, route_rows=route) if route else CAPS
    resume = CKPT if args and args[0] == "resume" else None
    sf = open(STATS, "a", buffering=1)

    def on_progress(s):
        sf.write(json.dumps(s) + "\n")

    eng = DDDEngine(CFG, caps)
    r = eng.check(on_progress=on_progress, checkpoint=CKPT,
                  checkpoint_every_s=900.0, resume=resume,
                  events=EVENTS)
    print(json.dumps({
        "n_states": r.n_states, "diameter": r.diameter,
        "n_transitions": r.n_transitions, "complete": r.complete,
        "violation": r.violation.invariant if r.violation else None,
        "levels": r.levels, "wall_s": round(r.wall_s, 1),
    }))


if __name__ == "__main__":
    main()
