"""Median, over the run's untraced sound resumed passes, of call ->
``run_start``: the snapshot's streams read back into the stores and the key
set rebuilt, before the first upload."""

import statistics


def read(ev):
    walls = [p.ramp_s for p in ev["passes"]
             if p.resumed and not p.traced and p.problem is None
             and p.ramp_s is not None]
    return statistics.median(walls) if walls else None
