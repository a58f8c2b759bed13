"""Median per-level wall in ``segment_wait`` spans over levels 1..A-1 of the
traced pass: the host blocked on the level's segments (device time)."""

from benchmark.harness import spanred


def read(ev):
    red = spanred.of(ev)
    return red and red["ramp_segment_ms"]
