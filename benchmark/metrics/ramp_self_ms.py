"""Median self time of the ``level`` spans over levels 1..A-1 of the traced
pass: level-loop time that no child span covers (Python in between)."""

from benchmark.harness import spanred


def read(ev):
    red = spanred.of(ev)
    return red and red["ramp_self_ms"]
