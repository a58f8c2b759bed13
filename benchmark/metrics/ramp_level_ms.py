"""Median wall of the program's ``level`` spans over levels 1..A-1 of the
traced pass: what one small level of the ramp costs, whatever it holds."""

from benchmark.harness import spanred


def read(ev):
    red = spanred.of(ev)
    return red and red["ramp_level_ms"]
