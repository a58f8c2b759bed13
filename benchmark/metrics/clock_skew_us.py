"""Median |start by the anchor - start of the span's own annotation| over the
main-thread spans in the capture: the error bar on every idle-gap metric
that places host spans on the device clock through the anchor."""

from benchmark.harness import stagered


def read(ev):
    red = stagered.of(ev)
    return red["skew"]["median_us"] if red and red["skew"] else None
