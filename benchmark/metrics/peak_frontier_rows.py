"""The largest frontier the traced pass expanded (the most ``rows`` of a
``level`` span): a guard on how much of a block the space's peak fills."""

from benchmark.harness import tailred


def read(ev):
    red = tailred.of(ev)
    return red and red["peak_frontier_rows"]
