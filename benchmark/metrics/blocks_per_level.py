"""Frontier blocks a level of the clocked span took (``blocks`` of the traced
pass's ``level`` spans, levels A+1..B, averaged): 1 while no level fills a
block."""

from benchmark.harness import depthred


def read(ev):
    red = depthred.of(ev)
    if not red or red["blocks"] is None:
        return None
    return red["blocks"] / red["levels"]
