"""Median wall of ``level_close`` spans over all levels of the traced pass:
the flush drain, the progress record, the store rotation."""

from benchmark.harness import spanred


def read(ev):
    red = spanred.of(ev)
    return red and red["level_close_ms"]
