"""Main-thread wall in ``dedup`` spans under levels A+1..B of the traced
pass: the exact key set's merge and the row store's appends done inline at a
level's close (the flush worker takes a batch only at ``flush`` pending keys),
with nothing dispatched to the device meanwhile."""

from benchmark.harness import depthred


def read(ev):
    red = depthred.of(ev)
    return red and red["dedup_inline_s"]
