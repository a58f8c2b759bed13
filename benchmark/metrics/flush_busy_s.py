"""Wall of ``dedup`` spans on the ``raft-tla-flush`` thread inside the traced
window A->A+1: the host key set's time busy."""

from benchmark.harness import spanred


def read(ev):
    red = spanred.of(ev)
    return red and red["flush_busy_s"]
