"""Wall of ``dedup`` spans on the ``raft-tla-flush`` thread over the whole
clocked span A->B of the traced pass: the host key set's worker busy at depth
(``flush_busy_s`` reads the traced level A->A+1 alone).  0.0 where the worker
was handed no batch (every ``dedup`` of the span ran inline on the main
thread); nothing to read where the pass's log holds no span at all: a
program without them."""

from benchmark.harness import spanred


def read(ev):
    p = spanred.traced_pass(ev)
    if p is None or p.t_b is None:
        return None
    spans = spanred.load(p.events)
    if not spans:
        return None
    return spanred.clipped_wall(spans, "dedup", spanred.FLUSH, p.t_a, p.t_b)
