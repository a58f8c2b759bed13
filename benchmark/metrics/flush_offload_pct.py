"""Share of the host key set's merge that ran beside the device: over the
whole clocked span A->B of the traced pass, 100 x the wall of ``dedup`` spans
on the ``raft-tla-flush`` thread over that plus the wall of ``dedup`` spans on
the main thread (the inline merge at a level's close, nothing dispatched
meanwhile).  0.0 where the worker was handed no batch: no level of the span
had a harvest with device work of its level behind it.  It counts where the
merge ran, not whether it was hidden: a hand-over that the level close then
waits for (``dedup_wait``) counts in full, so read it beside the main thread's
``dedup_wait`` wall and ``orbits_per_s``.  Nothing to read where
the pass's log holds no span at all (a program without them), or no ``dedup``
span inside the clocked span."""

from benchmark.harness import spanred


def read(ev):
    p = spanred.traced_pass(ev)
    if p is None or p.t_b is None:
        return None
    spans = spanred.load(p.events)
    if not spans:
        return None
    worker = spanred.clipped_wall(spans, "dedup", spanred.FLUSH, p.t_a, p.t_b)
    inline = spanred.clipped_wall(spans, "dedup", spanred.MAIN, p.t_a, p.t_b)
    if worker + inline <= 0.0:
        return None
    return 100.0 * worker / (worker + inline)
