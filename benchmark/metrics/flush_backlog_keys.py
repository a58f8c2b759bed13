"""Most keys waiting behind the flush worker when a batch was handed to it
(max ``backlog`` on the ``dedup_submit`` spans under levels A+1..B of the
traced pass).  Nothing to read where no batch was handed over: a level that
streams fewer rows than the engine's ``flush`` is flushed inline at its
close."""

from benchmark.harness import depthred


def read(ev):
    red = depthred.of(ev)
    return red and red["flush_backlog_max"]
