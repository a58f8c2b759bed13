"""Pass ledger: main-thread wall in ``upload`` over the number of uploads, in
levels A+1..B of the sound untraced passes — the untraced twin of
``upload_wait_ms``, which reads the first h2d after ``start_trace``."""

from benchmark.harness import levelred


def read(ev):
    red = levelred.of(ev)
    return red and red["upload_untraced_ms"]
