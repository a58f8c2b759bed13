"""Pass ledger: median over the sound untraced passes and levels 1..A-1 of
``wall_s - wait_s`` — the host's fixed cost a level as a user pays it, with
tracing off (its traced twin: ``ramp_level_ms - ramp_segment_ms``)."""

from benchmark.harness import levelred


def read(ev):
    red = levelred.of(ev)
    return red and red["level_host_ms"]
