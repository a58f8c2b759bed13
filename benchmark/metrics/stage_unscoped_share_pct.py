"""Share of the segment module's device self time, over the traced level,
that falls under no stage scope (the ``while`` itself, glue between stages)."""

from benchmark.harness import stagered


def read(ev):
    red = stagered.of(ev)
    st = red and red["stages"]
    if not st or not st["scoped"] or not st["total_ns"]:
        return None
    return 100.0 * st["unscoped_ns"] / st["total_ns"]
