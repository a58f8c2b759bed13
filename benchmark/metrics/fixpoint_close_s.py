"""Median, over the sound untraced passes that ran to their own end, of the
first record at the space's total -> ``check()`` returns: the last frontier
expanded to nothing, the final drain and flush, the result assembled."""

import statistics


def read(ev):
    walls = [p.close_s for p in ev["passes"]
             if not p.traced and p.problem is None
             and p.close_s is not None]
    return statistics.median(walls) if walls else None
