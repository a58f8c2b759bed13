"""Wall-clock to a verdict: median, over the run's sound untraced passes
that ran to their own end, of ``check()``'s call -> its return with
``complete = True`` (ramp, peak, tail, the empty last expansion, the final
flush and the result).  A pass stopped at a pin has no verdict."""

import statistics


def read(ev):
    walls = [p.verdict_s for p in ev["passes"]
             if not p.traced and p.problem is None
             and p.verdict_s is not None]
    return statistics.median(walls) if walls else None
