"""Median over the untraced sound passes of the stamp at B -> ``check()``
returns: what the engine still does once asked to stop (the ddd engine ends
at its next segment boundary, the mesh engine only after the window it is
in), all of it inside the window that ``orbits_per_s`` divides by."""

import statistics


def read(ev):
    over = [p.overshoot_s for p in ev["passes"]
            if not p.traced and p.problem is None
            and p.overshoot_s is not None]
    return statistics.median(over) if over else None
