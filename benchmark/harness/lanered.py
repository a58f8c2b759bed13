"""How full the dense step is, from the traced pass's ``level`` spans.

The fused step computes every lane of a chunk (``chunk * A`` of them a step)
whether the lane's action is enabled or not.  Since PR 26 a ``level`` span
carries, beside ``steps`` and ``stream_slabs`` (PR 25), the work counts the
harvest already fetched with each segment's stats: ``lanes`` (chunk steps x
chunk x A), ``n_valid`` (the enabled lanes among them) and ``route_peak``
(the most enabled lanes any one chunk step had).  This module sums them over
the whole traced pass, ramp included, and imports nothing of the program.

A program older than a count leaves it off its spans; the reduction then
holds ``None`` there and the reader reports nothing.
"""

from __future__ import annotations

import json

from benchmark.harness import spanred


def _over(levels: list, key: str, fold=sum):
    have = [a[key] for a in levels if key in a]
    return fold(have) if have else None


def reduce(spans: list) -> dict | None:
    """Sums and the maximum over the log's ``level`` spans; ``None`` where
    it has none."""
    levels = [s["args"] for s in spans if s["name"] == "level"]
    if not levels:
        return None
    return {"levels": len(levels),
            "steps": _over(levels, "steps"),
            "lanes": _over(levels, "lanes"),
            "n_valid": _over(levels, "n_valid"),
            "stream_slabs": _over(levels, "stream_slabs"),
            "route_peak": _over(levels, "route_peak", max)}


def ratio(red: dict | None, num: str, den: str):
    """``red[num] / red[den]``, or ``None`` where either is missing or
    the denominator is 0."""
    if not red or red[num] is None or not red[den]:
        return None
    return red[num] / red[den]


def of(ev: dict) -> dict | None:
    """The reduction of this run's traced pass (computed once a run and
    kept on the evidence; prints its one line the first time)."""
    if "lanered" not in ev:
        p = spanred.traced_pass(ev)
        red = reduce(spanred.load(p.events)) if p is not None else None
        ev["lanered"] = red
        if red is not None:
            print(f"lane counts pass {p.index}: " + json.dumps(red),
                  flush=True)
    return ev["lanered"]
