"""A pass that ends by itself, from the traced pass's ``level`` spans: the
peak it goes through and the tail it ends in.

A ``level`` span of level L expands the frontier that level L - 1 admitted
(``rows``) and admits ``new_states``.  The **peak** is the largest frontier a
pass expands.  The **tail** is every level after the one that admitted most
whose ``new_states`` are under one chunk: levels that cost a whole chunk step,
an upload and a close each for a handful of rows, deduplicated against the key
set at its largest, down to the empty expansion that ends the search.  Imports
nothing of the program; where the log has no ``level`` span, or a span lacks a
count, the reduction holds ``None`` there and the reader reports nothing.
"""

from __future__ import annotations

import json

from benchmark.harness import spanred


def reduce(spans: list, chunk: int) -> dict | None:
    """Peak and tail of the log's ``level`` spans, in time order."""
    rows = [r for r in spanred.level_rows(spans)
            if "new_states" in r["args"]]
    if not rows:
        return None
    top = max(range(len(rows)), key=lambda k: rows[k]["args"]["new_states"])
    tail = [r for r in rows[top + 1:] if r["args"]["new_states"] < chunk]
    frontiers = [r["args"]["rows"] for r in rows if "rows" in r["args"]]
    return {"levels": len(rows),
            "peak_level": rows[top]["level"],
            "peak_frontier_rows": max(frontiers) if frontiers else None,
            "tail_levels": [r["level"] for r in tail],
            "tail_new_states": [r["args"]["new_states"] for r in tail],
            "tail_wall_s": sum(r["wall_s"] for r in tail) if tail else None}


def of(ev: dict) -> dict | None:
    """The reduction of this run's traced pass (computed once a run and
    kept on the evidence; prints its one line the first time)."""
    if "tailred" not in ev:
        p = spanred.traced_pass(ev)
        red = None
        if p is not None and ev.get("work", {}).get("chunk"):
            red = reduce(spanred.load(p.events), ev["work"]["chunk"])
        ev["tailred"] = red
        if red is not None:
            print(f"peak and tail pass {p.index}: " + json.dumps(red),
                  flush=True)
    return ev["tailred"]
