"""What the host path does at depth, from the traced pass's span tree.

Reads the ``level`` spans of the clocked span (levels A+1..B: those a pass
discovers between its two stamps) with what hangs under them: how many rows
the device streamed against how many the exact host key set admitted (the
rest it rejected: the filter let them through), how many frontier blocks a
level took, whether a block's ``upload`` found its rows staged by the
prefetcher, how long the main thread spent in the inline ``dedup`` at the
levels' closes, and how many keys waited behind the flush worker when a batch
was handed over.  Also the chunk steps and streamed rows of a resumed pass's
traced window, which is bounded by steps (``passes.TRACED_STEPS``) and so read
from the ``segment`` spans harvested inside it.  Imports nothing of the
program; where a span or a count is not in the log the reduction holds
``None`` there and the reader reports nothing.
"""

from __future__ import annotations

import json

from benchmark.harness import spanred


def reduce(spans: list, level_a: int, level_b: int) -> dict | None:
    """Sums over the ``level`` spans of levels A+1..B and their
    descendants; ``None`` where the log has none of them."""
    kids = spanred.children(spans)
    levels = [s for s in spans if s["name"] == "level"
              and level_a < (s["args"].get("level") or 0) <= level_b]
    if not levels:
        return None
    under = [d for s in levels for d in spanred.descendants(s, kids)]
    uploads = [d["args"]["prefetch_hit"] for d in under
               if d["name"] == "upload" and "prefetch_hit" in d["args"]]
    backlog = [d["args"]["backlog"] for d in under
               if d["name"] == "dedup_submit" and "backlog" in d["args"]]
    inline = [d["dur"] for d in under
              if d["name"] == "dedup" and d["thread"] == spanred.MAIN]

    def total(key):
        have = [s["args"][key] for s in levels if key in s["args"]]
        return sum(have) if have else None

    return {"levels": len(levels),
            "streamed_rows": total("streamed_rows"),
            "new_states": total("new_states"),
            "blocks": total("blocks"),
            "uploads": len(uploads) or None,
            "prefetch_hits": sum(uploads) if uploads else None,
            "dedup_inline_s": sum(inline) if inline else None,
            "flush_submits": len(backlog),
            "flush_backlog_max": max(backlog) if backlog else None}


def window_segments(spans: list, t_a: float, t_end: float) -> dict:
    """Chunk steps and streamed rows of the segments harvested inside
    ``[t_a, t_end]`` (a ``segment`` span runs from dispatch to stats
    ready; the window closes at one's harvest)."""
    inside = [s["args"] for s in spans
              if s["name"] == "segment" and not s["args"].get("dropped")
              and s["t0"] >= t_a and s["t0"] + s["dur"] <= t_end + 1e-6]
    return {"segments": len(inside),
            "steps": sum(a["steps"] for a in inside),
            "streamed_rows": sum(a["streamed_rows"] for a in inside)}


def of(ev: dict) -> dict | None:
    """The reduction of this run's traced pass (computed once a run and
    kept on the evidence; prints its one line the first time)."""
    if "depthred" not in ev:
        p = spanred.traced_pass(ev)
        red = None
        if p is not None and ev.get("span_levels"):
            red = reduce(spanred.load(p.events), *ev["span_levels"])
        ev["depthred"] = red
        if red is not None:
            print(f"depth counts pass {p.index} levels "
                  f"{ev['span_levels']}: " + json.dumps(red), flush=True)
    return ev["depthred"]
