"""From the program's span tree (the traced pass's event log) to numbers.

The ddd engine's spans form one tree per pass — ``pass`` > ``level`` >
``upload`` / ``expand`` / ``export`` > {``segment_wait``, ``d2h``} /
``level_close`` > {``dedup_wait``, ``dedup``} — with the flush worker's and
the prefetcher's spans on their own threads and one ``segment`` per harvested
segment on the synthetic ``segments`` track.  This module reads the ``span``
events as plain JSON (it imports nothing of the program), rebuilds the tree
from ``parent_id``, computes self time (a span's duration less what its
children cover) and reduces the traced pass to:

- the **ramp anatomy**: for each ``level`` span of levels 1..A-1, its wall,
  its ``upload``, ``segment_wait`` and ``level_close`` walls and its own self
  time — the per-level fixed cost that is 37-44 % of a pass;
- the **traced window** A -> A+1 (clipped like ``tracered._span_walls``):
  main-thread wall in ``segment_wait`` and ``d2h``, and the flush thread's
  ``dedup`` wall.

Where the log holds no ``level`` span (a program older than the tree, or an
untraced run) every reduction is ``None`` and the readers report nothing.
"""

from __future__ import annotations

import json
import statistics

from benchmark.harness.tracered import union_ns as union

MAIN = "MainThread"
FLUSH = "raft-tla-flush"


def load(events_path: str) -> list:
    """The log's ``span`` events as dicts (``name``, ``thread``, ``t0``,
    ``dur``, ``id``, ``parent``, ``args``)."""
    spans = []
    with open(events_path, encoding="utf-8") as f:
        for line in f:
            if '"span"' not in line:
                continue
            ev = json.loads(line)
            if ev.get("event") == "span":
                spans.append({"name": ev["name"], "thread": ev["thread"],
                              "t0": ev["t0"], "dur": ev["dur"],
                              "id": ev["span_id"],
                              "parent": ev.get("parent_id"),
                              "args": ev.get("args") or {}})
    return spans


def children(spans: list) -> dict:
    """span id -> its child spans (same thread by construction: the parent
    stack is per thread)."""
    kids: dict = {}
    for s in spans:
        if s["parent"] is not None:
            kids.setdefault(s["parent"], []).append(s)
    return kids


def self_s(span: dict, kids: dict) -> float:
    """Duration less the union of the direct children, clipped to it."""
    lo, hi = span["t0"], span["t0"] + span["dur"]
    return max(0.0, span["dur"] - union(
        [(max(lo, c["t0"]), min(hi, c["t0"] + c["dur"]))
         for c in kids.get(span["id"], ())
         if c["t0"] < hi and c["t0"] + c["dur"] > lo]))


def descendants(span: dict, kids: dict):
    for c in kids.get(span["id"], ()):
        yield c
        yield from descendants(c, kids)


def level_rows(spans: list) -> list:
    """One row per ``level`` span, in time order: its counts and where its
    wall went (seconds)."""
    kids = children(spans)
    rows = []
    for s in sorted((s for s in spans if s["name"] == "level"),
                    key=lambda s: s["t0"]):
        wall = {}
        for d in descendants(s, kids):
            wall[d["name"]] = wall.get(d["name"], 0.0) + d["dur"]
        rows.append({"level": s["args"].get("level"), "t0": s["t0"],
                     "wall_s": s["dur"], "self_s": self_s(s, kids),
                     "args": s["args"], "by_name_s": wall})
    return rows


def clipped_wall(spans: list, name: str, thread: str, t_a: float,
                 t_b: float) -> float:
    """Wall of ``name@thread`` inside [t_a, t_b], as
    ``tracered._span_walls`` clips it."""
    return sum((max(0.0, min(s["t0"] + s["dur"], t_b) - max(s["t0"], t_a))
                for s in spans
                if s["name"] == name and s["thread"] == thread), 0.0)


def _median_ms(values: list):
    return 1e3 * statistics.median(values) if values else None


def reduce(spans: list, level_a: int, t_a: float, t_b: float) -> dict | None:
    """The ramp anatomy (levels 1..A-1) and the traced window's walls.
    ``None`` where the log has no ``level`` span."""
    rows = level_rows(spans)
    if not rows:
        return None
    ramp = [r for r in rows
            if r["level"] is not None and 1 <= r["level"] < level_a]
    names = sorted({n for r in ramp for n in r["by_name_s"]})
    red = {
        "levels": len(rows), "ramp_levels": len(ramp),
        "ramp_level_ms": _median_ms([r["wall_s"] for r in ramp]),
        "ramp_upload_ms": _median_ms(
            [r["by_name_s"].get("upload", 0.0) for r in ramp]),
        "ramp_segment_ms": _median_ms(
            [r["by_name_s"].get("segment_wait", 0.0) for r in ramp]),
        "ramp_self_ms": _median_ms([r["self_s"] for r in ramp]),
        "level_close_ms": _median_ms(
            [r["by_name_s"].get("level_close", 0.0) for r in rows]),
        # every name under the ramp's levels, so that what the four above
        # leave out is named (expand = the dispatches, d2h, dedup_submit)
        "ramp_by_name_ms": {n: _median_ms(
            [r["by_name_s"].get(n, 0.0) for r in ramp]) for n in names},
        "segment_wait_s": clipped_wall(spans, "segment_wait", MAIN, t_a,
                                       t_b),
        "export_d2h_s": clipped_wall(spans, "d2h", MAIN, t_a, t_b),
        "flush_busy_s": clipped_wall(spans, "dedup", FLUSH, t_a, t_b),
    }
    return red


def traced_pass(ev: dict):
    """The run's traced pass with its event log, or ``None``."""
    return next((p for p in ev["passes"]
                 if p.traced and p.events and p.t_a is not None
                 and p.t_trace_end is not None), None)


def of(ev: dict) -> dict | None:
    """The reduction of this run's traced pass (computed once a run and
    kept on the evidence; prints its one line the first time)."""
    if "spanred" not in ev:
        p = traced_pass(ev)
        red = None
        if p is not None:
            red = reduce(load(p.events), ev["work"]["traced_levels"][0],
                         p.t_a, p.t_trace_end)
        ev["spanred"] = red
        if red is not None:
            print("span tree pass " + str(p.index) + ": "
                  + json.dumps(red), flush=True)
    return ev["spanred"]
