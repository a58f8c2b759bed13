"""What a mesh adds to the traced window: the exchange's device time and how
evenly the chips were busy.

The mesh engine's compiled step wraps its ``all_to_all`` and the packing
around it (the count-sort of the candidates into per-destination blocks, one
scatter a field) in ``jax.named_scope("exchange")``.  ``stagered`` gives an op
the innermost of the one-chip step's stage names on its path and so counts
these ops as unscoped; here an op belongs to the exchange when ``exchange`` is
a component of its ``tf_op`` path, however deep below it the op sits (the
collective's own ops nest under it: a reader that matched the last component
only read 0.0 beside a live ``all_to_all``, PR 23).  Self time, inside the
segment module's intervals, clipped to the window: the arithmetic of
``stagered.stage_times``, per device plane and then averaged (each plane's
stage times stand beside them in ``stagered``'s ``stage_ns_by_device``).
Imports nothing of the program.
"""

from __future__ import annotations

import json

from benchmark.harness import spanred, stagered, tracered

SCOPE = "exchange"


def in_scope(path: str, scope: str = SCOPE) -> bool:
    return scope in path.split("/")


def scope_times(trace: dict, w0: int, w1: int, scope: str = SCOPE,
                module_hint: str = "segment") -> dict | None:
    """Per device plane that ran the segment module inside ``[w0, w1]`` ns:
    device self time of the ops under ``scope`` and of all the module's ops
    (``stagered.module_self_times``, keyed by whether the scope is on the
    op's path).  ``None`` where no plane ran the module."""
    planes = []
    for plane, lines in sorted(trace["devices"].items()):
        got = stagered.module_self_times(
            lines, w0, w1, lambda path: in_scope(path, scope), module_hint)
        if got is None:
            continue
        by_op = got[0]
        inside = {n: ns for (n, hit), ns in by_op.items() if hit}
        planes.append({
            "plane": plane, "scope_ns": sum(inside.values()),
            "total_ns": sum(by_op.values()),
            "top": sorted(inside.items(), key=lambda kv: -kv[1])[:5]})
    if not planes:
        return None
    n = len(planes)
    return {"devices": n, "scope": scope,
            "scope_ns": sum(p["scope_ns"] for p in planes) / n,
            "scope_ns_max": max(p["scope_ns"] for p in planes),
            "total_ns": sum(p["total_ns"] for p in planes) / n,
            "planes": planes}


def busy_skew_pct(busy_by_device_s: list) -> float | None:
    """(max - min) / max of the chips' busy time in the traced window: what
    lockstep costs the chip that waits; ``None`` on fewer than two chips."""
    if len(busy_by_device_s) < 2 or not max(busy_by_device_s):
        return None
    return 100.0 * (max(busy_by_device_s) - min(busy_by_device_s)) \
        / max(busy_by_device_s)


def of(ev: dict) -> dict | None:
    """The exchange's times in this run's traced pass (computed once a run
    and kept on the evidence; prints its one line the first time).  The
    capture is loaded once: the step's stage times and the span tree's
    anatomy, which the one-chip cells' readers print, are printed from here
    for a mesh cell, whose lists those readers are not on."""
    if "meshred" in ev:
        return ev["meshred"]
    p = spanred.traced_pass(ev)
    red = None
    if p is not None and p.trace_dir and p.anchor:
        trace = stagered.load_xplane(p.trace_dir, p.anchor[1])
        stagered.of(ev, trace)
        spanred.of(ev)
        if trace["anchor"] is not None:
            a_ns = trace["anchor"][1]
            red = scope_times(
                trace, tracered.to_trace_ns(p.t_a, p.anchor[0], a_ns),
                tracered.to_trace_ns(p.t_trace_end, p.anchor[0], a_ns))
            if red is not None:
                print(f"mesh pass {p.index}: " + json.dumps(red), flush=True)
    ev["meshred"] = red
    return red
