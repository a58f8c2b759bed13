"""What the history variables cost a chunk step, from the traced pass: device
self time of the segment program's ops under the two scopes that faithful mode
opens INSIDE a stage — ``history`` inside ``expand`` (the ``allLogs`` union,
the ``voterLog`` writes, the ``elections`` insert and sort, the ``mlog``
ranks) and ``orbit_moved`` inside ``orbit_scan`` (the fields the scan still
moves and canonicalises, an image at a time) — and the packed row's width as
the program's ``segment`` spans state it (``row_words``).

``stagered.STAGES`` is the accepted list of stage scopes and keeps such an op
under its innermost stage (``expand``, ``orbit_scan``): the stage table's
totals are unchanged, and this reduction reads the same capture with two more
names.  A scope opened under the step's vmaps, scans and maps is named on an
op's path as ``.../expand/vmap(vmap(history))/...``: a path part counts where
it is the name inside any number of ``vmap(`` ``)``, as ``quorumred`` reads
its own.  A fusion belongs to the scope its event names.

Where the capture names no op under either scope (a parity-mode program, or
one older than the scopes) and the spans carry no ``row_words``, every entry
is ``None`` or 0 and the readers report nothing.
"""

from __future__ import annotations

import json
import re

from benchmark.harness import spanred, stagered, tracered

SCOPES = ("history", "orbit_moved")
_PART = re.compile(r"(?:vmap\()*(" + "|".join(SCOPES) + r")\)*\Z")


def scope_of(path: str) -> str | None:
    """The innermost of the two scopes on an op's scope path."""
    for part in reversed(path.split("/")):
        m = _PART.match(part)
        if m:
            return m.group(1)
    return None


def scope_times(trace: dict, w0: int, w1: int) -> dict | None:
    """Self time under each scope and of the whole segment module inside
    ``[w0, w1]`` ns, averaged over the devices that ran the module; the ops
    under either scope by name, largest first.  ``None`` where no device ran
    the module inside the window."""
    per_dev = []
    for _plane, lines in sorted(trace["devices"].items()):
        got = stagered.module_self_times(lines, w0, w1, scope_of)
        if got is not None:
            per_dev.append(got[0])
    if not per_dev:
        return None
    n = len(per_dev)
    scope_ns = dict.fromkeys(SCOPES, 0.0)
    ops: dict = {}
    total = 0.0
    for by_op in per_dev:
        for (name, scope), ns in by_op.items():
            total += ns / n
            if scope is not None:
                scope_ns[scope] += ns / n
                ops[name, scope] = ops.get((name, scope), 0) + ns / n
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])
    return {"devices": n, "scope_ns": scope_ns, "total_ns": total,
            "ops": len(ops),
            "top_ops": [[name, scope, ns]
                        for (name, scope), ns in ranked[:8]]}


def window_row_words(spans: list, t_a: float, t_end: float) -> int | None:
    """``row_words`` of the segments harvested inside ``[t_a, t_end]``
    (``depthred.window_segments``' own rule); ``None`` where no such span
    says it, or they disagree."""
    said = {s["args"].get("row_words") for s in spans
            if s["name"] == "segment" and not s["args"].get("dropped")
            and s["t0"] >= t_a and s["t0"] + s["dur"] <= t_end + 1e-6}
    return said.pop() if len(said) == 1 else None


def of(ev: dict) -> dict | None:
    """The reduction of this run's traced pass (computed once a run and kept
    on the evidence; prints its one line the first time).  ``None`` where the
    run was not traced."""
    if "histred" in ev:
        return ev["histred"]
    p = spanred.traced_pass(ev)
    red = None
    if p is not None:
        red = {"row_words": window_row_words(
            spanred.load(p.events), p.t_a, p.t_trace_end), "scopes": None}
        if p.trace_dir and p.anchor:
            trace = stagered.load_xplane(p.trace_dir, p.anchor[1])
            # the accepted stage table of the same capture, printed in this
            # cell's log too (its readers list other cells; one load serves
            # both)
            stagered.of(ev, trace)
            if trace["anchor"] is not None:
                a_ns = trace["anchor"][1]
                red["scopes"] = scope_times(
                    trace, tracered.to_trace_ns(p.t_a, p.anchor[0], a_ns),
                    tracered.to_trace_ns(p.t_trace_end, p.anchor[0], a_ns))
        print(f"history scopes pass {p.index}: " + json.dumps(red),
              flush=True)
    ev["histred"] = red
    return red


def scope_ns(ev: dict, scope: str) -> float | None:
    """Device self time under ``scope`` in the traced window, ns; ``None``
    where the capture names nothing under it."""
    red = of(ev)
    ns = red and red["scopes"] and red["scopes"]["scope_ns"][scope]
    return ns or None


def scope_ms_per_step(ev: dict, scope: str) -> float | None:
    """... over the traced level's chunk steps, ms."""
    ns = scope_ns(ev, scope)
    return None if ns is None else ns / 1e6 / ev["work"]["steps"]
