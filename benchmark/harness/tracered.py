"""From a ``jax.profiler`` capture and the program's host spans to numbers.

The reduction works on a plain dict (``{"devices": {plane: {line: [[name,
start_ns, dur_ns], ...]}}, "anchor": [name, start_ns]}``) so the same code
reads an ``.xplane.pb`` and the small recorded trace kept in
``benchmark/testdata`` that the selftest checks it on.  All trace times are
nanoseconds since the capture began; host spans (``time.monotonic()``
seconds, from the program's ``span`` events) are moved onto that clock
through the anchor: a ``TraceAnnotation`` the benchmark opened at a known
monotonic time.
"""

from __future__ import annotations

import glob
import json
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host span name -> the gap category the breakdown prints
GAP_KINDS = {"upload": "upload", "export": "export", "expand": "expand",
             "dedup": "dedup", "dedup_wait": "dedup", "dedup_submit": "dedup",
             "devdedup": "expand", "snapshot": "snapshot"}


def load_xplane(trace_dir: str, anchor_name: str | None = None) -> dict:
    """Device planes' lines and the anchor, out of the capture's xplane."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {trace_dir}")
    data = ProfileData.from_file(paths[0])
    out = {"devices": {}, "anchor": None}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            out["devices"][plane.name] = {
                line.name: [[op_name(e.name), int(e.start_ns),
                             int(e.duration_ns)] for e in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif anchor_name and plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == anchor_name:
                        out["anchor"] = [e.name, int(e.start_ns)]
    return out


def op_name(text: str) -> str:
    """``fusion.1298`` out of the HLO line the trace names an op by."""
    return text.split(" = ", 1)[0].lstrip("%")[:80]


def load_spans(events_path: str) -> list:
    """The program's ``span`` events: ``[name, thread, t0_s, dur_s]``."""
    spans = []
    with open(events_path, encoding="utf-8") as f:
        for line in f:
            if '"span"' not in line:
                continue
            ev = json.loads(line)
            if ev.get("event") == "span":
                spans.append([ev["name"], ev["thread"], ev["t0"], ev["dur"]])
    return spans


def union_ns(intervals: list) -> int:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: list, w0: int, w1: int) -> list:
    """The parts of ``[w0, w1]`` no interval covers."""
    gaps, edge = [], w0
    for s, e in sorted(intervals):
        if s > edge:
            gaps.append((edge, min(s, w1)))
        edge = max(edge, e)
        if edge >= w1:
            break
    if edge < w1:
        gaps.append((edge, w1))
    return [(s, e) for s, e in gaps if e > s]


def self_times(events: list) -> dict:
    """Per-name self time on one line: an event's duration less what the
    events nested inside it cover (a ``while`` holds its body's ops)."""
    acc: dict = {}
    stack = []                       # [name, end_ns, dur_ns, child_ns]
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            n, _e, dur, child = stack.pop()
            acc[n] = acc.get(n, 0) + max(0, dur - child)
        if stack:
            stack[-1][3] += d
        stack.append([name, s + d, d, 0])
    for n, _e, dur, child in stack:
        acc[n] = acc.get(n, 0) + max(0, dur - child)
    return acc


def clip(events: list, w0: int, w1: int) -> list:
    return [[n, max(s, w0), min(s + d, w1) - max(s, w0)]
            for n, s, d in events if s < w1 and s + d > w0]


def to_trace_ns(t_mono_s: float, anchor_mono_ns: int, anchor_ns: int) -> int:
    return int(round(t_mono_s * 1e9)) - anchor_mono_ns + anchor_ns


def covering_kind(spans_ns: list, s: int, e: int) -> dict:
    """Split the gap ``[s, e]`` among the main-thread host spans that cover
    it, innermost first; what no span covers is ``unattributed``."""
    parts: dict = {}
    left = [(s, e)]
    for name, _thread, s0, e0 in sorted(spans_ns, key=lambda x: x[3] - x[2]):
        kind = GAP_KINDS.get(name)
        if kind is None or not left:
            continue
        nxt = []
        for a, b in left:
            lo, hi = max(a, s0), min(b, e0)
            if hi > lo:
                parts[kind] = parts.get(kind, 0) + hi - lo
                if a < lo:
                    nxt.append((a, lo))
                if hi < b:
                    nxt.append((hi, b))
            else:
                nxt.append((a, b))
        left = nxt
    rest = sum(b - a for a, b in left)
    if rest:
        parts["unattributed"] = parts.get("unattributed", 0) + rest
    return parts


def reduce(trace: dict, spans: list, anchor_mono_ns: int,
           t_a_s: float, t_b_s: float, main_thread: str = "MainThread",
           module_hint: str = "segment") -> dict:
    """Busy and idle time, top ops, idle gaps by host span and the segment
    program's device time over the traced window; per-device numbers are
    averaged over the devices that ran anything (the chips of a mesh step in
    lockstep: the mean is one chip's), with each device's own busy and
    segment time kept beside the means, in plane order."""
    if trace["anchor"] is None:
        raise ValueError("the capture holds no anchor annotation")
    a_ns = trace["anchor"][1]
    w0 = to_trace_ns(t_a_s, anchor_mono_ns, a_ns)
    w1 = to_trace_ns(t_b_s, anchor_mono_ns, a_ns)
    spans_ns = [[n, th, to_trace_ns(t0, anchor_mono_ns, a_ns),
                 to_trace_ns(t0 + d, anchor_mono_ns, a_ns)]
                for n, th, t0, d in spans if th == main_thread]
    busy, seg_ns = [], []
    ops_total: dict = {}
    gap_total: dict = {}
    for _plane, lines in sorted(trace["devices"].items()):
        ops = clip(lines.get(OPS_LINE, []), w0, w1)
        if not ops:
            continue
        ivals = [(s, s + d) for _n, s, d in ops]
        busy.append(union_ns(ivals))
        for n, ns in self_times(ops).items():
            ops_total[n] = ops_total.get(n, 0) + ns
        for s, e in gaps_ns(ivals, w0, w1):
            for kind, ns in covering_kind(spans_ns, s, e).items():
                gap_total[kind] = gap_total.get(kind, 0) + ns
        mods = clip(lines.get(MODULES_LINE, []), w0, w1)
        seg_ns.append(sum(d for n, _s, d in mods if module_hint in n))
    n_dev = len(busy)
    if not n_dev:
        raise ValueError("no device operation ran inside the traced window")
    top = sorted(ops_total.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(gap_total.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": n_dev,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "segment_device_s": sum(seg_ns) / n_dev / 1e9,
        "busy_by_device_s": [ns / 1e9 for ns in busy],
        "segment_by_device_s": [ns / 1e9 for ns in seg_ns],
        "device_ops": [[n, ns / n_dev / 1e9] for n, ns in top],
        "idle_gaps": [[k, ns / n_dev / 1e9] for k, ns in gaps],
        "span_wall_s": _span_walls(spans, t_a_s, t_b_s),
    }


def _span_walls(spans: list, t_a_s: float, t_b_s: float) -> dict:
    """Wall seconds per ``name@thread`` of the host spans inside A→B."""
    acc: dict = {}
    for n, th, t0, d in spans:
        lo, hi = max(t0, t_a_s), min(t0 + d, t_b_s)
        if hi > lo:
            k = f"{n}@{th}"
            acc[k] = acc.get(k, 0.0) + hi - lo
    return acc


def excerpt(trace: dict, per_line: int = 400) -> dict:
    """A small copy of a capture (first events of each line) — how the
    recorded trace in benchmark/testdata was made."""
    return {"anchor": trace["anchor"],
            "devices": {p: {ln: sorted(evs, key=lambda e: e[1])[:per_line]
                            for ln, evs in lines.items()}
                        for p, lines in trace["devices"].items()}}
