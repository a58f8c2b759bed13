"""From a ``jax.profiler`` capture of a program with named stage scopes to
per-stage device time, and from the program's host annotations in the same
capture to the error of the anchor alignment.

The compiled step wraps each stage in ``jax.named_scope`` (``unpack``,
``expand``, ``pack``, ``prescan``, ``orbit_scan``, ``plain_fp``,
``invariants``, ``constraint``, ``filter_insert``, ``stream``).  A scope is
HLO metadata (``op_name``), and the TPU's device trace keeps it: in JAX
0.9.0's xplane every ``XLA Ops`` event's **event metadata** carries a stat
``tf_op`` = ``"<op_name>:<op_type>"``, e.g.
``jit(segment)/while/body/stream/scatter:`` (found on the v5e, PR 24).
``jax.profiler.ProfileData`` shows an event's own stats only (offset and
duration), not its metadata's, so the loader reads the ``event_metadata`` and
``stat_metadata`` maps of each device plane straight off the protobuf wire
(``XSpace.planes[].event_metadata[].stats[]``: a dozen field numbers, below)
and joins them to ProfileData's events by the event's name.  An op's stage is
the INNERMOST stage name on its path (``prescan/.../orbit_scan/...`` is
``orbit_scan``); a fusion that spans stages belongs to the scope its event
names; an op of the segment module whose path names no stage is counted as
``unscoped`` — reported, not hidden.

Like ``tracered`` the reduction works on a plain dict, so the same code reads
an ``.xplane.pb`` and the small recorded excerpt in ``benchmark/testdata``:

    {"devices": {plane: {"XLA Ops": [[name, start_ns, dur_ns, path], ...],
                         "XLA Modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, span_id, start_ns, dur_ns], ...],   # annotations
     "anchor": [name, start_ns]}
"""

from __future__ import annotations

import glob
import json
import os
import statistics

from benchmark.harness import spanred, tracered
from benchmark.harness.tracered import MODULES_LINE, OPS_LINE

STAGES = ("unpack", "expand", "pack", "prescan", "orbit_scan", "plain_fp",
          "invariants", "constraint", "filter_insert", "stream")
# metric -> the stage scopes it sums
GROUPS = {"expand": ("unpack", "expand", "pack"),
          "orbit": ("prescan", "orbit_scan", "plain_fp"),
          "check": ("invariants", "constraint"),
          "filter": ("filter_insert",),
          "stream": ("stream",)}
UNSCOPED = "unscoped"


def stage_of(path: str) -> str | None:
    """The innermost stage scope on an op's scope path."""
    for part in reversed(path.split("/")):
        if part in STAGES:
            return part
    return None


def _varint(buf, i: int) -> tuple:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if not b & 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: ints for varints,
    a memoryview for length-delimited fields, raw bytes for fixed ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            val, i = bytes(buf[i:i + ln]), i + ln
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, val


def op_paths(xspace: bytes, stat: str = "tf_op") -> dict:
    """``{device plane: {event name: scope path}}`` from the raw XSpace:
    XSpace.planes=1; XPlane.name=2, .event_metadata=4, .stat_metadata=5
    (maps: key=1, value=2); XEventMetadata.name=2, .stats=5;
    XStatMetadata.name=2; XStat.metadata_id=1, .str_value=5.  Lines (field
    3, nearly all of the file) are skipped whole."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        name, stat_id, metas = None, None, []
        for pf, pv in _fields(plane):
            if pf == 2:
                name = bytes(pv).decode()
            elif pf == 5:
                entry = dict(_fields(pv))
                if bytes(dict(_fields(entry[2])).get(2, b"")) \
                        == stat.encode():
                    stat_id = entry[1]
            elif pf == 4:
                metas.append(pv)
        if name is None or not name.startswith("/device:") \
                or stat_id is None:
            continue
        paths = {}
        for m in metas:
            ev_name, path = None, None
            for mf_, mv in _fields(dict(_fields(m))[2]):
                if mf_ == 2:
                    ev_name = bytes(mv).decode()
                elif mf_ == 5:
                    st = dict(_fields(mv))
                    if st.get(1) == stat_id and 5 in st:
                        # "<op_name>:<op_type>"
                        path = bytes(st[5]).decode().rsplit(":", 1)[0]
            if ev_name is not None and path is not None:
                paths[ev_name] = path
        out[name] = paths
    return out


def load_xplane(trace_dir: str, anchor_name: str | None = None) -> dict:
    """Device ops with their scope paths, module intervals, the program's
    host annotations (events with a ``span_id`` stat) and the anchor."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {trace_dir}")
    with open(paths[0], "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    scope = op_paths(raw)
    out = {"devices": {}, "host": [], "anchor": None}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            by_name = scope.get(plane.name, {})
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    lines[line.name] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
                elif line.name == OPS_LINE:
                    lines[line.name] = [
                        [e.name.split(" = ", 1)[0].lstrip("%")[:80],
                         int(e.start_ns), int(e.duration_ns),
                         by_name.get(e.name, "")]
                        for e in line.events]
            out["devices"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if anchor_name and e.name == anchor_name:
                        out["anchor"] = [e.name, int(e.start_ns)]
                        continue
                    sid = dict(e.stats).get("span_id")
                    if sid is not None:
                        out["host"].append([e.name, int(sid),
                                            int(e.start_ns),
                                            int(e.duration_ns)])
    return out


def module_self_times(lines: dict, w0: int, w1: int, key_of,
                      module_hint: str = "segment") -> tuple | None:
    """One device plane: self time (a ``while`` holds its body's ops:
    tracered's own clip and arithmetic) of the ops that start inside the
    segment module's intervals, clipped to ``[w0, w1]`` ns and keyed by
    ``(op name, key_of(scope path))``; with the module's time there.
    ``None`` where the plane did not run the module inside the window."""
    mods = [(max(s, w0), min(s + d, w1))
            for n, s, d in lines.get(MODULES_LINE, [])
            if module_hint in n and s < w1 and s + d > w0]
    if not mods:
        return None
    keyed = [[(name, key_of(path)), s, d]
             for name, s, d, path in lines.get(OPS_LINE, [])]
    by_op = tracered.self_times(
        [ev for ev in tracered.clip(keyed, w0, w1)
         if any(a <= ev[1] < b for a, b in mods)])
    return by_op, sum(b - a for a, b in mods)


def stage_times(trace: dict, w0: int, w1: int,
                module_hint: str = "segment") -> dict | None:
    """Device self time per stage scope of the ops that run inside the
    segment module's intervals, clipped to ``[w0, w1]`` ns; averaged over
    the devices that ran any, each device's own kept beside the mean in
    plane order.  ``stage_ns`` + ``unscoped_ns`` == ``total_ns`` exactly
    (one partition of the same events)."""
    per_dev = []
    for _plane, lines in sorted(trace["devices"].items()):
        got = module_self_times(lines, w0, w1,
                                lambda path: stage_of(path) or UNSCOPED,
                                module_hint)
        if got is None:
            continue
        by_op, module_ns = got
        acc: dict = {}
        for (_name, stage), ns in by_op.items():
            acc[stage] = acc.get(stage, 0) + ns
        per_dev.append((acc, by_op, module_ns))
    if not per_dev:
        return None
    n = len(per_dev)
    stage_ns = {st: sum(a.get(st, 0) for a, _e, _m in per_dev) / n
                for st in STAGES}
    unscoped = sum(a.get(UNSCOPED, 0) for a, _e, _m in per_dev) / n
    ops_ns: dict = {}
    for _a, by_op, _m in per_dev:
        for k, v in by_op.items():
            ops_ns[k] = ops_ns.get(k, 0) + v / n
    ranked = sorted(ops_ns.items(), key=lambda kv: -kv[1])
    total = sum(stage_ns.values()) + unscoped
    return {"devices": n, "stage_ns": stage_ns, "unscoped_ns": unscoped,
            "total_ns": total,
            "module_ns": sum(m for _a, _e, m in per_dev) / n,
            "scoped": any(stage_ns.values()),
            "stage_ns_by_device": [a for a, _e, _m in per_dev],
            # the breakdown with stable names: each op beside its stage
            "top_ops": [[name, stage, ns]
                        for (name, stage), ns in ranked[:10]],
            "top_unscoped": [[name, ns] for (name, stage), ns in ranked
                             if stage == UNSCOPED][:5]}


def clock_skew(trace: dict, spans: list, anchor_mono_ns: int,
               thread: str = "MainThread") -> dict | None:
    """How far the anchor alignment puts a span's start from where the
    profiler's own clock saw the same region begin: ``|t0 moved through the
    anchor - start of the span's own annotation|`` over the ``thread``
    spans the capture holds an annotation for (matched by name and
    ``span_id``).  ``spans``: dicts as ``spanred.load`` returns them."""
    if trace["anchor"] is None or not trace["host"]:
        return None
    a_ns = trace["anchor"][1]
    ann = {(n, sid): s for n, sid, s, _d in trace["host"]}
    errs = []
    for sp in spans:
        if sp["thread"] != thread:
            continue
        start = ann.get((sp["name"], sp["id"]))
        if start is not None:
            by_anchor = tracered.to_trace_ns(sp["t0"], anchor_mono_ns, a_ns)
            errs.append(abs(by_anchor - start) / 1e3)
    if not errs:
        return None
    errs.sort()
    return {"n": len(errs), "median_us": statistics.median(errs),
            "p90_us": errs[int(0.9 * (len(errs) - 1))], "max_us": errs[-1],
            "annotations": len(trace["host"])}


def anchor_check_ms(trace: dict, spans: list, anchor_mono_ns: int,
                    w0: int, module_hint: str = "segment") -> float | None:
    """The anchor's own check (PERF.md section 3): the first segment-module
    interval after the window opens starts just after the ``expand`` span
    that dispatched it begins — the distance between the two, device clock
    against the host clock moved through the anchor."""
    if trace["anchor"] is None:
        return None
    a_ns = trace["anchor"][1]
    starts = [s for lines in trace["devices"].values()
              for n, s, _d in lines.get(MODULES_LINE, [])
              if module_hint in n and s >= w0]
    exp = [tracered.to_trace_ns(sp["t0"], anchor_mono_ns, a_ns)
           for sp in spans if sp["name"] == "expand"]
    exp = [t for t in exp if t >= w0]
    if not starts or not exp:
        return None
    return (min(starts) - min(exp)) / 1e6


def of(ev: dict, trace: dict | None = None) -> dict | None:
    """The stage and clock reductions of this run's traced pass (computed
    once a run and kept on the evidence; prints its one line the first
    time).  ``None`` where the run was not traced.  ``trace``: the capture
    as ``load_xplane`` gives it, where the caller has loaded it already."""
    if "stagered" in ev:
        return ev["stagered"]
    p = spanred.traced_pass(ev)
    red = None
    if p is not None and p.trace_dir and p.anchor:
        if trace is None:
            trace = load_xplane(p.trace_dir, p.anchor[1])
        if trace["anchor"] is not None:
            a_ns = trace["anchor"][1]
            w0 = tracered.to_trace_ns(p.t_a, p.anchor[0], a_ns)
            w1 = tracered.to_trace_ns(p.t_trace_end, p.anchor[0], a_ns)
            spans = spanred.load(p.events)
            red = {"stages": stage_times(trace, w0, w1),
                   "skew": clock_skew(trace, spans, p.anchor[0]),
                   "anchor_check_ms": anchor_check_ms(
                       trace, spans, p.anchor[0], w0)}
            seg = (ev.get("trace") or {}).get("segment_device_s")
            print(f"stages pass {p.index}: " + json.dumps(red)
                  + f" beside segment_device_s={seg}", flush=True)
    ev["stagered"] = red
    return red


def stage_ms_per_step(ev: dict, group: str) -> float | None:
    """Device self time under the group's scopes over the traced level's
    chunk steps; ``None`` where the capture names no stage at all (a
    program without scopes)."""
    red = of(ev)
    st = red and red["stages"]
    if not st or not st["scoped"]:
        return None
    ns = sum(st["stage_ns"][s] for s in GROUPS[group])
    return ns / 1e6 / ev["work"]["steps"]
