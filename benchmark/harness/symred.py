"""The orbit scan of a schema-declared spec under SYMMETRY, from the traced
pass: device self time under the step's stage scope ``orbit_scan``
(``stagered``'s own stage table of the same capture), the images the traced
window's segments keyed (``images`` among the ``args`` of the ``ddd`` engine's
``segment`` spans: ``group`` x the lanes of the segment's steps), and the work
the scan has to do a chunk step, counted by the configuration's family from its
declared shapes alone (``scan_ops`` / ``scan_bytes``), never from what the
program reports.

The evidence names no cell; the traced pass's event log lies under the run's
scratch directory, ``.bench_scratch/<cell>/pass<i>/run.events``
(``drive.scratch_dir``), and the manifest gives the cell's configuration.

Where the capture names no op under ``orbit_scan`` (a program that keys
plainly), the spans carry no ``images`` (a program older than the count) or
the family counts no scan, the reduction holds ``None`` there and the readers
report nothing.
"""

from __future__ import annotations

import json
import os

from benchmark.harness import manifest as mf
from benchmark.harness import spanred, stagered

SCOPE = "orbit_scan"


def window_images(spans: list, t_a: float, t_end: float) -> dict:
    """Images, lanes and chunk steps of the segments harvested inside
    ``[t_a, t_end]`` (``depthred.window_segments``' own rule); ``images`` is
    ``None`` where a segment's span carries none."""
    inside = [s["args"] for s in spans
              if s["name"] == "segment" and not s["args"].get("dropped")
              and s["t0"] >= t_a and s["t0"] + s["dur"] <= t_end + 1e-6]
    images = [a.get("images") for a in inside]
    return {"segments": len(inside),
            "steps": sum(a["steps"] for a in inside),
            "lanes": sum(a.get("lanes", 0) for a in inside),
            "group": max((a.get("group", 0) for a in inside), default=0)
            or None,
            "images": sum(images) if images and None not in images else None}


def config_of(p) -> dict | None:
    """The configuration of the cell whose run wrote the traced pass's
    event log, found by the scratch directory's name; ``None`` where the
    manifest knows no such cell (a rehearsal's toy)."""
    name = os.path.basename(os.path.dirname(os.path.dirname(
        os.path.abspath(p.events))))
    try:
        return mf.cell(mf.load(), name)["config_data"]
    except KeyError:
        return None


def scan_work(cfg: dict | None) -> dict | None:
    """``{"ops", "bytes"}`` of the scan a chunk step, as the configuration's
    family counts them; ``None`` where it counts no scan."""
    if cfg is None:
        return None
    fam = mf.family(cfg)
    if not (hasattr(fam, "scan_ops") and hasattr(fam, "scan_bytes")):
        return None
    return {"ops": fam.scan_ops(cfg), "bytes": fam.scan_bytes(cfg)}


def of(ev: dict) -> dict | None:
    """The reduction of this run's traced pass (computed once a run and kept
    on the evidence; prints its one line the first time).  ``None`` where the
    run was not traced or the capture names no stage."""
    if "symred" in ev:
        return ev["symred"]
    red = None
    p = spanred.traced_pass(ev)
    st = (stagered.of(ev) or {}).get("stages") if p is not None else None
    if st and st["scoped"]:
        red = {"scope_ns": st["stage_ns"].get(SCOPE, 0.0),
               "total_ns": st["total_ns"],
               "window": window_images(spanred.load(p.events), p.t_a,
                                       p.t_trace_end),
               "work": scan_work(config_of(p))}
        print(f"orbit scan pass {p.index}: " + json.dumps(red), flush=True)
    ev["symred"] = red
    return red


def scope_s_per_step(ev: dict) -> float | None:
    """Device self time under the scope over the traced level's chunk
    steps, seconds; ``None`` where there is nothing under it."""
    red = of(ev)
    if not red or not red["scope_ns"]:
        return None
    return red["scope_ns"] / 1e9 / ev["work"]["steps"]
