"""From the program's pass ledger (``raft_tla_tpu.obs.passlog``: every
``check()`` of the ddd engines keeps its own level-by-level account, traced or
not — wall, the main thread's seams, its CPU time, the collector's, page faults
and involuntary switches) to what the **untraced** passes of a run cost on the
host: the passes ``orbits_per_s`` and ``verdict_wall_s`` are made of, which the
span tree of the one traced pass cannot see.

A record belongs to the pass whose ``t_call <= t0 <= t_return`` (the clock is
``time.monotonic()`` on both sides).  The readers take the sound untraced
passes:

- ``level_host_ms``: median over passes and levels 1..A-1 of ``wall_s -
  wait_s``, the host's fixed cost a level as a user pays it (its traced twin:
  ``ramp_level_ms - ramp_segment_ms``);
- ``level_cpu_share_pct``: over the same levels, sum ``cpu_s`` over sum
  (``wall_s - wait_s``): how much of that cost the main thread computes, the
  rest it is blocked (h2d, ``device_get``, a lock);
- ``host_exposed_s``: median over passes of ``wall_s`` less the sum of
  ``wait_s``: the seconds of a pass in which the main thread does not wait
  for the device;
- ``upload_untraced_ms``: sum ``upload_s`` over sum ``uploads`` of levels
  A+1..B, the untraced twin of ``upload_wait_ms``;
- ``stall_s``: with m_k the low median (of two passes: the faster) of level
  k's wall over those passes (and of ``head``, ``tail``), the sum of
  ``wall - m_k`` where that exceeds max(0.25 s, m_k); each such level gets a
  line in the run's log with every seam.

Where the run has a traced pass with a record, one more line says where
tracing's own cost went: traced less the untraced median by the gap before the
record, ``head_s``, the seams of levels 1..A, ``tail_s`` and the gap after.  A
program without the ledger gives ``None`` everywhere, and so does a run whose
ledger (a ring of the last 64 passes) no longer holds every sound untraced
pass: that is said in one loud line, not reduced over what is left.
"""

from __future__ import annotations

import json
import statistics

SEAMS = ("upload_s", "expand_s", "wait_s", "d2h_s", "dedup_s", "close_s")
STALL_FLOOR_S = 0.25


def match(records: list, passes: list) -> list:
    """``(pass, record)`` for each pass that returned and has a record
    inside its call."""
    out = []
    for p in passes:
        if p.t_return is None:
            continue
        rec = next((r for r in records
                    if p.t_call <= r["t0"] <= p.t_return
                    and r["wall_s"] is not None), None)
        if rec is not None:
            out.append((p, rec))
    return out


def _host_s(lv: dict) -> float:
    return lv["wall_s"] - lv["wait_s"]


def stalls(recs: list) -> list:
    """One dict per level (or ``head`` / ``tail``) of ``recs`` whose wall
    exceeds the run's low median there by more than max(0.25 s, median).
    ``recs``: ``(label, record)`` pairs."""
    walls: dict = {}
    for label, rec in recs:
        for key, wall, lv in (
                [("head", rec["head_s"], None), ("tail", rec["tail_s"], None)]
                + [(lv["level"], lv["wall_s"], lv) for lv in rec["levels"]]):
            walls.setdefault(key, []).append((label, wall, lv))
    found = []
    for key, seen in walls.items():
        med = statistics.median_low([w for _l, w, _lv in seen])
        for label, wall, lv in seen:
            if wall - med > max(STALL_FLOOR_S, med):
                found.append({"pass": label, "level": key, "wall_s": wall,
                              "median_s": med, "excess_s": wall - med,
                              "entry": lv})
    return sorted(found, key=lambda s: (s["pass"], str(s["level"])))


def stall_line(s: dict) -> str:
    lv = s["entry"]
    where = f"level {s['level']}" if lv is not None else str(s["level"])
    line = (f"stall pass {s['pass']} {where}: wall {s['wall_s']:.6f}s "
            f"against the run's median {s['median_s']:.6f}s "
            f"(+{s['excess_s']:.6f}s)")
    if lv is not None:
        line += ": " + " ".join(
            f"{k} {lv[k]:.6f}" for k in SEAMS + ("cpu_s", "gc_s")) \
            + f" uploads {lv['uploads']} majflt {lv['majflt']} " \
              f"nivcsw {lv['nivcsw']}"
    return line


def _by_seam_ms(levels: list) -> dict | None:
    """Median over ``levels`` of the wall, each seam and the main thread's
    CPU time, in ms: the untraced twin of ``spanred``'s ``ramp_by_name_ms``
    (for the run's log; no reader of its own)."""
    if not levels:
        return None
    return {k[:-2]: 1e3 * statistics.median(lv[k] for lv in levels)
            for k in ("wall_s",) + SEAMS + ("cpu_s",)}


def reduce(recs: list, level_a: int, level_b: int) -> dict | None:
    """The five readings over ``recs`` (``(label, record)`` of the sound
    untraced passes); ``None`` where there is none."""
    if not recs:
        return None
    ramp = [lv for _l, r in recs for lv in r["levels"]
            if 1 <= lv["level"] < level_a]
    span = [lv for _l, r in recs for lv in r["levels"]
            if level_a < lv["level"] <= level_b]
    host = sum(_host_s(lv) for lv in ramp)
    uploads = sum(lv["uploads"] for lv in span)
    found = stalls(recs)
    return {
        "passes": len(recs), "ramp_levels": len(ramp),
        "level_host_ms": 1e3 * statistics.median(
            [_host_s(lv) for lv in ramp]) if ramp else None,
        "level_cpu_share_pct": 100.0 * sum(lv["cpu_s"] for lv in ramp)
        / host if host > 0 else None,
        "host_exposed_s": statistics.median(
            [r["wall_s"] - sum(lv["wait_s"] for lv in r["levels"])
             for _l, r in recs]),
        "upload_untraced_ms": 1e3 * sum(lv["upload_s"] for lv in span)
        / uploads if uploads else None,
        "ramp_by_seam_ms": _by_seam_ms(ramp),
        "span_by_seam_ms": _by_seam_ms(span),
        "stall_s": sum((s["excess_s"] for s in found), 0.0),
        "stalls": [stall_line(s) for s in found],
    }


def _ramp_sums(rec: dict, level_a: int) -> dict:
    ramp = [lv for lv in rec["levels"] if 1 <= lv["level"] <= level_a]
    return {k: sum(lv[k] for lv in ramp)
            for k in ("wall_s", "cpu_s", "gc_s") + SEAMS}


def tracing_cost(traced: tuple, plain: list, level_a: int) -> dict | None:
    """Where the traced pass's extra ramp went: each part of it (the gap
    from the benchmark's call to the record's ``t0``, ``head_s``, the walls
    and seams of levels 1..A, and by level the largest excess) less the
    median of the same part over the untraced passes.  ``tail_s`` and the gap
    after the record lie past the profiler's window and are given too."""
    if traced is None or not plain:
        return None

    def parts(p, rec):
        out = {"pre_s": rec["t0"] - p.t_call, "head_s": rec["head_s"],
               "tail_s": rec["tail_s"],
               "post_s": p.t_return - (rec["t0"] + rec["wall_s"])}
        out.update({"ramp_" + k: v
                    for k, v in _ramp_sums(rec, level_a).items()})
        return out

    mine = parts(*traced)
    theirs = [parts(p, rec) for p, rec in plain]
    diff = {k: mine[k] - statistics.median([t[k] for t in theirs])
            for k in mine}
    by_level = {}
    for lv in traced[1]["levels"]:
        if 1 <= lv["level"] <= level_a:
            same = [o["wall_s"] for _p, r in plain for o in r["levels"]
                    if o["level"] == lv["level"]]
            if same:
                by_level[lv["level"]] = lv["wall_s"] - statistics.median(same)
    if by_level:
        worst = max(by_level, key=by_level.get)
        diff["level_excess_median_s"] = statistics.median(by_level.values())
        diff["level_excess_max_s"] = by_level[worst]
        diff["level_excess_max_at"] = worst
    return diff


def of(ev: dict) -> dict | None:
    """The reduction for this run (once a run; prints its lines)."""
    if "levelred" not in ev:
        red = None
        try:
            from raft_tla_tpu.obs import passlog
        except ImportError:
            passlog = None
        if passlog is not None and ev.get("span_levels"):
            snap = passlog.snapshot()
            level_a, level_b = ev["span_levels"]
            # numbered as run.py's own ``pass N`` lines number them
            number = {id(p): k + 1 for k, p in enumerate(ev["passes"])}
            pairs = match(snap["records"], ev["passes"])
            plain = [(p, r) for p, r in pairs
                     if not p.traced and p.problem is None]
            lost = sum(not p.traced and p.problem is None
                       and p.t_return is not None
                       for p in ev["passes"]) - len(plain)
            if lost and snap["records"]:
                # the ring keeps the last passlog.KEEP passes and the
                # checks after the window make more: a reading over the
                # passes that are left is another sample, so none is given
                print(f"PASS LEDGER: {lost} sound untraced passes of this "
                      f"run have no record (the ledger dropped "
                      f"{snap['dropped']}): no reading from it", flush=True)
            else:
                red = reduce([(number[id(p)], r) for p, r in plain],
                             level_a, level_b)
            if red is not None:
                red["dropped"] = snap["dropped"]
                for line in red["stalls"]:
                    print(line, flush=True)
                print("pass ledger, untraced passes: " + json.dumps(
                    {k: v for k, v in red.items() if k != "stalls"}),
                    flush=True)
                cost = tracing_cost(
                    next(((p, r) for p, r in pairs if p.traced), None),
                    plain, level_a)
                if cost is not None:
                    print("tracing's own cost, traced pass less the "
                          "untraced median: " + json.dumps(cost), flush=True)
        ev["levelred"] = red
    return ev["levelred"]
