#!/usr/bin/env python3
"""Derive the level pins of a configuration with history (family
``raft_hist``) with the plain reference, deeper than a run's own check
reaches.

    python3 benchmark/reference/hist_pins.py --config faithful3 \
        --min-count 900000 [--workers 6]

``deep_pins.py``'s search (level-synchronous BFS from Init; ``seen`` holds
the 16-byte ``hashlib.blake2b`` digest of the canonical tuple's ``repr``;
the frontier is expanded by worker processes, chunk by chunk in order, so the
first-found member of an orbit is the one a single process would keep; a
state failing the StateConstraint is counted and checked, not expanded) over
FULL states: the canonical tuple is ``canon_hist``'s, history included, and
the invariants are ``invariants_hist``'s.  It stops one level past the first
level whose cumulative count reaches ``--min-count`` (the traffic's B; B + 1
is pinned so that a pass that overshoots is still held), or at
``--end-level``.  Imports nothing of the program and never touches JAX.  Off
the clock: run once when a pin is added; it prints one line a level, and
last the cumulative counts, the widest level and the wall as JSON.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import canon_hist, interp, invariants_hist  # noqa: E402
from benchmark.reference import spec as S  # noqa: E402
from benchmark.reference.bounds import Bounds  # noqa: E402
from benchmark.reference.deep_pins import CHUNK, digest  # noqa: E402

_W: dict = {}       # a worker's tables, built once by _start


def _start(bounds_kw: dict, spec: str, symmetry: tuple, inv_names: tuple):
    bounds = Bounds(**bounds_kw)
    _W.update(bounds=bounds, table=S.action_table(bounds, spec),
              key=canon_hist.orbit_key(symmetry),
              invs=[invariants_hist.REGISTRY[nm] for nm in inv_names])


def _expand(parents: list) -> list:
    """``[(digest, successor, invariants it breaks)]``
    for the expandable ``parents``, in discovery order, first occurrence in
    the chunk only."""
    bounds, table, key, invs = (_W[k] for k in
                                ("bounds", "table", "key", "invs"))
    out, mine = [], set()
    for s in parents:
        if not interp.constraint_ok(s, bounds):
            continue
        for _a, t in interp.successors(s, bounds, table):
            d = digest(key(t))
            if d in mine:
                continue
            mine.add(d)
            out.append((d, t, sum(not f(t, bounds) for f in invs)))
    return out


def bfs_counts(bounds_kw: dict, spec: str, symmetry, inv_names: tuple,
               end_level: int | None = None, min_count: int | None = None,
               workers: int = 1, out=print) -> dict:
    """The search from Init: ``cumulative`` counts a level, ``violations``
    seen, the ``widest`` level as ``[level, states]``, ``elections_peak``
    (the most records any admitted state holds) and ``wall_s``.  It ends one
    level past the first whose cumulative count is at least ``min_count``,
    at ``end_level``, or where the space ends."""
    args = (bounds_kw, spec, tuple(symmetry), tuple(inv_names))
    _start(*args)
    init = interp.init_state(_W["bounds"])
    seen = {digest(_W["key"](init))}
    violations = sum(not f(init, _W["bounds"]) for f in _W["invs"])
    cumulative, frontier = [1], [init]
    widest, epeak, last = [0, 1], 0, None
    # spawned, not forked: a caller may hold threads (a test under JAX does)
    pool = multiprocessing.get_context("spawn").Pool(workers, _start, args) \
        if workers > 1 else None
    t0 = time.monotonic()
    try:
        while frontier:
            level = len(cumulative) - 1
            if (end_level is not None and level >= end_level) \
                    or (last is not None and level > last):
                break
            chunks = (frontier[k:k + CHUNK]
                      for k in range(0, len(frontier), CHUNK))
            done = pool.imap(_expand, chunks) if pool else map(_expand, chunks)
            nxt = []
            for part in done:
                for d, t, broken in part:
                    if d not in seen:
                        seen.add(d)
                        violations += broken
                        epeak = max(epeak, len(t.elections))
                        nxt.append(t)
            if not nxt:
                break
            cumulative.append(cumulative[-1] + len(nxt))
            frontier = nxt
            if len(nxt) > widest[1]:
                widest = [level + 1, len(nxt)]
            if last is None and min_count is not None \
                    and cumulative[-1] >= min_count:
                last = level + 1
            out(f"level {level + 1}: {cumulative[-1]} orbits, "
                f"{len(nxt)} new, violations {violations}, elections peak "
                f"{epeak}, {time.monotonic() - t0:.0f}s")
    finally:
        if pool:
            pool.terminate()
            pool.join()
    return {"cumulative": cumulative, "violations": violations,
            "widest": widest, "elections_peak": epeak,
            "first_level_at_min_count": last,
            "wall_s": round(time.monotonic() - t0, 1), "workers": workers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True,
                    help="name of a file under benchmark/configs/ (or "
                         "benchmark/testdata/ with --testdata)")
    ap.add_argument("--end-level", type=int)
    ap.add_argument("--min-count", type=int)
    ap.add_argument("--workers", type=int, default=max(1, os.cpu_count() - 2))
    ap.add_argument("--testdata", action="store_true")
    a = ap.parse_args(argv)
    if a.end_level is None and a.min_count is None:
        ap.error("give --end-level or --min-count")
    path = os.path.join(ROOT, "benchmark",
                        "testdata" if a.testdata else "configs",
                        a.config + ".json")
    with open(path, encoding="utf-8") as f:
        cfg = json.load(f)
    got = bfs_counts(cfg["bounds"], cfg["spec"], cfg["symmetry"],
                     tuple(cfg["invariants"]), a.end_level, a.min_count,
                     a.workers, out=lambda m: print(m, flush=True))
    have = cfg.get("level_pins", [])
    got["differs_from_file_at_levels"] = [
        k for k, (x, y) in enumerate(zip(got["cumulative"], have)) if x != y]
    print(json.dumps({"config": a.config, **got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
