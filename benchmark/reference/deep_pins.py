#!/usr/bin/env python3
"""Re-derive a configuration's level pins with the plain reference, deeper
than a run's own check reaches.

    python3 benchmark/reference/deep_pins.py --config flagship3 --end-level 23 \
        [--workers 7]

The same search as ``canon.bfs_levels`` (level-synchronous BFS from Init, the
spec's or the one the configuration states, under the configuration's
SYMMETRY axes; states canonicalised in plain Python and compared as states, a
state failing the StateConstraint counted and checked but not expanded), with
two changes
that let it reach millions of orbits: ``seen`` holds the 16-byte
``hashlib.blake2b`` digest of the canonical tuple's ``repr`` instead of the
tuple (2^-128 a pair: nothing a count can show), and the frontier is expanded
by worker processes, chunk by chunk in order, so the first-found member of an
orbit is the one a single process would keep.  Imports nothing of the program
and never touches JAX.  Off the clock: run once when a pin is added; it prints
one line a level and the cumulative counts as JSON last.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import multiprocessing
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import canon, interp, invariants  # noqa: E402
from benchmark.reference import spec as S  # noqa: E402
from benchmark.reference.bounds import Bounds  # noqa: E402

CHUNK = 4096        # parents a task
_W: dict = {}       # a worker's tables, built once by _start


def digest(key: tuple) -> bytes:
    return hashlib.blake2b(repr(key).encode("ascii"), digest_size=16).digest()


def _start(bounds_kw: dict, spec: str, symmetry: tuple, inv_names: tuple):
    bounds = Bounds(**bounds_kw)
    _W.update(bounds=bounds, table=S.action_table(bounds, spec),
              key=canon.orbit_key(symmetry, bounds.n_values),
              invs=[invariants.REGISTRY[nm] for nm in inv_names])


def _expand(parents: list) -> list:
    """``[(digest, successor, invariants it breaks)]`` for the expandable
    ``parents``, in discovery order, first occurrence in the chunk only."""
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
               end_level: int, workers: int = 1, out=print, init=None):
    """``(cumulative counts 0..end_level, invariant violations seen)`` of
    the search from the spec's Init, or from the state ``init``, under the
    SYMMETRY axes ``symmetry``; the list is shorter where the space ends
    first."""
    args = (bounds_kw, spec, tuple(symmetry), tuple(inv_names))
    _start(*args)
    if init is None:
        init = interp.init_state(_W["bounds"])
    seen = {digest(_W["key"](init))}
    violations = sum(not f(init, _W["bounds"]) for f in _W["invs"])
    cumulative, frontier = [1], [init]
    pool = multiprocessing.Pool(workers, _start, args) if workers > 1 \
        else None
    t0 = time.monotonic()
    try:
        while frontier and len(cumulative) <= end_level:
            chunks = (frontier[k:k + CHUNK]
                      for k in range(0, len(frontier), CHUNK))
            done = pool.imap(_expand, chunks) if pool else map(_expand, chunks)
            nxt = []
            for part in done:
                for d, t, broken in part:
                    if d not in seen:
                        seen.add(d)
                        violations += broken
                        nxt.append(t)
            if not nxt:
                break
            cumulative.append(cumulative[-1] + len(nxt))
            frontier = nxt
            out(f"level {len(cumulative) - 1}: {cumulative[-1]} orbits, "
                f"{len(nxt)} new, violations {violations}, "
                f"{time.monotonic() - t0:.0f}s")
    finally:
        if pool:
            pool.terminate()
            pool.join()
    return cumulative, violations


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True,
                    help="name of a file under benchmark/configs/ (or "
                         "benchmark/testdata/ with --testdata)")
    ap.add_argument("--end-level", type=int, required=True)
    ap.add_argument("--workers", type=int, default=max(1, os.cpu_count() - 1))
    ap.add_argument("--testdata", action="store_true")
    a = ap.parse_args(argv)
    path = os.path.join(ROOT, "benchmark",
                        "testdata" if a.testdata else "configs",
                        a.config + ".json")
    with open(path, encoding="utf-8") as f:
        cfg = json.load(f)
    try:
        init = canon.stated_init(Bounds(**cfg["bounds"]), cfg.get("init"),
                                 cfg["invariants"])
        canon.orbit_key(cfg["symmetry"], cfg["bounds"]["n_values"])
    except ValueError as e:
        raise SystemExit(f"configuration {a.config}: {e}") from None
    cum, viol = bfs_counts(cfg["bounds"], cfg["spec"], cfg["symmetry"],
                           tuple(cfg["invariants"]), a.end_level, a.workers,
                           out=lambda m: print(m, flush=True), init=init)
    have = cfg.get("level_pins", [])
    diff = [k for k, (x, y) in enumerate(zip(cum, have)) if x != y]
    print(json.dumps({"config": a.config, "cumulative": cum,
                      "violations": viol,
                      "differs_from_file_at_levels": diff}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
