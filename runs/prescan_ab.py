"""On-chip A/B of the prescan ladder (ops/kernels._orbit_fp_prescan): the
fused step with the ladder forced on against forced off, on the chunks
an engine would hand it, at the shapes kernels._prescan_enabled's rule
is derived from.  Its one chip call (PR 32) is kept as
runs/prescan_ab.out and quoted by that docstring: on a TPU v5e the
ladder loses at every shape, 0.37x to 0.92x.

Shapes (chunk 4096; N = chunk * actions lanes a step):

- ``flagship3``: the benchmark's three-server configuration, |G| = 6,
  N = 172,032;
- ``elect5`` and ``full5``: its two five-server configurations
  (benchmark/configs/), |G| = 120, N = 155,648 / 344,064;
- ``elect6``: elect5's sub-spec and bounds at six servers, |G| = 720 —
  the largest group ops/symmetry admits, and no cell's.

The chunks are real ones: a breadth-first search driven through the
step itself (orbit keys, first occurrence in lane order, rows that fail
the StateConstraint kept and not expanded — the ddd engine's order), its
cumulative counts held to the configuration's pins where it has them,
down to the level named below; the arms are timed on that level's first
full chunks.  Three arms a shape, sync-timed (block_until_ready after
each call, median over chunks x reps): ``on``, ``off``, and ``plain``
(no SYMMETRY: the step without an orbit stage), so that off - plain is
about what a full scan costs and on - plain what ladder + rung scan
cost.  Keys are compared lane for lane, on against off.

Usage: python runs/prescan_ab.py [--cpu] [--chunk B] [--reps R]
           [--chunks K] [shape[:level] ...]
"""
import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax

if "--cpu" in sys.argv:
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.models import interp
from raft_tla_tpu.ops import kernels
from raft_tla_tpu.ops import symmetry as sym

# shape -> (benchmark configuration it is read from, n_servers, the level
# whose frontier is timed: the first level of each cell's clocked span,
# and for elect6 the first with eight full chunks)
SHAPES = {"flagship3": ("flagship3", 3, 15), "elect5": ("elect5", 5, 13),
          "full5": ("full5", 5, 10), "elect6": ("elect5", 6, None)}


def build(bounds, spec, invariants, symmetry, prescan):
    """The jitted fused step with _prescan_enabled answered for it: the
    script measures the comparison that rule encodes, so it goes past
    the rule (resolved when the step is traced: the first call)."""
    saved = kernels._prescan_enabled
    kernels._prescan_enabled = lambda *_: prescan
    try:
        fn = jax.jit(kernels.build_step(bounds, spec, invariants, symmetry))
        width = interp.to_vec(interp.init_state(bounds), bounds).shape[0]
        jax.block_until_ready(fn(jnp.zeros((ARGS.chunk, width), jnp.int32)))
    finally:
        kernels._prescan_enabled = saved
    return fn


def frontier_at(step, bounds, level, pins, min_chunks):
    """The rows of ``level``'s frontier in discovery order (level None:
    the first with ``min_chunks`` full chunks), that level, and the
    orbits found down to it."""
    B = ARGS.chunk
    init = interp.init_state(bounds)
    rows = interp.to_vec(init, bounds)[None, :]
    con = np.ones((1,), bool)
    hi, lo = sym.py_orbit_fingerprint(init, bounds, ("Server",))
    seen = np.array([int(hi) << 32 | int(lo)], np.uint64)
    total, lv = 1, 0
    while lv != level and not (level is None and len(rows) >= min_chunks * B):
        nxt_rows, nxt_con, nxt_keys = [], [], []
        for r0 in range(0, len(rows), B):
            blk, ok = rows[r0:r0 + B], con[r0:r0 + B]
            pad = B - len(blk)
            out = step(jnp.asarray(np.pad(blk, ((0, pad), (0, 0)))))
            valid = np.asarray(out["valid"]) & np.pad(ok, (0, pad))[:, None]
            keys = (np.asarray(out["fp_hi"]).astype(np.uint64) << 32
                    | np.asarray(out["fp_lo"]).astype(np.uint64))[valid]
            nxt_keys.append(keys)
            nxt_rows.append(np.asarray(out["svecs"])[valid])
            nxt_con.append(np.asarray(out["con_ok"])[valid])
        keys = np.concatenate(nxt_keys)
        _u, first = np.unique(keys, return_index=True)
        first = np.sort(first[~np.isin(keys[first], seen)])
        rows = np.concatenate(nxt_rows)[first]
        con = np.concatenate(nxt_con)[first]
        seen = np.union1d(seen, keys[first])
        total += len(first)
        lv += 1
        if pins and lv < len(pins) and total != pins[lv]:
            raise SystemExit(f"level {lv}: {total} orbits, the pin says "
                             f"{pins[lv]}")
        if not len(rows):
            raise SystemExit(f"space exhausted at level {lv}")
    return rows, lv, total


def timed(fn, chunks):
    times = []
    for _ in range(ARGS.reps):
        for c in chunks:
            t0 = time.monotonic()
            jax.block_until_ready(fn(c))
            times.append(time.monotonic() - t0)
    return sorted(times)[len(times) // 2] * 1e3


def run(shape, level):
    name, n_servers, default_level = SHAPES[shape]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{name}.json")) as f:
        cfg = json.load(f)
    level = default_level if level is None else level
    bounds = Bounds(**dict(cfg["bounds"], n_servers=n_servers))
    spec, invs = cfg["spec"], tuple(cfg["invariants"])
    pins = cfg["level_pins"] if n_servers == cfg["bounds"]["n_servers"] \
        else None
    g = math.factorial(n_servers)
    fns = {arm: build(bounds, spec, invs, axes, pre)
           for arm, axes, pre in (("off", ("Server",), False),
                                 ("on", ("Server",), True),
                                 ("plain", (), False))}
    rows, level, total = frontier_at(fns["off"], bounds, level, pins,
                                     ARGS.chunks)
    B = ARGS.chunk
    k = min(ARGS.chunks, len(rows) // B)
    if k < 1:
        raise SystemExit(f"{shape}: level {level} has {len(rows)} rows, "
                         f"under one chunk of {B}")
    chunks = [jnp.asarray(rows[i * B:(i + 1) * B]) for i in range(k)]
    # what the ladder sees in these chunks: every lane the dense step
    # calls valid (the engine masks unexpanded rows after the step), and
    # the raw-distinct candidates among them (+ 1 sentinel group)
    valid_share, uniq_share = [], []
    for c in chunks:
        off, on = fns["off"](c), fns["on"](c)
        valid = np.asarray(off["valid"])
        for key in ("fp_hi", "fp_lo"):
            assert np.array_equal(np.asarray(off[key])[valid],
                                  np.asarray(on[key])[valid]), key
        raw = np.unique(np.asarray(off["svecs"])[valid], axis=0)
        valid_share.append(valid.mean())
        uniq_share.append((len(raw) + 1) / valid.size)
    lanes = int(valid.size)
    ms = {arm: timed(fn, chunks) for arm, fn in fns.items()}
    scan_ns = (ms["off"] - ms["plain"]) * 1e6 / lanes / g
    rec = {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "shape": shape, "G": g, "chunk": B, "lanes": lanes,
        "level": level, "orbits_to_level": total, "chunks": k,
        "reps": ARGS.reps,
        "valid_share": round(float(np.mean(valid_share)), 4),
        "uniq_share_max": round(float(np.max(uniq_share)), 4),
        "ms_on": round(ms["on"], 3), "ms_off": round(ms["off"], 3),
        "ms_plain": round(ms["plain"], 3),
        "full_scan_ns_per_lane_image": round(scan_ns, 4),
        "full_scan_ns_per_lane": round(scan_ns * g, 2),
        "ladder_and_rung_scan_ns_per_lane": round(
            (ms["on"] - ms["plain"]) * 1e6 / lanes, 2),
        "speedup_from_prescan": round(ms["off"] / ms["on"], 3)}
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--chunk", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--chunks", type=int, default=8)
    ap.add_argument("shapes", nargs="*", default=list(SHAPES))
    ARGS = ap.parse_args()
    for item in ARGS.shapes:
        shape, _, lv = item.partition(":")
        run(shape, int(lv) if lv else None)
