"""VERDICT r3 item #1 closure gate: IN-ENGINE ablation of the DDD
filter redesign (not standalone, not synthetic — the protocol from
runs/filter_anatomy.py / RESULTS.md "measurement protocol").

Times the REAL jitted segment program (ddd_engine._build_segment — the
while_loop the campaigns run) over a 16-chunk constraint-clean frontier
block at flagship shapes, with the module filter swapped between:

- ``new``      — the round-4 compacted-insert filter (same two-table
                 layout and probe, argsort-compacted S=16k-update
                 scatters; ddd_engine._filter_insert as shipped.  A
                 combined [TB, BUCKET, 2] single-table variant was
                 measured 1.6x SLOWER in-engine — rank-3 minor-dim-2
                 layout wrecks the probe gather — and rejected);
- ``old2d``    — the rounds-1-3 design: two [TB, BUCKET] tables, full-N
                 2-D element scatters (reconstructed here verbatim);
- ``none``     — in-batch first-of-key only, no table (the filter's
                 lower bound; streams every cross-chunk re-sight).

Reports per-chunk device ms (sync timing minus the measured dispatch
floor) and the filter's share of the step.  The r3 G-probe bug (rows
past the state constraint fed with fcon=1 -> FAIL_WIDTH after chunk 0)
is fixed by keeping only constraint-ok states in the frontier.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import raft_tla_tpu.ddd_engine as dddm
from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
from raft_tla_tpu.device_engine import _EMPTY, BUCKET
from raft_tla_tpu.models import interp, spec as S

from filter_ablation import CFG, TABLE

I32 = jnp.int32
U32 = jnp.uint32
N_CHUNKS = 16
FLOOR_MS = 112.0          # rounds 2-5 dispatch floor (filter_anatomy);
#                           inherited, not re-measured on this machine


def frontier_rows_con(n_rows: int) -> np.ndarray:
    """Constraint-OK frontier states only (the engine never expands
    constraint violators — feeding them with fcon=1 was the r3 bug)."""
    bounds = CFG.bounds
    init = interp.init_state(bounds)
    seen, frontier = {init}, [init]
    rows = [interp.to_vec(init, bounds)]
    while len(rows) < n_rows:
        nxt = []
        for s in frontier:
            if not interp.constraint_ok(s, bounds):
                continue
            for _i, t in interp.successors(s, bounds, spec=CFG.spec):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
                    if interp.constraint_ok(t, bounds):
                        rows.append(interp.to_vec(t, bounds))
                        if len(rows) >= n_rows:
                            break
            if len(rows) >= n_rows:
                break
        frontier = nxt or frontier
    return np.asarray(rows[:n_rows], np.int32)


def filter_old2d(tbl_hi, tbl_lo, key_hi, key_lo, active):
    """The rounds-1-3 filter, verbatim: identical stream semantics,
    full-N 2-D element scatters on each word plane."""
    BA = key_hi.shape[0]
    TB, Sb = tbl_hi.shape
    bmask = jnp.uint32(TB - 1)
    skh = jnp.where(active, key_hi, _EMPTY)
    skl = jnp.where(active, key_lo, _EMPTY)
    perm = jnp.lexsort((skl, skh))
    ph, pl_, pa = key_hi[perm], key_lo[perm], active[perm]
    same_as_prev = jnp.concatenate([
        jnp.zeros((1,), bool),
        (ph[1:] == ph[:-1]) & (pl_[1:] == pl_[:-1]) & pa[1:] & pa[:-1]])
    first_of_key = jnp.zeros((BA,), bool).at[perm].set(~same_as_prev)
    probe = active & first_of_key
    bidx = (key_lo & bmask).astype(I32)
    row_hi, row_lo = tbl_hi[bidx], tbl_lo[bidx]
    seen = jnp.any((row_hi == key_hi[:, None])
                   & (row_lo == key_lo[:, None]), axis=1)
    stream = probe & ~seen
    slot_empty = (row_hi == _EMPTY) & (row_lo == _EMPTY)
    has_empty = jnp.any(slot_empty, axis=1)
    evict = (key_hi % jnp.uint32(Sb)).astype(I32)
    wslot = jnp.where(has_empty, jnp.argmax(slot_empty, axis=1), evict)
    wb = jnp.where(stream, bidx, TB)
    tbl_hi = tbl_hi.at[wb, wslot].set(key_hi, mode="drop")
    tbl_lo = tbl_lo.at[wb, wslot].set(key_lo, mode="drop")
    return tbl_hi, tbl_lo, stream


def filter_none(tbl_hi, tbl_lo, key_hi, key_lo, active):
    """In-batch first-of-key only — the no-table lower bound."""
    BA = key_hi.shape[0]
    skh = jnp.where(active, key_hi, _EMPTY)
    skl = jnp.where(active, key_lo, _EMPTY)
    perm = jnp.lexsort((skl, skh))
    ph, pl_, pa = key_hi[perm], key_lo[perm], active[perm]
    same_as_prev = jnp.concatenate([
        jnp.zeros((1,), bool),
        (ph[1:] == ph[:-1]) & (pl_[1:] == pl_[:-1]) & pa[1:] & pa[:-1]])
    first_of_key = jnp.zeros((BA,), bool).at[perm].set(~same_as_prev)
    return tbl_hi, tbl_lo, active & first_of_key


def filter_probeonly(tbl_hi, tbl_lo, key_hi, key_lo, active):
    """Probe + seen, NO insert — isolates the in-engine insert cost."""
    BA = key_hi.shape[0]
    TB, Sb = tbl_hi.shape
    bmask = jnp.uint32(TB - 1)
    skh = jnp.where(active, key_hi, _EMPTY)
    skl = jnp.where(active, key_lo, _EMPTY)
    perm = jnp.lexsort((skl, skh))
    ph, pl_, pa = key_hi[perm], key_lo[perm], active[perm]
    same_as_prev = jnp.concatenate([
        jnp.zeros((1,), bool),
        (ph[1:] == ph[:-1]) & (pl_[1:] == pl_[:-1]) & pa[1:] & pa[:-1]])
    first_of_key = jnp.zeros((BA,), bool).at[perm].set(~same_as_prev)
    probe = active & first_of_key
    bidx = (key_lo & bmask).astype(I32)
    row_hi, row_lo = tbl_hi[bidx], tbl_lo[bidx]
    seen = jnp.any((row_hi == key_hi[:, None])
                   & (row_lo == key_lo[:, None]), axis=1)
    return tbl_hi, tbl_lo, probe & ~seen


def main() -> None:
    B = CFG.chunk
    A = len(S.action_table(CFG.bounds, CFG.spec))
    rows = frontier_rows_con(B * N_CHUNKS)
    out = {"backend": jax.devices()[0].platform, "chunk": B, "lanes": A,
           "n_chunks": N_CHUNKS, "table_slots": TABLE}

    orig = dddm._filter_insert
    for name, filt, tbl_slots in (
            ("new", orig, TABLE), ("old2d", filter_old2d, TABLE),
            ("none", filter_none, TABLE),
            ("probeonly", filter_probeonly, TABLE),
            ("new_smalltbl", orig, 1 << 22),
            ("probeonly_smalltbl", filter_probeonly, 1 << 22)):
        dddm._filter_insert = filt
        eng = DDDEngine(CFG, DDDCapacities(
            block=B * N_CHUNKS, table=tbl_slots,
            seg_rows=B * A * N_CHUNKS))
        fbuf = jnp.asarray(eng.schema.pack(rows, np))
        fcon = jnp.ones((B * N_CHUNKS,), bool)

        def seg_once(fc, bufs):
            return eng._segment(fc, bufs, fbuf, fcon,
                                jnp.int32(N_CHUNKS), jnp.int32(0),
                                jnp.int32(B * N_CHUNKS))
        fc = eng._init_filter()
        bufs = eng._make_bufs()
        _, _, stats = jax.block_until_ready(seg_once(fc, bufs))
        res = {"chunks": int(stats.steps), "cursor": int(stats.cursor),
               "fail": int(stats.fail), "viol_kind": int(stats.viol_kind)}
        ts = []
        for _ in range(5):
            fc = eng._init_filter()
            bufs = eng._make_bufs()
            jax.block_until_ready((fc, bufs))
            t0 = time.perf_counter()
            _, _, statsx = seg_once(fc, bufs)
            jax.block_until_ready(statsx)
            ts.append(time.perf_counter() - t0)
        ms = float(np.median(ts)) * 1e3
        res["segment_sync_ms"] = round(ms, 3)
        res["per_chunk_ms"] = round(
            (ms - FLOOR_MS) / max(int(stats.steps), 1), 3)
        out[name] = res
        del eng

    dddm._filter_insert = orig
    new, old, none, ponly = (out[k]["per_chunk_ms"] for k in
                             ("new", "old2d", "none", "probeonly"))
    out["speedup_old_to_new"] = round(old / new, 3)
    out["filter_cost_new_ms"] = round(new - none, 3)
    out["filter_share_new"] = round((new - none) / new, 4)
    out["filter_cost_old_ms"] = round(old - none, 3)
    out["probe_cost_inengine_ms"] = round(ponly - none, 3)
    out["insert_cost_inengine_ms"] = round(new - ponly, 3)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
