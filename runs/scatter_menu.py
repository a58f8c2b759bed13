"""Round-4 device-perf design menu: with the filter-insert scatter
identified as the dominant cost (runs/filter_anatomy.out — ~28 ms of
device time per chunk vs ~5 ms sort + ~4.5 ms probe; cost tracks the
344k scatter UPDATES, not the 3.7k real inserts), measure the redesign
candidates before committing to one:

  G  in-engine baseline: the real jitted ddd segment program, per-chunk
  A  the engine's six output-compaction scatters, standalone
  B  filter insert as ONE combined [slots, 2] row scatter (also fixes
     the hi/lo chimera hazard of two independent scatters)
  C  compacted insert: sort-compact the 3.7k streamed rows, scatter a
     static S-row prefix (traffic-sound: overflow inserts drop)
  D  sort-based output compaction: one argsort + gathers + one
     dynamic_update_slice (no scatter at all)

Timing protocol per runs/filter_anatomy.py: sync = diff consecutive
block_until_ready stamps (includes the ~112 ms dispatch floor of the
rounds 2-5 machine — inherited, not re-measured on this one — reported
separately), async = amortized dispatch pipeline.
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

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.ddd_engine import (DDDCapacities, DDDEngine,
                                     _filter_insert)
from raft_tla_tpu.device_engine import _EMPTY, BUCKET
from raft_tla_tpu.models import spec as S
from raft_tla_tpu.ops import kernels

from filter_ablation import CFG, TABLE, frontier_rows
from filter_anatomy import timed_sync, timed_async

I32 = jnp.int32
U32 = jnp.uint32
S_INS = 1 << 15          # static compacted-insert budget (C)


def main() -> None:
    out = {}
    A = len(S.action_table(CFG.bounds, CFG.spec))
    B = CFG.chunk
    N = B * A
    step = jax.jit(kernels.build_step(CFG.bounds, CFG.spec,
                                      tuple(CFG.invariants),
                                      CFG.symmetry))
    n_chunks = 16
    rows = frontier_rows(B * n_chunks)
    vecs = jnp.asarray(rows[:B])
    so = jax.block_until_ready(step(vecs))
    kh = so["fp_hi"].reshape(N)
    kl = so["fp_lo"].reshape(N)
    act = so["valid"].reshape(N)

    TB = TABLE // BUCKET
    fresh = lambda: (jnp.full((TB, BUCKET), _EMPTY, U32),
                     jnp.full((TB, BUCKET), _EMPTY, U32))
    th, tl = fresh()
    th, tl, strm = jax.block_until_ready(
        jax.jit(_filter_insert)(th, tl, kh, kl, act))
    strm_np = np.asarray(strm)
    out["stream_count"] = int(strm_np.sum())

    # -- G: the real segment program, per chunk -------------------------
    eng = DDDEngine(CFG, DDDCapacities(block=B * n_chunks, table=TABLE,
                                       seg_rows=N * n_chunks))
    fbuf = jnp.asarray(eng.schema.pack(rows, np))
    fcon = jnp.ones((B * n_chunks,), bool)
    fc = eng._init_filter()
    bufs = eng._make_bufs()

    def seg_once(fc, bufs):
        return eng._segment(fc, bufs, fbuf, fcon, jnp.int32(n_chunks),
                            jnp.int32(0), jnp.int32(B * n_chunks))
    fc2, bufs2, stats = jax.block_until_ready(seg_once(fc, bufs))  # warm
    out["seg_warm_chunks"] = int(stats.steps)
    out["seg_warm_cursor"] = int(stats.cursor)
    ts = []
    for _ in range(5):
        fcx = eng._init_filter()
        bufx = eng._make_bufs()
        jax.block_until_ready((fcx, bufx))
        t0 = time.perf_counter()
        fcx, bufx, statsx = seg_once(fcx, bufx)
        jax.block_until_ready(statsx)
        ts.append(time.perf_counter() - t0)
    out["G_segment_sync_ms"] = round(float(np.median(ts)) * 1e3, 3)
    out["G_per_chunk_ms_minus_floor"] = round(
        (float(np.median(ts)) * 1e3 - 112.0) / n_chunks, 3)

    # -- A: the six output scatters, standalone -------------------------
    P = eng.schema.P
    OCAP = N
    svecs_words = jnp.asarray(
        np.random.default_rng(0).integers(0, 1 << 30, (N, P),
                                          dtype=np.int64).astype(np.int32))

    def out_scatters(okh, okl, orw, opa, ola, oco, stream, kh, kl):
        pos = jnp.cumsum(stream.astype(I32)) - 1
        sl = jnp.where(stream, pos, OCAP)
        okh = okh.at[sl].set(kh, mode="drop")
        okl = okl.at[sl].set(kl, mode="drop")
        orw = orw.at[sl].set(svecs_words, mode="drop")
        opa = opa.at[sl].set(jnp.arange(N, dtype=I32) // A, mode="drop")
        ola = ola.at[sl].set(jnp.arange(N, dtype=I32) % A, mode="drop")
        oco = oco.at[sl].set(stream, mode="drop")
        return okh, okl, orw, opa, ola, oco

    jout = jax.jit(out_scatters, donate_argnums=(0, 1, 2, 3, 4, 5))
    mk = lambda: (jnp.zeros((OCAP,), U32), jnp.zeros((OCAP,), U32),
                  jnp.zeros((OCAP, P), I32), jnp.zeros((OCAP,), I32),
                  jnp.zeros((OCAP,), I32), jnp.zeros((OCAP,), bool))
    bufs0 = mk()
    jax.block_until_ready(jout(*bufs0, strm, kh, kl))   # warm, consume
    ts = []
    for _ in range(8):
        bufs0 = mk()
        jax.block_until_ready(bufs0)
        t0 = time.perf_counter()
        r = jout(*bufs0, strm, kh, kl)
        jax.block_until_ready(r)
        ts.append(time.perf_counter() - t0)
    out["A_output_scatters_sync_ms"] = round(
        float(np.median(ts)) * 1e3, 3)

    # -- B: combined [slots, 2] row scatter, full N updates -------------
    def ins_combined(tbl, kh, kl, stream, wslot):
        bidx = (kl & jnp.uint32(TB - 1)).astype(I32)
        flat = bidx * BUCKET + wslot
        upd = jnp.stack([kh, kl], axis=1)
        tgt = jnp.where(stream, flat, TB * BUCKET)
        return tbl.at[tgt].set(upd, mode="drop")

    wslot = jnp.asarray(
        np.random.default_rng(1).integers(0, BUCKET, N, dtype=np.int64)
        .astype(np.int32))
    jins = jax.jit(ins_combined, donate_argnums=(0,))
    mkc = lambda: jnp.full((TB * BUCKET, 2), _EMPTY, U32)
    c = mkc()
    jax.block_until_ready(jins(c, kh, kl, strm, wslot))
    ts = []
    for _ in range(8):
        c = mkc()
        jax.block_until_ready(c)
        t0 = time.perf_counter()
        c = jins(c, kh, kl, strm, wslot)
        jax.block_until_ready(c)
        ts.append(time.perf_counter() - t0)
    out["B_combined_scatter_fullN_sync_ms"] = round(
        float(np.median(ts)) * 1e3, 3)

    # -- C: compact then scatter S_INS rows -----------------------------
    def ins_compact(tbl, kh, kl, stream, wslot):
        order = jnp.argsort(~stream)            # stream-first, stable
        sel = order[:S_INS]
        ok = stream[sel]
        bidx = (kl[sel] & jnp.uint32(TB - 1)).astype(I32)
        flat = jnp.where(ok, bidx * BUCKET + wslot[sel], TB * BUCKET)
        upd = jnp.stack([kh[sel], kl[sel]], axis=1)
        return tbl.at[flat].set(upd, mode="drop")

    jcomp = jax.jit(ins_compact, donate_argnums=(0,))
    c = mkc()
    jax.block_until_ready(jcomp(c, kh, kl, strm, wslot))
    ts = []
    for _ in range(8):
        c = mkc()
        jax.block_until_ready(c)
        t0 = time.perf_counter()
        c = jcomp(c, kh, kl, strm, wslot)
        jax.block_until_ready(c)
        ts.append(time.perf_counter() - t0)
    out["C_compact_scatter_sync_ms"] = round(
        float(np.median(ts)) * 1e3, 3)

    # -- D: sort-based output compaction (argsort + gathers + dus) ------
    def out_sorted(okh, okl, orw, opa, ola, oco, stream, kh, kl):
        order = jnp.argsort(~stream)
        iota = jnp.arange(N, dtype=I32)
        okh = jax.lax.dynamic_update_slice(okh, kh[order], (0,))
        okl = jax.lax.dynamic_update_slice(okl, kl[order], (0,))
        orw = jax.lax.dynamic_update_slice(orw, svecs_words[order],
                                           (0, 0))
        opa = jax.lax.dynamic_update_slice(opa, (iota // A)[order], (0,))
        ola = jax.lax.dynamic_update_slice(ola, (iota % A)[order], (0,))
        oco = jax.lax.dynamic_update_slice(oco, stream[order], (0,))
        return okh, okl, orw, opa, ola, oco

    jsorted = jax.jit(out_sorted, donate_argnums=(0, 1, 2, 3, 4, 5))
    bufs0 = mk()
    jax.block_until_ready(jsorted(*bufs0, strm, kh, kl))
    ts = []
    for _ in range(8):
        bufs0 = mk()
        jax.block_until_ready(bufs0)
        t0 = time.perf_counter()
        r = jsorted(*bufs0, strm, kh, kl)
        jax.block_until_ready(r)
        ts.append(time.perf_counter() - t0)
    out["D_output_sortcompact_sync_ms"] = round(
        float(np.median(ts)) * 1e3, 3)

    out["dispatch_floor_ms_ref"] = 112.0
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
