"""Mesh-sharded delayed-duplicate-detection engine — the scale engine's
multi-chip composition (SURVEY §2.9 DP row, §7.1 step 7; VERDICT r2
missing #1).

The single-chip DDD engine (ddd_engine.py) removed the device
fingerprint-table ceiling by moving exact dedup to host RAM; this module
removes its single-chip ceiling by spreading BOTH the device work and
the host key set over a ``jax.sharding.Mesh``:

- **Device: lockstep expand + owner-routed lossy filtering.**  Each
  frontier window of ``ndev * block`` states splits into contiguous
  per-shard slices; shards expand their slice in lockstep chunks.  Every
  candidate is routed over the mesh to its fingerprint owner
  (``fp_hi % ndev`` — TLC's fingerprint-space partition, the same map as
  shard_engine.py) with one ``all_to_all`` per chunk (two-stage over a
  2-D (dcn, ici) slice mesh), so all duplicates of a key funnel through
  ONE shard's lossy filter and filtering efficiency matches the
  single-chip engine.  As in ddd_engine, the filter affects candidate
  *traffic* only, never the verdict — resume starts it empty.
- **Host: per-shard exact dedup in canonical order.**  Master keys are
  partitioned by the same owner map, so shard streams can never collide
  across partitions and each partition dedups independently
  (utils/keyset.MasterKeys — LSM-tiered, O(log) per flush) at arbitrary
  flush times.  Global discovery order is **(level, window, shard,
  shard-stream position)**: within a window each shard's new states are
  staged, and at the window boundary stagings drain into the single
  global store shard-major.  Every merge point is a deterministic
  function of the search — never of wall-clock flush/segment timing —
  so counts, levels, parent links and traces are reproducible run to
  run and across checkpoint resume, the shard_engine.py determinism
  contract.  On a 1-device mesh the order (and the checkpoint streams)
  coincide with the single-chip DDD engine's exactly (tested).

Totals (n_states, per-level counts, diameter, n_transitions, verdicts)
match refbfs exactly on violation-free runs.  On violating runs the
engine stops at lockstep-chunk granularity and reports a *valid,
deterministic* counterexample that may differ from refbfs's pick, and
counts include the full stopping chunk — the same relaxation as
shard_engine.py (TLC's multi-worker mode shares it).

Capacity: host RAM for keys + rows (as ddd_engine), device HBM holds
only the per-shard lossy filter and transfer buffers — the composition
runs/northstar_sizing.md calls for.  Discovery ids are int64 end-to-end
since round 4 (C++ store links, width-3 checkpoint streams, host
rebasing of window-relative device parents), so neither 10^9- nor
10^10-scale spaces hit an id ceiling (VERDICT r3 missing #2 closed);
the binding limits are host RAM and wall clock.

Checkpoints reuse the single-chip DDD incremental stream format
(.rows/.links/.con/.keys + npz); ``blocks_done`` counts completed
*global* windows and the digest pins the mesh size (the window layout
and owner map depend on it).  ``reshard_ddd_checkpoint`` rewrites a
snapshot for a different mesh size — the streams are order-only history
and move verbatim; only the window accounting and digest change.

Reference: TLC's external-memory fingerprint regime + multi-worker mode
(`/root/reference/.gitignore:1-2`); raft.tla line citations live in
ops/kernels.py next to the action semantics.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tla_tpu.config import CheckConfig
from raft_tla_tpu.device_engine import (
    _EMPTY, BUCKET, FAIL_INDEX, FAIL_LEVEL, FAIL_ROUTE, FAIL_WIDTH,
    aggregate_coverage, decode_fail)
from raft_tla_tpu.ddd_engine import (
    _filter_insert_ordered, _IDX_CEIL, _slab_plan, _slab_trips,
    _write_slabs,
    frontier_backtrace,
    frontier_checkpoint_setup, load_ddd_snapshot,
    load_frontier_snapshot, save_ddd_snapshot, save_frontier_snapshot)
from raft_tla_tpu.engine import DEADLOCK, EngineResult, Violation
from raft_tla_tpu.frontend import resolve_model
from raft_tla_tpu.models import interp, invariants as inv_mod, spec as S
from raft_tla_tpu.obs import RunTelemetry
from raft_tla_tpu.ops import bitpack
from raft_tla_tpu.ops import devdedup
from raft_tla_tpu.ops import kernels
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops import symmetry as sym_mod
from raft_tla_tpu.parallel.mesh import (
    _AXIS, _DCN, _mesh_axes, exchange, make_mesh)
from raft_tla_tpu.utils import ckpt
from raft_tla_tpu.utils import keyset
from raft_tla_tpu.utils import native
from raft_tla_tpu.utils import pacing
from raft_tla_tpu.utils import prefetch

I32 = jnp.int32
U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class DDDShardCapacities:
    """Static shapes (per shard where noted).  ``block``: per-shard rows
    of one frontier window (a window is ``ndev * block`` global rows);
    ``table``: per-shard lossy filter slots (traffic only, never a
    ceiling); ``seg_rows``: per-shard output-buffer rows per segment (the
    worst case a chunk can receive bounds it from below; the buffers hold
    these plus the slack of the stream's last slab (``_buf_rows``); a
    harvest fetches the buffers' head, ``head_rows``, and all of them
    only past it);
    ``flush``: per-shard pending candidates per host dedup pass;
    ``send``: per-destination exchange depth per chunk (None = the safe
    bound ``chunk * A``; smaller trades memory for a loud FAIL_ROUTE: a
    block is ``send`` rows copied and sent whatever it holds, and a slab of
    gathers per 2^14 live lanes of the step beside it, ``mesh.exchange``);
    ``send2``: stage-B depth on 2-D meshes (None = ``nici * send``)."""

    block: int = 1 << 18
    table: int = 1 << 22
    seg_rows: int = 1 << 19
    flush: int = 1 << 22
    levels: int = 1 << 12
    send: Optional[int] = None
    send2: Optional[int] = None
    # "frontier": the single-chip campaign regime on the mesh — master
    # keys in RAM, rows/constraints in disk-backed current+next level
    # files, no trace links (ddd_engine.DDDCapacities.retention docs).
    # Shares the frontier snapshot format and migration with the
    # single-chip engine.
    retention: str = "full"
    # Retain ALL frontier level files for counterexample backtrace
    # (ddd_engine.DDDCapacities.keep_levels docs); tuning, not digest.
    keep_levels: bool = False
    # CP mode (SURVEY §2.9 CP row): every shard expands the SAME window
    # rows over its lane slice (parallel/cp_expand) instead of its own
    # row slice over all lanes — the bag-scan axis shards, the frontier
    # replicates.  Owner exchange, filters and host dedup are unchanged;
    # discovery order is (chunk, lane-slice shard, slot) and joins the
    # digest.  Pays only when bag lanes dominate the fan-out; see the
    # RESULTS.md measurement before choosing it.
    cp: bool = False

    def __post_init__(self):
        if self.retention not in ("full", "frontier"):
            raise ValueError(f"retention={self.retention!r}")
        # table is bitmask-addressed (power of two); block is only window
        # arithmetic and just needs to be chunk-aligned (engine-checked)
        if self.table & (self.table - 1):
            raise ValueError(f"table={self.table} must be a power of two")
        if self.table < BUCKET:
            raise ValueError(
                f"table={self.table} must be >= one bucket ({BUCKET})")


@dataclasses.dataclass(frozen=True)
class _DigestCaps:
    """Checkpoint-identity view: ``block`` + ``ndev`` fix the window
    layout and owner map, ``levels`` bounds the search; filter/buffer
    sizes are timing-only tuning.  Field names, class name and defaults
    deliberately coincide with ddd_engine._DigestCaps (+ ``ndev``,
    default-omitted at 1), so a single-chip DDD checkpoint with block B
    IS a valid 1-device-mesh checkpoint with block B and vice versa —
    the two engines produce identical discovery order there (tested)."""

    block: int = 1 << 20
    levels: int = 1 << 12
    ndev: int = 1
    cp: bool = False


class MFilter(NamedTuple):
    """Per-shard serial device state between segments: lossy filter +
    the replicated chunk cursor within the current window."""

    tbl_hi: jax.Array     # [dev] [TBd, BUCKET]
    tbl_lo: jax.Array     # [dev]
    c: jax.Array          # replicated scalar


class MBufs(NamedTuple):
    """Per-shard candidate-stream output buffers (donated), the engine's
    ``_buf_rows`` rows each; rows at and past a segment's cursor are
    unspecified."""

    okey_hi: jax.Array    # [dev] [OCAP]
    okey_lo: jax.Array    # [dev]
    orows: jax.Array      # [dev] [OCAP, P]
    opar: jax.Array       # [dev] [OCAP] parent id, WINDOW-RELATIVE
                          # (int32-safe at any depth; harvest adds wbase)
    olane: jax.Array      # [dev] [OCAP]
    ocon: jax.Array       # [dev] [OCAP]


class MStats(NamedTuple):
    cursor: jax.Array     # [dev] [1] streamed rows this segment
    n_valid: jax.Array    # [dev] [1] transitions this segment
    fail: jax.Array       # [dev] [1] FAIL_* bits
    viol_pos: jax.Array   # [dev] [1] buffer slot of first violating
    viol_inv: jax.Array   # [dev] [1]   streamed candidate, -1 if none
    dead_g: jax.Array     # [dev] [1] global id of first dead row, -1
    stream_slabs: jax.Array  # [dev] [1] slab writes; == steps unless a
                             #   step streamed more than one slab here
    stream_peak: jax.Array   # [dev] [1] most rows one step streamed here
    route_peak: jax.Array    # [dev] [1] most live lanes one exchange
                             #   packed here
    exchange_slabs: jax.Array  # [dev] [1] trips of the exchanges' gather
                               #   loops (both stages on a 2-D mesh)
    steps: jax.Array      # replicated: chunks executed (pacer signal)
    done: jax.Array       # replicated: window exhausted (reading it off
                          # stats keeps the host from syncing on the
                          # in-flight carry — the pipeline's precondition)


class _MCarry(NamedTuple):
    tbl_hi: jax.Array
    tbl_lo: jax.Array
    okey_hi: jax.Array
    okey_lo: jax.Array
    orows: jax.Array
    opar: jax.Array
    olane: jax.Array
    ocon: jax.Array
    cursor: jax.Array
    n_valid: jax.Array
    fail: jax.Array
    viol_pos: jax.Array
    viol_inv: jax.Array
    dead_g: jax.Array
    stream_slabs: jax.Array
    stream_peak: jax.Array
    route_peak: jax.Array
    exchange_slabs: jax.Array
    c: jax.Array          # replicated
    halt: jax.Array       # replicated: stop event or buffers full


_SHARDED = ("tbl_hi", "tbl_lo", "okey_hi", "okey_lo", "orows", "opar",
            "olane", "ocon", "cursor", "n_valid", "fail", "viol_pos",
            "viol_inv", "dead_g", "stream_slabs", "stream_peak", "route_peak",
            "exchange_slabs")


def _carry_specs(axes):
    ax = axes if len(axes) > 1 else axes[0]
    return _MCarry(**{f: P(ax) if f in _SHARDED else P()
                      for f in _MCarry._fields})


def _exchange_plan(config: CheckConfig, caps: DDDShardCapacities, A: int,
                   ndev: int, nici: int) -> tuple[int, int, int, int]:
    """``(A_loc, Csend, Csend2, NR)``: the lanes a row expands to on one
    shard (all of the action table, or its lane slice in CP mode), the
    two exchange depths, and the rows one lockstep step can deliver to a
    shard — what bounds ``seg_rows`` from below and sizes the stream's
    slabs (``ddd_engine._slab_plan(NR)``)."""
    if caps.cp:
        from raft_tla_tpu.parallel import cp_expand as cpx
        A_loc = cpx.cp_lane_count(config.bounds, config.spec, ndev)
    else:
        A_loc = A
    Csend = caps.send if caps.send is not None else config.chunk * A_loc
    nslice = ndev // nici
    Csend2 = caps.send2 if caps.send2 is not None else nici * Csend
    NR = nici * Csend if nslice == 1 else nslice * Csend2
    return A_loc, Csend, Csend2, NR


def _build_segment(config: CheckConfig, caps: DDDShardCapacities, A: int,
                   W: int, schema: bitpack.BitSchema, ndev: int,
                   nici: int, axes: tuple):
    """One watchdog-safe lockstep slice (<= budget chunks) of the
    window expansion, under shard_map."""
    B = config.chunk
    n_inv = len(config.invariants)
    if n_inv > 29:
        raise ValueError("at most 29 invariants (bit-packed into int32)")
    if caps.cp:
        from raft_tla_tpu.parallel import cp_expand as cpx

        step = cpx.build_cp_step(config.bounds, config.spec,
                                 tuple(config.invariants),
                                 config.symmetry, ndev=ndev,
                                 view=config.view)
        lane_map = jnp.asarray(cpx.cp_lane_map(config.bounds, config.spec,
                                               ndev))     # [ndev, A_loc]
    else:
        # The prescan ladder resolves at build time
        # (kernels._prescan_enabled) — bit-identical keys either way.
        step = kernels.build_step(config.bounds, config.spec,
                                  tuple(config.invariants),
                                  config.symmetry, view=config.view)
    A_loc, Csend, Csend2, NR = _exchange_plan(config, caps, A, ndev, nici)
    BA = B * A_loc
    OCAP = caps.seg_rows
    nslice = ndev // nici
    if OCAP < NR:
        raise ValueError(
            f"seg_rows={OCAP} must be >= per-chunk receivable rows {NR} "
            "(shrink send/send2 or grow seg_rows)")
    BIG = jnp.int32(np.iinfo(np.int32).max)

    def owner(key_hi):
        return (key_hi % jnp.uint32(ndev)).astype(I32)

    # Every closure over the per-call window arrays is built INSIDE
    # segment(), fresh per trace.  The shared-nonlocal-cell pattern the
    # single-chip engine uses is a retrace hazard here: a sharding change
    # on fc.c (fresh jnp scalar on the first window call vs the
    # NamedSharding-committed output afterwards) retraces the pjit, and
    # build-time closures would still hold the PREVIOUS trace's shard_map
    # tracers in their cells — UnexpectedTracerError on the first
    # multi-segment window (caught by review; the parity tests' windows
    # all fit one segment).
    def segment(fc: MFilter, bufs: MBufs, fbuf, fcon, fpar, nrows,
                budget, n_chunks):
        def chunk_body(carry: _MCarry) -> _MCarry:
            (tbl_hi, tbl_lo, okey_hi, okey_lo, orows, opar, olane, ocon,
             cursor, n_valid, fail, viol_pos, viol_inv, dead_g,
             stream_slabs, stream_peak, route_peak, exchange_slabs, c,
             halt) = carry
            cur, nva, fa = cursor[0], n_valid[0], fail[0]
            vpos, vinv, dg = viol_pos[0], viol_inv[0], dead_g[0]

            # ---- expand my chunk (my row slice, or in CP mode the
            # shared rows over my lane slice) ----
            r0 = c * B
            rows_l = r0 + jnp.arange(B, dtype=I32)
            row_act = rows_l < nrows[0]
            bidx = jnp.minimum(rows_l, caps.block - 1)
            # stage scopes as in ddd_engine (kernels.STAGE_SCOPES), plus
            # ``exchange`` around the all_to_all: a device trace names
            # the collective's ops, nested ones included
            with jax.named_scope("unpack"):
                vecs = schema.unpack(fbuf[bidx], jnp)
            row_ok = row_act & fcon[bidx]
            if caps.cp:
                dev = jax.lax.axis_index(_AXIS).astype(I32) \
                    if nslice == 1 else (
                        jax.lax.axis_index(_DCN).astype(I32) * nici
                        + jax.lax.axis_index(_AXIS).astype(I32))
                out = step(vecs, dev)
            else:
                out = step(vecs)
            valid = out["valid"] & row_ok[:, None]
            fvalid = valid.reshape(BA)
            nva = nva + jnp.sum(fvalid.astype(I32))
            fa = fa | jnp.any(fvalid & out["overflow"].reshape(BA)) \
                .astype(I32) * FAIL_WIDTH
            if config.check_deadlock:
                en = jnp.any(out["valid"], axis=1)
                if caps.cp:
                    # a row's enabled lanes are sliced across the mesh
                    en = jax.lax.psum(en.astype(I32), axes) > 0
                dead = row_ok & ~en
                drow = jnp.min(jnp.where(dead, jnp.arange(B, dtype=I32),
                                         BIG))
                dg = jnp.where((drow < BIG) & (dg < 0),
                               fpar[r0 + jnp.minimum(drow, B - 1)], dg)

            # ---- route candidates to their fingerprint owners ----
            fhi = out["fp_hi"].reshape(BA)
            flo = out["fp_lo"].reshape(BA)
            with jax.named_scope("pack"):
                svecs = schema.pack(out["svecs"].reshape(BA, W), jnp)
            par_g = fpar[r0 + jnp.arange(BA, dtype=I32) // A_loc]
            if caps.cp:
                # dense action-table index of each local lane (coverage
                # attribution and trace labels are table-global)
                lane_a = lane_map[dev][jnp.arange(BA, dtype=I32) % A_loc]
            else:
                lane_a = jnp.arange(BA, dtype=I32) % A_loc
            flags = jnp.ones((BA,), I32) | (
                out["con_ok"].reshape(BA).astype(I32) << 1)
            if n_inv:
                iv = out["inv_ok"].reshape(BA, n_inv).astype(I32)
                flags = flags | jnp.sum(
                    iv << (2 + jnp.arange(n_inv, dtype=I32))[None, :],
                    axis=1)

            dest_a = jnp.where(fvalid, owner(fhi) % nici, nici)
            with jax.named_scope("exchange"):
                (r_vec, r_hi, r_lo, r_par, r_lane, r_flags), ovf = \
                    exchange(
                        _AXIS, nici, Csend, dest_a,
                        ((svecs, 0, I32), (fhi, _EMPTY, U32),
                         (flo, _EMPTY, U32), (par_g, -1, I32),
                         (lane_a, -1, I32), (flags, 0, I32)))
            fa = fa | ovf.astype(I32) * FAIL_ROUTE
            # what the exchange packed: its live lanes and its gather's
            # trips (mesh.exchange's return keeps its shape: counted here)
            n_live = jnp.sum((dest_a < nici).astype(I32))
            rpeak = jnp.maximum(route_peak[0], n_live)
            xslabs = exchange_slabs[0] + _slab_trips(n_live, BA)
            active = (r_flags & 1) == 1
            if nslice > 1:
                dest_b = jnp.where(active, owner(r_hi) // nici, nslice)
                with jax.named_scope("exchange"):
                    (r_vec, r_hi, r_lo, r_par, r_lane, r_flags), ovf2 = \
                        exchange(
                            _DCN, nslice, Csend2, dest_b,
                            ((r_vec, 0, I32), (r_hi, _EMPTY, U32),
                             (r_lo, _EMPTY, U32), (r_par, -1, I32),
                             (r_lane, -1, I32), (r_flags, 0, I32)))
                fa = fa | ovf2.astype(I32) * FAIL_ROUTE
                n_live = jnp.sum((dest_b < nslice).astype(I32))
                rpeak = jnp.maximum(rpeak, n_live)
                xslabs = xslabs + _slab_trips(n_live, nici * Csend)
                active = (r_flags & 1) == 1

            # ---- owner-side lossy filter; stream to my buffer ----
            with jax.named_scope("filter_insert"):
                tbl_hi, tbl_lo, n_stream, compact, _ = \
                    _filter_insert_ordered(tbl_hi, tbl_lo, r_hi, r_lo,
                                           active)
            with jax.named_scope("stream"):
                # the one-chip stage (ddd_engine._write_slabs): slabs of
                # the received lanes in the filter's compaction order —
                # batch order, what a cumsum over a lane-order mask gives —
                # laid down at my cursor.  The trip count is per shard; no
                # collective runs inside the loop.
                def gather(sel):
                    # the packed rows word by word, as the one-chip stage
                    rows = jnp.stack([r_vec[:, p][sel]
                                      for p in range(schema.P)], axis=1)
                    return (r_hi[sel], r_lo[sel], rows, r_par[sel],
                            r_lane[sel], ((r_flags[sel] >> 1) & 1) == 1)

                def watch(seen, sel, live, at):
                    # first violating streamed candidate (relaxed stop),
                    # found in the slab from the flags it gathers anyway
                    # (the slab's own gather of r_flags, one op once XLA
                    # has merged the two): slabs come in stream order, so
                    # the first hit is the least buffer slot
                    vpos, vinv = seen
                    fl = r_flags[sel]
                    bad = live & ((fl >> 2) & ((1 << n_inv) - 1)
                                  != (1 << n_inv) - 1)
                    first = jnp.argmax(bad).astype(I32)
                    hit = bad[first] & (vpos < 0)
                    binv = jnp.argmax(
                        ((fl[first] >> 2) & (1 << jnp.arange(n_inv))) == 0
                    ).astype(I32)
                    return (jnp.where(hit, at + first, vpos),
                            jnp.where(hit, binv, vinv))

                ((okey_hi, okey_lo, orows, opar, olane, ocon), n_slabs,
                 (vpos, vinv)) = _write_slabs(
                    (okey_hi, okey_lo, orows, opar, olane, ocon), cur,
                    n_stream, compact, NR, gather,
                    watch if n_inv else None, (vpos, vinv))
                cur = cur + n_stream
                slabs = stream_slabs[0] + n_slabs
                peak = jnp.maximum(stream_peak[0], n_stream)

            # ---- lockstep continue/halt (replicated collectives) ----
            stop_ev = jax.lax.psum(
                ((vpos >= 0) | (dg >= 0) | (fa != 0)).astype(I32),
                axes) > 0
            full = jax.lax.pmax((cur + NR > OCAP).astype(I32), axes) > 0
            return _MCarry(tbl_hi, tbl_lo, okey_hi, okey_lo, orows, opar,
                           olane, ocon, cur[None], nva[None], fa[None],
                           vpos[None], vinv[None], dg[None], slabs[None],
                           peak[None], rpeak[None], xslabs[None], c + 1,
                           stop_ev | full)

        def cond(sc):
            s, carry = sc
            return (carry.c < n_chunks) & ~carry.halt & (s < budget)

        def body(sc):
            s, carry = sc
            return s + 1, chunk_body(carry)

        z1 = jnp.zeros((1,), I32)
        carry = _MCarry(
            fc.tbl_hi, fc.tbl_lo, *bufs,
            cursor=z1, n_valid=z1, fail=z1,
            viol_pos=z1 - 1, viol_inv=z1, dead_g=z1 - 1,
            stream_slabs=z1, stream_peak=z1, route_peak=z1,
            exchange_slabs=z1,
            c=fc.c, halt=jnp.bool_(False))
        steps, carry = jax.lax.while_loop(cond, body,
                                          (jnp.int32(0), carry))
        return (MFilter(carry.tbl_hi, carry.tbl_lo, carry.c),
                MBufs(carry.okey_hi, carry.okey_lo, carry.orows,
                      carry.opar, carry.olane, carry.ocon),
                MStats(carry.cursor, carry.n_valid, carry.fail,
                       carry.viol_pos, carry.viol_inv, carry.dead_g,
                       carry.stream_slabs, carry.stream_peak,
                       carry.route_peak, carry.exchange_slabs,
                       steps, carry.c >= n_chunks))

    return segment


def _dd_filter_shard(backend):
    """Per-shard devdedup export filter (ops/devdedup) for the local
    view under shard_map: drop lanes whose key already streamed from
    THIS shard this level and compact survivors in stream order.  Owner
    routing funnels all duplicates of a key through one shard, but the
    filter does not rely on it — a drop is sound whenever the key
    streamed earlier from the *same* shard, which is exactly what the
    per-shard set records.  ``viol_pos`` is a buffer SLOT, so it is
    remapped through the compaction (the violator itself always
    survives: an equal earlier candidate would have violated first and
    stopped the run at its own segment)."""
    filt = devdedup.make_filter(backend)

    def apply(dstate, bufs, cursor, viol_pos):
        stt = devdedup.DevSet(dstate.hi, dstate.lo, dstate.n[0])
        stt, keep, idx, new_n, hits = filt(
            stt, bufs.okey_hi, bufs.okey_lo, cursor[0])
        nbufs = MBufs(
            okey_hi=bufs.okey_hi[idx], okey_lo=bufs.okey_lo[idx],
            orows=bufs.orows[idx], opar=bufs.opar[idx],
            olane=bufs.olane[idx], ocon=bufs.ocon[idx])
        vp = viol_pos[0]
        kpos = jnp.cumsum(keep.astype(I32))
        nvp = jnp.where(
            vp >= 0, kpos[jnp.clip(vp, 0, keep.shape[0] - 1)] - 1, vp)
        return (devdedup.DevSet(stt.hi, stt.lo, stt.n[None]), nbufs,
                new_n[None], hits[None], nvp[None])

    return apply


def head_rows(caps: DDDShardCapacities) -> int:
    """Rows of each shard's output buffers that a harvest fetches unless
    a cursor outgrew them: the power of two at or under ``seg_rows / 16``.
    ``seg_rows`` is sized for the worst case (every lane of every chip
    streaming to one owner), a segment's stream for what the frontier
    admits — 19 rows at level 3 of a space whose buffers hold 1,245,184 —
    so the head is what crosses d2h and the whole buffers are the
    fallback."""
    return 1 << max(0, (caps.seg_rows // 16).bit_length() - 1)


def _build_head(H: int):
    """The first ``H`` rows of a shard's six output arrays as arrays of
    their own (local view under shard_map): a static slice, one program
    whatever streamed."""
    def head(bufs: MBufs) -> MBufs:
        return MBufs(*(a[:H] for a in bufs))

    return head


class DDDShardEngine:
    """Mesh-wide exhaustive checker with host-exact sharded dedup."""

    SEG_TARGET_S = 8.0
    SEG_CLAMP_S = 25.0
    SEG_MIN, SEG_MAX = 4, 1 << 16

    def __init__(self, config: CheckConfig, mesh: Mesh | None = None,
                 caps: DDDShardCapacities | None = None,
                 seg_chunks: int = 64):
        self.config = config
        self.bounds = config.bounds
        if not resolve_model(config.spec).is_raft:
            # the mesh segment builds Raft's step and Raft's packed row;
            # only the one-chip ddd engine takes them from the registry
            raise ValueError(
                f"the ddd-shard engine does not run spec {config.spec!r}: "
                "its segment is built for Raft's row (use --engine ddd)")
        self.lay = st.Layout.of(self.bounds)
        self.table = S.action_table(self.bounds, config.spec)
        self.A = len(self.table)
        self.mesh = mesh if mesh is not None else make_mesh()
        self.ndev = self.mesh.devices.size
        self.caps = caps or DDDShardCapacities()
        if self.caps.block < config.chunk or \
                self.caps.block % config.chunk:
            raise ValueError(
                "block must be a multiple of chunk (chunk-local frontier "
                "indexing assumes whole chunks per window slice)")
        self.seg_chunks = seg_chunks
        self._digest_caps = _DigestCaps(block=self.caps.block,
                                        levels=self.caps.levels,
                                        ndev=self.ndev,
                                        cp=self.caps.cp)
        self.schema = bitpack.BitSchema(self.bounds)
        # RAFT_TLA_HOSTDEDUP: per-shard masters ride the partitioned
        # keyset and the process-shared dedup pool.  Shard ownership is
        # hi mod ndev — orthogonal to the keyset's top-bit partitioning,
        # so every shard splits evenly.  The flush itself stays
        # synchronous here: the canonical (level, window, shard) drain
        # order is fixed at window boundaries, not flush time.
        self._host_dedup = keyset.host_dedup_enabled()
        # RAFT_TLA_PREFETCH: the next window's rows are read and staged
        # by a daemon thread while the devices expand the current one
        # (utils/prefetch).  Flushes stay synchronous and the canonical
        # (level, window, shard) drain order is untouched — the prefetch
        # only reads rows published before the level began, disjoint
        # from anything the window-boundary drain appends.
        self._prefetch = prefetch.prefetch_enabled()
        # RAFT_TLA_DEVDEDUP: per-shard device-resident exact within-
        # level sets filter each segment's output buffers before export
        # (ops/devdedup).  Per-shard drops are sound regardless of key
        # routing (a drop proves the key already streamed from the same
        # shard), and the canonical (level, window, shard) drain order
        # is untouched — the filter only thins each shard's stream.
        # NOT part of the digest: resume across either gate setting.
        self._devdedup = devdedup.devdedup_backend()
        self._merge_budget = max(1 << 16,
                                 (8 * self.caps.flush)
                                 // keyset.DEFAULT_PARTS)
        axes = _mesh_axes(self.mesh)
        nici = self.mesh.shape[_AXIS]
        specs = _carry_specs(axes)
        self._ax = axes if len(axes) > 1 else axes[0]
        fc_specs = MFilter(specs.tbl_hi, specs.tbl_lo, P())
        buf_specs = MBufs(*(getattr(specs, f) for f in MBufs._fields))
        st_specs = MStats(*(getattr(specs, f)
                            for f in MStats._fields[:-2]), P(), P())
        dp = P(self._ax)
        # what the step below is built with, for the ``pass`` span (the
        # dense and the CP step both go through kernels.apply_stages)
        self._prescan = kernels._prescan_enabled(config.bounds,
                                                 config.symmetry)
        fn = _build_segment(config, self.caps, self.A, self.lay.width,
                            self.schema, self.ndev, nici, axes)
        # rows of one shard's segment buffers: seg_rows plus the slack
        # that keeps a step's last slab inside them (0 where a step
        # receives whole slabs), fixed here with the program that writes
        # the slabs
        NR = _exchange_plan(config, self.caps, self.A, self.ndev, nici)[3]
        self._buf_rows = self.caps.seg_rows + _slab_plan(NR)[1]
        self._segment = jax.jit(
            jax.shard_map(fn, mesh=self.mesh,
                          in_specs=(fc_specs, buf_specs, dp, dp, dp, dp,
                                    P(), P()),
                          out_specs=(fc_specs, buf_specs, st_specs),
                          check_vma=False),
            donate_argnums=(0, 1))
        self._dd_apply = None
        if self._devdedup:
            dd_specs = devdedup.DevSet(dp, dp, dp)
            self._dd_apply = jax.jit(
                jax.shard_map(_dd_filter_shard(self._devdedup),
                              mesh=self.mesh,
                              in_specs=(dd_specs, buf_specs, dp, dp),
                              out_specs=(dd_specs, buf_specs, dp, dp, dp),
                              check_vma=False),
                donate_argnums=(0, 1))
        # The harvest's d2h (see _check_impl): dispatched between segment
        # k and segment k+1, so it never queues behind the speculative
        # segment, and a static slice, so it compiles once.
        self._head_rows = head_rows(self.caps)
        self._head = jax.jit(
            jax.shard_map(_build_head(self._head_rows), mesh=self.mesh,
                          in_specs=(buf_specs,), out_specs=buf_specs,
                          check_vma=False))
        self._in_shardings = [
            NamedSharding(self.mesh, dp) for _ in range(4)]
        # window staging, lazy-alloc: one buffer set per prefetch slot
        # (slot 0 doubles as the gate-off synchronous path's buffers)
        self._gstage: list = [None, None]

    # -- device-side helpers --------------------------------------------

    def _init_filter(self) -> MFilter:
        TBd = self.caps.table // BUCKET
        sh = NamedSharding(self.mesh, P(self._ax))
        return MFilter(
            tbl_hi=jax.device_put(
                np.full((self.ndev * TBd, BUCKET), _EMPTY, np.uint32), sh),
            tbl_lo=jax.device_put(
                np.full((self.ndev * TBd, BUCKET), _EMPTY, np.uint32), sh),
            c=jnp.int32(0))

    def _init_devset(self):
        one = devdedup.init_set(self.caps.table, self._devdedup)
        nd = self.ndev
        reps = (nd, 1) if one.hi.ndim == 2 else nd
        sh = NamedSharding(self.mesh, P(self._ax))
        return devdedup.DevSet(
            hi=jax.device_put(np.tile(one.hi, reps), sh),
            lo=jax.device_put(np.tile(one.lo, reps), sh),
            n=jax.device_put(np.zeros((nd,), np.int32), sh))

    def _make_bufs(self) -> MBufs:
        OCAP = self._buf_rows
        nd = self.ndev
        sh = NamedSharding(self.mesh, P(self._ax))
        z = lambda shape, dt, fill=0: jax.device_put(  # noqa: E731
            np.full(shape, fill, dt), sh)
        return MBufs(
            okey_hi=z((nd * OCAP,), np.uint32),
            okey_lo=z((nd * OCAP,), np.uint32),
            orows=z((nd * OCAP, self.schema.P), np.int32),
            opar=z((nd * OCAP,), np.int32),
            olane=z((nd * OCAP,), np.int32),
            ocon=z((nd * OCAP,), bool))

    def _upload_window(self, host, constore, wbase: int, wrows: int,
                       slot: int = 0):
        """Sharded upload of one frontier window: shard s expands global
        rows [wbase + s*block, ...); parent ids ride along.  The host
        staging buffers are allocated once per slot (inter-window
        critical path: devices idle during upload) and only their live
        prefix is rewritten — rows past ``wrows`` are masked off by
        ``nrows``, so stale tail contents are never read.  ``slot``
        selects the staging buffer set: the upload prefetcher
        double-buffers so staging window k+1 never scribbles over the
        buffers window k was uploaded from."""
        nd, Fcap = self.ndev, self.caps.block
        if self._gstage[slot] is None:
            self._gstage[slot] = (
                np.zeros((nd * Fcap, self.schema.P), np.int32),
                np.zeros((nd * Fcap,), bool))
        gbuf, gcon = self._gstage[slot]
        if self.caps.cp:
            # CP mode: every shard expands the SAME rows (its lane slice)
            blk = host.read(wbase, wrows)
            con = constore.read(wbase, wrows)[:, 0]
            for s in range(nd):
                gbuf[s * Fcap:s * Fcap + wrows] = blk
                gcon[s * Fcap:s * Fcap + wrows] = con
            # WINDOW-RELATIVE parent ids (fit int32 at any campaign
            # depth); the harvest rebases by adding wbase as int64
            gpar = np.tile(np.arange(Fcap), nd).astype(np.int32)
            nrows = np.full((nd,), wrows, np.int32)
        else:
            gbuf[:wrows] = host.read(wbase, wrows)
            gcon[:wrows] = constore.read(wbase, wrows)[:, 0]
            gpar = np.arange(nd * Fcap, dtype=np.int32)  # window-relative
            nrows = np.clip(wrows - np.arange(nd) * Fcap, 0, Fcap) \
                .astype(np.int32)
        sh = self._in_shardings
        return (jax.device_put(gbuf, sh[0]),
                jax.device_put(gcon, sh[1]),
                jax.device_put(gpar, sh[2]), jax.device_put(nrows, sh[3]),
                int(nrows.max() + self.config.chunk - 1)
                // self.config.chunk)

    # -- host dedup ------------------------------------------------------

    def _flush_shard(self, s, pend, masters, staging) -> int:
        """Exact-dedup shard ``s``'s pending stream into its staging (new
        states await the window-boundary drain).  Order within the shard
        stream is preserved; keys land in the master immediately so later
        flushes anti-join correctly."""
        if not pend[s]["keys"]:
            return 0
        keys = np.concatenate(pend[s]["keys"])
        new_idx = masters[s].dedup(keys)
        n_new = int(new_idx.size)
        if n_new:
            staging[s]["keys"].append(keys[new_idx])
            fields = ("rows", "lane", "con") if not pend[s]["par"] \
                else ("rows", "par", "lane", "con")
            for f in fields:
                staging[s][f].append(np.concatenate(pend[s][f])[new_idx])
        for lst in pend[s].values():
            lst.clear()
        return n_new

    def _drain(self, staging, host, constore, keystore, cov) -> int:
        """Window-boundary drain: append every shard's staged new states
        to the global store in shard order — the canonical merge point
        that fixes global discovery order."""
        n = 0
        for s in range(self.ndev):
            if not staging[s]["keys"]:
                continue
            keys = np.concatenate(staging[s]["keys"])
            rows = np.concatenate(staging[s]["rows"])
            lane = np.concatenate(staging[s]["lane"])
            con = np.concatenate(staging[s]["con"])
            host.append(rows)
            if self.caps.retention == "full":
                par = np.concatenate(staging[s]["par"])
                host.append_links(par, lane)
            constore.append(con.astype(np.int32)[:, None])
            keystore.append(np.stack(
                [(keys & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                 (keys >> np.uint64(32)).astype(np.uint32)],
                axis=1).view(np.int32))
            cov += np.bincount(lane, minlength=self.A)
            n += keys.size
            for lst in staging[s].values():
                lst.clear()
        return n

    # -- checkpoint / resume ---------------------------------------------

    def save_checkpoint(self, path, host, constore, keystore, n_states,
                        n_trans, cov, level_ends, blocks_done,
                        init_key) -> None:
        """Window-boundary snapshots (pending + staging empty); the
        shared ddd_engine snapshot format — see reshard_ddd_checkpoint."""
        digest = ckpt.config_digest(self.config, self._digest_caps,
                                    init_key)
        if self.caps.retention == "frontier":
            save_frontier_snapshot(path, host, constore, keystore,
                                   n_states, n_trans, cov, level_ends,
                                   blocks_done, digest,
                                   keep_levels=self.caps.keep_levels)
        else:
            save_ddd_snapshot(path, host, constore, keystore, n_states,
                              n_trans, cov, level_ends, blocks_done,
                              self.schema.P, digest)

    def load_checkpoint(self, path, init_key):
        digest = ckpt.config_digest(self.config, self._digest_caps,
                                    init_key)
        load = load_frontier_snapshot \
            if self.caps.retention == "frontier" else load_ddd_snapshot
        (host, constore, keystore, n_states, n_trans, cov, level_ends,
         blocks_done) = load(path, self.schema.P, digest)
        masters = self._rebuild_masters(keystore, n_states, source=path)
        return (host, constore, keystore, masters, n_states, n_trans,
                cov, level_ends, blocks_done)

    def _new_master(self):
        return keyset.new_master(self._host_dedup,
                                 merge_budget=self._merge_budget)

    def _rebuild_masters(self, keystore, n_states, source="checkpoint"):
        kw = keystore.read(0, n_states).view(np.uint32)
        keys = keyset.pack_keys(kw[:, 1], kw[:, 0])
        own = (kw[:, 1] % np.uint32(self.ndev)).astype(np.int64)
        # master_from_keys dedupe-checks per shard and (partitioned)
        # sorts per partition on the shared pool, naming the snapshot in
        # the corruption diagnostic
        masters = [
            keyset.master_from_keys(
                keys[own == s], source=source,
                partitioned=self._host_dedup,
                merge_budget=self._merge_budget)
            for s in range(self.ndev)]
        if sum(len(m) for m in masters) != n_states:
            raise ValueError(
                f"checkpoint key log partitions to "
                f"{sum(len(m) for m in masters)} keys for {n_states} "
                "states — stream corrupt")
        return masters

    # -- main loop --------------------------------------------------------

    def check(self, init_override: interp.PyState | None = None,
              on_progress=None, checkpoint: str | None = None,
              checkpoint_every_s: float = 600.0,
              resume: str | None = None,
              events: str | None = None) -> EngineResult:
        import contextlib
        from raft_tla_tpu.ddd_engine import install_sigint_boundary_stop
        with contextlib.ExitStack() as stack:
            install_sigint_boundary_stop(self, stack, boundary="window")
            return self._check_impl(init_override, on_progress,
                                    checkpoint, checkpoint_every_s,
                                    resume, stack, events)

    def _check_impl(self, init_override, on_progress, checkpoint,
                    checkpoint_every_s, resume, _cleanup,
                    events=None) -> EngineResult:
        t0 = time.monotonic()
        tel = RunTelemetry(
            "ddd-shard", config=self.config, caps=self.caps,
            on_progress=on_progress, events=events,
            resumed=resume is not None, n0=1,
            n_devices=self.ndev, t0=t0, level_log=True)
        _cleanup.callback(tel.close)
        # the ddd engine's span tree, where this loop has the same seams
        # (host side only): pass > level > upload / expand / export >
        # {segment_wait, d2h} / level_close; traced or not, the same sites
        # feed the pass ledger (obs/passlog)
        tr = tel.trace
        pass_sp = tr.open("pass", engine="ddd-shard",
                          resumed=resume is not None,
                          prescan=self._prescan)
        _cleanup.callback(pass_sp.close)     # raise paths; idempotent
        bounds = self.bounds
        init_py = init_override if init_override is not None \
            else interp.init_state(bounds)
        init_vec = interp.to_vec(init_py, bounds)
        hi0, lo0 = sym_mod.init_fingerprint(self.config, init_py, init_vec)

        for nm in self.config.invariants:
            if not inv_mod.py_invariant(nm)(init_py, bounds):
                from collections import Counter
                res = EngineResult(
                    n_states=1, diameter=0, n_transitions=0,
                    coverage=Counter(),
                    violation=Violation(nm, init_py, [(None, init_py)]),
                    levels=[1], wall_s=time.monotonic() - t0,
                    level_log=tel.passlog.record)
                pass_sp.set(levels=1, n_states=1,
                            stopped_by="violation").close()
                tel.run_end(res)
                return res

        frontier = self.caps.retention == "frontier"
        tmpdir = None
        if frontier:
            checkpoint, checkpoint_every_s, tmpdir = \
                frontier_checkpoint_setup(resume, checkpoint,
                                          checkpoint_every_s, _cleanup,
                                          "dddshard_frontier_")
        _SUFFIXES = (".rows", ".links", ".con", ".keys")
        if checkpoint and not (resume and os.path.abspath(resume)
                               == os.path.abspath(checkpoint)):
            import glob as _glob
            for suf in _SUFFIXES:
                try:
                    os.remove(checkpoint + suf)
                except FileNotFoundError:
                    pass
            for pat in (".rowsL*", ".conL*"):
                for pth in _glob.glob(checkpoint + pat):
                    try:
                        os.remove(pth)
                    except OSError:
                        pass
        if resume:
            (host, constore, keystore, masters, n_states, n_trans, cov,
             level_ends, blocks_done) = self.load_checkpoint(
                resume, (hi0, lo0))
            if checkpoint and os.path.abspath(resume) == \
                    os.path.abspath(checkpoint) and not frontier:
                for suf, w in ((".rows", self.schema.P), (".links", 3),
                               (".con", 1), (".keys", 2)):
                    ckpt.trim_stream(checkpoint + suf, n_states, w)
        else:
            if frontier:
                host = native.LevelStore(checkpoint + ".rows",
                                         self.schema.P, 1, 0, 1,
                                         reset=True)
                constore = native.LevelStore(checkpoint + ".con", 1, 1,
                                             0, 1, reset=True)
                keystore = native.FileStore(checkpoint + ".keys", 2, 0,
                                            reset=True)
            else:
                host = native.make_store(self.schema.P)
                constore = native.make_store(1)
                keystore = native.make_store(2)
            masters = [self._new_master() for _ in range(self.ndev)]
            k0 = int(keyset.pack_keys(np.uint32(hi0)[None],
                                      np.uint32(lo0)[None])[0])
            masters[int(np.uint32(hi0) % np.uint32(self.ndev))].seed(k0)
            init_row = self.schema.pack(
                np.asarray(init_vec, np.int32), np)[None, :]
            con_row = np.asarray(
                [[interp.constraint_ok(init_py, bounds)]], np.int32)
            if frontier:
                host.cur.append(init_row)
                constore.cur.append(con_row)
            else:
                host.append(init_row)
                host.append_links(np.asarray([-1], np.int64),
                                  np.asarray([-1], np.int32))
                constore.append(con_row)
            keystore.append(np.asarray(
                [[np.uint32(lo0), np.uint32(hi0)]],
                np.uint32).view(np.int32))
            n_states = 1
            n_trans = 0
            cov = np.zeros(self.A, np.int64)
            level_ends = [1]
            blocks_done = 0

        fc = self._init_filter()
        # run_start.n_devices is a statement about where the carry lives,
        # so hold it to the placement JAX reports, not to the request.
        placed = {s.device for s in fc.tbl_hi.addressable_shards}
        if len(placed) != self.ndev:
            raise RuntimeError(
                f"filter carry landed on {len(placed)} distinct device(s) "
                f"of a {self.ndev}-device mesh: {sorted(map(str, placed))}")
        dst = self._init_devset() if self._dd_apply else None
        export_rows = 0      # rows actually exported d2h (post-filter)
        dd_hits = 0          # rows the per-shard device sets dropped
        bufsets = [self._make_bufs(), self._make_bufs()]
        pend = [{"keys": [], "rows": [], "par": [], "lane": [], "con": []}
                for _ in range(self.ndev)]
        staging = [{"keys": [], "rows": [], "par": [], "lane": [],
                    "con": []} for _ in range(self.ndev)]
        # global window rows: row-sharded in DP mode, replicated in CP
        W = self.caps.block if self.caps.cp \
            else self.ndev * self.caps.block
        # Upload prefetcher (RAFT_TLA_PREFETCH): stage window k+1 on a
        # daemon thread while the devices expand window k.  Reads hit
        # rows < level_ends[-1] only — disjoint from everything the
        # window-boundary drain appends (>= level_ends[-1]), the store
        # concurrency contract (utils/native) — and the canonical
        # (level, window, shard) drain order is untouched.
        prefetcher = None
        if self._prefetch:
            def pf_load(wb, wr, slot):
                # range-disjointness precondition (utils/prefetch)
                assert wb + wr <= level_ends[-1], \
                    (wb, wr, level_ends[-1])
                out = self._upload_window(host, constore, wb, wr,
                                          slot=slot)
                jax.block_until_ready(out[:4])
                return out

            prefetcher = prefetch.BlockPrefetcher(
                pf_load, phases=tel.phases, tracer=tel.trace)
            _cleanup.callback(prefetcher.close)
        OCAP = self._buf_rows       # a shard's rows of the whole buffers
        H = self._head_rows
        # one row of the six output arrays (the ddd engine's d2h ``bytes``)
        row_bytes = self.schema.P * 4 + 17
        fail = 0
        viol = None        # (kind, inv_idx, key_or_gid) once detected
        stopped = False
        complete = True    # False on a graceful SIGINT window-boundary stop
        pacer = pacing.SegmentPacer(self.seg_chunks, self.SEG_MIN,
                                    self.SEG_MAX, self.SEG_TARGET_S,
                                    self.SEG_CLAMP_S)
        budget = pacer.budget
        last_ckpt = time.monotonic()
        tel.run_start(n_states=n_states)

        def progress():
            if not tel.active:
                return
            # report the same INCLUSIVE count the old stats stream did:
            # bare n_states only advances at window-boundary drains,
            # which would read as 0-0-spike.  Staged counts are exact
            # (post-dedup); pend is the raw harvested stream, so the sum
            # is an upper bound — same contract as the single-chip
            # engine's progress().  The tracker's running-max anchor
            # keeps the post-drain dip from reading as a negative rate.
            n_incl = n_states + sum(
                sum(len(k) for k in st_["keys"]) for st_ in staging) \
                + sum(sum(len(k) for k in p_["keys"]) for p_ in pend)
            tel.segment(
                n_states=n_states, n_incl=n_incl,
                level=len(level_ends), n_transitions=n_trans,
                coverage=dict(aggregate_coverage(self.table, cov)),
                upload_wait_ms=round(prefetcher.wait_s * 1e3, 3)
                if prefetcher else None,
                prefetch_hits=prefetcher.hits if prefetcher else None,
                export_rows=export_rows,
                dev_dedup_hits=dd_hits if self._dd_apply else None)

        lvl_segs = lvl_steps = lvl_rows = 0  # the open level's work
        # the stream stage's slab writes (MStats): the most any shard
        # wrote, summed over the level's segments, and the most rows any
        # shard streamed in one step; and the exchange's two likewise
        lvl_slabs = lvl_peak = lvl_xslabs = lvl_route = 0

        def end_level():
            level_sp.set(segments=lvl_segs, steps=lvl_steps,
                         streamed_rows=lvl_rows,
                         stream_slabs=lvl_slabs, stream_peak=lvl_peak,
                         exchange_slabs=lvl_xslabs, route_peak=lvl_route,
                         new_states=n_states - lvl_hi).close()

        while not stopped:
            lvl_lo = level_ends[-2] if len(level_ends) > 1 else 0
            lvl_hi = level_ends[-1]
            w0 = lvl_lo + blocks_done * W
            # explicit handle: every exit of the body lands on
            # end_level(), here or after the loop (close is idempotent)
            level_sp = tr.open("level", level=len(level_ends),
                               rows=lvl_hi - lvl_lo,
                               row_words=self.schema.P,
                               blocks=-(-(lvl_hi - w0) // W))
            lvl_segs = lvl_steps = lvl_rows = lvl_slabs = lvl_peak = 0
            lvl_xslabs = lvl_route = 0
            if prefetcher is not None and w0 < lvl_hi:
                # level start: all window addresses are known — warm the
                # first window immediately
                prefetcher.schedule(w0, min(W, lvl_hi - w0))
            for wbase in range(w0, lvl_hi, W):
                wrows = min(W, lvl_hi - wbase)
                if prefetcher is not None:
                    # hit: swap to the staged, already-resident window;
                    # miss: the loader runs inline, same bytes either way
                    with tel.phases.phase("upload"):
                        fbuf, fcon, fpar, nrows, n_chunks = \
                            prefetcher.take(wbase, wrows)
                    nxtw = wbase + W
                    if nxtw < lvl_hi:
                        prefetcher.schedule(nxtw, min(W, lvl_hi - nxtw))
                else:
                    with tel.phases.phase("upload") as ph:
                        fbuf, fcon, fpar, nrows, n_chunks = \
                            self._upload_window(host, constore, wbase,
                                                wrows)
                        ph.sync((fbuf, fcon, fpar))
                fc = fc._replace(c=jnp.int32(0))
                # Two-deep segment pipeline (the ddd_engine PP overlap):
                # segment k+1 depends on k only through the filter carry,
                # so it is dispatched BEFORE k's stats/buffers are
                # harvested — d2h transfer and host dedup overlap device
                # compute.  Dispatch order == harvest order == stream
                # order, so the canonical-order argument is unchanged; a
                # segment harvested AFTER a stop event is dropped whole
                # (its chunks lie past the chunk-granular stop point),
                # and one dispatched past the window's last chunk runs
                # zero chunks.
                #
                # What a harvest fetches is the HEAD of segment k's buffers
                # (the first ``head_rows`` of each shard's six arrays),
                # sliced by a program dispatched right here, between
                # segment k and segment k+1: dispatched at harvest time it
                # would queue BEHIND the speculative segment k+1 on the
                # serial device queue and stall the harvest for a whole
                # segment (the ddd engine's note at its own d2h), and a
                # slice of ``cursor`` rows would be a new program at every
                # harvest.  The whole ``seg_rows`` buffers, sized for every
                # lane of every chip streaming to one owner, cross only
                # when some shard's cursor outgrew the head.
                q = []               # in-flight: (bufset idx, head, stats, t)
                free = list(range(len(bufsets)))
                window_done = False
                t_last_harvest = time.monotonic()
                while q or not (window_done or stopped):
                    if not (window_done or stopped) and free:
                        idx = free.pop(0)
                        t_disp = time.monotonic()
                        # NB: enabling phase timers blocks each dispatch,
                        # trading the two-deep overlap for honest walls
                        with tel.phases.phase("expand") as ph:
                            fc, bufsets[idx], stats = self._segment(
                                fc, bufsets[idx], fbuf, fcon, fpar,
                                nrows, jnp.int32(budget),
                                jnp.int32(n_chunks))
                            ph.sync(stats)
                        ncur = dhits = nvp = None
                        if self._dd_apply is not None:
                            # dispatch order == per-shard stream order,
                            # so each shard's set carry reflects exactly
                            # its rows streamed before this segment
                            with tel.phases.phase("devdedup") as ph:
                                (dst, bufsets[idx], ncur, dhits,
                                 nvp) = self._dd_apply(
                                    dst, bufsets[idx], stats.cursor,
                                    stats.viol_pos)
                                ph.sync(ncur)
                        # after the compaction where the gate is on: the
                        # head holds the post-filter stream
                        head = self._head(bufsets[idx])
                        q.append((idx, head, stats, ncur, dhits, nvp,
                                  t_disp))
                        if len(q) < 2:
                            continue         # keep the pipeline full
                    if not q:
                        break
                    idx, head, stats, ncur, dhits, nvp, t_disp = q.pop(0)
                    with tel.phases.phase("export"):
                        with tr.span("segment_wait"):
                            st_h = jax.device_get(stats)
                            # gate on: harvest the POST-filter cursors —
                            # dropped rows never cross d2h at all
                            cursors = np.asarray(st_h.cursor) \
                                if ncur is None \
                                else np.asarray(jax.device_get(ncur))
                        lvl_segs += 1
                        lvl_steps += int(st_h.steps)
                        lvl_slabs += int(np.max(st_h.stream_slabs))
                        lvl_peak = max(lvl_peak,
                                       int(np.max(st_h.stream_peak)))
                        lvl_xslabs += int(np.max(st_h.exchange_slabs))
                        lvl_route = max(lvl_route,
                                        int(np.max(st_h.route_peak)))
                        bufs_h = None
                        # ``stride``: rows a shard of the fetched arrays
                        src, stride, path = (head, H, "head") \
                            if cursors.max() <= H \
                            else (bufsets[idx], OCAP, "whole")
                        if cursors.sum() and not stopped:
                            with tr.span(
                                    "d2h", rows=int(cursors.sum()),
                                    bytes=self.ndev * stride * row_bytes,
                                    path=path):
                                bufs_h = jax.device_get(src)
                    free.append(idx)
                    if stopped:
                        continue             # drop post-stop segments
                    # harvest per shard in shard order
                    for s in range(self.ndev):
                        ns = int(cursors[s])
                        if not ns:
                            continue
                        o = s * stride
                        pend[s]["keys"].append(keyset.pack_keys(
                            bufs_h.okey_hi[o:o + ns],
                            bufs_h.okey_lo[o:o + ns]))
                        pend[s]["rows"].append(
                            bufs_h.orows[o:o + ns].copy())
                        if not frontier:
                            pend[s]["par"].append(   # rebase to global
                                bufs_h.opar[o:o + ns].astype(np.int64)
                                + wbase)
                        pend[s]["lane"].append(
                            bufs_h.olane[o:o + ns].copy())
                        pend[s]["con"].append(
                            bufs_h.ocon[o:o + ns].copy())
                    n_trans += int(np.asarray(st_h.n_valid).sum())
                    lvl_rows += int(cursors.sum())
                    export_rows += int(cursors.sum())
                    if dhits is not None:
                        dd_hits += int(np.asarray(
                            jax.device_get(dhits)).sum())
                    fail |= int(np.bitwise_or.reduce(
                        np.asarray(st_h.fail)))
                    # gate on: viol_pos remapped through the compaction
                    vpos = np.asarray(st_h.viol_pos) if nvp is None \
                        else np.asarray(jax.device_get(nvp))
                    dgs = np.asarray(st_h.dead_g)
                    if fail:
                        stopped = True
                        continue
                    elif (vpos >= 0).any():
                        s = int(np.nonzero(vpos >= 0)[0][0])
                        viol = (1, int(np.asarray(st_h.viol_inv)[s]),
                                int(keyset.pack_keys(
                                    bufs_h.okey_hi[s * stride + vpos[s]]
                                    [None],
                                    bufs_h.okey_lo[s * stride + vpos[s]]
                                    [None])[0]))
                        stopped = True
                        continue
                    elif (dgs >= 0).any():
                        s = int(np.nonzero(dgs >= 0)[0][0])
                        viol = (2, 0, int(dgs[s]) + wbase)
                        stopped = True
                        continue
                    now = time.monotonic()
                    # own device time ~ since the later of my dispatch
                    # and the previous harvest (queue wait excluded);
                    # zero-chunk speculative segments carry no signal
                    if int(st_h.steps) > 0:
                        budget = pacer.update(
                            now - max(t_disp, t_last_harvest),
                            int(st_h.steps))
                        self.seg_chunks = budget
                    t_last_harvest = now
                    window_done = window_done or bool(st_h.done)
                    flushed = False
                    for s in range(self.ndev):
                        if sum(len(x) for x in pend[s]["keys"]) >= \
                                self.caps.flush:
                            with tel.phases.phase("dedup"):
                                self._flush_shard(s, pend, masters,
                                                  staging)
                            flushed = True
                    if flushed:
                        # the flush ran while the next segment computed;
                        # re-stamp so its duration never inflates the
                        # next harvest's dt
                        t_last_harvest = time.monotonic()
                    progress()
                if stopped:
                    break
                # window boundary: flush all shards, drain shard-major
                with tel.phases.phase("dedup"):
                    for s in range(self.ndev):
                        self._flush_shard(s, pend, masters, staging)
                    n_states += self._drain(staging, host, constore,
                                            keystore, cov)
                blocks_done += 1
                if n_states > _IDX_CEIL:
                    fail = FAIL_INDEX
                    stopped = True
                    break
                if checkpoint and (time.monotonic() - last_ckpt
                                   >= checkpoint_every_s):
                    with tel.phases.phase("snapshot"):
                        self.save_checkpoint(checkpoint, host, constore,
                                             keystore, n_states, n_trans,
                                             cov, level_ends, blocks_done,
                                             (hi0, lo0))
                    tel.checkpoint(checkpoint, n_states)
                    last_ckpt = time.monotonic()
                if getattr(self, "_sigint", False):
                    # Graceful-stop contract (install_sigint_boundary_
                    # stop): stop at the WINDOW boundary, the only point
                    # where the canonical shard-major stream order is
                    # whole — pend/staging just drained, blocks_done just
                    # advanced, every counter (incl. n_trans: all of this
                    # window's segments are harvested) names exactly the
                    # completed-window prefix.  A mid-window drain would
                    # emit a partial window in shard-major order and
                    # diverge from the uninterrupted stream.
                    complete = False
                    stopped = True
                    tel.stop_requested("sigint")
                    if checkpoint:
                        with tel.phases.phase("snapshot"):
                            self.save_checkpoint(
                                checkpoint, host, constore, keystore,
                                n_states, n_trans, cov, level_ends,
                                blocks_done, (hi0, lo0))
                        tel.checkpoint(checkpoint, n_states)
                    break
            if stopped:
                break
            with tr.span("level_close"):
                blocks_done = 0
                if n_states == level_ends[-1]:       # no new states: done
                    break
                level_ends.append(n_states)
                if self._dd_apply is not None:
                    # within-level sets by contract: reset empty at every
                    # boundary (re-sights of previous-level states stream
                    # and the per-shard masters drop them, as with the
                    # gate off)
                    dst = self._init_devset()
                if prefetcher is not None:
                    # quiesce before rotation (no-op unless a stop raced
                    # the level end — the last take() consumed the final
                    # window)
                    prefetcher.invalidate()
                if self.caps.retention == "frontier":
                    # finished level's rows are dead weight (snapshots
                    # keep files alive until their npz commits; tmpdir
                    # runs have nothing to resume — delete immediately)
                    keep = self.caps.keep_levels
                    host.rotate(delete_old=tmpdir is not None and not keep)
                    constore.rotate(delete_old=tmpdir is not None
                                    and not keep)
                progress()
                if len(level_ends) > self.caps.levels:
                    raise RuntimeError(
                        "DDD-shard search aborted: "
                        f"{decode_fail(FAIL_LEVEL)} (caps={self.caps}) — "
                        "grow capacities and rerun")
            end_level()
        end_level()          # the exits by break; a no-op after the above

        if prefetcher is not None:
            # stop paths can leave a window prefetch in flight; no store
            # read survives past here, so the drain, traces and store
            # teardown below see a quiet store
            prefetcher.invalidate()
        # terminal drain (stopped runs keep everything streamed so far —
        # the relaxed chunk-granular stop, as shard_engine)
        with tel.phases.phase("dedup"):
            for s in range(self.ndev):
                self._flush_shard(s, pend, masters, staging)
            n_states += self._drain(staging, host, constore, keystore,
                                    cov)
        if fail:
            raise RuntimeError(
                f"DDD-shard search aborted: {decode_fail(fail)} "
                f"(caps={self.caps}, ndev={self.ndev}) — grow "
                "capacities and rerun")

        violation = None
        if viol is not None:
            kind, vi, ref = viol
            if kind == 1:
                # the violator's first occurrence was discovered this
                # level; find its global id by key
                lvl_base = level_ends[-1] if len(level_ends) else 0
                kw = keystore.read(lvl_base, n_states - lvl_base) \
                    .view(np.uint32)
                got = keyset.pack_keys(kw[:, 1], kw[:, 0])
                hits = np.nonzero(got == np.uint64(ref))[0]
                if not hits.size:
                    raise RuntimeError(
                        "DDD-shard violator key not found after drain — "
                        "fingerprint collision or dedup-order bug")
                viol_g = lvl_base + int(hits[0])
                n_inv = len(self.config.invariants)
                inv_name = self.config.invariants[min(vi, n_inv - 1)]
            else:
                viol_g = ref
                inv_name = DEADLOCK
            if self.caps.retention == "frontier":
                # no trace links; keep_levels restores the full trace
                # via backward re-search (ddd_engine.frontier_backtrace
                # — the level files are mesh-agnostic global streams),
                # else TLC -noTrace: report the state
                row = self.schema.unpack(host.read(int(viol_g), 1)[0],
                                         np)
                py = interp.from_struct(st.unpack(row, self.lay, np),
                                        self.bounds)
                host.sync()
                constore.sync()
                trace = frontier_backtrace(
                    self.config, self.schema, self.bounds, self.table,
                    checkpoint, level_ends, n_states, int(viol_g),
                    keystore)
                violation = Violation(invariant=inv_name, state=py,
                                      trace=trace or [(None, py)])
            else:
                chain_idx = host.trace_chain(viol_g)
                chain = []
                for k, g in enumerate(chain_idx):
                    row = self.schema.unpack(host.read(int(g), 1)[0], np)
                    _, lane_g = host.read_links(int(g), 1)
                    py = interp.from_struct(st.unpack(row, self.lay, np),
                                            self.bounds)
                    label = self.table[int(lane_g[0])].label() if k > 0 \
                        else None
                    chain.append((label, py))
                violation = Violation(invariant=inv_name,
                                      state=chain[-1][1], trace=chain)

        levels_arr = [level_ends[0]] + [
            level_ends[k] - level_ends[k - 1]
            for k in range(1, len(level_ends))]
        tail = n_states - level_ends[-1]
        if tail > 0:
            levels_arr.append(tail)
        coverage = aggregate_coverage(self.table, cov)
        host.close()
        constore.close()
        keystore.close()
        pass_sp.set(levels=len(levels_arr), n_states=n_states,
                    stopped_by="violation" if violation is not None
                    else None if complete else "sigint").close()
        result = EngineResult(
            n_states=n_states, diameter=len(levels_arr) - 1,
            n_transitions=n_trans, coverage=coverage,
            violation=violation, levels=levels_arr,
            wall_s=time.monotonic() - t0, complete=complete,
            level_log=tel.passlog.record)
        tel.run_end(result)
        return result


def check(config: CheckConfig, mesh: Mesh | None = None,
          caps: DDDShardCapacities | None = None, **kw) -> EngineResult:
    return DDDShardEngine(config, mesh, caps).check(**kw)


def reshard_ddd_checkpoint(config: CheckConfig,
                           caps_src: DDDShardCapacities, src_path: str,
                           dst_path: str, ndev_src: int, ndev_dst: int,
                           caps_dst: DDDShardCapacities | None = None,
                           init_override: interp.PyState | None = None,
                           ) -> dict:
    """Rewrite a DDD-shard checkpoint for a different mesh size.

    Unlike the shard engine's resharder, nothing about the *stored*
    search history depends on the mesh: the streams record discovery
    order, which is immutable history, and the per-shard master keys are
    rebuilt from the key stream at load time for whatever mesh resumes.
    Only the window accounting changes — ``blocks_done`` denominates in
    ``ndev * block`` global rows — so the completed-row count must land
    on a destination window boundary (checkpoints are written at window
    boundaries, so for ``ndev_dst * block_dst`` dividing
    ``ndev_src * block_src`` every snapshot qualifies; otherwise let the
    run reach a compatible boundary first).  The single-chip DDD engine
    writes the identical stream format, so this also migrates a
    single-chip campaign onto a mesh: pass the single-chip engine's
    ``block`` inside ``caps_src`` and ``ndev_src=1``.
    """
    caps_dst = caps_dst or caps_src
    init_py = init_override if init_override is not None \
        else interp.init_state(config.bounds)
    init_vec = interp.to_vec(init_py, config.bounds)
    hi0, lo0 = sym_mod.init_fingerprint(config, init_py, init_vec)
    init_key = (hi0, lo0)
    src_digest = ckpt.config_digest(
        config, _DigestCaps(block=caps_src.block, levels=caps_src.levels,
                            ndev=ndev_src, cp=caps_src.cp), init_key)
    with ckpt.load_npz_checked(src_path, src_digest) as z:
        fields = {k: np.asarray(z[k]).copy() for k in
                  ("n_states", "n_trans", "cov", "level_ends",
                   "blocks_done")}
        is_frontier = "retention" in z.files
    rows_done = int(fields["blocks_done"]) * (
        caps_src.block if caps_src.cp else ndev_src * caps_src.block)
    w_dst = caps_dst.block if caps_dst.cp else ndev_dst * caps_dst.block
    # a partial final level window is clamped by the level size; rows
    # actually expanded = min(rows_done, current level rows)
    le = [int(x) for x in fields["level_ends"]]
    lvl_lo = le[-2] if len(le) > 1 else 0
    lvl_rows = le[-1] - lvl_lo
    rows_done = min(rows_done, lvl_rows)
    if rows_done % w_dst and rows_done != lvl_rows:
        raise ValueError(
            f"completed rows {rows_done} of the current level do not "
            f"land on a {w_dst}-row destination window boundary — "
            "resume on the source mesh until they do, or pick a "
            "divisible block size")
    fields["blocks_done"] = np.int64(-(-rows_done // w_dst)
                                     if rows_done == lvl_rows
                                     else rows_done // w_dst)
    n_states = int(fields["n_states"])
    P_ = bitpack.BitSchema(config.bounds).P
    if is_frontier:
        # frontier snapshots: keys + the two live level files move
        # verbatim (they are mesh-independent history, same as the full
        # streams); links don't exist
        le = [int(x) for x in fields["level_ends"]]
        L = len(le)
        lvl_lo = le[-2] if L > 1 else 0
        ckpt.copy_stream(src_path + ".keys", dst_path + ".keys",
                         n_states, 2)
        for prefix, w in ((".rows", P_), (".con", 1)):
            for idx, base, end in ((L, lvl_lo, le[-1]),
                                   (L + 1, le[-1], n_states)):
                ckpt.copy_stream(f"{src_path}{prefix}L{idx}",
                                 f"{dst_path}{prefix}L{idx}",
                                 end - base, w)
    else:
        # .links is width 3 post-int64-widening, width 2 in pre-round-4
        # snapshots; the stream moves verbatim either way (the loader
        # dual-reads both), so copy at the source's own width
        links_w = ckpt.stream_width(src_path + ".links")
        for suf, w in ((".rows", P_),
                       (".links", links_w), (".con", 1), (".keys", 2)):
            ckpt.copy_stream(src_path + suf, dst_path + suf, n_states, w)
    extra = {"retention": np.bytes_(b"frontier")} if is_frontier else {}
    ckpt.atomic_savez(
        dst_path, **fields, **extra,
        config_digest=np.uint64(ckpt.config_digest(
            config, _DigestCaps(block=caps_dst.block,
                                levels=caps_dst.levels, ndev=ndev_dst,
                                cp=caps_dst.cp),
            init_key)))
    return {"ndev_src": ndev_src, "ndev_dst": ndev_dst,
            "n_states": n_states, "rows_done": rows_done,
            "blocks_done_dst": int(fields["blocks_done"])}
