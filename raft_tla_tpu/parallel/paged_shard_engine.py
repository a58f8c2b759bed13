"""Paged + sharded composition: a mesh whose stores page to host RAM.

VERDICT r1 next#6.  The plain shard engine (shard_engine.py) keeps every
device's full state store in HBM — flagship-scale spaces do not fit.  The
paged single-chip engine (paged_engine.py) keeps only a ring of the live
BFS window in HBM and pages completed rows to a host store.  This module
composes the two, the architecture the north-star run needs:

- **per-device HBM**: a bit-packed ring of the live window (current +
  next BFS level of the states this device owns) plus the device's shard
  of the fingerprint table — nothing else;
- **dedup exchange**: the shard engine's FP-prefix ownership with an
  ``all_to_all`` per chunk, but the routed payload is the *bit-packed*
  row (ops/bitpack.py, ~8x narrower than the unpacked vector the plain
  shard engine routes);
- **host RAM**: one append-only store per device (utils/native.py, the
  C++ path when built) holding every state that device owns, paged out
  between watchdog-safe segments.  Current scope is single-controller
  (every shard addressable from this host — true on one multi-chip host
  and on the virtual CPU mesh); the multi-host extension is per-host
  stores over exactly the locally-addressable shards, and ``_pageout``
  fails loudly if it meets a shard it cannot address;
- **trace links**: per-row ``(parent_device, parent_local_index, lane)``
  — parent chains hop across devices through the per-device host stores.

Segments yield to the host either when the chunk budget is spent or when
ANY device's ring is within half a ring of lapping its unpaged rows (a
``pmax`` pause flag, the multi-device analog of paged_engine's
``pause_at``); the host pages out every device's new rows and redispatches.
Same watchdog/checkpoint architecture as every other engine: donated
carries, adaptive budgets, atomic digest-guarded snapshots (the digest
pins the mesh size — FP ownership depends on it).

Exploration metrics (state counts, levels, diameter, transition totals,
verdicts) match refbfs exactly; violation traces are valid but possibly
different counterexamples, and per-action coverage matches in total, with
the same attribution caveat as shard_engine.py (module docstring there).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import Counter
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tla_tpu.config import CheckConfig
from raft_tla_tpu.device_engine import (
    _EMPTY, _dedup_insert, BUCKET, FAIL_INDEX, FAIL_LEVEL, FAIL_PROBE,
    FAIL_RING, FAIL_WIDTH, decode_fail, _acc64_add, _acc64_zero, acc64_int,
    aggregate_coverage)
from raft_tla_tpu.engine import DEADLOCK, EngineResult, Violation
from raft_tla_tpu.obs import RunTelemetry
from raft_tla_tpu.models import interp, invariants as inv_mod, spec as S
from raft_tla_tpu.ops import bitpack
from raft_tla_tpu.ops import kernels
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops import symmetry as sym_mod
from raft_tla_tpu.parallel.shard_engine import (FAIL_ROUTE, _DCN,
    _mesh_axes, exchange, make_mesh)
from raft_tla_tpu.utils import ckpt, native, pacing

I32 = jnp.int32
U32 = jnp.uint32
_AXIS = "d"


@dataclasses.dataclass(frozen=True)
class PagedShardCapacities:
    """Per-device static shapes.  ``ring`` must hold the device's widest
    live window (current + next level of its ~1/ndev share); ``table``
    slots bound the device's distinct-state share (load factor <= 0.5 for
    sane probing); ``send`` as in ShardCapacities."""

    ring: int = 1 << 20
    table: int = 1 << 22
    levels: int = 512
    send: Optional[int] = None
    send2: Optional[int] = None    # stage-B depth, 2-D meshes (see
    #                                ShardCapacities.send2)


class PSCarry(NamedTuple):
    """Mesh-wide carry; [dev] leaves are sharded over the mesh axis."""

    store: jax.Array     # [dev] [Rcap, P] bit-packed ring, local discovery
    pdev: jax.Array      # [dev] [Rcap] parent's owner device
    pidx: jax.Array      # [dev] [Rcap] parent's local discovery index
    lane: jax.Array      # [dev] [Rcap]
    conflag: jax.Array   # [dev] [Rcap]
    tbl_hi: jax.Array    # [dev] [TBd, BUCKET]
    tbl_lo: jax.Array    # [dev] [TBd, BUCKET]
    n_states: jax.Array  # [dev] [1] local discovery count
    lvl_start: jax.Array  # [dev] [1] local level window (discovery idx)
    lvl_end: jax.Array   # [dev] [1]
    viol_l: jax.Array    # [dev] [1] local discovery idx of violation, -1
    viol_i: jax.Array    # [dev] [1]
    n_trans: jax.Array   # [dev] [2] uint32 limbs
    cov: jax.Array       # [dev] [A]
    fail: jax.Array      # [dev] [1]
    levels: jax.Array    # replicated [Lcap]
    lvl: jax.Array       # replicated scalar
    c: jax.Array         # replicated scalar
    n_chunks: jax.Array  # replicated scalar
    stop: jax.Array      # replicated scalar bool
    yieldf: jax.Array    # replicated scalar bool: ring needs pageout


_SHARDED = ("store", "pdev", "pidx", "lane", "conflag", "tbl_hi", "tbl_lo",
            "n_states", "lvl_start", "lvl_end", "viol_l", "viol_i",
            "n_trans", "cov", "fail")


def _carry_specs(axes=(_AXIS,)):
    ax = axes if len(axes) > 1 else axes[0]
    return PSCarry(**{f: P(ax) if f in _SHARDED else P()
                      for f in PSCarry._fields})


def _build_segment(config: CheckConfig, caps: PagedShardCapacities, A: int,
                   W: int, ndev: int, schema: bitpack.BitSchema,
                   nici: int | None = None, axes: tuple = (_AXIS,)):
    B = config.chunk
    n_inv = len(config.invariants)
    if n_inv > 29:
        raise ValueError("at most 29 invariants (bit-packed int32 flags)")
    # The prescan ladder resolves at build time
    # (kernels._prescan_enabled) — bit-identical keys either way.
    step = kernels.build_step(config.bounds, config.spec,
                              tuple(config.invariants), config.symmetry,
                              view=config.view)
    Rcap, Lcap = caps.ring, caps.levels
    rmask = Rcap - 1
    Pw = schema.P
    Csend = caps.send if caps.send is not None else B * A
    nici = ndev if nici is None else nici
    nslice = ndev // nici
    Csend2 = caps.send2 if caps.send2 is not None else nici * Csend
    NR = nici * Csend if nslice == 1 else nslice * Csend2
    BIG = jnp.int32(np.iinfo(np.int32).max)
    # Index-ceiling headroom must cover the worst-case per-chunk append,
    # which here is the full routed-buffer width NR (every sender fills
    # this owner's routing buffer) — not the single-device engine's 2*B*A.
    IDX_CEIL = jnp.int32(np.iinfo(np.int32).max - 2 * NR)

    def owner(key_hi):
        return (key_hi % jnp.uint32(ndev)).astype(I32)

    def chunk_body(carry: PSCarry) -> PSCarry:
        dev = jax.lax.axis_index(_AXIS).astype(I32) if nslice == 1 else (
            jax.lax.axis_index(_DCN).astype(I32) * nici
            + jax.lax.axis_index(_AXIS).astype(I32))
        lvl_start, lvl_end = carry.lvl_start[0], carry.lvl_end[0]
        n_states, fail = carry.n_states[0], carry.fail[0]
        viol_l, viol_i = carry.viol_l[0], carry.viol_i[0]
        store, pdev, pidx, lane = (carry.store, carry.pdev, carry.pidx,
                                   carry.lane)
        conflag, tbl_hi, tbl_lo = carry.conflag, carry.tbl_hi, carry.tbl_lo
        n_trans, cov = carry.n_trans, carry.cov

        # ---- expand my chunk out of the ring ----
        start = lvl_start + carry.c * B
        rows_g = start + jnp.arange(B, dtype=I32)     # local discovery ids
        row_act = rows_g < lvl_end
        ridx = rows_g & rmask
        vecs = schema.unpack(store[ridx], jnp)
        out = step(vecs)
        con_par = conflag[ridx]
        valid = out["valid"] & row_act[:, None] & con_par[:, None]
        n_trans = _acc64_add(n_trans, jnp.sum(valid.astype(I32)))
        fail = fail | jnp.any(valid & out["overflow"]) * FAIL_WIDTH

        # ---- route candidates to their fingerprint owners ----
        BA = B * A
        fhi = out["fp_hi"].reshape(BA)
        flo = out["fp_lo"].reshape(BA)
        fvalid = valid.reshape(BA)
        flat_b = jnp.arange(BA, dtype=I32) // A
        flat_a = jnp.arange(BA, dtype=I32) % A
        flags = jnp.ones((BA,), I32) | (
            out["con_ok"].reshape(BA).astype(I32) << 1)
        if n_inv:
            iv = out["inv_ok"].reshape(BA, n_inv).astype(I32)
            flags = flags | jnp.sum(
                iv << (2 + jnp.arange(n_inv, dtype=I32))[None, :], axis=1)

        # the routed row is BIT-PACKED — the whole point of the composition
        svecs = schema.pack(out["svecs"].reshape(BA, W), jnp)
        # stage A over ICI to the owner's in-slice chip (1-D: the whole
        # exchange); stage B over DCN in aggregated per-slice blocks
        dest_a = jnp.where(fvalid, owner(fhi) % nici, nici)
        (r_vec, r_hi, r_lo, r_pd, r_pi, r_lane, r_flags), ovf = exchange(
            _AXIS, nici, Csend, dest_a,
            ((svecs, 0, I32), (fhi, _EMPTY, U32), (flo, _EMPTY, U32),
             (jnp.full((BA,), 0, I32) + dev, -1, I32),
             (rows_g[flat_b], -1, I32), (flat_a, -1, I32),
             (flags, 0, I32)))
        fail = fail | ovf * FAIL_ROUTE
        active = (r_flags & 1) == 1
        if nslice > 1:
            dest_b = jnp.where(active, owner(r_hi) // nici, nslice)
            (r_vec, r_hi, r_lo, r_pd, r_pi, r_lane,
             r_flags), ovf2 = exchange(
                _DCN, nslice, Csend2, dest_b,
                ((r_vec, 0, I32), (r_hi, _EMPTY, U32),
                 (r_lo, _EMPTY, U32), (r_pd, -1, I32), (r_pi, -1, I32),
                 (r_lane, -1, I32), (r_flags, 0, I32)))
            fail = fail | ovf2 * FAIL_ROUTE
            active = (r_flags & 1) == 1

        # ---- owner-side dedup + ring append ----
        tbl_hi, tbl_lo, is_new, pfail = _dedup_insert(
            tbl_hi, tbl_lo, r_hi, r_lo, active)
        fail = fail | jnp.any(pfail) * FAIL_PROBE
        pos_st = n_states + jnp.cumsum(is_new.astype(I32)) - 1
        n_new = jnp.sum(is_new.astype(I32))
        # Ring-lap guard.  Two live regions must never be overwritten: the
        # level window being expanded (from lvl_start) AND the rows not yet
        # paged to the host (from the paged watermark — a mesh device can
        # receive up to NR appends in ONE chunk under routing skew, far
        # past the between-chunks pause heuristic).  Exact and loud:
        fail = fail | (n_states + n_new
                       - jnp.minimum(lvl_start, paged_wm) > Rcap) * FAIL_RING
        fail = fail | (n_states > IDX_CEIL) * FAIL_INDEX
        ok = is_new & (pos_st - lvl_start < Rcap)
        sl = jnp.where(ok, pos_st & rmask, Rcap)
        store = store.at[sl].set(r_vec, mode="drop")
        pdev = pdev.at[sl].set(r_pd, mode="drop")
        pidx = pidx.at[sl].set(r_pi, mode="drop")
        lane = lane.at[sl].set(r_lane, mode="drop")
        conflag = conflag.at[sl].set(((r_flags >> 1) & 1) == 1, mode="drop")
        cov = cov.at[jnp.where(is_new, r_lane, A)].add(1, mode="drop")
        n_states = n_states + n_new

        # ---- first violation among my new states ----
        if n_inv:
            inv_bits = (r_flags >> 2) & ((1 << n_inv) - 1)
            inv_bad = is_new & (inv_bits != (1 << n_inv) - 1)
        else:
            inv_bad = jnp.zeros_like(is_new)
        first = jnp.min(jnp.where(
            inv_bad, jnp.arange(NR, dtype=I32), BIG))
        new_viol = (first < BIG) & (viol_l < 0)
        fidx = jnp.minimum(first, NR - 1)
        viol_l = jnp.where(new_viol, pos_st[fidx], viol_l)
        if n_inv:
            bad_inv = jnp.argmax(
                ((r_flags[fidx] >> 2) & (1 << jnp.arange(n_inv))) == 0
            ).astype(I32)
        else:
            bad_inv = jnp.int32(0)
        viol_i = jnp.where(new_viol, bad_inv, viol_i)
        if config.check_deadlock:
            # local deadlock check; attribution caveat as in shard_engine
            dead = row_act & con_par & ~jnp.any(out["valid"], axis=1)
            drow = jnp.min(jnp.where(dead, jnp.arange(B, dtype=I32), BIG))
            dl = (drow < BIG) & (viol_l < 0)
            viol_l = jnp.where(
                dl, start + jnp.minimum(drow, B - 1), viol_l)
            viol_i = jnp.where(dl, jnp.int32(n_inv), viol_i)

        stop = (jax.lax.psum((viol_l >= 0).astype(I32), axes) > 0) | \
            (jax.lax.pmax(fail, axes) != 0)
        # a ring nearing its unpaged rows anywhere -> yield for pageout
        yieldf = jax.lax.pmax(
            (n_states >= paged_wm + half).astype(I32), axes) > 0
        return carry._replace(
            store=store, pdev=pdev, pidx=pidx, lane=lane, conflag=conflag,
            tbl_hi=tbl_hi, tbl_lo=tbl_lo,
            n_states=n_states[None], n_trans=n_trans, cov=cov,
            viol_l=viol_l[None], viol_i=viol_i[None], fail=fail[None],
            stop=stop, yieldf=yieldf, c=carry.c + 1)

    def outer_body(sc):
        steps, carry = sc

        def ccond(cc):
            s, inner = cc
            return ((inner.c < inner.n_chunks) & ~inner.stop
                    & ~inner.yieldf & (s < budget))

        def cbody(cc):
            s, inner = cc
            return s + 1, chunk_body(inner)

        steps, carry = jax.lax.while_loop(ccond, cbody, (steps, carry))
        adv = (carry.c >= carry.n_chunks) & ~carry.stop & ~carry.yieldf
        n_new = carry.n_states[0] - carry.lvl_end[0]
        n_new_tot = jax.lax.psum(n_new, axes)
        levels = jnp.where(
            adv,
            carry.levels.at[jnp.minimum(carry.lvl, Lcap - 1)].set(n_new_tot),
            carry.levels)
        fail = carry.fail[0] | (
            adv & (carry.lvl >= Lcap - 1) & (n_new_tot > 0)) * FAIL_LEVEL
        lvl_start = jnp.where(adv, carry.lvl_end[0], carry.lvl_start[0])
        lvl_end = jnp.where(adv, carry.n_states[0], carry.lvl_end[0])
        n_act = lvl_end - lvl_start
        n_chunks = jnp.where(
            adv, jax.lax.pmax((n_act + B - 1) // B, axes), carry.n_chunks)
        stop = carry.stop | (adv & (n_new_tot == 0)) | \
            (jax.lax.pmax(fail, axes) != 0)
        return steps, carry._replace(
            levels=levels, fail=fail[None],
            lvl_start=lvl_start[None], lvl_end=lvl_end[None],
            lvl=jnp.where(adv, carry.lvl + 1, carry.lvl),
            c=jnp.where(adv, 0, carry.c), n_chunks=n_chunks, stop=stop)

    def outer_cond(sc):
        steps, carry = sc
        return (steps < budget) & ~carry.stop & ~carry.yieldf

    def segment(carry: PSCarry, budget_, paged_d):
        nonlocal budget, paged_wm
        budget = budget_
        paged_wm = paged_d[0]      # this device's host-paged watermark
        # fresh segment: the host just paged out, the yield flag resets
        carry = carry._replace(yieldf=jnp.zeros((), bool))
        steps, carry = jax.lax.while_loop(outer_cond, outer_body,
                                          (jnp.int32(0), carry))
        return steps, carry

    budget = paged_wm = None
    half = Rcap // 2
    return segment


class PagedShardEngine:
    """Mesh-sharded exhaustive checker bounded by host RAM per device."""

    SEG_TARGET_S = 8.0
    SEG_CLAMP_S = 25.0
    SEG_MIN, SEG_MAX = 16, 1 << 16
    PAGE_ROWS = 1 << 16          # fixed pageout gather width (one compile)

    def __init__(self, config: CheckConfig, mesh: Mesh | None = None,
                 caps: PagedShardCapacities | None = None,
                 seg_chunks: int = 64):
        self.config = config
        self.bounds = config.bounds
        self.lay = st.Layout.of(self.bounds)
        self.table = S.action_table(self.bounds, config.spec)
        self.A = len(self.table)
        self.mesh = mesh if mesh is not None else make_mesh()
        self.ndev = self.mesh.devices.size
        self.caps = caps or PagedShardCapacities()
        for nm in ("ring", "table"):
            v = getattr(self.caps, nm)
            if v & (v - 1):
                raise ValueError(f"{nm}={v} must be a power of two "
                                 "(bucket/ring masks are bitwise)")
        if self.caps.ring < 2 * config.chunk * self.A:
            raise ValueError(
                f"ring={self.caps.ring} must be >= 2 * chunk * A = "
                f"{2 * config.chunk * self.A} (pageout headroom; worst-"
                "case routing skew is guarded loudly in-kernel)")
        # trace links pack (lane, parent_device) into one int32 word:
        # lane in bits 0..15, device in bits 16..23 (_extract_trace)
        if self.ndev > 1 << 8:
            raise ValueError(f"at most {1 << 8} devices (link-word field)")
        if self.A > 1 << 16:
            raise ValueError("action table exceeds the link-word field")
        self.seg_chunks = seg_chunks
        self.schema = bitpack.BitSchema(self.bounds)
        axes = _mesh_axes(self.mesh)
        nici = self.mesh.shape[_AXIS]
        specs = _carry_specs(axes)
        fn = _build_segment(config, self.caps, self.A, self.lay.width,
                            self.ndev, self.schema, nici=nici, axes=axes)
        paged_spec = P(axes if len(axes) > 1 else axes[0])
        self._segment = jax.jit(jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(specs, P(), paged_spec),
            out_specs=(P(), specs),
            check_vma=False), donate_argnums=(0,))
        self._shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs)

    def _put(self, carry: PSCarry) -> PSCarry:
        return PSCarry(*(jax.device_put(x, s)
                         for x, s in zip(carry, self._shardings)))

    def _init_carry(self, init_packed, hi0, lo0, con0) -> PSCarry:
        nd, Rcap, A = self.ndev, self.caps.ring, self.A
        Pw, Lcap = self.schema.P, self.caps.levels
        TBd = self.caps.table // BUCKET
        own = int(np.uint32(hi0) % np.uint32(nd))
        store = np.zeros((nd * Rcap, Pw), np.int32)
        store[own * Rcap] = init_packed
        pdev = np.full((nd * Rcap,), -1, np.int32)
        pidx = np.full((nd * Rcap,), -1, np.int32)
        lane = np.full((nd * Rcap,), -1, np.int32)
        conflag = np.zeros((nd * Rcap,), bool)
        conflag[own * Rcap] = con0
        tbl_hi = np.full((nd * TBd, BUCKET), _EMPTY, np.uint32)
        tbl_lo = np.full((nd * TBd, BUCKET), _EMPTY, np.uint32)
        b0 = int(np.uint32(lo0) & np.uint32(TBd - 1))
        tbl_hi[own * TBd + b0, 0] = hi0
        tbl_lo[own * TBd + b0, 0] = lo0
        n0 = np.zeros((nd,), np.int32)
        n0[own] = 1
        return self._put(PSCarry(
            store=store, pdev=pdev, pidx=pidx, lane=lane, conflag=conflag,
            tbl_hi=tbl_hi, tbl_lo=tbl_lo,
            n_states=n0, lvl_start=np.zeros((nd,), np.int32),
            lvl_end=n0.copy(),
            viol_l=np.full((nd,), -1, np.int32),
            viol_i=np.zeros((nd,), np.int32),
            n_trans=np.zeros((nd * 2,), np.uint32),
            cov=np.zeros((nd * A,), np.int32),
            fail=np.zeros((nd,), np.int32),
            levels=np.zeros((Lcap,), np.int32),
            lvl=np.int32(1), c=np.int32(0), n_chunks=np.int32(1),
            stop=np.bool_(False), yieldf=np.bool_(False)))

    # -- pageout --------------------------------------------------------

    def _shard_data(self, arr, d: int):
        """Device d's local block of a [dev]-sharded global array."""
        for sh in arr.addressable_shards:
            # a fully-replicated / single-shard index reads slice(None)
            if (sh.index[0].start or 0) == d * (arr.shape[0] // self.ndev):
                return sh.data
        raise RuntimeError(f"shard {d} not addressable from this host")

    def _pageout(self, carry: PSCarry, hosts: list, paged: list) -> list:
        """Copy each device's rows [paged[d], n_states[d]) from its ring
        into its host store.  Per-device gathers run on the owning device;
        only the gathered block crosses to the host."""
        rmask = self.caps.ring - 1
        n_d = np.asarray(jax.device_get(carry.n_states))
        iota = np.arange(self.PAGE_ROWS, dtype=np.int32)
        for d in range(self.ndev):
            n = int(n_d[d])
            st_d = self._shard_data(carry.store, d)
            pd_d = self._shard_data(carry.pdev, d)
            pi_d = self._shard_data(carry.pidx, d)
            la_d = self._shard_data(carry.lane, d)
            dev_obj = list(st_d.devices())[0]
            while paged[d] < n:
                k = min(n - paged[d], self.PAGE_ROWS)
                gidx = np.minimum(paged[d] + iota, n - 1)
                # the gather runs on the owning device; only the gathered
                # block crosses to the host
                ridx = jax.device_put(jnp.asarray(gidx & rmask), dev_obj)
                rows, pdv, piv, lav = jax.device_get(
                    (st_d[ridx], pd_d[ridx], pi_d[ridx], la_d[ridx]))
                hosts[d].append(rows[:k])
                # lane (bits 0..15) and parent device (16..23) share a word
                hosts[d].append_links(
                    piv[:k], lav[:k] | (pdv[:k].astype(np.int32) << 16))
                paged[d] += k
        return paged

    # -- checkpoint / resume --------------------------------------------

    def save_checkpoint(self, path: str, carry: PSCarry, hosts: list,
                        paged: list, init_key: tuple) -> None:
        for d in range(self.ndev):
            ckpt.stream_rows_out(f"{path}.rows{d}", hosts[d].read,
                                 paged[d], self.schema.P)

            def links_reader(start, n, _d=d):
                par, lan = hosts[_d].read_links(start, n)
                return np.stack([par, lan], axis=1)

            ckpt.stream_rows_out(f"{path}.links{d}", links_reader,
                                 paged[d], 2)
        arrs = jax.device_get(carry)
        ckpt.atomic_savez(
            path,
            **{f"c{i}": np.asarray(x) for i, x in enumerate(arrs)},
            paged=np.asarray(paged, np.int64),
            config_digest=np.uint64(ckpt.config_digest(
                self.config, self.caps, init_key + (self.ndev,))))

    def load_checkpoint(self, path: str, init_key: tuple):
        with ckpt.load_npz_checked(
                path, ckpt.config_digest(
                    self.config, self.caps,
                    init_key + (self.ndev,))) as z:
            carry = PSCarry(*(jnp.asarray(z[f"c{i}"])
                              for i in range(len(PSCarry._fields))))
            paged = [int(x) for x in z["paged"]]
        hosts = [native.make_store(self.schema.P) for _ in range(self.ndev)]
        for d in range(self.ndev):
            ckpt.stream_rows_in(f"{path}.rows{d}", hosts[d].append,
                                paged[d], expect_width=self.schema.P)
            ckpt.stream_rows_in(
                f"{path}.links{d}",
                lambda blk, _d=d: hosts[_d].append_links(
                    blk[:, 0], blk[:, 1]),
                paged[d], expect_width=2)
        return self._put(carry), hosts, paged

    # -- public API -----------------------------------------------------

    def check(self, init_override: interp.PyState | None = None,
              checkpoint: str | None = None,
              checkpoint_every_s: float = 600.0,
              resume: str | None = None,
              on_progress=None, events: str | None = None) -> EngineResult:
        t0 = time.monotonic()
        tel = RunTelemetry(
            "pagedshard", config=self.config, caps=self.caps,
            on_progress=on_progress, events=events,
            resumed=resume is not None,
            n0=1 if resume is None else None,
            n_devices=self.ndev, t0=t0)
        try:
            return self._check_impl(tel, t0, init_override, checkpoint,
                                    checkpoint_every_s, resume)
        finally:
            tel.close()

    def _check_impl(self, tel, t0, init_override, checkpoint,
                    checkpoint_every_s, resume) -> EngineResult:
        bounds = self.bounds
        init_py = init_override if init_override is not None \
            else interp.init_state(bounds)
        init_vec = interp.to_vec(init_py, bounds)
        hi0, lo0 = sym_mod.init_fingerprint(self.config, init_py, init_vec)
        tel.run_start()

        for nm in self.config.invariants:
            if not inv_mod.py_invariant(nm)(init_py, bounds):
                res = EngineResult(
                    n_states=1, diameter=0, n_transitions=0,
                    coverage=Counter(),
                    violation=Violation(nm, init_py, [(None, init_py)]),
                    levels=[1], wall_s=time.monotonic() - t0)
                tel.run_end(res)
                return res

        if resume:
            carry, hosts, paged = self.load_checkpoint(resume, (hi0, lo0))
        else:
            init_packed = self.schema.pack(
                np.asarray(init_vec, np.int32), np)
            carry = self._init_carry(
                init_packed, np.uint32(hi0), np.uint32(lo0),
                bool(interp.constraint_ok(init_py, bounds)))
            hosts = [native.make_store(self.schema.P)
                     for _ in range(self.ndev)]
            paged = [0] * self.ndev

        pacer = pacing.SegmentPacer(self.seg_chunks, self.SEG_MIN,
                                    self.SEG_MAX, self.SEG_TARGET_S,
                                    self.SEG_CLAMP_S)
        budget = pacer.budget
        last_ckpt = time.monotonic()
        while True:
            paged_d = jnp.asarray(np.asarray(paged, np.int32))
            t_seg = time.monotonic()
            with tel.phases.phase("expand") as ph:
                steps_d, carry = self._segment(carry, jnp.int32(budget),
                                               paged_d)
                ph.sync(steps_d)
            with tel.phases.phase("export"):
                paged = self._pageout(carry, hosts, paged)
            if tel.active:
                n_states_d, lvl, n_trans_d, cov_arr = jax.device_get(
                    (carry.n_states, carry.lvl, carry.n_trans, carry.cov))
                tel.segment(
                    n_states=int(np.asarray(n_states_d).sum()),
                    level=int(lvl), n_transitions=acc64_int(n_trans_d),
                    coverage=dict(aggregate_coverage(self.table, cov_arr)))
            if bool(np.asarray(carry.stop)):
                break
            dt = time.monotonic() - t_seg
            executed = max(1, int(np.asarray(steps_d)))
            if checkpoint and (time.monotonic() - last_ckpt
                               >= checkpoint_every_s):
                with tel.phases.phase("snapshot"):
                    self.save_checkpoint(checkpoint, carry, hosts, paged,
                                         (hi0, lo0))
                tel.checkpoint(checkpoint)
                last_ckpt = time.monotonic()
            budget = pacer.update(dt, executed)
            self.seg_chunks = budget

        (n_states_d, viol_ls, viol_is, n_trans_d, fail_d, n_levels,
         levels_dev, cov_arr) = jax.device_get(
             (carry.n_states, carry.viol_l, carry.viol_i, carry.n_trans,
              carry.fail, carry.lvl, carry.levels, carry.cov))
        fail = int(np.bitwise_or.reduce(np.asarray(fail_d)))
        if fail:
            raise RuntimeError(
                f"paged-shard search aborted: {decode_fail(fail)} "
                f"(caps={self.caps}, ndev={self.ndev}) — grow "
                "PagedShardCapacities and rerun")
        n_states = int(np.asarray(n_states_d).sum())
        levels_arr = [1] + [int(x) for x in
                            np.asarray(levels_dev)[:int(n_levels)]
                            if int(x) > 0]
        cov_tot = np.asarray(cov_arr).reshape(self.ndev, self.A).sum(axis=0)
        coverage: Counter = Counter()
        for a, inst in enumerate(self.table):
            if cov_tot[a]:
                coverage[inst.family] += int(cov_tot[a])

        violation = None
        viol_ls = np.asarray(viol_ls)
        viol_devs = np.nonzero(viol_ls >= 0)[0]
        if viol_devs.size:
            d = int(viol_devs[0])
            violation = self._extract_trace(
                hosts, d, int(viol_ls[d]), int(np.asarray(viol_is)[d]))
        for h in hosts:
            h.close()

        result = EngineResult(
            n_states=n_states,
            diameter=len(levels_arr) - 1,
            n_transitions=acc64_int(n_trans_d),
            coverage=coverage,
            violation=violation,
            levels=levels_arr,
            wall_s=time.monotonic() - t0)
        tel.run_end(result)
        return result

    def _extract_trace(self, hosts: list, dev: int, lidx: int,
                       viol_i: int) -> Violation:
        """Walk the parent chain across the per-device host stores."""
        chain = []                     # (dev, local idx) root..violation
        d, li = dev, lidx
        while li >= 0:
            chain.append((d, li))
            par, word = hosts[d].read_links(li, 1)
            li = int(par[0])
            d = (int(word[0]) >> 16) & 0xFF
        chain.reverse()
        out = []
        for k, (cd, cl) in enumerate(chain):
            row = self.schema.unpack(hosts[cd].read(cl, 1)[0], np)
            py = interp.from_struct(st.unpack(row, self.lay, np),
                                    self.bounds)
            if k == 0:
                out.append((None, py))
            else:
                _par, word = hosts[cd].read_links(cl, 1)
                out.append((self.table[int(word[0]) & 0xFFFF].label(), py))
        inv_name = DEADLOCK if viol_i == len(self.config.invariants) \
            else self.config.invariants[viol_i]
        return Violation(invariant=inv_name, state=out[-1][1], trace=out)


def check(config: CheckConfig, mesh: Mesh | None = None,
          caps: PagedShardCapacities | None = None, **kw) -> EngineResult:
    return PagedShardEngine(config, mesh, caps).check(**kw)
