"""Multi-chip sharded BFS engine — L4 over ICI (SURVEY §7.1 step 7, §2.9).

The reference is single-process (TLC's distributed mode is unused —
SURVEY §2.9); this module is the scale-out design the task demands, built the
TPU way: ``jax.sharding.Mesh`` + ``shard_map`` + XLA collectives, not
NCCL/MPI.  The multi-device search runs as **watchdog-safe segments** (the
device_engine.py architecture): one jitted program advances the whole mesh by
up to ``budget`` chunk expansions and returns the carry with its buffers
donated back into the next dispatch — so a search of any length survives a
~60 s program watchdog (the rounds 2-5 machine's; inherited, not re-measured
on this one), the host can snapshot the carry
for checkpoint/resume (TLC ``-recover``), and per-segment stats stream out.
Three collectives run in the hot loop:

- **all_to_all** — fingerprint-prefix dedup exchange (SURVEY §2.9 row SP):
  every chip owns the slice of fingerprint space ``fp_hi % n_dev == d``.
  After a chip expands a chunk of its local frontier, each candidate
  successor is routed to its owner chip, which alone consults/updates its
  local fingerprint table.  Because a state's owner is a pure function of its
  fingerprint, a state is only ever deduplicated in one place — no global
  table, no host round-trips.
- **pmax** — lockstep chunk scheduling: devices run the same number of chunk
  iterations per level (all_to_all requires all participants), idle rows
  masked off.
- **psum** — termination detection (frontier empty everywhere), violation
  broadcast, level histograms, coverage and transition totals.

Data placement per device (all static shapes): its shard of the store
(states it owns, in local discovery order), parent **global ids**
(``dev * n_states_cap + local_idx`` — trace chains cross chips), lane ids,
constraint flags, and the local fingerprint table.  The frontier is a
contiguous store segment per device, exactly as in device_engine.py — BFS is
level-synchronous, and new states append to their owner's store.

Load balance comes from the hash: fingerprints are avalanche-mixed
(ops/fingerprint.py), so each chip owns ~1/n of every level's new states.
This is the checker's DP axis; the per-state action fan-out is its TP axis
(SURVEY §2.9).

Determinism: within a device, candidate order is (sender device, send slot) —
fixed — so parent links and local discovery order are reproducible run to
run, and a checkpoint resume replays the identical search.  Global discovery
order differs from the single-chip engines (states interleave across chips),
so total counts, per-level counts, transition counts, verdicts and diameter
all match refbfs/DeviceEngine exactly, while (a) a violation trace may be a
*different valid counterexample* than the single-chip one (still replayable —
tested), and (b) per-action coverage *attribution* can differ when the same
new state is producible by several actions within one level — the first
discoverer gets credit, and "first" depends on interleaving.  Coverage
*totals* still equal n_states - 1 (every non-initial state credited exactly
once); TLC's own multi-worker mode has the same attribution nondeterminism.

Differences vs TLC's distributed mode (Java sockets, central fingerprint
server): here dedup is sharded, not centralized, and the exchange is a
single fused collective per chunk on the ICI fabric.
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
    _EMPTY, _dedup_insert, BUCKET, FAIL_LEVEL, FAIL_PROBE, FAIL_ROUTE,
    FAIL_STORE, FAIL_WIDTH, decode_fail, _acc64_add, acc64_int,
    aggregate_coverage, widen_legacy_n_trans)
from raft_tla_tpu.engine import DEADLOCK, EngineResult, Violation
from raft_tla_tpu.obs import RunTelemetry
from raft_tla_tpu.models import interp, invariants as inv_mod, spec as S
from raft_tla_tpu.ops import fingerprint as fpr
from raft_tla_tpu.ops import kernels
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops import symmetry as sym_mod
from raft_tla_tpu.parallel.mesh import (
    _AXIS, _DCN, _mesh_axes, exchange, make_mesh)
from raft_tla_tpu.utils import ckpt
from raft_tla_tpu.utils import pacing

I32 = jnp.int32
U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class ShardCapacities:
    """Static shapes of one compiled sharded search (per-device where noted).

    ``send`` is the per-destination routing buffer depth per chunk; ``None``
    means the safe bound ``chunk * A`` (no overflow possible).  Smaller
    values trade memory for a loud abort if one chip's candidates
    concentrate on one destination.  Expected occupancy is hash-uniform
    over the STAGE-A destination count: ~BA/ndev on a 1-D mesh but
    ~BA/per_slice on a 2-D mesh (stage A routes within the slice), so a
    ``send`` tuned on a flat mesh must be rescaled by ndev/per_slice when
    moving to a slice mesh.  ``send2`` is the stage-B (cross-slice, 2-D
    only) per-destination-slice depth; ``None`` means the safe bound
    ``per_slice * send``.
    """

    n_states: int = 1 << 17      # store rows per device
    levels: int = 256
    send: Optional[int] = None
    send2: Optional[int] = None

    @property
    def table(self) -> int:      # per-device hash slots, load factor <= 0.5
        return 1 << (2 * self.n_states - 1).bit_length()


class SCarry(NamedTuple):
    """The segment carry — the entire mesh-wide search state.

    Leaves marked [dev] are sharded over the mesh axis (global leading dim
    ``ndev * per-device``; scalars are shape-[1] per device, [ndev] global);
    the rest are replicated lockstep values, identical on every device by
    construction (they only change through psum/pmax results).
    """

    store: jax.Array      # [dev] [Ncap, W] states this device owns
    parent: jax.Array     # [dev] [Ncap] parent GLOBAL id (dev*Ncap + row)
    lane: jax.Array       # [dev] [Ncap]
    conflag: jax.Array    # [dev] [Ncap]
    tbl_hi: jax.Array     # [dev] [TBd, BUCKET]
    tbl_lo: jax.Array     # [dev] [TBd, BUCKET]
    n_states: jax.Array   # [dev] [1]
    lvl_start: jax.Array  # [dev] [1] local level window
    lvl_end: jax.Array    # [dev] [1]
    viol_g: jax.Array     # [dev] [1] first violating GLOBAL id, -1 if none
    viol_i: jax.Array     # [dev] [1] invariant index (n_inv = deadlock)
    n_trans: jax.Array    # [dev] [2] uint32 limbs (64-bit counter)
    cov: jax.Array        # [dev] [A]
    fail: jax.Array       # [dev] [1] FAIL_* bitmask
    levels: jax.Array     # replicated [Lcap] global per-level new states
    lvl: jax.Array        # replicated scalar
    c: jax.Array          # replicated scalar: chunk cursor within level
    n_chunks: jax.Array   # replicated scalar: lockstep chunks this level
    stop: jax.Array       # replicated scalar bool


_SHARDED = ("store", "parent", "lane", "conflag", "tbl_hi", "tbl_lo",
            "n_states", "lvl_start", "lvl_end", "viol_g", "viol_i",
            "n_trans", "cov", "fail")


def _carry_specs(axes=(_AXIS,)):
    ax = axes if len(axes) > 1 else axes[0]
    return SCarry(**{f: P(ax) if f in _SHARDED else P()
                     for f in SCarry._fields})


def _build_segment(config: CheckConfig, caps: ShardCapacities,
                   A: int, W: int, ndev: int, nici: int | None = None,
                   axes: tuple = (_AXIS,)):
    """One watchdog-safe slice of the mesh-wide search (<= budget chunks).

    ``nici`` (2-D meshes): devices per slice; the dedup exchange then runs
    hierarchically — stage A routes candidates over ICI to the owner's
    in-slice index, stage B forwards them over DCN to the owner's slice in
    aggregated per-slice blocks (one DCN message per destination slice per
    chunk instead of per destination chip)."""
    B = config.chunk
    n_inv = len(config.invariants)
    if n_inv > 29:
        raise ValueError("at most 29 invariants (bit-packed into int32 flags)")
    # The prescan ladder resolves at build time
    # (kernels._prescan_enabled); keys stay bit-identical either way, so mixed
    # settings across reshard/resume cannot corrupt the store.
    step = kernels.build_step(config.bounds, config.spec,
                              tuple(config.invariants), config.symmetry,
                              view=config.view)
    Ncap, Lcap = caps.n_states, caps.levels
    Csend = caps.send if caps.send is not None else B * A
    nici = ndev if nici is None else nici
    nslice = ndev // nici
    Csend2 = caps.send2 if caps.send2 is not None else nici * Csend
    NR = nici * Csend if ndev // nici == 1 else (ndev // nici) * Csend2
    BIG = jnp.int32(np.iinfo(np.int32).max)

    def owner(key_hi):
        """FP shard map: FLAT device id ``slice * nici + chip`` that dedups
        and stores this state (slice decomposition does not change it, so
        checkpoints move between 1-D and 2-D meshes of equal size)."""
        return (key_hi % jnp.uint32(ndev)).astype(I32)

    def chunk_body(carry: SCarry) -> SCarry:
        dev = jax.lax.axis_index(_AXIS).astype(I32) if nslice == 1 else (
            jax.lax.axis_index(_DCN).astype(I32) * nici
            + jax.lax.axis_index(_AXIS).astype(I32))
        lvl_start, lvl_end = carry.lvl_start[0], carry.lvl_end[0]
        n_states, fail = carry.n_states[0], carry.fail[0]
        viol_g, viol_i = carry.viol_g[0], carry.viol_i[0]
        store, parent, lane = carry.store, carry.parent, carry.lane
        conflag, tbl_hi, tbl_lo = carry.conflag, carry.tbl_hi, carry.tbl_lo
        n_trans, cov = carry.n_trans, carry.cov

        # ---- expand my chunk (rows may be inactive on ragged levels) ----
        start = lvl_start + carry.c * B
        gstart = jnp.clip(start, 0, Ncap - B)
        rows_l = gstart + jnp.arange(B, dtype=I32)
        row_act = (rows_l >= start) & (rows_l < lvl_end)
        vecs = jax.lax.dynamic_slice(store, (gstart, 0), (B, W))
        out = step(vecs)
        con_par = jax.lax.dynamic_slice(conflag, (gstart,), (B,))
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
        # flags: bit0 occupied, bit1 con_ok, bits 2.. per-invariant ok
        flags = jnp.ones((BA,), I32) | (
            out["con_ok"].reshape(BA).astype(I32) << 1)
        if n_inv:
            iv = out["inv_ok"].reshape(BA, n_inv).astype(I32)
            flags = flags | jnp.sum(
                iv << (2 + jnp.arange(n_inv, dtype=I32))[None, :], axis=1)
        svecs = out["svecs"].reshape(BA, W)
        par_g = dev * Ncap + gstart + flat_b

        # stage A over ICI: route to the owner's in-slice chip index (for
        # 1-D meshes nici == ndev and this IS the whole exchange)
        dest_a = jnp.where(fvalid, owner(fhi) % nici, nici)
        (r_vec, r_hi, r_lo, r_par, r_lane, r_flags), ovf = exchange(
            _AXIS, nici, Csend, dest_a,
            ((svecs, 0, I32), (fhi, _EMPTY, U32), (flo, _EMPTY, U32),
             (par_g, -1, I32), (flat_a, -1, I32), (flags, 0, I32)))
        fail = fail | ovf * FAIL_ROUTE
        active = (r_flags & 1) == 1
        if nslice > 1:
            # stage B over DCN: every active row already sits on the
            # owner's chip index; forward to the owner's slice in one
            # aggregated block per destination slice
            dest_b = jnp.where(active, owner(r_hi) // nici, nslice)
            (r_vec, r_hi, r_lo, r_par, r_lane, r_flags), ovf2 = exchange(
                _DCN, nslice, Csend2, dest_b,
                ((r_vec, 0, I32), (r_hi, _EMPTY, U32),
                 (r_lo, _EMPTY, U32), (r_par, -1, I32),
                 (r_lane, -1, I32), (r_flags, 0, I32)))
            fail = fail | ovf2 * FAIL_ROUTE
            active = (r_flags & 1) == 1

        # ---- owner-side dedup + append (same protocol as device_engine) ----
        tbl_hi, tbl_lo, is_new, pfail = _dedup_insert(
            tbl_hi, tbl_lo, r_hi, r_lo, active)
        fail = fail | jnp.any(pfail) * FAIL_PROBE
        pos_st = n_states + jnp.cumsum(is_new.astype(I32)) - 1
        sl = jnp.where(is_new & (pos_st < Ncap), pos_st, Ncap)
        store = store.at[sl].set(r_vec, mode="drop")
        parent = parent.at[sl].set(r_par, mode="drop")
        lane = lane.at[sl].set(r_lane, mode="drop")
        conflag = conflag.at[sl].set(((r_flags >> 1) & 1) == 1, mode="drop")
        cov = cov.at[jnp.where(is_new, r_lane, A)].add(1, mode="drop")
        n_new = jnp.sum(is_new.astype(I32))
        fail = fail | (n_states + n_new > Ncap) * FAIL_STORE
        n_states = jnp.minimum(n_states + n_new, Ncap)

        # ---- first invariant violation among my new states ----
        if n_inv:
            inv_bits = (r_flags >> 2) & ((1 << n_inv) - 1)
            inv_bad = is_new & (inv_bits != (1 << n_inv) - 1)
        else:
            inv_bad = jnp.zeros_like(is_new)
        first = jnp.min(jnp.where(
            inv_bad, jnp.arange(NR, dtype=I32), BIG))
        new_viol = (first < BIG) & (viol_g < 0)
        fidx = jnp.minimum(first, NR - 1)
        viol_g = jnp.where(new_viol, dev * Ncap + pos_st[fidx], viol_g)
        if n_inv:
            bad_inv = jnp.argmax(
                ((r_flags[fidx] >> 2) & (1 << jnp.arange(n_inv))) == 0
            ).astype(I32)
        else:
            bad_inv = jnp.int32(0)
        viol_i = jnp.where(new_viol, bad_inv, viol_i)
        if config.check_deadlock:
            # TLC's default deadlock check, device-locally: an expanded row
            # with no enabled action.  Which event is reported first when a
            # deadlock and a violation coexist is interleaving-dependent
            # here, like coverage attribution (module docstring) — either
            # is a correct counterexample.
            dead = row_act & con_par & ~jnp.any(out["valid"], axis=1)
            drow = jnp.min(jnp.where(dead, jnp.arange(B, dtype=I32), BIG))
            dl = (drow < BIG) & (viol_g < 0)
            viol_g = jnp.where(
                dl, dev * Ncap + gstart + jnp.minimum(drow, B - 1), viol_g)
            viol_i = jnp.where(dl, jnp.int32(n_inv), viol_i)

        # replicated stop flag: any device saw a violation or failed
        stop = (jax.lax.psum((viol_g >= 0).astype(I32), axes) > 0) | \
            (jax.lax.pmax(fail, axes) != 0)
        return carry._replace(
            store=store, parent=parent, lane=lane, conflag=conflag,
            tbl_hi=tbl_hi, tbl_lo=tbl_lo,
            n_states=n_states[None], n_trans=n_trans, cov=cov,
            viol_g=viol_g[None], viol_i=viol_i[None], fail=fail[None],
            stop=stop, c=carry.c + 1)

    def outer_body(sc):
        """Run chunks until the level is exhausted, the budget runs out, or
        a stop event lands; then (maybe) advance the level window."""
        steps, carry = sc

        def ccond(cc):
            s, inner = cc
            return (inner.c < inner.n_chunks) & ~inner.stop & (s < budget)

        def cbody(cc):
            s, inner = cc
            return s + 1, chunk_body(inner)

        steps, carry = jax.lax.while_loop(ccond, cbody, (steps, carry))
        # Level advance (lockstep: c/n_chunks/stop are replicated).
        adv = (carry.c >= carry.n_chunks) & ~carry.stop
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
        return (steps < budget) & ~carry.stop

    def segment(carry: SCarry, budget_):
        nonlocal budget
        budget = budget_
        steps, carry = jax.lax.while_loop(outer_cond, outer_body,
                                          (jnp.int32(0), carry))
        # Executed chunk count (lockstep-replicated) — the host divides the
        # segment wall time by THIS, not the requested budget, so a segment
        # cut short never underestimates per-chunk cost (advisor finding).
        return steps, carry

    budget = None
    return segment


class ShardEngine:
    """Segmented multi-device exhaustive checker; reusable across runs.

    Same watchdog/checkpoint architecture as DeviceEngine: donated carries,
    adaptive segment budgets, atomic digest-guarded snapshots."""

    SEG_TARGET_S = 8.0
    SEG_CLAMP_S = 25.0
    SEG_MIN, SEG_MAX = 16, 1 << 16

    def __init__(self, config: CheckConfig, mesh: Mesh | None = None,
                 caps: ShardCapacities | None = None, seg_chunks: int = 256):
        self.config = config
        self.bounds = config.bounds
        self.lay = st.Layout.of(self.bounds)
        self.table = S.action_table(self.bounds, config.spec)
        self.A = len(self.table)
        self.mesh = mesh if mesh is not None else make_mesh()
        self.ndev = self.mesh.devices.size
        self.caps = caps or ShardCapacities()
        if self.caps.n_states < config.chunk:
            raise ValueError("ShardCapacities.n_states must be >= chunk")
        # Global state ids are int32 ``dev * Ncap + row`` (parent links,
        # viol_g): the address space must fit, or ids on high-numbered
        # devices wrap negative — corrupt traces and a silently missed
        # violation stop.  Fail at construction, not mid-run.
        if self.ndev * self.caps.n_states > 2**31 - 1:
            raise ValueError(
                f"ndev * n_states = {self.ndev} * {self.caps.n_states} "
                "exceeds the int32 global-id space (2^31-1); shrink "
                "ShardCapacities.n_states")
        self.seg_chunks = seg_chunks
        axes = _mesh_axes(self.mesh)
        nici = self.mesh.shape[_AXIS]
        specs = _carry_specs(axes)
        fn = _build_segment(config, self.caps, self.A, self.lay.width,
                            self.ndev, nici=nici, axes=axes)
        self._segment = jax.jit(jax.shard_map(
            fn, mesh=self.mesh, in_specs=(specs, P()),
            out_specs=(P(), specs),
            check_vma=False), donate_argnums=(0,))
        self._shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs)

    # -- carry construction / checkpointing ---------------------------------

    def _init_carry(self, init_vec, hi0, lo0, con0) -> SCarry:
        """Host-built initial carry: Init lives on its fingerprint owner."""
        nd, Ncap, A = self.ndev, self.caps.n_states, self.A
        W, Lcap = self.lay.width, self.caps.levels
        TBd = self.caps.table // BUCKET
        own = int(np.uint32(hi0) % np.uint32(nd))
        store = np.zeros((nd * Ncap, W), np.int32)
        store[own * Ncap] = init_vec
        parent = np.full((nd * Ncap,), -1, np.int32)
        lane = np.full((nd * Ncap,), -1, np.int32)
        conflag = np.zeros((nd * Ncap,), bool)
        conflag[own * Ncap] = con0
        tbl_hi = np.full((nd * TBd, BUCKET), _EMPTY, np.uint32)
        tbl_lo = np.full((nd * TBd, BUCKET), _EMPTY, np.uint32)
        b0 = int(np.uint32(lo0) & np.uint32(TBd - 1))
        tbl_hi[own * TBd + b0, 0] = hi0
        tbl_lo[own * TBd + b0, 0] = lo0
        n0 = np.zeros((nd,), np.int32)
        n0[own] = 1
        carry = SCarry(
            store=store, parent=parent, lane=lane, conflag=conflag,
            tbl_hi=tbl_hi, tbl_lo=tbl_lo,
            n_states=n0, lvl_start=np.zeros((nd,), np.int32),
            lvl_end=n0.copy(),
            viol_g=np.full((nd,), -1, np.int32),
            viol_i=np.zeros((nd,), np.int32),
            n_trans=np.zeros((nd * 2,), np.uint32),
            cov=np.zeros((nd * A,), np.int32),
            fail=np.zeros((nd,), np.int32),
            levels=np.zeros((Lcap,), np.int32),
            lvl=np.int32(1), c=np.int32(0), n_chunks=np.int32(1),
            stop=np.bool_(False))
        return self._put(carry)

    def _put(self, carry: SCarry) -> SCarry:
        return SCarry(*(jax.device_put(x, s)
                        for x, s in zip(carry, self._shardings)))

    def save_checkpoint(self, path: str, carry: SCarry,
                        init_key: tuple) -> None:
        """Atomic digest-guarded snapshot of the mesh-wide carry (the mesh
        size joins the digest key — a checkpoint is only resumable on an
        equal-size mesh, since the FP-ownership map depends on it)."""
        host = jax.device_get(carry)
        ckpt.atomic_savez(
            path,
            **{f"c{i}": np.asarray(x) for i, x in enumerate(host)},
            config_digest=np.uint64(ckpt.config_digest(
                self.config, self.caps, init_key + (self.ndev,))))

    def load_checkpoint(self, path: str, init_key: tuple) -> SCarry:
        with ckpt.load_npz_checked(
                path, ckpt.config_digest(
                    self.config, self.caps,
                    init_key + (self.ndev,))) as z:
            arrs = [z[f"c{i}"] for i in range(len(SCarry._fields))]
        return self._put(SCarry(*widen_legacy_n_trans(
            arrs, SCarry._fields)))

    # -- public API ----------------------------------------------------------

    def check(self, init_override: interp.PyState | None = None,
              checkpoint: str | None = None,
              checkpoint_every_s: float = 600.0,
              resume: str | None = None,
              on_progress=None, events: str | None = None) -> EngineResult:
        t0 = time.monotonic()
        tel = RunTelemetry(
            "shard", config=self.config, caps=self.caps,
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
        hi0, lo0 = sym_mod.init_fingerprint(self.config, init_py,
                                            init_vec)
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

        carry = self.load_checkpoint(resume, (hi0, lo0)) if resume \
            else self._init_carry(
                np.asarray(init_vec, np.int32), np.uint32(hi0),
                np.uint32(lo0), bool(interp.constraint_ok(init_py, bounds)))

        pacer = pacing.SegmentPacer(self.seg_chunks, self.SEG_MIN,
                                    self.SEG_MAX, self.SEG_TARGET_S,
                                    self.SEG_CLAMP_S)
        budget = pacer.budget
        last_ckpt = time.monotonic()
        while True:
            t_seg = time.monotonic()
            with tel.phases.phase("expand") as ph:
                steps_d, carry = self._segment(carry, jnp.int32(budget))
                ph.sync(steps_d)
            if tel.active:
                with tel.phases.phase("export"):
                    n_states_d, lvl, n_trans_d, cov_arr = jax.device_get(
                        (carry.n_states, carry.lvl, carry.n_trans,
                         carry.cov))
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
                    self.save_checkpoint(checkpoint, carry, (hi0, lo0))
                tel.checkpoint(checkpoint)
                last_ckpt = time.monotonic()
            budget = pacer.update(dt, executed)
            self.seg_chunks = budget

        (n_states_d, viol_gs, viol_is, n_trans_d, fail_d, n_levels,
         levels_dev, cov_arr) = jax.device_get(
             (carry.n_states, carry.viol_g, carry.viol_i, carry.n_trans,
              carry.fail, carry.lvl, carry.levels, carry.cov))
        fail = int(np.bitwise_or.reduce(np.asarray(fail_d)))
        if fail:
            raise RuntimeError(
                f"sharded search aborted: {decode_fail(fail)} "
                f"(caps={self.caps}, ndev={self.ndev}) — grow "
                "ShardCapacities and rerun")
        n_states = int(np.asarray(n_states_d).sum())
        viol_gs = np.asarray(viol_gs)
        viol_devs = np.nonzero(viol_gs >= 0)[0]
        # The partially-explored violating level is never recorded (the
        # level window only advances on completed levels), matching refbfs.
        levels_arr = [1] + [int(x) for x in
                            np.asarray(levels_dev)[:int(n_levels)]
                            if int(x) > 0]
        cov_tot = np.asarray(cov_arr).reshape(self.ndev, self.A).sum(axis=0)
        coverage: Counter = Counter()
        for a, inst in enumerate(self.table):
            if cov_tot[a]:
                coverage[inst.family] += int(cov_tot[a])

        violation = None
        if viol_devs.size:
            d = int(viol_devs[0])
            violation = self._extract_trace(
                carry, int(viol_gs[d]), int(np.asarray(viol_is)[d]))

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

    def _extract_trace(self, carry: SCarry, viol_g: int,
                       viol_i: int) -> Violation:
        """Walk the cross-device parent chain through the global arrays."""
        parent = np.asarray(carry.parent)   # [ndev * Ncap]
        lane = np.asarray(carry.lane)
        chain_idx = []
        cur = viol_g
        while cur >= 0:
            chain_idx.append(cur)
            cur = int(parent[cur])
        chain_idx.reverse()
        rows = np.asarray(carry.store[jnp.asarray(chain_idx)])
        chain = []
        for k, g in enumerate(chain_idx):
            py = interp.from_struct(
                st.unpack(rows[k], self.lay, np), self.bounds)
            label = self.table[int(lane[g])].label() if k > 0 else None
            chain.append((label, py))
        inv_name = DEADLOCK if viol_i == len(self.config.invariants) \
            else self.config.invariants[viol_i]
        return Violation(invariant=inv_name, state=chain[-1][1], trace=chain)


@functools.lru_cache(maxsize=None)
def _cached_engine(config: CheckConfig, mesh: Mesh,
                   caps: ShardCapacities) -> ShardEngine:
    return ShardEngine(config, mesh, caps)


def check(config: CheckConfig, mesh: Mesh | None = None,
          caps: ShardCapacities | None = None, **kw) -> EngineResult:
    """One-shot convenience mirroring the other engines' ``check``."""
    return _cached_engine(config, mesh if mesh is not None else make_mesh(),
                          caps or ShardCapacities()).check(**kw)


def reshard_checkpoint(config: CheckConfig, caps_src: ShardCapacities,
                       src_path: str, dst_path: str, ndev_dst: int,
                       caps_dst: ShardCapacities | None = None,
                       init_override: interp.PyState | None = None) -> dict:
    """Rewrite a shard-engine checkpoint for a different mesh size.

    A snapshot's FP-ownership map (``owner = fp_hi % ndev``) and its
    global discovery ids (``dev * Ncap + row``) are baked into the saved
    carry, so the digest pins the mesh size — without this loader, a
    pod-size change discards a multi-hour run.  The resharder rebuilds
    the carry host-side from first principles:

    - every stored state's dedup key is **recomputed** from its packed
      row (the fp/orbit pipeline is deterministic, so keys are
      bit-identical to the original run's) and the state moves to its
      new owner ``hi % ndev_dst``;
    - the already-expanded prefix of the current BFS window (``c``
      lockstep chunks) is **promoted into the done region** — expanded
      is expanded, whichever device now holds the row — so mid-level
      snapshots reshard exactly: the new window holds only unexpanded
      rows, ``c`` resets to 0, and level accounting (``levels``, the
      post-window next-level states) is unchanged;
    - parent links are remapped old-gid -> new-gid (traces survive);
    - per-device fingerprint tables are rebuilt by replaying the
      engine's own ``_dedup_insert`` over each new device's keys in
      its new discovery order;
    - counters that only ever report as mesh-wide sums (``n_trans``,
      ``cov``) are totalled onto device 0.

    ``caps_dst`` may also grow ``n_states``/``table`` (rescuing a run
    near FAIL_STORE/FAIL_PROBE); it defaults to ``caps_src``.  Refuses
    runs that already stopped, failed, or found a violation.  Returns a
    summary dict (per-device state counts, window sizes).
    """
    caps_dst = caps_dst or caps_src
    bounds = config.bounds
    lay = st.Layout.of(bounds)
    A = len(S.action_table(bounds, config.spec))
    B = config.chunk
    W = lay.width
    Ncap_s, Ncap_d = caps_src.n_states, caps_dst.n_states
    if ndev_dst * Ncap_d > 2**31 - 1:
        raise ValueError("ndev_dst * n_states exceeds the int32 global-id "
                         "address space")

    init_py = init_override if init_override is not None \
        else interp.init_state(bounds)
    init_vec = interp.to_vec(init_py, bounds)
    hi0, lo0 = sym_mod.init_fingerprint(config, init_py, init_vec)
    init_key = (int(hi0), int(lo0))

    with ckpt.load_npz_verified(src_path) as z:
        arrs = [np.asarray(z[f"c{i}"])
                for i in range(len(SCarry._fields))]
        stored_digest = int(z["config_digest"])
    arrs = widen_legacy_n_trans(arrs, SCarry._fields)
    src = SCarry(*arrs)
    nd_src = src.n_states.shape[0]
    want = ckpt.config_digest(config, caps_src, init_key + (nd_src,))
    if stored_digest != np.uint64(want):
        raise ValueError(
            f"checkpoint digest mismatch: {src_path} was not written by "
            f"this config/caps on a {nd_src}-device mesh")
    if bool(np.asarray(src.stop)):
        raise ValueError("run already complete (stop flag set) — "
                         "nothing to reshard")
    if int(np.bitwise_or.reduce(src.fail)) != 0:
        raise ValueError(f"refusing to reshard a failed run: "
                         f"{decode_fail(int(np.bitwise_or.reduce(src.fail)))}")
    if (src.viol_g >= 0).any():
        raise ValueError("refusing to reshard a run with a recorded "
                         "violation")

    # -- recompute every stored state's dedup key (batched, jitted) --------
    consts_j = jnp.asarray(fpr.lane_constants(W))
    faithful = "allLogs" in lay.shapes
    if config.symmetry:
        # host one-off: the bare scan, no prescan ladder (keys are
        # bit-identical, so either reproduces the store)
        orbit = sym_mod.build_orbit_fp(bounds, tuple(config.symmetry),
                                       consts_j, faithful)

        @jax.jit
        def fp_batch(vecs):
            structs = jax.vmap(lambda v: st.unpack(v, lay, jnp))(vecs)
            return orbit(structs)
    else:
        @jax.jit
        def fp_batch(vecs):
            return fpr.fingerprint(vecs, consts_j, jnp)

    # -- live rows in (group, old_dev, row) order, fully vectorized --------
    # group 0: done + expanded window prefix; 1: unexpanded window;
    # 2: next-level states.  Everything below is array-at-a-time so a
    # flagship-scale (10^8-row) rescue stays in numpy, not Python loops.
    store = src.store.reshape(nd_src, Ncap_s, W)
    c_cur = int(np.asarray(src.c))
    devs_l, rows_l, grp_l = [], [], []
    for d in range(nd_src):
        ns_d = int(src.n_states[d])
        ls_d, le_d = int(src.lvl_start[d]), int(src.lvl_end[d])
        ec_d = min(c_cur * B, le_d - ls_d)       # expanded window prefix
        g = np.empty((ns_d,), np.int8)
        g[:ls_d + ec_d] = 0
        g[ls_d + ec_d:le_d] = 1
        g[le_d:] = 2
        devs_l.append(np.full((ns_d,), d, np.int64))
        rows_l.append(np.arange(ns_d, dtype=np.int64))
        grp_l.append(g)
    devs = np.concatenate(devs_l)
    rows = np.concatenate(rows_l)
    grp = np.concatenate(grp_l)
    M = devs.size
    if M == 0:
        raise ValueError("empty checkpoint")
    # concat order is dev-major with ascending rows, so a stable sort on
    # group alone yields (group, dev, row) lexicographic order
    order = np.argsort(grp, kind="stable")
    devs, rows, grp = devs[order], rows[order], grp[order]
    vecs_all = np.ascontiguousarray(store[devs, rows])
    del store, order            # at 10^8-row rescue scale every full-
    #                             store intermediate is multi-GB
    #                             (round-2 advisor finding)

    # fixed-size batches (only the ragged tail padded) — one jit
    # compile, no second full-store copy
    CH = 8192
    keys_hi = np.empty((M,), np.uint32)
    keys_lo = np.empty((M,), np.uint32)
    for o in range(0, M, CH):
        nb = min(CH, M - o)
        chunk = vecs_all[o:o + nb]
        if nb < CH:
            chunk = np.concatenate(
                [chunk, np.zeros((CH - nb, W), np.int32)])
        h, l = fp_batch(jnp.asarray(chunk))
        keys_hi[o:o + nb] = np.asarray(h)[:nb]
        keys_lo[o:o + nb] = np.asarray(l)[:nb]

    # -- assign new owners, preserving sequence order per owner ------------
    owner_of = (keys_hi % np.uint32(ndev_dst)).astype(np.int64)
    counts = np.bincount(owner_of, minlength=ndev_dst)
    ns_new = counts.astype(np.int32)
    if (ns_new > Ncap_d).any():
        raise ValueError(
            f"caps_dst.n_states={Ncap_d} too small: a device would hold "
            f"{int(ns_new.max())} states — grow caps_dst")
    perm = np.argsort(owner_of, kind="stable")   # owner-major, seq order
    offsets = np.cumsum(counts) - counts
    local_idx = np.empty((M,), np.int64)
    local_idx[perm] = np.arange(M) - np.repeat(offsets, counts)
    new_gid = owner_of * Ncap_d + local_idx
    gid_map = np.full((nd_src * Ncap_s,), -1, np.int64)
    gid_map[devs * Ncap_s + rows] = new_gid
    ls_new = np.bincount(owner_of[grp == 0],
                         minlength=ndev_dst).astype(np.int32)
    le_new = ls_new + np.bincount(owner_of[grp == 1],
                                  minlength=ndev_dst).astype(np.int32)

    # -- rebuild the sharded leaves (vectorized scatters) ------------------
    # The src carry's big arrays must actually die before the destination
    # allocations: reshape views alone free nothing while ``src``/``arrs``
    # stay referenced, so the small surviving fields are extracted first
    # and the carry dropped wholesale (round-2 advisor finding).
    par_src = src.parent.reshape(nd_src, Ncap_s)
    lane_src = src.lane.reshape(nd_src, Ncap_s)
    con_src = src.conflag.reshape(nd_src, Ncap_s)
    parent_new = np.full((ndev_dst * Ncap_d,), -1, np.int32)
    lane_new = np.full((ndev_dst * Ncap_d,), -1, np.int32)
    con_new = np.zeros((ndev_dst * Ncap_d,), bool)
    p_old = par_src[devs, rows]
    parent_new[new_gid] = np.where(p_old >= 0, gid_map[np.maximum(p_old, 0)],
                                   -1).astype(np.int32)
    lane_new[new_gid] = lane_src[devs, rows]
    con_new[new_gid] = con_src[devs, rows]
    n_trans_tot = sum(
        acc64_int(src.n_trans.reshape(nd_src, 2)[d]) for d in range(nd_src))
    cov_tot = src.cov.reshape(nd_src, A).sum(axis=0)
    levels_src = np.asarray(src.levels).copy()
    lvl_src = np.asarray(src.lvl).copy()
    del par_src, lane_src, con_src, p_old, gid_map, src, arrs

    store_new = np.zeros((ndev_dst * Ncap_d, W), np.int32)
    store_new[new_gid] = vecs_all
    del vecs_all                 # scattered; free before the table build
    TBd = caps_dst.table // BUCKET
    tbl_hi_new = np.full((ndev_dst * TBd, BUCKET), _EMPTY, np.uint32)
    tbl_lo_new = np.full((ndev_dst * TBd, BUCKET), _EMPTY, np.uint32)
    ins = jax.jit(_dedup_insert)
    for o in range(ndev_dst):
        th = jnp.asarray(tbl_hi_new[o * TBd:(o + 1) * TBd])
        tl = jnp.asarray(tbl_lo_new[o * TBd:(o + 1) * TBd])
        sl = perm[offsets[o]:offsets[o] + counts[o]]  # new local order
        IB = 4096
        for jo in range(0, sl.size, IB):
            s2 = sl[jo:jo + IB]
            kh = np.full((IB,), 0, np.uint32)
            kl = np.full((IB,), 0, np.uint32)
            act = np.zeros((IB,), bool)
            kh[:s2.size] = keys_hi[s2]
            kl[:s2.size] = keys_lo[s2]
            act[:s2.size] = True       # fixed batch shape: one compile
            th, tl, is_new, pf = ins(th, tl, jnp.asarray(kh),
                                     jnp.asarray(kl), jnp.asarray(act))
            if bool(np.asarray(pf).any()) or \
                    not bool(np.asarray(is_new)[:s2.size].all()):
                raise RuntimeError(
                    "table rebuild failed (probe overflow or duplicate "
                    "key) — grow caps_dst.table")
        tbl_hi_new[o * TBd:(o + 1) * TBd] = np.asarray(th)
        tbl_lo_new[o * TBd:(o + 1) * TBd] = np.asarray(tl)

    n_trans_new = np.zeros((ndev_dst * 2,), np.uint32)
    n_trans_new[0] = np.uint32(n_trans_tot & 0xFFFFFFFF)
    n_trans_new[1] = np.uint32(n_trans_tot >> 32)
    cov_new = np.zeros((ndev_dst * A,), np.int32)
    cov_new[:A] = cov_tot

    # the levels array is caps.levels long — resize to caps_dst (the
    # digest is written for caps_dst, so a mismatched length would
    # silently clamp deep-level accounting)
    lvl_cur = int(lvl_src)
    if caps_dst.levels <= lvl_cur + 1:
        raise ValueError(
            f"caps_dst.levels={caps_dst.levels} too small: the run is "
            f"already at BFS level {lvl_cur}")
    levels_new = np.zeros((caps_dst.levels,), np.int32)
    n_keep = min(caps_src.levels, caps_dst.levels)
    levels_new[:n_keep] = levels_src[:n_keep]

    win = (le_new - ls_new).astype(np.int64)
    n_chunks = int(max(1, ((win + B - 1) // B).max()))
    dst = SCarry(
        store=store_new, parent=parent_new, lane=lane_new,
        conflag=con_new, tbl_hi=tbl_hi_new, tbl_lo=tbl_lo_new,
        n_states=ns_new, lvl_start=ls_new, lvl_end=le_new,
        viol_g=np.full((ndev_dst,), -1, np.int32),
        viol_i=np.zeros((ndev_dst,), np.int32),
        n_trans=n_trans_new, cov=cov_new,
        fail=np.zeros((ndev_dst,), np.int32),
        levels=levels_new, lvl=lvl_src,
        c=np.int32(0), n_chunks=np.int32(n_chunks),
        stop=np.bool_(False))
    ckpt.atomic_savez(
        dst_path,
        **{f"c{i}": np.asarray(x) for i, x in enumerate(dst)},
        config_digest=np.uint64(ckpt.config_digest(
            config, caps_dst, init_key + (ndev_dst,))))
    return {"ndev_src": nd_src, "ndev_dst": ndev_dst,
            "n_states": int(ns_new.sum()),
            "per_device": ns_new.tolist(),
            "window": win.tolist(),
            "promoted_expanded": c_cur > 0}
