"""Device-resident BFS engine — the flagship L4 checker (SURVEY §7.1 step 5-6).

``engine.py`` proved the semantics with a host-side dedup loop; this module is
the TPU-first redesign the hardware demands.  Measured on the rounds 1-5
machine (inherited, not re-measured on this one), every host↔device round
trip cost ~0.7 s and every eager-op compile ~10 s, so the architecture keeps
**all search state resident in HBM**: the
state store, the fingerprint table, the frontier, parent links, coverage
counters and violation flags never leave the device.  The host sees nothing
but a ``done`` scalar until the search ends, then makes at most two more
gathers to reconstruct a counterexample trace.

Execution is **segmented**: one jitted *segment* advances the search by up to
``seg_chunks`` chunk expansions (crossing BFS-level boundaries freely) and
returns the carry, whose buffers are **donated** back into the next segment
call — zero copies, zero reallocation.  Segmenting exists because single XLA
program executions were killed by the rounds 1-5 machine's watchdog at roughly
a minute of device time (measured empirically there: ~25 s fine, ~2 min kills
the TPU worker process; the 60 s clamp is inherited, not re-measured on this
machine); it also gives the host a natural place to snapshot the
carry for checkpoint/resume and to report per-level progress (SURVEY §5).
The search is resumable mid-level: the chunk cursor is part of the carry.

Architecture (all shapes static — XLA's compilation model, SURVEY §7.2.4):

- **Store** ``[Ncap, W] int32``: every discovered state, in discovery order.
  Because BFS is level-synchronous, each level is a *contiguous segment*
  ``[level_start, level_end)`` — the frontier is a slice of the store, never
  a separate buffer.
- **Fingerprint table** ``2·[Tcap/8, 8] uint32``: a bucketized open-
  addressing hash set of (hi, lo) fingerprint pairs (TLC's FP64 set, SURVEY
  §2.8), probed bucket-rows-at-a-time with batched inserts resolved by a
  scatter-min claim protocol (full design notes on ``_dedup_insert``).
  ``scatter-min`` by flat index makes the *first* candidate in discovery
  order the winner — exactly the oracle's first-discoverer-is-parent rule,
  so parent links and traces match refbfs.
- **Per-chunk fused step** (``ops/kernels.build_step``): unpack → all action
  guards/effects → canonicalize → pack → fingerprint → invariants →
  constraint, for ``chunk`` states × A action lanes at a time.
- **TLC CONSTRAINT semantics**: states violating the bound are stored,
  counted and invariant-checked, but their expansion lanes are masked off
  (``conflag`` gates ``valid``).
- **Failure is loud** (SURVEY §4.5): store overflow, level overflow, probe
  overflow and transition-capacity overflow each set a flag that aborts the
  search; the host raises.  Nothing is silently clamped.

Fingerprint collisions merge states, as in TLC (probability ~2^-64 per pair;
the parity tests run on spaces where a collision would surface as a count
mismatch).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import time
from collections import Counter
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.config import CheckConfig
from raft_tla_tpu.engine import DEADLOCK, EngineResult, Violation
from raft_tla_tpu.obs import RunTelemetry
from raft_tla_tpu.models import interp, invariants as inv_mod, spec as S
from raft_tla_tpu.ops import fingerprint as fpr
from raft_tla_tpu.ops import kernels
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops import symmetry as sym_mod
from raft_tla_tpu.utils import ckpt
from raft_tla_tpu.utils import pacing

I32 = jnp.int32
U32 = jnp.uint32
_EMPTY = np.uint32(0xFFFFFFFF)   # table sentinel: both words all-ones
_MAX_PROBE = 64                  # probe-iteration safety cap -> fail flag
BUCKET = 8                       # fingerprint-table slots per bucket row


@dataclasses.dataclass(frozen=True)
class Capacities:
    """Static shapes of one compiled search. Doubling any field recompiles."""

    n_states: int = 1 << 20      # store rows (Ncap)
    levels: int = 256            # max BFS depth (Lcap)

    @property
    def table(self) -> int:      # hash slots, load factor <= 0.5
        return 1 << (2 * self.n_states - 1).bit_length()

    def grown(self) -> "Capacities":
        return dataclasses.replace(self, n_states=self.n_states * 2)


# Chip-measured note (round 4, runs/filter_inengine.out): inside a
# while_loop body that both GATHERS from and SCATTERS to the same carry
# table, XLA materializes a full defensive copy of the table every
# iteration (~45 ns per byte of table) — in-place donation does not
# apply.  For this EXACT table the size is a correctness requirement
# (unlike the DDD engines' shrinkable lossy filter), so large --cap
# runs pay ~45 ms/chunk per GiB of table; that copy, not the probe
# gathers, is what an exact table of 2^28 slots costs (round 2 measured
# ~8k orbits/s there).  The DDD engines are the
# designed escape (host-exact dedup, small filter).
def _dedup_insert(tbl_hi, tbl_lo, key_hi, key_lo, active):
    """Batched insert-if-absent of fingerprint pairs into the hash set.

    Returns ``(tbl_hi, tbl_lo, is_new, unres)``.  ``is_new[c]`` is True
    iff candidate c's key was absent and c is the *first* active candidate
    (smallest flat index) carrying that key in this batch.  ``unres[c]``
    is True iff lane c's probe was still unresolved at ``_MAX_PROBE`` —
    its key was neither matched nor inserted.  The table engines treat
    any unresolved lane as fatal (``jnp.any(unres) * FAIL_PROBE``); the
    devdedup filter instead streams such lanes to the exact host tier.

    Two-stage design (dedup is the chunk pipeline's hottest stage —
    measured 30 ms of a 53 ms chunk before these changes):

    1. **In-batch dedup by sort**: one ``lexsort`` finds each key's first
       active occurrence; only those lanes probe the table at all.  BFS
       batches carry heavy duplication (every state is typically produced
       by several (parent, action) lanes), so this removes most table
       traffic outright.
    2. **Probe with a hashed claim domain**: contenders for an empty slot
       scatter-min their flat index into a small claim array indexed by
       ``slot mod CA`` rather than a table-sized one (which materialized
       the full table width every probe iteration).  Distinct slots
       sharing a claim cell are false contention: the cell's loser simply
       re-contends next iteration — correctness is unaffected, and at
       CA = 4·BA the collision rate is a few percent.

    ``scatter-min`` by flat index makes the *first* candidate in discovery
    order the winner — the oracle's first-discoverer-is-parent rule.
    """
    BA = key_hi.shape[0]
    TB, S = tbl_hi.shape            # buckets x slots
    bmask = jnp.uint32(TB - 1)
    ids = jnp.arange(BA, dtype=I32)
    h0 = key_lo & bmask             # lo lane is already avalanche-mixed

    # -- stage 1: batch-first occurrences (smallest id per distinct key) --
    # Two stable sorts (lexsort cost scales with key count); inactive lanes
    # sort to the back under all-ones keys.  An active lane whose real key
    # is all-ones may interleave with them and get conservatively marked
    # first-of-key — it then probes redundantly and resolves as a duplicate
    # through the claim protocol, so correctness is unaffected.
    skh = jnp.where(active, key_hi, _EMPTY)
    skl = jnp.where(active, key_lo, _EMPTY)
    perm = jnp.lexsort((skl, skh))      # stable: ties keep id order
    ph, pl = key_hi[perm], key_lo[perm]
    pa = active[perm]
    same_as_prev = jnp.concatenate([
        jnp.zeros((1,), bool),
        (ph[1:] == ph[:-1]) & (pl[1:] == pl[:-1]) & pa[1:] & pa[:-1]])
    first_of_key = jnp.zeros((BA,), bool).at[perm].set(~same_as_prev)
    probe = active & first_of_key

    CA = max(1024, 1 << (4 * BA - 1).bit_length())
    cmask = jnp.int32(CA - 1)

    def cond(c):
        _, _, unres, _, d, _ = c
        return jnp.any(unres) & (d < _MAX_PROBE)

    def body(c):
        tbl_hi, tbl_lo, unres, is_new, d, dist = c
        bidx = ((h0 + dist.astype(U32)) & bmask).astype(I32)
        # One ROW gather per lane (the TPU embedding-lookup fast path)
        # examines S slots at once — the whole batch advances in lockstep,
        # so iteration count is set by the worst lane, and S-wide buckets
        # divide the worst probe chain by S.
        row_hi, row_lo = tbl_hi[bidx], tbl_lo[bidx]          # [L, S]
        slot_empty = (row_hi == _EMPTY) & (row_lo == _EMPTY)
        slot_match = (row_hi == key_hi[:, None]) & (row_lo == key_lo[:, None])
        dup_old = unres & jnp.any(slot_match, axis=1)
        has_empty = jnp.any(slot_empty, axis=1)
        contend = unres & ~dup_old & has_empty
        # Claim a bucket via scatter-min into a small hashed claim domain;
        # smallest flat index wins — the oracle's first-discoverer rule.
        cidx = bidx & cmask
        claim = jnp.full((CA,), BA, dtype=I32).at[
            jnp.where(contend, cidx, CA)].min(
                jnp.where(contend, ids, BA), mode="drop")
        won = contend & (claim[cidx] == ids)
        wslot = jnp.argmax(slot_empty, axis=1)               # first empty
        wb = jnp.where(won, bidx, TB)
        tbl_hi = tbl_hi.at[wb, wslot].set(key_hi, mode="drop")
        tbl_lo = tbl_lo.at[wb, wslot].set(key_lo, mode="drop")
        # Losers consult the winner through the (VMEM-sized) claim/key
        # arrays instead of re-gathering the table: if the winner put MY
        # key in MY bucket, I'm a duplicate; otherwise my bucket merely
        # gained an entry (same bucket) or nothing changed (false claim
        # collision) — either way retry the same bucket, which is only
        # left behind when it has no empty slot at all.
        wid = jnp.clip(claim[cidx], 0, BA - 1)
        dup_batch = contend & ~won & (bidx[wid] == bidx) & \
            (key_hi[wid] == key_hi) & (key_lo[wid] == key_lo)
        resolved = dup_old | won | dup_batch
        unres = unres & ~resolved
        dist = dist + (unres & ~has_empty).astype(I32)       # bucket full
        return tbl_hi, tbl_lo, unres, is_new | won, d + 1, dist

    init = (tbl_hi, tbl_lo, probe, jnp.zeros((BA,), bool), jnp.int32(0),
            jnp.zeros((BA,), I32))
    tbl_hi, tbl_lo, unres, is_new, _, _ = jax.lax.while_loop(cond, body, init)
    return tbl_hi, tbl_lo, is_new, unres


# Failure bitmask (the "fail loudly" contract, SURVEY §4.5).
FAIL_WIDTH = 1      # a successor exceeded a tensor-encoding capacity
FAIL_PROBE = 2      # linear probe exceeded _MAX_PROBE (table too full)
FAIL_STORE = 4      # more distinct states than Capacities.n_states
FAIL_LEVEL = 8      # BFS deeper than Capacities.levels
FAIL_ROUTE = 32     # a routing budget overflowed: shard engine's
                    # all_to_all exchange halo, or the EP-routed step's
                    # route_rows compaction slots (ddd_engine)
FAIL_INDEX = 64     # ddd engines: discovery index past their ceiling
                    # (ddd_engine._IDX_CEIL)

_FAIL_TEXT = {
    FAIL_WIDTH: "state-width overflow (encoding capacity exceeded)",
    FAIL_PROBE: "fingerprint-table probe overflow (table too full)",
    FAIL_STORE: "state-store capacity exceeded",
    FAIL_LEVEL: "BFS level capacity exceeded",
    FAIL_ROUTE: "routing budget exceeded (all_to_all halo or EP "
                "route_rows too small)",
    FAIL_INDEX: "global state index reached the int32 ceiling "
                "(2^31-1 rows/device is the per-run limit)",
}


def decode_fail(fail_bits: int) -> str:
    return "; ".join(txt for bit, txt in _FAIL_TEXT.items()
                     if fail_bits & bit) or "unknown"


# -- 64-bit run counters without jax_enable_x64 ----------------------------
# JAX's default x64-disabled mode silently narrows jnp.int64 to int32, and
# the round-1 flagship already logged 258M transitions — a 5-server/2-value
# run exceeds 2^31, where an int32 accumulator wraps silently.  Counters
# that can pass 2^31 are therefore carried as TWO uint32 limbs with
# branchless carry propagation (regression: tests/test_device_engine.py::
# test_transition_counter_64bit).  State *indices* stay int32: the device
# and shard engines bound rows by Capacities.n_states (far below 2^31 at
# any allocatable HBM size; the shard engine additionally asserts
# ndev * n_states fits the int32 global-id space at construction), and the
# ddd engines keep discovery indices on the host as int64 (FAIL_INDEX is
# their loud guard).

def _acc64_zero():
    return jnp.zeros((2,), U32)


def _acc64_add(acc, delta):
    """``acc (+)= delta`` for a traced int32 ``0 <= delta < 2^31``."""
    lo = acc[..., 0] + delta.astype(U32)
    hi = acc[..., 1] + (lo < acc[..., 0]).astype(U32)
    return jnp.stack([lo, hi], axis=-1)


def acc64_int(arr) -> int:
    """Host side: combine two-limb counters (summing any leading axes)."""
    a = np.asarray(arr, dtype=np.uint64).reshape(-1, 2)
    return int(((a[:, 1] << np.uint64(32)) | a[:, 0]).sum())


def widen_legacy_n_trans(arrs: list, fields: tuple) -> list:
    """Checkpoint migration: round-1 checkpoints carried ``n_trans`` as a
    scalar (or per-device vector of) int32; widen to the two-limb uint32
    layout so long runs resume across the upgrade."""
    i = fields.index("n_trans")
    a = np.asarray(arrs[i])
    if a.dtype != np.uint32:
        lo = a.astype(np.int64).reshape(-1).astype(np.uint32)
        limbs = np.stack([lo, np.zeros_like(lo)], axis=-1)
        arrs[i] = limbs[0] if a.ndim == 0 else limbs.reshape(-1)
    return arrs


class Carry(NamedTuple):
    """The segment carry: the entire search state, resident in HBM.

    A NamedTuple is a pytree, so it threads through ``lax.while_loop`` and
    ``donate_argnums`` unchanged while keeping every access self-describing.
    """

    store: jax.Array      # [Ncap, W] every discovered state, discovery order
    parent: jax.Array     # [Ncap] parent row (trace links)
    lane: jax.Array       # [Ncap] action lane that produced the row
    conflag: jax.Array    # [Ncap] state satisfies the CONSTRAINT -> expand
    tbl_hi: jax.Array     # [Tcap] fingerprint table, hi words
    tbl_lo: jax.Array     # [Tcap] fingerprint table, lo words
    n_states: jax.Array   # rows used
    lvl_start: jax.Array  # current BFS level window [lvl_start, lvl_end)
    lvl_end: jax.Array
    viol_g: jax.Array     # first violating row, -1 if none
    viol_i: jax.Array     # index into config.invariants
    n_trans: jax.Array    # [2] uint32 limbs: enabled (state, action) pairs
    cov: jax.Array        # [A] per-lane new-state counts
    fail: jax.Array       # FAIL_* bitmask
    levels: jax.Array     # [Lcap] per-level new-state counts
    lvl: jax.Array        # current level number
    c: jax.Array          # chunk cursor within the current level


def _carry_done(carry: Carry):
    """Search-complete predicate over the segment carry."""
    return ((carry.lvl_end <= carry.lvl_start) | (carry.viol_g >= 0)
            | (carry.fail != 0))


def _build_segment(config: CheckConfig, caps: Capacities, A: int, W: int):
    """One watchdog-safe slice of the search: ≤ ``budget`` chunk steps.

    ``budget`` is a traced scalar, so the host can retune the segment length
    every dispatch (targeting a fixed seconds-per-segment) without
    recompiling.
    """
    B = config.chunk
    n_inv = len(config.invariants)
    # The prescan ladder is resolved inside build_step at CONSTRUCTION
    # time (kernels._prescan_enabled) — set RAFT_TLA_PRESCAN before
    # building the engine to override it.
    step = kernels.build_step(config.bounds, config.spec,
                              tuple(config.invariants), config.symmetry,
                              view=config.view)
    Ncap, Lcap, Tcap = caps.n_states, caps.levels, caps.table
    BIG = jnp.int32(np.iinfo(np.int32).max)

    def chunk_body(carry: Carry) -> Carry:
        (store, parent, lane, conflag, tbl_hi, tbl_lo, n_states,
         lvl_start, lvl_end, viol_g, viol_i, n_trans, cov, fail,
         levels, lvl, c) = carry
        start = lvl_start + c * B
        gstart = jnp.minimum(start, Ncap - B)      # clamped window (see below)
        rows_g = gstart + jnp.arange(B, dtype=I32)
        row_act = (rows_g >= start) & (rows_g < lvl_end)
        vecs = jax.lax.dynamic_slice(store, (gstart, 0), (B, W))
        out = step(vecs)
        con_par = jax.lax.dynamic_slice(conflag, (gstart,), (B,))
        valid = out["valid"] & row_act[:, None] & con_par[:, None]
        n_trans = _acc64_add(n_trans, jnp.sum(valid.astype(I32)))
        fail = fail | jnp.any(valid & out["overflow"]) * FAIL_WIDTH

        fhi = out["fp_hi"].reshape(-1)
        flo = out["fp_lo"].reshape(-1)
        fvalid = valid.reshape(-1)
        tbl_hi, tbl_lo, is_new, pfail = _dedup_insert(
            tbl_hi, tbl_lo, fhi, flo, fvalid)
        fail = fail | jnp.any(pfail) * FAIL_PROBE

        # Append new states to the store in discovery order.
        pos = n_states + jnp.cumsum(is_new.astype(I32)) - 1
        sl = jnp.where(is_new & (pos < Ncap), pos, Ncap)
        svecs = out["svecs"].reshape(B * A, W)
        store = store.at[sl].set(svecs, mode="drop")
        flat_b = jnp.arange(B * A, dtype=I32) // A
        flat_a = jnp.arange(B * A, dtype=I32) % A
        parent = parent.at[sl].set(gstart + flat_b, mode="drop")
        lane = lane.at[sl].set(flat_a, mode="drop")
        conflag = conflag.at[sl].set(out["con_ok"].reshape(-1), mode="drop")
        cov = cov.at[jnp.where(is_new, flat_a, A)].add(1, mode="drop")

        n_new = jnp.sum(is_new.astype(I32))
        fail = fail | (n_states + n_new > Ncap) * FAIL_STORE
        n_states = jnp.minimum(n_states + n_new, Ncap)

        # First invariant violation among new states, in discovery order.
        inv_bad = is_new & jnp.any(
            ~out["inv_ok"].reshape(B * A, n_inv), axis=-1) if n_inv \
            else jnp.zeros((B * A,), bool)
        first = jnp.min(jnp.where(inv_bad, jnp.arange(B * A, dtype=I32), BIG))
        bad_inv = jnp.argmax(
            ~out["inv_ok"].reshape(B * A, n_inv)
            [jnp.minimum(first, B * A - 1)]) if n_inv else jnp.int32(0)
        g_target = pos[jnp.minimum(first, B * A - 1)]
        if config.check_deadlock:
            # TLC's default deadlock check: an expanded row with no enabled
            # action (pre-constraint — CONSTRAINT gates exploration, not
            # enabledness).  Flat priority b*A orders it before any
            # successor of the same row, after earlier rows' successors.
            dead = row_act & con_par & ~jnp.any(out["valid"], axis=1)
            drow = jnp.min(jnp.where(dead, jnp.arange(B, dtype=I32), BIG))
            dpos = jnp.where(drow < BIG // A, drow * A, BIG)
            use_dead = dpos < first
            first = jnp.minimum(first, dpos)
            g_target = jnp.where(use_dead,
                                 gstart + jnp.minimum(drow, B - 1), g_target)
            bad_inv = jnp.where(use_dead, jnp.int32(n_inv), bad_inv)
        has_viol = first < BIG
        new_viol = has_viol & (viol_g < 0)
        viol_g = jnp.where(new_viol, g_target, viol_g)
        viol_i = jnp.where(new_viol, bad_inv, viol_i)
        return Carry(store, parent, lane, conflag, tbl_hi, tbl_lo, n_states,
                     lvl_start, lvl_end, viol_g, viol_i, n_trans, cov, fail,
                     levels, lvl, c + 1)

    def outer_body(sc):
        """Run chunks until the level is exhausted or the budget runs out,
        then (maybe) advance the level window — scalar selects only, so the
        big buffers are never threaded through a conditional."""
        steps, carry = sc
        n_chunks = (carry.lvl_end - carry.lvl_start + B - 1) // B

        def ccond(cc):
            s, inner = cc
            return ((inner.c < n_chunks) & (inner.viol_g < 0) &
                    (inner.fail == 0) & (s < budget))

        def cbody(cc):
            s, inner = cc
            return s + 1, chunk_body(inner)

        steps, carry = jax.lax.while_loop(ccond, cbody, (steps, carry))
        (store, parent, lane, conflag, tbl_hi, tbl_lo, n_states,
         lvl_start, lvl_end, viol_g, viol_i, n_trans, cov, fail,
         levels, lvl, c) = carry
        adv = (c >= n_chunks) & (viol_g < 0) & (fail == 0)
        n_new = n_states - lvl_end
        levels = levels.at[jnp.where(adv, jnp.minimum(lvl, Lcap - 1),
                                     Lcap)].set(n_new, mode="drop")
        fail = fail | (adv & (lvl >= Lcap - 1) & (n_new > 0)) * FAIL_LEVEL
        lvl_start = jnp.where(adv, lvl_end, lvl_start)
        lvl_end = jnp.where(adv, n_states, lvl_end)
        lvl = jnp.where(adv, lvl + 1, lvl)
        c = jnp.where(adv, 0, c)
        return steps, Carry(store, parent, lane, conflag, tbl_hi, tbl_lo,
                            n_states, lvl_start, lvl_end, viol_g, viol_i,
                            n_trans, cov, fail, levels, lvl, c)

    def outer_cond(sc):
        steps, carry = sc
        return (steps < budget) & ~_carry_done(carry)

    def segment(carry, budget_):
        nonlocal budget
        budget = budget_
        _, carry = jax.lax.while_loop(outer_cond, outer_body,
                                      (jnp.int32(0), carry))
        return carry, _carry_done(carry)

    budget = None
    return segment


def _build_init(caps: Capacities, A: int, W: int):
    """The initial segment carry: Init in the store, its FP in the table."""
    Ncap, Lcap, Tcap = caps.n_states, caps.levels, caps.table
    TB = Tcap // BUCKET

    def init(init_vec, init_key_hi, init_key_lo, init_con):
        store = jnp.zeros((Ncap, W), I32).at[0].set(init_vec)
        parent = jnp.full((Ncap,), -1, I32)
        lane = jnp.full((Ncap,), -1, I32)
        conflag = jnp.zeros((Ncap,), bool).at[0].set(init_con)
        b0 = (init_key_lo & jnp.uint32(TB - 1)).astype(I32)
        tbl_hi = jnp.full((TB, BUCKET), _EMPTY, U32).at[b0, 0].set(
            init_key_hi)
        tbl_lo = jnp.full((TB, BUCKET), _EMPTY, U32).at[b0, 0].set(
            init_key_lo)
        levels = jnp.zeros((Lcap,), I32)
        return Carry(store, parent, lane, conflag, tbl_hi, tbl_lo,
                     jnp.int32(1), jnp.int32(0), jnp.int32(1),
                     jnp.int32(-1), jnp.int32(0), _acc64_zero(),
                     jnp.zeros((A,), I32), jnp.int32(0),
                     levels, jnp.int32(1), jnp.int32(0))

    return init


def aggregate_coverage(table, cov) -> Counter:
    """Per-action-family coverage from the device counters ([.., A]) —
    ONE definition for every engine's result assembly and stats stream."""
    cov = np.asarray(cov).reshape(-1, len(table)).sum(axis=0)
    out: Counter = Counter()
    for a, inst in enumerate(table):
        if cov[a]:
            out[inst.family] += int(cov[a])
    return out


class DeviceEngine:
    """One compiled exhaustive checker; reusable across runs."""

    # Adaptive segment sizing: target seconds of device time per dispatch,
    # far enough under the ~60 s watchdog to absorb a 2-3x misprediction.
    SEG_TARGET_S = 8.0
    SEG_CLAMP_S = 25.0       # hard ceiling on projected segment seconds
    SEG_MIN, SEG_MAX = 16, 1 << 16

    def __init__(self, config: CheckConfig, caps: Capacities | None = None,
                 device=None, seg_chunks: int = 64):
        self.config = config
        self.bounds = config.bounds
        self.lay = st.Layout.of(self.bounds)
        self.table = S.action_table(self.bounds, config.spec)
        self.A = len(self.table)
        self.caps = caps or Capacities()
        if self.caps.n_states < config.chunk:
            raise ValueError("Capacities.n_states must be >= config.chunk")
        # jit follows input placement; ``device`` (None = default backend)
        # is applied to the four small inputs in check().
        self.device = device
        self.seg_chunks = seg_chunks    # initial budget; adapted per segment
        self._init = jax.jit(_build_init(self.caps, self.A, self.lay.width))
        # The carry's buffers are donated: each segment updates the search
        # state in place in HBM; the host only syncs on the `done` scalar.
        self._segment = jax.jit(
            _build_segment(config, self.caps, self.A, self.lay.width),
            donate_argnums=(0,))

    # -- checkpoint / resume (SURVEY §5: TLC's states/ + -recover analog) ---
    # A checkpoint is the full carry — the search is a pure function of it,
    # so resume is exact: same discovery order, counts, traces.

    def save_checkpoint(self, path: str, carry: Carry,
                        init_key: tuple) -> None:
        """Snapshot the carry to ``path`` (.npz), atomically.  The digest
        pins the full model identity (bounds/spec/invariants/symmetry/
        chunk/capacities) AND the initial state's dedup key, so a resume
        under a different config or a different ``init_override`` fails
        loudly (utils/ckpt.py)."""
        host = jax.device_get(carry)
        ckpt.atomic_savez(
            path,
            **{f"c{i}": np.asarray(x) for i, x in enumerate(host)},
            config_digest=np.uint64(
                ckpt.config_digest(self.config, self.caps, init_key)),
            width=np.int64(self.lay.width))

    def load_checkpoint(self, path: str, init_key: tuple) -> Carry:
        """Load a carry saved by :meth:`save_checkpoint` (digest-checked)."""
        with ckpt.load_npz_checked(
                path, ckpt.config_digest(self.config, self.caps,
                                         init_key)) as z:
            arrs = [z[f"c{i}"] for i in range(len(Carry._fields))]
        arrs = widen_legacy_n_trans(arrs, Carry._fields)
        carry = Carry(*(jnp.asarray(a) for a in arrs))
        if self.device is not None:
            carry = jax.device_put(carry, self.device)
        return carry

    def check(self, init_override: interp.PyState | None = None,
              checkpoint: str | None = None,
              checkpoint_every_s: float = 600.0,
              resume: str | None = None,
              on_progress=None, retain_carry: bool = False,
              events: str | None = None) -> EngineResult:
        """``on_progress``, if given, is called after every segment with the
        shared :class:`~raft_tla_tpu.obs.ProgressRecord` dict (SURVEY §5
        observability): wall seconds, states found, BFS level, transitions,
        dedup hit rate, cumulative + incremental throughput, live
        per-action-family coverage — TLC's ``-coverage 1`` minute-ticker
        analog, here per segment.  ``events`` (or ``RAFT_TLA_EVENTS``)
        additionally streams the versioned run-event log (obs/events.py).
        Either costs one extra batched transfer per segment.

        ``retain_carry=True`` keeps the final carry on ``self.retained_carry``
        (store/conflag for post-hoc passes, e.g. liveness graph export —
        models/liveness.engine_graph).  The retained buffers stay resident
        in HBM until the caller sets ``retained_carry = None``; a second
        ``check`` on the same engine allocates a fresh carry alongside."""
        t0 = time.monotonic()
        tel = RunTelemetry(
            "device", config=self.config, caps=self.caps,
            on_progress=on_progress, events=events,
            resumed=resume is not None,
            n0=1 if resume is None else None, t0=t0)
        try:
            return self._check_impl(tel, t0, init_override, checkpoint,
                                    checkpoint_every_s, resume, retain_carry)
        finally:
            tel.close()

    def _check_impl(self, tel, t0, init_override, checkpoint,
                    checkpoint_every_s, resume, retain_carry) -> EngineResult:
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

        args = (jnp.asarray(init_vec, I32), jnp.uint32(hi0), jnp.uint32(lo0),
                jnp.bool_(interp.constraint_ok(init_py, bounds)))
        if self.device is not None:
            args = jax.device_put(args, self.device)
        carry = self.load_checkpoint(resume, (hi0, lo0)) if resume \
            else self._init(*args)
        # Segment loop: each dispatch runs <= budget chunk expansions on
        # device, then the host syncs on one scalar.  Buffers are donated, so
        # the search state never moves.  The budget is retuned each dispatch
        # toward SEG_TARGET_S seconds (the first, compile-carrying dispatch
        # is excluded from the timing signal).
        pacer = pacing.SegmentPacer(self.seg_chunks, self.SEG_MIN,
                                    self.SEG_MAX, self.SEG_TARGET_S,
                                    self.SEG_CLAMP_S)
        budget = pacer.budget
        last_ckpt = time.monotonic()
        while True:
            t_seg = time.monotonic()
            with tel.phases.phase("expand") as ph:
                carry, done = self._segment(carry, jnp.int32(budget))
                ph.sync(done)
            if tel.active:
                with tel.phases.phase("export") as ph:
                    n_states, lvl, n_trans, cov = jax.device_get(
                        (carry.n_states, carry.lvl, carry.n_trans,
                         carry.cov))
                tel.segment(
                    n_states=int(n_states), level=int(lvl),
                    n_transitions=acc64_int(n_trans),
                    coverage=dict(aggregate_coverage(self.table, cov)))
            if bool(done):
                break
            dt = time.monotonic() - t_seg
            if checkpoint and (time.monotonic() - last_ckpt
                               >= checkpoint_every_s):
                with tel.phases.phase("snapshot"):
                    self.save_checkpoint(checkpoint, carry, (hi0, lo0))
                tel.checkpoint(checkpoint)
                last_ckpt = time.monotonic()
            # this segment loop has no executed-chunk count; the requested
            # budget only underestimates chunk cost on early-exiting final
            # segments, which break above — harmless (pacing.py policy)
            budget = pacer.update(dt, budget)
            self.seg_chunks = budget        # warm check() calls start tuned
        if retain_carry:
            self.retained_carry = carry
        # One batched transfer for all the small outputs; the wide arrays
        # (store, parent, lane) stay on device unless a trace is needed.
        (n_states, viol_g, viol_i, n_trans, fail, n_levels, levels_dev,
         cov_arr) = jax.device_get((
             carry.n_states, carry.viol_g, carry.viol_i, carry.n_trans,
             carry.fail, carry.lvl, carry.levels, carry.cov))
        n_states, viol_g, fail = int(n_states), int(viol_g), int(fail)
        if fail:
            raise RuntimeError(
                f"device search aborted: {decode_fail(fail)} "
                f"(caps={self.caps}) — grow Capacities and rerun")
        out = {"store": carry.store, "parent": carry.parent,
               "lane": carry.lane, "viol_i": viol_i,
               "n_transitions": acc64_int(n_trans)}
        # The partially-explored violating level is never recorded (the
        # level window only advances on completed levels), matching refbfs.
        levels_arr = [1] + [int(x) for x in levels_dev[:int(n_levels)]
                            if int(x) > 0]
        coverage: Counter = Counter()
        for a, inst in enumerate(self.table):
            if cov_arr[a]:
                coverage[inst.family] += int(cov_arr[a])

        violation = None
        if viol_g >= 0:
            violation = self._extract_trace(out, viol_g)

        result = EngineResult(
            n_states=n_states,
            diameter=len(levels_arr) - 1,
            n_transitions=int(out["n_transitions"]),
            coverage=coverage,
            violation=violation,
            levels=levels_arr,
            wall_s=time.monotonic() - t0)
        tel.run_end(result)
        return result

    def _extract_trace(self, out, viol_g: int) -> Violation:
        """Two extra transfers: parent/lane links, then the chain's rows."""
        n = viol_g + 1
        parent = np.asarray(out["parent"][:n])
        lane = np.asarray(out["lane"][:n])
        chain_idx = []
        cur = viol_g
        while cur >= 0:
            chain_idx.append(cur)
            cur = int(parent[cur])
        chain_idx.reverse()
        rows = np.asarray(out["store"][jnp.asarray(chain_idx)])
        chain = []
        for k, g in enumerate(chain_idx):
            py = interp.from_struct(
                st.unpack(rows[k], self.lay, np), self.bounds)
            label = self.table[int(lane[g])].label() if g > 0 else None
            chain.append((label, py))
        vi = int(out["viol_i"])   # lint: jit-ok — host path, out is fetched
        inv_name = DEADLOCK if vi == len(self.config.invariants) \
            else self.config.invariants[vi]
        return Violation(invariant=inv_name, state=chain[-1][1], trace=chain)


@functools.lru_cache(maxsize=None)
def _cached_engine(config: CheckConfig, caps: Capacities) -> DeviceEngine:
    return DeviceEngine(config, caps)


def check(config: CheckConfig, caps: Capacities | None = None,
          **kw) -> EngineResult:
    """One-shot convenience mirroring ``engine.check`` / ``refbfs.check``."""
    return _cached_engine(config, caps or Capacities()).check(**kw)
