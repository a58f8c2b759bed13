"""Host-streamed frontier engine — no live-window ceiling (paged v2).

The host-paged engine (paged_engine.py) must hold the live BFS window
(current + next level) in an HBM ring.  The deployment target's 2 GiB
single-buffer limit caps that ring at 2^25 bit-packed rows — and the
5-server election space's level pairs outgrow ANY legal ring from level
~24 (measured: FAIL_RING at 53.8M orbits, runs/elect5v2.stats).  This
engine removes the ceiling by inverting the data flow:

- **The frontier streams host→device in fixed blocks.**  Every discovered
  state already lives in the host store (utils/native.py); each block of
  the current level is uploaded into a device frontier buffer, expanded in
  watchdog-safe segments, and replaced by the next block.  HBM never holds
  more than one block of frontier.
- **The ring only buffers appends** between pageouts.  New states append
  at (discovery index mod ring) and page out to the host store when the
  ring is half full — the loud-guard invariant is simply
  ``n_states - paged <= ring``, independent of level widths.
- **Level bookkeeping moves to the host** (it knows every level boundary:
  the discovery index at each advance).  The device segment is simpler
  than the paged engine's: expand chunks of the block, dedup, append.
- Only the fingerprint table still scales with the full space on device
  (~8 B/slot; the 2 GiB buffer limit caps it at 2^28 slots ≈ 134M states
  at load 0.5 — the next capacity frontier, which FAIL_PROBE guards
  loudly).

Streaming cost: one host→device upload of each level (bit-packed rows, so
~44 B/state at 5 servers) — measured single-digit seconds per 10M-row
level on the rounds 2-5 host link (inherited, not re-measured on this
machine), amortized over minutes of expansion.

Discovery order — and therefore counts, levels, coverage attribution and
first-violation — is byte-identical to the oracle and the other
single-chip engines (the parity tests assert it with blocks and rings
small enough to cycle many times per run).  Checkpoint/resume as in the
paged engine: host-store streams + device carry snapshot, digest-guarded.
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections import Counter
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.config import CheckConfig
from raft_tla_tpu.device_engine import (
    _EMPTY, _dedup_insert, BUCKET, FAIL_INDEX, FAIL_LEVEL, FAIL_PROBE,
    FAIL_RING, FAIL_WIDTH, aggregate_coverage, decode_fail, _acc64_add,
    _acc64_zero, acc64_int)
from raft_tla_tpu.engine import DEADLOCK, EngineResult, Violation
from raft_tla_tpu.models import interp, invariants as inv_mod, spec as S
from raft_tla_tpu.obs import RunTelemetry
from raft_tla_tpu.ops import bitpack
from raft_tla_tpu.ops import kernels
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops import symmetry as sym_mod
from raft_tla_tpu.utils import ckpt
from raft_tla_tpu.utils import native
from raft_tla_tpu.utils import pacing

I32 = jnp.int32
U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class StreamedCapacities:
    """Static shapes.  ``block`` is the frontier upload granularity;
    ``ring`` buffers appends between pageouts (both independent of level
    widths); ``table`` bounds total distinct states at ~2 slots/state."""

    block: int = 1 << 20
    ring: int = 1 << 22
    table: int = 1 << 26
    levels: int = 1 << 12        # host-side level-count bound (bookkeeping)

    def __post_init__(self):
        for nm in ("block", "ring", "table"):
            v = getattr(self, nm)
            if v & (v - 1):
                raise ValueError(f"{nm}={v} must be a power of two")


class SCarry(NamedTuple):
    """Device carry between segments (the frontier block is an argument,
    not a carry member — the host replaces it per block)."""

    store: jax.Array     # [Rcap, P] append ring, bit-packed
    parent: jax.Array    # [Rcap] parent discovery index
    lane: jax.Array      # [Rcap]
    conflag: jax.Array   # [Rcap]
    tbl_hi: jax.Array    # [TB, BUCKET]
    tbl_lo: jax.Array    # [TB, BUCKET]
    n_states: jax.Array  # discovery count
    viol_g: jax.Array    # discovery index of first violation, -1
    viol_i: jax.Array
    n_trans: jax.Array   # [2] uint32 limbs
    cov: jax.Array       # [A]
    fail: jax.Array
    c: jax.Array         # chunk cursor within the current block


def _build_segment(config: CheckConfig, caps: StreamedCapacities, A: int,
                   W: int, schema: bitpack.BitSchema):
    B = config.chunk
    n_inv = len(config.invariants)
    # The prescan ladder resolves at build time
    # (kernels._prescan_enabled) — the segment must be rebuilt to change it.
    step = kernels.build_step(config.bounds, config.spec,
                              tuple(config.invariants), config.symmetry,
                              view=config.view)
    Rcap = caps.ring
    rmask = Rcap - 1
    BIG = jnp.int32(np.iinfo(np.int32).max)
    IDX_CEIL = jnp.int32(np.iinfo(np.int32).max - 2 * B * A)

    def chunk_body(carry: SCarry) -> SCarry:
        (store, parent, lane, conflag, tbl_hi, tbl_lo, n_states,
         viol_g, viol_i, n_trans, cov, fail, c) = carry
        # rows of the CURRENT BLOCK (fbuf/fcon are segment closures)
        r0 = c * B
        rows_b = r0 + jnp.arange(B, dtype=I32)       # block-local
        row_act = rows_b < block_rows
        bidx = jnp.minimum(rows_b, caps.block - 1)
        vecs = schema.unpack(fbuf[bidx], jnp)
        out = step(vecs)
        valid = out["valid"] & row_act[:, None] & fcon[bidx][:, None]
        n_trans = _acc64_add(n_trans, jnp.sum(valid.astype(I32)))
        fail = fail | jnp.any(valid & out["overflow"]) * FAIL_WIDTH

        fhi = out["fp_hi"].reshape(-1)
        flo = out["fp_lo"].reshape(-1)
        fvalid = valid.reshape(-1)
        tbl_hi, tbl_lo, is_new, pfail = _dedup_insert(
            tbl_hi, tbl_lo, fhi, flo, fvalid)
        fail = fail | jnp.any(pfail) * FAIL_PROBE

        pos = n_states + jnp.cumsum(is_new.astype(I32)) - 1
        n_new = jnp.sum(is_new.astype(I32))
        # appends must not lap rows not yet paged to the host — the ONLY
        # ring invariant in this engine (no level-window term)
        fail = fail | (n_states + n_new - paged_wm > Rcap) * FAIL_RING
        fail = fail | (n_states > IDX_CEIL) * FAIL_INDEX
        ok = is_new & (pos - paged_wm < Rcap)
        sl = jnp.where(ok, pos & rmask, Rcap)
        svecs = schema.pack(out["svecs"].reshape(B * A, W), jnp)
        store = store.at[sl].set(svecs, mode="drop")
        flat_b = jnp.arange(B * A, dtype=I32) // A
        flat_a = jnp.arange(B * A, dtype=I32) % A
        parent = parent.at[sl].set(block_start + r0 + flat_b, mode="drop")
        lane = lane.at[sl].set(flat_a, mode="drop")
        conflag = conflag.at[sl].set(out["con_ok"].reshape(-1), mode="drop")
        cov = cov.at[jnp.where(is_new, flat_a, A)].add(1, mode="drop")
        n_states = n_states + n_new

        inv_bad = is_new & jnp.any(
            ~out["inv_ok"].reshape(B * A, n_inv), axis=-1) if n_inv \
            else jnp.zeros((B * A,), bool)
        first = jnp.min(jnp.where(inv_bad, jnp.arange(B * A, dtype=I32),
                                  BIG))
        bad_inv = jnp.argmax(
            ~out["inv_ok"].reshape(B * A, n_inv)
            [jnp.minimum(first, B * A - 1)]) if n_inv else jnp.int32(0)
        g_target = pos[jnp.minimum(first, B * A - 1)]
        if config.check_deadlock:
            dead = row_act & fcon[bidx] & ~jnp.any(out["valid"], axis=1)
            drow = jnp.min(jnp.where(dead, jnp.arange(B, dtype=I32), BIG))
            dpos = jnp.where(drow < BIG // A, drow * A, BIG)
            use_dead = dpos < first
            first = jnp.minimum(first, dpos)
            g_target = jnp.where(
                use_dead, block_start + r0 + jnp.minimum(drow, B - 1),
                g_target)
            bad_inv = jnp.where(use_dead, jnp.int32(n_inv), bad_inv)
        has_viol = first < BIG
        new_viol = has_viol & (viol_g < 0)
        viol_g = jnp.where(new_viol, g_target, viol_g)
        viol_i = jnp.where(new_viol, bad_inv, viol_i)
        return SCarry(store, parent, lane, conflag, tbl_hi, tbl_lo,
                      n_states, viol_g, viol_i, n_trans, cov, fail, c + 1)

    def cond(sc):
        s, carry = sc
        n_chunks = (block_rows + B - 1) // B
        return ((carry.c < n_chunks) & (carry.viol_g < 0)
                & (carry.fail == 0) & (s < budget)
                & (carry.n_states < pause))

    def body(sc):
        s, carry = sc
        return s + 1, chunk_body(carry)

    def segment(carry, fbuf_, fcon_, budget_, paged_, block_start_,
                block_rows_):
        nonlocal fbuf, fcon, budget, pause, paged_wm, block_start, \
            block_rows
        fbuf, fcon = fbuf_, fcon_
        budget = budget_
        paged_wm = paged_
        pause = paged_ + Rcap // 2
        block_start, block_rows = block_start_, block_rows_
        steps, carry = jax.lax.while_loop(cond, body,
                                          (jnp.int32(0), carry))
        n_chunks = (block_rows + B - 1) // B
        return steps, carry.c >= n_chunks, carry

    fbuf = fcon = budget = pause = block_start = block_rows = None
    paged_wm = None
    return segment


class StreamedEngine:
    """Exhaustive checker with no live-window ceiling (host-RAM-bounded
    frontier AND store; only the fingerprint table scales on device)."""

    SEG_TARGET_S = 8.0
    SEG_CLAMP_S = 25.0
    SEG_MIN, SEG_MAX = 16, 1 << 16
    PAGE_ROWS = 1 << 16

    def __init__(self, config: CheckConfig,
                 caps: StreamedCapacities | None = None,
                 seg_chunks: int = 64):
        self.config = config
        self.bounds = config.bounds
        self.lay = st.Layout.of(self.bounds)
        self.table = S.action_table(self.bounds, config.spec)
        self.A = len(self.table)
        self.caps = caps or StreamedCapacities()
        if self.caps.ring < 2 * config.chunk * self.A:
            raise ValueError(
                f"ring={self.caps.ring} must be >= 2 * chunk * A = "
                f"{2 * config.chunk * self.A} (pageout headroom)")
        if self.caps.block < config.chunk:
            raise ValueError("block must be >= chunk")
        self.seg_chunks = seg_chunks
        self.schema = bitpack.BitSchema(self.bounds)
        self._segment = jax.jit(
            _build_segment(config, self.caps, self.A, self.lay.width,
                           self.schema),
            donate_argnums=(0,))
        self._gather = jax.jit(
            lambda carry, ridx: (carry.store[ridx], carry.parent[ridx],
                                 carry.lane[ridx], carry.conflag[ridx]))

    def _init_carry(self, hi0, lo0) -> SCarry:
        Rcap, TB = self.caps.ring, self.caps.table // BUCKET
        b0 = int(np.uint32(lo0) & np.uint32(TB - 1))
        tbl_hi = np.full((TB, BUCKET), _EMPTY, np.uint32)
        tbl_lo = np.full((TB, BUCKET), _EMPTY, np.uint32)
        tbl_hi[b0, 0] = hi0
        tbl_lo[b0, 0] = lo0
        return SCarry(
            store=jnp.zeros((Rcap, self.schema.P), I32),
            parent=jnp.full((Rcap,), -1, I32),
            lane=jnp.full((Rcap,), -1, I32),
            conflag=jnp.zeros((Rcap,), bool),
            tbl_hi=jnp.asarray(tbl_hi), tbl_lo=jnp.asarray(tbl_lo),
            n_states=jnp.int32(1), viol_g=jnp.int32(-1),
            viol_i=jnp.int32(0), n_trans=_acc64_zero(),
            cov=jnp.zeros((self.A,), I32), fail=jnp.int32(0),
            c=jnp.int32(0))

    def _pageout(self, carry, host, constore, paged: int,
                 n_states: int) -> int:
        """``constore`` is a width-1 host store of CONSTRAINT flags — the
        frontier re-upload needs them (expansion gates on conflag)."""
        rmask = self.caps.ring - 1
        iota = np.arange(self.PAGE_ROWS, dtype=np.int32)
        while paged < n_states:
            n = min(n_states - paged, self.PAGE_ROWS)
            gidx = np.minimum(paged + iota, n_states - 1)
            ridx = jnp.asarray(gidx & rmask)
            rows, par, lan, con = jax.device_get(
                self._gather(carry, ridx))
            host.append(rows[:n])
            host.append_links(par[:n], lan[:n])
            constore.append(con[:n].astype(np.int32)[:, None])
            paged += n
        return paged

    # -- checkpoint / resume --------------------------------------------

    def save_checkpoint(self, path: str, carry: SCarry, host, constore,
                        paged: int, level_ends: list, blocks_done: int,
                        init_key) -> None:
        """Snapshots are taken at BLOCK boundaries only (the host loop's
        invariant): re-expansion on resume would double-count transition/
        coverage counters, so the resume point must be exactly a completed
        block.  ``blocks_done`` = completed blocks of the frontier level.

        Streams extend INCREMENTALLY (ckpt.stream_rows_append): the host
        stores are append-only, so each snapshot writes only the suffix
        since the previous one — full rewrites cost ~10 idle-device
        minutes each at 10^8-orbit scale."""
        ckpt.stream_rows_append(path + ".rows", host.read, paged,
                                self.schema.P)

        def links_reader(start, n):
            par, lan = host.read_links(start, n)
            return np.stack([par, lan], axis=1)

        ckpt.stream_rows_append(path + ".links", links_reader, paged, 2)
        ckpt.stream_rows_append(path + ".con", constore.read, paged, 1)
        arrs = jax.device_get(carry)
        ckpt.atomic_savez(
            path,
            **{f"c{i}": np.asarray(x) for i, x in enumerate(arrs)},
            paged=np.int64(paged),
            level_ends=np.asarray(level_ends, np.int64),
            blocks_done=np.int64(blocks_done),
            config_digest=np.uint64(
                ckpt.config_digest(self.config, self.caps, init_key)))

    def load_checkpoint(self, path: str, init_key):
        with ckpt.load_npz_checked(
                path, ckpt.config_digest(self.config, self.caps,
                                         init_key)) as z:
            carry = SCarry(*(jnp.asarray(z[f"c{i}"])
                             for i in range(len(SCarry._fields))))
            paged = int(z["paged"])
            level_ends = [int(x) for x in z["level_ends"]]
            blocks_done = int(z["blocks_done"])
        host = native.make_store(self.schema.P)
        constore = native.make_store(1)
        ckpt.stream_rows_in(path + ".rows", host.append, paged,
                            expect_width=self.schema.P)
        ckpt.stream_rows_in(
            path + ".links",
            lambda blk: host.append_links(blk[:, 0], blk[:, 1]), paged,
            expect_width=2)
        ckpt.stream_rows_in(path + ".con", constore.append, paged,
                            expect_width=1)
        return carry, host, constore, paged, level_ends, blocks_done

    # -- main loop ------------------------------------------------------

    def check(self, init_override: interp.PyState | None = None,
              on_progress=None, checkpoint: str | None = None,
              checkpoint_every_s: float = 600.0,
              resume: str | None = None,
              deadline_s: float | None = None,
              events: str | None = None) -> EngineResult:
        t0 = time.monotonic()
        tel = RunTelemetry(
            "streamed", config=self.config, caps=self.caps,
            on_progress=on_progress, events=events,
            resumed=resume is not None,
            n0=1 if resume is None else None, t0=t0)
        try:
            return self._check_impl(tel, t0, init_override, checkpoint,
                                    checkpoint_every_s, resume, deadline_s)
        finally:
            tel.close()

    def _check_impl(self, tel, t0, init_override, checkpoint,
                    checkpoint_every_s, resume, deadline_s) -> EngineResult:
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

        B = self.config.chunk
        # Incremental snapshots (save_checkpoint) extend the checkpoint
        # path's stream files in place, trusting their existing prefix.
        # That trust is only valid for rows THIS run verified:
        # - fresh run: any streams at the checkpoint path are leftovers
        #   of some other run — delete them (a digest on the npz alone
        #   would not protect them);
        # - resume from the same path: rows beyond the npz's ``paged``
        #   were written by a later, superseded snapshot of a previous
        #   process — trim to ``paged`` so they are re-written from this
        #   run's own store, not assumed bit-identical.
        if checkpoint:
            if resume and os.path.abspath(resume) == \
                    os.path.abspath(checkpoint):
                pass                      # trimmed after load, below
            else:
                for suf in (".rows", ".links", ".con"):
                    try:
                        os.remove(checkpoint + suf)
                    except FileNotFoundError:
                        pass
        if resume:
            (carry, host, constore, paged, level_ends,
             blocks_done) = self.load_checkpoint(resume, (hi0, lo0))
            if checkpoint and os.path.abspath(resume) == \
                    os.path.abspath(checkpoint):
                ckpt.trim_stream(checkpoint + ".rows", paged,
                                 self.schema.P)
                ckpt.trim_stream(checkpoint + ".links", paged, 2)
                ckpt.trim_stream(checkpoint + ".con", paged, 1)
        else:
            carry = self._init_carry(np.uint32(hi0), np.uint32(lo0))
            host = native.make_store(self.schema.P)
            constore = native.make_store(1)
            init_packed = self.schema.pack(
                np.asarray(init_vec, np.int32), np)
            host.append(init_packed[None, :])
            host.append_links(np.asarray([-1], np.int32),
                              np.asarray([-1], np.int32))
            constore.append(np.asarray(
                [[interp.constraint_ok(init_py, bounds)]], np.int32))
            paged = 1
            # level_ends[k] = discovery index just past level k
            level_ends = [1]
            blocks_done = 0              # completed blocks, frontier level

        pacer = pacing.SegmentPacer(self.seg_chunks, self.SEG_MIN,
                                    self.SEG_MAX, self.SEG_TARGET_S,
                                    self.SEG_CLAMP_S)
        budget = pacer.budget
        complete = True
        t_warm = None
        last_ckpt = time.monotonic()
        Fcap = self.caps.block
        stopped = False

        while not stopped:
            lvl_lo = level_ends[-2] if len(level_ends) > 1 else 0
            lvl_hi = level_ends[-1]
            for b_start in range(lvl_lo + blocks_done * Fcap, lvl_hi,
                                 Fcap):
                b_rows = min(Fcap, lvl_hi - b_start)
                with tel.phases.phase("upload") as ph:
                    blk = host.read(b_start, b_rows)
                    con = constore.read(b_start, b_rows)[:, 0].astype(bool)
                    if b_rows < Fcap:
                        blk = np.concatenate([blk, np.zeros(
                            (Fcap - b_rows, self.schema.P), np.int32)])
                        con = np.concatenate(
                            [con, np.zeros((Fcap - b_rows,), bool)])
                    fbuf, fcon = ph.sync((jnp.asarray(blk),
                                          jnp.asarray(con)))
                carry = carry._replace(c=jnp.int32(0))
                block_done = False
                while not block_done:
                    if (deadline_s is not None and t_warm is not None
                            and time.monotonic() - t_warm > deadline_s):
                        complete = False
                        stopped = True
                        tel.stop_requested("deadline")
                        break
                    t_seg = time.monotonic()
                    with tel.phases.phase("expand"):
                        steps_d, done_d, carry = self._segment(
                            carry, fbuf, fcon, jnp.int32(budget),
                            jnp.int32(paged), jnp.int32(b_start),
                            jnp.int32(b_rows))
                        n_states, fail_v, viol_v = map(int, jax.device_get(
                            (carry.n_states, carry.fail, carry.viol_g)))
                    with tel.phases.phase("export"):
                        paged = self._pageout(carry, host, constore, paged,
                                              n_states)
                    if tel.active:
                        n_trans, cov = jax.device_get(
                            (carry.n_trans, carry.cov))
                        tel.segment(
                            n_states=n_states, level=len(level_ends),
                            n_transitions=acc64_int(n_trans),
                            coverage=dict(aggregate_coverage(
                                self.table, cov)))
                    if fail_v or viol_v >= 0:
                        stopped = True
                        break
                    dt = time.monotonic() - t_seg
                    if t_warm is None:
                        t_warm = time.monotonic()
                    budget = pacer.update(dt, max(1, int(steps_d)))
                    self.seg_chunks = budget
                    block_done = bool(done_d)
                if stopped:
                    break
                blocks_done += 1
                # snapshots land exactly at block boundaries (see
                # save_checkpoint: resume must never re-expand rows)
                if checkpoint and (time.monotonic() - last_ckpt
                                   >= checkpoint_every_s):
                    with tel.phases.phase("snapshot"):
                        self.save_checkpoint(checkpoint, carry, host,
                                             constore, paged, level_ends,
                                             blocks_done, (hi0, lo0))
                    tel.checkpoint(checkpoint)
                    last_ckpt = time.monotonic()
            if stopped:
                break
            blocks_done = 0
            n_now = int(carry.n_states)
            if n_now == level_ends[-1]:          # no new states: done
                break
            level_ends.append(n_now)
            if len(level_ends) > self.caps.levels:
                # host-side condition, same loud-fail contract/wording as
                # the device-side FAIL_* path
                raise RuntimeError(
                    f"streamed search aborted: {decode_fail(FAIL_LEVEL)} "
                    f"(caps={self.caps}) — grow StreamedCapacities and "
                    "rerun")

        (viol_g, viol_i, n_trans, fail, cov_arr) = jax.device_get((
            carry.viol_g, carry.viol_i, carry.n_trans, carry.fail,
            carry.cov))
        viol_g, fail = int(viol_g), int(fail)
        if fail:
            raise RuntimeError(
                f"streamed search aborted: {decode_fail(fail)} "
                f"(caps={self.caps}) — grow StreamedCapacities and rerun")
        n_states = int(carry.n_states)
        levels_arr = [level_ends[0]] + [
            level_ends[k] - level_ends[k - 1]
            for k in range(1, len(level_ends))]
        coverage = aggregate_coverage(self.table, cov_arr)

        violation = None
        if viol_g >= 0:
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
            violation = Violation(
                invariant=DEADLOCK
                if int(viol_i) == len(self.config.invariants)
                else self.config.invariants[int(viol_i)],
                state=chain[-1][1], trace=chain)
        host.close()
        constore.close()

        result = EngineResult(
            n_states=n_states, diameter=len(levels_arr) - 1,
            n_transitions=acc64_int(n_trans), coverage=coverage,
            violation=violation, levels=levels_arr,
            wall_s=time.monotonic() - t0, complete=complete)
        tel.run_end(result)
        return result


def check(config: CheckConfig, caps: StreamedCapacities | None = None,
          **kw) -> EngineResult:
    return StreamedEngine(config, caps).check(**kw)
