"""Host-paged BFS engine — HBM ring + native host store (SURVEY §2.8).

The device-resident engine (device_engine.py) keeps every discovered state in
HBM: at ~240 B/state plus the <2 GiB single-buffer limit, that caps a run at
~8M states — far below the bounded full-``Next`` spaces (the 3-server/2-value
model exceeds that by level 18).  This engine removes the ceiling the way TLC
does with its disk-backed ``states/`` queue (reference ``.gitignore:2``):

- **Only the active BFS window lives in HBM** — a ring of the current level
  (being expanded) and the next (being appended).  A state's ring row is its
  discovery index mod ``ring``; level-synchronous BFS guarantees the live
  window ``[lvl_start, n_states)`` is contiguous, so ring reuse is safe while
  the window fits (checked loudly: FAIL_RING).
- **Every new state pages out to the C++ host store** (utils/native.py)
  after each watchdog segment, with its (parent, lane) trace links, via a
  single fixed-shape gather (a mid-run XLA compile against a busy device
  wedged the rounds 2-5 worker; inherited, not re-measured on this
  machine).  Host RAM (then disk) is the capacity bound, not HBM.
- **Only the fingerprint table scales with the full space** on device:
  8 B/slot at load ≤ 0.5 → ~16 B/state, an order of magnitude less than
  storing states.  ~64M states fit in ~1 GiB of table.
- Violation traces reconstruct entirely host-side: ``store_trace_chain``
  walks the native link log; the device is never consulted.

Shares the fingerprint table protocol, failure bitmask, segment/watchdog
machinery and Carry layout with device_engine.py; discovery order — and
therefore counts, levels, coverage, and first-violation — is byte-identical
to the oracle's, which the parity tests assert with rings small enough to
wrap many times per run.
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.config import CheckConfig
from raft_tla_tpu.device_engine import (
    _EMPTY, _dedup_insert, BUCKET, Carry, FAIL_INDEX,
    FAIL_LEVEL, FAIL_PROBE, FAIL_RING, FAIL_WIDTH, decode_fail, _carry_done,
    _acc64_add, _acc64_zero, acc64_int, aggregate_coverage,
    widen_legacy_n_trans)
from raft_tla_tpu.engine import DEADLOCK, EngineResult, Violation
from raft_tla_tpu.obs import RunTelemetry
from raft_tla_tpu.models import interp, invariants as inv_mod, spec as S
from raft_tla_tpu.ops import bitpack
from raft_tla_tpu.ops import fingerprint as fpr
from raft_tla_tpu.ops import kernels
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops import symmetry as sym_mod
from raft_tla_tpu.utils import ckpt
from raft_tla_tpu.utils import native
from raft_tla_tpu.utils import pacing

I32 = jnp.int32
U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class PagedCapacities:
    """Static shapes of one compiled paged search.

    ``ring`` bounds the *live window* (current + next BFS level), not the
    total space; ``table`` bounds total distinct states at ~2 slots/state.
    """

    ring: int = 1 << 20          # HBM rows for the active window
    table: int = 1 << 24         # fingerprint slots (power of two)
    levels: int = 1 << 10

    def __post_init__(self):
        if self.ring & (self.ring - 1) or self.table & (self.table - 1):
            raise ValueError("ring and table must be powers of two")


def _build_segment(config: CheckConfig, caps: PagedCapacities, A: int,
                   W: int, schema: bitpack.BitSchema):
    """Ring variant of device_engine._build_segment (same Carry, same loop
    structure; store/parent/lane/conflag are rings indexed by discovery
    index mod ``ring``).  Ring rows are bit-packed (ops/bitpack.py) —
    ~4-8x more frontier per HBM byte; rows unpack only for the chunk
    being expanded."""
    B = config.chunk
    n_inv = len(config.invariants)
    step = kernels.build_step(config.bounds, config.spec,
                              tuple(config.invariants), config.symmetry,
                              view=config.view)
    Rcap, Lcap = caps.ring, caps.levels
    rmask = Rcap - 1
    BIG = jnp.int32(np.iinfo(np.int32).max)
    IDX_CEIL = jnp.int32(np.iinfo(np.int32).max - 2 * B * A)

    def chunk_body(carry: Carry) -> Carry:
        (store, parent, lane, conflag, tbl_hi, tbl_lo, n_states,
         lvl_start, lvl_end, viol_g, viol_i, n_trans, cov, fail,
         levels, lvl, c) = carry
        start = lvl_start + c * B
        rows_g = start + jnp.arange(B, dtype=I32)
        row_act = rows_g < lvl_end
        ridx = rows_g & rmask
        vecs = schema.unpack(store[ridx], jnp)
        out = step(vecs)
        valid = out["valid"] & row_act[:, None] & conflag[ridx][:, None]
        n_trans = _acc64_add(n_trans, jnp.sum(valid.astype(I32)))
        fail = fail | jnp.any(valid & out["overflow"]) * FAIL_WIDTH

        fhi = out["fp_hi"].reshape(-1)
        flo = out["fp_lo"].reshape(-1)
        fvalid = valid.reshape(-1)
        tbl_hi, tbl_lo, is_new, pfail = _dedup_insert(
            tbl_hi, tbl_lo, fhi, flo, fvalid)
        fail = fail | jnp.any(pfail) * FAIL_PROBE

        # Append new states into the ring at (discovery index mod Rcap).
        pos = n_states + jnp.cumsum(is_new.astype(I32)) - 1
        n_new = jnp.sum(is_new.astype(I32))
        # Live window must fit the ring: appending past lvl_start + Rcap
        # would overwrite the frontier still being expanded.
        fail = fail | (n_states + n_new - lvl_start > Rcap) * FAIL_RING
        # The paged engine is host-RAM-bounded, so (unlike the HBM-bounded
        # engines) its int32 discovery index could genuinely reach 2^31 —
        # fail loudly with a chunk's worth of headroom left.
        fail = fail | (n_states > IDX_CEIL) * FAIL_INDEX
        ok = is_new & (pos - lvl_start < Rcap)
        sl = jnp.where(ok, pos & rmask, Rcap)
        svecs = schema.pack(out["svecs"].reshape(B * A, W), jnp)
        store = store.at[sl].set(svecs, mode="drop")
        flat_b = jnp.arange(B * A, dtype=I32) // A
        flat_a = jnp.arange(B * A, dtype=I32) % A
        parent = parent.at[sl].set(start + flat_b, mode="drop")
        lane = lane.at[sl].set(flat_a, mode="drop")
        conflag = conflag.at[sl].set(out["con_ok"].reshape(-1), mode="drop")
        cov = cov.at[jnp.where(is_new, flat_a, A)].add(1, mode="drop")
        n_states = n_states + n_new

        inv_bad = is_new & jnp.any(
            ~out["inv_ok"].reshape(B * A, n_inv), axis=-1) if n_inv \
            else jnp.zeros((B * A,), bool)
        first = jnp.min(jnp.where(inv_bad, jnp.arange(B * A, dtype=I32), BIG))
        bad_inv = jnp.argmax(
            ~out["inv_ok"].reshape(B * A, n_inv)
            [jnp.minimum(first, B * A - 1)]) if n_inv else jnp.int32(0)
        g_target = pos[jnp.minimum(first, B * A - 1)]
        if config.check_deadlock:
            # TLC's default deadlock check (see device_engine.chunk_body).
            dead = row_act & conflag[ridx] & ~jnp.any(out["valid"], axis=1)
            drow = jnp.min(jnp.where(dead, jnp.arange(B, dtype=I32), BIG))
            dpos = jnp.where(drow < BIG // A, drow * A, BIG)
            use_dead = dpos < first
            first = jnp.minimum(first, dpos)
            g_target = jnp.where(use_dead,
                                 start + jnp.minimum(drow, B - 1), g_target)
            bad_inv = jnp.where(use_dead, jnp.int32(n_inv), bad_inv)
        has_viol = first < BIG
        new_viol = has_viol & (viol_g < 0)
        viol_g = jnp.where(new_viol, g_target, viol_g)
        viol_i = jnp.where(new_viol, bad_inv, viol_i)
        return Carry(store, parent, lane, conflag, tbl_hi, tbl_lo, n_states,
                     lvl_start, lvl_end, viol_g, viol_i, n_trans, cov, fail,
                     levels, lvl, c + 1)

    def outer_body(sc):
        steps, carry = sc
        n_chunks = (carry.lvl_end - carry.lvl_start + B - 1) // B

        def ccond(cc):
            s, inner = cc
            return ((inner.c < n_chunks) & (inner.viol_g < 0) &
                    (inner.fail == 0) & (s < budget) &
                    (inner.n_states < pause))    # host must page out first

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

    def segment(carry, budget_, pause_at):
        # ``pause_at``: also return control once n_states crosses this mark,
        # so the host can page out before the ring laps itself.
        nonlocal budget, pause
        budget, pause = budget_, pause_at
        steps, carry = jax.lax.while_loop(
            lambda sc: outer_cond(sc) & (sc[1].n_states < pause),
            lambda sc: outer_body(sc), (jnp.int32(0), carry))
        # Executed chunk count: paged segments routinely end mid-budget
        # (the pause_at pageout yield), so the host's per-chunk cost
        # estimate must divide by THIS, not the requested budget —
        # otherwise the watchdog clamp projects oversized segments.
        return carry, _carry_done(carry), steps

    budget = pause = None
    return segment


def _build_init(caps: PagedCapacities, A: int, P: int):
    Rcap, Lcap, Tcap = caps.ring, caps.levels, caps.table
    TB = Tcap // BUCKET

    def init(init_vec_packed, init_key_hi, init_key_lo, init_con):
        store = jnp.zeros((Rcap, P), I32).at[0].set(init_vec_packed)
        parent = jnp.full((Rcap,), -1, I32)
        lane = jnp.full((Rcap,), -1, I32)
        conflag = jnp.zeros((Rcap,), bool).at[0].set(init_con)
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


class PagedEngine:
    """Exhaustive checker bounded by host RAM, not HBM."""

    SEG_TARGET_S = 8.0
    SEG_CLAMP_S = 25.0       # see DeviceEngine: watchdog-overshoot guard
    SEG_MIN, SEG_MAX = 16, 1 << 16

    def __init__(self, config: CheckConfig, caps: PagedCapacities | None =
                 None, seg_chunks: int = 64):
        self.config = config
        self.bounds = config.bounds
        self.lay = st.Layout.of(self.bounds)
        self.table = S.action_table(self.bounds, config.spec)
        self.A = len(self.table)
        self.caps = caps or PagedCapacities()
        # One chunk appends up to chunk*A rows past the pause mark (the
        # pause check runs between chunks); ring//2 headroom must absorb it
        # so unpaged rows are never overwritten.
        if self.caps.ring < 2 * config.chunk * self.A:
            raise ValueError(
                f"PagedCapacities.ring={self.caps.ring} must be >= "
                f"2 * chunk * A = {2 * config.chunk * self.A}")
        self.seg_chunks = seg_chunks
        self.schema = bitpack.BitSchema(self.bounds)
        self._init = jax.jit(_build_init(self.caps, self.A, self.schema.P))
        self._segment = jax.jit(
            _build_segment(config, self.caps, self.A, self.lay.width,
                           self.schema),
            donate_argnums=(0,))
        self._gather = jax.jit(
            lambda carry, ridx: (carry.store[ridx], carry.parent[ridx],
                                 carry.lane[ridx]))

    # Fixed pageout gather width: ONE compiled gather shape for the whole
    # run.  A size ladder would trigger a fresh XLA compile the first time
    # a segment's new-state count crossed each bucket — and on the rounds
    # 2-5 machine a mid-run compile against a busy device wedged the
    # worker (observed repeatedly ~13 min into large runs; inherited, not
    # re-measured on this machine).  Padding waste
    # is bounded at PAGE_ROWS rows (~2 MB packed) per segment.
    PAGE_ROWS = 1 << 16

    def _pageout(self, carry, host, paged: int, n_states: int) -> int:
        """Copy rows [paged, n_states) from the device ring to the host
        store, PAGE_ROWS at a time."""
        iota = np.arange(self.PAGE_ROWS, dtype=np.int32)
        while paged < n_states:
            n = min(n_states - paged, self.PAGE_ROWS)
            gidx = np.minimum(paged + iota, n_states - 1)   # pad w/ last row
            ridx = jnp.asarray(gidx & (self.caps.ring - 1))
            rows, par, lan = jax.device_get(self._gather(carry, ridx))
            host.append(rows[:n])
            host.append_links(par[:n], lan[:n])
            paged += n
        return paged

    # -- checkpoint / resume --------------------------------------------
    # A paged checkpoint is the device carry plus the host store's row and
    # link logs; resume is bit-exact (the search is a pure function of
    # both).  Needed in anger: a chip can be preempted mid-run (the
    # worker dies silently, the client hangs), so long exhaustive runs
    # are driven as checkpoint → rerun → resume.

    def save_checkpoint(self, path: str, carry: Carry, host, paged: int,
                        init_key: tuple) -> None:
        """Snapshot carry + host store.  The store's row/link logs stream
        to ``path + ".rows"``/``".links"`` in bounded blocks (never a
        second full copy in RAM); the metadata npz with the ``paged``
        counter is written LAST, so a crash between files leaves an older
        counter next to longer streams — safe, because the store is
        append-only and prefixes are stable (utils/ckpt.py)."""
        ckpt.stream_rows_out(path + ".rows", host.read, paged,
                             self.schema.P)

        def links_reader(start, n):
            par, lan = host.read_links(start, n)
            return np.stack([par, lan], axis=1)

        ckpt.stream_rows_out(path + ".links", links_reader, paged, 2)
        arrs = jax.device_get(carry)
        ckpt.atomic_savez(
            path,
            **{f"c{i}": np.asarray(x) for i, x in enumerate(arrs)},
            paged=np.int64(paged),
            config_digest=np.uint64(
                ckpt.config_digest(self.config, self.caps, init_key)))

    def load_checkpoint(self, path: str, init_key: tuple):
        """Returns ``(carry, host, paged)`` restored from ``path``."""
        with ckpt.load_npz_checked(
                path, ckpt.config_digest(self.config, self.caps,
                                         init_key)) as z:
            arrs = [z[f"c{i}"] for i in range(len(Carry._fields))]
            carry = Carry(*(jnp.asarray(a) for a in
                            widen_legacy_n_trans(arrs, Carry._fields)))
            paged = int(z["paged"])
        host = native.make_store(self.schema.P)
        ckpt.stream_rows_in(path + ".rows", host.append, paged,
                            expect_width=self.schema.P)
        ckpt.stream_rows_in(
            path + ".links",
            lambda blk: host.append_links(blk[:, 0], blk[:, 1]), paged,
            expect_width=2)
        return carry, host, paged

    def check(self, init_override: interp.PyState | None = None,
              on_progress=None, checkpoint: str | None = None,
              checkpoint_every_s: float = 300.0,
              resume: str | None = None,
              deadline_s: float | None = None,
              events: str | None = None) -> EngineResult:
        """``on_progress``/``events`` as in DeviceEngine.check: the shared
        per-segment ProgressRecord + run-event log (SURVEY §5).
        ``checkpoint``/``resume`` as in DeviceEngine, additionally
        snapshotting the host store.

        ``deadline_s`` time-boxes the search: segments stop once that many
        seconds have passed AFTER the first (compile-carrying) segment, and
        the result comes back with ``complete=False`` and the counts found
        so far — the bench's north-star-shaped throughput probe."""
        t0 = time.monotonic()
        tel = RunTelemetry(
            "paged", config=self.config, caps=self.caps,
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

        if resume:
            carry, host, paged = self.load_checkpoint(resume, (hi0, lo0))
        else:
            host = native.make_store(self.schema.P)
            init_packed = self.schema.pack(init_vec.astype(np.int32), np)
            carry = self._init(
                jnp.asarray(init_packed, I32), jnp.uint32(hi0),
                jnp.uint32(lo0),
                jnp.bool_(interp.constraint_ok(init_py, bounds)))
            paged = 0
        pacer = pacing.SegmentPacer(self.seg_chunks, self.SEG_MIN,
                                    self.SEG_MAX, self.SEG_TARGET_S,
                                    self.SEG_CLAMP_S)
        budget = pacer.budget
        complete = True
        t_warm = None
        last_ckpt = time.monotonic()
        while True:
            if (deadline_s is not None and t_warm is not None
                    and time.monotonic() - t_warm > deadline_s):
                complete = False
                tel.stop_requested("deadline")
                break
            # Pause the device loop before unpaged rows could be overwritten:
            # rows < pause_at are safe while n_states - lvl_start <= ring.
            pause_at = paged + self.caps.ring // 2
            t_seg = time.monotonic()
            with tel.phases.phase("expand") as ph:
                carry, done, steps_d = self._segment(carry, jnp.int32(budget),
                                                     jnp.int32(pause_at))
                n_states = int(carry.n_states)
            with tel.phases.phase("export"):
                paged = self._pageout(carry, host, paged, n_states)
            if tel.active:
                lvl, n_trans, cov = jax.device_get(
                    (carry.lvl, carry.n_trans, carry.cov))
                tel.segment(
                    n_states=n_states, level=int(lvl),
                    n_transitions=acc64_int(n_trans),
                    coverage=dict(aggregate_coverage(self.table, cov)))
            if bool(done):
                break
            dt = time.monotonic() - t_seg
            # dt includes the pageout above — attributing it to chunk cost
            # overestimates, which is the safe direction for the watchdog.
            executed = max(1, int(steps_d))
            if checkpoint and (time.monotonic() - last_ckpt
                               >= checkpoint_every_s):
                with tel.phases.phase("snapshot"):
                    self.save_checkpoint(checkpoint, carry, host, paged,
                                         (hi0, lo0))
                tel.checkpoint(checkpoint, n_states)
                last_ckpt = time.monotonic()
            if t_warm is None:
                t_warm = time.monotonic()   # deadline starts post-compile
            budget = pacer.update(dt, executed)
            self.seg_chunks = budget

        (viol_g, viol_i, n_trans, fail, n_levels, levels_dev,
         cov_arr) = jax.device_get((
             carry.viol_g, carry.viol_i, carry.n_trans, carry.fail,
             carry.lvl, carry.levels, carry.cov))
        viol_g, fail = int(viol_g), int(fail)
        if fail:
            raise RuntimeError(
                f"paged search aborted: {decode_fail(fail)} "
                f"(caps={self.caps}) — grow PagedCapacities and rerun")
        levels_arr = [1] + [int(x) for x in levels_dev[:int(n_levels)]
                            if int(x) > 0]
        coverage: Counter = Counter()
        for a, inst in enumerate(self.table):
            if cov_arr[a]:
                coverage[inst.family] += int(cov_arr[a])

        violation = None
        if viol_g >= 0:
            chain_idx = host.trace_chain(viol_g)
            chain = []
            for k, g in enumerate(chain_idx):
                row = self.schema.unpack(host.read(int(g), 1)[0], np)
                _, lane_g = host.read_links(int(g), 1)
                py = interp.from_struct(st.unpack(row, self.lay, np),
                                        self.bounds)
                label = self.table[int(lane_g[0])].label() if k > 0 else None
                chain.append((label, py))
            violation = Violation(
                invariant=DEADLOCK
                if int(viol_i) == len(self.config.invariants)
                else self.config.invariants[int(viol_i)],
                state=chain[-1][1], trace=chain)
        host.close()

        result = EngineResult(
            n_states=n_states, diameter=len(levels_arr) - 1,
            n_transitions=acc64_int(n_trans), coverage=coverage,
            violation=violation, levels=levels_arr,
            wall_s=time.monotonic() - t0, complete=complete)
        tel.run_end(result)
        return result


def check(config: CheckConfig, caps: PagedCapacities | None = None,
          **kw) -> EngineResult:
    return PagedEngine(config, caps).check(**kw)
