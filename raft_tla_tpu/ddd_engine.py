"""Delayed-duplicate-detection engine — exact dedup on the host.

The ``device`` and ``shard`` engines keep the EXACT fingerprint set in
HBM, which caps distinct-state capacity at ~2^28 slots (2 GiB buffer limit;
the elect5 campaign measured probing degrade as load crossed 0.48 near
130M orbits — RESULTS.md "capacity findings").  This engine removes the
device table from the correctness path entirely, the external-memory
regime TLC itself uses for its `states/` fingerprint set
(`/root/reference/.gitignore:2`):

- **Device: expand + fingerprint only.**  The per-chunk program expands a
  slice of the frontier block, fingerprints the candidates, and pushes a
  *compacted* candidate stream (key, packed row, parent, lane, constraint
  flag) to the host.  The only device state is a **lossy filter table**:
  a bucketized fingerprint cache probed in one gather, inserting with
  overwrite-on-full-bucket instead of FAIL_PROBE.  A filter hit proves
  the key was already streamed (inserts happen only for streamed
  candidates), so hits are dropped on device — that filters the heavy
  recent-duplicate traffic cheaply.  Misses (true new states + evicted
  re-sights) stream to the host.  The filter affects traffic volume only,
  never the verdict: resume even starts it EMPTY.
- **Host: exact dedup in first-occurrence stream order.**  Candidates
  buffer in a pending list; each flush sorts them, keeps each key's first
  occurrence, anti-joins against the sorted master key array
  (`utils/keyset.MasterKeys`), appends the genuinely-new states to the
  native store in stream order, and merges their keys into the master.
  Because the table engines also admit each state at its first occurrence
  in stream order, discovery order — counts, levels, per-action coverage,
  traces — is byte-identical to the oracle and every other engine (the
  parity suite asserts it, including under forced filter eviction).
- **Level-synchronous BFS** keeps counts exact: new states join the next
  level only (the frontier streams host→device one block at a time, so
  no level has to fit on the device).

Capacity: master keys 8 B/state + packed rows in host RAM (~10^9 states
on this host), no device table in the correctness path — the designed
fix for the elect5 2^28 ceiling (RESULTS.md, runs/northstar_sizing.md).

Violation semantics match refbfs exactly: the candidate stream is
truncated ON DEVICE at the first violating candidate (kept inclusively)
or the first deadlocked row (its successors excluded), so `n_states` and
`n_transitions` stop where the oracle's do.  A violating candidate is
always genuinely new — a previously-seen state with a failing invariant
would have stopped the run at ITS first occurrence — so after a forced
flush the violator is the last appended state (asserted by key).

Checkpoints are fully incremental: rows/links/constraints are appended to
``.rows`` / ``.links`` / ``.con`` streams (``ckpt.stream_rows_append``),
and the master keys are checkpointed as their
*discovery-order append log* (a width-2 int32 native store) — sorted
back into the master on resume.  Snapshots land at block boundaries with
an empty pending buffer, so resume never re-expands or double-counts.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.config import CheckConfig
from raft_tla_tpu.device_engine import (
    _EMPTY, BUCKET, FAIL_INDEX, FAIL_LEVEL, FAIL_ROUTE, FAIL_WIDTH,
    aggregate_coverage, decode_fail)
from raft_tla_tpu.engine import DEADLOCK, EngineResult, Violation
from raft_tla_tpu.frontend import resolve_model
from raft_tla_tpu.obs import RunTelemetry, compiles
from raft_tla_tpu.ops import bitpack
from raft_tla_tpu.ops import devdedup
from raft_tla_tpu.ops import kernels
from raft_tla_tpu.utils import ckpt
from raft_tla_tpu.utils import flushq
from raft_tla_tpu.utils import keyset
from raft_tla_tpu.utils import native
from raft_tla_tpu.utils import pacing
from raft_tla_tpu.utils import prefetch

I32 = jnp.int32
U32 = jnp.uint32

# Discovery-index ceiling.  Round 4 widened the whole id path to int64
# (C++ store links, checkpoint streams, host flush; the DEVICE emits
# block-relative parents that always fit int32 and the host rebases
# them), so the old ~2.13e9 int32 ceiling — which the elect5 campaign
# was measured to hit mid-level-31/32 (VERDICT r3 missing #2) — is
# gone.  The guard remains as a loud absurdity check far past any
# host-RAM-feasible state count.
_IDX_CEIL = 1 << 62

# When a pending stream is worth handing to the flush worker below
# ``DDDCapacities.flush`` (the harvest loop of ``_check_impl``): while the
# level has at least a third as many chunk steps left to run as streamed the
# batch.  Measured on the chip (PERF.md section 6, PR 44): the worker merges
# a batch in up to three quarters of the time the device took to stream it,
# and a key costs 1.5-1.8x more in such a batch than in the one merge of the
# level close, so a hand-over pays once about a third of the batch's own
# device time still lies ahead; with less (a level whose first segment fills
# the buffer and whose second runs a few steps) the close would wait for the
# worker longer than it would have merged.
_HANDOVER_STEPS = 3


def install_sigint_boundary_stop(eng, stack, boundary="segment") -> None:
    """The runs/campaign_stop.sh contract, shared by the DDD engine
    family: the FIRST SIGINT sets ``eng._sigint``, a flag the engine's
    harvest loop reads next to the deadline check, so the engine stops
    at the next *boundary* (segment for ddd, window for ddd-shard) —
    pending candidates flushed, a snapshot saved when a --checkpoint
    path is configured, and a normal ``complete=False`` EngineResult
    returned (the campaign wrapper then prints its endpoint JSON).
    A SECOND SIGINT restores the previous handler and aborts raw
    (KeyboardInterrupt), for when the graceful path is itself wedged
    behind a dead dispatch.  signal.signal is main-thread-only; off the
    main thread the flag stays False and Ctrl-C keeps its raw meaning.
    The previous handler is restored via ``stack`` on every exit."""
    import signal
    import sys
    import threading
    eng._sigint = False
    if threading.current_thread() is not threading.main_thread():
        return
    prev = signal.getsignal(signal.SIGINT)

    def handler(_signum, _frame):
        if eng._sigint:
            signal.signal(signal.SIGINT, prev)
            raise KeyboardInterrupt
        eng._sigint = True
        print(f"SIGINT: stopping at the next {boundary} boundary "
              "(SIGINT again aborts raw)", file=sys.stderr, flush=True)

    signal.signal(signal.SIGINT, handler)
    stack.callback(signal.signal, signal.SIGINT, prev)


@dataclasses.dataclass(frozen=True)
class DDDCapacities:
    """Static shapes.  ``block``: frontier upload granularity; ``table``:
    lossy filter slots (traffic optimization only — NOT a state-count
    ceiling; keep it SMALL: XLA copies the whole table every chunk
    inside the segment while_loop — gather+scatter on one carry defeats
    its in-place pass — so the filter costs ~45 ns per BYTE of table
    per chunk.  Chip-measured (runs/filter_inengine.out): 2^22 slots
    filter within 0.6% of 2^26's traffic at 9% of the per-chunk cost;
    2^26 was costing 46% of the whole step); ``seg_rows``: device output-buffer rows per segment (a
    segment runs many chunks inside one dispatch and stops early when the
    next chunk might not fit — a dispatch round trip was measured at
    ~100-300 ms on the rounds 2-5 host link (inherited, not re-measured
    on this machine), so per-chunk dispatch is ~10x slower);
    ``flush``: the most candidates that may be pending for the host
    dedup — at it the harvest loop hands over (or, with the flush worker
    off, merges inline) whatever the worker is doing, waiting for its
    previous flush; below it the worker is handed the pending stream at
    a harvest that has enough device work of its level behind it
    (``_HANDOVER_STEPS``) and finds the worker free, and the level close
    merges the rest (``_check_impl``);
    ``levels``:
    host-side BFS-depth bound; ``route_rows``: >0 switches the chunk
    program to the EP-routed step (kernels.build_step_routed) with that
    many compacted candidate slots per chunk — discovery order is
    engine-identical (the parity suite asserts it), so like ``table``/
    ``seg_rows``/``flush`` it is checkpoint-compatible tuning, not
    digest identity; a chunk with more enabled lanes than slots aborts
    loudly (FAIL_ROUTE)."""

    block: int = 1 << 20
    table: int = 1 << 22
    seg_rows: int = 1 << 19
    flush: int = 1 << 23
    levels: int = 1 << 12
    route_rows: int = 0
    # "full": every state row + trace link retained (traces, liveness
    # exports, reshard).  "frontier": TLC's own campaign regime — RAM
    # holds the master keys only, rows live in disk-backed current+next
    # level files (utils/native.LevelStore), NO trace links (a
    # violation reports the violating state, not a path — TLC -noTrace
    # equivalence).  Lifts both the host-RAM (~76 B/state) and the
    # checkpoint-disk (~68 B/state) ceilings to ~16 B/state, the
    # difference between a ~1.5e9 and a ~7e9 state capacity on this
    # host.  Retention is NOT checkpoint identity (the npz records the
    # format; a full-format snapshot migrates on first frontier resume).
    retention: str = "full"
    # Frontier mode only: retain ALL level files instead of deleting
    # pre-frontier ones — TLC's own disk regime (its states/ dir keeps
    # every level), which restores FULL counterexample traces via
    # backward re-search (frontier_backtrace) at ~rows-stream disk cost
    # (~P*4 B/state).  Checkpoint-compatible tuning, not digest
    # identity: flipping it mid-campaign only changes which files are
    # garbage-collected.
    keep_levels: bool = False

    def __post_init__(self):
        if self.retention not in ("full", "frontier"):
            raise ValueError(f"retention={self.retention!r}")
        for nm in ("block", "table"):
            v = getattr(self, nm)
            if v & (v - 1):
                raise ValueError(f"{nm}={v} must be a power of two")
        if self.table < BUCKET:
            raise ValueError(
                f"table={self.table} must be >= one bucket ({BUCKET})")
        if self.route_rows < 0:
            raise ValueError(f"route_rows={self.route_rows} must be >= 0")


@dataclasses.dataclass(frozen=True)
class _DigestCaps:
    """Checkpoint-identity view of DDDCapacities: only fields that change
    what a snapshot MEANS join the digest.  ``block`` denominates
    ``blocks_done``; ``levels`` bounds the search.  ``table`` (lossy
    filter), ``seg_rows`` and ``flush`` provably cannot affect discovery
    order or any checkpointed byte, so tuning them mid-campaign must not
    orphan a multi-hour snapshot.  Defaults mirror DDDCapacities so
    default-valued fields keep dropping out of the digest (_stable).
    Introducing this class rotated the digest once (the class NAME joins
    the _stable tuple); no snapshot predating it existed outside tests."""

    block: int = 1 << 20
    levels: int = 1 << 12


class FilterCarry(NamedTuple):
    """The only serial device state between segments: the lossy filter
    and the chunk cursor.  Everything else is per-segment output, which
    is what makes the two-deep segment pipeline possible — segment k+1
    depends on k only through this carry, so it can be dispatched before
    k's outputs are harvested."""

    tbl_hi: jax.Array     # [TB, BUCKET] lossy filter (donated through)
    tbl_lo: jax.Array
    c: jax.Array          # chunk cursor within the current block


class SegBufs(NamedTuple):
    """One segment's candidate-stream output buffers (donated; the
    engine ping-pongs two sets so one can transfer/flush on the host
    while the device fills the other)."""

    okey_hi: jax.Array    # [OCAP]
    okey_lo: jax.Array
    orows: jax.Array      # [OCAP, P] bit-packed successor rows
    opar: jax.Array       # [OCAP] parent id, BLOCK-RELATIVE (int32-
                          # safe at any depth; harvest adds block start)
    olane: jax.Array      # [OCAP] action lane
    ocon: jax.Array       # [OCAP] constraint flag


class SegStats(NamedTuple):
    cursor: jax.Array     # streamed rows this segment (output fill)
    n_valid: jax.Array    # transitions counted (truncated at violation)
    fail: jax.Array       # FAIL_WIDTH / FAIL_ROUTE bits
    viol_kind: jax.Array  # 0 none / 1 invariant / 2 deadlock
    viol_inv: jax.Array   # invariant index (kind 1)
    dead_g: jax.Array     # kind 2: dead state's discovery index
    steps: jax.Array      # chunks executed (pacer signal)
    done: jax.Array       # block exhausted
    peak: jax.Array       # max live enabled lanes in any chunk — the
                          # route_rows sizing signal (both step shapes)
    stream_peak: jax.Array   # most rows one chunk streamed — the
                             # _S_OUT sizing signal
    stream_slabs: jax.Array  # slab writes; == steps unless a chunk
                             # streamed more than one slab holds
    probe_tiles: jax.Array   # tiles of the filter probe; a step takes
                             # ceil(its live lanes / _T_PROBE)


class _SegCarry(NamedTuple):
    """Internal while_loop carry (FilterCarry + SegBufs + SegStats
    scalars)."""

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
    viol_kind: jax.Array
    viol_inv: jax.Array
    dead_g: jax.Array
    c: jax.Array
    peak: jax.Array
    stream_peak: jax.Array
    stream_slabs: jax.Array
    probe_tiles: jax.Array


def save_ddd_snapshot(path, host, constore, keystore, n_states, n_trans,
                      cov, level_ends, blocks_done, P, digest) -> None:
    """ONE definition site for the DDD four-stream snapshot format
    (.rows/.links/.con/.keys + metadata npz) — the single-chip and
    mesh-sharded DDD engines interoperate on it byte-for-byte
    (parallel/ddd_shard_engine.reshard_ddd_checkpoint migrates campaigns
    between them), so the writer must not fork."""
    ckpt.stream_rows_append(path + ".rows", host.read, n_states, P)

    def links_reader(start, n):
        # int64 parents as (lo, hi) int32 words + lane: width-3 rows.
        # (The pre-round-4 format was width-2 int32 (parent, lane);
        # load_ddd_snapshot dual-reads it, and stream_rows_append's
        # width check turns the first post-widening snapshot of an old
        # campaign into one full .links rewrite — the migration.)
        par, lan = host.read_links(start, n)
        pu = par.astype(np.int64).view(np.uint64)
        return np.stack(
            [(pu & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32),
             (pu >> np.uint64(32)).astype(np.uint32).view(np.int32),
             lan.astype(np.int32)], axis=1)

    ckpt.stream_rows_append(path + ".links", links_reader, n_states, 3)
    ckpt.stream_rows_append(path + ".con", constore.read, n_states, 1)
    ckpt.stream_rows_append(path + ".keys", keystore.read, n_states, 2)
    ckpt.atomic_savez(
        path,
        n_states=np.int64(n_states),
        n_trans=np.uint64(n_trans),
        cov=np.asarray(cov, np.int64),
        level_ends=np.asarray(level_ends, np.int64),
        blocks_done=np.int64(blocks_done),
        config_digest=np.uint64(digest))


def load_ddd_snapshot(path, P, digest):
    """Counterpart reader: rebuilds the native stores from the streams
    (master keys are engine-specific and rebuilt by the caller)."""
    with ckpt.load_npz_checked(path, digest) as z:
        n_states = int(z["n_states"])
        n_trans = int(z["n_trans"])
        cov = np.asarray(z["cov"], np.int64).copy()
        level_ends = [int(x) for x in z["level_ends"]]
        blocks_done = int(z["blocks_done"])
    host = native.make_store(P)
    constore = native.make_store(1)
    keystore = native.make_store(2)
    ckpt.stream_rows_in(path + ".rows", host.append, n_states,
                        expect_width=P)

    def links_in_w3(blk):
        par = (blk[:, 0].view(np.uint32).astype(np.uint64)
               | (blk[:, 1].view(np.uint32).astype(np.uint64)
                  << np.uint64(32))).view(np.int64)
        host.append_links(par, blk[:, 2])

    if ckpt.stream_width(path + ".links") == 2:
        # pre-round-4 snapshot: int32 (parent, lane) — widen on read
        ckpt.stream_rows_in(
            path + ".links",
            lambda blk: host.append_links(blk[:, 0].astype(np.int64),
                                          blk[:, 1]),
            n_states, expect_width=2)
    else:
        ckpt.stream_rows_in(path + ".links", links_in_w3, n_states,
                            expect_width=3)
    ckpt.stream_rows_in(path + ".con", constore.append, n_states,
                        expect_width=1)
    ckpt.stream_rows_in(path + ".keys", keystore.append, n_states,
                        expect_width=2)
    return (host, constore, keystore, n_states, n_trans, cov, level_ends,
            blocks_done)


def save_frontier_snapshot(path, rows_ls, con_ls, keystore, n_states,
                           n_trans, cov, level_ends, blocks_done,
                           digest, keep_levels: bool = False) -> None:
    """Frontier-retention snapshots: the level files and the keys
    stream ARE the store, so a snapshot is three syncs + the metadata
    npz + post-commit cleanup of pre-frontier level files (skipped
    under ``keep_levels``: retained levels feed frontier_backtrace) —
    no stream copying at any state count."""
    rows_ls.sync()
    con_ls.sync()
    keystore.sync()
    ckpt.atomic_savez(
        path,
        n_states=np.int64(n_states),
        n_trans=np.uint64(n_trans),
        cov=np.asarray(cov, np.int64),
        level_ends=np.asarray(level_ends, np.int64),
        blocks_done=np.int64(blocks_done),
        retention=np.bytes_(b"frontier"),
        config_digest=np.uint64(digest))
    if not keep_levels:
        rows_ls.delete_old()
        con_ls.delete_old()


def load_frontier_snapshot(path, P, digest):
    """Open a frontier-format snapshot IN PLACE (no copying); also
    migrates a full-format snapshot (no ``retention`` field in the
    npz): the retained level window is sliced out of the old .rows/.con
    streams into level files, the keys stream is renamed (formats
    coincide), and the old full streams are REMOVED — a 983M-state
    campaign checkpoint shrinks by the dead-prefix ~56 B/state."""
    with ckpt.load_npz_checked(path, digest) as z:
        n_states = int(z["n_states"])
        n_trans = int(z["n_trans"])
        cov = np.asarray(z["cov"], np.int64).copy()
        level_ends = [int(x) for x in z["level_ends"]]
        blocks_done = int(z["blocks_done"])
        is_frontier = "retention" in z.files
    L = len(level_ends)
    lvl_lo = level_ends[-2] if L > 1 else 0
    lvl_hi = level_ends[-1]
    if not is_frontier:
        _migrate_full_to_frontier(path, P, n_states, n_trans, cov,
                                  level_ends, blocks_done, lvl_lo,
                                  lvl_hi, L, digest)
    else:
        # idempotent leftover cleanup: a crash between the migration's
        # npz commit and its stream deletions leaves full streams behind
        for suf in (".rows", ".links", ".con"):
            try:
                os.remove(path + suf)
            except FileNotFoundError:
                pass
    rows_ls = native.LevelStore(path + ".rows", P, L, lvl_lo, lvl_hi)
    con_ls = native.LevelStore(path + ".con", 1, L, lvl_lo, lvl_hi)
    keystore = native.FileStore(path + ".keys", 2, 0)
    if len(keystore) < n_states:
        raise ValueError(
            f"key stream holds {len(keystore)} rows, metadata expects "
            f"{n_states} — torn snapshot")
    # a crash between keystore.sync() and the npz commit leaves the key
    # stream LONGER than the metadata: truncate, or post-resume appends
    # land past a stale gap and every key row misaligns from its state
    keystore.trim(n_states)
    rows_ls.trim_next(n_states)
    con_ls.trim_next(n_states)
    if len(rows_ls.cur) != lvl_hi or len(rows_ls) != n_states:
        raise ValueError(
            f"frontier level files hold [{rows_ls.cur.base}, "
            f"{len(rows_ls.cur)}) + [{rows_ls.nxt.base}, {len(rows_ls)}),"
            f" metadata expects [{lvl_lo}, {lvl_hi}) + {n_states} — "
            "torn snapshot")
    return (rows_ls, con_ls, keystore, n_states, n_trans, cov,
            level_ends, blocks_done)


def _mmap_rows(path: str, width: int):
    """Read-only view of a committed FileStore stream.  Never opens the
    file writable (FileStore's own open truncates to the header count,
    which must not happen to a retained level file)."""
    hdr = np.fromfile(path, np.int64, 2)
    if hdr.shape[0] != 2 or int(hdr[1]) != width:
        raise ValueError(f"{path}: not a width-{width} row stream")
    n = int(hdr[0])
    if n == 0:
        return np.zeros((0, width), np.int32)
    return np.memmap(path, np.int32, mode="r", offset=16,
                     shape=(n, width))


def frontier_backtrace(config, schema, bounds, table, prefix,
                       level_ends, n_states, viol_g, keystore):
    """TLC-equivalent counterexample reconstruction in frontier mode.

    TLC's external-memory regime still produces full error traces: its
    ``states/`` directory retains every BFS level and a violation
    triggers a backward predecessor search over them.  Same algorithm
    here (VERDICT r4 missing #3): re-expand level file L(t-1) through
    the SAME fused step the forward search ran — fingerprints match
    bit-exactly, symmetry/view included — scanning for any predecessor
    of the current target key; repeat down to Init.  BFS level
    minimality makes any such chain a shortest counterexample, exactly
    like the trace links the full-retention mode stores.

    Requires the retained level files of ``DDDCapacities.keep_levels``
    (default off: a campaign-scale rows stream can exceed the disk);
    returns ``[(action_label, py_state), ...]`` from Init to the
    violator, or ``None`` when any needed level file is absent.
    """
    import bisect
    P = schema.P
    K = len(level_ends)

    def file_of(g):     # level file L{i} index holding global row g
        return bisect.bisect_right(level_ends, g) + 1

    def span_of(i):     # global [start, end) of level file L{i}
        lo = level_ends[i - 2] if i >= 2 else 0
        hi = level_ends[i - 1] if i - 1 < K else n_states
        return lo, hi

    tf = file_of(int(viol_g))
    if not all(os.path.exists(f"{prefix}.rowsL{i}")
               and os.path.exists(f"{prefix}.conL{i}")
               for i in range(1, tf + 1)):
        return None

    A = len(table)
    B = config.chunk
    model = resolve_model(config.spec)
    step = model.build_step(dataclasses.replace(config, invariants=()))

    @jax.jit
    def match(fbuf, fcon, nrows, tgt_hi, tgt_lo):
        vecs = schema.unpack(fbuf, jnp)
        out = step(vecs)
        act = (jnp.arange(B, dtype=I32) < nrows) & fcon
        hit = (out["valid"] & act[:, None]
               & (out["fp_hi"] == tgt_hi) & (out["fp_lo"] == tgt_lo))
        flat = hit.reshape(-1)
        return jnp.any(flat), jnp.argmax(flat)

    def unpack_state(fi, g):
        lo, _ = span_of(fi)
        rows = _mmap_rows(f"{prefix}.rowsL{fi}", P)
        row = schema.unpack(np.asarray(rows[g - lo]), np)
        return model.from_vec(row, bounds)

    rev = []                      # [(label_into_state, py)] backwards
    tgt_g = int(viol_g)
    while True:
        fi = file_of(tgt_g)
        py = unpack_state(fi, tgt_g)
        if fi == 1:
            rev.append((None, py))
            break
        kw = keystore.read(tgt_g, 1).view(np.uint32)
        tgt_lo, tgt_hi = np.uint32(kw[0, 0]), np.uint32(kw[0, 1])
        plo, phi = span_of(fi - 1)
        rows = _mmap_rows(f"{prefix}.rowsL{fi - 1}", P)
        cons = _mmap_rows(f"{prefix}.conL{fi - 1}", 1)
        hitg = None
        for b in range(plo, phi, B):
            n = min(B, phi - b)
            blk = np.asarray(rows[b - plo:b - plo + n])
            con = np.asarray(cons[b - plo:b - plo + n])[:, 0] != 0
            if n < B:
                blk = np.concatenate(
                    [blk, np.zeros((B - n, P), np.int32)])
                con = np.concatenate([con, np.zeros(B - n, bool)])
            found, idx = match(jnp.asarray(blk), jnp.asarray(con),
                               jnp.int32(n), jnp.uint32(tgt_hi),
                               jnp.uint32(tgt_lo))
            if bool(found):
                idx = int(idx)
                hitg = b + idx // A
                rev.append((table[idx % A].label(), py))
                break
        if hitg is None:
            raise RuntimeError(
                f"frontier backtrace: no predecessor of state {tgt_g} "
                f"in level file L{fi - 1} — level-file corruption or a "
                "kernel/dedup soundness bug")
        tgt_g = hitg
    rev.reverse()
    return rev


def _migrate_full_to_frontier(path, P, n_states, n_trans, cov,
                              level_ends, blocks_done, lvl_lo, lvl_hi,
                              L, digest):
    """One-way, one-time: slice the retained window out of a
    full-format snapshot's streams into level files, verify the copies,
    COMMIT a frontier-format metadata npz, and only then delete the
    full .rows/.links/.con (the keys stream is format-identical and
    stays).  Every crash window re-runs safely: before the npz commit
    the old npz + full streams are intact (level files rewrite from
    scratch); after it, the loader takes the frontier path and removes
    stream leftovers idempotently.

    ``.links`` is deleted FIRST: the frontier format never reads it,
    and at campaign scale that frees the gigabytes the level-file
    slices are about to need (the 983M-orbit checkpoint then migrates
    within ~15 GB of transient headroom instead of ~22).  A crash after
    that point only forecloses resuming this snapshot in FULL retention
    (which the caller just chose to leave); frontier re-migration is
    unaffected."""
    try:
        os.remove(path + ".links")
    except FileNotFoundError:
        pass
    for prefix, width, reader_path in ((".rows", P, path + ".rows"),
                                       (".con", 1, path + ".con")):
        with open(reader_path, "rb") as f:
            have, w = (int(x) for x in np.fromfile(f, np.int64, 2))
            if w != width or have < n_states:
                raise ValueError(
                    f"{reader_path}: width {w} rows {have}, expected "
                    f"width {width} >= {n_states} rows")

            def slice_to(dst_path, base, end):
                fs = native.FileStore(dst_path, width, base, reset=True)
                step = 1 << 20
                for s0 in range(base, end, step):
                    n = min(step, end - s0)
                    f.seek(16 + s0 * width * 4)
                    fs.append(np.fromfile(f, np.int32, n * width)
                              .reshape(n, width))
                fs.sync()
                fs.close()

            slice_to(f"{path}{prefix}L{L}", lvl_lo, lvl_hi)
            slice_to(f"{path}{prefix}L{L + 1}", lvl_hi, n_states)

            # verify BEFORE the source streams are removed below — the
            # full streams are the only copy of the campaign's history
            rng = np.random.default_rng(0)
            for dst, base, end in ((f"{path}{prefix}L{L}", lvl_lo,
                                    lvl_hi),
                                   (f"{path}{prefix}L{L + 1}", lvl_hi,
                                    n_states)):
                fs = native.FileStore(dst, width, base)
                if len(fs) != end:
                    raise RuntimeError(
                        f"migration wrote {len(fs)} != {end} rows to "
                        f"{dst} — full streams left untouched")
                for s0 in ([base, max(base, end - 7)]
                           + [int(x) for x in rng.integers(
                               base, max(end - 7, base + 1), 8)]
                           if end > base else []):
                    n = min(7, end - s0)
                    f.seek(16 + s0 * width * 4)
                    want = np.fromfile(f, np.int32, n * width) \
                        .reshape(n, width)
                    if not np.array_equal(fs.read(s0, n), want):
                        raise RuntimeError(
                            f"migration verification mismatch at row "
                            f"{s0} of {dst} — full streams left "
                            "untouched")
                fs.close()
    ckpt.atomic_savez(
        path,
        n_states=np.int64(n_states),
        n_trans=np.uint64(n_trans),
        cov=np.asarray(cov, np.int64),
        level_ends=np.asarray(level_ends, np.int64),
        blocks_done=np.int64(blocks_done),
        retention=np.bytes_(b"frontier"),
        config_digest=np.uint64(digest))
    for suf in (".rows", ".links", ".con"):
        try:
            os.remove(path + suf)
        except FileNotFoundError:
            pass


def frontier_checkpoint_setup(resume, checkpoint, checkpoint_every_s,
                              cleanup, prefix):
    """The frontier checkpoint-path contract, ONE definition for both
    DDD engines (single-chip + mesh): in-place resume mapping, tmpdir
    creation with cleanup registered on the caller's ExitStack, and the
    resume==checkpoint requirement — which must be enforced BEFORE
    load_checkpoint because the full->frontier migration rewrites the
    RESUME path's files.  Returns (checkpoint, checkpoint_every_s,
    tmpdir); ``tmpdir is not None`` is the ONLY sound gate for deleting
    level files at rotation (nothing can resume a tmpdir run)."""
    tmpdir = None
    if resume and not checkpoint:
        checkpoint = resume              # frontier resumes in place
    if not checkpoint:
        import shutil
        import tempfile
        tmpdir = tempfile.mkdtemp(prefix=prefix,
                                  dir=os.environ.get("TMPDIR", "."))
        cleanup.callback(
            lambda d=tmpdir: shutil.rmtree(d, ignore_errors=True))
        checkpoint_every_s = float("inf")
        checkpoint = os.path.join(tmpdir, "run")
    if resume and os.path.abspath(resume) != os.path.abspath(checkpoint):
        raise ValueError(
            "frontier mode resumes in place: --checkpoint must equal "
            "--resume (the level files are the store)")
    return checkpoint, checkpoint_every_s, tmpdir


# Per-call compacted-insert budget: only streamed keys reach the table
# scatter (typically a few thousand of the N=chunk*A candidates — 3.7k
# at flagship shapes, round 4), and a chunk streaming more than this
# simply drops the excess INSERTS — the key still streams to the host,
# so exactness is untouched and the only cost is re-sighted traffic.
# Chip-measured in round 4 (the record is `git show 51d3f6c^:RESULTS.md`
# lines 305-325; the runs/*.out files it names were never committed):
# TPU scatter cost is per-UPDATE (~80 ns) regardless of how few updates
# really write (mode="drop" masking is not free), so compacting 172k
# masked updates to 16k is the win; a combined [TB, BUCKET, 2] table
# layout that would fix this with one row scatter was measured SLOWER
# in-engine (rank-3 minor-dim-2 layout wrecks the probe gather) and
# rejected.  The budget's entries are the head of the stage's second
# sort, keys and slots its payload: nothing is gathered for them.
_S_INS = 1 << 14

# Rows one slab of the candidate stream holds (_build_segment's
# ``stream`` stage): the same lesson applied to the output buffers — a
# chunk's streamed rows are gathered in compaction order and written
# with one contiguous dynamic_update_slice per buffer, not scattered
# lane by lane.  Unlike a filter insert a streamed row may never be
# dropped, so a chunk that streams more than one slab writes further
# slabs.  Sized from SegStats.stream_peak (PERF.md, PR 25).
_S_OUT = 1 << 14

# Sorted positions one tile of the filter probe holds
# (_filter_insert_ordered): the same lesson applied to the bucket
# gathers — a gather costs per lane gathered, so the stage gathers the
# live prefix of its own sort tile by tile, the tile count taken from
# the live lanes the step observes, and not every lane.  Unlike a slab
# a tile is work for every position in it, live or not, so the size
# trades the dead part of a step's last tile against the loop's trips.
# Chip-measured (PERF.md, PR 35; the stage alone, random keys, a 2^19 x 8
# table): a position costs 17 ns, a trip nothing that shows (all 172,032
# lanes live: 42 tiles of 2^12 4.01 ms, 21 of 2^13 3.96, 11 of 2^14 4.44,
# 6 of 2^15 4.97), and a step's live lanes (SegStats.n_valid over steps)
# are 8-20 k in the benchmark's cells, so 2^13 wastes the least: at
# 344,064 lanes with 19.6 k live 1.75 ms against 2.79 at 2^14, at 73,728
# with 8.1 k live 0.92 against 1.41.  SegStats.probe_tiles over steps
# says how many tiles a step took.
_T_PROBE = 1 << 13


def _tile_plan(t: int, nk: int) -> tuple[int, int]:
    """``(T, slack)`` for ``nk`` entries cut into tiles of at most ``t``:
    the entries a tile holds, and how far past ``nk`` whole tiles
    reach."""
    t = min(t, nk)
    return t, -nk % t


def _slab_plan(nk: int) -> tuple[int, int]:
    """``(S, slack)`` for a chunk of ``nk`` candidate rows: the rows a
    slab holds, and how far past ``nk`` whole slabs reach — the rows the
    segment buffers carry beyond ``seg_rows`` so that a chunk's last
    slab always lands inside them."""
    return _tile_plan(_S_OUT, nk)


# A block's upload sends the rows it has.  The frontier block is a device
# buffer that stays resident for the whole check(); the host ships the live
# prefix in pieces of block / _UP_PIECES rows (2^15 at the default block:
# 1.0-1.7 MB at 8-13 packed words), each laid into the buffer by the one
# ``_place_piece`` program.  A block whose pieces would cover more than
# _UP_WHOLE / _UP_PIECES of the buffer goes as one whole-buffer transfer
# instead: PERF.md (section 5, PR 39) has a piece's measured cost and where
# the two cross.
_UP_PIECES = 32
_UP_WHOLE = 16


def _upload_plan(block: int, chunk: int) -> tuple[int, int]:
    """``(S, whole_above)``: the rows of one upload piece, and the most
    piece-rounded rows a block sends as pieces — past them (or where whole
    pieces would overrun the buffer) it goes as one transfer."""
    return (max(chunk, block // _UP_PIECES),
            block * _UP_WHOLE // _UP_PIECES)


def _place_piece(fbuf, fcon, rows, con, at):
    """One upload piece laid into the resident frontier block at row
    ``at``.  ``at`` + the piece's rows never pass the buffer's end
    (``_upload_plan``), so the update is never clamped."""
    return (jax.lax.dynamic_update_slice(fbuf, rows, (at, 0)),
            jax.lax.dynamic_update_slice(fcon, con, (at,)))


def _slab_trips(n, nk: int):
    """Slabs that ``_write_slabs`` writes for ``n`` observed rows of a
    step that can hold ``nk``: one for nearly every step (an empty step
    still writes one, of garbage above the cursor), more only past a
    slab's rows."""
    SLAB = _slab_plan(nk)[0]
    return jnp.maximum((n + SLAB - 1) // SLAB, 1)


def _write_slabs(bufs, cursor, n_stream, compact, nk, gather,
                 watch=None, seen=()):
    """The ``stream`` stage's writes, for a chunk of a one-chip segment and
    for a lockstep step of a mesh shard alike: compact once, write
    contiguously.  Slab j is the streamed candidates j*SLAB.. of the
    filter's compaction order (``compact[:n_stream]``,
    _filter_insert_ordered), gathered by ``gather(sel)`` — one column a
    buffer, in the buffers' order — and laid down with one
    ``dynamic_update_slice`` a buffer at ``cursor + j*SLAB``.  A slab's
    entries past the streamed count are other candidates' rows above the
    new cursor: the next step overwrites them and the harvest never reads
    past the cursor.  The buffers' slack rows (``_slab_plan(nk)``, ``nk``
    the most rows a step can stream) keep the last slab inside them, so
    no write is ever clamped.  ``watch(seen, sel, live, at)``, where
    given, folds what a caller wants to know of a slab's candidates
    (``live``: which of its entries streamed) into ``seen`` as the loop
    goes — the mesh step's first violating slot.  Returns ``(bufs,
    n_slabs, seen)``."""
    SLAB, SLACK = _slab_plan(nk)
    compact_p = jnp.pad(compact, (0, SLACK))

    def write_slab(j, carry):
        bufs, seen = carry
        sel = jax.lax.dynamic_slice(compact_p, (j * SLAB,), (SLAB,))
        slab = gather(sel)
        at = cursor + j * SLAB
        if watch is not None:
            live = j * SLAB + jnp.arange(SLAB, dtype=I32) < n_stream
            seen = watch(seen, sel, live, at)
        return tuple(
            jax.lax.dynamic_update_slice(
                b, v, (at,) + (0,) * (b.ndim - 1))
            for b, v in zip(bufs, slab)), seen

    # the trip count is the observed count
    n_slabs = _slab_trips(n_stream, nk)
    bufs, seen = jax.lax.fori_loop(0, n_slabs, write_slab,
                                   (tuple(bufs), seen))
    return bufs, n_slabs, seen


def _filter_insert_ordered(tbl_hi, tbl_lo, key_hi, key_lo, active):
    """Lossy one-gather filter probe + compacted insert, in key-sorted
    space over the live prefix of the stage's own sort.

    Returns ``(tbl_hi, tbl_lo, n_stream, compact, n_tiles)``.  A
    candidate c *streams* iff it is active, is the first active candidate
    carrying its key in this batch (same two-sort first-occurrence pass
    as device_engine._dedup_insert stage 1), and its key is NOT in the
    filter — bit-identical stream semantics to the rounds-1-3
    implementation (discovery order never depends on filter contents: a
    filter hit proves the key already streamed, so the parity argument
    is insert-policy-independent).  ``compact[:n_stream]`` are the
    streamed candidates in batch order (the entries past them are
    in-range lanes of no meaning): the insert takes its first ``_S_INS``
    entries, the segment's ``stream`` stage its slabs.  ``n_tiles`` is
    the probe's trip count (``SegStats.probe_tiles``).

    How (the work follows the live candidates, of which a dense step
    has one lane in ten, not the lanes):

    1. One ``lax.sort`` over ``(key_hi, key_lo, lane)`` — ties in lane
       order, what a stable sort on the keys gives — so the sorted keys
       are the sort's own output and nothing is gathered back by a
       permutation.  Inactive lanes sort under ``_EMPTY``, the largest
       key, so the live candidates are the first ``n_valid`` sorted
       positions, and first-of-key is a compare with the left neighbour
       there, with no scatter back to lane order.  (An active key that
       IS all-ones interleaves with the dead lanes: the lane word's low
       bit keeps who is live, and the tiles cover up to the last live
       position, so the corner probes like any other key.)
    2. The bucket rows are gathered for ``T = _T_PROBE`` sorted positions
       a tile, ``ceil(n_valid / T)`` tiles — the ``stream`` stage's slab
       loop again, the trip count what the step observes.  The tables
       are read only: every probe sees them as they stood before this
       batch's inserts.  A tile leaves one word a position: the lane and
       the write slot of a position that streams, a dead mark otherwise.
       The loop's operands are the sort's results, so no gather can be
       scheduled ahead of the sort (PR 27 needed an optimization barrier
       for that), and the compiler still copies one table into fast
       memory under the sort (read in the segment program compiled for
       a described v5e: a ``ConcatBitcast`` of four ``slice-start``s).
    3. A second sort, on that word with the keys as payload, brings the
       streamed positions to the head in batch order: the compaction
       order, the insert's keys and its slots in one op.

    What it costs (one v5e chip, the flagship's 172,032 lanes, 9 % of
    them live, 2.4 tiles a step; PERF.md, PR 35): the stage 0.86 ms a
    step in the segment program, where probing every lane in lane order
    took 9.93 — sort 1 0.24 ms, sort 2 0.22, a tile's two bucket gathers
    17 ns a position (18 + 5 ns a lane over all N in lane order, 4.0
    ms), and that stage's 4.4 ms of N-lane gathers by the permutation
    and scatter back to lane order have nothing left to do.  With every lane live the
    tiles do the gathers' whole work and the stage still reads 4.0 ms
    (the stage alone): one path at any fill.

    Inserts: first empty slot, else overwrite the key-hashed slot —
    eviction, the ``_S_INS`` compaction budget, and the in-batch
    (bucket, slot) dedup below only widen the stream (the host dedups
    exactly), they never drop a state.  The hi and lo words scatter
    with IDENTICAL compacted index vectors, and those vectors are made
    DUPLICATE-FREE before the scatters: rounds 1-4 relied on XLA
    applying duplicate-index updates in operand order identically in
    both set() ops (implementation-defined — a drift could fuse a
    fabricated (hiA, loB) "chimera" key that aliases a never-streamed
    candidate and silently drops a state, VERDICT r4 weak #3).  Keeping
    only the first insert per (bucket, slot) per batch removes the
    reliance outright; the loser key simply isn't remembered and may
    re-stream later, which the host dedups.
    """
    BA = key_hi.shape[0]
    TB, Sb = tbl_hi.shape
    bmask = jnp.uint32(TB - 1)
    T, slack = _tile_plan(_T_PROBE, BA)
    # one int32 a position: (lane * Sb + write slot) of a position that
    # streams, DEAD otherwise
    DEAD = BA * Sb
    if DEAD >= 1 << 30:
        raise ValueError(
            f"{BA} candidate lanes x {Sb} bucket slots exceed 30 bits")
    iota = jnp.arange(BA, dtype=I32)

    skh = jnp.where(active, key_hi, _EMPTY)
    skl = jnp.where(active, key_lo, _EMPTY)
    # the lane id as third key keeps ties in lane order with no stable
    # sort's hidden iota operand; its low bit carries who is live
    ph, pl, tag = jax.lax.sort((skh, skl, iota * 2 + active.astype(I32)),
                               num_keys=3, is_stable=False)
    pa = (tag & 1) == 1
    same_as_prev = jnp.concatenate([
        jnp.zeros((1,), bool),
        (ph[1:] == ph[:-1]) & (pl[1:] == pl[:-1]) & pa[1:] & pa[:-1]])
    # lane * Sb of a first-of-key live position, DEAD otherwise
    base = jnp.where(pa & ~same_as_prev, (tag >> 1) * Sb, DEAD)
    n_tiles = (jnp.max(jnp.where(pa, iota + 1, 0)) + T - 1) // T
    ph_p, pl_p = jnp.pad(ph, (0, slack)), jnp.pad(pl, (0, slack))
    base_p = jnp.pad(base, (0, slack), constant_values=DEAD)

    def probe_tile(t, word):
        at = (t * T,)
        kh = jax.lax.dynamic_slice(ph_p, at, (T,))
        kl = jax.lax.dynamic_slice(pl_p, at, (T,))
        b = jax.lax.dynamic_slice(base_p, at, (T,))
        bidx = (kl & bmask).astype(I32)
        row_hi, row_lo = tbl_hi[bidx], tbl_lo[bidx]      # [T, Sb] gather
        seen = jnp.any((row_hi == kh[:, None])
                       & (row_lo == kl[:, None]), axis=1)
        slot_empty = (row_hi == _EMPTY) & (row_lo == _EMPTY)
        wslot = jnp.where(jnp.any(slot_empty, axis=1),
                          jnp.argmax(slot_empty, axis=1).astype(I32),
                          (kh % jnp.uint32(Sb)).astype(I32))
        return jax.lax.dynamic_update_slice(
            word, jnp.where((b < DEAD) & ~seen, b + wslot, DEAD), at)

    word = jax.lax.fori_loop(0, n_tiles, probe_tile,
                             jnp.full((BA + slack,), DEAD, I32))[:BA]

    # the streamed positions to the head, batch order (lanes are
    # distinct, so the order of the dead tail is nobody's business)
    word, kh, kl = jax.lax.sort((word, ph, pl), num_keys=1,
                                is_stable=False)
    n_stream = jnp.sum((word < DEAD).astype(I32))
    compact = jnp.minimum(word // Sb, BA - 1)

    # scatter only S updates instead of BA
    S = min(_S_INS, BA)
    word, kh, kl = word[:S], kh[:S], kl[:S]
    wb = jnp.where(word < DEAD, (kl & bmask).astype(I32), TB)  # TB = dropped
    ws = word % Sb
    # in-batch (bucket, slot) dedup: duplicate-free scatter indices have
    # no update-order semantics to rely on (see docstring)
    lin = wb * Sb + ws
    order = jnp.argsort(lin, stable=True)
    dup = jnp.concatenate(
        [jnp.zeros((1,), bool), lin[order][1:] == lin[order][:-1]])
    wb = jnp.where(jnp.zeros((S,), bool).at[order].set(~dup), wb, TB)
    tbl_hi = tbl_hi.at[wb, ws].set(kh, mode="drop")
    tbl_lo = tbl_lo.at[wb, ws].set(kl, mode="drop")
    return tbl_hi, tbl_lo, n_stream, compact, n_tiles


def _build_step(model, config: CheckConfig, caps: DDDCapacities):
    """The fused step a segment expands with: the spec's own dense step
    (``model.build_step``; it resolves the prescan ladder,
    kernels._prescan_enabled, at build time), or with ``route_rows`` the
    EP-routed one, which is Raft's — both share _step_stages, keys are
    bit-identical either way."""
    if not caps.route_rows:
        return model.build_step(config)
    if not model.is_raft:
        raise ValueError(
            f"route_rows={caps.route_rows}: the routed step "
            f"(kernels.build_step_routed) is Raft's; spec {config.spec!r} "
            "runs the dense step only")
    return kernels.build_step_routed(
        config.bounds, model.sub, tuple(config.invariants),
        config.symmetry, k_rows=caps.route_rows, view=config.view)


def _build_segment(config: CheckConfig, caps: DDDCapacities, A: int,
                   W: int, schema: bitpack.BitSchema, step):
    """One dispatch = up to ``budget`` chunks via ``lax.while_loop``,
    compacting every chunk's candidate stream into the segment output
    buffers at a running cursor.  The loop stops when the block is done,
    the next chunk might overflow the output buffers, a violation or
    failure is flagged, or the budget is spent.  ``bufs`` hold
    ``seg_rows`` rows plus the slack of ``_slab_plan``; rows at and
    past ``stats.cursor`` are unspecified.  ``step`` is the spec's fused
    step (_build_step), ``schema`` its packed row."""
    B = config.chunk
    N = B * A
    routed = caps.route_rows > 0
    NK = caps.route_rows if routed else N   # max streamed rows per chunk
    OCAP = caps.seg_rows
    if OCAP < NK:
        raise ValueError(
            f"seg_rows={OCAP} must be >= per-chunk candidate rows = {NK}")
    n_inv = len(config.invariants)
    BIG = jnp.int32(np.iinfo(np.int32).max)

    def chunk_body(carry: _SegCarry) -> _SegCarry:
        (tbl_hi, tbl_lo, okey_hi, okey_lo, orows, opar, olane, ocon,
         cursor, n_valid_a, fail, viol_kind, viol_inv, dead_g, c,
         peak, stream_peak, stream_slabs, probe_tiles) = carry
        r0 = c * B
        rows_b = r0 + jnp.arange(B, dtype=I32)
        row_act = rows_b < block_rows
        bidx = jnp.minimum(rows_b, caps.block - 1)
        # stage scopes (kernels.STAGE_SCOPES): metadata for the device
        # trace, no computation
        with jax.named_scope("unpack"):
            vecs = schema.unpack(fbuf[bidx], jnp)
        row_ok = row_act & fcon[bidx]
        out = step(vecs, row_ok) if routed else step(vecs)
        valid = out["valid"] & row_ok[:, None]
        fvalid = valid.reshape(-1)
        iota = jnp.arange(N, dtype=I32)

        # Normalize both step shapes to one candidate stream of NK rows
        # in flat (b*A + a) order: ``src`` = flat source lane, ``order``
        # = flat position for refbfs-exact truncation, ``cand_act`` =
        # live candidate.  Dense: the full N-lane grid.  Routed: the
        # step's compacted slots (already row_ok-masked — only live
        # rows' lanes consume routing budget).
        peak = jnp.maximum(peak, out["n_en"] if routed
                           else jnp.sum(fvalid.astype(I32)))
        if routed:
            cidx = out["cidx"]
            src = jnp.minimum(cidx, N - 1)
            cand_act = out["cvalid"]
            order = cidx
            kh, kl = out["cfp_hi"], out["cfp_lo"]
            inv_ok_rows = out["cinv_ok"]
            ovf_rows = out["overflow"].reshape(-1)[src]
            con_rows = out["ccon_ok"]
            word_rows = out["csvecs"]
            route_ovf = out["route_ovf"]
        else:
            src = iota
            cand_act = fvalid
            order = iota
            kh = out["fp_hi"].reshape(-1)
            kl = out["fp_lo"].reshape(-1)
            inv_ok_rows = out["inv_ok"].reshape(N, n_inv)
            ovf_rows = out["overflow"].reshape(-1)
            con_rows = out["con_ok"].reshape(-1)
            word_rows = out["svecs"].reshape(N, W)
            route_ovf = jnp.bool_(False)

        # refbfs-exact truncation: first invariant violation (violator
        # kept) vs first dead row (its and later rows' candidates cut),
        # ordered by flat candidate position (a dead row ``drow`` sits at
        # ``drow * A``)
        with jax.named_scope("invariants"):
            inv_bad = cand_act & jnp.any(~inv_ok_rows, axis=-1) if n_inv \
                else jnp.zeros((NK,), bool)
            first_inv = jnp.min(jnp.where(inv_bad, order, BIG))
            if config.check_deadlock:
                dead = row_act & fcon[bidx] & ~jnp.any(out["valid"], axis=1)
                drow = jnp.min(jnp.where(dead, jnp.arange(B, dtype=I32), BIG))
                dpos = jnp.where(drow < BIG // A, drow * A, BIG)
            else:
                drow = BIG
                dpos = BIG
            use_dead = dpos < first_inv
            has_inv = (first_inv < BIG) & ~use_dead
            cut_incl = jnp.where(use_dead, dpos - 1,
                                 jnp.where(first_inv < BIG, first_inv, BIG))
            keep = order <= cut_incl
            kvalid = cand_act & keep
            n_valid_a = n_valid_a + jnp.sum(kvalid.astype(I32))
            fail = fail | jnp.any(kvalid & ovf_rows).astype(I32) * FAIL_WIDTH

        with jax.named_scope("filter_insert"):
            (tbl_hi, tbl_lo, n_stream, compact,
             n_tiles) = _filter_insert_ordered(tbl_hi, tbl_lo, kh, kl, kvalid)
            probe_tiles = probe_tiles + n_tiles
        with jax.named_scope("pack"):
            svecs = schema.pack(word_rows, jnp)
        with jax.named_scope("stream"):
            def gather(sel):
                lane = src[sel] if routed else sel
                # the packed rows word by word: P lane gathers keep the
                # [N, P] rows and the buffer in their compact layouts; one
                # row gather made the TPU compiler keep both row-major,
                # 8..11 words padded to 128 lanes (PERF.md, PR 25)
                rows = jnp.stack([svecs[:, p][sel]
                                  for p in range(schema.P)], axis=1)
                # BLOCK-RELATIVE parent (always fits int32 regardless of
                # how deep the campaign is); the harvest rebases to the
                # global int64 discovery index by adding the block start
                # on the host
                return (kh[sel], kl[sel], rows, r0 + lane // A,
                        lane % A, con_rows[sel])

            (okey_hi, okey_lo, orows, opar, olane,
             ocon), n_slabs, _ = _write_slabs(
                (okey_hi, okey_lo, orows, opar, olane, ocon), cursor,
                n_stream, compact, NK, gather)
            cursor = cursor + n_stream
            stream_peak = jnp.maximum(stream_peak, n_stream)
            stream_slabs = stream_slabs + n_slabs

        viol_kind = jnp.where(use_dead, 2, jnp.where(has_inv, 1, 0)) \
            .astype(I32)
        # A detected invariant violation outranks a routing overflow:
        # compaction keeps the FIRST K enabled lanes in flat order, so
        # every dropped lane lies past the detected violator — beyond
        # the truncation cut the dense engine applies anyway — and the
        # emitted stream is already dense-exact.  A deadlock cut (or no
        # detection at all) may have lost pre-cut candidates: abort.
        fail = fail | (route_ovf & (viol_kind != 1)).astype(I32) \
            * FAIL_ROUTE
        viol_inv_c = jnp.argmax(~inv_ok_rows[
            jnp.argmin(jnp.where(inv_bad, order, BIG))]) \
            if n_inv else jnp.int32(0)
        dead_g = jnp.where(                 # block-relative, as opar
            use_dead, r0 + jnp.minimum(drow, B - 1), dead_g)
        return _SegCarry(tbl_hi, tbl_lo, okey_hi, okey_lo, orows, opar,
                         olane, ocon, cursor, n_valid_a, fail, viol_kind,
                         viol_inv_c.astype(I32), dead_g, c + 1, peak,
                         stream_peak, stream_slabs, probe_tiles)

    def cond(sc):
        s, carry = sc
        n_chunks = (block_rows + B - 1) // B
        return ((carry.c < n_chunks) & (carry.viol_kind == 0)
                & (carry.fail == 0) & (s < budget)
                & (carry.cursor + NK <= OCAP))

    def body(sc):
        s, carry = sc
        return s + 1, chunk_body(carry)

    def segment(fc, bufs, fbuf_, fcon_, budget_, block_rows_):
        nonlocal fbuf, fcon, budget, block_rows
        fbuf, fcon = fbuf_, fcon_
        budget = budget_
        block_rows = block_rows_
        carry = _SegCarry(
            fc.tbl_hi, fc.tbl_lo, *bufs,
            cursor=jnp.int32(0), n_valid=jnp.int32(0), fail=jnp.int32(0),
            viol_kind=jnp.int32(0), viol_inv=jnp.int32(0),
            dead_g=jnp.int32(-1), c=fc.c, peak=jnp.int32(0),
            stream_peak=jnp.int32(0), stream_slabs=jnp.int32(0),
            probe_tiles=jnp.int32(0))
        steps, carry = jax.lax.while_loop(cond, body,
                                          (jnp.int32(0), carry))
        n_chunks = (block_rows + B - 1) // B
        return (FilterCarry(carry.tbl_hi, carry.tbl_lo, carry.c),
                SegBufs(carry.okey_hi, carry.okey_lo, carry.orows,
                        carry.opar, carry.olane, carry.ocon),
                SegStats(carry.cursor, carry.n_valid, carry.fail,
                         carry.viol_kind, carry.viol_inv, carry.dead_g,
                         steps, carry.c >= n_chunks, carry.peak,
                         carry.stream_peak, carry.stream_slabs,
                         carry.probe_tiles))

    fbuf = fcon = budget = block_rows = None
    return segment


def _dd_filter(backend):
    """Devdedup export filter for one segment's output buffers: drop
    every lane whose key already streamed this level (ops/devdedup) and
    compact the survivors to the buffer head in stream order, so the
    harvest's existing ``[:ns]`` slices transfer and append only rows
    the master keyset would actually admit.  Jitted with dstate and
    bufs donated — runs in dispatch order, so the set's serial carry
    always reflects exactly the rows streamed before this segment."""
    filt = devdedup.make_filter(backend)

    def apply(dstate, bufs, cursor):
        dstate, _keep, idx, new_n, hits = filt(
            dstate, bufs.okey_hi, bufs.okey_lo, cursor)
        bufs = SegBufs(
            okey_hi=bufs.okey_hi[idx], okey_lo=bufs.okey_lo[idx],
            orows=bufs.orows[idx], opar=bufs.opar[idx],
            olane=bufs.olane[idx], ocon=bufs.ocon[idx])
        return dstate, bufs, new_n, hits

    return apply


class DDDEngine:
    """Exhaustive checker whose exact dedup lives on the host — distinct-
    state capacity is host RAM, with no device fingerprint table in the
    correctness path."""

    SEG_TARGET_S = 8.0
    SEG_CLAMP_S = 25.0
    SEG_MIN, SEG_MAX = 4, 1 << 16

    def __init__(self, config: CheckConfig,
                 caps: DDDCapacities | None = None,
                 seg_chunks: int = 64):
        self.config = config
        self.bounds = config.bounds
        # everything of the spec comes from its model adapter (layout,
        # action table, step, packed row, Init, row codec), as the host
        # anchor engine.Engine takes it
        self.model = resolve_model(config.spec)
        self.lay = self.model.layout(self.bounds)
        self.table = self.model.action_table(self.bounds)
        self.A = len(self.table)
        self.caps = caps or DDDCapacities()
        if self.caps.block < config.chunk:
            raise ValueError("block must be >= chunk")
        self.seg_chunks = seg_chunks
        self._digest_caps = _DigestCaps(block=self.caps.block,
                                        levels=self.caps.levels)
        self.schema = self.model.bit_schema(self.bounds)
        # faithful mode: where the ``elections`` slots' eTerm words lie in
        # the flat row, for the pass ledger's ``elections_peak``
        self._eterm0 = self.lay.offset("eTerm") \
            if getattr(self.lay, "history", False) else None
        self._epeak = 0
        # RAFT_TLA_HOSTDEDUP gate: partitioned master keys + background
        # flush worker.  Resolved once at construction (like the
        # prescan gate) and deliberately NOT part of
        # _DigestCaps — checkpoints are compatible both directions.
        self._host_dedup = keyset.host_dedup_enabled()
        # RAFT_TLA_PREFETCH gate: double-buffered background staging of
        # the next frontier block (utils/prefetch).  Same resolution
        # discipline; also NOT part of _DigestCaps — checkpoints resume
        # across either gate setting.
        self._prefetch = prefetch.prefetch_enabled()
        # RAFT_TLA_DEVDEDUP gate: device-resident exact within-level
        # fingerprint set applied to each segment's output buffers
        # before export (ops/devdedup) — drops rows the master keyset
        # would reject anyway, shrinking d2h export volume by the
        # within-level duplicate rate.  Same resolution discipline;
        # also NOT part of _DigestCaps — a resumed set starts empty and
        # merely re-streams, which the master dedups exactly.
        self._devdedup = devdedup.devdedup_backend()
        self._dd_apply = jax.jit(_dd_filter(self._devdedup),
                                 donate_argnums=(0, 1)) \
            if self._devdedup else None
        # Per-flush, per-partition merge budget: 8x the partition's
        # expected share of one flush covers the amortized LSM movement
        # (flush/parts keys in, each moved ~log2(N/flush) ~ 7 times at
        # campaign scale) while bounding any single flush's spike.
        self._merge_budget = max(1 << 16,
                                 (8 * self.caps.flush)
                                 // keyset.DEFAULT_PARTS)
        # the compile ledger listens before this engine's first program
        # is traced (idempotent; obs/compiles)
        compiles.install()
        # rows of one segment buffer: seg_rows plus the slack that keeps
        # a chunk's last slab inside it, fixed here with the program that
        # writes the slabs
        self._buf_rows = self.caps.seg_rows + _slab_plan(
            self.caps.route_rows or config.chunk * self.A)[1]
        # what the step below is built with, for the ``pass`` span: a
        # trace then names the program that ran (the routed step has no
        # ladder: it compacts the live lanes before its scan)
        self._prescan = not self.caps.route_rows and \
            kernels._prescan_enabled(config.bounds, config.symmetry)
        # |G| of the run's SYMMETRY (1 with none), for ``run_start`` and
        # the ``segment`` spans: scope time over ``images`` is time an image
        self._group = self.model.group_order(config)
        # ... and how many fields its scan still moves an image at a time
        # (None with no SYMMETRY), for the ``pass`` span
        self._scan_moved = self.model.scan_moved_fields(config)
        self._segment = jax.jit(
            _build_segment(config, self.caps, self.A, self.lay.width,
                           self.schema,
                           _build_step(self.model, config, self.caps)),
            donate_argnums=(0, 1))
        # the frontier block's upload (_upload_plan): one allocator of a
        # resident block ``(fbuf, fcon)`` and one placer, each of one
        # shape, so a level's size compiles nothing.  Nothing reads a
        # block's rows at or past a dispatch's ``block_rows``: what they
        # hold — zeros at first, an earlier block's rows later — is
        # immaterial.
        self._up_rows, self._up_whole = _upload_plan(self.caps.block,
                                                     config.chunk)
        block_shape = (self.caps.block, self.schema.P)
        self._alloc_block = jax.jit(
            lambda: (jnp.zeros(block_shape, I32),
                     jnp.zeros(block_shape[:1], bool)))
        self._place = jax.jit(_place_piece, donate_argnums=(0, 1))

    def _new_master(self):
        return keyset.new_master(self._host_dedup,
                                 merge_budget=self._merge_budget)

    def _init_filter(self) -> FilterCarry:
        TB = self.caps.table // BUCKET
        return FilterCarry(
            tbl_hi=jnp.full((TB, BUCKET), _EMPTY, U32),
            tbl_lo=jnp.full((TB, BUCKET), _EMPTY, U32),
            c=jnp.int32(0))

    def _init_devset(self):
        return jax.device_put(
            devdedup.init_set(self.caps.table, self._devdedup))

    def _make_bufs(self) -> SegBufs:
        OCAP = self._buf_rows
        return SegBufs(
            okey_hi=jnp.zeros((OCAP,), U32),
            okey_lo=jnp.zeros((OCAP,), U32),
            orows=jnp.zeros((OCAP, self.schema.P), I32),
            opar=jnp.zeros((OCAP,), I32),
            olane=jnp.zeros((OCAP,), I32),
            ocon=jnp.zeros((OCAP,), bool))

    # -- host dedup -----------------------------------------------------

    def _flush(self, pend, master, host, constore, keystore, cov) -> int:
        """Exact-dedup the pending candidate stream; append the new
        states in first-occurrence order.  Returns the number appended."""
        if not pend["keys"]:
            return 0
        keys = np.concatenate(pend["keys"])
        new_idx = master.dedup(keys)
        n_new = int(new_idx.size)
        if n_new:
            rows = np.concatenate(pend["rows"])[new_idx]
            if self._eterm0 is not None:
                # occupied slots sort first (ops/state.canonicalize): the
                # peak moves when a row fills the slot after it
                while self._epeak < self.lay.E and self.schema.unpack_word(
                        rows, self._eterm0 + self._epeak, np).any():
                    self._epeak += 1
            lane = np.concatenate(pend["lane"])[new_idx]
            con = np.concatenate(pend["con"])[new_idx]
            host.append(rows)
            if self.caps.retention == "full":
                par = np.concatenate(pend["par"])[new_idx]
                host.append_links(par, lane)
            constore.append(con.astype(np.int32)[:, None])
            nk = keys[new_idx]
            keystore.append(np.stack(
                [(nk & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                 (nk >> np.uint64(32)).astype(np.uint32)],
                axis=1).view(np.int32))
            cov += np.bincount(lane, minlength=self.A)
        for lst in pend.values():
            lst.clear()
        return n_new

    # -- checkpoint / resume --------------------------------------------

    def save_checkpoint(self, path: str, host, constore, keystore,
                        n_states: int, n_trans: int, cov, level_ends,
                        blocks_done: int, init_key) -> None:
        """Block-boundary snapshots with an empty pending buffer; every
        stream (rows/links/constraints/keys) extends incrementally."""
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

    def load_checkpoint(self, path: str, init_key):
        digest = ckpt.config_digest(self.config, self._digest_caps,
                                    init_key)
        load = load_frontier_snapshot \
            if self.caps.retention == "frontier" else load_ddd_snapshot
        (host, constore, keystore, n_states, n_trans, cov, level_ends,
         blocks_done) = load(path, self.schema.P, digest)
        kw = keystore.read(0, n_states).view(np.uint32)
        keys = keyset.pack_keys(kw[:, 1], kw[:, 0])
        # master_from_keys dedupe-checks BEFORE construction: a corrupt
        # log raises the stream-corrupt diagnostic naming the snapshot,
        # not MasterKeys's generic sortedness error; the partitioned
        # build also splits the O(N log N) resume sort across the pool
        master = keyset.master_from_keys(
            keys, source=path, partitioned=self._host_dedup,
            merge_budget=self._merge_budget)
        if len(master) != n_states:
            raise ValueError(
                f"checkpoint key log has {len(master)} distinct keys for "
                f"{n_states} states — stream corrupt")
        return (host, constore, keystore, master, n_states, n_trans, cov,
                level_ends, blocks_done)

    # -- main loop ------------------------------------------------------

    def check(self, init_override=None, on_progress=None,
              checkpoint: str | None = None,
              checkpoint_every_s: float = 600.0,
              resume: str | None = None,
              deadline_s: float | None = None,
              retain_store: bool = False,
              events: str | None = None) -> EngineResult:
        import contextlib
        with contextlib.ExitStack() as stack:
            # bound stack: tmpdir cleanup runs on EVERY exit, including
            # KeyboardInterrupt and unexpected errors (review r4)
            self._install_sigint(stack)
            return self._check_impl(
                init_override, on_progress, checkpoint,
                checkpoint_every_s, resume, deadline_s, retain_store,
                stack, events)

    def _install_sigint(self, stack) -> None:
        install_sigint_boundary_stop(self, stack, boundary="segment")

    def _check_impl(self, init_override, on_progress, checkpoint,
                    checkpoint_every_s, resume, deadline_s,
                    retain_store, _cleanup, events=None) -> EngineResult:
        t0 = time.monotonic()
        tel = RunTelemetry(
            "ddd", config=self.config, caps=self.caps,
            on_progress=on_progress, events=events,
            resumed=resume is not None, n0=1, t0=t0, level_log=True)
        _cleanup.callback(tel.close)
        # Span tree (obs/trace): pass > level > upload / expand / export >
        # {segment_wait, d2h} / level_close, the flush worker's and the
        # prefetcher's spans on their own threads, one ``segment`` per
        # harvested segment on the synthetic ``segments`` track.  With
        # tracing off nothing is emitted (``tr.enabled`` is False) and the
        # sites the pass ledger reads (obs/passlog) are timed for it alone;
        # every other site is the shared null handle.
        tr = tel.trace
        pass_sp = tr.open("pass", engine="ddd", resumed=resume is not None,
                          prescan=self._prescan)
        self._epeak = 0     # (a resumed pass counts what it admits itself)
        _cleanup.callback(pass_sp.close)     # raise paths; idempotent
        bounds, model = self.bounds, self.model
        init_py = init_override if init_override is not None \
            else model.init_py(bounds)
        init_vec = model.to_vec(init_py, bounds)
        hi0, lo0 = model.init_fingerprint(self.config, init_py, init_vec)

        for nm in self.config.invariants:
            if not model.py_invariant(nm)(init_py, bounds):
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

        B = self.config.chunk
        N = B * self.A
        frontier = self.caps.retention == "frontier"
        if frontier and retain_store:
            raise ValueError(
                "retain_store (liveness graph export) needs retention="
                "'full' — frontier mode drops pre-frontier rows")
        tmpdir = None
        if frontier:
            # shared contract with DDDShardEngine (ADVICE r4: the two
            # inline copies had started to drift)
            checkpoint, checkpoint_every_s, tmpdir = \
                frontier_checkpoint_setup(resume, checkpoint,
                                          checkpoint_every_s, _cleanup,
                                          prefix="ddd_frontier_")
        # fresh run: any stream files at the checkpoint path belong to
        # some other run — remove before incremental appends trust them
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
            (host, constore, keystore, master, n_states, n_trans, cov,
             level_ends, blocks_done) = self.load_checkpoint(
                resume, (hi0, lo0))
            if checkpoint and os.path.abspath(resume) == \
                    os.path.abspath(checkpoint) and not frontier:
                for suf, w in ((".rows", self.schema.P), (".links", 3),
                               (".con", 1), (".keys", 2)):
                    # a pre-widening .links (width 2) is left alone: the
                    # first post-resume snapshot rewrites it whole
                    ckpt.trim_stream(checkpoint + suf, n_states, w)
        else:
            if frontier:
                # level 1 = the init state alone; next level opens empty
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
            master = self._new_master()
            master.seed(int(keyset.pack_keys(
                np.uint32(hi0)[None], np.uint32(lo0)[None])[0]))
            init_packed = self.schema.pack(
                np.asarray(init_vec, np.int32), np)
            if frontier:
                host.cur.append(init_packed[None, :])
                con0 = model.constraint_ok(init_py, bounds)
                constore.cur.append(np.asarray([[con0]], np.int32))
            else:
                host.append(init_packed[None, :])
                host.append_links(np.asarray([-1], np.int64),
                                  np.asarray([-1], np.int32))
                con0 = model.constraint_ok(init_py, bounds)
                constore.append(np.asarray([[con0]], np.int32))
            keystore.append(np.asarray(
                [[np.uint32(lo0), np.uint32(hi0)]],
                np.uint32).view(np.int32))
            n_states = 1
            n_trans = 0
            cov = np.zeros(self.A, np.int64)
            level_ends = [1]
            blocks_done = 0

        fc = self._init_filter()                # filter ≠ correctness:
        dst = self._init_devset() if self._dd_apply else None
        export_rows = 0      # rows actually exported d2h (post-filter)
        dd_hits = 0          # rows the device set dropped pre-export
        bufsets = [self._make_bufs(), self._make_bufs()]
        pend = {"keys": [], "rows": [], "par": [],  # resume starts empty
                "lane": [], "con": []}
        pend_steps = 0       # chunk steps whose stream ``pend`` holds
        # Background dedup worker (RAFT_TLA_HOSTDEDUP): flushes run on
        # one daemon thread, depth-1 ordered, so flush i's new keys are
        # in the master before flush i+1's dedup starts — cross-flush
        # first-occurrence order is untouched and discovery stays byte-
        # identical.  Every reader of flush-mutated state (block upload,
        # checkpoint, level boundary, terminal/stop paths) drains first.
        worker = flushq.DedupWorker(
            lambda batch: self._flush(batch, master, host, constore,
                                      keystore, cov),
            phases=tel.phases) \
            if self._host_dedup else None
        if worker is not None:
            _cleanup.callback(worker.close)

        def seal(p):
            batch = {k: v[:] for k, v in p.items()}
            for v in p.values():
                v.clear()
            return batch

        def flush_sync():
            """Drain the background queue, then flush the remaining pend
            inline — afterwards master/stores/cov reflect every streamed
            candidate, exactly as in the synchronous engine."""
            nonlocal n_states, pend_steps
            pend_steps = 0
            if worker is not None:
                with tel.phases.phase("dedup_wait") as ph:
                    if tr.enabled:
                        ph.set(backlog=worker.backlog())
                    n_states += worker.drain()
            with tel.phases.phase("dedup") as ph:
                if tr.enabled:
                    ph.set(keys=sum(len(k) for k in pend["keys"]))
                n_states += self._flush(pend, master, host, constore,
                                        keystore, cov)
        Fcap = self.caps.block
        S_up, whole_above = self._up_rows, self._up_whole
        row_bytes = self.schema.P * 4 + 1     # a frontier row on the wire
        # what one segment's buffers weigh on the wire, from shapes (span
        # args; that transfer is whole buffers)
        buf_bytes = self._buf_rows * (self.schema.P * 4 + 17)
        # The frontier block is resident: one device buffer pair a slot
        # for the whole check() (two slots under the prefetcher, so block
        # k+1 lands while k is expanded), and one host staging pair a
        # slot.  A block's upload reads its rows into the staging pair
        # and ships the live prefix only, in pieces of S_up rows that
        # ``_place`` lays into the resident buffers (donated: in place);
        # past ``whole_above`` piece-rounded rows one whole-buffer
        # transfer is cheaper and takes the slot's place.  Either way the
        # rows past the block's own are whatever an earlier block left,
        # on the host and on the device: the segment masks them by
        # ``block_rows``, which only the dispatch after a complete upload
        # sets — a stop between two pieces leaves nothing a later block
        # could read as live.  One loader for both RAFT_TLA_PREFETCH arms.
        n_slots = 2 if self._prefetch else 1
        resident = [self._alloc_block() for _ in range(n_slots)]
        stage_rows = [np.zeros((Fcap, self.schema.P), np.int32)
                      for _ in range(n_slots)]
        stage_con = [np.zeros((Fcap,), bool) for _ in range(n_slots)]

        def load_block(start, rows, slot):
            """Rows ``[start, start + rows)`` on the device in ``slot``:
            ``(fbuf, fcon, rows sent, pieces)``."""
            # range-disjointness precondition (utils/prefetch)
            assert start + rows <= level_ends[-1], \
                (start, rows, level_ends[-1])
            rb, cb = stage_rows[slot], stage_con[slot]
            rb[:rows] = host.read(start, rows)
            cb[:rows] = constore.read(start, rows)[:, 0]
            pieces = -(-rows // S_up)
            sent = pieces * S_up
            if sent > whole_above:
                pieces, sent = 1, Fcap
                resident[slot] = None    # freed before its successor lands
                blk = (jax.device_put(rb), jax.device_put(cb))
            else:
                blk = resident[slot]
                for at in range(0, sent, S_up):
                    blk = self._place(*blk, rb[at:at + S_up],
                                      cb[at:at + S_up], np.int32(at))
            # the staging pair is reusable, and the span honest, only
            # once the transfers have landed
            resident[slot] = jax.block_until_ready(blk)
            return (*blk, sent, pieces)

        # Upload prefetcher (RAFT_TLA_PREFETCH): while the device
        # expands block k, a daemon thread runs ``load_block`` for block
        # k+1 into the other slot, so the block boundary swaps to a
        # resident buffer instead of paying drain→read→h2d.  Safe
        # concurrently with the flush worker: block reads target rows <
        # level_ends[-1], all published before the level began, while
        # in-flight flushes append only rows >= level_ends[-1] (the
        # store concurrency contract, utils/native) — so prefetch-on
        # also drops the upload's unconditional dedup_wait drain.
        prefetcher = None
        if self._prefetch:
            prefetcher = prefetch.BlockPrefetcher(
                load_block, phases=tel.phases, tracer=tel.trace)
            _cleanup.callback(prefetcher.close)
        viol = None          # (kind, inv_idx, dead_g) once detected
        viol_key = None
        fail = 0
        route_peak = 0       # max live enabled lanes seen in any chunk
        stream_peak = 0      # most rows any chunk streamed (sizes _S_OUT)
        stream_slabs = 0     # slab writes of the pass
        probe_tiles = 0      # tiles of the filter probe (sizes _T_PROBE)
        complete = True
        stopped = False
        t_warm = None
        pacer = pacing.SegmentPacer(self.seg_chunks, self.SEG_MIN,
                                    self.SEG_MAX, self.SEG_TARGET_S,
                                    self.SEG_CLAMP_S)
        budget = pacer.budget
        last_ckpt = time.monotonic()
        tel.run_start(n_states=n_states, group=self._group)

        def progress():
            if not tel.active:
                return
            # report the same inclusive count the old stats stream did
            # (ADVICE r4): bare n_states advances only at flushes, which
            # read as a 0-then-spike rate artifact; the tracker anchors
            # its incremental rate on the running max of this count, so a
            # post-flush dip never reads as a negative rate
            n_incl = n_states + sum(len(k) for k in pend["keys"])
            if worker is not None:
                n_incl += worker.inclusive_extra()
            tel.segment(
                n_states=n_states, n_incl=n_incl,
                level=len(level_ends), n_transitions=n_trans,
                coverage=dict(aggregate_coverage(self.table, cov)),
                route_peak=route_peak,
                stream_peak=stream_peak, stream_slabs=stream_slabs,
                probe_tiles=probe_tiles,
                flush_backlog=worker.backlog() if worker else None,
                upload_wait_ms=round(prefetcher.wait_s * 1e3, 3)
                if prefetcher else None,
                prefetch_hits=prefetcher.hits if prefetcher else None,
                export_rows=export_rows,
                dev_dedup_hits=dd_hits if self._dd_apply else None)

        n_trans_mark = n_trans   # n_trans as of the current block's start
        stopped_by = None
        # the open level's work
        lvl_segs = lvl_steps = lvl_rows = lvl_slabs = lvl_peak = 0
        lvl_valid = lvl_route = lvl_tiles = 0

        def end_level():
            # lanes: what the dense step computed, enabled or not;
            # n_valid / lanes is how much of it was enabled work
            level_sp.set(segments=lvl_segs, steps=lvl_steps,
                         lanes=lvl_steps * N, n_valid=lvl_valid,
                         route_peak=lvl_route,
                         streamed_rows=lvl_rows,
                         stream_peak=lvl_peak, stream_slabs=lvl_slabs,
                         probe_tiles=lvl_tiles,
                         new_states=n_states - lvl_hi).close()

        while not stopped:
            lvl_lo = level_ends[-2] if len(level_ends) > 1 else 0
            lvl_hi = level_ends[-1]
            b0 = lvl_lo + blocks_done * Fcap
            # explicit handle: every exit of the body lands on
            # end_level(), here or after the loop (close is idempotent)
            level_sp = tr.open("level", level=len(level_ends),
                               rows=lvl_hi - lvl_lo,
                               row_words=self.schema.P,
                               blocks=-(-(lvl_hi - b0) // Fcap))
            lvl_segs = lvl_steps = lvl_rows = lvl_slabs = lvl_peak = 0
            lvl_valid = lvl_route = lvl_tiles = 0
            if prefetcher is not None and b0 < lvl_hi:
                # level start: every block address in [lvl_lo, lvl_hi)
                # is known now — warm the first block immediately
                prefetcher.schedule(b0, min(Fcap, lvl_hi - b0))
            for b_start in range(b0, lvl_hi, Fcap):
                b_rows = min(Fcap, lvl_hi - b_start)
                n_trans_mark = n_trans
                if prefetcher is not None:
                    # prefetch-on: NO pre-upload drain — block reads hit
                    # rows below lvl_hi only, published before the level
                    # began; the in-flight flush appends rows >= lvl_hi
                    # (disjoint ranges, utils/native contract).  The
                    # dedup_wait phase now fires only at flush_sync /
                    # checkpoint drains: that asymmetry in the phase
                    # timers is the gate's signature.
                    with tel.phases.phase("upload") as ph:
                        hits0 = prefetcher.hits
                        fbuf, fcon, sent, pieces = prefetcher.take(
                            b_start, b_rows)
                        ph.set(rows=b_rows, padded_rows=sent,
                               bytes=sent * row_bytes, pieces=pieces,
                               prefetch_hit=prefetcher.hits > hits0)
                    nxt = b_start + Fcap
                    if nxt < lvl_hi:
                        prefetcher.schedule(nxt,
                                            min(Fcap, lvl_hi - nxt))
                else:
                    if worker is not None:
                        # without the prefetcher's disjointness
                        # discipline, settle the in-flight flush before
                        # reading the block
                        with tel.phases.phase("dedup_wait") as ph:
                            if tr.enabled:
                                ph.set(backlog=worker.backlog())
                            n_states += worker.drain()
                    with tel.phases.phase("upload") as ph:
                        fbuf, fcon, sent, pieces = load_block(
                            b_start, b_rows, 0)
                        ph.set(rows=b_rows, padded_rows=sent,
                               bytes=sent * row_bytes, pieces=pieces,
                               prefetch_hit=False)
                fc = fc._replace(c=jnp.int32(0))
                # Two-deep segment pipeline: segment k+1 depends on k only
                # through the filter carry, so it is dispatched BEFORE k's
                # outputs are harvested — the d2h transfer and the host
                # dedup flush overlap device compute (the PP overlap the
                # round-1 verdict called out).  Dispatch order == harvest
                # order == stream order, so every exactness argument is
                # unchanged.  A segment dispatched speculatively after the
                # block's last chunk runs zero chunks (its while_loop cond
                # fails immediately); one harvested AFTER a stop event
                # (violation/failure/deadline) is dropped whole — its work
                # lies beyond the refbfs-exact stop point, and its filter
                # insertions are harmless (the run is over; resume
                # rebuilds the filter empty).
                q = []               # in-flight: (bufset idx, stats, t)
                free = list(range(len(bufsets)))
                block_done = False
                # chunk steps of the level past this block, and of this
                # block not yet harvested (what a hand-over hides behind)
                later_steps = -(-(lvl_hi - b_start - b_rows) // B)
                block_steps_left = -(-b_rows // B)
                t_last_harvest = time.monotonic()
                while q or not (block_done or stopped):
                    if (not stopped and deadline_s is not None
                            and t_warm is not None
                            and time.monotonic() - t_warm > deadline_s):
                        complete = False
                        stopped = True
                        stopped_by = "deadline"
                        tel.stop_requested("deadline")
                    if not stopped and self._sigint:
                        complete = False      # graceful-stop contract:
                        stopped = True        # flush+snapshot below
                        stopped_by = "sigint"
                        tel.stop_requested("sigint")
                    if not (block_done or stopped) and free:
                        idx = free.pop(0)
                        t_disp = time.monotonic()
                        # enabling phase timers blocks on each dispatch —
                        # honest per-phase walls at the cost of the
                        # two-deep overlap (obs/phases.py contract)
                        with tel.phases.phase("expand") as ph:
                            fc, bufsets[idx], stats = self._segment(
                                fc, bufsets[idx], fbuf, fcon,
                                jnp.int32(budget), jnp.int32(b_rows))
                            ph.sync(stats)
                        ncur = dhits = None
                        if self._dd_apply is not None:
                            # applied in dispatch order (== stream
                            # order): the set's serial carry reflects
                            # exactly the rows streamed before this
                            # segment, so drops are provably re-sights
                            with tel.phases.phase("devdedup") as ph:
                                dst, bufsets[idx], ncur, dhits = \
                                    self._dd_apply(dst, bufsets[idx],
                                                   stats.cursor)
                                ph.sync(ncur)
                        q.append((idx, stats, ncur, dhits, t_disp, budget))
                        if len(q) < 2:
                            continue         # keep the pipeline full
                    if not q:                # stop landed with nothing
                        break                # in flight
                    idx, stats, ncur, dhits, t_disp, seg_budget = q.pop(0)
                    # Stats first (tiny); the OCAP-sized buffers transfer
                    # only when the segment streamed anything.  The full-
                    # buffer transfer (vs the old jitted prefix slice) is
                    # deliberate: a slice program would enqueue BEHIND the
                    # in-flight speculative segment on the serial device
                    # queue and stall the harvest until it finishes —
                    # defeating the overlap this pipeline exists for.  At
                    # the 8 s segment target the fixed transfer is a few
                    # percent; zero-stream segments (every block end) now
                    # skip it entirely.
                    with tel.phases.phase("export"):
                        # the stats fetch is where the host waits for the
                        # segment: device time, not transfer
                        with tr.span("segment_wait"):
                            st_h = jax.device_get(stats)
                            # gate on: the harvest slices the POST-filter
                            # cursor — dropped rows never cross d2h at all
                            ns = int(st_h.cursor) if ncur is None \
                                else int(jax.device_get(ncur))
                        nv = int(st_h.n_valid)
                        vk = int(st_h.viol_kind)
                        n_steps = int(st_h.steps)
                        seg_peak = int(st_h.stream_peak)
                        seg_slabs = int(st_h.stream_slabs)
                        seg_tiles = int(st_h.probe_tiles)
                        seg_route = int(st_h.peak)
                        route_peak = max(route_peak, seg_route)
                        stream_peak = max(stream_peak, seg_peak)
                        stream_slabs += seg_slabs
                        probe_tiles += seg_tiles
                        if tr.enabled:
                            # dispatch -> stats ready, on its own track
                            # (segments overlap: two are in flight)
                            tr.emit_span(
                                "segment", t_disp,
                                time.monotonic() - t_disp,
                                thread="segments", level=len(level_ends),
                                block=(b_start - lvl_lo) // Fcap,
                                row_words=self.schema.P,
                                budget=seg_budget, steps=n_steps,
                                lanes=n_steps * N, group=self._group,
                                images=self._group * n_steps * N,
                                streamed_rows=ns, n_valid=nv,
                                route_peak=seg_route,
                                stream_peak=seg_peak,
                                stream_slabs=seg_slabs,
                                probe_tiles=seg_tiles,
                                dropped=stopped)
                        lvl_segs += 1
                        lvl_steps += n_steps
                        lvl_valid += nv
                        lvl_route = max(lvl_route, seg_route)
                        lvl_slabs += seg_slabs
                        lvl_tiles += seg_tiles
                        lvl_peak = max(lvl_peak, seg_peak)
                        bufs_h = None
                        if ns and not stopped:
                            with tr.span("d2h", rows=ns, bytes=buf_bytes):
                                bufs_h = jax.device_get(bufsets[idx])
                    free.append(idx)
                    if stopped:
                        continue             # drop post-stop segments
                    lvl_rows += ns
                    n_trans += nv
                    fail |= int(st_h.fail)
                    if dhits is not None:
                        dd_hits += int(jax.device_get(dhits))
                    if ns:
                        export_rows += ns
                        # .copy(): a bare slice would pin the whole OCAP
                        # transfer buffer in pend until the next flush
                        pend["keys"].append(keyset.pack_keys(
                            bufs_h.okey_hi[:ns], bufs_h.okey_lo[:ns]))
                        pend["rows"].append(bufs_h.orows[:ns].copy())
                        if not frontier:
                            # rebase block-relative device parents to
                            # global int64 discovery indices (frontier
                            # mode keeps no links — skip the dead copy)
                            pend["par"].append(
                                bufs_h.opar[:ns].astype(np.int64)
                                + b_start)
                        pend["lane"].append(bufs_h.olane[:ns].copy())
                        pend["con"].append(bufs_h.ocon[:ns].copy())
                    if vk or fail:
                        if vk:
                            dg = int(st_h.dead_g)
                            viol = (vk, int(st_h.viol_inv),
                                    dg + b_start if dg >= 0 else dg)
                            if vk == 1:
                                # truncation makes the violator the last
                                # streamed candidate; remember its key to
                                # assert the flushed identity below
                                viol_key = pend["keys"][-1][-1]
                        stopped = True
                        continue
                    now = time.monotonic()
                    if t_warm is None:
                        t_warm = now
                    # own device time ~ since the later of my dispatch
                    # and the previous harvest (queue wait excluded); a
                    # zero-chunk speculative segment (block already done)
                    # is pure transfer time — no pacing signal, and it
                    # would poison the watchdog ratchet
                    if n_steps > 0:
                        budget = pacer.update(
                            now - max(t_disp, t_last_harvest), n_steps)
                    t_last_harvest = now
                    self.seg_chunks = budget
                    block_done = block_done or bool(st_h.done)
                    n_pend = sum(len(x) for x in pend["keys"])
                    block_steps_left -= n_steps
                    pend_steps += n_steps
                    # At ``caps.flush`` the pending stream goes to the
                    # host dedup whatever else holds (the submit waits for
                    # the previous flush).  Below it the worker is handed
                    # the stream only while the level has device work left
                    # to hide the merge behind (``_HANDOVER_STEPS``) and
                    # only when it is free, so the submit never blocks and
                    # a batch grows to what the worker keeps up with.
                    # ``q`` being non-empty is no sign of work left: the
                    # segment in flight after a block's last chunk runs
                    # zero chunks.  So a level's last harvest hands nothing
                    # over, the level close merges that stream inline, and
                    # a level of one segment never meets the worker.
                    hand_over = n_pend >= self.caps.flush
                    if worker is not None and n_pend and not hand_over:
                        hand_over = (
                            (block_steps_left + later_steps)
                            * _HANDOVER_STEPS >= pend_steps
                            and not worker.backlog())
                    if hand_over:
                        pend_steps = 0
                        if worker is not None:
                            # sealed-batch submission: blocks only until
                            # the PREVIOUS flush completes (depth-1);
                            # this one runs while the next segment
                            # computes.  n_states lags by at most one
                            # in-flight flush — the _IDX_CEIL re-check
                            # at every drain point keeps the ceiling
                            # honest.
                            with tel.phases.phase("dedup_submit") as ph:
                                if tr.enabled:
                                    ph.set(keys=n_pend,
                                           backlog=worker.backlog())
                                worker.submit(seal(pend), n_pend)
                            n_states += worker.collect()
                        else:
                            with tel.phases.phase("dedup") as ph:
                                if tr.enabled:
                                    ph.set(keys=sum(
                                        len(k) for k in pend["keys"]))
                                n_states += self._flush(pend, master,
                                                        host, constore,
                                                        keystore, cov)
                        if n_states > _IDX_CEIL:
                            fail = FAIL_INDEX
                            stopped = True
                        progress()
                        # the flush ran while the next segment computed;
                        # re-stamp so its duration never inflates the next
                        # harvest's dt (the pacer ratchet never decays)
                        t_last_harvest = time.monotonic()
                if stopped:
                    break
                blocks_done += 1
                if checkpoint and (time.monotonic() - last_ckpt
                                   >= checkpoint_every_s):
                    flush_sync()
                    with tel.phases.phase("snapshot"):
                        self.save_checkpoint(checkpoint, host, constore,
                                             keystore, n_states, n_trans,
                                             cov, level_ends, blocks_done,
                                             (hi0, lo0))
                    tel.checkpoint(checkpoint, n_states)
                    last_ckpt = time.monotonic()
            if stopped:
                break
            # level_close: everything between the level's last block and
            # the next level's first upload
            with tr.span("level_close"):
                blocks_done = 0
                flush_sync()
                progress()
                if n_states > _IDX_CEIL:
                    fail = FAIL_INDEX
                    break
                if n_states == level_ends[-1]:       # no new states: done
                    break
                level_ends.append(n_states)
                if self._dd_apply is not None:
                    # the set is within-level by contract: reset it empty
                    # at every boundary so capacity tracks one level's
                    # stream, not the whole run (a next-level re-sight of
                    # a previous-level state streams and the master drops
                    # it, exactly as with the gate off)
                    dst = self._init_devset()
                if prefetcher is not None:
                    # quiesce before any rotation/teardown below; by now
                    # the last take() consumed the final scheduled block,
                    # so this is a no-op unless a stop raced the level end
                    prefetcher.invalidate()
                if frontier:
                    # the just-finished level's rows are dead weight now.
                    # With snapshots, the files outlive the rotation until
                    # the npz commits (save_frontier_snapshot.delete_old);
                    # without (tmpdir mode) there is nothing to resume, so
                    # delete immediately or every level accumulates.
                    keep = self.caps.keep_levels
                    host.rotate(delete_old=tmpdir is not None and not keep)
                    constore.rotate(delete_old=tmpdir is not None
                                    and not keep)
                if len(level_ends) > self.caps.levels:
                    _cleanup.close()
                    raise RuntimeError(
                        f"DDD search aborted: {decode_fail(FAIL_LEVEL)} "
                        f"(caps={self.caps}) — grow DDDCapacities and "
                        "rerun")
            end_level()
        end_level()          # the exits by break; a no-op after the above

        if prefetcher is not None:
            # stop paths (violation/SIGINT/deadline) can leave a
            # prefetch in flight; no store read survives past here, so
            # snapshots, traces and store teardown see a quiet store
            prefetcher.invalidate()
        flush_sync()
        if not complete and checkpoint and not viol and not fail:
            # graceful stop (SIGINT or deadline): same mid-level snapshot
            # shape as the periodic path above (pend flushed first, so
            # re-running the partial block on resume dedups against the
            # master keys) — a deadline stop must be as lossless as a
            # SIGINT one or --deadline silently discards work.  The
            # snapshot records n_trans as of the partial block's START:
            # states dedup on the re-run, transitions do not, so counting
            # any of the partial block here would double them on resume.
            with tel.phases.phase("snapshot"):
                self.save_checkpoint(checkpoint, host, constore, keystore,
                                     n_states, n_trans_mark, cov,
                                     level_ends, blocks_done, (hi0, lo0))
            tel.checkpoint(checkpoint, n_states)
        if fail:
            _cleanup.close()
            raise RuntimeError(
                f"DDD search aborted: {decode_fail(fail)} "
                f"(caps={self.caps}) — grow DDDCapacities and rerun")

        violation = None
        if viol is not None:
            kind, vi, dead_g = viol
            if kind == 1:
                viol_g = n_states - 1    # the violator is always new and
                n_inv = len(self.config.invariants)   # last in the flush
                inv_name = self.config.invariants[min(vi, n_inv - 1)]
                kw = keystore.read(viol_g, 1).view(np.uint32)
                got_key = int(keyset.pack_keys(kw[:, 1], kw[:, 0])[0])
                if got_key != int(viol_key):
                    _cleanup.close()
                    raise RuntimeError(
                        "DDD violator identity mismatch after flush — "
                        "fingerprint collision or dedup-order bug")
            else:
                viol_g = dead_g
                inv_name = DEADLOCK
            if frontier:
                # no trace links in frontier retention; with
                # keep_levels a backward re-search over the retained
                # level files rebuilds the full TLC-equivalent trace,
                # else (-noTrace equivalence) report the state itself
                row = self.schema.unpack(host.read(int(viol_g), 1)[0],
                                         np)
                py = model.from_vec(row, self.bounds)
                host.sync()          # commit cur/nxt for mmap reads
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
                    py = model.from_vec(row, self.bounds)
                    label = self.table[int(lane_g[0])].label() if k > 0 \
                        else None
                    chain.append((label, py))
                violation = Violation(invariant=inv_name,
                                      state=chain[-1][1], trace=chain)

        levels_arr = [level_ends[0]] + [
            level_ends[k] - level_ends[k - 1]
            for k in range(1, len(level_ends))]
        tail = n_states - level_ends[-1]
        if tail > 0:                 # partial final level (stopped run)
            levels_arr.append(tail)
        coverage = aggregate_coverage(self.table, cov)
        if tmpdir is not None:
            host.close()
            constore.close()
            keystore.close()
        if retain_store:
            # graph exports (models/liveness.ddd_graph) re-expand the
            # stored rows; the caller owns closing these
            self.retained = (host, constore, keystore, n_states)
        else:
            host.close()
            constore.close()
            keystore.close()
        if self._eterm0 is not None:
            pass_sp.set(elections_peak=self._epeak)
        if self._scan_moved is not None:
            pass_sp.set(scan_moved_fields=self._scan_moved)
        pass_sp.set(levels=len(levels_arr), n_states=n_states,
                    stopped_by="violation" if violation is not None
                    else stopped_by).close()
        result = EngineResult(
            n_states=n_states, diameter=len(levels_arr) - 1,
            n_transitions=n_trans, coverage=coverage,
            violation=violation, levels=levels_arr,
            wall_s=time.monotonic() - t0, complete=complete,
            level_log=tel.passlog.record)
        tel.run_end(result)
        _cleanup.close()
        return result


def check(config: CheckConfig, caps: DDDCapacities | None = None,
          **kw) -> EngineResult:
    return DDDEngine(config, caps).check(**kw)
