"""Async dispatch scheduler — keep the device busy through every host
phase of the serving loop.

PR 6's :class:`~raft_tla_tpu.serve.batch.BatchExecutor` dispatched bins
round-robin but *synchronously*: pack bin A's chunk, run its fused step,
then immediately fetch the outputs and walk every lane's host phases
(d2h fetch -> dedup -> lane scan -> backfill) while the device sat idle
— and every new step signature paid its jit compile on that same
critical path.  This module lifts the ddd engines' two-deep segment
pipeline (``ddd_engine.py`` harvest loop, ``parallel/ddd_shard_engine``)
into the serving layer:

- **Pipelined dispatch** — up to ``depth`` fused dispatches are kept in
  flight at once (JAX async dispatch: enqueue returns immediately; the
  d2h fetch is the only blocking point).  While bin A's harvest runs on
  the host, bin B's step — or bin A's *next* chunk of the same frontier
  level — is already executing.  Tickets are harvested strictly FIFO, so
  per-lane slice order equals dispatch order equals the order a solo
  ``engine.Engine`` would process the same frontier: per-lane chunk
  semantics stay Engine-verbatim and completing lanes remain
  byte-identical to their solo runs (the PR 6 invariant).
- **Double-buffered staging** (the ddd bufset discipline): each bin owns
  ``depth`` host staging buffers; a dispatch claims one, the harvest
  frees it, so an in-flight dispatch's input is never overwritten — and
  the packer writes rows in place instead of reallocating per dispatch.
- **Speculative same-bin dispatch**: within a BFS level, chunk k+1 of a
  lane's frontier does not depend on chunk k's harvest (new states only
  extend the *next* level), so it may be dispatched before k's results
  land.  If k stops the lane (violation, deadlock, failure), k+1's
  slice for that lane is dropped whole at harvest — exactly the ddd
  rule that post-stop segments are dropped — which leaves every counter
  identical to a run that never speculated.
- **Compile off the critical path**: each bin's fused step is
  lowered+compiled AOT on a background thread, so already-compiled bins
  keep the device fed while a new signature compiles.  The scheduler
  only blocks on a compile when nothing else has work (the device would
  idle anyway).  ``enable_compile_cache`` places JAX's persistent
  compilation cache (``JAX_COMPILATION_CACHE_DIR`` from outside, else
  ``--compile-cache DIR`` / ``RAFT_TLA_COMPILE_CACHE``, else — off the
  CPU — ``<checkout>/.jax_cache``) so daemon restarts are warm.
- **Fair-share packing** (deficit round robin): when a bin's live lanes
  oversubscribe the chunk, each dispatch grants every pending lane a
  quantum of ``max(1, B // n_live)`` rows plus any deficit carried from
  dispatches where the chunk ran out; the ring head advances past the
  lanes served, so consecutive dispatches sweep the ring.  Starvation
  bound (asserted in tests): a live lane with pending rows rides at
  least once in any window of ``ceil(n_live / lanes-served-per-dispatch)``
  consecutive dispatches — at most ``n_live``.  Leftover chunk space
  backfills greedily in ring order (work-conserving), so the chunk stays
  full whenever any lane has work.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

import numpy as np

from raft_tla_tpu.obs.trace import NULL_TRACER
from raft_tla_tpu.ops import fingerprint as fpr

ENV_COMPILE_CACHE = "RAFT_TLA_COMPILE_CACHE"


# <checkout>/.jax_cache — fixed, because the directory is part of the
# cache key's lookup: a temp name, pid or timestamp would never hit.
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(path: str | None = None, *,
                         platform: str) -> str | None:
    """The one place JAX's persistent compilation cache is placed, for
    every process that compiles (check, serve, pool and campaign
    children).  Returns the directory in effect, or None for no cache.

    ``JAX_COMPILATION_CACHE_DIR`` set: the machine placed the cache from
    outside — JAX reads the variable itself and this function sets no
    directory at all, whatever ``path`` or ``RAFT_TLA_COMPILE_CACHE``
    say.  Unset: ``path`` (``--compile-cache``), else the gate, else
    ``<checkout>/.jax_cache``.  Either way the min-time / min-size knobs
    drop to zero so small step programs are cached too (a served toy job
    is otherwise all compile).

    One exception to the default directory: on the ``cpu`` platform
    (``platform`` = what utils.device.select_device reported) the cache
    is on only where somebody placed it.  jaxlib 0.9.0's XLA:CPU loader
    prints a ~3 KB ``cpu_aot_loader.cc`` line per cached program to
    stderr on every warm load; always-on, that flood filled the stderr
    pipe of a piped child and wedged it (tests/test_serve_sched's
    daemon test), and CPU runs here are correctness runs that have no
    use for a warm start."""
    import jax

    from raft_tla_tpu.obs import compiles
    # every process that compiles comes through here: the compile ledger
    # (what was traced, lowered, compiled or loaded, and for how long)
    # listens from now on
    compiles.install()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The step's stage scopes (ops/kernels.STAGE_SCOPES) are HLO metadata,
    # which JAX strips from the cache key by default: a cache filled before
    # a scope was added or renamed would hand back an executable whose
    # device trace names the OLD scopes (or none), and every per-stage
    # reading would be silently wrong.  With metadata in the key a cached
    # program always carries the names its source has.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    path = path or os.environ.get(ENV_COMPILE_CACHE) \
        or (None if platform == "cpu" else _DEFAULT_COMPILE_CACHE)
    if not path:
        return None
    path = os.path.abspath(path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1)).bit_length()


class _Ticket:
    """One in-flight fused dispatch: the device outputs plus the host
    metadata needed to demux them per lane at harvest time."""

    __slots__ = ("bn", "slices", "out", "buf_idx", "t_disp")

    def __init__(self, bn, slices, out, buf_idx, t_disp=0.0):
        self.bn = bn
        self.slices = slices            # [(lane, row0, nrows, gidx)]
        self.out = out                  # device dict (async results)
        self.buf_idx = buf_idx
        self.t_disp = t_disp            # monotonic issue time (tracing)


class _BinState:
    """Scheduler-side state for one bin: staging buffers, the DRR ring,
    and the background compile."""

    __slots__ = ("bn", "bufs", "free", "rr", "deficit", "compiled",
                 "thread", "compile_wall_s", "compiled_async",
                 "compile_error")

    def __init__(self, bn, depth: int, chunk: int):
        self.bn = bn
        self.bufs = [np.zeros((chunk, bn.lay.width), np.int32)
                     for _ in range(depth)]
        self.free = list(range(depth))
        self.rr = 0
        self.deficit: dict[str, int] = {}
        self.compiled = None
        self.thread: threading.Thread | None = None
        self.compile_wall_s: float | None = None
        self.compiled_async = False
        self.compile_error: Exception | None = None


class DispatchScheduler:
    """Route every bin dispatch through one pipelined issue/harvest loop.

    ``depth`` is the global in-flight dispatch cap (2 = the ddd two-deep
    precedent; 1 = fully synchronous, byte-for-byte the PR 6 executor's
    issue order — the A/B baseline).  ``compile_async=False`` also moves
    compiles back onto the dispatch path (lazy jit), completing the
    sequential baseline.  ``stop`` is an optional zero-arg callable; when
    it turns truthy the scheduler stops submitting, harvests what is in
    flight (their rows were already claimed from the frontiers, so the
    accounting stays exact) and returns — the daemon's drain hook.
    """

    def __init__(self, chunk: int, max_states: int | None = None,
                 depth: int = 2, compile_async: bool = True,
                 stop=None, tracer=None):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.chunk = chunk
        self.max_states = max_states
        self.depth = depth
        self.compile_async = compile_async
        self.stop = stop
        # v8 tracing (``--trace``): dispatch/harvest/compile spans plus
        # per-ticket issue->harvest lifetimes on a synthetic "tickets"
        # track (they overlap the main thread's nested spans).  The
        # NULL tracer's span() returns one shared no-op handle, so the
        # untraced path stays allocation-free.
        self.tracer = tracer or NULL_TRACER
        self.inflight: deque[_Ticket] = deque()
        self.stats = {"dispatches": 0, "peak_inflight": 0,
                      "async_compiles": 0, "compile_wall_s": {}}

    # -- compile ------------------------------------------------------------

    def _compile(self, st: _BinState) -> None:
        """Lower+compile a bin's fused step AOT (worker thread).  A
        failure is carried to the dispatch thread, which stops the bin's
        lanes with the compiler's message (:meth:`_fail_uncompilable`) —
        never a quiet retry as a lazy jit on the dispatch path."""
        import jax
        import jax.numpy as jnp
        with self.tracer.span("compile",
                              bin=getattr(st.bn, "tag", "bin")):
            t0 = time.monotonic()
            spec = jax.ShapeDtypeStruct((self.chunk, st.bn.lay.width),
                                        jnp.int32)
            try:
                st.compiled = jax.jit(st.bn.step_fn).lower(spec).compile()
            except Exception as e:      # thread boundary: reported by
                st.compile_error = e    # _fail_uncompilable, not lost
            st.compile_wall_s = time.monotonic() - t0

    def _fail_uncompilable(self, order: list, outcomes: dict) -> None:
        """Stop every live lane of a bin whose step failed to compile,
        attributed with the compiler's own message; other bins serve on."""
        for st in order:
            if st.compile_error is None:
                continue
            for lane in st.bn.live_lanes():
                lane.fail(f"step compile failed: {st.compile_error}")
                outcomes[lane.job_id] = lane.outcome

    def _start_compile(self, st: _BinState) -> None:
        if not self.compile_async:
            # sequential baseline: lazy jit, compiled at first dispatch
            import jax
            st.compiled = jax.jit(st.bn.step_fn)
            return
        st.compiled_async = True
        st.thread = threading.Thread(
            target=self._compile, args=(st,),
            name=f"serve-compile-{getattr(st.bn, 'tag', 'bin')}",
            daemon=True)
        st.thread.start()

    def _ready(self, st: _BinState) -> bool:
        if st.compiled is not None:
            return True
        if st.thread is not None and not st.thread.is_alive():
            st.thread.join()
            return st.compiled is not None
        return False

    # -- fair-share packing (deficit round robin) ---------------------------

    def _plan_takes(self, st: _BinState, live: list) -> list:
        """Decide how many rows each live lane rides this dispatch.
        Returns ``[(lane, take)]`` in ring order (takes > 0 only)."""
        B = self.chunk
        n = len(live)
        quantum = max(1, B // n)
        start = st.rr % n
        order = live[start:] + live[:start]
        budget = B
        takes: dict[str, int] = {}
        cut = n                          # ring index where the chunk ran out
        for i, lane in enumerate(order):
            if budget == 0:
                cut = i
                break
            d = min(st.deficit.get(lane.job_id, 0) + quantum, B)
            t = min(d, lane.pending_rows(), budget)
            if t > 0:
                takes[lane.job_id] = t
                budget -= t
            # deficit carries only while the lane still has unserved work
            st.deficit[lane.job_id] = \
                d - t if lane.pending_rows() - t > 0 else 0
        # ring head past the lanes served: consecutive dispatches sweep
        # the ring (the starvation bound); on a full sweep rotate by one
        # so pass-2 leftover priority also rotates
        st.rr = (start + (cut if cut < n else 1)) % n
        if budget:
            # work-conserving backfill: leftover space goes to deeper
            # frontiers in ring order, no deficit charge (it's idle space)
            for lane in order:
                if budget == 0:
                    break
                extra = min(lane.pending_rows() - takes.get(lane.job_id, 0),
                            budget)
                if extra > 0:
                    takes[lane.job_id] = takes.get(lane.job_id, 0) + extra
                    budget -= extra
        return [(lane, takes[lane.job_id]) for lane in order
                if takes.get(lane.job_id, 0) > 0]

    # -- issue --------------------------------------------------------------

    def _try_submit(self, st: _BinState) -> bool:
        """Pack and dispatch one chunk from this bin.  False when the bin
        has nothing packable right now (no live pending lanes, step not
        compiled yet, or no free staging buffer)."""
        import jax.numpy as jnp
        bn = st.bn
        if not st.free or not self._ready(st):
            return False
        live = [ln for ln in bn.live_lanes() if ln.pending_rows() > 0]
        if not live:
            return False
        plan = self._plan_takes(st, live)
        if not plan:
            return False
        tr = self.tracer
        with tr.span("dispatch", bin=getattr(bn, "tag", "bin")) as sp:
            buf_idx = st.free.pop(0)
            buf = st.bufs[buf_idx]
            B = self.chunk
            slices, pos = [], 0
            for lane, take in plan:
                gidx, vecs = lane.take(take)
                lane.inflight_slices += 1
                buf[pos:pos + take] = vecs
                slices.append((lane, pos, take, gidx))
                pos += take
            if pos < B:                  # pad to the static chunk shape
                buf[pos:B] = buf[0]
            out = st.compiled(jnp.asarray(buf))  # async: enqueue, no wait
            sp.set(rows=pos, lanes=len(slices))
        t_disp = time.monotonic() if tr.enabled else 0.0
        self.inflight.append(_Ticket(bn, slices, out, buf_idx, t_disp))
        self.stats["dispatches"] += 1
        self.stats["peak_inflight"] = max(self.stats["peak_inflight"],
                                          len(self.inflight))
        return True

    # -- harvest ------------------------------------------------------------

    def _harvest_one(self, states: dict, outcomes: dict) -> None:
        """Pop the oldest ticket, block on its d2h fetch, and run every
        host phase (dedup, lane scan, gather, backfill) — verbatim the
        PR 6 ``_dispatch`` tail, minus the lanes stopped since issue
        (their speculative slices drop whole)."""
        tk = self.inflight.popleft()
        tr = self.tracer
        tag = getattr(tk.bn, "tag", "bin")
        with tr.span("harvest", bin=tag):
            self._harvest_ticket(tk, states, outcomes)
        if tr.enabled:
            # The ticket's issue->harvest lifetime overlaps the main
            # thread's nested spans, so it rides a synthetic track.
            tr.emit_span("ticket", tk.t_disp,
                         time.monotonic() - tk.t_disp,
                         thread="tickets", bin=tag)

    def _harvest_ticket(self, tk: _Ticket, states: dict,
                        outcomes: dict) -> None:
        from raft_tla_tpu.serve.batch import _LaneFailure
        import jax.numpy as jnp
        bn, out = tk.bn, tk.out
        B, W, A = self.chunk, bn.lay.width, bn.A

        valid = np.asarray(out["valid"])
        ovf = np.asarray(out["overflow"])
        keys = fpr.to_u64(np.asarray(out["fp_hi"]),
                          np.asarray(out["fp_lo"]))
        inv_ok = np.asarray(out["inv_ok"])
        con_ok = np.asarray(out["con_ok"])

        # Phase 1 per lane slice; collect the chunk-global flat indices
        # of every accepted new state for one shared device gather.
        sel_flat: list[int] = []
        committing = []
        for lane, r0, nb, gidx in tk.slices:
            lane.inflight_slices -= 1
            if not lane.active:          # stopped since issue: drop whole
                continue
            sl = slice(r0, r0 + nb)
            try:
                new_flat = lane.scan_slice(valid[sl], ovf[sl], keys[sl],
                                           inv_ok[sl], con_ok[sl], gidx)
            except _LaneFailure as e:
                lane.fail(str(e))
                outcomes[lane.job_id] = lane.outcome
                continue
            committing.append((lane, len(new_flat)))
            sel_flat.extend(r0 * A + fi for fi in new_flat)

        # One gather for the whole dispatch (padded to a pow2 bucket so
        # the eager gather compiles O(log) distinct shapes), then split
        # back per lane in chunk order.
        n_new = len(sel_flat)
        if n_new:
            cap = _next_pow2(n_new)
            sel = np.asarray(sel_flat + [0] * (cap - n_new), dtype=np.int64)
            rows_all = np.asarray(
                out["svecs"].reshape(B * A, W)[jnp.asarray(sel)])[:n_new]
        else:
            rows_all = np.empty((0, W), dtype=np.int32)
        off = 0
        inflight_now = len(self.inflight)
        for lane, n_lane in committing:
            lane.commit_slice(rows_all[off:off + n_lane])
            off += n_lane
            try:
                lane.advance(self.max_states, inflight=inflight_now)
            except _LaneFailure as e:
                lane.fail(str(e))
            if not lane.active:
                outcomes[lane.job_id] = lane.outcome
        states[bn.key].free.append(tk.buf_idx)

    # -- main loop ----------------------------------------------------------

    def _stopping(self) -> bool:
        return bool(self.stop and self.stop())

    def run(self, bins: dict, outcomes: dict) -> dict:
        """Drive every bin to quiescence (or to the stop signal).
        Returns the per-bin compile stats (also kept on ``self.stats``)."""
        states = {key: _BinState(bn, self.depth, self.chunk)
                  for key, bn in bins.items()}
        # Kick off every compile up-front: the first signatures to finish
        # start dispatching while the rest still compile in background.
        for st in states.values():
            if st.bn.live_lanes():
                self._start_compile(st)
        order = list(states.values())
        rr = 0
        while True:
            self._fail_uncompilable(order, outcomes)
            stopping = self._stopping()
            if not stopping:
                # fill the pipeline, round-robin across bins
                while len(self.inflight) < self.depth:
                    submitted = False
                    for k in range(len(order)):
                        st = order[(rr + k) % len(order)]
                        if self._try_submit(st):
                            rr = (rr + k + 1) % len(order)
                            submitted = True
                            break
                    if not submitted:
                        break
            if self.inflight:
                self._harvest_one(states, outcomes)
                continue
            if stopping:
                break
            # Nothing in flight and nothing packable: done, unless a bin
            # with live work is still compiling — then wait for it (the
            # device would idle regardless; this is the only block).
            waiting = [st for st in order
                       if st.thread is not None and st.thread.is_alive()
                       and any(ln.pending_rows() > 0
                               for ln in st.bn.live_lanes())]
            if not waiting:
                break
            waiting[0].thread.join()
        self._fail_uncompilable(order, outcomes)   # a compile that failed
        #                                            after the last sweep
        for st in order:
            if st.compile_wall_s is not None:
                tag = getattr(st.bn, "tag", str(st.bn.key))
                self.stats["compile_wall_s"][tag] = \
                    round(st.compile_wall_s, 3)
                if st.compiled_async:
                    self.stats["async_compiles"] += 1
        return self.stats
