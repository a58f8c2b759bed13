"""The compile ledger — what JAX compiled in this process, when, and what
each stage cost.

One process-wide ``jax.monitoring`` listener pair, installed idempotently
by the compile-and-cache layer's one entry point
(``serve/sched.enable_compile_cache``) and by the ddd engine
constructors.  It records ``(t_mono, kind, dur_s, fun)`` for the four
stages a program passes on its way to the device —

    ``trace``       jaxpr tracing of the Python function (outermost
                    only: a program's trace holds those of the jitted
                    functions it calls, which JAX reports one by one)
    ``lower``       jaxpr -> MLIR module
    ``backend``     the backend's compile call, **persistent-cache load
                    included**: on a warm cache this is the retrieval
    ``cache_load``  the retrieval alone, when the persistent cache hit

— plus the persistent cache's request / hit / miss counts, in a bounded
list (:func:`snapshot`).  It fires only when JAX compiles: nothing here is
on a run's hot path.  While an engine run traces (``RAFT_TLA_TRACE``), each
record is also a ``compile`` span on the synthetic ``compiles`` track of
that run's log, and ``run_end.compiles`` carries the run's totals.

This module imports JAX only inside :func:`install`, which only processes
that compile call: ``obs`` stays importable by supervisors that must never
open a device.
"""

from __future__ import annotations

import collections
import threading
import time

from raft_tla_tpu.obs.trace import NULL_TRACER

MAX_RECORDS = 4096

_DURATION_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COUNT_KINDS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class CompileLedger:
    """The records and totals behind the module-level functions; its own
    class so a test can drive the listeners without touching JAX."""

    def __init__(self, max_records: int = MAX_RECORDS):
        self._lock = threading.Lock()
        self._records: collections.deque = collections.deque(
            maxlen=max_records)
        self._n_recorded = 0
        # kind -> [events, seconds] for durations, kind -> count otherwise
        self._totals: dict = {}
        self._tracer = NULL_TRACER
        self._installed = False
        self._tls = threading.local()    # .depth: traces open on a thread

    def install(self) -> bool:
        """Register the listeners with ``jax.monitoring`` (once a
        process).  Returns True the first time."""
        with self._lock:
            if self._installed:
                return False
            self._installed = True
        import jax.monitoring
        jax.monitoring.register_scalar_listener(self.on_scalar)
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return True

    @property
    def installed(self) -> bool:
        with self._lock:
            return self._installed

    def attach(self, tracer) -> None:
        """Route each new record to ``tracer`` as a ``compile`` span too
        (one run's tracer at a time; the last attached wins)."""
        with self._lock:
            self._tracer = tracer

    def detach(self, tracer) -> None:
        with self._lock:
            if self._tracer is tracer:
                self._tracer = NULL_TRACER

    def on_scalar(self, event: str, _value, **_kw) -> None:
        # JAX stamps a stage's start as a scalar of the same name
        if event == _TRACE_EVENT:
            self._tls.depth = getattr(self._tls, "depth", 0) + 1

    def on_duration(self, event: str, secs: float, **kw) -> None:
        kind = _DURATION_KINDS.get(event)
        if kind is None:
            return
        if event == _TRACE_EVENT:
            depth = self._tls.depth = max(
                0, getattr(self._tls, "depth", 1) - 1)
            if depth:
                return       # a trace inside a trace: the outer holds it
        t0 = time.monotonic() - secs
        fun = kw.get("fun_name")
        fun = None if fun is None else str(fun)
        with self._lock:
            self._records.append((t0, kind, float(secs), fun))
            self._n_recorded += 1
            tot = self._totals.setdefault(kind, [0, 0.0])
            tot[0] += 1
            tot[1] += float(secs)
            tracer = self._tracer
        extra = {} if fun is None else {"fun": fun}
        tracer.emit_span("compile", t0, secs, thread="compiles",
                         kind=kind, **extra)

    def on_event(self, event: str, **_kw) -> None:
        kind = _COUNT_KINDS.get(event)
        if kind is None:
            return
        with self._lock:
            self._totals[kind] = self._totals.get(kind, 0) + 1

    def totals(self) -> dict:
        """``{kind: [events, seconds]}`` for the four stages and
        ``{kind: count}`` for the cache counters, since the process
        began."""
        with self._lock:
            return {k: list(v) if isinstance(v, list) else v
                    for k, v in self._totals.items()}

    def snapshot(self) -> dict:
        """The records still held (oldest first; ``dropped`` says how
        many the bound pushed out) and the totals."""
        with self._lock:
            records = [{"t0": t0, "kind": kind, "dur_s": dur, "fun": fun}
                       for t0, kind, dur, fun in self._records]
            dropped = self._n_recorded - len(records)
        return {"records": records, "dropped": dropped,
                "totals": self.totals()}


def totals_since(before: dict, after: dict) -> dict:
    """``after - before`` of two :meth:`CompileLedger.totals`, rounded for
    an event line; kinds that did not move are left out."""
    out: dict = {}
    for kind, v in after.items():
        if isinstance(v, list):
            n0, s0 = before.get(kind, (0, 0.0))
            if v[0] > n0:
                out[kind] = [v[0] - n0, round(v[1] - s0, 6)]
        elif v > before.get(kind, 0):
            out[kind] = v - before.get(kind, 0)
    return out


LEDGER = CompileLedger()
install = LEDGER.install
snapshot = LEDGER.snapshot
