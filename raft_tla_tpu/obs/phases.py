"""Device-sync-aware phase timers — the measurement layer for the
export-anatomy / post-filter-anatomy chip jobs (ROADMAP item 3).

Timing an async-dispatch JAX program phase-by-phase requires a
``block_until_ready`` at each phase edge, which *serialises* the very
pipelining the engines rely on (the ddd engines dispatch segment k+1
before harvesting segment k).  So the timers are **off by default** and
the off path is engineered to be unmeasurable:

- ``phase(name)`` returns one shared, stateless no-op handle when
  disabled — no allocation, no clock read, no sync, nothing for jit to
  see.  An A/B with chip-state fiducials backs this (RESULTS.md).
- enabled (``--phase-timers`` / ``RAFT_TLA_PHASE_TIMERS=1``), each
  ``with timers.phase("expand") as ph: ... ph.sync(out)`` blocks on the
  value handed to ``sync`` before stamping, so the phase wall is honest
  device time, not dispatch time.  Enabling timers trades pipelining for
  attribution — per-phase numbers are for anatomy runs, not records.

Accumulated walls are drained into each ``segment`` event's ``phase_s``
field by :meth:`PhaseTimers.snapshot`.

Phase vocabulary (shared so logs compare across engines): ``upload``
(host->device frontier/block staging), ``expand`` (the jit segment),
``export`` (device->host harvest / pageout), ``dedup`` (host-side exact
dedup flush run inline, ddd only), ``snapshot`` (checkpoint save).
With background host dedup (``RAFT_TLA_HOSTDEDUP``) the ddd engine
splits ``dedup`` into ``dedup_submit`` (sealing + handing the batch to
the depth-1 worker — blocks only while the *previous* flush is still
running, so a nonzero wall here means the device outran the host dedup)
and ``dedup_wait`` (drain at a block/checkpoint/level/stop boundary —
the part of the flush that did NOT overlap device compute), so the
overlap is attributable, not inferred.  With upload prefetch
(``RAFT_TLA_PREFETCH``) the per-block ``dedup_wait`` drain disappears
entirely — block reads rely on the stores' disjoint-range concurrency
contract instead — so ``dedup_wait`` fires only at
checkpoint/level/stop drains (the on/off asymmetry is the gate's
phase-timer signature), and ``upload`` becomes the wait for an
already-staged buffer (a prefetch *hit* costs a swap; a *miss* pays
the old read+pad+h2d inline).  With device dedup
(``RAFT_TLA_DEVDEDUP``) a ``devdedup`` phase covers the per-segment
export-filter dispatch (ops/devdedup) — the on-device set membership
pass that shrinks the subsequent ``export`` wall.

**Thread attribution** (schema v8): phases recorded on a thread other
than the one that built the ``PhaseTimers`` accumulate under
``{name}@{thread-name}`` — a background flush shows up as
``dedup@raft-tla-flush``, never silently folded into (or racing with)
the main thread's bucket.  Accumulation is lock-protected so background
workers (flushq, prefetch) can time their own work.

**Span integration**: when a :class:`~raft_tla_tpu.obs.trace.SpanTracer`
is attached (``timers.tracer``, wired by ``RunTelemetry``), every
enabled phase handle also emits one v8 ``span`` event at exit — the same
named region lands in both the per-segment ``phase_s`` aggregate and the
merged trace timeline.  With tracing on but timers off the handle skips
``sync`` (no ``block_until_ready``), so spans record honest *host-side*
walls — dispatch time, not device time — and the engine pipelining the
timers would serialise stays intact.  The same holds for a tracer that
emits nothing and only feeds the ddd engines' pass ledger
(obs/passlog.py, always on): the phases the ledger reads (``upload``,
``expand``, ``dedup*``, a worker's ``prefetch``) are live without
``RAFT_TLA_PHASE_TIMERS`` and without a sync, every other phase stays the
null handle.

This module is host-path orchestration only — nothing here runs under
jit (the no-op handle is what jit-adjacent code touches).
"""

from __future__ import annotations

import os
import threading
import time

ENV_PHASE_TIMERS = "RAFT_TLA_PHASE_TIMERS"


class _NullPhase:
    """The disabled-path handle: a shared singleton that does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def sync(self, value=None):
        return value

    def set(self, **args):
        return self


_NULL = _NullPhase()


class _Phase:
    """An enabled timed region; ``sync(x)`` marks x to block on at exit."""

    __slots__ = ("_timers", "_name", "_t0", "_pending", "_span")

    def __init__(self, timers: "PhaseTimers", name: str):
        self._timers = timers
        self._name = name
        self._pending = None
        self._span = None

    def __enter__(self):
        tr = self._timers.tracer
        if tr is not None and tr.wants(self._name):
            self._span = tr.span(self._name).__enter__()
        self._t0 = time.monotonic()
        return self

    def sync(self, value=None):
        self._pending = value
        return value

    def set(self, **args):
        """Work counts for the phase's span (rows, bytes, keys); the
        ``phase_s`` bucket holds seconds only and ignores them."""
        if self._span is not None:
            self._span.set(**args)
        return self

    def __exit__(self, *exc):
        timers = self._timers
        if timers.enabled and self._pending is not None:
            import jax  # host path; deferred so obs imports stay light
            jax.block_until_ready(self._pending)
        self._pending = None
        if timers.enabled:
            dur = time.monotonic() - self._t0
            name = self._name
            if threading.get_ident() != timers._owner:
                # Explicit background-thread attribution: never race
                # with (or masquerade as) the owning thread's bucket.
                name = f"{name}@{threading.current_thread().name}"
            with timers._lock:
                acc = timers._acc
                acc[name] = acc.get(name, 0.0) + dur
        if self._span is not None:
            # Close after the sync so a timed phase's span covers the
            # same (device-honest) wall the phase_s bucket records.
            self._span.__exit__()
        return False


class PhaseTimers:
    """Per-phase wall-time accumulator; disabled unless asked for.

    ``tracer`` (attached by ``RunTelemetry``) piggybacks v8 trace spans
    on the same phase sites: the handle is live when *either* layer is
    on (or the tracer's sink reads the phase), but syncs (and
    accumulates) only when the timers are.
    """

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.tracer = None               # SpanTracer | None (NULL ok)
        self._acc: dict = {}
        self._lock = threading.Lock()
        self._owner = threading.get_ident()

    @classmethod
    def from_env(cls) -> "PhaseTimers":
        return cls(os.environ.get(ENV_PHASE_TIMERS, "").lower()
                   in ("1", "on", "true", "yes"))

    def phase(self, name: str):
        if not self.enabled:
            tr = self.tracer
            if tr is None or not tr.wants(name):
                return _NULL
        return _Phase(self, name)

    def snapshot(self, reset: bool = True) -> dict:
        """Drain accumulated per-phase walls (rounded; {} when disabled)."""
        with self._lock:
            if not self._acc:
                return {}
            out = {k: round(v, 4) for k, v in sorted(self._acc.items())}
            if reset:
                self._acc = {}
        return out
