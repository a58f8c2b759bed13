"""Cross-process trace spans — the schema-v8 profiling layer.

The obs event logs (events.py) record *what happened* per process; this
module records *where the wall went*: nested, thread-attributed spans
emitted as schema-v8 ``span`` events through the same JSONL sinks, so a
supervised pool run (supervisor + N serve children + per-process
background threads) collects into one merged timeline (obs/collect.py)
that Perfetto can render (obs/perfetto.py) and ``raft-tla-trace report``
can attribute.

Design points, in the order they matter:

- **Off by default, off path unmeasurable.**  Tracing is gated by
  ``--trace`` / ``RAFT_TLA_TRACE``.  Disabled, every instrumentation
  site touches :data:`NULL_TRACER`, whose ``span()`` returns one shared
  stateless handle — no allocation, no clock read, nothing enqueued —
  the same discipline as ``PhaseTimers``'s null handle (A/B'd by the
  ``runs/obs_overhead_ab.py`` protocol; ``bench.py`` pins the per-call
  cost as the ``trace_emit_overhead_us`` fiducial).
- **One set of sites, two sinks.**  A tracer may also carry a ``sink``
  (the ddd engines' pass ledger, obs/passlog.py): the spans whose names
  the sink reads are then timed whether events are emitted or not, and
  handed to it with the very ``t0`` and ``dur`` an emitted span carries.
  ``enabled`` still says only whether ``span`` events are emitted;
  :meth:`SpanTracer.wants` says whether a site's span goes anywhere.
- **Monotonic timestamps + a wall anchor.**  Span ``t0`` is
  ``time.monotonic()`` in the emitting process (immune to NTP steps
  mid-run); each process stamps one wall/monotonic :func:`clock_anchor`
  pair into its ``run_start`` so the collector can place every process's
  spans on one wall-clock axis, with the alignment error bounded by the
  recorded ``err_s`` (the width of the anchor's wall read).
- **Thread-aware context.**  Every span records the emitting thread's
  name, and parenthood nests per thread via a thread-local stack — a
  flush running on ``raft-tla-flush`` is attributed to that track, never
  folded into the main thread's phase (the PhaseTimers bug this PR
  fixes).  :meth:`SpanTracer.emit_span` additionally places *manual*
  spans on synthetic tracks (``thread="tickets"``/``"workers"``) for
  lifetimes that start and end in different stack frames (dispatch
  tickets, pool worker lifetimes).
- **A causal tree with work counts.**  A span's ``parent_id`` is the
  innermost span open on its thread, and ``set(**args)`` attaches how
  much work the region held (rows, bytes, steps, keys), so a reader can
  compute self time (duration less what the children cover) and rates
  without a second source.  The ddd engines open ``pass`` > ``level`` >
  ``upload`` / ``expand`` / ``export`` > {``segment_wait``, ``d2h``} /
  ``level_close``; :meth:`SpanTracer.open` gives the explicit
  open/close handle for the level loop, whose body is too long to
  re-indent.
- **One clock with the profiler.**  A tracer built with an ``annotate``
  factory (``jax.profiler.TraceAnnotation``; ``RunTelemetry`` hands it
  in once the process has opened a backend — this module never imports
  JAX, supervisors import it) opens each span as a host annotation of
  the same name and ``span_id`` too: a ``jax.profiler`` capture taken
  while tracing is on holds the program's spans, from every thread, on
  the profiler's own clock beside the device ops.
- **One sink, no new I/O machinery.**  Spans ride the existing
  non-blocking ``EventLog`` (engines: ``tracer = SpanTracer(log.emit)``)
  or the synchronous validated ``append_event`` (supervisors, low rate),
  so `tel.active`'s no-listener fast path and the crash-attribution
  contract (log without ``run_end`` = death) are untouched.
"""

from __future__ import annotations

import itertools
import os
import threading
import time

ENV_TRACE = "RAFT_TLA_TRACE"


def trace_enabled(env: str | None = None) -> bool:
    """The ``--trace`` / ``RAFT_TLA_TRACE`` gate (default: off)."""
    v = (env if env is not None
         else os.environ.get(ENV_TRACE, "")).strip().lower()
    return v in ("1", "on", "true", "yes")


def clock_anchor() -> dict:
    """One wall/monotonic pair: ``wall`` was read between two monotonic
    reads whose spread is ``err_s`` — the bound on how precisely this
    process's monotonic span timestamps can be placed on the wall axis
    (plus whatever NTP skew separates the hosts, which no process can
    observe alone)."""
    m1 = time.monotonic()
    wall = time.time()
    m2 = time.monotonic()
    return {"wall": round(wall, 6), "mono": round((m1 + m2) / 2.0, 6),
            "err_s": round(m2 - m1, 6)}


def host_context() -> dict:
    """Where a log's process ran: nproc always; jax version and the
    device (platform / kind / count, as utils.device reports them) only
    when this process has ALREADY opened a backend.  Never opens one:
    the supervising parents (campaign, serve pool, bench) stamp their
    logs through here too, and a parent that touched the device would
    hold the chip against its own children."""
    from raft_tla_tpu.utils import device
    ctx: dict = {"nproc": os.cpu_count() or 1}
    if device.backends_initialized():
        import jax
        ctx["jax"] = str(jax.__version__)
        ctx.update(device.device_info())
    return ctx


class _NullSpan:
    """The disabled-path handle: a shared singleton that does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        return self

    def close(self):
        pass


_NULL_SPAN = _NullSpan()


class NullTracer:
    """The disabled tracer: every method is a no-op returning the shared
    null span, so instrumentation sites need no ``if`` guards."""

    __slots__ = ()
    enabled = False
    sink = None

    def wants(self, name: str) -> bool:
        return False

    def span(self, name: str, **args):
        return _NULL_SPAN

    def open(self, name: str, **args):
        return _NULL_SPAN

    def emit_span(self, name: str, t0: float, dur: float,
                  thread: str | None = None, **args) -> None:
        pass

    def current_id(self):
        return None


NULL_TRACER = NullTracer()


class _Span:
    """An open traced region; emitted as one ``span`` event at exit.

    Used as a context manager, or — for a region whose body is too long
    to re-indent (the ddd engines' per-level loop) — through
    :meth:`SpanTracer.open` and :meth:`close`, which push and pop the
    same per-thread parent stack.  ``close`` is idempotent, so a region
    with several exits can close at each and once more after them."""

    __slots__ = ("_tr", "_name", "_args", "_id", "_parent", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict):
        self._tr = tracer
        self._name = name
        self._args = args
        self._t0 = None

    def __enter__(self):
        tr = self._tr
        self._ann = None
        if not tr.enabled:               # timed for the sink alone: no
            self._t0 = time.monotonic()  # id, no parent, no annotation
            return self
        stack = tr._stack()
        self._parent = stack[-1] if stack else None
        self._id = next(tr._ids)
        stack.append(self._id)
        ann = tr.annotate
        self._t0 = time.monotonic()
        # the profiler's own clock: the same region as a TraceMe in any
        # capture that is running (a flag test when none is)
        if ann is not None:
            self._ann = ann(self._name, span_id=self._id)
            self._ann.__enter__()
        return self

    def set(self, **args):
        """Attach result attributes discovered inside the region (row
        counts, hit/miss) — lands in the event's ``args`` dict.  A no-op
        once the span has closed: the event is with the sink then."""
        if self._t0 is not None:
            self._args.update(args)
        return self

    def close(self):
        self.__exit__(None, None, None)

    def __exit__(self, *exc):
        if self._t0 is None:             # never opened, or closed already
            return False
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        t0, self._t0 = self._t0, None
        dur = time.monotonic() - t0
        tr = self._tr
        if tr.feeds(self._name):
            tr.sink.closed(self._name, t0, dur, self._args)
        if tr.enabled:
            stack = tr._stack()
            if stack and stack[-1] == self._id:
                stack.pop()
            fields = {"name": self._name, "span_id": self._id,
                      "t0": round(t0, 6), "dur": round(dur, 6),
                      "thread": threading.current_thread().name}
            if self._parent is not None:
                fields["parent_id"] = self._parent
            if self._args:
                fields["args"] = self._args
            tr._emit("span", **fields)
        return False


class SpanTracer:
    """Emit nested, thread-attributed ``span`` events through ``emit``.

    ``emit`` is any ``(event_type, **fields) -> ...`` callable — an
    ``EventLog.emit`` bound method (non-blocking; engines) or a
    ``functools.partial(append_event, path)`` (synchronous + validated;
    supervisors).  Span ids are unique per tracer; parenthood nests via
    a per-thread stack, so concurrent threads trace independently.

    ``annotate`` (``jax.profiler.TraceAnnotation``, handed in by whoever
    builds the tracer in a process that has opened a backend — this
    module never imports JAX) opens each span a second time as a host
    annotation named like it, with its ``span_id``: a ``jax.profiler``
    capture then holds the program's spans of every thread on the
    profiler's own clock, beside the device ops.

    ``sink`` (an ``obs.passlog.PassLog``) is told of every span whose
    name is in its ``NAMES``: ``opened(name, t0)`` for the explicit
    handles of :meth:`open`, ``closed(name, t0, dur, args)`` for all.
    ``emit`` may then be ``None``: the tracer times those spans for the
    sink alone, emits nothing, and every other site gets the null span.
    """

    def __init__(self, emit, annotate=None, sink=None):
        self._emit = emit
        self.enabled = emit is not None  # ``span`` events are emitted
        self.sink = sink
        self.annotate = annotate         # set once, before any worker runs
        self._ids = itertools.count(1)   # CPython-atomic __next__
        self._tls = threading.local()

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def feeds(self, name: str) -> bool:
        """Whether the sink reads spans of this name."""
        return self.sink is not None and name in self.sink.NAMES

    def wants(self, name: str) -> bool:
        """Whether a span of this name goes anywhere: to the event log,
        or to a sink that reads the name."""
        return self.enabled or self.feeds(name)

    def span(self, name: str, **args):
        """Context manager for a region on the current thread."""
        return _Span(self, name, args) if self.wants(name) else _NULL_SPAN

    def open(self, name: str, **args):
        """The same region as an explicit handle, already entered: pair
        with ``handle.close()`` on the same thread."""
        if not self.wants(name):
            return _NULL_SPAN
        sp = _Span(self, name, args).__enter__()
        if self.feeds(name):
            self.sink.opened(name, sp._t0)
        return sp

    def emit_span(self, name: str, t0: float, dur: float,
                  thread: str | None = None, **args) -> None:
        """Manual span for lifetimes that open and close in different
        stack frames (dispatch tickets, worker lifetimes).  ``thread``
        names the track — pass a synthetic one (``"tickets"``) when the
        span overlaps the emitting thread's nested spans, so renderers
        that require proper nesting per track stay happy."""
        if not self.enabled:
            return
        fields = {"name": name, "span_id": next(self._ids),
                  "t0": round(t0, 6), "dur": round(max(0.0, dur), 6),
                  "thread": thread or threading.current_thread().name}
        if args:
            fields["args"] = args
        self._emit("span", **fields)

    def current_id(self):
        """Id of the innermost open span on this thread (or None)."""
        st = self._stack()
        return st[-1] if st else None


def tracer_for(log_path: str) -> SpanTracer:
    """A tracer whose spans append synchronously (validated) to
    ``log_path`` — the supervisor-side sink (low event rate)."""
    import functools

    from raft_tla_tpu.obs.events import append_event
    return SpanTracer(functools.partial(append_event, log_path))


def anchored_run_start(log_path: str, engine: str) -> dict:
    """Append the minimal ``run_start`` that makes a supervisor-side log
    (pool.events / supervisor.events / sched-*.events) alignable: the
    clock anchor, host context and pid.  Engine logs get theirs through
    ``RunTelemetry.run_start`` instead."""
    from raft_tla_tpu.obs.events import append_event
    return append_event(log_path, "run_start", engine=engine,
                        universe={}, spec="", invariants=[],
                        resumed=False, pid=os.getpid(),
                        anchor=clock_anchor(), host=host_context())
