"""The pass ledger — every ``check()`` of the ddd engines keeps its own
level-by-level account, traced or not.

The span tracer (obs/trace.py) says where the wall went, and is off unless
``RAFT_TLA_TRACE=1``; this is the part of that account that is always on,
built like the compile ledger (obs/compiles.py): one process-global
:data:`LEDGER`, bounded (the last :data:`KEEP` passes), read with
:func:`snapshot`, monotonic ``t0``s.  It adds no site to the engines: a
:class:`PassLog` is the *sink* of the run's ``SpanTracer`` — the one sink
when spans are off, a second one when they are on — so the sites that exist
(``tr.open("pass")``, ``tr.open("level")``, ``tr.span(...)``,
``tel.phases.phase(...)``, ``end_level()``) feed it with the same clock reads
that make a span, and a traced and an untraced pass are accounted by the same
lines of code.  No flag, no environment variable.

One record a ``check()``::

    {"t0", "wall_s", "engine", "resumed", "stopped_by", "n_states",
     "head_s", "tail_s", "levels": [...], "stalls": [...],
     "threads": {"dedup@raft-tla-flush": s, "prefetch@raft-tla-prefetch": s}}

``t0`` is the call, in ``time.monotonic()`` seconds (the clock of the spans
and, through ``run_start.anchor``, of a profiler capture); ``head_s`` is the
call -> the first level opens (a resumed pass: the resume), ``tail_s`` the
last level's close -> the ``pass`` span closes.  One entry a level, in the
names the ``level`` span uses::

    {"level", "t0", "gap_s", "wall_s", "rows", "row_words", "blocks",
     "segments", "steps", "streamed_rows", "new_states", "upload_s",
     "uploads", "upload_bytes", "upload_pieces", "d2h_bytes", "expand_s",
     "wait_s", "d2h_s", "dedup_s",
     "close_s", "cpu_s", "gc_s", "majflt", "nivcsw"}

``wall_s`` is the ``level`` span's own ``dur``; ``gap_s`` is the previous
level's close -> this one's open (0 for the first: that is the head), which
holds the loop's own turn-around and the ledger's bookkeeping.  All of them
come from one chain of stamps (the call, each level's open and close, the
pass span's close), so ``head_s`` + the sum of ``gap_s + wall_s`` +
``tail_s`` is ``wall_s`` of the pass by construction, whatever the host was
doing.
``upload_s`` .. ``close_s`` are main-thread wall inside the ``upload``,
``expand``, ``segment_wait``, ``d2h``, ``dedup`` + ``dedup_wait`` +
``dedup_submit`` and ``level_close`` seams (``level_close`` holds the
``dedup`` at a level's end, so the two overlap); ``uploads`` counts the
level's ``upload`` spans and ``upload_bytes`` / ``upload_pieces`` sum their
``bytes`` and ``pieces`` (what the ddd engine really sent, and in how many
transfers; 0 where an engine's span does not say) and ``d2h_bytes`` sums the
``bytes`` of its ``d2h`` spans (what a harvest really fetched: on the mesh
the head of each shard's buffers, or the whole buffers when a cursor outgrew
it); ``cpu_s`` is the main
thread's CPU time over the level, ``majflt`` / ``nivcsw`` its major faults
and involuntary switches — all three from one ``getrusage(RUSAGE_THREAD)``
at each end of the level, so ``cpu_s`` is as fine as the kernel accounts a
thread (microseconds in most places; 10 ms ticks on the v5e machines' host,
where a level of 23-39 ms then reads 0, 10 or 20 ms and only sums over many
levels mean anything) — and ``gc_s`` the collector's time inside it.  A
level whose wall is 2 s with ``cpu_s`` 0.03 was blocked, and the seam says on
what; with ``cpu_s`` 2 it was computing.  A seam closed on another thread
lands in ``threads`` under ``name@thread``, never in a level.

``stalls`` is what an operator is told without asking: the levels (and
``head`` / ``tail``) of this pass whose wall exceeds, by more than
max(:data:`STALL_FLOOR_S`, m), the median m of the same level — same engine,
same start, same ``rows`` — over the last :data:`BASELINE` passes the ledger
holds.  Each is also one line on stderr, with every seam, as the pass
returns: a stall is rare, falls where nobody traces, and is gone by the time
anyone asks.  A first pass has nothing to be held against and reads ``[]``.

Host path only; imports nothing of JAX.
"""

from __future__ import annotations

import collections
import gc
import resource
import statistics
import sys
import threading
import time

KEEP = 64
# a level is a stall when it ran longer than the median of the same level in
# the last BASELINE passes by more than max(STALL_FLOOR_S, that median)
STALL_FLOOR_S = 0.25
BASELINE = 8

# main-thread seam (a span's name) -> the level field its wall is added to
SEAMS = {"upload": "upload_s", "expand": "expand_s",
         "segment_wait": "wait_s", "d2h": "d2h_s", "dedup": "dedup_s",
         "dedup_wait": "dedup_s", "dedup_submit": "dedup_s",
         "level_close": "close_s"}
# the seam fields of a level's entry, in the order a report prints them
SEAM_FIELDS = tuple(dict.fromkeys(SEAMS.values()))
# every span name the ledger reads: the seams, the two explicit handles and
# the prefetcher's stage (a worker's: it lands in ``threads``)
NAMES = frozenset(SEAMS) | {"pass", "level", "prefetch"}
_COUNTS = ("level", "rows", "row_words", "blocks", "segments", "steps",
           "streamed_rows", "new_states")
# beside the seams, what a stall's line says of its level
_SUSPECTS = ("uploads", "upload_bytes", "upload_pieces", "d2h_bytes", "cpu_s",
             "gc_s", "majflt", "nivcsw")

_RUSAGE = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)
# the collector's clock: [seconds collecting so far, start of the open one]
_gc = [0.0, 0.0]
_gc_lock = threading.Lock()
_gc_installed = False


def _on_gc(phase: str, _info: dict) -> None:
    if phase == "start":
        _gc[1] = time.monotonic()
    else:
        _gc[0] += time.monotonic() - _gc[1]


def _install_gc() -> None:
    global _gc_installed
    with _gc_lock:
        if not _gc_installed:
            gc.callbacks.append(_on_gc)
            _gc_installed = True


def _counters() -> tuple:
    ru = resource.getrusage(_RUSAGE)
    return ru.ru_utime + ru.ru_stime, _gc[0], ru.ru_majflt, ru.ru_nivcsw


class PassLedger:
    """The finished records, oldest first; its own class so a test can
    fill one without running an engine."""

    def __init__(self, keep: int = KEEP):
        self._lock = threading.Lock()
        self._records: collections.deque = collections.deque(maxlen=keep)
        self._n_recorded = 0

    def add(self, record: dict) -> list:
        """Keep ``record``, first holding it against the passes before it
        (``record["stalls"]``); returns those stalls."""
        with self._lock:
            found = record["stalls"] = _stalls(record, self._records)
            self._records.append(record)
            self._n_recorded += 1
        return found

    def snapshot(self) -> dict:
        """Copies of the records still held; ``dropped`` says how many
        the bound pushed out."""
        with self._lock:
            records = [{**r, "levels": [dict(lv) for lv in r["levels"]],
                        "stalls": [dict(st) for st in r["stalls"]],
                        "threads": dict(r["threads"])}
                       for r in self._records]
            dropped = self._n_recorded - len(records)
        return {"records": records, "dropped": dropped}


def _parts(rec: dict) -> dict:
    """``{key: (wall, entry)}`` of a record's head, levels and tail, keyed
    so that equal keys of two passes name the same work: a level by its
    number and rows, the head by the first level, the tail by the last."""
    levels = rec["levels"]
    out = {(lv["level"], lv["rows"]): (lv["wall_s"], lv) for lv in levels}
    if levels:
        first, last = levels[0], levels[-1]
        out["head", first["level"], first["rows"]] = (rec["head_s"], None)
        out["tail", last["level"], last["rows"], rec["stopped_by"]] = (
            rec["tail_s"], None)
    return out


def _stalls(rec: dict, earlier) -> list:
    """The parts of ``rec`` that ran longer than the median of the same
    part in the last :data:`BASELINE` passes of ``earlier`` (same engine,
    same start) by more than max(:data:`STALL_FLOOR_S`, that median)."""
    mine = {key: part for key, part in _parts(rec).items()
            if part[0] > STALL_FLOOR_S}
    if not mine:
        return []
    base = [_parts(r) for r in list(earlier)[-BASELINE:]
            if (r["engine"], r["resumed"]) == (rec["engine"], rec["resumed"])]
    found = []
    for key, (wall, lv) in mine.items():
        seen = [b[key][0] for b in base if key in b]
        if seen and wall - (med := statistics.median(seen)) \
                > max(STALL_FLOOR_S, med):
            found.append({
                "level": key[0], "wall_s": wall, "median_s": med,
                "passes": len(seen),
                **({} if lv is None else
                   {k: lv[k] for k in SEAM_FIELDS + _SUSPECTS
                    if k in lv})})       # a record from before a field
    return found


def stall_line(rec: dict, st: dict) -> str:
    """One stall of ``rec`` as the line stderr gets."""
    of_level = "cpu_s" in st
    line = (f"raft-tla pass ledger: stall in the {rec['engine']} pass at "
            f"t0={rec['t0']:.3f}, {'level ' * of_level}{st['level']}: wall "
            f"{st['wall_s']:.3f}s against a median of {st['median_s']:.3f}s "
            f"over the last {st['passes']} passes")
    if of_level:
        line += ": " + " ".join(
            f"{k} {st[k]:.3f}" if isinstance(st[k], float)
            else f"{k} {st[k]}" for k in SEAM_FIELDS + _SUSPECTS
            if k in st)
    return line


LEDGER = PassLedger()
snapshot = LEDGER.snapshot


class PassLog:
    """One ``check()``'s record in the making: the sink of its tracer
    (``SpanTracer(emit, sink=...)``), which calls :meth:`opened` for the
    explicit handles and :meth:`closed` for every span whose name is in
    :data:`NAMES`.  Built on the thread that runs the level loop; spans of
    other threads are attributed to them."""

    NAMES = NAMES

    def __init__(self, engine: str, resumed: bool, t0: float,
                 ledger: PassLedger = LEDGER):
        _install_gc()
        self._ledger = ledger
        self._owner = threading.get_ident()
        self._lock = threading.Lock()    # guards record["threads"], _done
        self._done = False
        self._cur: dict | None = None    # the open level's entry
        self._base = ()                  # _counters() at its open
        self._t_end = t0                 # where the last level closed
        self.record = {
            "t0": t0, "wall_s": None, "engine": engine,
            "resumed": bool(resumed), "stopped_by": None, "n_states": None,
            "head_s": None, "tail_s": None, "levels": [], "stalls": [],
            "threads": {}}

    def opened(self, name: str, t0: float) -> None:
        if name != "level" or threading.get_ident() != self._owner:
            return
        gap = t0 - self._t_end
        if self.record["head_s"] is None:
            self.record["head_s"], gap = gap, 0.0
        self._cur = {"level": None, "t0": t0, "gap_s": gap, "wall_s": None,
                     **dict.fromkeys(_COUNTS[1:], 0), "uploads": 0,
                     "upload_bytes": 0, "upload_pieces": 0, "d2h_bytes": 0,
                     **dict.fromkeys(SEAM_FIELDS, 0.0)}
        self._base = _counters()

    def closed(self, name: str, t0: float, dur: float, args: dict) -> None:
        if threading.get_ident() != self._owner:
            key = f"{name}@{threading.current_thread().name}"
            with self._lock:
                if not self._done:
                    th = self.record["threads"]
                    th[key] = th.get(key, 0.0) + dur
            return
        field = SEAMS.get(name)
        if field is not None:
            cur = self._cur
            if cur is not None:          # a seam of the head or the tail
                cur[field] += dur        # is in head_s / tail_s alone
                if name == "upload":
                    cur["uploads"] += 1
                    cur["upload_bytes"] += args.get("bytes", 0)
                    cur["upload_pieces"] += args.get("pieces", 0)
                elif name == "d2h":
                    cur["d2h_bytes"] += args.get("bytes", 0)
        elif name == "level":
            self._close_level(t0, dur, args)
        elif name == "pass":
            self._close_pass(t0 + dur, args)

    def _close_level(self, t0: float, dur: float, args: dict) -> None:
        cur, self._cur = self._cur, None
        if cur is None:
            return
        cur["wall_s"] = dur
        for key in _COUNTS:
            if key in args:
                cur[key] = args[key]
        cpu, gcs, majflt, nivcsw = _counters()
        base = self._base
        cur["cpu_s"] = cpu - base[0]
        cur["gc_s"] = gcs - base[1]
        cur["majflt"] = majflt - base[2]
        cur["nivcsw"] = nivcsw - base[3]
        self.record["levels"].append(cur)
        self._t_end = t0 + dur

    def _close_pass(self, t_end: float, args: dict) -> None:
        rec = self.record
        rec["wall_s"] = t_end - rec["t0"]
        if rec["head_s"] is None:        # no level opened: all of it head
            rec["head_s"], self._t_end = rec["wall_s"], t_end
        rec["tail_s"] = t_end - self._t_end
        rec["stopped_by"] = args.get("stopped_by")
        rec["n_states"] = args.get("n_states")
        for key in ("elections_peak",      # faithful mode alone
                    "scan_moved_fields"):  # under SYMMETRY alone
            if key in args:
                rec[key] = args[key]
        with self._lock:                 # a worker's late seam is dropped
            self._done = True
        for st in self._ledger.add(rec):
            print(stall_line(rec, st), file=sys.stderr, flush=True)


def rounded(record: dict, ndigits: int = 6) -> dict:
    """The record with its seconds rounded, for an event line."""
    def rnd(d: dict) -> dict:
        return {k: round(v, ndigits) if isinstance(v, float) else v
                for k, v in d.items()}
    return {**rnd(record), "levels": [rnd(lv) for lv in record["levels"]],
            "stalls": [rnd(st) for st in record["stalls"]],
            "threads": rnd(record["threads"])}
