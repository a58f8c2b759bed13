"""Everything that touches the system under test: opening the device,
building the engine a cell names, driving passes through its public
``check()``, and feeding its compiled segment a seeded sample.  What is
particular to a spec (its configuration for the program, the crossing of a
state, the row codec) is asked of the configuration's family
(``manifest.family``)."""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import threading
import time

from benchmark.harness import manifest as mf
from benchmark.harness import passes

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts JAX's backend-compile events (a persistent-cache hit fires it
    too: any program requested for the first time in this process)."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.n += 1


def open_device(chips: int, rehearsal: bool = False) -> dict:
    """The device as JAX reports it.  A measured run needs a TPU with at
    least ``chips`` chips and does not fall back; the selftest's rehearsal
    runs on whatever JAX_PLATFORMS names and says so."""
    from raft_tla_tpu.utils import device
    info = device.select_device()
    if rehearsal:
        return info
    if info["platform"] != "tpu":
        raise SystemExit(f"benchmark: platform is {info['platform']!r}, not "
                         "a TPU; nothing is measured off the chip")
    if info["count"] < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chip(s), "
                         f"JAX sees {info['count']}")
    return info


def enable_cache(platform: str):
    """The program's one rule for placing JAX's persistent compile cache
    (JAX_COMPILATION_CACHE_DIR wins, else <checkout>/.jax_cache)."""
    from raft_tla_tpu.serve.sched import enable_compile_cache
    return enable_compile_cache(platform=platform)


def build_engine(cfg: dict):
    """The engine the configuration names, with its capacities for it: the
    ``ddd`` engine on one device, or ``ddd-shard`` on a mesh of the
    configuration's ``devices`` (the constructors ``check.py`` uses), on
    the ``CheckConfig`` its family makes of it.  The constructor builds the
    jitted segment once."""
    name, devices = mf.engine_of(cfg)
    caps = cfg["engine_caps"][name]
    check_config = mf.family(cfg).check_config
    if name == "ddd-shard":
        from raft_tla_tpu.parallel.ddd_shard_engine import (
            DDDShardCapacities, DDDShardEngine)
        from raft_tla_tpu.parallel.shard_engine import make_mesh
        return DDDShardEngine(check_config(cfg), make_mesh(devices),
                              DDDShardCapacities(**caps))
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    return DDDEngine(check_config(cfg), DDDCapacities(**caps))


class CaptureCloser(threading.Thread):
    """Bounds a resumed pass's traced window by chunk steps.  The program
    writes a ``segment`` span to the pass's event log as it harvests each
    segment; this thread tails the log and, at the segment that brings the
    steps since the resume to ``steps``, stamps that segment's harvest as the
    window's end and stops the profiler (no progress record arrives inside a
    level at depth, so the pass's own thread has no place to do it)."""

    def __init__(self, p: passes.Pass, steps: int, stop_trace):
        super().__init__(name="bench-capture-closer", daemon=True)
        self.p, self.steps, self.stop_trace = p, steps, stop_trace
        self._over = threading.Event()
        self.error = None

    def run(self) -> None:
        seen, tail, pos = 0, "", 0
        while self.p.t_trace_end is None:
            over = self._over.is_set()      # read once more after the pass
            if os.path.exists(self.p.events):
                with open(self.p.events, encoding="utf-8") as f:
                    f.seek(pos)
                    tail += f.read()
                    pos = f.tell()
            *lines, tail = tail.split("\n")
            for line in lines:
                if '"segment"' not in line:
                    continue
                ev = json.loads(line)
                if ev.get("event") == "span" and ev["name"] == "segment" \
                        and not ev["args"].get("dropped"):
                    seen += ev["args"]["steps"]
                    if seen >= self.steps:
                        self.p.t_trace_end = ev["t0"] + ev["dur"]
                        try:
                            self.stop_trace()
                        except RuntimeError as e:
                            self.error = e
                        return
            if over:
                return
            self._over.wait(0.01)

    def finish(self) -> None:
        """After the pass: let the thread see the whole log, then wait."""
        self._over.set()
        self.join(timeout=120.0)
        if self.is_alive():
            raise SystemExit("benchmark: the capture never closed")
        if self.error is not None:
            raise self.error


class Driver:
    """One engine object, many passes over one pinned span."""

    def __init__(self, cell: dict, scratch: str):
        self.cell = cell
        self.cfg = cell["config_data"]
        self.family = mf.family(self.cfg)
        self.traffic = cell["traffic_data"]
        self.pins = self.cfg["level_pins"]
        t = self.traffic
        self.a, self.b = t["start_level"], t["end_level"]
        # where a pass starts: Init, or the run's own snapshot of level S
        start = t.get("start", "init")
        self.snapshot_level = None if start == "init" \
            else start["snapshot_level"]
        if self.snapshot_level not in (None, self.a):
            raise ValueError(
                f"traffic {cell['traffic']}: the span of a resumed pass "
                f"starts at its snapshot, level {self.snapshot_level}, "
                f"not at start_level {self.a}")
        self.engine_name, self.ndev = mf.engine_of(self.cfg, cell["chips"])
        # how a pass ends: stopped at B's pin, or left to its own end
        self.fixpoint = mf.end_of(t, self.cfg, cell["traffic"]) == "fixpoint"
        self._level_ahead = mf.ENGINES[self.engine_name]["record_level_ahead"]
        if self.snapshot_level is not None \
                and self.cfg["engine_caps"][self.engine_name].get(
                    "retention", "full") != "full":
            # frontier retention resumes in place: a resumed pass would
            # write its own stop over the snapshot the next one needs
            raise ValueError(
                f"traffic {cell['traffic']} resumes every pass from one "
                f"snapshot; configuration {self.cfg['name']} keeps its rows "
                "in level files that a resume rewrites (retention is not "
                "'full'): it would need one copy a pass")
        self.snapshot = None            # set by build_snapshot()
        # a configuration that states its Init: every warm and timed pass is
        # handed it through the public ``init_override``; one that states
        # none drives the calls it always drove
        self._from = {}
        init = self.family.stated_init(self.cfg)
        if init is not None:
            self._from["init_override"] = self.family.to_program(init)
        if self._from and self.snapshot_level is not None:
            raise ValueError(
                f"configuration {self.cfg['name']} states its Init and "
                f"traffic {cell['traffic']} resumes from a snapshot: no cell "
                "has driven a resume with init_override yet")
        count_a, count_b = self.pins[self.a], self.pins[self.b]
        if (t["count_at_start"], t["count_at_end"]) != (count_a, count_b):
            raise ValueError(
                f"traffic {cell['traffic']} pins {t['count_at_start']}/"
                f"{t['count_at_end']}, configuration {self.cfg['name']} has "
                f"{count_a}/{count_b} at levels {self.a}/{self.b}")
        self.orbits = count_b - count_a     # admitted in the clocked span
        self.pass_orbits = count_b          # ... and in a whole pass
        self.scratch = scratch
        self.compiles = CompileCounter()
        self.engine = build_engine(self.cfg)
        self._seg_chunks0 = self.engine.seg_chunks
        self.made = 0

    def build_snapshot(self) -> passes.Pass | None:
        """Set-up of a traffic with ``start.snapshot_level`` S: one pass
        from Init on the run's engine object through the public
        ``check(checkpoint=, checkpoint_every_s=inf)``, stopped at the
        boundary record of level S whose count equals the pin, so that the
        engine's own lossless stop writes the snapshot.  Built in every run
        (a kept one would be another commit's work).  What a resumed pass
        admits is the pin at B less the keys the snapshot holds, as the
        stopped pass's result counts them; never assumed to be the pin."""
        if self.snapshot_level is None:
            return None
        snap_dir = os.path.join(self.scratch, "snap")
        os.makedirs(snap_dir)
        path = os.path.join(snap_dir, "run")
        p = self.run_pass(end_level=self.snapshot_level, start_level=1,
                          checkpoint=path, checkpoint_every_s=float("inf"))
        self.snapshot = {
            "path": path, "level": self.snapshot_level, "keys": p.n_states,
            "build_s": p.t_return - p.t_call, "problem": p.problem,
            "bytes": sum(f.stat().st_size for f in os.scandir(snap_dir))}
        self.orbits = self.pass_orbits = self.pins[self.b] - p.n_states
        return p

    def timed_pass(self, trace: bool = False) -> passes.Pass:
        """One pass of the window: from Init, or resumed from the snapshot."""
        if self.snapshot is None:
            return self.run_pass(trace=trace)
        return self.run_pass(trace=trace, resume=self.snapshot["path"])

    def run_pass(self, end_level: int | None = None, trace: bool = False,
                 start_level: int | None = None,
                 **check_kw) -> passes.Pass:
        """One ``check()``, stopped losslessly once the record at
        ``end_level``'s pinned count is stamped; ``check_kw`` are further
        public arguments of it (``checkpoint=``, ``resume=``).  Where the
        traffic runs to the fixpoint, a pass to the traffic's own end (no
        ``end_level`` given) is stopped by nothing and held to the verdict;
        the warm pass names its level and is stopped there.  ``trace``:
        the program's own spans go to an event log and the first level of
        the span, or of a resumed pass its first ``passes.TRACED_STEPS``
        chunk steps, run under ``jax.profiler``.  A pass to or from a
        snapshot keeps an event log without spans: a resumed pass is clocked
        from its ``run_start``, and the one that writes the snapshot pays in
        set-up what the program's log does once a process."""
        import jax
        end = self.b if end_level is None else end_level
        start = self.a if start_level is None else start_level
        resumed = "resume" in check_kw
        fixpoint = self.fixpoint and end_level is None
        p = passes.Pass(index=self.made, t_call=0.0, traced=trace,
                        resumed=resumed)
        self.made += 1
        kw, at_a, trace_end, closer = dict(check_kw), None, None, None
        env_trace = os.environ.get("RAFT_TLA_TRACE")
        if trace or check_kw:
            pdir = os.path.join(self.scratch, f"pass{p.index}")
            shutil.rmtree(pdir, ignore_errors=True)
            os.makedirs(pdir)
            p.events = os.path.join(pdir, "run.events")
            kw["events"] = p.events
        if trace:
            p.trace_dir = os.path.join(pdir, "profile")
            os.environ["RAFT_TLA_TRACE"] = "1"
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host TraceMe only: the anchor
            opts.host_tracer_level = 1

            def at_a():
                jax.profiler.start_trace(p.trace_dir, profiler_options=opts)
                name = f"bench_anchor_{p.index}"
                with jax.profiler.TraceAnnotation(name):
                    p.anchor = (time.monotonic_ns(), name)

            def trace_end(now):
                p.t_trace_end = now
                jax.profiler.stop_trace()

            if resumed:
                # no record arrives at A: the capture opens before the call
                # (the resume itself runs nothing on the device) and is
                # closed by steps, from beside the pass
                at_a()
                at_a = trace_end = None
                closer = CaptureCloser(p, passes.TRACED_STEPS,
                                       jax.profiler.stop_trace)

        # the capture is one level: a whole span is millions of op events,
        # and writing them out takes a minute
        clock = passes.SpanClock(p, self.pins, start, end, at_a, trace_end,
                                 level_ahead=self._level_ahead,
                                 fixpoint=fixpoint)
        # every pass starts from the same segment budget: check() leaves its
        # pacer's last budget on the object
        self.engine.seg_chunks = self._seg_chunks0
        n0 = self.compiles.n
        try:
            if closer is not None:
                closer.start()
            p.t_call = time.monotonic()
            result = self.engine.check(on_progress=clock, **self._from,
                                       **kw)
        finally:
            if trace:
                if env_trace is None:
                    os.environ.pop("RAFT_TLA_TRACE", None)
                else:
                    os.environ["RAFT_TLA_TRACE"] = env_trace
                if closer is not None:
                    closer.finish()
                if p.anchor is not None and p.t_trace_end is None:
                    with contextlib.suppress(RuntimeError):
                        jax.profiler.stop_trace()    # never got that far
                    if closer is not None:
                        # a pass shorter than the step bound: all of it
                        p.t_trace_end = p.t_b
        passes.finish(p, result, self.pins, end, fixpoint=fixpoint)
        p.compiles = self.compiles.n - n0
        return p

    # -- after the window: the seeded sample and the planted fault ---------

    def expand_sample(self, parents: list) -> dict:
        """Feed ``parents`` (reference states) to the SAME compiled segment
        program the passes drove, as one frontier block (on the mesh: one
        window, dealt to the shards by the engine's own upload) behind an
        empty filter, and decode what it streams back into reference
        states.  The family packs and decodes; the engine privates used
        below are the benchmark's frozen interface (README, "What the
        benchmark holds the program to")."""
        import numpy as np
        rows, con = self.family.pack_rows(self.engine, parents)
        n0 = self.compiles.n
        got = (self._stream_mesh if self.engine_name == "ddd-shard"
               else self._stream_ddd)(rows, con)
        got["states"] = self.family.decode_rows(self.engine,
                                                got.pop("orows"))
        got["keys"] = (got.pop("key_hi").astype(np.uint64) << np.uint64(32)) \
            | got.pop("key_lo").astype(np.uint64)
        got["compiles"] = self.compiles.n - n0
        return got

    def _stream_ddd(self, rows, con) -> dict:
        """One frontier block through ``DDDEngine._segment``."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        eng = self.engine
        n, block = len(rows), eng.caps.block
        brows = np.zeros((block, rows.shape[1]), np.int32)
        bcon = np.zeros((block,), bool)
        brows[:n], bcon[:n] = rows, con
        n_chunks = -(-n // eng.config.chunk)
        _fc, bufs, stats = eng._segment(
            eng._init_filter(), eng._make_bufs(), jnp.asarray(brows),
            jnp.asarray(bcon), jnp.int32(n_chunks), jnp.int32(n))
        st_h = jax.device_get(stats)
        bufs_h = jax.device_get(bufs)
        take = slice(0, int(st_h.cursor))
        return {"orows": bufs_h.orows[take], "key_hi": bufs_h.okey_hi[take],
                "key_lo": bufs_h.okey_lo[take],
                "con": [bool(c) for c in bufs_h.ocon[take]],
                "n_transitions": int(st_h.n_valid),
                "done": bool(st_h.done),
                "fail": int(st_h.fail) | int(st_h.viol_kind)}

    def _stream_mesh(self, rows, con) -> dict:
        """One window through ``DDDShardEngine._segment`` under its
        ``shard_map``: the engine's own ``_upload_window`` deals the rows to
        the shards (from two stand-in stores that hold nothing else), every
        shard starts behind an empty filter, and the segment is dispatched
        as the window loop dispatches it until it reports the window done.
        The shards' streams are taken shard by shard, as the harvest takes
        them; ``misrouted`` counts streamed keys on a shard that does not
        own them (the engine's owner map: ``key_hi % ndev``)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        eng = self.engine
        nd, ocap = eng.ndev, eng.caps.seg_rows
        fbuf, fcon, fpar, nrows, n_chunks = eng._upload_window(
            _Rows(rows), _Rows(con.astype(np.int32)[:, None]), 0, len(rows))
        fc, bufs = eng._init_filter(), eng._make_bufs()
        out = {k: [] for k in ("orows", "key_hi", "key_lo", "con")}
        n_trans = fail = misrouted = 0
        done = False
        for _ in range(n_chunks + 1):       # a segment runs >= 1 chunk
            fc, bufs, stats = eng._segment(
                fc, bufs, fbuf, fcon, fpar, nrows,
                jnp.int32(self._seg_chunks0), jnp.int32(n_chunks))
            st_h = jax.device_get(stats)
            bufs_h = jax.device_get(bufs)
            cursors = np.asarray(st_h.cursor)
            for s in range(nd):
                take = slice(s * ocap, s * ocap + int(cursors[s]))
                hi = bufs_h.okey_hi[take]
                misrouted += int(np.sum(hi % np.uint32(nd) != s))
                out["orows"].append(bufs_h.orows[take])
                out["key_hi"].append(hi)
                out["key_lo"].append(bufs_h.okey_lo[take])
                out["con"].append(bufs_h.ocon[take])
            n_trans += int(np.asarray(st_h.n_valid).sum())
            fail |= int(np.bitwise_or.reduce(np.asarray(st_h.fail))) \
                | int((np.asarray(st_h.viol_pos) >= 0).any()) \
                | int((np.asarray(st_h.dead_g) >= 0).any())
            done = bool(st_h.done)
            if done or fail:
                break
        got = {k: np.concatenate(v) for k, v in out.items()}
        got["con"] = [bool(c) for c in got["con"]]
        got.update(n_transitions=n_trans, done=done, fail=fail,
                   misrouted=misrouted, shards=nd)
        return got

    def planted_violation(self, parent) -> dict:
        """One ``check()`` of the run's engine object from ``parent`` (a
        reference state that holds every invariant and has a successor that
        does not), through the public ``init_override``: the compiled
        segment has to flag the violator at the first level.  Stopped at
        the first level boundary if it flags nothing."""
        import signal

        def stop_after_one_level(rec):
            if rec["level"] >= 1:
                signal.raise_signal(signal.SIGINT)

        self.engine.seg_chunks = self._seg_chunks0
        result = self.engine.check(
            init_override=self.family.to_program(parent),
            on_progress=stop_after_one_level)
        v = result.violation
        return {"invariant": v.invariant if v else None,
                "state": self.family.from_program(v.state) if v else None,
                "levels": list(result.levels)}


class _Rows:
    """A stand-in row store for ``_upload_window``: ``read(base, n)``."""

    def __init__(self, a):
        self.a = a

    def read(self, base: int, n: int):
        return self.a[base:base + n]


def memory_peak_bytes() -> int:
    """Peak on the fullest chip, where the backend reports it."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def scratch_dir(workload: str) -> str:
    """``.bench_scratch/<workload>`` in the checkout, emptied; TMPDIR points
    into it so the engine's frontier level files land there too."""
    d = os.path.join(mf.SCRATCH, workload)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(os.path.join(d, "tmp"))
    os.environ["TMPDIR"] = os.path.join(d, "tmp")
    return d
