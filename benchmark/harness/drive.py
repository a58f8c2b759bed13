"""Everything that touches the system under test: opening the device,
building the engine a cell names, driving passes through its public
``check()``, and feeding its compiled segment a seeded sample."""

from __future__ import annotations

import contextlib
import os
import shutil
import time

from benchmark.harness import correct
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


def check_config(cfg: dict):
    from raft_tla_tpu.config import Bounds, CheckConfig
    from raft_tla_tpu.utils import cfgparse
    tlc = cfgparse.parse_cfg(cfg["cfg_text"])
    b = cfg["bounds"]
    said = (len(tlc.server_names()), len(tlc.value_names()),
            sorted(tlc.invariants), sorted(tlc.symmetry))
    want = (b["n_servers"], b["n_values"], sorted(cfg["invariants"]),
            sorted(cfg["symmetry"]))
    if said != want:
        raise ValueError(f"config {cfg['name']}: cfg_text says {said}, the "
                         f"fields say {want}")
    return CheckConfig(bounds=Bounds(**b), spec=cfg["spec"],
                       invariants=tuple(cfg["invariants"]),
                       symmetry=tuple(cfg["symmetry"]), chunk=cfg["chunk"])


def build_engine(cfg: dict):
    """The ``ddd`` engine with the configuration's capacities for it.  Its
    constructor builds the jitted segment once."""
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    return DDDEngine(check_config(cfg),
                     DDDCapacities(**cfg["engine_caps"]["ddd"]))


class Driver:
    """One engine object, many passes over one pinned span."""

    def __init__(self, cell: dict, scratch: str):
        self.cell = cell
        self.cfg = cell["config_data"]
        self.traffic = cell["traffic_data"]
        self.pins = self.cfg["level_pins"]
        t = self.traffic
        self.a, self.b = t["start_level"], t["end_level"]
        count_a, count_b = self.pins[self.a], self.pins[self.b]
        if (t["count_at_start"], t["count_at_end"]) != (count_a, count_b):
            raise ValueError(
                f"traffic {cell['traffic']} pins {t['count_at_start']}/"
                f"{t['count_at_end']}, configuration {self.cfg['name']} has "
                f"{count_a}/{count_b} at levels {self.a}/{self.b}")
        self.orbits = count_b - count_a
        self.scratch = scratch
        self.compiles = CompileCounter()
        self.engine = build_engine(self.cfg)
        self._seg_chunks0 = self.engine.seg_chunks
        self.made = 0

    def run_pass(self, end_level: int | None = None, trace: bool = False,
                 start_level: int | None = None) -> passes.Pass:
        """One ``check()`` from Init, stopped losslessly once the record at
        ``end_level``'s pinned count is stamped.  ``trace``: the program's
        own spans go to an event log and the first level of the span runs
        under ``jax.profiler``."""
        import jax
        end = self.b if end_level is None else end_level
        start = self.a if start_level is None else start_level
        p = passes.Pass(index=self.made, t_call=0.0, traced=trace)
        self.made += 1
        kw, at_a, trace_end = {}, None, None
        env_trace = os.environ.get("RAFT_TLA_TRACE")
        if trace:
            pdir = os.path.join(self.scratch, f"pass{p.index}")
            shutil.rmtree(pdir, ignore_errors=True)
            os.makedirs(pdir)
            p.events = os.path.join(pdir, "run.events")
            p.trace_dir = os.path.join(pdir, "profile")
            kw["events"] = p.events
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

        # the capture is one level: a whole span is millions of op events,
        # and writing them out takes a minute
        clock = passes.SpanClock(p, self.pins, start, end, at_a, trace_end)
        # every pass starts from the same segment budget: check() leaves its
        # pacer's last budget on the object
        self.engine.seg_chunks = self._seg_chunks0
        n0 = self.compiles.n
        try:
            p.t_call = time.monotonic()
            result = self.engine.check(on_progress=clock, **kw)
        finally:
            if trace:
                if env_trace is None:
                    os.environ.pop("RAFT_TLA_TRACE", None)
                else:
                    os.environ["RAFT_TLA_TRACE"] = env_trace
                if p.t_a is not None and p.t_trace_end is None:
                    with contextlib.suppress(RuntimeError):
                        jax.profiler.stop_trace()    # never got that far
        passes.finish(p, result, self.pins, end)
        p.compiles = self.compiles.n - n0
        return p

    # -- after the window: the seeded sample and the planted fault ---------

    def expand_sample(self, parents: list) -> dict:
        """Feed ``parents`` (reference states) to the SAME compiled segment
        program the passes drove, as one frontier block behind an empty
        filter, and decode what it streams.  The program symbols used here
        are the benchmark's frozen interface (README, "What the benchmark
        holds the program to")."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from raft_tla_tpu.models import interp as pinterp
        from raft_tla_tpu.ops import state as st
        eng = self.engine
        n, P = len(parents), eng.schema.P
        block = eng.caps.block
        rows = np.zeros((block, P), np.int32)
        con = np.zeros((block,), bool)
        for k, s in enumerate(parents):
            ps = _program_state(s)
            rows[k] = eng.schema.pack(
                np.asarray(pinterp.to_vec(ps, eng.bounds), np.int32), np)
            con[k] = pinterp.constraint_ok(ps, eng.bounds)
        n_chunks = -(-n // eng.config.chunk)
        _fc, bufs, stats = eng._segment(
            eng._init_filter(), eng._make_bufs(), jnp.asarray(rows),
            jnp.asarray(con), jnp.int32(n_chunks), jnp.int32(n))
        st_h = jax.device_get(stats)
        bufs_h = jax.device_get(bufs)
        take = slice(0, int(st_h.cursor))
        states = []
        for row in bufs_h.orows[take]:
            vec = eng.schema.unpack(np.asarray(row), np)
            states.append(pinterp.from_struct(
                st.unpack(vec, eng.lay, np), eng.bounds))
        keys = (bufs_h.okey_hi[take].astype(np.uint64) << np.uint64(32)) \
            | bufs_h.okey_lo[take].astype(np.uint64)
        return {"states": states, "keys": keys,
                "con": [bool(c) for c in bufs_h.ocon[take]],
                "n_transitions": int(st_h.n_valid),
                "done": bool(st_h.done),
                "fail": int(st_h.fail) | int(st_h.viol_kind)}

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
        result = self.engine.check(init_override=_program_state(parent),
                                   on_progress=stop_after_one_level)
        v = result.violation
        return {"invariant": v.invariant if v else None,
                "state": v.state if v else None,
                "levels": list(result.levels)}


def _program_state(s):
    """A reference state as the program's PyState (same fields, two
    unrelated classes)."""
    from raft_tla_tpu.models import interp as pinterp
    return pinterp.PyState(**{f: getattr(s, f) for f in correct.STATE_FIELDS})


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
