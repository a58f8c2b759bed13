"""The controls: one stated guarantee broken underneath the timed path, from
outside the program (nothing of raft_tla_tpu is edited; these patch it in the
benchmark's own process).  ``correct`` has to come out false under each:
benchmark/control.py shows it on the chip at a cell's own size,
benchmark/selftest.py at toy size.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def short_keys(bits: int = 32):
    """Guarantee broken: exact dedup on the 64-bit key.  Every step program
    built inside emits keys cut to their low ``bits`` bits."""
    import jax.numpy as jnp
    from raft_tla_tpu.ops import kernels
    real = kernels.build_step

    def build_step(*args, **kw):
        step = real(*args, **kw)
        lo_mask = jnp.uint32((1 << min(bits, 32)) - 1)
        hi_mask = jnp.uint32((1 << max(bits - 32, 0)) - 1)

        def cut(vecs):
            out = dict(step(vecs))
            out["fp_hi"] = out["fp_hi"] & hi_mask
            out["fp_lo"] = out["fp_lo"] & lo_mask
            return out
        return cut

    kernels.build_step = build_step
    try:
        yield
    finally:
        kernels.build_step = real


class _AdmitAll:
    """A host key set that forgets: every streamed candidate is 'new', so
    the only dedup left is the device's lossy filter."""

    def __init__(self, n: int = 0):
        self.n = n

    def __len__(self):
        return self.n

    def seed(self, key):
        self.n += 1

    def dedup(self, keys):
        import numpy as np
        self.n += int(keys.size)
        return np.arange(keys.size, dtype=np.int64)


@contextlib.contextmanager
def filter_only_dedup():
    """Guarantee broken: the device filter only advises.  The exact host
    key set is replaced by one that admits everything it is shown, whether
    a pass builds it empty or rebuilds it from a snapshot's keys."""
    from raft_tla_tpu.utils import keyset
    real = keyset.new_master, keyset.master_from_keys
    keyset.new_master = lambda *a, **kw: _AdmitAll()
    keyset.master_from_keys = lambda keys, *a, **kw: _AdmitAll(len(keys))
    try:
        yield
    finally:
        keyset.new_master, keyset.master_from_keys = real


@contextlib.contextmanager
def invariants_off():
    """Guarantee broken: every invariant evaluated on every admitted orbit.
    Every step program built inside says that every invariant holds (the
    change that would tempt a later PR: the invariant pass is work that no
    sound run's counts depend on)."""
    import jax.numpy as jnp
    from raft_tla_tpu.ops import kernels
    real = kernels.build_step

    def build_step(*args, **kw):
        step = real(*args, **kw)

        def blind(vecs):
            out = dict(step(vecs))
            out["inv_ok"] = jnp.ones_like(out["inv_ok"])
            return out
        return blind

    kernels.build_step = build_step
    try:
        yield
    finally:
        kernels.build_step = real


@contextlib.contextmanager
def misrouted_exchange():
    """Guarantee broken (a mesh configuration's own): every candidate is
    filtered and deduplicated by the shard that owns its key.  Every mesh
    segment traced inside sends each candidate to the shard AFTER its owner.
    All duplicates of a key still meet on one shard, so every count stays
    right; only ``owner_misrouted`` sees it.  Does nothing to a one-chip
    engine, which has no exchange."""
    import jax.numpy as jnp
    from raft_tla_tpu.parallel import ddd_shard_engine as mesh_engine
    real = mesh_engine.exchange

    def exchange(axis_name, n_dest, cap, dest, payload):
        wrong = jnp.where(dest < n_dest, (dest + 1) % n_dest, dest)
        return real(axis_name, n_dest, cap, wrong, payload)

    mesh_engine.exchange = exchange
    try:
        yield
    finally:
        mesh_engine.exchange = real


@contextlib.contextmanager
def drops_last_level():
    """Guarantee broken (a configuration's whose passes run to their own
    end): every reachable orbit inside the bounds was admitted.  Every
    engine built inside hands its compiled segment the frontier of the
    second-to-last pinned level as an empty block, so the last level that
    would admit anything admits nothing: the search ends a level early,
    ``complete = True``, with a smaller total.  A pass stopped at a pin
    below that level never gets there and passes the control."""
    from benchmark.harness import drive
    real = drive.build_engine

    def build_engine(cfg):
        import jax.numpy as jnp
        eng = real(cfg)
        pins = cfg["level_pins"]
        last = len(pins) - 2    # its frontier is the last that bears fruit
        segment, check = eng._segment, eng.check
        armed = []

        def empty_frontier(fc, bufs, rows, con, budget, n_rows):
            return segment(fc, bufs, rows, con, budget,
                           jnp.int32(0) if armed else n_rows)

        def watched(on_progress=None, **kw):
            def cb(rec):
                # the boundary record of that level: what follows is the
                # upload and the expansion of its frontier
                if (rec["level"], rec["n_states"]) == (last, pins[last]):
                    armed.append(1)
                if on_progress is not None:
                    on_progress(rec)
            try:
                return check(on_progress=cb, **kw)
            finally:
                del armed[:]    # check (c) feeds the same segment its sample

        eng._segment, eng.check = empty_frontier, watched
        return eng

    drive.build_engine = build_engine
    try:
        yield
    finally:
        drive.build_engine = real


CONTROLS = {"key32": lambda: short_keys(32),
            "filter_only": filter_only_dedup,
            "invariants_off": invariants_off,
            "misroute": misrouted_exchange,
            "drops_last_level": drops_last_level}
