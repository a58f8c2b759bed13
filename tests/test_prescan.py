"""The prescan ladder (ops/kernels._orbit_fp_prescan), the one selection
left inside the fused step: which rung a chunk takes, that every rung
gives the bare scan's keys, the rule that decides whether a program has
the ladder at all, and that nothing else splits a compile.

- rungs: inputs built so that the number of raw-distinct candidates lands
  exactly on the N/4 boundary, one past it (N/2) and one past N/2 (the
  full scan), at |G| = 6, 12 and 120 (the benchmark's two five-server
  universes), under a VIEW and under faithful history; the rung is seen,
  not inferred: the scan a ``lax.cond`` takes reports its lane count;
- ``_prescan_enabled``'s auto policy, by backend at |G| = 6 to 720: it
  decides which program each benchmark cell compiles, and which
  configurations of ``benchmark/configs/`` have the ladder on the TPU
  (none) is pinned;
- ``step_signature`` / ``serve.batch.bin_key`` split on the prescan
  resolution and on no other environment variable;
- the ``ddd`` engine with the ladder forced off against on: the same
  level counts and the same discovery order, rows and keys.
"""

import dataclasses
import functools
import hashlib
import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.models import interp, views
from raft_tla_tpu.ops import fingerprint as fpr
from raft_tla_tpu.ops import kernels
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops import symmetry as sym

from symmetry_cases import _SCAN_CASES

N = 64                                   # lanes of the synthetic chunk
# rung -> (raw-distinct valid states, invalid lanes, lanes the taken scan
# must run on): the invalid lanes' one sentinel group makes n_uniq one
# more than the distinct states, i.e. exactly N/4, N/4 + 1 and N/2 + 1;
# "every-lane" has no sentinel and no repeat, n_uniq = N
_RUNGS = {"N/4": (N // 4 - 1, 8, N // 4), "N/2": (N // 4, 8, N // 2),
          "full": (N // 2, 8, N), "every-lane": (N, 0, N)}
_LADDER_CASES = [(c, r) for c in ("3s-server", "3s-server-value",
                                  "elect5-server", "full5-server")
                 for r in ("N/4", "N/2", "full")]
_LADDER_CASES += [("3s-view-server", "N/2"),
                  ("2s-faithful-server-value", "N/4"),
                  ("elect5-server", "every-lane")]


@functools.lru_cache(maxsize=None)
def _ladder(case):
    """One compile a case, shared by its rungs: the ladder around a scan
    that says how many lanes it was run on, the bare scan, and the case's
    raw-distinct states."""
    bounds, axes, view, make, _n = _SCAN_CASES[case]
    lay = st.Layout.of(bounds)
    consts = jnp.asarray(fpr.lane_constants(lay.width))
    orbit_fp = sym.build_orbit_fp(bounds, axes, consts,
                                  "allLogs" in lay.shapes)
    ran = []

    def telling(flat):
        lanes = flat["role"].shape[0]
        jax.debug.callback(lambda: ran.append(lanes))
        return orbit_fp(flat)

    viewer = views.jnp_view(view, bounds) if view else (lambda s: s)

    @jax.jit
    def both(vecs, valid):
        # as apply_stages does: the scan sees the (viewed) struct, the raw
        # keys hash the packed un-viewed rows, invalid lanes share one key
        flat = jax.vmap(lambda v: viewer(st.unpack(v, lay, jnp)))(vecs)
        rh, rl = fpr.fingerprint(vecs, consts, jnp)
        rh = jnp.where(valid, rh, ~jnp.uint32(0))
        rl = jnp.where(valid, rl, ~jnp.uint32(0))
        got = kernels._orbit_fp_prescan(telling, flat, rh, rl, N)
        return got, orbit_fp(flat)

    distinct = np.unique(
        np.stack([interp.to_vec(s, bounds) for s in make()]), axis=0)
    return both, ran, distinct


@pytest.mark.parametrize("case,rung", _LADDER_CASES)
def test_every_rung_gives_the_bare_scans_keys(case, rung):
    both, ran, distinct = _ladder(case)
    n_distinct, n_invalid, lanes = _RUNGS[rung]
    assert len(distinct) >= n_distinct + n_invalid
    rng = np.random.default_rng(zlib.crc32(f"{case} {rung}".encode()))
    distinct = rng.permutation(distinct)     # a copy: _ladder's is shared
    live, dead = distinct[:n_distinct], distinct[len(distinct) - n_invalid:]
    # every distinct state at least once, the rest of the lanes repeats
    pick = np.concatenate([np.arange(n_distinct), rng.integers(
        0, n_distinct, N - n_invalid - n_distinct)])
    vecs = np.concatenate([live[pick], dead])
    valid = np.arange(N) < N - n_invalid
    order = rng.permutation(N)
    vecs, valid = vecs[order], valid[order]
    del ran[:]
    (gh, gl), (wh, wl) = both(jnp.asarray(vecs), jnp.asarray(valid))
    jax.effects_barrier()
    assert ran == [lanes], (ran, rung)
    np.testing.assert_array_equal(np.asarray(gh)[valid],
                                  np.asarray(wh)[valid])
    np.testing.assert_array_equal(np.asarray(gl)[valid],
                                  np.asarray(wl)[valid])


# -- the auto policy ---------------------------------------------------------

_B3 = Bounds(n_servers=3, n_values=2, max_term=2, max_log=1, max_msgs=2,
             max_dup=1)                      # flagship3: |G| = 6
_B5 = Bounds(n_servers=5, n_values=2, max_term=2, max_log=0, max_msgs=2,
             max_dup=1)                      # elect5 / full5: |G| = 120
_B6 = dataclasses.replace(_B5, n_servers=6)  # no cell's: |G| = 720


@pytest.mark.parametrize("backend,bounds,axes,want", [
    ("cpu", _B3, ("Server",), True),
    ("tpu", _B3, ("Server",), False),        # flagship3.passes
    ("tpu", _B5, ("Server",), False),        # elect5.passes, full5.passes
    ("tpu", _B5, (), False),
    ("tpu", _B3, ("Server", "Value"), False),    # the axes multiply: 3!·2!
    ("tpu", _B5, ("Server", "Value"), False),
    ("tpu", _B6, ("Server",), False),        # runs/prescan_ab.out: 0.92x
], ids=["cpu-G6", "tpu-G6", "tpu-G120", "tpu-no-symmetry", "tpu-G12",
        "tpu-G240", "tpu-G720"])
def test_prescan_auto_policy(monkeypatch, backend, bounds, axes, want):
    monkeypatch.delenv("RAFT_TLA_PRESCAN", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kernels._prescan_enabled(bounds, axes) is want


_CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")
# the benchmark's configurations whose program has the ladder on the
# TPU: a change of the rule that re-programs a cell has to name it here
_LADDER_ON_THE_TPU = set()


@pytest.mark.parametrize("name", sorted(
    f[:-len(".json")] for f in os.listdir(_CONFIGS) if f.endswith(".json")))
def test_which_benchmark_configurations_take_the_ladder_on_the_tpu(
        monkeypatch, name):
    with open(os.path.join(_CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    monkeypatch.delenv("RAFT_TLA_PRESCAN", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the program's bounds as the configuration's spec family states them
    # (a family other than Raft's names its own: twophase10 says n_rms)
    from benchmark.harness import manifest as mf
    config = mf.family(cfg).check_config(cfg)
    got = kernels._prescan_enabled(config.bounds, tuple(cfg["symmetry"]))
    assert got is (name in _LADDER_ON_THE_TPU)


# -- compile identity --------------------------------------------------------

def _bin_key(bounds, spec, invariants, symmetry, view):
    from raft_tla_tpu.serve import batch
    return batch.bin_key(CheckConfig(
        bounds=bounds, spec=spec, invariants=invariants, symmetry=symmetry,
        view=view, chunk=64))


class _ReadsOf(dict):
    """A copy of the environment that notes every name looked up."""

    def __init__(self, env):
        super().__init__(env)
        self.read = set()

    def get(self, name, default=None):
        self.read.add(name)
        return super().get(name, default)

    def __getitem__(self, name):
        self.read.add(name)
        return super().__getitem__(name)

    def __contains__(self, name):
        self.read.add(name)
        return super().__contains__(name)


@pytest.mark.parametrize("signature", [kernels.step_signature, _bin_key],
                         ids=["step_signature", "bin_key"])
def test_compile_identity_splits_on_prescan_alone(monkeypatch, signature):
    """Five positional items, then ("prescan", .), ("devdedup", .): the
    prescan resolution moves the signature, and the two names of that
    tail are the only ``RAFT_TLA_*`` variables it is computed from."""
    args = (_B3, "full", ("NoTwoLeaders",), ("Server",), None)
    env = _ReadsOf(os.environ)
    monkeypatch.setattr(os, "environ", env)
    env["RAFT_TLA_PRESCAN"] = "off"
    off = signature(*args)
    assert off[:5] == args
    assert [k for k, _v in off[5:7]] == ["prescan", "devdedup"]
    assert dict(off[5:7])["prescan"] is False
    env["RAFT_TLA_PRESCAN"] = "on"
    on = signature(*args)
    assert dict(on[5:7])["prescan"] is True
    assert on[:5] + on[6:] == off[:5] + off[6:]
    assert {n for n in env.read if n.startswith("RAFT_TLA_")} \
        == {"RAFT_TLA_PRESCAN", "RAFT_TLA_DEVDEDUP"}


# -- the engine --------------------------------------------------------------

_TOY_FLAGSHIP = (CheckConfig(          # runs/MC3s2v.cfg at the flagship bounds
    bounds=_B3, spec="full",
    invariants=("NoTwoLeaders", "LogMatching", "CommittedWithinLog",
                "LeaderCompleteness"),
    symmetry=("Server",), chunk=64), 7)
_TOY_ELECTION = (CheckConfig(
    bounds=Bounds(n_servers=3, n_values=1, max_term=2, max_log=0,
                  max_msgs=1),
    spec="election", invariants=("NoTwoLeaders",), symmetry=("Server",),
    chunk=256), 12)


@pytest.mark.parametrize("cfg,depth", [_TOY_FLAGSHIP, _TOY_ELECTION],
                         ids=["toy-flagship", "toy-election"])
def test_ddd_engine_prescan_off_equals_on(monkeypatch, cfg, depth):
    """Levels 0..depth complete: equal cumulative counts, and the stored
    rows and master keys of those levels equal in discovery order."""
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine

    caps = DDDCapacities(block=1 << 12, table=1 << 14, flush=1 << 14,
                         levels=64)
    seen = {}
    for mode in ("off", "on"):
        monkeypatch.setenv("RAFT_TLA_PRESCAN", mode)
        eng = DDDEngine(cfg, caps)
        assert eng._prescan is (mode == "on")

        def stop_past_depth(rec):
            if rec["level"] > depth:
                eng._sigint = True       # what the first SIGINT sets

        res = eng.check(on_progress=stop_past_depth, retain_store=True)
        host, constore, keystore, _n = eng.retained
        try:
            assert res.violation is None
            cum = [int(c) for c in np.cumsum(res.levels)[:depth + 1]]
            assert len(cum) == depth + 1
            order = hashlib.sha256()
            order.update(np.ascontiguousarray(
                host.read(0, cum[-1])).tobytes())
            order.update(np.ascontiguousarray(
                keystore.read(0, cum[-1])).tobytes())
        finally:
            for store in (host, constore, keystore):
                store.close()
        seen[mode] = (cum, order.hexdigest())
    assert seen["on"] == seen["off"]
    assert seen["on"][0][-1] > 1000


@pytest.mark.parametrize("engine,mode,want", [
    ("ddd", "on", True), ("ddd", "off", False),
    ("ddd-routed", "on", False),             # compacts before its scan
    ("ddd-shard", "on", True)])
def test_pass_span_says_which_step_ran(monkeypatch, tmp_path, engine, mode,
                                       want):
    """A trace names the program without the environment: the ``pass``
    span's ``prescan`` is what the engine's step was built with."""
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    from raft_tla_tpu.models import spec as S

    monkeypatch.setenv("RAFT_TLA_PRESCAN", mode)
    monkeypatch.setenv("RAFT_TLA_TRACE", "1")
    cfg = CheckConfig(
        bounds=Bounds(n_servers=2, n_values=1, max_term=2, max_log=0,
                      max_msgs=2),
        spec="election", invariants=("NoTwoLeaders",), symmetry=("Server",),
        chunk=32)
    caps = dict(block=256, table=1 << 14, flush=1 << 10, levels=64)
    if engine == "ddd-shard":
        from raft_tla_tpu.parallel.ddd_shard_engine import (
            DDDShardCapacities, DDDShardEngine)
        from raft_tla_tpu.parallel.mesh import make_mesh
        eng = DDDShardEngine(cfg, make_mesh(2),
                             DDDShardCapacities(seg_rows=1 << 14, **caps))
    else:
        if engine == "ddd-routed":
            caps["route_rows"] = cfg.chunk * len(
                S.action_table(cfg.bounds, cfg.spec))
        eng = DDDEngine(cfg, DDDCapacities(**caps))
    log = str(tmp_path / "pass.events")
    res = eng.check(events=log)
    assert res.violation is None and res.complete
    with open(log) as f:
        evs = [json.loads(line) for line in f]
    (root,) = [e for e in evs
               if e["event"] == "span" and e["name"] == "pass"]
    assert root["args"]["prescan"] is want
