"""CP lane sharding (parallel/cp_expand.py): the per-state bag-scan
fan-out partitioned across mesh devices.

Gates: every dense action lane is owned by exactly one (device, local
lane); under shard_map on the virtual 8-device mesh each owned lane's
(valid, overflow, svec, fingerprint, invariant, constraint) values are
bit-identical to the dense step's at the mapped index; dead lanes
(non-bag off device 0, slot padding) are never valid; and the partition
covers awkward shapes (S not divisible by ndev, ndev > S).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.models import interp, spec as SP
from raft_tla_tpu.ops import kernels
from raft_tla_tpu.parallel.cp_expand import (
    build_cp_step, cp_lane_count, cp_lane_map)
from raft_tla_tpu.parallel.mesh import make_mesh, _AXIS

from test_state import random_pystate

# a bag-heavy universe: S = msg_cap large enough that the bag lanes
# dominate the table — CP's operating regime
B5 = Bounds(n_servers=2, n_values=1, max_term=2, max_log=0, max_msgs=5)


def test_lane_map_is_a_partition():
    for bounds, spec, ndev in ((B5, "full", 8), (B5, "full", 4),
                               (B5, "election", 3),
                               (B5, "full", 16)):   # ndev > S
        m = cp_lane_map(bounds, spec, ndev)
        A = len(SP.action_table(bounds, spec))
        assert m.shape == (ndev, cp_lane_count(bounds, spec, ndev))
        owned = m[m >= 0]
        assert sorted(owned.tolist()) == list(range(A))


def _run_cp(bounds, spec, invs, sym, vecs, ndev):
    mesh = make_mesh(ndev)
    step = build_cp_step(bounds, spec, invs, sym, ndev=ndev)

    def shard_fn(v):
        return step(v, jax.lax.axis_index(_AXIS))

    out = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=P(), out_specs=P(_AXIS)))(vecs)
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.mark.slow      # virtual-mesh test (see test_shard_engine)
def test_cp_step_matches_dense_per_lane():
    rng = np.random.default_rng(23)
    states = [random_pystate(rng, B5) for _ in range(8)]
    vecs = jnp.asarray(np.stack([interp.to_vec(s, B5) for s in states]))
    invs = ("NoTwoLeaders",)
    for sym in ((), ("Server",)):
        dense = {k: np.asarray(v) for k, v in jax.jit(
            kernels.build_step(B5, "full", invs, sym))(vecs).items()}
        ndev = 8
        got = _run_cp(B5, "full", invs, sym, vecs, ndev)
        lanes = cp_lane_map(B5, "full", ndev)     # [ndev, A_local]
        Al = lanes.shape[1]
        Bc = len(states)
        # out_specs stacks the device axis first: [ndev * Bc, A_local]
        for d in range(ndev):
            seg = {k: v[d * Bc:(d + 1) * Bc] for k, v in got.items()}
            for l in range(Al):
                g = lanes[d, l]
                if g < 0:
                    assert not seg["valid"][:, l].any()
                    continue
                np.testing.assert_array_equal(seg["valid"][:, l],
                                              dense["valid"][:, g])
                np.testing.assert_array_equal(seg["overflow"][:, l],
                                              dense["overflow"][:, g])
                np.testing.assert_array_equal(seg["svecs"][:, l],
                                              dense["svecs"][:, g])
                np.testing.assert_array_equal(seg["fp_hi"][:, l],
                                              dense["fp_hi"][:, g])
                np.testing.assert_array_equal(seg["fp_lo"][:, l],
                                              dense["fp_lo"][:, g])
                np.testing.assert_array_equal(seg["inv_ok"][:, l],
                                              dense["inv_ok"][:, g])
                np.testing.assert_array_equal(seg["con_ok"][:, l],
                                              dense["con_ok"][:, g])


@pytest.mark.slow      # virtual-mesh test (see test_shard_engine)
def test_cp_step_faithful_mode():
    """History fields (allLogs union) ride the CP expansion too."""
    bounds = Bounds(n_servers=2, n_values=1, max_term=2, max_log=1,
                    max_msgs=3, history=True, max_elections=4)
    rng = np.random.default_rng(29)
    states = [random_pystate(rng, bounds) for _ in range(4)]
    vecs = jnp.asarray(np.stack([interp.to_vec(s, bounds)
                                 for s in states]))
    dense = {k: np.asarray(v) for k, v in jax.jit(
        kernels.build_step(bounds, "full", ()))(vecs).items()}
    ndev = 4
    got = _run_cp(bounds, "full", (), (), vecs, ndev)
    lanes = cp_lane_map(bounds, "full", ndev)
    Bc = len(states)
    for d in range(ndev):
        seg_v = got["valid"][d * Bc:(d + 1) * Bc]
        seg_s = got["svecs"][d * Bc:(d + 1) * Bc]
        for l in range(lanes.shape[1]):
            g = lanes[d, l]
            if g < 0:
                assert not seg_v[:, l].any()
                continue
            np.testing.assert_array_equal(seg_v[:, l],
                                          dense["valid"][:, g])
            np.testing.assert_array_equal(seg_s[:, l],
                                          dense["svecs"][:, g])
