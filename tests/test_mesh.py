"""The mesh module (parallel/mesh.py): the meshes it builds, the names of
their axes, and ``exchange`` — the one primitive that moves rows between
shards — held to a NumPy statement of what it delivers, on the virtual CPU
mesh (conftest: 8 devices)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from raft_tla_tpu import parallel
from raft_tla_tpu.parallel import mesh as M


@pytest.mark.parametrize("n", [1, 2, 4])
def test_make_mesh_puts_n_devices_on_the_axis(n):
    mesh = M.make_mesh(n)
    assert mesh.axis_names == (M._AXIS,)
    assert mesh.shape[M._AXIS] == n
    assert list(mesh.devices.ravel()) == jax.devices()[:n]


def test_make_mesh_defaults_to_every_device_and_refuses_more_by_number():
    have = len(jax.devices())
    assert M.make_mesh().devices.size == have
    with pytest.raises(ValueError,
                       match=f"need {have + 1} devices, have {have}"):
        M.make_mesh(have + 1)


@pytest.mark.parametrize("n_slices, per_slice", [(2, 2), (2, 4), (4, 2)])
def test_make_slice_mesh_has_the_shape_asked_for(n_slices, per_slice):
    mesh = M.make_slice_mesh(n_slices, per_slice)
    assert mesh.axis_names == (M._DCN, M._AXIS)
    assert mesh.devices.shape == (n_slices, per_slice)
    # the flat-id convention: dev = slice * per_slice + chip
    assert list(mesh.devices.ravel()) == jax.devices()[:n_slices * per_slice]


def test_make_slice_mesh_refuses_a_product_past_the_device_count():
    have = len(jax.devices())
    with pytest.raises(ValueError,
                       match=f"need {3 * have} devices, have {have}"):
        M.make_slice_mesh(3, have)


def test_mesh_axes_names_every_axis_of_both_meshes():
    assert M._mesh_axes(M.make_mesh(4)) == (M._AXIS,)
    assert M._mesh_axes(M.make_slice_mesh(2, 2)) == (M._DCN, M._AXIS)


def _exchanged(ndev, cap, dest, vals, words):
    """``exchange`` over a ``ndev``-device mesh: ``dest`` is [ndev, L],
    ``vals`` [ndev, L(, words)]; returns the rows each shard received,
    [ndev, ndev * cap(, words)], and each shard's overflow flag."""
    fill = -1

    def body(d, v):
        (got,), ovf = M.exchange(M._AXIS, ndev, cap, d,
                                 [(v, fill, jnp.int32)])
        return got, ovf[None]

    L = dest.shape[1]
    flat = (ndev * L,) + ((words,) if words > 1 else ())
    got, ovf = jax.jit(jax.shard_map(
        body, mesh=M.make_mesh(ndev), in_specs=(P(M._AXIS), P(M._AXIS)),
        out_specs=(P(M._AXIS), P(M._AXIS)), check_vma=False))(
            jnp.asarray(dest.reshape(-1), jnp.int32),
            jnp.asarray(vals.reshape(flat), jnp.int32))
    got = np.asarray(got)
    return got.reshape((ndev, ndev * cap) + got.shape[1:]), np.asarray(ovf)


def _delivered(ndev, cap, dest, vals):
    """What ``exchange`` promises: shard r holds, block by source shard,
    the rows that source addressed to r in lane order, ``-1`` after."""
    want = np.full((ndev, ndev, cap) + vals.shape[2:], -1, np.int32)
    for r in range(ndev):
        for s in range(ndev):
            rows = vals[s][dest[s] == r][:cap]
            want[r, s, :len(rows)] = rows
    return want.reshape((ndev, ndev * cap) + vals.shape[2:])


@pytest.mark.parametrize("words", [1, 5])
@pytest.mark.parametrize("ndev", [2, 4])
def test_exchange_delivers_every_row_to_its_shard_in_source_lane_order(
        ndev, words):
    L, cap = 24, 24                    # cap = L: no destination overflows
    rng = np.random.default_rng(ndev * 10 + words)
    dest = rng.integers(0, ndev + 1, (ndev, L))     # ndev = "no shard"
    dest[:, 0], dest[:, 1] = ndev, 0                # both kinds every time
    vals = rng.integers(0, 1 << 20, (ndev, L) + ((words,) * (words > 1)))
    got, ovf = _exchanged(ndev, cap, dest, vals, words)
    assert not ovf.any()
    assert np.array_equal(got, _delivered(ndev, cap, dest, vals))
    # every addressed row arrived once, and no unaddressed one did
    sent = np.sort(vals[dest < ndev].reshape(-1))
    assert np.array_equal(np.sort(got[got >= 0]), sent)


@pytest.mark.parametrize("ndev", [2, 4])
def test_exchange_raises_its_flag_at_one_row_past_cap_and_never_before(ndev):
    L, cap = 16, 6
    dest = np.full((ndev, L), ndev)                 # nothing addressed
    dest[0, :cap] = 1                               # shard 0 -> 1: cap rows
    vals = np.arange(ndev * L).reshape(ndev, L)
    got, ovf = _exchanged(ndev, cap, dest, vals, 1)
    assert not ovf.any()
    assert np.array_equal(got, _delivered(ndev, cap, dest, vals))
    dest[0, L - 1] = 1                              # ... and one more
    got, ovf = _exchanged(ndev, cap, dest, vals, 1)
    assert ovf.tolist() == [True] + [False] * (ndev - 1)    # the sender's
    # the first ``cap`` rows still arrive, in order; the flag is the loss
    assert np.array_equal(got, _delivered(ndev, cap, dest, vals))


@pytest.mark.parametrize("module, name", [
    ("raft_tla_tpu.parallel.shard_engine", "make_mesh"),
    ("raft_tla_tpu.parallel.ddd_shard_engine", "exchange"),
])
def test_the_benchmarks_import_paths_are_the_mesh_modules_objects(module,
                                                                  name):
    """benchmark/harness/drive.py imports the first, breakers.py replaces
    the second as a module global of the engine that calls it."""
    assert getattr(importlib.import_module(module), name) is getattr(M, name)


def test_every_public_name_of_the_package_resolves():
    assert parallel.make_mesh is M.make_mesh
    assert parallel.make_slice_mesh is M.make_slice_mesh
    for name, module in parallel._LAZY.items():
        owner = importlib.import_module(f"raft_tla_tpu.parallel.{module}")
        assert getattr(parallel, name) is getattr(owner, name)
    with pytest.raises(AttributeError, match="no_such_engine"):
        parallel.no_such_engine
