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
    got, ovf = _routed((ndev,), (cap,), dest, vals.reshape(ndev, -1, words))
    return (got[..., 0] if words == 1 else got), ovf


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


def _routed(shape, caps, owner, vals):
    """Rows routed to their owner device the way the engines route them:
    stage A over the ICI axis to the owner's chip index and, on a 2-D
    ``(dcn, ici)`` mesh, stage B over DCN to the owner's slice, the owner
    riding along as a second payload field.  ``owner`` is [ndev, L]
    (``>= ndev``: nobody), ``vals`` [ndev, L, words]; returns what each
    device holds, [ndev, rows, words], and each device's overflow flag."""
    nici = shape[-1]
    ndev = int(np.prod(shape))
    nslice = ndev // nici
    mesh = M.make_mesh(ndev) if len(shape) == 1 else M.make_slice_mesh(*shape)
    axes = M._mesh_axes(mesh)
    spec = P(axes if len(axes) > 1 else axes[0])

    def body(o, v):
        (v, o), ovf = M.exchange(
            M._AXIS, nici, caps[0], jnp.where(o < ndev, o % nici, nici),
            [(v, -1, jnp.int32), (o, -1, jnp.int32)])
        if nslice > 1:
            (v, o), ovf2 = M.exchange(
                M._DCN, nslice, caps[1],
                jnp.where(o >= 0, o // nici, nslice),
                [(v, -1, jnp.int32), (o, -1, jnp.int32)])
            ovf = ovf | ovf2
        return v, ovf[None]

    got, ovf = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec),
        check_vma=False))(
            jnp.asarray(owner.reshape(-1), jnp.int32),
            jnp.asarray(vals.reshape((-1,) + vals.shape[2:]), jnp.int32))
    got = np.asarray(got)
    return got.reshape((ndev, -1) + got.shape[1:]), np.asarray(ovf)


def _routed_want(shape, caps, owner, vals):
    """The same routing as two applications of ``_delivered``: stage A
    inside each slice, stage B down each column of chips."""
    nici = shape[-1]
    ndev = int(np.prod(shape))
    rows = np.concatenate([vals, owner[..., None]], axis=2)
    a = np.concatenate([
        _delivered(nici, caps[0],
                   np.where(owner[s:s + nici] < ndev,
                            owner[s:s + nici] % nici, nici),
                   rows[s:s + nici])
        for s in range(0, ndev, nici)])
    if ndev == nici:
        return a[..., :-1]
    out = np.empty((ndev, ndev // nici * caps[1], rows.shape[2]), np.int32)
    for c in range(nici):
        col = a[c::nici]                        # the devices (slice, c)
        o = col[..., -1]
        out[c::nici] = _delivered(ndev // nici, caps[1],
                                  np.where(o >= 0, o // nici, ndev // nici),
                                  col)
    return out[..., :-1]


_L = 24


def _owners(case, ndev):
    rng = np.random.default_rng(len(case))
    owner = rng.integers(0, ndev + 1, (ndev, _L))       # ndev = nobody
    if case == "every row to one destination":
        owner[:] = 2
    elif case == "a destination nobody addresses":
        owner[owner == 2] = ndev
    elif case == "no live row at all":
        owner[:] = ndev
    elif case == "cap under the rows sent":
        owner[:] = ndev
        owner[0, 1::2] = 1                              # 12 rows, cap 6
        owner[3, :6] = 1                                # cap rows: no flag
    return owner


@pytest.mark.parametrize("case, shape, caps, slab, flags", [
    ("every row to one destination", (4,), (_L,), None, [0, 0, 0, 0]),
    ("a destination nobody addresses", (4,), (_L,), None, [0, 0, 0, 0]),
    ("no live row at all", (4,), (_L,), None, [0, 0, 0, 0]),
    ("more live rows than one slab", (4,), (_L,), 5, [0, 0, 0, 0]),
    ("cap under the rows sent", (4,), (6,), None, [1, 0, 0, 0]),
    ("both stages of a 2-D mesh", (2, 2), (_L, 2 * _L), None, [0, 0, 0, 0]),
    ("both stages, several slabs", (2, 2), (_L, 2 * _L), 7, [0, 0, 0, 0]),
])
def test_exchange_packs_its_blocks_from_the_live_lanes(
        case, shape, caps, slab, flags, monkeypatch):
    """The corners of the packing (one sort, the live prefix gathered in
    slabs, a contiguous masked block a destination) against the contract:
    what ``_delivered`` states, whatever is live and however many slabs
    the gather takes; past ``cap`` the flag, and the first ``cap`` rows in
    lane order with ``fill`` after."""
    if slab is not None:
        # as the ``stream`` stage's tests drive several slabs a step
        monkeypatch.setattr("raft_tla_tpu.ddd_engine._S_OUT", slab)
    ndev = int(np.prod(shape))
    owner = _owners(case, ndev)
    vals = np.random.default_rng(7).integers(0, 1 << 20, (ndev, _L, 3))
    got, ovf = _routed(shape, caps, owner, vals)
    assert ovf.astype(int).tolist() == flags
    assert np.array_equal(got, _routed_want(shape, caps, owner, vals))


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
