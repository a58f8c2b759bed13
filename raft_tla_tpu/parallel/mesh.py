"""The device mesh and its one data-moving primitive.

A search over several chips shards its frontier and its fingerprint space
over a ``jax.sharding.Mesh``: one axis (``_AXIS``) within a pod slice,
riding ICI, and an optional outer axis (``_DCN``) across slices.  Every
program that runs under ``shard_map`` on such a mesh takes the axis names
and its mesh from here, and routes rows between shards with ``exchange``,
whose send blocks cost what they carry: a slab of gathers per
``ddd_engine._S_OUT`` live lanes, not a scatter over every lane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

I32 = jnp.int32


_AXIS = "d"     # the frontier/fingerprint mesh axis (DP, SURVEY §2.9)
_DCN = "dcn"    # outer mesh axis for multi-slice scale-out (SURVEY §2.9
#                 comm-backend row: ICI within a slice, DCN across slices)


def make_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` devices (default: all)."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"need {n_devices} devices, have {len(devs)} "
                "(tests: --xla_force_host_platform_device_count)")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (_AXIS,))


def make_slice_mesh(n_slices: int, per_slice: int) -> Mesh:
    """A 2-D ``(dcn, ici)`` mesh: ``n_slices`` pod slices of ``per_slice``
    chips.  The outer axis rides DCN, the inner ICI; the hierarchical
    dedup exchange (stage A over ICI, stage B over DCN) keeps cross-slice
    traffic aggregated into per-slice blocks.  On real multi-slice pods
    the device order from ``jax.devices()`` groups by slice already; under
    the virtual CPU mesh the reshape just fixes the flat-id convention
    ``dev = slice * per_slice + chip``."""
    devs = jax.devices()
    if n_slices * per_slice > len(devs):
        raise ValueError(
            f"need {n_slices * per_slice} devices, have {len(devs)} "
            "(tests: --xla_force_host_platform_device_count)")
    grid = np.asarray(devs[:n_slices * per_slice]).reshape(
        n_slices, per_slice)
    return Mesh(grid, (_DCN, _AXIS))


def _mesh_axes(mesh: Mesh) -> tuple:
    """Collective axis names spanning every device of ``mesh``."""
    return (_DCN, _AXIS) if _DCN in mesh.axis_names else (_AXIS,)


def exchange(axis_name, n_dest, cap, dest, payload):
    """Pack ``payload`` rows into per-destination blocks of ``cap`` rows
    and all_to_all them over one mesh axis (the 2-D hierarchical exchange
    is two calls — stage A over ICI, stage B over DCN).  A shard receives
    its rows in (source shard, lane) order.  ``dest >= n_dest`` drops the
    row; ``payload`` is a sequence of (values, fill, dtype), values 1-D or
    2-D.  Returns (received payload, overflow flag): the flag is raised
    when a destination was sent more than ``cap`` rows.

    The blocks are packed from the live lanes, the ``stream`` stage's way
    (``ddd_engine._write_slabs``: a TPU scatter or gather costs per lane
    it touches): one sort puts the lanes in (destination, lane) order, the
    live prefix of that order is gathered slab by slab into a staging
    array — rows word by word, the trips taken from the live lanes the
    step observes — and a destination's block is ``cap`` contiguous rows
    of it from that destination's offset, ``fill`` at and past its
    count."""
    # lazily: importing the package stays cheap (parallel/__init__.py)
    from raft_tla_tpu.ddd_engine import _slab_plan, _write_slabs
    N = dest.shape[0]
    cnt = jnp.sum(dest[:, None] == jnp.arange(n_dest, dtype=I32)[None, :],
                  axis=0, dtype=I32)
    off = jnp.cumsum(cnt) - cnt
    _, order = jax.lax.sort((dest, jnp.arange(N, dtype=I32)), num_keys=2)
    # columns sliced once, outside the slab loop
    cols = [[v.astype(t)] if v.ndim == 1 else
            [v[:, p].astype(t) for p in range(v.shape[1])]
            for v, _, t in payload]

    def gather(sel):
        return tuple(c[0][sel] if v.ndim == 1 else
                     jnp.stack([w[sel] for w in c], axis=1)
                     for c, (v, _, _) in zip(cols, payload))

    # ``cap`` rows of slack (and the last slab's): no block's slice and no
    # slab's write is ever clamped
    rows = N + max(_slab_plan(N)[1], cap)
    stage, _, _ = _write_slabs(
        [jnp.zeros((rows,) + v.shape[1:], t) for v, _, t in payload],
        jnp.int32(0), jnp.sum(cnt), order, N, gather)
    keep = jnp.arange(cap, dtype=I32)[None, :] < cnt[:, None]
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            split_axis=0, concat_axis=0, tiled=True)
    outs = []
    for st, (val, fill, dtype) in zip(stage, payload):
        blocks = jnp.stack([jax.lax.dynamic_slice_in_dim(st, off[d], cap)
                            for d in range(n_dest)])
        blocks = jnp.where(keep.reshape(keep.shape + (1,) * (val.ndim - 1)),
                           blocks, jnp.asarray(fill, dtype))
        outs.append(a2a(blocks).reshape((n_dest * cap,) + val.shape[1:]))
    return outs, jnp.any(cnt > cap)
