"""The device mesh and its one data-moving primitive.

A search over several chips shards its frontier and its fingerprint space
over a ``jax.sharding.Mesh``: one axis (``_AXIS``) within a pod slice,
riding ICI, and an optional outer axis (``_DCN``) across slices.  Every
program that runs under ``shard_map`` on such a mesh takes the axis names
and its mesh from here, and routes rows between shards with ``exchange``.
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
    """Count-sort ``payload`` rows into per-destination blocks of ``cap``
    rows and all_to_all them over one mesh axis (the 2-D hierarchical
    exchange is two calls — stage A over ICI, stage B over DCN).  A shard
    receives its rows in (source shard, lane) order.  ``dest >= n_dest``
    drops the row; ``payload`` is a sequence of (values, fill, dtype).
    Returns (received payload, overflow flag): the flag is raised when a
    destination was sent more than ``cap`` rows."""
    oh = (dest[:, None] == jnp.arange(n_dest, dtype=I32)[None, :])
    cum = jnp.cumsum(oh.astype(I32), axis=0)
    pos = jnp.take_along_axis(
        cum, jnp.clip(dest, 0, n_dest - 1)[:, None], axis=1)[:, 0] - 1
    live = dest < n_dest
    overflow = jnp.any(live & (pos >= cap))
    slot = jnp.where(live & (pos < cap), dest * cap + pos, n_dest * cap)
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            split_axis=0, concat_axis=0, tiled=True)
    outs = []
    for val, fill, dtype in payload:
        buf = jnp.full((n_dest * cap,) + val.shape[1:], fill, dtype)
        buf = buf.at[slot].set(val.astype(dtype), mode="drop")
        outs.append(a2a(buf.reshape((n_dest, cap) + val.shape[1:]))
                    .reshape((n_dest * cap,) + val.shape[1:]))
    return outs, overflow
