"""Pallas TPU kernel for the two-lane multilinear fingerprint.

The fingerprint (ops/fingerprint.py) is a per-row multiply-accumulate over
uint32 lanes plus a murmur3 finalizer — exactly the shape the VPU wants:
one [rows, lanes] elementwise product, a lane reduction, and a handful of
shifts.  XLA already fuses the jnp version into the surrounding step
kernel, so this Pallas twin exists for the cases where the fingerprint
runs *standalone* over large row blocks (host-store audits, re-hashing a
host store after a bounds change, the sharded engine's routing prefix)
and as the reference pattern for hand-scheduled kernels in this codebase:
explicit VMEM blocking over a 1-D grid, broadcast constants, lane-padded
inputs.

Bit-identical to the NumPy/jnp implementations (asserted in tests): same
constants, same uint32 wraparound, same finalizer.  Falls back to the jnp
path off-TPU (Pallas interpret mode is used by the CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.ops import fingerprint as fpr
from raft_tla_tpu.ops import pallas_compat as pc

_BLOCK_ROWS = 1024
_LANES = 128          # TPU lane width; W pads up to a multiple


def i32_const(u) -> int:
    """uint32 constant as the same-bits PYTHON int32 literal (shared by
    the Pallas kernels: they may not close over traced array constants,
    and plain ints fold into the program)."""
    return int(np.uint32(u).astype(np.int32))


def _i32(u) -> jnp.int32:
    """Reinterpret a uint32 constant as int32 (same bits)."""
    return jnp.int32(np.uint32(u).astype(np.int32))


def fmix_i32(h):
    """murmur3 finalizer in two's-complement int32 — bit-identical to
    the uint32 reference (ops/fingerprint._fmix32); right shifts are
    explicitly logical.  Shared by both Pallas kernels."""
    srl = jax.lax.shift_right_logical
    h = h ^ srl(h, 16)
    h = h * i32_const(0x85EBCA6B)
    h = h ^ srl(h, 13)
    h = h * i32_const(0xC2B2AE35)
    h = h ^ srl(h, 16)
    return h


def _fp_kernel(vec_ref, c1_ref, c2_ref, hi_ref, lo_ref):
    # Mosaic has no unsigned reductions; two's-complement int32 add/mul/xor
    # are bit-identical to uint32 mod 2^32, and the finalizer's right
    # shifts are made explicitly logical.
    srl = jax.lax.shift_right_logical
    w = vec_ref[...]
    w = w ^ srl(w, jnp.int32(16))       # the fold (ops/fingerprint)
    s1 = jnp.sum(w * c1_ref[...], axis=1, dtype=jnp.int32)
    s2 = jnp.sum(w * c2_ref[...], axis=1, dtype=jnp.int32)

    def fmix(h):
        h = h ^ srl(h, jnp.int32(16))
        h = h * _i32(0x85EBCA6B)
        h = h ^ srl(h, jnp.int32(13))
        h = h * _i32(0xC2B2AE35)
        h = h ^ srl(h, jnp.int32(16))
        return h

    hi_ref[...] = fmix(s1 + _i32(fpr._LANE_SEEDS[0]))
    lo_ref[...] = fmix(s2 + _i32(fpr._LANE_SEEDS[1]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fp_call(vecs, c1, c2, interpret=False):
    from jax.experimental import pallas as pl

    B, Wp = vecs.shape
    grid = (B // _BLOCK_ROWS,)
    return pl.pallas_call(
        _fp_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, Wp), lambda i: (i, 0)),
            pl.BlockSpec((1, Wp), lambda i: (0, 0)),
            pl.BlockSpec((1, Wp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_BLOCK_ROWS,), lambda i: (i,)),
            pl.BlockSpec((_BLOCK_ROWS,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        interpret=interpret,
    )(vecs, c1, c2)


@functools.lru_cache(maxsize=None)
def _padded_constants(W: int, Wp: int):
    """Lane-padded int32 views of the multipliers, built once per width
    (callers loop over row blocks of a fixed W)."""
    ci = np.asarray(fpr.lane_constants(W)).astype(np.int32)  # same bits
    c1 = jnp.zeros((1, Wp), jnp.int32).at[0, :W].set(ci[0])
    c2 = jnp.zeros((1, Wp), jnp.int32).at[0, :W].set(ci[1])
    return c1, c2


def fingerprint_rows(vecs, interpret: bool | None = None):
    """``int32[B, W] -> (hi, lo) uint32[B]`` via the Pallas kernel.

    Rows pad to the block multiple and lanes to 128 (zero pads contribute
    zero to the multilinear sum, so padding never changes a fingerprint).
    Execution mode is resolved by ``ops.pallas_compat``: ``interpret=True``
    runs the kernel under the Pallas interpreter (CPU testing), ``None``
    auto-selects — Mosaic on TPU, else the bit-identical portable jnp
    path (``ops.fingerprint.fingerprint``) — and ``False`` forces a real
    Mosaic build (loud failure off-TPU).
    """
    vecs = jnp.asarray(vecs, jnp.int32)
    B, W = vecs.shape
    mode = pc.resolve(interpret)
    if mode == pc.JNP:
        # the portable jnp path (XLA-fused; bit-identical by construction)
        return fpr.fingerprint(vecs, jnp.asarray(fpr.lane_constants(W)),
                               jnp)
    Wp = ((W + _LANES - 1) // _LANES) * _LANES
    Bp = ((B + _BLOCK_ROWS - 1) // _BLOCK_ROWS) * _BLOCK_ROWS
    vp = jnp.zeros((Bp, Wp), jnp.int32).at[:B, :W].set(vecs)
    c1, c2 = _padded_constants(W, Wp)
    hi, lo = _fp_call(vp, c1, c2, interpret=mode == pc.INTERPRET)
    return hi[:B].astype(jnp.uint32), lo[:B].astype(jnp.uint32)
