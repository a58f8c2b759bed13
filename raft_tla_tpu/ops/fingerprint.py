"""64-bit state fingerprints — the dedup key (TLC's FP64 analog, SURVEY §2.8).

TLC deduplicates states by a 64-bit fingerprint of the canonicalized value
(probabilistically exact, with a reported collision bound).  This module plays
that role for the tensor encoding: a canonical ``int32[W]`` state vector hashes
to two independent 32-bit lanes, combined host-side into one ``uint64``.

Scheme: two-lane *multilinear* hash + murmur3 finalizer.  Lane k computes
``fmix32(seed_k + sum_w c_k[w] * fold(state[w]) mod 2^32)`` with per-position
odd random constants ``c_k`` and ``fold(x) = x ^ (x >> 16)``.  The linear part
is one elementwise multiply + reduction (TPU-friendly: no sequential
dependency over W, unlike a rolling hash), and the fmix32 avalanche
decorrelates lanes from the raw linear structure for use as a hash-table
index.

What the family guarantees, and why the fold (scheme 2, PR 26).  Two vectors
collide in a lane iff ``sum_w c[w] * d[w] == 0 (mod 2^32)`` over their word
differences ``d``.  A single differing word never collides (``c`` is odd);
for several, the probability is ``2^-(32-t)`` a lane where ``2^t`` is the
largest power of two dividing every ``d[w]`` — ``2^-32`` only when some
difference is odd, and both lanes see the same ``t``.  Packed message words
keep ``src``/``dst`` at bits 21-28 (ops/msgbits), so two bags that differ
only in who sent to whom had ``t = 21``: ``2^-11`` a lane, ``2^-22`` a pair
of states.  At 5 servers under the full ``Next`` that merged distinct orbits
from BFS level 6 on (936 counted of 937; 261,499 of 261,844 by level 12 —
found by the benchmark's plain reference, which never sees a fingerprint).
Folding each word's high half into its low half (a bijection on 32 bits,
the identity on words below 2^16) brings those differences down to bits
5-12: ``t <= 12``, ``2^-40`` a pair or better.  Differences confined to bits
13-15 of several words stay where they were (``2^-34`` at worst); no packed
field of this schema lives there alone.

Bit-identical across backends: all arithmetic is uint32 wraparound, explicit
dtypes everywhere, same constants (fixed PRNG seed) — NumPy host, jnp device,
the Pallas kernel (ops/pallas_fp.py), and the C++ host store (native/) must
all agree, because sharding routes states by fingerprint (SURVEY §2.8).
"""

from __future__ import annotations

import numpy as np

from raft_tla_tpu.ops import state as st

_SEED = 0x5AF7_0001
# Joins every checkpoint digest (utils/ckpt.config_digest): a snapshot's
# master keys are fingerprints, so one written under another scheme must be
# refused, not resumed.  1 = no fold (through PR 25); 2 = ``x ^ (x >> 16)``.
SCHEME = 2
_LANE_SEEDS = (np.uint32(0x9E3779B9), np.uint32(0x85EBCA77))


def lane_constants(width: int) -> np.ndarray:
    """Per-position odd uint32 multipliers, shape (2, width). Deterministic."""
    rng = np.random.Generator(np.random.PCG64(_SEED))
    c = rng.integers(0, 2**32, size=(2, width), dtype=np.uint32)
    return c | np.uint32(1)  # odd => multiplication is invertible mod 2^32


def _fmix32(h, xp):
    """murmur3 32-bit finalizer (public domain avalanche function)."""
    u = xp.uint32
    h = h ^ (h >> u(16))
    h = h * u(0x85EBCA6B)
    h = h ^ (h >> u(13))
    h = h * u(0xC2B2AE35)
    h = h ^ (h >> u(16))
    return h


def fold(a, xp):
    """The fold (module docstring): ``x ^ (x >> 16)`` on the uint32 word."""
    w = a.astype(xp.uint32)
    return w ^ (w >> xp.uint32(16))


def finalise(s1, s2, xp):
    """The lanes' sums before the finaliser -> the (hi, lo) key."""
    with np.errstate(over="ignore"):
        return (_fmix32(s1 + _LANE_SEEDS[0], xp),
                _fmix32(s2 + _LANE_SEEDS[1], xp))


def fingerprint(vec, consts, xp):
    """Canonical int32[..., W] -> (hi, lo) uint32 lanes, shape [...]."""
    # uint32 wraparound is the *point* of the arithmetic; silence NumPy's
    # scalar-overflow warning (no-op under jnp, which never warns).
    with np.errstate(over="ignore"):
        w = fold(vec, xp)
        c1 = consts[0].astype(xp.uint32)
        c2 = consts[1].astype(xp.uint32)
        s1 = xp.sum(w * c1, axis=-1, dtype=xp.uint32)
        s2 = xp.sum(w * c2, axis=-1, dtype=xp.uint32)
    return finalise(s1, s2, xp)


def field_constants(shapes: dict, consts) -> dict:
    """field -> ``uint32[2, *shape]``: each field's slice of the two
    lanes' constants at its packed positions (``shapes``: a layout's, in
    ``state.pack``'s order, row-major inside a field).  ``consts`` must
    be concrete (closed over, never a traced argument): the slices
    become literals of the compiled program."""
    c = np.asarray(consts).astype(np.uint32)
    out, off = {}, 0
    for f, shape in shapes.items():
        size = int(np.prod(shape))
        out[f] = c[:, off:off + size].reshape((2,) + tuple(shape))
        off += size
    return out


def field_sums(struct, consts, xp, fields=None):
    """The two lanes' sums before the finaliser, over ``fields`` (default:
    every field) of a state struct (ops/state.py; leading batch dims pass
    through): each field is folded where it lies, multiplied by the
    constants of its packed positions (:func:`field_constants`) and
    reduced over its own axes.  The sums are mod 2^32, so partial sums
    over disjoint field sets simply add."""
    batch = struct["role"].ndim - 1
    fc = field_constants({f: struct[f].shape[batch:]
                          for f in st.fields_of(struct)}, consts)
    s1 = s2 = xp.uint32(0)
    with np.errstate(over="ignore"):
        for f in (fc if fields is None else fields):
            w = fold(struct[f], xp)
            axes = tuple(range(batch, w.ndim))
            s1 = s1 + xp.sum(w * fc[f][0], axis=axes, dtype=xp.uint32)
            s2 = s2 + xp.sum(w * fc[f][1], axis=axes, dtype=xp.uint32)
    return s1, s2


def fingerprint_fields(struct, consts, xp):
    """State struct -> (hi, lo) uint32 lanes, bit-identical to
    ``fingerprint(state.pack(struct), consts, xp)``, and no ``[..., W]``
    row is built: the sum before the finaliser may be taken in any order,
    so it is taken field by field (:func:`field_sums`).  The orbit scan
    (ops/symmetry.build_orbit_fp) keyed every image from here from PR 27
    to PR 29; it now adds ``field_sums`` of the few fields it still moves
    to a sum it takes without moving any, and this function is the
    whole-struct form the tests hold both against."""
    return finalise(*field_sums(struct, consts, xp), xp)


def to_u64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """Host-side combine: two uint32 lanes -> one uint64 key."""
    return (np.asarray(hi, dtype=np.uint64) << np.uint64(32)) | np.asarray(
        lo, dtype=np.uint64)
