# COPY of raft_tla_tpu/ops/loguniv.py at commit 51d3f6c (PR 23): the benchmark's frozen plain reference.
# Only the import lines were rewritten; it imports nothing of raft_tla_tpu.
"""Bounded-log universe enumeration — the keystone of faithful mode.

Faithful mode (SURVEY §7.0.3b) carries the spec's proof-only history
variables — ``elections`` (raft.tla:39), ``allLogs`` (raft.tla:44),
``voterLog`` (raft.tla:77) and the ``mlog`` message fields
(raft.tla:220-222, 297-299) — as real, fingerprinted state.  All of them
are *log-valued*: sets of logs, maps to logs, logs inside messages.  Under
the StateConstraint every log is drawn from the finite universe

    U = { <<e_1..e_k>> : 0 <= k <= L, e_i in [1..T] x [1..V] }

(L = ``Bounds.log_cap``, T = ``Bounds.term_cap``, V = ``n_values``), so a
log is representable as its *rank* in a fixed enumeration — one small
integer instead of a 2L-word sequence.  That turns

- ``allLogs``      into a U-bit bitmask (set of ranks),
- ``voterLog``     into an n x n table of rank+1 (0 = absent),
- ``elections``    into slots holding ranks for elog/evoterLog,
- ``mlog``         into one extra packed message field (ops/msgbits.py),

each updated with a handful of integer ops inside the fused transition
kernel — no variable-length data anywhere, XLA-static throughout.

Enumeration: logs ordered by length, then lexicographically by entry codes.
An entry (t, v) has code ``c = (t-1)*V + (v-1)`` in radix ``R = T*V``; a
log of length k has ``id = offset[k] + sum_i c_i * R^(k-1-i)`` where
``offset[k] = (R^k - 1) / (R - 1)`` counts all shorter logs.  Properties
used downstream:

- ``id = 0``  iff the log is empty (``offset[0] = 0``);
- dropping the last entry is ``prefix_id(id) = offset[k-1] + (id - offset[k]) // R``
  — a closed form, so the AllLogsPrefixClosed invariant needs no tables;
- appending entry c is ``offset[k+1] + (id - offset[k]) * R + c``.

Dual-backend like ops/state.py: every function takes ``xp`` (numpy |
jax.numpy) and works element-wise on arrays, so the interpreter, the
invariants and the fused kernels share one implementation bit for bit.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.reference.bounds import Bounds


@dataclasses.dataclass(frozen=True)
class LogUniverse:
    """Static enumeration tables for one Bounds instance."""

    T: int          # entry terms 1..T (term_cap: one past MaxTerm, config.py)
    V: int          # entry values 1..V
    L: int          # lengths 0..L (log_cap)
    R: int          # entry radix T*V
    offsets: tuple  # offsets[k] = first id of length-k logs; len L+2
    size: int       # |U| = offsets[L+1]

    @classmethod
    def of(cls, bounds: Bounds) -> "LogUniverse":
        T, V, L = bounds.term_cap, bounds.n_values, bounds.log_cap
        R = T * V
        offs = [0]
        for _k in range(L + 1):
            offs.append(offs[-1] * R + 1)
        # offs[k] = (R^k - 1)/(R - 1) by Horner; offs[L+1] = |U|
        return cls(T=T, V=V, L=L, R=R, offsets=tuple(offs), size=offs[-1])

    @property
    def id_bits(self) -> int:
        """Bits for a rank+1 value (0 reserved for 'absent')."""
        return max(1, int(self.size).bit_length())

    @property
    def mask_words(self) -> int:
        """int32 words of a U-bit set-of-logs bitmask (allLogs)."""
        return (self.size + 31) // 32

    # -- rank arithmetic (xp-generic, element-wise) --------------------------

    def log_id(self, log_term, log_val, log_len, xp):
        """Rank of the log held in padded rows (ops/state.py log encoding).

        ``log_term``/``log_val`` are ``[..., L]`` padded arrays, ``log_len``
        the matching lengths; columns >= len are ignored (they are zero in
        canonical states, but this does not rely on that).
        """
        L, R, V = self.L, self.R, self.V
        offs = xp.asarray(self.offsets, dtype=xp.int32)
        k = xp.arange(L, dtype=xp.int32)
        ln = xp.asarray(log_len, dtype=xp.int32)[..., None]
        code = (xp.asarray(log_term, xp.int32) - 1) * V \
            + (xp.asarray(log_val, xp.int32) - 1)
        # weight of column k is R^(len-1-k) for k < len, else 0
        expo = xp.clip(ln - 1 - k, 0, max(L - 1, 0))
        powR = xp.asarray([R ** e for e in range(max(L, 1))], dtype=xp.int32)
        w = xp.where(k < ln, powR[expo], 0)
        return offs[xp.asarray(log_len, xp.int32)] \
            + xp.sum(code * w, axis=-1).astype(xp.int32)

    def log_len_of(self, ids, xp):
        """Length of the log with the given rank."""
        ids = xp.asarray(ids, xp.int32)
        ln = xp.zeros_like(ids)
        for k in range(1, self.L + 1):
            ln = xp.where(ids >= self.offsets[k], k, ln)
        return ln

    def prefix_id(self, ids, xp):
        """Rank of the log minus its last entry (undefined-at-0 maps to 0)."""
        ids = xp.asarray(ids, xp.int32)
        ln = self.log_len_of(ids, xp)
        offs = xp.asarray(self.offsets, dtype=xp.int32)
        kk = xp.clip(ln, 1, self.L)
        return xp.where(
            ln > 0, offs[kk - 1] + (ids - offs[kk]) // self.R, 0)

    def decode(self, ids, xp):
        """Rank -> padded (log_term [...,L], log_val [...,L], log_len).

        Static L-step digit extraction (big-endian: entry 0 is the most
        significant digit), vectorized over any leading shape.
        """
        L, R, V = self.L, self.R, self.V
        ids = xp.asarray(ids, xp.int32)
        ln = self.log_len_of(ids, xp)
        offs = xp.asarray(self.offsets, dtype=xp.int32)
        rem = ids - offs[ln]
        terms, vals = [], []
        for k in range(L):
            # digit k has weight R^(len-1-k); extract by repeated divmod
            # from the most significant side: divide by R^(len-1-k).
            expo = xp.clip(ln - 1 - k, 0, max(L - 1, 0))
            powR = xp.asarray([R ** e for e in range(max(L, 1))],
                              dtype=xp.int32)
            w = powR[expo]
            digit = xp.where(k < ln, rem // w, 0)
            rem = xp.where(k < ln, rem - digit * w, rem)
            terms.append(xp.where(k < ln, digit // V + 1, 0))
            vals.append(xp.where(k < ln, digit % V + 1, 0))
        if L == 0:
            z = xp.zeros(ids.shape + (0,), xp.int32)
            return z, z, ln
        return (xp.stack(terms, axis=-1).astype(xp.int32),
                xp.stack(vals, axis=-1).astype(xp.int32), ln)

    # -- host-side conveniences ----------------------------------------------

    def id_of_tuple(self, log: tuple) -> int:
        """Rank of a ((term, value), ...) tuple (interpreter form)."""
        k = len(log)
        if k > self.L:
            raise OverflowError(f"log of length {k} exceeds universe L={self.L}")
        rid = self.offsets[k]
        for pos, (t, v) in enumerate(log):
            if not (1 <= t <= self.T and 1 <= v <= self.V):
                raise OverflowError(f"entry ({t},{v}) outside universe "
                                    f"T={self.T} V={self.V}")
            rid += ((t - 1) * self.V + (v - 1)) * self.R ** (k - 1 - pos)
        return rid

    def tuple_of_id(self, rid: int) -> tuple:
        """Inverse of :meth:`id_of_tuple`."""
        lt, lv, ln = self.decode(np.asarray(rid), np)
        ln = int(ln)
        return tuple((int(lt[..., k]), int(lv[..., k])) for k in range(ln))
