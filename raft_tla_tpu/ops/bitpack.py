"""Bit-packed state rows — the canonical pack kernel (SURVEY §2.8).

The flat ``int32[W]`` state vector (ops/state.py) spends a full 32-bit word
on every field element, though no field needs more than 29 bits and most
need 2-6: the 3-server/2-value flagship layout is 60 words (240 B) carrying
~390 useful bits (~49 B).  HBM capacity and host↔device pageout bandwidth
are the checker's scaling limits, so the ddd engines store and move rows
*bit-packed* at ~4-5x density and unpack only the chunk being expanded.

The packing is a static bitstream: field element w occupies bits
``[start[w], start[w] + bits[w])`` of the row, where ``bits[w]`` is derived
from :class:`~raft_tla_tpu.config.Bounds` capacities (one step past each
bound, config.py) and ``start`` is the running sum.  Everything is computed
at trace time from static widths, so pack/unpack lower to a fixed sequence
of shifts and ors that XLA fuses into the surrounding kernel — no gathers,
no loops.

Dual-backend (``xp`` = numpy | jax.numpy), like ops/state.py: the host
store holds the same packed bytes the device block holds, and the trace
decoder unpacks with the identical code path.
"""

from __future__ import annotations

import numpy as np

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops.msgbits import _HI_FIELDS, _LO_FIELDS


# A TPU vector tile is 128 lanes wide: a row of up to that many words
# packs column by column (every accepted Raft and TwoPhase row: 33..114
# words), a wider one row-wise (``BitSchema._pack_rows``).
_LANE_TILE = 128


def _bits(max_value: int) -> int:
    """Bits to represent values 0..max_value."""
    return max(1, int(max_value).bit_length())


# Fields whose packed width is a RAW bit block, not a value range: the
# allLogs mask words carry 32 bits of set-membership data (the int32 sign
# bit is data, uint32 semantics).  The analyzer exempts these from the
# "width <= 31 so int32 stays non-negative" flat-vector rule.
RAW_FIELDS = ("allLogs",)


def width_table(bounds: Bounds) -> dict:
    """The full width contract for one Bounds instance — the table the
    static analyzer (analysis/widthcheck) proves the kernels against.

    Returns ``{"bits": field -> width, "raw": RAW_FIELDS subset present,
    "total_bits": packed row bits, "packed_words": P, "flat_words": W}``.
    """
    schema = BitSchema(bounds)
    fb = field_bits(bounds)
    return {
        "bits": fb,
        "raw": tuple(f for f in RAW_FIELDS if f in fb),
        "total_bits": schema.total_bits,
        "packed_words": schema.P,
        "flat_words": schema.W,
    }


def field_bits(bounds: Bounds) -> dict:
    """Per-element bit width for every Layout field (pack() order)."""
    n = bounds.n_servers
    hi_bits = max(sh + w for sh, w in _HI_FIELDS.values())
    # Parity mode never sets the mlog field 'g' (always 0): pack only the
    # bits below it, so parity rows don't widen with the faithful schema.
    lo_fields = _LO_FIELDS if bounds.history else \
        {k: v for k, v in _LO_FIELDS.items() if k != "g"}
    lo_bits = max(sh + w for sh, w in lo_fields.values())
    out = {
        "role": _bits(2),
        "term": _bits(bounds.term_cap),
        "votedFor": _bits(n),                    # 0 = Nil, else id+1
        "commitIndex": _bits(bounds.log_cap),
        "logLen": _bits(bounds.log_cap),
        "logTerm": _bits(bounds.term_cap),
        "logVal": _bits(bounds.n_values),
        "vResp": n,                              # bitmask over servers
        "vGrant": n,
        "nextIndex": _bits(bounds.log_cap + 1),  # 1..Len(log)+1
        "matchIndex": _bits(bounds.log_cap),
        "msgHi": hi_bits,                        # 29: the packed record word
        "msgLo": lo_bits,                        # the packed record word
        "msgCount": _bits(bounds.dup_cap),
    }
    if bounds.history:
        from raft_tla_tpu.ops.loguniv import LogUniverse
        uni = LogUniverse.of(bounds)
        out.update({
            "allLogs": 32,                       # raw bitmask words
            "vLog": uni.id_bits,                 # rank+1, 0 = absent
            "eTerm": _bits(bounds.term_cap),
            "eLeader": _bits(max(n - 1, 1)),
            "eLog": uni.id_bits,
            "eVotes": n,                         # evotes server bitmask
            "eVLog": uni.id_bits,                # rank+1, 0 = absent
        })
    return out


class BitSchema:
    """Static pack plan: per-position widths, offsets, packed width."""

    def __init__(self, bounds: Bounds):
        lay = st.Layout.of(bounds)
        self._plan(lay, field_bits(bounds))

    @classmethod
    def of_schema(cls, schema, bounds: Bounds) -> "BitSchema":
        """The pack plan of a frontend ``Schema`` (frontend/schema.py):
        every field declares its value range, so its width is that of
        its declared ``hi``.  A field that may be negative has no packed
        form here and is refused by name."""
        from raft_tla_tpu.frontend.schema import envelope
        fb = {}
        for name, iv in envelope(schema, bounds).items():
            if iv.lo < 0:
                raise ValueError(
                    f"schema {schema.name!r}: field {name!r} declares "
                    f"[{iv.lo}, {iv.hi}]; a packed field holds 0..hi")
            fb[name] = _bits(iv.hi)
        self = cls.__new__(cls)
        self._plan(schema.layout(bounds), fb)
        return self

    def _plan(self, lay, fb: dict) -> None:
        bits = []
        for f in lay.fields:
            bits += [fb[f]] * int(np.prod(lay.shapes[f]))
        self.bits = np.asarray(bits, np.int64)          # [W]
        self.start = np.concatenate(([0], np.cumsum(self.bits)[:-1]))
        self.total_bits = int(self.bits.sum())
        self.W = lay.width
        self.P = (self.total_bits + 31) // 32           # packed words

    def pack(self, vec, xp):
        """``int32[..., W] -> int32[..., P]`` (uint32 bitstream in int32)."""
        if self.W > _LANE_TILE:
            return self._pack_rows(vec, xp)
        u = vec.astype(xp.uint32)
        words = [None] * self.P
        for w in range(self.W):
            b, s = int(self.bits[w]), int(self.start[w])
            v = u[..., w] & xp.uint32((1 << b) - 1)
            o, sh = s // 32, s % 32
            lowpart = (v << xp.uint32(sh)) if sh else v
            words[o] = lowpart if words[o] is None else words[o] | lowpart
            if sh + b > 32:                      # straddles two words
                spill = v >> xp.uint32(32 - sh)
                words[o + 1] = spill if words[o + 1] is None \
                    else words[o + 1] | spill
        zero = xp.zeros_like(u[..., 0])
        cols = [zero if c is None else c for c in words]
        return xp.stack(cols, axis=-1).astype(xp.int32)

    def _pack_rows(self, vec, xp):
        """:meth:`pack` for a row wider than one 128-lane tile, word by
        word over whole rows: every element masked and shifted where it
        lies, then one masked sum over the row an output word (the fields
        of a word share no bit, so the sum is the or).  The column form
        above slices W single columns off the row; past one tile the TPU
        compiler stops fusing those slices and lays each out as an
        ``[N, 1]`` array padded to a tile — at W = 153 that was 72 of a
        chunk step's 73 ms and 2 GB of temporaries (PERF.md section 6,
        PR 43) — and forces the successors into a row-minor layout that
        the fingerprint then pays for too.  Same bits, either way
        (tests/test_ddd_paxos.py)."""
        u = vec.astype(xp.uint32)
        word, sh = self.start // 32, (self.start % 32).astype(np.uint32)
        straddles = (self.start % 32 + self.bits) > 32
        v = u & ((np.uint64(1) << self.bits.astype(np.uint64)) - 1) \
            .astype(np.uint32)
        low = v << sh
        spill = v >> np.where(straddles, 32 - sh, 0).astype(np.uint32)
        zero = xp.uint32(0)
        cols = []
        for o in range(self.P):
            part = xp.where(word == o, low, zero)
            if straddles[word == o - 1].any():
                part = part | xp.where((word == o - 1) & straddles, spill,
                                       zero)
            cols.append(xp.sum(part, axis=-1, dtype=xp.uint32))
        return xp.stack(cols, axis=-1).astype(xp.int32)

    def unpack(self, packed, xp):
        """``int32[..., P] -> int32[..., W]``."""
        u = packed.astype(xp.uint32)
        return xp.stack([self._word(u, w, xp) for w in range(self.W)],
                        axis=-1).astype(xp.int32)

    def unpack_word(self, packed, w: int, xp):
        """``int32[..., P] -> int32[...]``: element ``w`` of the flat
        vector alone."""
        return self._word(packed.astype(xp.uint32), w, xp).astype(xp.int32)

    def _word(self, u, w: int, xp):
        b, s = int(self.bits[w]), int(self.start[w])
        o, sh = s // 32, s % 32
        v = u[..., o] >> xp.uint32(sh) if sh else u[..., o]
        if sh + b > 32:
            v = v | (u[..., o + 1] << xp.uint32(32 - sh))
        return v & xp.uint32((1 << b) - 1)
