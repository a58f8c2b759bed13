# COPY of raft_tla_tpu/frontend/schema.py at commit 51d3f6c (PR 23): the benchmark's frozen plain reference.
# Only the import lines were rewritten and the analysis hooks (envelope, check_schema) cut; it imports nothing of raft_tla_tpu.
"""Declared tensor state schemas — the frontend's model-independent core.

A :class:`Schema` is the declaration a spec makes about its state: a
tuple of small-int tensor fields with symbolic shapes and value ranges.
Resolving it against a :class:`~raft_tla_tpu.config.Bounds` yields a
:class:`SchemaLayout`, which duck-types ``ops/state.Layout`` (``shapes``
/ ``fields`` / ``width``) and carries the generic pack/unpack between
the struct-of-arrays form the kernels use and the flat ``[W]`` int32
vector the engines dedup and store.

The declared ranges are what upgrade speclint from a Raft artifact into
a compiler property: :func:`envelope` hands the width analyzer an
interval per field straight from the declaration, and
:func:`check_schema` is the admission-time validity gate for non-Raft
specs (shape sanity, range sanity, int32 headroom).
"""

from __future__ import annotations

import dataclasses

import numpy as np

I32 = np.int32

# Symbolic dimension / bound names resolve against Bounds attributes;
# the short forms mirror the letters ops/state.Layout uses.
_DIM_ALIASES = {"n": "n_servers", "L": "log_cap", "S": "msg_cap",
                "E": "elections_cap", "V": "n_values"}


def _resolve(sym, bounds) -> int:
    """An int stands for itself; a string names a Bounds attribute
    (aliases above); a callable is evaluated on bounds."""
    if isinstance(sym, int):
        return sym
    if callable(sym):
        return int(sym(bounds))
    return int(getattr(bounds, _DIM_ALIASES.get(sym, sym)))


@dataclasses.dataclass(frozen=True)
class Field:
    """One state variable: a small-int tensor with a declared shape and
    value range.

    ``shape`` entries are ints or symbolic dimension names (``"n"`` =
    ``n_servers``, ``"L"`` = ``log_cap``, ``"S"`` = ``msg_cap``); an
    empty shape is a scalar carried as one vector word.  ``lo``/``hi``
    declare the inclusive value range (``hi`` may be symbolic), and
    ``init`` is the uniform initial value.
    """
    name: str
    shape: tuple = ()
    lo: int = 0
    hi: object = 0
    init: int = 0


@dataclasses.dataclass(frozen=True)
class Schema:
    """A named tuple of fields; the unit the frontend compiles against."""
    name: str
    fields: tuple

    def __post_init__(self):
        seen = set()
        for f in self.fields:
            if f.name in seen:
                raise ValueError(
                    f"schema {self.name!r}: duplicate field {f.name!r}")
            seen.add(f.name)

    def field(self, name: str) -> Field:
        for f in self.fields:
            if f.name == name:
                return f
        raise KeyError(f"schema {self.name!r} has no field {name!r}")

    @property
    def field_names(self) -> tuple:
        return tuple(f.name for f in self.fields)

    def layout(self, bounds) -> "SchemaLayout":
        return SchemaLayout(self, bounds)


class SchemaLayout:
    """Schema resolved against concrete bounds.

    Duck-types ``ops/state.Layout`` where the engines need it: a
    ``shapes`` dict (field -> concrete shape, declaration order), a
    ``fields`` tuple, and the flat vector ``width``.
    """

    def __init__(self, schema: Schema, bounds):
        self.schema = schema
        self.bounds = bounds
        self.shapes = {f.name: tuple(_resolve(d, bounds) for d in f.shape)
                       for f in schema.fields}

    @property
    def fields(self) -> tuple:
        return tuple(self.shapes)

    @property
    def width(self) -> int:
        return sum(int(np.prod(s, dtype=np.int64)) if s else 1
                   for s in self.shapes.values())

    def init_struct(self, xp=np):
        """The (single) initial state as a struct of arrays."""
        out = {}
        for f in self.schema.fields:
            shp = self.shapes[f.name]
            out[f.name] = (xp.full(shp, f.init, dtype=I32) if shp
                           else xp.asarray(f.init, dtype=I32))
        return out

    def pack(self, struct, xp):
        """Struct of arrays -> flat int32 vector(s).  Arrays may carry
        arbitrary leading batch dims; trailing dims must match the
        declared shapes (scalars get one word)."""
        parts = []
        for name, shp in self.shapes.items():
            a = xp.asarray(struct[name])
            k = int(np.prod(shp, dtype=np.int64)) if shp else 1
            lead = a.shape[:len(a.shape) - len(shp)]
            parts.append(xp.reshape(a, lead + (k,)))
        return xp.concatenate(parts, axis=-1).astype(I32)

    def unpack(self, vec, xp):
        """Flat int32 vector(s) -> struct of arrays (leading batch dims
        preserved) — the inverse of :meth:`pack`."""
        out, off = {}, 0
        for name, shp in self.shapes.items():
            k = int(np.prod(shp, dtype=np.int64)) if shp else 1
            sl = vec[..., off:off + k]
            out[name] = xp.reshape(sl, vec.shape[:-1] + shp) if shp \
                else xp.reshape(sl, vec.shape[:-1])
            off += k
        return out
