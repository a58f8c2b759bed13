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
class Const:
    """One constant table of the model (a TLA+ ``CONSTANT`` that is not a
    plain set size): a small-int tensor with a declared shape and value
    range, no part of the state.  Its values are bound for a run
    (``Bounds.constants``, from the cfg) and held to the declaration by
    :meth:`Schema.bind_consts`.  A ``"*"`` in ``shape`` is a free length,
    taken from the bound value (the number of quorums)."""
    name: str
    shape: tuple = ()
    lo: int = 0
    hi: object = 0


@dataclasses.dataclass(frozen=True)
class Schema:
    """A named tuple of fields, and of the constant tables the actions and
    invariants read beside them; the unit the frontend compiles against."""
    name: str
    fields: tuple
    consts: tuple = ()

    def __post_init__(self):
        seen = set()
        for f in self.fields + self.consts:
            if f.name in seen:
                raise ValueError(
                    f"schema {self.name!r}: duplicate field {f.name!r}")
            seen.add(f.name)

    def bind_consts(self, bounds, values) -> dict:
        """``{name: int32 array}`` for every declared constant table, from
        ``values`` (a mapping, or ``Bounds.constants``' pairs); a
        missing or undeclared table, a shape or a value outside the
        declaration is refused by name."""
        values = dict(values)
        extra = sorted(set(values) - {c.name for c in self.consts})
        if extra:
            raise ValueError(f"schema {self.name!r} declares no constant "
                             f"{extra[0]!r}")
        out = {}
        for c in self.consts:
            if c.name not in values:
                raise ValueError(f"schema {self.name!r}: constant "
                                 f"{c.name!r} is not bound")
            a = np.asarray(values[c.name], dtype=np.int64)
            want = tuple(d if d == "*" else _resolve(d, bounds)
                         for d in c.shape)
            if a.ndim != len(want) or a.size == 0 or any(
                    w != "*" and w != d for w, d in zip(want, a.shape)):
                raise ValueError(
                    f"schema {self.name!r}: constant {c.name!r} has shape "
                    f"{a.shape}, declared {want}")
            hi = _resolve(c.hi, bounds)
            if a.min() < c.lo or a.max() > hi:
                raise ValueError(
                    f"schema {self.name!r}: constant {c.name!r} holds "
                    f"{int(a.min())}..{int(a.max())}, declared "
                    f"[{c.lo}, {hi}]")
            out[c.name] = a.astype(I32)
        return out

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


def envelope(schema: Schema, bounds) -> dict:
    """Field -> declared value interval — the width analyzer's input for
    schema-declared specs (the analog of ``intervals.envelope`` for
    Raft's hand-declared table)."""
    from raft_tla_tpu.analysis.intervals import Interval
    return {f.name: Interval(f.lo, _resolve(f.hi, bounds))
            for f in schema.fields}


def const_envelope(schema: Schema, bounds) -> dict:
    """Constant table -> declared value interval (what a read of the table
    abstracts to, whatever a run binds)."""
    from raft_tla_tpu.analysis.intervals import Interval
    return {c.name: Interval(c.lo, _resolve(c.hi, bounds))
            for c in schema.consts}


def check_schema(schema: Schema, bounds) -> list:
    """Admission-time validity findings for a schema at these bounds
    (lint-style: a list of ``analysis.report.Finding``, empty = clean).

    Checks shape positivity, range sanity, and int32 headroom — the
    declared analog of the Raft packed-width proof: a declared range the
    vector words cannot carry is rejected before any device time.
    """
    from raft_tla_tpu.analysis import report
    findings = []
    lay = schema.layout(bounds)
    for f in schema.fields:
        shp = lay.shapes[f.name]
        if any(d <= 0 for d in shp):
            findings.append(report.Finding(
                report.WIDTH, report.ERROR, "schema-empty-dim",
                f"field {f.name!r} resolves to shape {shp} at these "
                f"bounds", field=f.name))
        hi = _resolve(f.hi, bounds)
        if hi < f.lo:
            findings.append(report.Finding(
                report.WIDTH, report.ERROR, "schema-empty-range",
                f"field {f.name!r} declares empty range "
                f"[{f.lo}, {hi}]", field=f.name))
        if f.lo < -(1 << 31) or hi > (1 << 31) - 1:
            findings.append(report.Finding(
                report.WIDTH, report.ERROR, "schema-i32-overflow",
                f"field {f.name!r} range [{f.lo}, {hi}] exceeds the "
                f"int32 state words", field=f.name))
        if not (f.lo <= f.init <= hi):
            findings.append(report.Finding(
                report.WIDTH, report.ERROR, "schema-init-range",
                f"field {f.name!r} init {f.init} outside declared "
                f"range [{f.lo}, {hi}]", field=f.name))
    return findings
