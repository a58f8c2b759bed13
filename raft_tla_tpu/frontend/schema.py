"""Declared tensor state schemas — the frontend's model-independent core.

A :class:`Schema` is the declaration a spec makes about its state: a
tuple of small-int tensor fields with symbolic shapes and value ranges.
Resolving it against a :class:`~raft_tla_tpu.config.Bounds` yields a
:class:`SchemaLayout`, which duck-types ``ops/state.Layout`` (``shapes``
/ ``fields`` / ``width``) and carries the generic pack/unpack between
the struct-of-arrays form the kernels use and the flat ``[W]`` int32
vector the engines dedup and store.

A schema may also declare its **symmetric sorts** (:class:`Sort`: a set
of model values the spec never tells apart, and the dimension it ranges
over), and a field which of its axes a sort indexes and whether its
contents are members of one (:class:`Over`).  That is all
``ops/symmetry`` needs to permute a struct and to key an orbit; a schema
that declares none is keyed as it always was.

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
class Sort:
    """A symmetric sort: a set of model values the spec's actions and
    invariants never tell apart (TLC's ``Permutations(Acceptor)``), named
    as the cfg's SYMMETRY stanza names it, and the dimension its members
    number (``Acceptor`` <-> ``"n"``)."""
    name: str
    dim: object


@dataclasses.dataclass(frozen=True)
class Over:
    """How the indices of one axis, or the contents of a field, follow a
    sort: the first ``fixed`` are no members of it and stay (``None`` =
    0), and past them ``i - fixed = outer * |sort| + member``, of which a
    permutation ``p`` of the sort moves the member alone.  A plain axis
    over the sort is ``Over(sort)``; ``maxVal`` (0 = None, else 1 + the
    value) holds ``Over("Value", fixed=1)``; so does the pair axis of a
    "1b" flag (0 = (-1, None), else ``1 + mbal * |Value| + value``)."""
    sort: str
    fixed: int = 0

    def image(self, p: tuple, size: int) -> np.ndarray:
        """``int64[size]``: where each index (or content value) goes
        under the permutation ``p`` of the sort."""
        k = len(p)
        if size < self.fixed or (size - self.fixed) % k:
            raise ValueError(
                f"{size} indices do not hold {self.fixed} fixed and whole "
                f"copies of the {k} members of {self.sort!r}")
        i = np.arange(size - self.fixed)
        return np.concatenate([
            np.arange(self.fixed),
            self.fixed + (i // k) * k + np.asarray(p, np.int64)[i % k]])


@dataclasses.dataclass(frozen=True)
class Field:
    """One state variable: a small-int tensor with a declared shape and
    value range.

    ``shape`` entries are ints or symbolic dimension names (``"n"`` =
    ``n_servers``, ``"L"`` = ``log_cap``, ``"S"`` = ``msg_cap``); an
    empty shape is a scalar carried as one vector word.  ``lo``/``hi``
    declare the inclusive value range (``hi`` may be symbolic), and
    ``init`` is the uniform initial value.  ``axes`` says, an axis, which
    symmetric sort indexes it (``None``: none; empty: no axis of this
    field is), and ``content`` which sort the field's values are members
    of (:class:`Over`).
    """
    name: str
    shape: tuple = ()
    lo: int = 0
    hi: object = 0
    init: int = 0
    axes: tuple = ()
    content: Over | None = None

    def overs(self) -> tuple:
        """``((axis, Over), ...)`` of the axes a sort indexes."""
        return tuple((k, o) for k, o in enumerate(self.axes)
                     if o is not None)


@dataclasses.dataclass(frozen=True)
class Const:
    """One constant table of the model (a TLA+ ``CONSTANT`` that is not a
    plain set size): a small-int tensor with a declared shape and value
    range, no part of the state.  Its values are bound for a run
    (``Bounds.constants``, from the cfg) and held to the declaration by
    :meth:`Schema.bind_consts`.  A ``"*"`` in ``shape`` is a free length,
    taken from the bound value (the number of quorums).  ``axes`` as a
    :class:`Field`'s: an axis a symmetric sort indexes (a quorum's mask
    over ``Acceptor``), so that a table some permutation of the sort does
    not map onto itself can be refused (:meth:`Schema.variant_const`)."""
    name: str
    shape: tuple = ()
    lo: int = 0
    hi: object = 0
    axes: tuple = ()

    overs = Field.overs


@dataclasses.dataclass(frozen=True)
class Schema:
    """A named tuple of fields, and of the constant tables the actions and
    invariants read beside them; the unit the frontend compiles against."""
    name: str
    fields: tuple
    consts: tuple = ()
    sorts: tuple = ()

    def __post_init__(self):
        seen = set()
        for f in self.fields + self.consts:
            if f.name in seen:
                raise ValueError(
                    f"schema {self.name!r}: duplicate field {f.name!r}")
            seen.add(f.name)
        declared = {s.name for s in self.sorts}
        for f in self.fields + self.consts:
            if f.axes and len(f.axes) != len(f.shape):
                raise ValueError(
                    f"schema {self.name!r}: {f.name!r} names "
                    f"{len(f.axes)} axes, its shape has {len(f.shape)}")
            over = [o for _k, o in f.overs()]
            if getattr(f, "content", None) is not None:
                over.append(f.content)
            for o in over:
                if o.sort not in declared:
                    raise ValueError(
                        f"schema {self.name!r}: {f.name!r} follows the "
                        f"sort {o.sort!r}, which the schema does not "
                        "declare")

    @property
    def sort_names(self) -> tuple:
        return tuple(s.name for s in self.sorts)

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

    def variant_const(self, bounds, sorts: tuple):
        """The first ``(constant, sort)`` whose bound table some
        permutation of a sort of ``sorts`` that indexes one of its axes
        does **not** map onto itself (a free-length ``"*"`` axis lists a
        set: its order is immaterial): reducing by that sort would be
        unsound, and TLC does not check it.  ``None`` where every table
        is invariant."""
        import itertools
        tables = self.bind_consts(bounds, bounds.constants)
        sizes = self.layout(bounds).sort_sizes

        def as_set(c, a):
            if "*" not in c.shape:
                return a.tobytes()
            return frozenset(r.tobytes() for r in np.ascontiguousarray(
                np.moveaxis(a, c.shape.index("*"), 0)))

        for c in self.consts:
            a = tables[c.name]
            for axis, over in c.overs():
                if over.sort not in sorts:
                    continue
                for p in itertools.permutations(range(sizes[over.sort])):
                    back = np.argsort(over.image(p, a.shape[axis]))
                    if as_set(c, np.take(a, back, axis=axis)) \
                            != as_set(c, a):
                        return c.name, over.sort
        return None

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
        # each field's largest value, each symmetric sort's member count
        self.his = {f.name: _resolve(f.hi, bounds) for f in schema.fields}
        self.sort_sizes = {s.name: _resolve(s.dim, bounds)
                           for s in schema.sorts}

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
        # a sort's axis (or a field's contents) holds its fixed indices
        # and whole copies of the sort, or no permutation has an image
        spans = [(shp[k], o) for k, o in f.overs()]
        if f.content is not None:
            spans.append((hi + 1, f.content))
        for size, o in spans:
            k = lay.sort_sizes[o.sort]
            if size < o.fixed or (size - o.fixed) % k:
                findings.append(report.Finding(
                    report.WIDTH, report.ERROR, "schema-sort-span",
                    f"field {f.name!r}: {size} indices do not hold "
                    f"{o.fixed} fixed and whole copies of the {k} members "
                    f"of sort {o.sort!r}", field=f.name))
        if not (f.lo <= f.init <= hi):
            findings.append(report.Finding(
                report.WIDTH, report.ERROR, "schema-init-range",
                f"field {f.name!r} init {f.init} outside declared "
                f"range [{f.lo}, {hi}]", field=f.name))
    return findings
