"""The action-definition IR — guards, per-field updates, bag ops.

An :class:`ActionDef` describes one action family of a spec as data:
scalar guard/value expressions over the state struct, one-hot field
updates, and message-bag operations.  Two independent consumers compile
it:

- ``frontend/actions.py`` lowers it to a batched kernel with the exact
  ``(bounds, s, *params) -> (out, valid, ovf)`` contract
  ``ops/kernels.grouped_dispatch`` expects.  The lowering calls the SAME
  helper functions the hand-written kernels use (``_set1``/``_set2``/
  ``bag_add``/``_tree_select``/msgbits accessors), so equal IR semantics
  produce bit-identical lanes — the Raft parity guarantee is structural,
  not coincidental.
- ``frontend/widthgen.py`` abstract-interprets the same tree over the
  interval domain (``analysis/intervals``) to *generate* speclint's
  Pass-1 transfer twins, cross-checked against the hand-written ones.

Expression values are scalars (per-action-instance) or, since Paxos, small
arrays over a field's axes: :class:`Sel` reads a field with some axes
selected and the rest kept, :class:`ConstTab` reads a constant table the
schema declares (``Quorum``), :class:`Reduce` folds an axis (``any`` /
``all`` / ``max`` / ``min``), :class:`Exists` is the bounded existential
over a table's rows (``\\E Q \\in Quorum``), and the arithmetic and
comparison nodes broadcast.  Array effects live in the Update/Bag nodes
(:class:`SetAt` writes one cell at computed indices).  Every node carries
both a concrete evaluator (``ev``) and an interval transfer (``iv``);
:class:`Intrinsic` is the escape hatch for aggregations the language cannot
express (e.g. Raft's quorum-max-agree) — a compiler builtin with a declared
transfer, exactly like the relational ``facts`` a :class:`PackMsg` may
declare.
"""

from __future__ import annotations

import dataclasses

from raft_tla_tpu.analysis import intervals as iv

BOOL, INT = "bool", "int"


class Infeasible(Exception):
    """Raised during interval evaluation when a branch cannot execute
    under the current message envelope / guard refinement (e.g. a
    MsgField read of an mtype no creation site produces).  widthgen
    skips the branch — mirroring the hand twins' ``if rec is not None``
    structure."""


class Ctx:
    """Concrete evaluation context: one action instance on one state."""

    __slots__ = ("bounds", "s", "params", "xp", "consts", "_msg")

    def __init__(self, bounds, s, params, xp, consts=None):
        self.bounds, self.s, self.params, self.xp = bounds, s, params, xp
        self.consts = consts or {}      # the schema's bound constant tables
        self._msg = None

    def bind(self, name, value) -> "Ctx":
        """This context with one more parameter (a quantifier's bound
        variable)."""
        return Ctx(self.bounds, self.s, {**self.params, name: value},
                   self.xp, self.consts)

    def msg_words(self):
        """(msgHi[slot], msgLo[slot]) of the instance's ``slot`` param."""
        if self._msg is None:
            slot = self.params["slot"]
            self._msg = (self.s["msgHi"][slot], self.s["msgLo"][slot])
        return self._msg


class IvCtx:
    """Abstract evaluation context for widthgen: the expansion envelope,
    the message envelope, per-param declared intervals, and the active
    branch's mtype scope for MsgField reads."""

    __slots__ = ("bounds", "env", "menv", "param_iv", "mtype", "const_iv")

    def __init__(self, bounds, env, menv, param_iv, mtype=None,
                 const_iv=None):
        self.bounds = bounds
        self.env = env
        self.menv = menv
        self.param_iv = param_iv
        self.mtype = mtype
        self.const_iv = const_iv or {}  # constant table -> declared interval


# ---------------------------------------------------------------------------
# Scalar expressions


@dataclasses.dataclass(frozen=True)
class Lit:
    v: object                     # int or bool

    def ev(self, ctx):
        return self.v

    def iv(self, ictx):
        if isinstance(self.v, bool):
            return iv.BOOL if self.v else iv.const(0)
        return iv.const(self.v)


@dataclasses.dataclass(frozen=True)
class Dim:
    """A bounds-derived static integer (``n_servers``, ``log_cap``, ...);
    evaluates to a Python int so it can parameterize shapes/clips."""
    name: str

    def ev(self, ctx):
        return int(getattr(ctx.bounds, self.name))

    def iv(self, ictx):
        return iv.const(int(getattr(ictx.bounds, self.name)))


@dataclasses.dataclass(frozen=True)
class Param:
    name: str

    def ev(self, ctx):
        return ctx.params[self.name]

    def iv(self, ictx):
        return ictx.param_iv[self.name]


@dataclasses.dataclass(frozen=True)
class Get:
    """State read ``s[field][idx...]`` (0, 1 or 2 scalar indices)."""
    field: str
    idx: tuple = ()

    def ev(self, ctx):
        a = ctx.s[self.field]
        if not self.idx:
            return a
        if len(self.idx) == 1:
            return a[self.idx[0].ev(ctx)]
        return a[tuple(e.ev(ctx) for e in self.idx)]

    def iv(self, ictx):
        return ictx.env[self.field]


# evaluator / interval-transfer tables per op code; "and"/"or" are the
# logical forms (BOOL), "band"/"bor" the bitwise forms (value intervals)
_EV = {
    "+": lambda a, b: a + b,
    "-": lambda a, b: a - b,
    "*": lambda a, b: a * b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
    "and": lambda a, b: a & b,
    "or": lambda a, b: a | b,
    "band": lambda a, b: a & b,
    "bor": lambda a, b: a | b,
    "<<": lambda a, b: a << b,
    ">>": lambda a, b: a >> b,
    "//": lambda a, b: a // b,
    "%": lambda a, b: a % b,
}


def _iv_div(op):
    """Transfer of ``//`` / ``%`` for a non-negative dividend and a positive
    divisor (what an index computation needs); anything else is refused
    rather than bounded wrongly."""
    def transfer(a, b):
        if a.lo < 0 or b.lo < 1:
            raise ValueError(f"{op!r} takes a non-negative dividend and a "
                             f"positive divisor, got {a} {op} {b}")
        if op == "//":
            return iv.Interval(a.lo // b.hi, a.hi // b.lo)
        return iv.Interval(0, min(a.hi, b.hi - 1))
    return transfer


_IV = {
    "+": lambda a, b: a + b,
    "-": lambda a, b: a - b,
    "*": lambda a, b: iv.Interval(a.lo * b.lo, a.hi * b.hi),
    "band": lambda a, b: iv.Interval(0, min(a.hi, b.hi)),
    "bor": lambda a, b: a.or_(b),
    "<<": lambda a, b: iv.Interval(a.lo << b.lo, a.hi << b.hi),
    ">>": lambda a, b: iv.Interval(a.lo >> b.hi, a.hi >> b.lo),
    "//": _iv_div("//"),
    "%": _iv_div("%"),
}


@dataclasses.dataclass(frozen=True)
class Bin:
    op: str
    a: object
    b: object

    def ev(self, ctx):
        return _EV[self.op](self.a.ev(ctx), self.b.ev(ctx))

    def iv(self, ictx):
        if self.op in ("==", "!=", "<", "<=", ">", ">=", "and", "or"):
            return iv.BOOL
        return _IV[self.op](self.a.iv(ictx), self.b.iv(ictx))


@dataclasses.dataclass(frozen=True)
class Not:
    a: object

    def ev(self, ctx):
        return ~self.a.ev(ctx)

    def iv(self, ictx):
        return iv.BOOL


@dataclasses.dataclass(frozen=True)
class Where:
    c: object
    a: object
    b: object

    def ev(self, ctx):
        return ctx.xp.where(self.c.ev(ctx), self.a.ev(ctx), self.b.ev(ctx))

    def iv(self, ictx):
        return self.a.iv(ictx).join(self.b.iv(ictx))


@dataclasses.dataclass(frozen=True)
class Clip:
    a: object
    lo: object
    hi: object

    def ev(self, ctx):
        return ctx.xp.clip(self.a.ev(ctx), self.lo.ev(ctx), self.hi.ev(ctx))

    def iv(self, ictx):
        a = self.a.iv(ictx)
        lo, hi = self.lo.iv(ictx), self.hi.iv(ictx)
        return iv.Interval(max(a.lo, lo.lo), min(a.hi, hi.hi))


@dataclasses.dataclass(frozen=True)
class MinE:
    a: object
    b: object

    def ev(self, ctx):
        return ctx.xp.minimum(self.a.ev(ctx), self.b.ev(ctx))

    def iv(self, ictx):
        return self.a.iv(ictx).min_(self.b.iv(ictx))


@dataclasses.dataclass(frozen=True)
class MaxE:
    a: object
    b: object

    def ev(self, ctx):
        return ctx.xp.maximum(self.a.ev(ctx), self.b.ev(ctx))

    def iv(self, ictx):
        return self.a.iv(ictx).max_(self.b.iv(ictx))


@dataclasses.dataclass(frozen=True)
class Popcount:
    a: object

    def ev(self, ctx):
        from raft_tla_tpu.ops.kernels import _popcount
        return _popcount(self.a.ev(ctx))

    def iv(self, ictx):
        return iv.Interval(0, max(self.a.iv(ictx).hi.bit_length(), 1))


@dataclasses.dataclass(frozen=True)
class LastTerm:
    """``LastTerm(log[i])`` (raft.tla:102) — a builtin: 0 on an empty
    log, else the stored tail term."""
    i: object

    def ev(self, ctx):
        from raft_tla_tpu.ops.kernels import _last_term
        return _last_term(ctx.s, self.i.ev(ctx))

    def iv(self, ictx):
        return ictx.env["logTerm"].join(0)


@dataclasses.dataclass(frozen=True)
class MsgField:
    """Subfield read of the current ``slot``'s packed message words.

    Abstractly this reads the message envelope: scoped to the enclosing
    branch's ``mtype`` when set, else joined across every record that
    carries the subfield (the UpdateTerm shape).  No carrying record =>
    the branch is infeasible under this envelope."""
    name: str

    def ev(self, ctx):
        from raft_tla_tpu.ops import msgbits as mb
        hi, lo = ctx.msg_words()
        acc = {"mtype": (mb.mtype, 0), "mterm": (mb.mterm, 0),
               "a": (mb.fa, 0), "b": (mb.fb, 0), "src": (mb.src, 0),
               "dst": (mb.dst, 0), "c": (mb.fc, 1), "d": (mb.fd, 1),
               "e": (mb.fe, 1), "f": (mb.ff, 1), "g": (mb.fg, 1)}
        fn, word = acc[self.name]
        return fn(lo if word else hi)

    def iv(self, ictx):
        if ictx.mtype is not None:
            rec = ictx.menv.get(ictx.mtype)
            if rec is None or self.name not in rec:
                raise Infeasible(self.name)
            return rec[self.name]
        vals = [rec[self.name] for rec in ictx.menv.values()
                if self.name in rec]
        if not vals:
            raise Infeasible(self.name)
        out = vals[0]
        for v in vals[1:]:
            out = out.join(v)
        return out


@dataclasses.dataclass(frozen=True)
class Intrinsic:
    """Compiler builtin: an aggregation the scalar IR cannot express,
    with a declared interval transfer (the IR analog of a declared
    relational fact — widthgen uses ``ivfn(bounds, env)`` verbatim)."""
    name: str
    fn: object        # (bounds, s, params, xp) -> value
    ivfn: object      # (bounds, env) -> Interval

    def ev(self, ctx):
        return self.fn(ctx.bounds, ctx.s, ctx.params, ctx.xp)

    def iv(self, ictx):
        return self.ivfn(ictx.bounds, ictx.env)


# ---------------------------------------------------------------------------
# Array-valued expressions: reads over a field's axes, constant tables,
# reductions and the bounded existential


def _is_jnp(xp) -> bool:
    return xp.__name__ == "jax.numpy"


def _take(a, i, axis: int, xp):
    """``a`` with ``axis`` selected at the scalar ``i`` (one-hot form, like
    ``kernels._set1``: an elementwise mask and a fold, no gather, so a
    traced or vmapped ``i`` costs what a constant one does)."""
    shape = [1] * a.ndim
    shape[axis] = a.shape[axis]
    hot = xp.reshape(xp.arange(a.shape[axis]), shape) == i
    return xp.sum(xp.where(hot, a, 0), axis=axis, dtype=a.dtype)


@dataclasses.dataclass(frozen=True)
class Sel:
    """Array-valued state read: ``idx`` has one entry an axis of the field,
    ``None`` to keep the axis and a scalar expression to select it
    (``msg1b[:, b, :]`` is ``Sel("msg1b", (None, b, None))``)."""
    field: str
    idx: tuple

    def ev(self, ctx):
        a = ctx.s[self.field]
        if len(self.idx) != a.ndim:
            raise ValueError(f"Sel({self.field!r}): {len(self.idx)} "
                             f"indices for {a.ndim} axes")
        for axis in reversed(range(a.ndim)):
            if self.idx[axis] is not None:
                a = _take(a, self.idx[axis].ev(ctx), axis, ctx.xp)
        return a

    def iv(self, ictx):
        return ictx.env[self.field]


@dataclasses.dataclass(frozen=True)
class ConstTab:
    """Array-valued read of a constant table the schema declares
    (``schema.Const``), as the run bound it."""
    name: str

    def ev(self, ctx):
        return ctx.xp.asarray(ctx.consts[self.name])

    def iv(self, ictx):
        return ictx.const_iv[self.name]


@dataclasses.dataclass(frozen=True)
class Iota:
    """``0..n-1`` along one axis; ``n`` is static (a ``Lit`` / ``Dim``
    expression)."""
    n: object

    def ev(self, ctx):
        return ctx.xp.arange(int(self.n.ev(ctx)))

    def iv(self, ictx):
        return iv.Interval(0, max(self.n.iv(ictx).hi - 1, 0))


@dataclasses.dataclass(frozen=True)
class Lift:
    """``a`` with ``k`` unit axes appended, so that it broadcasts along the
    leading axes of a wider operand (``inQ[a]`` against ``m[a, k]``)."""
    a: object
    k: int = 1

    def ev(self, ctx):
        v = ctx.xp.asarray(self.a.ev(ctx))
        return ctx.xp.reshape(v, v.shape + (1,) * self.k)

    def iv(self, ictx):
        return self.a.iv(ictx)


_REDUCERS = ("any", "all", "max", "min")


@dataclasses.dataclass(frozen=True)
class Reduce:
    """Fold an array over ``axis`` (``None``: every axis) with ``any`` /
    ``all`` (of booleans) or ``max`` / ``min`` (of integers)."""
    fn: str
    a: object
    axis: object = None

    def __post_init__(self):
        if self.fn not in _REDUCERS:
            raise ValueError(f"unknown reducer {self.fn!r} (known: "
                             f"{', '.join(_REDUCERS)})")

    def ev(self, ctx):
        return getattr(ctx.xp, self.fn)(self.a.ev(ctx), axis=self.axis)

    def iv(self, ictx):
        a = self.a.iv(ictx)
        return iv.BOOL if self.fn in ("any", "all") else a


@dataclasses.dataclass(frozen=True)
class Exists:
    """``\\E var \\in table : body``, ``table`` an array with one row an
    element (a :class:`ConstTab`): ``body`` is evaluated with ``var`` bound
    to each row in turn (``Param(var)`` reads it) and the results are
    or-ed.  The table's length is static, so the existential is unrolled."""
    var: str
    table: object
    body: object

    def ev(self, ctx):
        rows = self.table.ev(ctx)
        out = None
        for q in range(rows.shape[0]):
            hit = self.body.ev(ctx.bind(self.var, rows[q]))
            out = hit if out is None else (out | hit)
        return out

    def iv(self, ictx):
        inner = IvCtx(ictx.bounds, ictx.env, ictx.menv,
                      {**ictx.param_iv, self.var: self.table.iv(ictx)},
                      ictx.mtype, ictx.const_iv)
        self.body.iv(inner)         # an infeasible body is the caller's
        return iv.BOOL


@dataclasses.dataclass(frozen=True)
class Scope:
    """``a`` lowered under a named scope (``jax.named_scope``: metadata
    for the device trace, no computation), so that a profile splits it
    from the stage it is nested in."""
    name: str
    a: object

    def ev(self, ctx):
        if not _is_jnp(ctx.xp):
            return self.a.ev(ctx)
        import jax
        with jax.named_scope(self.name):
            return self.a.ev(ctx)

    def iv(self, ictx):
        return self.a.iv(ictx)


# ---------------------------------------------------------------------------
# Field updates (array effects; values read the PRE-state, like the
# functional hand kernels)


@dataclasses.dataclass(frozen=True)
class Set1:
    """``field[i] := val`` (optionally only when ``cond``); the hand
    kernels' ``_set1``/conditional-``_set1`` idiom."""
    field: str
    i: object
    val: object
    cond: object = None


@dataclasses.dataclass(frozen=True)
class SetRow:
    """``field[i][*] := val`` — whole row to a scalar (``_set_row``)."""
    field: str
    i: object
    val: object


@dataclasses.dataclass(frozen=True)
class Set2:
    """``field[i][j] := val`` (optionally only when ``cond``) — one cell
    of a 2-D field (``_set2``; the log writes use j = a log index)."""
    field: str
    i: object
    j: object
    val: object
    cond: object = None


@dataclasses.dataclass(frozen=True)
class SetAt:
    """``field[idx...] := val`` (optionally only when ``cond``): one cell
    of a field of any rank, every index a scalar expression (a message
    flag at the indices the message's fields compute)."""
    field: str
    idx: tuple
    val: object
    cond: object = None


# ---------------------------------------------------------------------------
# Bag / message ops (applied after the field updates, in order)


@dataclasses.dataclass(frozen=True)
class PackMsg:
    """One packed-record creation site.  ``fields`` maps msgbits
    subfield names to scalar exprs (missing names pack as 0); ``facts``
    declares relational facts ((name, (bounds, env, menv) -> Interval))
    that join into the message envelope but are not packed — e.g. the
    AppendEntriesRequest ``a+c`` bound; ``overrides`` replaces a
    subfield's *derived* interval with an envelope fact by name (the
    done-reply's ``b`` echoes ``a+c``)."""
    mtype: int
    fields: tuple                 # ((name, Expr), ...)
    facts: tuple = ()             # ((name, fn), ...)
    overrides: tuple = ()         # ((field, fact_name_in_menv), ...)


@dataclasses.dataclass(frozen=True)
class BagAdd:
    msg: PackMsg


@dataclasses.dataclass(frozen=True)
class BagRemove:
    """Remove the current ``slot``'s message (WithoutMessage)."""


@dataclasses.dataclass(frozen=True)
class Reply:
    """Remove the current ``slot``'s message, add the response
    (``kernels.reply``: remove-first, overflow on the final bag)."""
    msg: PackMsg


# ---------------------------------------------------------------------------
# Branches and actions


@dataclasses.dataclass(frozen=True)
class Branch:
    """One guarded alternative.  ``guard=None`` only in single-branch
    actions (updates apply unconditionally; validity masks downstream).
    ``mtype`` scopes MsgField reads for widthgen; ``refines`` declares
    guard-implied envelope refinements ((field, lo, hi) meets — an
    empty meet marks the branch infeasible); ``overflow`` is an extra
    overflow condition OR'd with the branch's bag overflows."""
    guard: object = None
    updates: tuple = ()
    ops: tuple = ()
    overflow: object = None
    mtype: object = None
    refines: tuple = ()


@dataclasses.dataclass(frozen=True)
class ActionDef:
    """One action family: parameter names (kernel argument order),
    validity, and ordered branches (``_tree_select`` order — guards must
    be exclusive).  ``any_guard_valid`` AND-joins ``valid`` with "some
    branch fired" (the Receive shape).  ``param_iv`` declares per-param
    intervals for widthgen ((name, fn(bounds) -> Interval))."""
    family: str
    params: tuple
    valid: object
    branches: tuple
    param_iv: tuple = ()
    any_guard_valid: bool = False

    def __post_init__(self):
        if len(self.branches) > 1:
            for br in self.branches:
                if br.guard is None:
                    raise ValueError(
                        f"{self.family}: multi-branch actions need a "
                        "guard on every branch")
