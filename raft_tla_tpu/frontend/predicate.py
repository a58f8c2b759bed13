"""Quantifier-free boolean predicate compiler over a state schema.

Any loaded spec's INVARIANT stanza may name a registered invariant OR
write an expression directly; expressions compile here into the same
dual py/jnp probe shape the hand-written Raft invariants use (a scalar-
bool function of the struct-of-arrays state), so they ride the existing
vmapped invariant stack unchanged.

Grammar (TLA+ ASCII operators, loosest to tightest):

    expr   :=  impl
    impl   :=  or  ("=>" or)*                  -- right-associative
    or     :=  and ("\\/" and)*
    and    :=  not ("/\\" not)*
    not    :=  "~" not | cmp
    cmp    :=  sum (("=" | "/=" | "<=" | ">=" | "<" | ">") sum)?
    sum    :=  term (("+" | "-") term)*
    term   :=  unary ("*" unary)*
    unary  :=  "-" unary | atom
    atom   :=  INT | TRUE | FALSE | NAME | NAME "[" expr "]"
            |  ("any" | "all" | "count" | "min" | "max")
               "(" expr ["," ["-"] INT] ")"
            |  "dot" "(" expr "," expr ")"
            |  "(" expr ")"

NAME reads a schema field elementwise, or a constant table the spec's
schema declares (``Quorum``: bound when the predicate is compiled, no part
of the state); comparisons and arithmetic broadcast; a non-scalar boolean
result is implicitly universally quantified (``xp.all``) at the top — the
quantifier-free reading of TLA+'s ``\\A i \\in Server: P(i)``.  ``count``
sums a boolean array.  A reducer folds every axis, or the one axis its
second argument names; ``dot(A, B)`` contracts the last axis of ``A`` with
the first of ``B`` (``dot(Quorum, 1 - msg2b)[q, b, v]``: how many members
of quorum q have not voted for v in ballot b — the bounded reading of
``\\A a \\in Q``).

Everything is statically typed (BOOL vs INT) so malformed invariants
fail at admission with a position-carrying ValueError, never inside a
jit trace.
"""

from __future__ import annotations

import dataclasses
import re

BOOL, INT = "bool", "int"

_TOKEN = re.compile(r"""
    \s*(?:
      (?P<int>\d+)
    | (?P<name>[A-Za-z_]\w*)
    | (?P<op>=>|\\/|/\\|/=|<=|>=|[~=<>+\-*()\[\],])
    )""", re.VERBOSE)

_REDUCERS = ("any", "all", "count", "min", "max")
_CMP = {"=", "/=", "<", "<=", ">", ">="}

_IDENT = re.compile(r"[A-Za-z_]\w*\Z")


def is_expression(text: str) -> bool:
    """A bare identifier is a registered-invariant NAME; anything else
    (operators, brackets, digits-leading, ...) is an expression for this
    compiler.  One definition shared by cfgparse, cfglint, invariants,
    and serve admission so they can never disagree."""
    return _IDENT.match(text.strip()) is None


def _tokenize(text: str):
    toks, pos = [], 0
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if not m or m.end() == m.start():
            rest = text[pos:].lstrip()
            if not rest:
                break
            raise ValueError(
                f"predicate syntax error at column {pos + 1}: "
                f"unexpected {rest[:10]!r}")
        if m.lastgroup is not None:
            toks.append((m.lastgroup, m.group(m.lastgroup), m.start()))
        pos = m.end()
    toks.append(("end", "", len(text)))
    return toks


# ---------------------------------------------------------------------------
# AST — each node evaluates against a struct of arrays with xp in
# {numpy, jax.numpy} and reports its static type and field reads.

@dataclasses.dataclass(frozen=True)
class Lit:
    v: int
    kind: str = INT

    def ev(self, struct, xp):
        return self.v

    def reads(self):
        return frozenset()


@dataclasses.dataclass(frozen=True)
class Name:
    field: str
    kind: str = INT

    def ev(self, struct, xp):
        return struct[self.field]

    def reads(self):
        return frozenset((self.field,))


@dataclasses.dataclass(frozen=True)
class ConstArr:
    """A constant table of the spec's schema, bound at compile time
    (nested tuples, so the node stays hashable); reads no state field."""
    name: str
    value: tuple
    kind: str = INT

    def ev(self, struct, xp):
        return xp.asarray(self.value, dtype="int32")

    def reads(self):
        return frozenset()


@dataclasses.dataclass(frozen=True)
class Index:
    field: str
    idx: object
    kind: str = INT

    def ev(self, struct, xp):
        return struct[self.field][..., self.idx.ev(struct, xp)]

    def reads(self):
        return frozenset((self.field,)) | self.idx.reads()


@dataclasses.dataclass(frozen=True)
class Neg:
    a: object
    kind: str = INT

    def ev(self, struct, xp):
        return -self.a.ev(struct, xp)

    def reads(self):
        return self.a.reads()


@dataclasses.dataclass(frozen=True)
class Not:
    a: object
    kind: str = BOOL

    def ev(self, struct, xp):
        return xp.logical_not(self.a.ev(struct, xp))

    def reads(self):
        return self.a.reads()


_BIN_EV = {
    "+": lambda a, b, xp: a + b,
    "-": lambda a, b, xp: a - b,
    "*": lambda a, b, xp: a * b,
    "=": lambda a, b, xp: a == b,
    "/=": lambda a, b, xp: a != b,
    "<": lambda a, b, xp: a < b,
    "<=": lambda a, b, xp: a <= b,
    ">": lambda a, b, xp: a > b,
    ">=": lambda a, b, xp: a >= b,
    "/\\": lambda a, b, xp: xp.logical_and(a, b),
    "\\/": lambda a, b, xp: xp.logical_or(a, b),
    "=>": lambda a, b, xp: xp.logical_or(xp.logical_not(a), b),
}


@dataclasses.dataclass(frozen=True)
class Bin:
    op: str
    a: object
    b: object
    kind: str = INT

    def ev(self, struct, xp):
        return _BIN_EV[self.op](self.a.ev(struct, xp),
                                self.b.ev(struct, xp), xp)

    def reads(self):
        return self.a.reads() | self.b.reads()


@dataclasses.dataclass(frozen=True)
class Reduce:
    fn: str
    a: object
    kind: str = INT
    axis: object = None           # None: every axis

    def ev(self, struct, xp):
        v = self.a.ev(struct, xp)
        if self.fn == "any":
            return xp.any(v, axis=self.axis)
        if self.fn == "all":
            return xp.all(v, axis=self.axis)
        if self.fn == "count":
            # sum of a boolean array; int32 keeps it on the state dtype
            return xp.sum(xp.asarray(v, dtype="int32"), axis=self.axis)
        if self.fn == "min":
            return xp.min(v, axis=self.axis)
        return xp.max(v, axis=self.axis)

    def reads(self):
        return self.a.reads()


@dataclasses.dataclass(frozen=True)
class Dot:
    """``dot(a, b)``: the last axis of ``a`` contracted with the first of
    ``b``, as a broadcast product and a fold (small integer tables: no
    matrix unit is worth asking for)."""
    a: object
    b: object
    kind: str = INT

    def ev(self, struct, xp):
        a = xp.asarray(self.a.ev(struct, xp))
        b = xp.asarray(self.b.ev(struct, xp))
        wide = xp.reshape(a, a.shape + (1,) * (b.ndim - 1))
        return xp.sum(wide * b, axis=a.ndim - 1)

    def reads(self):
        return self.a.reads() | self.b.reads()


class _Parser:
    def __init__(self, text: str, fields=None, consts=None):
        self.text = text
        self.toks = _tokenize(text)
        self.i = 0
        self.fields = None if fields is None else tuple(fields)
        self.consts = dict(consts or {})

    def peek(self):
        return self.toks[self.i]

    def next(self):
        t = self.toks[self.i]
        self.i += 1
        return t

    def err(self, msg, tok=None):
        tok = tok or self.peek()
        return ValueError(f"predicate syntax error at column "
                          f"{tok[2] + 1}: {msg} (in {self.text!r})")

    def expect(self, op):
        t = self.next()
        if t[0] != "op" or t[1] != op:
            raise self.err(f"expected {op!r}, got {t[1] or 'end'!r}", t)

    def want_bool(self, node, ctx):
        if node.kind != BOOL:
            raise self.err(f"{ctx} needs a boolean operand")
        return node

    def want_int(self, node, ctx):
        if node.kind != INT:
            raise self.err(f"{ctx} needs an integer operand")
        return node

    def parse(self):
        node = self.impl()
        t = self.peek()
        if t[0] != "end":
            raise self.err(f"trailing input {t[1]!r}")
        return node

    def impl(self):
        left = self.or_()
        if self.peek()[:2] == ("op", "=>"):
            self.next()
            right = self.impl()                     # right-associative
            return Bin("=>", self.want_bool(left, "'=>'"),
                       self.want_bool(right, "'=>'"), BOOL)
        return left

    def or_(self):
        node = self.and_()
        while self.peek()[:2] == ("op", "\\/"):
            self.next()
            rhs = self.and_()
            node = Bin("\\/", self.want_bool(node, "'\\/'"),
                       self.want_bool(rhs, "'\\/'"), BOOL)
        return node

    def and_(self):
        node = self.not_()
        while self.peek()[:2] == ("op", "/\\"):
            self.next()
            rhs = self.not_()
            node = Bin("/\\", self.want_bool(node, "'/\\'"),
                       self.want_bool(rhs, "'/\\'"), BOOL)
        return node

    def not_(self):
        if self.peek()[:2] == ("op", "~"):
            self.next()
            return Not(self.want_bool(self.not_(), "'~'"))
        return self.cmp()

    def cmp(self):
        left = self.sum()
        t = self.peek()
        if t[0] == "op" and t[1] in _CMP:
            self.next()
            right = self.sum()
            return Bin(t[1], self.want_int(left, f"{t[1]!r}"),
                       self.want_int(right, f"{t[1]!r}"), BOOL)
        return left

    def sum(self):
        node = self.term()
        while self.peek()[0] == "op" and self.peek()[1] in ("+", "-"):
            op = self.next()[1]
            rhs = self.term()
            node = Bin(op, self.want_int(node, f"{op!r}"),
                       self.want_int(rhs, f"{op!r}"), INT)
        return node

    def term(self):
        node = self.unary()
        while self.peek()[:2] == ("op", "*"):
            self.next()
            rhs = self.unary()
            node = Bin("*", self.want_int(node, "'*'"),
                       self.want_int(rhs, "'*'"), INT)
        return node

    def unary(self):
        if self.peek()[:2] == ("op", "-"):
            self.next()
            return Neg(self.want_int(self.unary(), "unary '-'"))
        return self.atom()

    def atom(self):
        t = self.next()
        if t[0] == "int":
            return Lit(int(t[1]))
        if t[0] == "name":
            name = t[1]
            if name == "TRUE":
                return Lit(True, BOOL)
            if name == "FALSE":
                return Lit(False, BOOL)
            if name in _REDUCERS:
                self.expect("(")
                arg = self.impl()
                axis = None
                if self.peek()[:2] == ("op", ","):
                    self.next()
                    sign = 1
                    if self.peek()[:2] == ("op", "-"):
                        self.next()
                        sign = -1
                    t_ax = self.next()
                    if t_ax[0] != "int":
                        raise self.err("a reducer's axis is an integer "
                                       "literal", t_ax)
                    axis = sign * int(t_ax[1])
                self.expect(")")
                if name in ("any", "all"):
                    return Reduce(name, self.want_bool(arg, name), BOOL,
                                  axis)
                if name == "count":
                    return Reduce(name, self.want_bool(arg, name), INT,
                                  axis)
                return Reduce(name, self.want_int(arg, name), INT, axis)
            if name == "dot" and self.peek()[:2] == ("op", "("):
                self.next()
                lhs = self.want_int(self.impl(), "dot")
                self.expect(",")
                rhs = self.want_int(self.impl(), "dot")
                self.expect(")")
                return Dot(lhs, rhs)
            if name in self.consts:
                return ConstArr(name, _nested_tuple(self.consts[name]))
            if self.fields is not None and name not in self.fields:
                raise self.err(
                    f"unknown field {name!r}; schema fields: "
                    f"{', '.join(self.fields)}", t)
            if self.peek()[:2] == ("op", "["):
                self.next()
                idx = self.sum()
                self.expect("]")
                return Index(name, self.want_int(idx, "index"))
            return Name(name)
        if t[:2] == ("op", "("):
            node = self.impl()
            self.expect(")")
            return node
        raise self.err(f"unexpected {t[1] or 'end of input'!r}", t)


def _nested_tuple(a):
    """An array (or nested sequence) as nested tuples of ints."""
    if hasattr(a, "tolist"):
        a = a.tolist()
    if isinstance(a, (list, tuple)):
        return tuple(_nested_tuple(x) for x in a)
    return int(a)


@dataclasses.dataclass(frozen=True)
class Predicate:
    """A compiled predicate: ``ev(struct, xp)`` -> scalar bool (numpy or
    traced jnp), ``reads`` for the vacuity pass, ``text`` for display."""
    text: str
    node: object
    reads: frozenset

    def ev(self, struct, xp):
        v = self.node.ev(struct, xp)
        # implicit universal quantification over any residual axes
        return xp.all(v)


def parse(text: str, fields=None, consts=None):
    """Parse to an AST; ``fields`` (optional) enables unknown-field
    errors at compile time instead of KeyErrors at probe time; ``consts``
    (``{name: array}``) are the constant tables a NAME may read."""
    return _Parser(text, fields, consts).parse()


def compile_predicate(text: str, fields=None, consts=None) -> Predicate:
    node = parse(text, fields, consts)
    if node.kind != BOOL:
        raise ValueError(
            f"predicate {text!r} is arithmetic, not boolean — an "
            "invariant must evaluate to TRUE/FALSE (wrap it in a "
            "comparison)")
    return Predicate(text, node, frozenset(node.reads()))
