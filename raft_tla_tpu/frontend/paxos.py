"""Lamport's single-decree Paxos — the third bundled spec, the first with
quorums.

``Paxos.tla`` of tlaplus/Examples (``specifications/Paxos/``, the spec of
*Paxos Made Simple*) declared purely as frontend data, the twin of
``frontend/twophase.py``: a :class:`~raft_tla_tpu.frontend.schema.Schema`
with one constant table, an :class:`~raft_tla_tpu.frontend.expr.ActionDef`
table, and two invariants in the predicate language, compiled by
``frontend/actions.build_schema_step`` into the fused step every engine
consumes.  No kernel, intrinsic or engine branch is specific to this
protocol: what it needed of the frontend is general (``expr.Sel`` /
``Reduce`` / ``Exists`` / ``ConstTab`` / ``SetAt``, ``schema.Const``, the
predicate language's ``dot`` and axis-wise reducers), so the next quorum
spec is data too.

Constants
---------
``Acceptor`` and ``Value`` are set sizes (``bounds.n_servers`` /
``n_values``), ``Ballot`` is ``0..bounds.max_term`` (what the model's
``MCBallot`` stands for), and ``Quorum`` is a constant TABLE, one 0/1 row a
quorum and one entry an acceptor (the quorum's mask over ``Acceptor``),
bound from the cfg's ``Quorum = {{a1, a2}, ...}`` and never recomputed as
"the majorities": ``Bounds.constants = (("Quorum", rows),)``.

Encoding
--------
``msgs`` only grows (``Send(m) == msgs' = msgs \\cup {m}``), so each possible
message is one monotone flag, in four flag fields: ``msg1a[b]``,
``msg1b[a, b, k]``, ``msg2a[b, v]``, ``msg2b[a, b, v]``.  A "1b" message
carries the acceptor's ``maxVBal`` / ``maxVal`` pair, which is ``(-1, None)``
or ``(a ballot, a value)`` and nothing else (``Phase2b`` sets both), so the
pair is one index: ``k = 0`` for ``(-1, None)``, else ``1 + mbal * |Value| +
value``.  ``maxBal`` / ``maxVBal`` are stored shifted by one (0 is -1: a
packed field holds 0..hi) and ``maxVal`` as 0 for ``None``, else 1 + the
value.  At 3 acceptors, 2 values and ballots 0..3 a state is 144 flags and 9
small fields (168 bits, 6 packed words) with 48 successor lanes.

The module also carries what a model adapter needs end to end: a hashable
Python state and its vec codec, a TLC-style renderer, and :func:`emit_tla`
for a stock-TLC run of the same bounded model.  The oracle the compiled step
is held to is not here: ``benchmark/reference/paxos.py`` is a separate hand
transcription that imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.frontend import expr as E
from raft_tla_tpu.frontend.schema import (Const, Field, Over, Schema, Sort,
                                          envelope)


def n_pairs(bounds) -> int:
    """The ``(mbal, mval)`` pairs a "1b" message may carry: ``(-1, None)``
    and every (ballot, value)."""
    return 1 + bounds.term_cap * bounds.n_values


# The model's SYMMETRY (MCPaxos: Permutations(Acceptor) \cup
# Permutations(Value)): the spec tells no two acceptors and no two values
# apart, so both are symmetric sorts.  An acceptor only ever indexes an
# axis; a value indexes one ("2a", "2b"), is half of the "1b" pair index
# (k = 0 fixed, else 1 + mbal * |Value| + value) and is what maxVal holds
# (0 = None fixed).  Ballots are numbers, ordered: no sort.
_ACC, _VAL, _VAL1 = Over("Acceptor"), Over("Value"), Over("Value", fixed=1)

SCHEMA = Schema("paxos", (
    Field("maxBal", ("n",), 0, "term_cap", axes=(_ACC,)),   # 0 = -1, else
    Field("maxVBal", ("n",), 0, "term_cap", axes=(_ACC,)),  # ballot + 1
    Field("maxVal", ("n",), 0, "V", axes=(_ACC,),   # 0 = None, else value + 1
          content=_VAL1),
    Field("msg1a", ("term_cap",), 0, 1),
    Field("msg1b", ("n", "term_cap", n_pairs), 0, 1,
          axes=(_ACC, None, _VAL1)),
    Field("msg2a", ("term_cap", "V"), 0, 1, axes=(None, _VAL)),
    Field("msg2b", ("n", "term_cap", "V"), 0, 1, axes=(_ACC, None, _VAL)),
), consts=(
    Const("Quorum", ("*", "n"), 0, 1, axes=(None, _ACC)),
), sorts=(
    Sort("Acceptor", "n"), Sort("Value", "V"),
))

PHASE1A, PHASE1B, PHASE2A, PHASE2B = "Phase1a", "Phase1b", "Phase2a", \
    "Phase2b"
ALL_FAMILIES = (PHASE1A, PHASE1B, PHASE2A, PHASE2B)

# TypeOK (Paxos.tla) is the declared ranges themselves, as TPTypeOK is
# (``msgs \subseteq Message`` is the four flag fields being flags).
# Consistency is what Paxos is for: at most one value is chosen, with
# ``chosen`` of Voting.tla under Paxos.tla's ``votes`` mapping — v is chosen
# iff some quorum's every member has sent "2b" (b, v) for one ballot b.
# ``dot(Quorum, 1 - msg2b)[q, b, v]`` counts the members of quorum q that
# have NOT voted for v in b.

def _type_ok(bounds) -> str:
    return " /\\ ".join(
        f"all({name} >= {rng.lo}) /\\ all({name} <= {rng.hi})"
        for name, rng in envelope(SCHEMA, bounds).items())


# name -> the predicate text at given bounds
INVARIANTS = {
    "TypeOK": _type_ok,
    "Consistency": lambda bounds:
        "count(any(any(dot(Quorum, 1 - msg2b) = 0, 0), 0)) <= 1",
}
DEFAULT_INVARIANT = "Consistency"


def quorum_rows(quorums, n: int) -> tuple:
    """Quorums given as sets of acceptor numbers, as the rows of the
    ``Quorum`` table (its value in ``Bounds.constants``)."""
    return tuple(tuple(int(a in q) for a in range(n)) for q in quorums)


# -- the IR action table ------------------------------------------------------

def _bin(op, a, b):
    return E.Bin(op, a, b)


def _and(*xs):
    out = xs[0]
    for x in xs[1:]:
        out = E.Bin("and", out, x)
    return out


def _flag(field, *idx):
    """One message flag, every axis selected: is the message in msgs?"""
    return _bin("==", E.Sel(field, tuple(idx)), _ONE)


_ZERO, _ONE = E.Lit(0), E.Lit(1)
_A, _B, _V = E.Param("a"), E.Param("b"), E.Param("v")
_NV = E.Dim("n_values")
_BAL = _bin("+", _B, _ONE)                    # ballot b as stored
_MAXBAL = E.Sel("maxBal", (_A,))

# the (maxVBal[a], maxVal[a]) pair as the index k of the "1b" flag
_MVB, _MVL = E.Sel("maxVBal", (_A,)), E.Sel("maxVal", (_A,))
_PAIR = E.Where(
    _bin("==", _MVB, _ZERO), _ZERO,
    _bin("+", _ONE, _bin("+", _bin("*", _bin("-", E.MaxE(_MVB, _ONE), _ONE),
                                   _NV),
                         _bin("-", E.MaxE(_MVL, _ONE), _ONE))))

# Phase2a's quorum guard.  Over the pair axis k: mbal(k) and the value of
# slot k, -1 and unread at k = 0.
_K = E.Iota(_bin("+", _ONE, _bin("*", E.Dim("term_cap"), _NV)))
_SLOT = E.MaxE(_bin("-", _K, _ONE), _ZERO)
_MBAL_K = E.Where(_bin(">=", _K, _ONE), _bin("//", _SLOT, _NV), E.Lit(-1))
_MVAL_K = _bin("%", _SLOT, _NV)
_Q = E.Param("Q")                             # one row of Quorum: [n] of 0/1
_M1B = _bin("==", E.Sel("msg1b", (None, _B, None)), _ONE)     # [n, k]
# \A a \in Q : \E m \in Q1b : m.acc = a
_EVERY = E.Reduce("all", _bin("or", _bin("==", _Q, _ZERO),
                              E.Reduce("any", _M1B, axis=1)))
# Q1bv: the "1b" messages of ballot b from Q's members with mbal >= 0
_Q1BV = _and(_M1B, _bin("==", E.Lift(_Q), _ONE), _bin(">=", _K, _ONE))
_TOP = E.Reduce("max", E.Where(_Q1BV, _MBAL_K, E.Lit(-1)))
# Q1bv = {} \/ \E m \in Q1bv : m.mval = v /\ \A mm \in Q1bv : m.mbal >= mm.mbal
_SAFE = _bin("or", _bin("==", _TOP, E.Lit(-1)),
             E.Reduce("any", _and(_Q1BV, _bin("==", _MVAL_K, _V),
                                  _bin("==", _MBAL_K, _TOP))))
_QUORUM_GUARD = E.Scope("quorum", E.Exists(
    "Q", E.ConstTab("Quorum"), _and(_EVERY, _SAFE)))

_IV_A = ("a", lambda b: E.iv.Interval(0, b.n_servers - 1))
_IV_B = ("b", lambda b: E.iv.Interval(0, b.max_term))
_IV_V = ("v", lambda b: E.iv.Interval(0, b.n_values - 1))

ACTIONS = (
    # Phase1a(b): Send([type |-> "1a", bal |-> b]); always enabled.
    E.ActionDef(
        PHASE1A, ("b",), E.Lit(True),
        (E.Branch(updates=(E.SetAt("msg1a", (_B,), _ONE),)),),
        param_iv=(_IV_B,)),
    # Phase1b(a), one lane a "1a" message: m.bal > maxBal[a]; the acceptor
    # promises and reports the last vote it cast.
    E.ActionDef(
        PHASE1B, ("a", "b"),
        _and(_flag("msg1a", _B), _bin(">", _BAL, _MAXBAL)),
        (E.Branch(updates=(E.SetAt("maxBal", (_A,), _BAL),
                           E.SetAt("msg1b", (_A, _B, _PAIR), _ONE))),),
        param_iv=(_IV_A, _IV_B)),
    # Phase2a(b, v): no "2a" of ballot b yet, and some quorum's "1b"
    # messages of ballot b make v safe.
    E.ActionDef(
        PHASE2A, ("b", "v"),
        _and(E.Not(E.Reduce("any", _bin("==", E.Sel("msg2a", (_B, None)),
                                        _ONE))),
             _QUORUM_GUARD),
        (E.Branch(updates=(E.SetAt("msg2a", (_B, _V), _ONE),)),),
        param_iv=(_IV_B, _IV_V)),
    # Phase2b(a), one lane a "2a" message: m.bal >= maxBal[a]; the acceptor
    # votes.
    E.ActionDef(
        PHASE2B, ("a", "b", "v"),
        _and(_flag("msg2a", _B, _V), _bin(">=", _BAL, _MAXBAL)),
        (E.Branch(updates=(E.SetAt("maxBal", (_A,), _BAL),
                           E.SetAt("maxVBal", (_A,), _BAL),
                           E.SetAt("maxVal", (_A,), _bin("+", _V, _ONE)),
                           E.SetAt("msg2b", (_A, _B, _V), _ONE))),),
        param_iv=(_IV_A, _IV_B, _IV_V)),
)


@dataclasses.dataclass(frozen=True)
class PaxosInstance:
    """One successor lane: family + the bound acceptor, ballot and value
    (those its family does not take stay 0, unread)."""

    family: str
    a: int = 0
    b: int = 0
    v: int = 0

    def label(self) -> str:
        args = {PHASE1A: (self.b,), PHASE1B: (f"a{self.a + 1}", self.b),
                PHASE2A: (self.b, f"v{self.v + 1}"),
                PHASE2B: (f"a{self.a + 1}", self.b,
                          f"v{self.v + 1}")}[self.family]
        return f"{self.family}({', '.join(map(str, args))})"


def action_table(bounds: Bounds) -> list:
    """The static successor fan-out, in Next-disjunct order:
    ``|Ballot| * (1 + n + |Value| + n * |Value|)`` lanes, 48 at 3 / 2 /
    0..3."""
    n, nv, nb = bounds.n_servers, bounds.n_values, bounds.term_cap
    table = [PaxosInstance(PHASE1A, b=b) for b in range(nb)]
    table += [PaxosInstance(PHASE1B, a=a, b=b)
              for a in range(n) for b in range(nb)]
    table += [PaxosInstance(PHASE2A, b=b, v=v)
              for b in range(nb) for v in range(nv)]
    table += [PaxosInstance(PHASE2B, a=a, b=b, v=v)
              for a in range(n) for b in range(nb) for v in range(nv)]
    return table


# -- Python state + codec -----------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PaxosState:
    """One state, hashable.  ``maxBal`` / ``maxVBal`` hold a ballot or -1,
    ``maxVal`` a value number or ``None``; ``msgs`` holds the message tuples
    ``("1a", bal)``, ``("1b", acc, bal, mbal, mval)``, ``("2a", bal, val)``,
    ``("2b", acc, bal, val)``."""

    maxBal: tuple
    maxVBal: tuple
    maxVal: tuple
    msgs: frozenset

    def _replace(self, **kw) -> "PaxosState":
        return dataclasses.replace(self, **kw)


def init_state(bounds: Bounds) -> PaxosState:
    """Init: every acceptor at -1 / -1 / None, no message."""
    n = bounds.n_servers
    return PaxosState((-1,) * n, (-1,) * n, (None,) * n, frozenset())


def _pair(mbal, mval, bounds: Bounds) -> int:
    if (mbal == -1) != (mval is None):
        raise ValueError(
            f"a \"1b\" message carries (-1, None) or (a ballot, a value); "
            f"({mbal}, {mval}) has no flag")
    return 0 if mval is None else 1 + mbal * bounds.n_values + mval


def to_vec(s: PaxosState, bounds: Bounds) -> np.ndarray:
    """Pack in schema declaration order — must agree with
    ``SCHEMA.layout(bounds).pack`` (pinned by tests)."""
    shapes = SCHEMA.layout(bounds).shapes
    flags = {f: np.zeros(shapes[f], np.int32)
             for f in ("msg1a", "msg1b", "msg2a", "msg2b")}
    for m in s.msgs:
        if m[0] == "1a":
            flags["msg1a"][m[1]] = 1
        elif m[0] == "1b":
            flags["msg1b"][m[1], m[2], _pair(m[3], m[4], bounds)] = 1
        elif m[0] == "2a":
            flags["msg2a"][m[1], m[2]] = 1
        elif m[0] == "2b":
            flags["msg2b"][m[1], m[2], m[3]] = 1
        else:
            raise ValueError(f"unknown message {m!r}")
    return np.concatenate([
        np.asarray([b + 1 for b in s.maxBal], np.int32),
        np.asarray([b + 1 for b in s.maxVBal], np.int32),
        np.asarray([0 if v is None else v + 1 for v in s.maxVal], np.int32),
        *(flags[f].reshape(-1)
          for f in ("msg1a", "msg1b", "msg2a", "msg2b"))])


def from_vec(vec, bounds: Bounds) -> PaxosState:
    t = SCHEMA.layout(bounds).unpack(np.asarray(vec).reshape(-1), np)
    nv = bounds.n_values
    msgs = [("1a", int(b)) for (b,) in np.argwhere(t["msg1a"])]
    for a, b, k in np.argwhere(t["msg1b"]):
        pair = (-1, None) if k == 0 else (int(k - 1) // nv, int(k - 1) % nv)
        msgs.append(("1b", int(a), int(b), *pair))
    msgs += [("2a", int(b), int(v)) for b, v in np.argwhere(t["msg2a"])]
    msgs += [("2b", int(a), int(b), int(v))
             for a, b, v in np.argwhere(t["msg2b"])]
    return PaxosState(
        tuple(int(b) - 1 for b in t["maxBal"]),
        tuple(int(b) - 1 for b in t["maxVBal"]),
        tuple(None if v == 0 else int(v) - 1 for v in t["maxVal"]),
        frozenset(msgs))


# -- rendering ----------------------------------------------------------------

def _acc(i: int) -> str:
    return f"a{i + 1}"


def _val(v) -> str:
    return "None" if v is None else f"v{v + 1}"


def _render_msg(m: tuple) -> str:
    if m[0] == "1a":
        return f'[type |-> "1a", bal |-> {m[1]}]'
    if m[0] == "1b":
        return (f'[type |-> "1b", acc |-> {_acc(m[1])}, bal |-> {m[2]}, '
                f'mbal |-> {m[3]}, mval |-> {_val(m[4])}]')
    if m[0] == "2a":
        return f'[type |-> "2a", bal |-> {m[1]}, val |-> {_val(m[2])}]'
    return (f'[type |-> "2b", acc |-> {_acc(m[1])}, bal |-> {m[2]}, '
            f'val |-> {_val(m[3])}]')


def render_state(s: PaxosState, bounds: Bounds, indent: str = "    ") -> str:
    """TLC-style conjunction, the message flags rendered back as the
    Paxos.tla message *set*."""
    n = bounds.n_servers

    def fn(vals, show):
        return "(" + " @@ ".join(f"{_acc(i)} :> {show(vals[i])}"
                                 for i in range(n)) + ")"

    order = {"1a": 0, "1b": 1, "2a": 2, "2b": 3}
    msgs = sorted(s.msgs, key=lambda m: (order[m[0]], tuple(
        -1 if x is None else x for x in m[1:])))
    lines = [
        "/\\ maxBal = " + fn(s.maxBal, str),
        "/\\ maxVBal = " + fn(s.maxVBal, str),
        "/\\ maxVal = " + fn(s.maxVal, _val),
        "/\\ msgs = {" + ", ".join(_render_msg(m) for m in msgs) + "}",
    ]
    return "\n".join(indent + ln for ln in lines)


def render_trace(violation, bounds: Bounds) -> str:
    from raft_tla_tpu.utils import render
    return render.render_trace(violation, bounds,
                               state_renderer=render_state)


# -- TLC parity emission ------------------------------------------------------

_TLA_TEMPLATE = """---------------------------- MODULE MCPaxos ----------------------------
\\* Bounded single-decree Paxos — emitted by raft_tla_tpu for a stock-TLC
\\* run of the exact model the TPU checker explored: Paxos.tla of
\\* tlaplus/Examples with Ballot == 0..MaxBallot, and the consistency of
\\* Voting.tla's chosen under Paxos.tla's votes mapping as an invariant.
EXTENDS Integers%(extends)s

CONSTANTS Acceptor, Value, Quorum, None, MaxBallot

Ballot == 0..MaxBallot

Message ==      [type : {"1a"}, bal : Ballot]
           \\cup [type : {"1b"}, acc : Acceptor, bal : Ballot,
                 mbal : Ballot \\cup {-1}, mval : Value \\cup {None}]
           \\cup [type : {"2a"}, bal : Ballot, val : Value]
           \\cup [type : {"2b"}, acc : Acceptor, bal : Ballot, val : Value]

VARIABLES maxBal, maxVBal, maxVal, msgs
vars == <<maxBal, maxVBal, maxVal, msgs>>

TypeOK == /\\ maxBal \\in [Acceptor -> Ballot \\cup {-1}]
          /\\ maxVBal \\in [Acceptor -> Ballot \\cup {-1}]
          /\\ maxVal \\in [Acceptor -> Value \\cup {None}]
          /\\ msgs \\subseteq Message

Init == /\\ maxBal = [a \\in Acceptor |-> -1]
        /\\ maxVBal = [a \\in Acceptor |-> -1]
        /\\ maxVal = [a \\in Acceptor |-> None]
        /\\ msgs = {}

Send(m) == msgs' = msgs \\cup {m}

Phase1a(b) == /\\ Send([type |-> "1a", bal |-> b])
              /\\ UNCHANGED <<maxBal, maxVBal, maxVal>>

Phase1b(a) ==
  /\\ \\E m \\in msgs :
        /\\ m.type = "1a"
        /\\ m.bal > maxBal[a]
        /\\ maxBal' = [maxBal EXCEPT ![a] = m.bal]
        /\\ Send([type |-> "1b", acc |-> a, bal |-> m.bal,
                  mbal |-> maxVBal[a], mval |-> maxVal[a]])
  /\\ UNCHANGED <<maxVBal, maxVal>>

Phase2a(b, v) ==
  /\\ ~ \\E m \\in msgs : m.type = "2a" /\\ m.bal = b
  /\\ \\E Q \\in Quorum :
        LET Q1b == {m \\in msgs : /\\ m.type = "1b"
                                  /\\ m.acc \\in Q
                                  /\\ m.bal = b}
            Q1bv == {m \\in Q1b : m.mbal >= 0}
        IN  /\\ \\A a \\in Q : \\E m \\in Q1b : m.acc = a
            /\\ \\/ Q1bv = {}
               \\/ \\E m \\in Q1bv :
                     /\\ m.mval = v
                     /\\ \\A mm \\in Q1bv : m.mbal >= mm.mbal
  /\\ Send([type |-> "2a", bal |-> b, val |-> v])
  /\\ UNCHANGED <<maxBal, maxVBal, maxVal>>

Phase2b(a) ==
  \\E m \\in msgs :
    /\\ m.type = "2a"
    /\\ m.bal >= maxBal[a]
    /\\ maxBal' = [maxBal EXCEPT ![a] = m.bal]
    /\\ maxVBal' = [maxVBal EXCEPT ![a] = m.bal]
    /\\ maxVal' = [maxVal EXCEPT ![a] = m.val]
    /\\ Send([type |-> "2b", acc |-> a, bal |-> m.bal, val |-> m.val])

Next == \\/ \\E b \\in Ballot : \\/ Phase1a(b)
                              \\/ \\E v \\in Value : Phase2a(b, v)
        \\/ \\E a \\in Acceptor : Phase1b(a) \\/ Phase2b(a)

Spec == Init /\\ [][Next]_vars

votes == [a \\in Acceptor |->
            {<<m.bal, m.val>> : m \\in {mm \\in msgs : /\\ mm.type = "2b"
                                                     /\\ mm.acc = a}}]
VotedFor(a, b, v) == <<b, v>> \\in votes[a]
ChosenAt(b, v) == \\E Q \\in Quorum : \\A a \\in Q : VotedFor(a, b, v)
chosen == {v \\in Value : \\E b \\in Ballot : ChosenAt(b, v)}

Consistency == \\A v1, v2 \\in chosen : v1 = v2
%(symmetry)s=======================================================================
"""


def emit_tla(out_dir: str, bounds: Bounds, invariants=(),
             symmetry=()) -> tuple:
    """Write ``MCPaxos.tla`` / ``MCPaxos.cfg`` — the stock-TLC twin of this
    bounded model, ``Quorum`` as the run binds it (``bounds.constants``).  Only
    registered (named) invariants can be emitted; a whole-line expression
    has no TLA+ operator name to reference.  ``symmetry``: the sorts the run
    reduced by; the twin then defines ``Sym<Sorts>`` (``SymAcceptorValue``)
    as the union of their ``Permutations`` (TLC module) and its cfg carries
    ``SYMMETRY SymAcceptorValue``, so that TLC counts the same orbits (and
    this checker reads the twin's cfg back: ``registry``)."""
    names = []
    for nm in invariants:
        if nm not in INVARIANTS:
            raise ValueError(
                f"cannot emit invariant expression {nm!r} to TLC: only "
                f"the registered names ({', '.join(sorted(INVARIANTS))}) "
                "have TLA+ operator definitions")
        names.append(nm)
    quorum = SCHEMA.bind_consts(bounds, bounds.constants)["Quorum"]
    os.makedirs(out_dir, exist_ok=True)
    tla = os.path.join(out_dir, "MCPaxos.tla")
    cfgp = os.path.join(out_dir, "MCPaxos.cfg")
    unknown = sorted(set(symmetry) - set(SCHEMA.sort_names))
    if unknown:
        raise ValueError(f"cannot emit SYMMETRY {unknown[0]}: paxos "
                         f"declares {', '.join(SCHEMA.sort_names)}")
    sorts = [nm for nm in SCHEMA.sort_names if nm in symmetry]
    sym_name = "Sym" + "".join(sorts)
    sym_def = "" if not sorts else (
        f"\n{sym_name} == " + " \\cup ".join(
            f"Permutations({nm})" for nm in sorts) + "\n")
    with open(tla, "w", encoding="utf-8") as f:
        f.write(_TLA_TEMPLATE % {"extends": ", TLC" if symmetry else "",
                                 "symmetry": sym_def})
    n = bounds.n_servers
    sets = ", ".join(
        "{" + ", ".join(_acc(a) for a in range(n) if row[a]) + "}"
        for row in quorum)
    lines = ["SPECIFICATION Spec", "CONSTANTS",
             "  Acceptor = {" + ", ".join(map(_acc, range(n))) + "}",
             "  Value = {" + ", ".join(
                 _val(v) for v in range(bounds.n_values)) + "}",
             "  Quorum = {" + sets + "}",
             "  None = None",
             f"  MaxBallot = {bounds.max_term}"]
    for nm in names:
        lines.append(f"INVARIANT {nm}")
    if symmetry:
        lines.append(f"SYMMETRY {sym_name}")
    with open(cfgp, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    return tla, cfgp
