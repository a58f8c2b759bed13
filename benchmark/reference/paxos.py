"""Lamport's single-decree Paxos as plain Python: ``Paxos.tla`` of
tlaplus/Examples, ``specifications/Paxos/`` (``Next``, ``TypeOK``, and the
consistency of ``Voting.tla``'s ``chosen`` under ``Paxos.tla``'s ``votes``
mapping), transcribed by hand from the TLA+ text as it is remembered.

A plain reference of the benchmark: it imports nothing of the program and
was not taken from the program's own declaration (``frontend/paxos.py``).
The acceptors are ``Acceptor = {a1 .. an}``, numbered 0..n-1 here, the values
``Value = {v1 .. vk}`` numbered 0..k-1, the ballots ``0..max_ballot`` (what the
model's ``MCBallot`` stands for), ``None`` is ``None`` and "no ballot" is -1.

    VARIABLES maxBal, maxVBal, maxVal, msgs

``msgs`` is the set of all messages ever sent (``Send(m) == msgs' = msgs \\cup
{m}``): it only grows.  A state is kept as a state: three tuples and a
``frozenset`` of message tuples,

    ("1a", bal)   ("1b", acc, bal, mbal, mval)
    ("2a", bal, val)   ("2b", acc, bal, val)

``Quorum`` is a constant of the model, a set of sets of acceptors, and is an
argument here (a tuple of frozensets): nothing below recomputes it as "the
majorities".
"""

from __future__ import annotations

import itertools
import sys
import time
from typing import NamedTuple

ACTIONS = ("Phase1a", "Phase1b", "Phase2a", "Phase2b")


class Model(NamedTuple):
    """The constants of one model: ``Acceptor`` and ``Value`` by size,
    ``Ballot = 0..max_ballot``, ``Quorum`` as given."""
    n_acceptors: int
    n_values: int
    max_ballot: int
    quorums: tuple      # of frozensets of acceptor numbers


class State(NamedTuple):
    maxBal: tuple       # a ballot or -1, an acceptor
    maxVBal: tuple      # a ballot or -1, an acceptor
    maxVal: tuple       # a value or None, an acceptor
    msgs: frozenset     # of the message tuples above


def majorities(n: int) -> tuple:
    """Every set of more than half of ``n`` acceptors: the ``Quorum`` of the
    source's model at n = 3, ``{{a1, a2}, {a1, a3}, {a2, a3}}``, with the
    whole set beside them (a superset of a quorum enables nothing more)."""
    return tuple(frozenset(c) for k in range(n // 2 + 1, n + 1)
                 for c in itertools.combinations(range(n), k))


def minimal_majorities(n: int) -> tuple:
    """The sets of exactly ``n // 2 + 1`` acceptors: at n = 3 the source's
    own three quorums."""
    return tuple(frozenset(c)
                 for c in itertools.combinations(range(n), n // 2 + 1))


def model(n_acceptors: int, n_values: int, max_ballot: int,
          quorums=None) -> Model:
    qs = minimal_majorities(n_acceptors) if quorums is None \
        else tuple(frozenset(q) for q in quorums)
    for q in qs:
        if not q or not q <= set(range(n_acceptors)):
            raise ValueError(f"quorum {sorted(q)} is empty or names an "
                             f"acceptor outside 0..{n_acceptors - 1}")
    return Model(n_acceptors, n_values, max_ballot, qs)


def init_state(m: Model) -> State:
    """``Init``: every acceptor at -1 / -1 / None, no message sent."""
    n = m.n_acceptors
    return State((-1,) * n, (-1,) * n, (None,) * n, frozenset())


def _set(t: tuple, i: int, v) -> tuple:
    return t[:i] + (v,) + t[i + 1:]


def _send(s: State, msg: tuple) -> frozenset:
    """``Send(m)``: ``msgs' = msgs \\cup {m}``."""
    return s.msgs if msg in s.msgs else s.msgs | {msg}


def _phase2a_enabled(s: State, m: Model, b: int, v: int) -> bool:
    """The guard of ``Phase2a(b, v)``."""
    # ~ \E m \in msgs : m.type = "2a" /\ m.bal = b
    if any(x[0] == "2a" and x[1] == b for x in s.msgs):
        return False
    for q in m.quorums:
        # Q1b == {m \in msgs : m.type = "1b" /\ m.acc \in Q /\ m.bal = b}
        q1b = [x for x in s.msgs
               if x[0] == "1b" and x[1] in q and x[2] == b]
        # Q1bv == {m \in Q1b : m.mbal >= 0}
        q1bv = [x for x in q1b if x[3] >= 0]
        # \A a \in Q : \E m \in Q1b : m.acc = a
        if not all(any(x[1] == a for x in q1b) for a in q):
            continue
        # Q1bv = {} \/ \E m \in Q1bv : m.mval = v
        #                  /\ \A mm \in Q1bv : m.mbal >= mm.mbal
        if not q1bv or any(x[4] == v and all(x[3] >= y[3] for y in q1bv)
                           for x in q1bv):
            return True
    return False


def successors(s: State, m: Model) -> list:
    """Every enabled disjunct of ``Next`` in ``s``, in the order of
    ``ACTIONS``: ``[((action, arguments), successor)]``.  A step that changes
    nothing (a message sent again) is enabled in the spec and is listed: TLC
    counts it as a state generated."""
    out = []
    ballots = range(m.max_ballot + 1)
    # Phase1a(b): Send([type |-> "1a", bal |-> b])
    for b in ballots:
        out.append((("Phase1a", (b,)),
                    s._replace(msgs=_send(s, ("1a", b)))))
    # Phase1b(a): \E m \in msgs : m.type = "1a" /\ m.bal > maxBal[a]
    for a in range(m.n_acceptors):
        for b in ballots:
            if ("1a", b) in s.msgs and b > s.maxBal[a]:
                out.append((("Phase1b", (a, b)), s._replace(
                    maxBal=_set(s.maxBal, a, b),
                    msgs=_send(s, ("1b", a, b, s.maxVBal[a], s.maxVal[a])))))
    # Phase2a(b, v)
    for b in ballots:
        for v in range(m.n_values):
            if _phase2a_enabled(s, m, b, v):
                out.append((("Phase2a", (b, v)),
                            s._replace(msgs=_send(s, ("2a", b, v)))))
    # Phase2b(a): \E m \in msgs : m.type = "2a" /\ m.bal >= maxBal[a]
    for a in range(m.n_acceptors):
        for b in ballots:
            for v in range(m.n_values):
                if ("2a", b, v) in s.msgs and b >= s.maxBal[a]:
                    out.append((("Phase2b", (a, b, v)), State(
                        _set(s.maxBal, a, b), _set(s.maxVBal, a, b),
                        _set(s.maxVal, a, v),
                        _send(s, ("2b", a, b, v)))))
    return out


def type_ok(s: State, m: Model) -> bool:
    """``TypeOK``: every variable in its declared set, ``msgs \\subseteq
    Message``."""
    ballots = range(-1, m.max_ballot + 1)
    accs, vals = range(m.n_acceptors), range(m.n_values)
    if not (all(b in ballots for b in s.maxBal)
            and all(b in ballots for b in s.maxVBal)
            and all(v is None or v in vals for v in s.maxVal)):
        return False
    for x in s.msgs:
        if x[0] == "1a":
            ok = len(x) == 2 and x[1] in ballots[1:]
        elif x[0] == "1b":
            ok = len(x) == 5 and x[1] in accs and x[2] in ballots[1:] \
                and x[3] in ballots and (x[4] is None or x[4] in vals)
        elif x[0] == "2a":
            ok = len(x) == 3 and x[1] in ballots[1:] and x[2] in vals
        elif x[0] == "2b":
            ok = len(x) == 4 and x[1] in accs and x[2] in ballots[1:] \
                and x[3] in vals
        else:
            ok = False
        if not ok:
            return False
    return True


def chosen(s: State, m: Model) -> set:
    """``chosen`` of ``Voting.tla`` under ``votes[a] == {<<m.bal, m.val>> :
    m \\in {mm \\in msgs : mm.type = "2b" /\\ mm.acc = a}}``: the values some
    quorum has voted for in one ballot."""
    return {v for v in range(m.n_values)
            for b in range(m.max_ballot + 1) for q in m.quorums
            if all(("2b", a, b, v) in s.msgs for a in q)}


def consistency(s: State, m: Model) -> bool:
    """At most one value is chosen."""
    return len(chosen(s, m)) <= 1


INVARIANTS = {"TypeOK": type_ok, "Consistency": consistency}


def _message_numbers(m: Model) -> dict:
    """Every message of ``Message`` with a number of its own."""
    ballots = range(m.max_ballot + 1)
    accs, vals = range(m.n_acceptors), range(m.n_values)
    every = [("1a", b) for b in ballots]
    every += [("1b", a, b, mb, mv) for a in accs for b in ballots
              for mb in range(-1, m.max_ballot + 1)
              for mv in (None, *vals)]
    every += [("2a", b, v) for b in ballots for v in vals]
    every += [("2b", a, b, v) for a in accs for b in ballots for v in vals]
    return {x: k for k, x in enumerate(every)}


def packer(m: Model):
    """``pack(s)``: ``s`` as one integer, one to one (what a long search
    keeps in ``seen`` in place of the state)."""
    number = _message_numbers(m)
    nb, nv, n = m.max_ballot + 2, m.n_values + 1, m.n_acceptors

    def pack(s: State) -> int:
        x = 0
        for msg in s.msgs:
            x |= 1 << number[msg]
        for a in range(n):
            x = (x * nb + s.maxBal[a] + 1) * nb + s.maxVBal[a] + 1
            x = x * nv + (0 if s.maxVal[a] is None else s.maxVal[a] + 1)
        return x

    return pack


def bfs_levels(m: Model, inv_names: tuple = ("TypeOK", "Consistency"),
               min_level_states: int | None = None,
               max_level: int | None = None):
    """Level-synchronous BFS from ``Init``, to the first level of
    ``min_level_states`` states, to level ``max_level``, or to the level that
    admits nothing where both are ``None``.

    Returns ``(cumulative distinct states per level, the last level's states,
    invariant violations seen, transitions taken)``; a transition is an
    enabled step out of an expanded state, one that changes nothing included.
    The last level is not expanded."""
    invs = [INVARIANTS[nm] for nm in inv_names]
    pack = packer(m)
    init = init_state(m)
    seen = {pack(init)}
    violations = sum(not f(init, m) for f in invs)
    cumulative, frontier, transitions = [1], [init], 0
    while (min_level_states is None or len(frontier) < min_level_states) \
            and (max_level is None or len(cumulative) - 1 < max_level):
        nxt = []
        for s in frontier:
            for _a, t in successors(s, m):
                transitions += 1
                k = pack(t)
                if k in seen:
                    continue
                seen.add(k)
                violations += sum(not f(t, m) for f in invs)
                nxt.append(t)
        if not nxt:
            break
        cumulative.append(cumulative[-1] + len(nxt))
        frontier = nxt
    return cumulative, frontier, violations, transitions


def main(argv=None) -> int:
    """``python3 benchmark/reference/paxos.py ACCEPTORS VALUES MAX_BALLOT``:
    the whole space under the minimal majorities as ``Quorum`` (the source's
    three at three acceptors), both invariants on every state, off the clock
    (what a configuration's pins are taken from)."""
    args = [int(a) for a in (sys.argv[1:] if argv is None else argv)]
    if len(args) != 3:
        print(main.__doc__, file=sys.stderr)
        return 2
    m = model(*args)
    t0 = time.monotonic()
    cum, _last, viol, trans = bfs_levels(m)
    levels = [b - a for a, b in zip([0] + cum, cum)]
    print(f"paxos acceptors={m.n_acceptors} values={m.n_values} "
          f"ballots=0..{m.max_ballot} quorums="
          f"{[sorted(q) for q in m.quorums]}: {cum[-1]} states, "
          f"{len(cum)} levels (diameter {len(cum) - 1}), widest level "
          f"{max(levels)}, {trans} transitions, {viol} violations, "
          f"{time.monotonic() - t0:.1f}s", flush=True)
    print(f"  levels {levels}", flush=True)
    print(f"  cumulative {cum}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
