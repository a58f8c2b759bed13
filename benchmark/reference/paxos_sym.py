#!/usr/bin/env python3
"""Single-decree Paxos under its model's SYMMETRY, as plain Python: the
orbits of ``Permutations(Acceptor) \\cup Permutations(Value)`` over the states
of ``benchmark/reference/paxos.py`` (its states, ``successors`` and invariants,
unchanged and imported here), and a breadth-first search that counts them.

A plain reference of the benchmark: it imports nothing of the program.  The
program names an orbit by the least fingerprint over the images of a state;
this file names it **by sorting**, a method that shares nothing with that one
and has to agree with it orbit for orbit.  No variable and no message of
``Paxos.tla`` is indexed by two acceptors, so a state is a bag of acceptor
*columns* (``maxBal[a]``, ``maxVBal[a]``, ``maxVal[a]``, the "1b" and the "2b"
messages ``a`` has sent) beside what no acceptor owns (the "1a" and "2a"
messages): two states are one up to a renaming of the acceptors iff their
sorted columns are equal.  Values are relabelled the plain way, one candidate a
relabelling (``|Value|!`` of them), and the least candidate is the orbit's
name.  ``brute_canonical`` is the definition (the least image over the whole
group), kept for the tests that hold ``canonical`` to it.
"""

from __future__ import annotations

import itertools
import multiprocessing
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import paxos as px  # noqa: E402

CHUNK = 2048        # parents a worker task
_W: dict = {}       # a worker's model, set once by _start


def invariant_quorums(m: px.Model) -> bool:
    """Is ``Quorum`` mapped onto itself by every permutation of the
    acceptors?  Symmetry over a table that is not is unsound (TLC does not
    check it)."""
    qs = set(m.quorums)
    return all({frozenset(pi[a] for a in q) for q in qs} == qs
               for pi in itertools.permutations(range(m.n_acceptors)))


def permute(s: px.State, pi: tuple, sigma: tuple) -> px.State:
    """The image of ``s`` under ``(pi, sigma)``: acceptor ``a`` becomes
    ``pi[a]``, value ``v`` becomes ``sigma[v]``; ballots, -1 and ``None`` are
    no members of either set and stay."""
    n = len(pi)
    back = [0] * n
    for a in range(n):
        back[pi[a]] = a

    def val(v):
        return None if v is None else sigma[v]

    msgs = set()
    for x in s.msgs:
        if x[0] == "1a":
            msgs.add(x)
        elif x[0] == "1b":
            msgs.add(("1b", pi[x[1]], x[2], x[3], val(x[4])))
        elif x[0] == "2a":
            msgs.add(("2a", x[1], sigma[x[2]]))
        else:
            msgs.add(("2b", pi[x[1]], x[2], sigma[x[3]]))
    return px.State(tuple(s.maxBal[back[k]] for k in range(n)),
                    tuple(s.maxVBal[back[k]] for k in range(n)),
                    tuple(val(s.maxVal[back[k]]) for k in range(n)),
                    frozenset(msgs))


def _order(s: px.State) -> tuple:
    """A total order on states (``None`` below every value)."""
    return (s.maxBal, s.maxVBal,
            tuple(-1 if v is None else v for v in s.maxVal),
            sorted(tuple(-1 if f is None else f for f in x) for x in s.msgs))


def brute_canonical(s: px.State, m: px.Model) -> px.State:
    """The least image of ``s`` over every ``(pi, sigma)``: the definition,
    ``n! * |Value|!`` images a call."""
    return min((permute(s, pi, sigma)
                for pi in itertools.permutations(range(m.n_acceptors))
                for sigma in itertools.permutations(range(m.n_values))),
               key=_order)


def canonical_key(s: px.State, m: px.Model) -> tuple:
    """The orbit's name as nested tuples: over the value relabellings, the
    least ``("1a" ballots, relabelled "2a" messages, sorted acceptor
    columns)``.  ``None`` is written -1."""
    n = m.n_acceptors
    m1a, m2a = [], []
    m1b = [[] for _ in range(n)]
    m2b = [[] for _ in range(n)]
    for x in s.msgs:
        t = x[0]
        if t == "1b":
            m1b[x[1]].append(x[2:])
        elif t == "2b":
            m2b[x[1]].append(x[2:])
        elif t == "1a":
            m1a.append(x[1])
        else:
            m2a.append(x[1:])
    m1a = tuple(sorted(m1a))
    best = None
    for sigma in itertools.permutations(range(m.n_values)):
        cols = sorted(
            (s.maxBal[a], s.maxVBal[a],
             -1 if s.maxVal[a] is None else sigma[s.maxVal[a]],
             tuple(sorted((b, mb, -1 if mv is None else sigma[mv])
                          for b, mb, mv in m1b[a])),
             tuple(sorted((b, sigma[v]) for b, v in m2b[a])))
            for a in range(n))
        key = (m1a, tuple(sorted((b, sigma[v]) for b, v in m2a)),
               tuple(cols))
        if best is None or key < best:
            best = key
    return best


def state_of(key: tuple) -> px.State:
    """The member of the orbit that ``canonical_key`` names: its columns in
    sorted order are its acceptors 0..n-1."""
    m1a, m2a, cols = key
    msgs = {("1a", b) for b in m1a} | {("2a", b, v) for b, v in m2a}
    for a, (_mb, _mvb, _mv, c1b, c2b) in enumerate(cols):
        msgs |= {("1b", a, b, mb, None if mv < 0 else mv)
                 for b, mb, mv in c1b}
        msgs |= {("2b", a, b, v) for b, v in c2b}
    return px.State(tuple(c[0] for c in cols), tuple(c[1] for c in cols),
                    tuple(None if c[2] < 0 else c[2] for c in cols),
                    frozenset(msgs))


def canonical(s: px.State, m: px.Model) -> px.State:
    """The orbit of ``s`` named by one of its members, sort-based: equal for
    two states iff some ``(pi, sigma)`` maps one to the other."""
    return state_of(canonical_key(s, m))


def successor_orbits(parents: list, m: px.Model):
    """``({canonical successor}, transitions)`` of ``parents``: every enabled
    step of every parent, one that changes nothing included."""
    reps, n_trans = set(), 0
    for s in parents:
        for _a, t in px.successors(s, m):
            n_trans += 1
            reps.add(canonical(t, m))
    return reps, n_trans


def _start(m: px.Model, inv_names: tuple) -> None:
    _W.update(m=m, pack=px.packer(m),
              invs=[px.INVARIANTS[nm] for nm in inv_names])


def _expand(parents: list) -> tuple:
    """``([(packed name, canonical successor, invariants it breaks)],
    transitions)`` of ``parents``, in discovery order, the first occurrence
    in the task only."""
    m, pack, invs = _W["m"], _W["pack"], _W["invs"]
    out, mine, n_trans = [], set(), 0
    for s in parents:
        for _a, t in px.successors(s, m):
            n_trans += 1
            c = canonical(t, m)
            k = pack(c)
            if k in mine:
                continue
            mine.add(k)
            out.append((k, c, sum(not f(c, m) for f in invs)))
    return out, n_trans


def bfs_orbit_levels(m: px.Model,
                     inv_names: tuple = ("TypeOK", "Consistency"),
                     min_level_states: int | None = None,
                     max_level: int | None = None, workers: int = 1,
                     out=None):
    """``paxos.bfs_levels`` over orbits: level-synchronous BFS from ``Init``
    in which a state is its orbit's name (``canonical``), to the first level
    of ``min_level_states`` orbits, to level ``max_level``, or to the level
    that admits nothing.

    Returns ``(cumulative orbits a level, the last level's orbits (canonical
    members), invariant violations, transitions)``: a transition is an
    enabled step out of an expanded orbit's canonical member (the successor
    sets of two members of one orbit are images of each other, so the count
    is the orbit's own).  The last level is not expanded.  ``seen`` holds the
    canonical member's one-to-one integer packing.  ``workers`` > 1 expands
    a level in worker processes, task by task in order: the same counts."""
    if not invariant_quorums(m):
        raise ValueError("Quorum is not invariant under the permutations of "
                         "Acceptor: no symmetry to reduce by")
    _start(m, tuple(inv_names))
    init = canonical(px.init_state(m), m)
    seen = {_W["pack"](init)}
    violations = sum(not f(init, m) for f in _W["invs"])
    cumulative, frontier, transitions = [1], [init], 0
    # spawned, not forked: a caller may hold threads (a test process does)
    pool = multiprocessing.get_context("spawn").Pool(
        workers, _start, (m, tuple(inv_names))) if workers > 1 else None
    t0 = time.monotonic()
    try:
        while (min_level_states is None
               or len(frontier) < min_level_states) \
                and (max_level is None or len(cumulative) - 1 < max_level):
            tasks = (frontier[k:k + CHUNK]
                     for k in range(0, len(frontier), CHUNK))
            done = pool.imap(_expand, tasks) if pool else map(_expand, tasks)
            nxt = []
            for part, n_trans in done:
                transitions += n_trans
                for k, c, broken in part:
                    if k not in seen:
                        seen.add(k)
                        violations += broken
                        nxt.append(c)
            if not nxt:
                break
            cumulative.append(cumulative[-1] + len(nxt))
            frontier = nxt
            if out:
                out(f"level {len(cumulative) - 1}: {cumulative[-1]} orbits, "
                    f"{len(nxt)} new, {transitions} transitions, violations "
                    f"{violations}, {time.monotonic() - t0:.0f}s")
    finally:
        if pool:
            pool.terminate()
            pool.join()
    return cumulative, frontier, violations, transitions


def main(argv=None) -> int:
    """``python3 benchmark/reference/paxos_sym.py ACCEPTORS VALUES MAX_BALLOT
    [WORKERS]``: the whole space of orbits under the minimal majorities as
    ``Quorum``, both invariants on every orbit's member, off the clock (what
    a configuration's pins are taken from)."""
    args = [int(a) for a in (sys.argv[1:] if argv is None else argv)]
    if len(args) not in (3, 4):
        print(main.__doc__, file=sys.stderr)
        return 2
    m = px.model(*args[:3])
    workers = args[3] if len(args) == 4 else max(1, (os.cpu_count() or 2) - 1)
    t0 = time.monotonic()
    cum, _last, viol, trans = bfs_orbit_levels(
        m, workers=workers, out=lambda s: print(s, flush=True))
    levels = [b - a for a, b in zip([0] + cum, cum)]
    group = 1
    for k in range(2, m.n_acceptors + 1):
        group *= k
    for k in range(2, m.n_values + 1):
        group *= k
    print(f"paxos_sym acceptors={m.n_acceptors} values={m.n_values} "
          f"ballots=0..{m.max_ballot} quorums="
          f"{[sorted(q) for q in m.quorums]} |G|={group}: {cum[-1]} orbits, "
          f"{len(cum)} levels (diameter {len(cum) - 1}), widest level "
          f"{max(levels)}, {trans} transitions, {viol} violations, "
          f"{time.monotonic() - t0:.1f}s", flush=True)
    print(f"  levels {levels}", flush=True)
    print(f"  cumulative {cum}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
