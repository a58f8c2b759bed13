"""Plain-Python SYMMETRY Server reduction and BFS for states that carry
``raft.tla``'s history variables (faithful mode: ``elections``
raft.tla:39, ``allLogs`` raft.tla:44, ``voterLog`` raft.tla:77 and the
``mlog`` fields of RequestVoteResponse / AppendEntriesRequest,
raft.tla:220-222, 297-299), as stock TLC fingerprints them.

Written for the benchmark (PR 50) from ``raft.tla``, not from the program's
``ops/symmetry.py``: the parity half of the tuple is ``canon.permute``'s;
what a server permutation p does to the history is said here, variable by
variable:

- ``allLogs`` is a set of logs, and a log names no server: fixed;
- ``voterLog[i][j]`` is a function of servers to functions of servers to
  logs: both axes are re-indexed, the logs stay;
- an ``elections`` record ``[eterm, eleader, elog, evotes, evoterLog]``
  names servers three ways: ``eleader`` is a member (relabelled), ``evotes``
  a set of members (each bit relabelled), ``evoterLog`` a function of members
  (re-indexed); ``eterm`` and ``elog`` stay.  ``elections`` is a SET: its
  image is the set of the records' images, compared here as the sorted tuple
  of them;
- ``mlog`` is a log inside a message (a rank in the message's lo word): it
  stays, while ``canon.permute`` relabels the message's source and
  destination.

States are compared as STATES, history included: no fingerprint anywhere.
"""

from __future__ import annotations

import functools
import itertools

from benchmark.reference import canon, interp
from benchmark.reference import spec as S
from benchmark.reference.bounds import Bounds


def _opt(log) -> tuple:
    """An optional log as a comparable tuple: absent sorts before every
    log, the empty log ``()`` included."""
    return () if log is None else (log,)


def _with_history(s):
    if s.allLogs is None:
        raise ValueError("canon_hist compares states with their history; "
                         "this one carries none (parity mode: canon.py)")
    return s


def as_tuple(s) -> tuple:
    """The full state, history included, as one comparable tuple."""
    return permute(s, tuple(range(len(s.role))))


def permute(s, p: tuple) -> tuple:
    """Tuple form of ``s`` with server j renamed p[j]: the parity fields as
    ``canon.permute`` has them, then ``allLogs``, ``voterLog`` and
    ``elections``."""
    n = len(_with_history(s).role)
    inv = [0] * n
    for j, k in enumerate(p):
        inv[k] = j

    def bits(mask):
        out = 0
        for j in range(n):
            if (mask >> j) & 1:
                out |= 1 << p[j]
        return out

    vlog = tuple(tuple(_opt(s.vLog[inv[k]][inv[l]]) for l in range(n))
                 for k in range(n))
    elections = tuple(sorted(
        (eterm, p[eleader], elog, bits(evotes),
         tuple(_opt(evlog[inv[l]]) for l in range(n)))
        for eterm, eleader, elog, evotes, evlog in s.elections))
    return canon.permute(s, p) + (tuple(sorted(s.allLogs)), vlog, elections)


def _signature(s, i: int) -> tuple:
    """What server i looks like whatever the servers are called: the parity
    signature, the logs its ``voterLog`` row holds and the elections it won
    (neither names a server by more than a count)."""
    return (s.role[i], s.term[i], s.votedFor[i] == 0, s.commitIndex[i],
            s.log[i], bin(s.vResp[i]).count("1"),
            bin(s.vGrant[i]).count("1"),
            tuple(sorted(_opt(l) for l in s.vLog[i])),
            tuple(sorted((r[0], r[2]) for r in s.elections if r[1] == i)))


def canonical(s) -> tuple:
    """Smallest permuted tuple form of ``s`` over the server permutations.
    Only those that put the servers in ascending signature order are tried:
    the signature is the same whatever the servers are called, so every
    member of an orbit offers the same candidates and the minimum is the
    orbit's."""
    n = len(_with_history(s).role)
    sig = functools.partial(_signature, s)
    groups = [list(g) for _k, g in itertools.groupby(
        sorted(range(n), key=sig), key=sig)]
    best = None
    for arrangement in itertools.product(
            *(itertools.permutations(g) for g in groups)):
        p = [0] * n
        for new, old in enumerate(itertools.chain(*arrangement)):
            p[old] = new
        t = permute(s, tuple(p))
        if best is None or t < best:
            best = t
    return best


def canonical_all_perms(s) -> tuple:
    """The definition: the least image over all n! permutations (the tests'
    twin of ``canonical``)."""
    n = len(s.role)
    return min(permute(s, p) for p in itertools.permutations(range(n)))


def drop_history(s):
    """``s`` without its history variables and with every ``mlog`` zeroed:
    the parity-mode state it projects to."""
    from benchmark.reference import msgbits as mb
    sh, w = mb._LO_FIELDS["g"]
    keep = ~(((1 << w) - 1) << sh)
    bag = {}
    for (hi, lo), cnt in s.msgs:
        m = (hi, lo & keep)
        bag[m] = bag.get(m, 0) + cnt
    return s._replace(allLogs=None, vLog=None, elections=None,
                      msgs=tuple(sorted(bag.items())))


def orbit_key(symmetry):
    """The function that names a state's orbit: the state itself (no
    SYMMETRY) or ``canonical`` over Server; any other axis is refused by
    name (Value symmetry would rename the logs inside every history
    variable: no configuration asks for it)."""
    axes = sorted(symmetry or ())
    if not axes:
        return as_tuple
    if axes == ["Server"]:
        return canonical
    raise ValueError(
        "the reference with history reduces over no axis or over Server; "
        f"the configuration's SYMMETRY names {list(symmetry)}")


def bfs_levels(bounds: Bounds, spec: str, symmetry, invs: dict,
               min_level_states: int, on_level=None):
    """``canon.bfs_levels`` over full states: BFS from Init until a level
    holds ``min_level_states`` states.  ``invs`` maps a name to its
    predicate.  Returns ``(cumulative counts per level, that level's states,
    number of invariant violations seen)``; TLC's semantics (a state failing
    the StateConstraint is counted and checked, not expanded; the first-found
    member of an orbit is the one kept).  ``on_level(level, states)`` sees
    every level as it closes (the tests keep the level sets)."""
    if not bounds.history:
        raise ValueError("canon_hist.bfs_levels needs Bounds(history=True)")
    table = S.action_table(bounds, spec)
    key = orbit_key(symmetry)
    init = interp.init_state(bounds)
    seen = {key(init)}
    violations = sum(not f(init, bounds) for f in invs.values())
    cumulative, frontier = [1], [init]
    if on_level:
        on_level(0, frontier)
    while frontier and len(frontier) < min_level_states:
        nxt = []
        for s in frontier:
            if not interp.constraint_ok(s, bounds):
                continue
            for _a, t in interp.successors(s, bounds, table):
                k = key(t)
                if k in seen:
                    continue
                seen.add(k)
                violations += sum(not f(t, bounds) for f in invs.values())
                nxt.append(t)
        if not nxt:
            break
        cumulative.append(cumulative[-1] + len(nxt))
        frontier = nxt
        if on_level:
            on_level(len(cumulative) - 1, frontier)
    return cumulative, frontier, violations


def successor_orbits(parents, bounds: Bounds, spec: str, symmetry):
    """For the expandable ``parents``: ``(set of successor orbit
    representatives, number of transitions, {representative:
    constraint_ok})``."""
    table = S.action_table(bounds, spec)
    key = orbit_key(symmetry)
    reps, n_trans = {}, 0
    for s in parents:
        if not interp.constraint_ok(s, bounds):
            continue
        for _a, t in interp.successors(s, bounds, table):
            n_trans += 1
            reps.setdefault(key(t), interp.constraint_ok(t, bounds))
    return set(reps), n_trans, reps
