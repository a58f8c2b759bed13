"""Plain-Python SYMMETRY Server reduction and level-synchronous BFS.

Written for the benchmark (PR 23), independent of the program's
ops/symmetry.py: a state's orbit representative is the smallest tuple form
over the server permutations, compared as STATES (no fingerprint anywhere).
"""

from __future__ import annotations

import itertools

from benchmark.reference import interp
from benchmark.reference import invariants
from benchmark.reference import msgbits as mb
from benchmark.reference import spec as S
from benchmark.reference.bounds import Bounds

_S_SH, _S_W = mb._HI_FIELDS["src"]
_D_SH, _D_W = mb._HI_FIELDS["dst"]
_KEEP = ~((((1 << _S_W) - 1) << _S_SH) | (((1 << _D_W) - 1) << _D_SH))


def as_tuple(s) -> tuple:
    """The parity-mode state as one comparable tuple."""
    return (s.role, s.term, s.votedFor, s.commitIndex, s.log, s.vResp,
            s.vGrant, s.nextIndex, s.matchIndex, s.msgs)


def permute(s, p: tuple) -> tuple:
    """Tuple form of ``s`` with server j renamed p[j]."""
    n = len(p)
    inv = [0] * n
    for j, k in enumerate(p):
        inv[k] = j

    def rows(t):
        return tuple(t[inv[k]] for k in range(n))

    def bits(mask):
        out = 0
        for j in range(n):
            if (mask >> j) & 1:
                out |= 1 << p[j]
        return out

    def grid(m):
        return tuple(tuple(m[inv[k]][inv[l]] for l in range(n))
                     for k in range(n))

    msgs = []
    for (hi, lo), cnt in s.msgs:
        src = (hi >> _S_SH) & ((1 << _S_W) - 1)
        dst = (hi >> _D_SH) & ((1 << _D_W) - 1)
        msgs.append((((hi & _KEEP) | (p[src] << _S_SH) | (p[dst] << _D_SH),
                      lo), cnt))
    msgs.sort()
    return (rows(s.role), rows(s.term),
            tuple(0 if v == 0 else p[v - 1] + 1 for v in rows(s.votedFor)),
            rows(s.commitIndex), rows(s.log),
            tuple(bits(m) for m in rows(s.vResp)),
            tuple(bits(m) for m in rows(s.vGrant)),
            grid(s.nextIndex), grid(s.matchIndex), tuple(msgs))


def _signature(s, i: int) -> tuple:
    """What server i looks like whatever the servers are called."""
    return (s.role[i], s.term[i], s.votedFor[i] == 0, s.commitIndex[i],
            s.log[i], bin(s.vResp[i]).count("1"), bin(s.vGrant[i]).count("1"))


def canonical(s) -> tuple:
    """Smallest permuted tuple form of ``s``.  Only permutations that put
    the servers in ascending signature order are tried: every member of an
    orbit offers the same candidates, so the minimum is the orbit's."""
    n = len(s.role)
    if s.allLogs is not None:
        raise ValueError("the benchmark's reference covers parity mode only")
    order = sorted(range(n), key=lambda i: _signature(s, i))
    groups = [list(g) for _k, g in itertools.groupby(
        order, key=lambda i: _signature(s, i))]
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
    """The definition, without the signature shortcut (selftest twin)."""
    n = len(s.role)
    return min(permute(s, p) for p in itertools.permutations(range(n)))


def bfs_levels(bounds: Bounds, spec: str, symmetry: bool, inv_names: tuple,
               min_level_states: int):
    """BFS from Init until a level holds ``min_level_states`` states.

    Returns ``(cumulative counts per level, that level's states, number of
    invariant violations seen)``.  Semantics as TLC's: a state failing the
    StateConstraint is counted and checked but not expanded; under SYMMETRY
    the first-found member of an orbit is the one kept.
    """
    table = S.action_table(bounds, spec)
    invs = [invariants.REGISTRY[nm] for nm in inv_names]
    key = canonical if symmetry else as_tuple
    init = interp.init_state(bounds)
    seen = {key(init)}
    violations = sum(not f(init, bounds) for f in invs)
    cumulative = [1]
    frontier = [init]
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
                violations += sum(not f(t, bounds) for f in invs)
                nxt.append(t)
        if not nxt:
            break
        cumulative.append(cumulative[-1] + len(nxt))
        frontier = nxt
    return cumulative, frontier, violations


def successor_orbits(parents, bounds: Bounds, spec: str, symmetry: bool):
    """For the expandable ``parents``: ``(set of successor orbit
    representatives, number of transitions, {representative:
    constraint_ok})``."""
    table = S.action_table(bounds, spec)
    key = canonical if symmetry else as_tuple
    reps, n_trans = {}, 0
    for s in parents:
        if not interp.constraint_ok(s, bounds):
            continue
        for _a, t in interp.successors(s, bounds, table):
            n_trans += 1
            reps.setdefault(key(t), interp.constraint_ok(t, bounds))
    return set(reps), n_trans, reps
