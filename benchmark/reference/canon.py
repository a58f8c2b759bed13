"""Plain-Python SYMMETRY reduction (over Server, or over Server and Value)
and level-synchronous BFS, from the spec's Init or the one a configuration
states.

Written for the benchmark (PR 23; the Value axis and the stated Init: PR 33),
independent of the program's ops/symmetry.py: a state's orbit representative
is the smallest tuple form over the server permutations (and, where the
configuration's SYMMETRY names Value too, the value permutations), compared
as STATES (no fingerprint anywhere).
"""

from __future__ import annotations

import functools
import itertools

from benchmark.reference import interp
from benchmark.reference import invariants
from benchmark.reference import msgbits as mb
from benchmark.reference import spec as S
from benchmark.reference.bounds import Bounds

_S_SH, _S_W = mb._HI_FIELDS["src"]
_D_SH, _D_W = mb._HI_FIELDS["dst"]
_KEEP = ~((((1 << _S_W) - 1) << _S_SH) | (((1 << _D_W) - 1) << _D_SH))
# the one value a message carries: mentries[1].value of an AppendEntries
# request (lo field ``e``; 0 where the request carries no entry)
_E_SH, _E_W = mb._LO_FIELDS["e"]
_E_MASK = ((1 << _E_W) - 1) << _E_SH


def as_tuple(s) -> tuple:
    """The parity-mode state as one comparable tuple."""
    return (s.role, s.term, s.votedFor, s.commitIndex, s.log, s.vResp,
            s.vGrant, s.nextIndex, s.matchIndex, s.msgs)


def permute(s, p: tuple, q: tuple | None = None) -> tuple:
    """Tuple form of ``s`` with server j renamed p[j] and, where ``q`` is
    given, value v (1-based, as logs and messages carry it) renamed
    q[v - 1] + 1: in every log entry and in the entry an AppendEntries
    request carries."""
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
        if q is not None and mb.mtype(hi) == S.M_AEREQ and mb.fc(lo):
            lo = (lo & ~_E_MASK) | ((q[mb.fe(lo) - 1] + 1) << _E_SH)
        msgs.append((((hi & _KEEP) | (p[src] << _S_SH) | (p[dst] << _D_SH),
                      lo), cnt))
    msgs.sort()
    logs = rows(s.log)
    if q is not None:
        logs = tuple(tuple((t, q[v - 1] + 1) for t, v in log)
                     for log in logs)
    return (rows(s.role), rows(s.term),
            tuple(0 if v == 0 else p[v - 1] + 1 for v in rows(s.votedFor)),
            rows(s.commitIndex), logs,
            tuple(bits(m) for m in rows(s.vResp)),
            tuple(bits(m) for m in rows(s.vGrant)),
            grid(s.nextIndex), grid(s.matchIndex), tuple(msgs))


def _signature(s, i: int, values: bool) -> tuple:
    """What server i looks like whatever the servers (and, with ``values``,
    the values) are called: under Value symmetry a log counts by its terms
    alone."""
    log = tuple(t for t, _v in s.log[i]) if values else s.log[i]
    return (s.role[i], s.term[i], s.votedFor[i] == 0, s.commitIndex[i],
            log, bin(s.vResp[i]).count("1"), bin(s.vGrant[i]).count("1"))


@functools.lru_cache(maxsize=None)
def _value_perms(n_values: int) -> tuple:
    """The value renamings tried: none (Server symmetry alone) or all."""
    return tuple(itertools.permutations(range(n_values))) if n_values \
        else (None,)


def canonical(s, n_values: int = 0) -> tuple:
    """Smallest permuted tuple form of ``s`` over the server permutations
    and, with ``n_values`` > 0, the permutations of that many values.  Only
    server permutations that put the servers in ascending signature order
    are tried (every value permutation is): the signature is the same
    whatever servers and values are called, so every member of an orbit
    offers the same candidates and the minimum is the orbit's."""
    n = len(s.role)
    if s.allLogs is not None:
        raise ValueError("the benchmark's reference covers parity mode only")
    sig = functools.partial(_signature, s, values=bool(n_values))
    groups = [list(g) for _k, g in itertools.groupby(
        sorted(range(n), key=sig), key=sig)]
    best = None
    for arrangement in itertools.product(
            *(itertools.permutations(g) for g in groups)):
        p = [0] * n
        for new, old in enumerate(itertools.chain(*arrangement)):
            p[old] = new
        for q in _value_perms(n_values):
            t = permute(s, tuple(p), q)
            if best is None or t < best:
                best = t
    return best


def canonical_all_perms(s, n_values: int = 0) -> tuple:
    """The definition, without the signature shortcut (selftest twin)."""
    n = len(s.role)
    return min(permute(s, p, q) for p in itertools.permutations(range(n))
               for q in _value_perms(n_values))


def orbit_key(symmetry, n_values: int):
    """The function that names a state's orbit under the configuration's
    SYMMETRY axes: the state itself (none), ``canonical`` over Server, or
    over Server and the ``n_values`` values.  ``True`` is the Server axis
    alone (how callers said it before there was a second).  Any other set
    of axes is refused by name."""
    axes = ["Server"] if symmetry is True else sorted(symmetry or ())
    if not axes:
        return as_tuple
    if axes == ["Server"]:
        return canonical
    if axes == ["Server", "Value"]:
        return functools.partial(canonical, n_values=n_values)
    raise ValueError(
        "the reference reduces over no axis, over Server, or over Server "
        f"and Value; the configuration's SYMMETRY names {list(symmetry)}")


# what a configuration's ``init`` may state, by the field's name in the spec
_INIT_ROWS = ("role", "term", "votedFor", "commitIndex", "log", "vResp",
              "vGrant")
_INIT_GRIDS = ("nextIndex", "matchIndex")


def stated_init(bounds: Bounds, init: dict | None, inv_names=()):
    """The Init a configuration starts from: the spec's own, with the
    fields its ``init`` states (one entry a server; ``nextIndex`` and
    ``matchIndex`` one row a server) in place of the spec's.  ``role`` is
    stated by name (Follower, Candidate, Leader), ``votedFor`` as Nil or
    s<k>, a log as [term, value] pairs, a vote set as a list of s<k>.  The
    state has to lie inside the bounds and hold every listed invariant;
    ``msgs`` cannot be stated (Init's bag is empty)."""
    s = interp.init_state(bounds)
    if not init:
        return s
    n = bounds.n_servers

    def server(name) -> int:
        k = int(name[1:]) if isinstance(name, str) and name[:1] == "s" \
            and name[1:].isdigit() else 0
        if not 1 <= k <= n:
            raise ValueError(f"init: {name!r} is no server s1..s{n}")
        return k

    def mask(names) -> int:
        return sum({1 << (server(x) - 1) for x in names})

    read = {
        "role": lambda v: S.ROLE_NAMES.index(v),
        "votedFor": lambda v: S.NIL if v == "Nil" else server(v),
        "log": lambda v: tuple((int(t), int(x)) for t, x in v),
        "vResp": mask, "vGrant": mask,
    }
    new = {}
    for field, stated in init.items():
        if field in _INIT_ROWS:
            conv = read.get(field, int)
            value = tuple(conv(v) for v in stated)
            ok = len(value) == n
        elif field in _INIT_GRIDS:
            value = tuple(tuple(int(v) for v in row) for row in stated)
            ok = len(value) == n and all(len(row) == n for row in value)
        else:
            raise ValueError(
                f"init states {field!r}; it may state "
                f"{', '.join(_INIT_ROWS + _INIT_GRIDS)}")
        if not ok:
            raise ValueError(f"init.{field}: not one entry for each of the "
                             f"{n} servers")
        new[field] = value
    s = s._replace(**new)
    in_range = (
        all(1 <= t <= bounds.max_term for t in s.term)
        and all(0 <= c <= bounds.max_log for c in s.commitIndex)
        and all(1 <= t <= bounds.max_term and 1 <= v <= bounds.n_values
                for log in s.log for t, v in log)
        and all(1 <= x <= bounds.max_log + 1 for r in s.nextIndex for x in r)
        and all(0 <= x <= bounds.max_log for r in s.matchIndex for x in r))
    if not in_range or not interp.constraint_ok(s, bounds):
        raise ValueError(f"init lies outside the bounds: {init}")
    broken = [nm for nm in inv_names
              if not invariants.REGISTRY[nm](s, bounds)]
    if broken:
        raise ValueError(f"init breaks {', '.join(broken)}: {init}")
    return s


def bfs_levels(bounds: Bounds, spec: str, symmetry, inv_names: tuple,
               min_level_states: int, init=None):
    """BFS from Init (the spec's, or the state ``init``) until a level holds
    ``min_level_states`` states, under the SYMMETRY axes ``symmetry``.

    Returns ``(cumulative counts per level, that level's states, number of
    invariant violations seen)``.  Semantics as TLC's: a state failing the
    StateConstraint is counted and checked but not expanded; under SYMMETRY
    the first-found member of an orbit is the one kept (so a stated Init
    stands for its orbit: "some server leads").
    """
    table = S.action_table(bounds, spec)
    invs = [invariants.REGISTRY[nm] for nm in inv_names]
    key = orbit_key(symmetry, bounds.n_values)
    if init is None:
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


def successor_orbits(parents, bounds: Bounds, spec: str, symmetry):
    """For the expandable ``parents``: ``(set of successor orbit
    representatives, number of transitions, {representative:
    constraint_ok})``."""
    table = S.action_table(bounds, spec)
    key = orbit_key(symmetry, bounds.n_values)
    reps, n_trans = {}, 0
    for s in parents:
        if not interp.constraint_ok(s, bounds):
            continue
        for _a, t in interp.successors(s, bounds, table):
            n_trans += 1
            reps.setdefault(key(t), interp.constraint_ok(t, bounds))
    return set(reps), n_trans, reps
