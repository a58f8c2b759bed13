"""The Raft family with its history variables: bernborgess/raft-tla
``raft.tla`` as stock TLC explores it, every variable of ``vars`` part of the
state (``elections`` raft.tla:39, ``allLogs`` raft.tla:44, ``voterLog``
raft.tla:77, the ``mlog`` fields raft.tla:220-222, 297-299): the program's
faithful mode (``Bounds.history``, ``check.py --faithful``).  A configuration
says ``"family": "raft_hist"`` and, in ``bounds``, ``"history": true`` with
the ``max_elections`` slots the program's encoding is given.

The reference half: transitions ``benchmark/reference/interp.py`` under
``Bounds(history=True)`` as it stands; the canonical form of a state with
history ``reference/canon_hist.py``; the invariants ``reference/
invariants_hist.py`` (the three history invariants, and the Raft family's
state invariants, which read no history variable).  Two states that differ
in a history variable alone are two states.

The program half is the Raft family's, by import where it is the same
(``check_config``, ``gates``, ``scan_words``: the step builder, the scan and
the engine are ``flagship3``'s) and said here where the state is wider: the
crossing carries ``allLogs``, ``vLog`` and ``elections`` beside the parity
fields, and ``mlog`` inside the message words.

The program symbols used are the Raft family's frozen interface
(``families/raft.py``), with ``models/interp.PyState``'s three history fields
(``allLogs``, ``vLog``, ``elections``: a sorted tuple of logs, a grid of
optional logs, a sorted tuple of ``(eterm, eleader, elog, evotes,
evoterLog)``) and ``config.Bounds``' ``history`` / ``max_elections``.
"""

from __future__ import annotations

import random

from benchmark.families import raft
from benchmark.families.raft import (  # noqa: F401  (the same program half)
    check_config, gates, scan_words)
from benchmark.reference import canon_hist, interp, invariants_hist
from benchmark.reference import spec as S
from benchmark.reference.bounds import Bounds

# the state with history: what crosses between the program's PyState and the
# reference's
STATE_FIELDS = raft.STATE_FIELDS + ("allLogs", "vLog", "elections")


# ------------------------------------------------------- the program's side

def to_program(s):
    """A reference state with its history as the program's PyState: what
    ``check(init_override=)`` takes."""
    from raft_tla_tpu.models import interp as pinterp
    return pinterp.PyState(**{f: getattr(s, f) for f in STATE_FIELDS})


def from_program(s):
    """The crossing back: the state a violation names, as a reference
    state, history included."""
    return interp.PyState(**{f: getattr(s, f) for f in STATE_FIELDS})


def pack_rows(eng, parents: list):
    """``parents`` (reference states) as the packed rows and constraint
    flags the compiled segment takes."""
    import numpy as np
    from raft_tla_tpu.models import interp as pinterp
    rows = np.zeros((len(parents), eng.schema.P), np.int32)
    con = np.zeros((len(parents),), bool)
    for k, s in enumerate(parents):
        ps = to_program(s)
        rows[k] = eng.schema.pack(
            np.asarray(pinterp.to_vec(ps, eng.bounds), np.int32), np)
        con[k] = pinterp.constraint_ok(ps, eng.bounds)
    return rows, con


def decode_rows(eng, orows) -> list:
    """The rows a segment streamed, as reference states with history."""
    import numpy as np
    from raft_tla_tpu.models import interp as pinterp
    from raft_tla_tpu.ops import state as st
    return [from_program(pinterp.from_struct(
        st.unpack(eng.schema.unpack(np.asarray(row), np), eng.lay, np),
        eng.bounds)) for row in orows]


# ------------------------------------------------------ the plain reference

def bounds(cfg: dict) -> Bounds:
    b = Bounds(**cfg["bounds"])
    if not b.history:
        raise ValueError(f"configuration {cfg.get('name')}: family raft_hist "
                         "needs bounds.history = true (parity mode is family "
                         "raft)")
    return b


def stated_init(cfg: dict):
    """No configuration of this family states its Init (a stated Init would
    have to state its history too); one that does is refused by name."""
    if "init" in cfg:
        raise ValueError(f"configuration {cfg.get('name')}: family raft_hist "
                         "starts from the spec's Init")
    return None


def _invs(cfg: dict) -> dict:
    return {nm: invariants_hist.REGISTRY[nm] for nm in cfg["invariants"]}


def bfs_levels(cfg: dict, min_level_states: int):
    """The plain reference's BFS over full states from Init, under the
    configuration's SYMMETRY, to the first level of ``min_level_states``
    states: ``(cumulative counts, that level's states, violations)``."""
    return canon_hist.bfs_levels(bounds(cfg), cfg["spec"], cfg["symmetry"],
                                 _invs(cfg), min_level_states)


def successor_orbits(parents: list, cfg: dict):
    """``(successor orbits, transitions, {orbit: constraint_ok})`` of the
    expandable ``parents``."""
    return canon_hist.successor_orbits(parents, bounds(cfg), cfg["spec"],
                                       cfg["symmetry"])


def orbit_key(cfg: dict):
    """The function that names a state's orbit, history included."""
    return canon_hist.orbit_key(cfg["symmetry"])


def holds(s, cfg: dict) -> list:
    """Names of the configuration's invariants that ``s`` breaks."""
    b = bounds(cfg)
    return [nm for nm, f in _invs(cfg).items() if not f(s, b)]


def _second_election_of_a_term(s, b: Bounds, rng):
    """``s`` rewritten so that ``elections`` records server i as the leader
    elected in term t, i has since restarted (a follower of t: no state
    invariant sees it), and server j is a candidate of t holding a quorum of
    votes: ``BecomeLeader(j)`` puts a second record of term t into
    ``elections``, with another leader."""
    n = b.n_servers
    i, j = rng.sample(range(n), 2)
    t = max(s.term)

    def quorum_of(k):
        votes = 1 << k
        for v in rng.sample([v for v in range(n) if v != k], n // 2):
            votes |= 1 << v
        return votes

    votes = quorum_of(j)
    role = tuple(S.CANDIDATE if k == j
                 else S.FOLLOWER if k == i or (r == S.LEADER
                                               and s.term[k] == t)
                 else r for k, r in enumerate(s.role))
    record = (t, i, s.log[i], quorum_of(i), (None,) * n)
    elections = [r for r in s.elections if r[0] != t] + [record]
    return s._replace(
        role=role,
        term=tuple(t if k in (i, j) else x for k, x in enumerate(s.term)),
        votedFor=tuple(j + 1 if k == j else v
                       for k, v in enumerate(s.votedFor)),
        vResp=tuple(votes if k == j else v for k, v in enumerate(s.vResp)),
        vGrant=tuple(votes if k == j else v
                     for k, v in enumerate(s.vGrant)),
        elections=tuple(sorted(elections, key=interp._election_key)))


def planted_fault(cfg: dict, level: list, seed: int) -> dict:
    """The planted fault, in ``elections``: a state of the reference's
    level, drawn with the seed and rewritten
    (:func:`_second_election_of_a_term`) so that it holds every listed
    invariant itself, fits the program's ``max_elections`` slots with one to
    spare, and one step, ``BecomeLeader(j)``, gives a term two elected
    leaders: ``ElectionSafetyHist``, which no state invariant can see (the
    first leader is a follower again).  Returns the parent and ``{orbit of a
    violating successor: names of the invariants it breaks}``, both judged
    by the plain reference."""
    b = bounds(cfg)
    invs = _invs(cfg)
    if "ElectionSafetyHist" not in invs:
        raise ValueError(f"configuration {cfg.get('name')} lists no "
                         "ElectionSafetyHist for the planted fault to break")
    key = orbit_key(cfg)
    table = S.action_table(b, cfg["spec"])
    if S.BECOMELEADER not in {a.family for a in table}:
        raise ValueError(f"spec {cfg['spec']!r} has no BecomeLeader: nothing "
                         "writes elections, no planted fault is known for it")
    rng = random.Random(f"plant/{seed}")
    for s in rng.sample(level, len(level)):
        parent = _second_election_of_a_term(s, b, rng)
        if len(parent.elections) >= b.max_elections \
                or not interp.constraint_ok(parent, b) \
                or not all(f(parent, b) for f in invs.values()):
            continue
        violators = {}
        for _a, nxt in interp.successors(parent, b, table):
            broken = [nm for nm, f in invs.items() if not f(nxt, b)]
            if broken:
                violators[key(nxt)] = broken
        if violators and all(names == ["ElectionSafetyHist"]
                             for names in violators.values()):
            return {"parent": parent, "violators": violators, "key": key}
    raise ValueError("no state of the reference level takes the planted "
                     "fault")
