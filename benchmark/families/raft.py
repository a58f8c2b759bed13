"""The Raft family: bernborgess/raft-tla ``raft.tla`` in parity mode, every
configuration of the accepted benchmark (a configuration file with no
``"family"`` key is this one).

A family is everything ``drive``, ``correct`` and ``run`` know of a spec: its
plain reference (``benchmark/reference/``: canon, interp, spec, invariants),
how a reference state crosses to the program and back, the planted fault, and
the counts of one chunk step.  The harness holds the module
(``manifest.family``) and asks it for the names below, nothing else.

The program symbols used here are the benchmark's frozen interface for this
family (README, "What the benchmark holds the program to"): ``config.Bounds``
/ ``CheckConfig``, ``utils/cfgparse.parse_cfg``, the row codec
``models/interp.to_vec`` / ``from_struct`` / ``PyState`` / ``constraint_ok``,
``ops/state.unpack``, ``engine.schema.pack`` / ``.unpack``, ``engine.lay``,
``engine.bounds``, ``engine.A``, ``engine.config``, and
``ops/kernels.step_signature`` (printed only).
"""

from __future__ import annotations

import random

from benchmark.harness import work
from benchmark.reference import canon, interp, invariants
from benchmark.reference import spec as S
from benchmark.reference.bounds import Bounds

# the parity-mode state: what crosses between the program's PyState and the
# reference's (two classes of the same shape, by design unrelated)
STATE_FIELDS = ("role", "term", "votedFor", "commitIndex", "log", "vResp",
                "vGrant", "nextIndex", "matchIndex", "msgs")


# ------------------------------------------------------- the program's side

def check_config(cfg: dict):
    """The program's ``CheckConfig`` for this configuration, its
    ``cfg_text`` held to the fields beside it."""
    from raft_tla_tpu.config import Bounds, CheckConfig
    from raft_tla_tpu.utils import cfgparse
    tlc = cfgparse.parse_cfg(cfg["cfg_text"])
    b = cfg["bounds"]
    said = (len(tlc.server_names()), len(tlc.value_names()),
            sorted(tlc.invariants), sorted(tlc.symmetry))
    want = (b["n_servers"], b["n_values"], sorted(cfg["invariants"]),
            sorted(cfg["symmetry"]))
    if said != want:
        raise ValueError(f"config {cfg['name']}: cfg_text says {said}, the "
                         f"fields say {want}")
    return CheckConfig(bounds=Bounds(**b), spec=cfg["spec"],
                       invariants=tuple(cfg["invariants"]),
                       symmetry=tuple(cfg["symmetry"]), chunk=cfg["chunk"])


def to_program(s):
    """A reference state as the program's PyState (same fields, two
    unrelated classes): what ``check(init_override=)`` takes."""
    from raft_tla_tpu.models import interp as pinterp
    return pinterp.PyState(**{f: getattr(s, f) for f in STATE_FIELDS})


def from_program(s):
    """The crossing back: the state a violation names, as a reference
    state."""
    return interp.PyState(**{f: getattr(s, f) for f in STATE_FIELDS})


def pack_rows(eng, parents: list):
    """``parents`` (reference states) as the packed rows and constraint
    flags the compiled segment takes."""
    import numpy as np
    from raft_tla_tpu.models import interp as pinterp
    n, P = len(parents), eng.schema.P
    rows = np.zeros((n, P), np.int32)
    con = np.zeros((n,), bool)
    for k, s in enumerate(parents):
        ps = to_program(s)
        rows[k] = eng.schema.pack(
            np.asarray(pinterp.to_vec(ps, eng.bounds), np.int32), np)
        con[k] = pinterp.constraint_ok(ps, eng.bounds)
    return rows, con


def decode_rows(eng, orows) -> list:
    """The rows a segment streamed, as reference states."""
    import numpy as np
    from raft_tla_tpu.models import interp as pinterp
    from raft_tla_tpu.ops import state as st
    states = []
    for row in orows:
        vec = eng.schema.unpack(np.asarray(row), np)
        states.append(from_program(pinterp.from_struct(
            st.unpack(vec, eng.lay, np), eng.bounds)))
    return states


def gates(eng, cfg: dict) -> dict:
    """The construction-time gates of the step this engine was built with,
    as the program resolves them (printed, never compared)."""
    from raft_tla_tpu.ops import kernels
    sig = kernels.step_signature(eng.bounds, cfg["spec"],
                                 tuple(cfg["invariants"]),
                                 tuple(cfg["symmetry"]), None)
    return dict(sig[5:])


def scan_words(eng) -> int:
    """32-bit words the orbit scan touches in one chunk step."""
    return work.scan_words(
        eng.config.chunk, eng.A, eng.bounds.n_servers, eng.lay.width,
        bool(eng.config.symmetry))


# ------------------------------------------------------ the plain reference

def bounds(cfg: dict) -> Bounds:
    return Bounds(**cfg["bounds"])


def stated_init(cfg: dict):
    """The Init the configuration states, or ``None`` where it states none
    (the spec's own: the passes are then handed no ``init_override``)."""
    if "init" not in cfg:
        return None
    return canon.stated_init(bounds(cfg), cfg["init"], cfg["invariants"])


def bfs_levels(cfg: dict, min_level_states: int):
    """The plain reference's BFS from the Init the configuration states,
    under its SYMMETRY axes, to the first level of ``min_level_states``
    states: ``(cumulative counts, that level's states, violations)``."""
    b = bounds(cfg)
    return canon.bfs_levels(
        b, cfg["spec"], cfg["symmetry"], tuple(cfg["invariants"]),
        min_level_states,
        init=canon.stated_init(b, cfg.get("init"), cfg["invariants"]))


def successor_orbits(parents: list, cfg: dict):
    """``(successor orbits, transitions, {orbit: constraint_ok})`` of the
    expandable ``parents``."""
    return canon.successor_orbits(parents, bounds(cfg), cfg["spec"],
                                  cfg["symmetry"])


def orbit_key(cfg: dict):
    """The function that names a reference state's orbit under the
    configuration's SYMMETRY axes."""
    return canon.orbit_key(cfg["symmetry"], cfg["bounds"]["n_values"])


def holds(s, cfg: dict) -> list:
    """Names of the configuration's invariants that ``s`` breaks (empty:
    it holds them all)."""
    b = bounds(cfg)
    return [nm for nm in cfg["invariants"]
            if not invariants.REGISTRY[nm](s, b)]


def _two_leaders_in_a_term(s, bounds: Bounds, rng):
    """``s`` rewritten so that server i leads term t and server j is a
    candidate of term t holding a quorum of votes: its ``BecomeLeader(j)``
    successor has two leaders in one term."""
    n = bounds.n_servers
    i, j = rng.sample(range(n), 2)
    t = max(s.term)
    votes = 1 << j
    for k in rng.sample([k for k in range(n) if k != j], n // 2):
        votes |= 1 << k
    role = tuple(S.LEADER if k == i else S.CANDIDATE if k == j
                 else S.FOLLOWER if (r == S.LEADER and s.term[k] == t)
                 else r for k, r in enumerate(s.role))
    term = tuple(t if k in (i, j) else x for k, x in enumerate(s.term))
    return s._replace(
        role=role, term=term,
        votedFor=tuple(j + 1 if k == j else v
                       for k, v in enumerate(s.votedFor)),
        vResp=tuple(votes if k == j else v for k, v in enumerate(s.vResp)),
        vGrant=tuple(votes if k == j else v
                     for k, v in enumerate(s.vGrant)))


def _commit_a_later_leader_lacks(s, bounds: Bounds, rng):
    """``s`` rewritten so that beside its leader i of the newest term t a
    server j leads term t - 1 with one entry of that term in its log, and
    ``matchIndex[j]`` claims a quorum for it: ``AdvanceCommitIndex(j)``
    commits an entry that the later leader's log lacks.  It needs only the
    log actions.  None where ``s`` has no leader of a term above 1."""
    n, t = bounds.n_servers, max(s.term)
    leaders = [k for k in range(n) if s.role[k] == S.LEADER and s.term[k] == t]
    if not leaders or t < 2:
        return None
    i = rng.choice(leaders)
    j = rng.choice([k for k in range(n) if k != i])
    entry = (t - 1, rng.randint(1, bounds.n_values))
    agreed = set(rng.sample([k for k in range(n) if k != j], n // 2))

    def put(row, v):
        return tuple(v if k == j else x for k, x in enumerate(row))

    return s._replace(
        role=put(s.role, S.LEADER), term=put(s.term, t - 1),
        commitIndex=put(s.commitIndex, 0), log=put(s.log, (entry,)),
        matchIndex=put(s.matchIndex,
                       tuple(int(k in agreed) for k in range(n))))


def planted_fault(cfg: dict, level: list, seed: int) -> dict:
    """The planted fault: a state of the reference's level, drawn with the
    seed and rewritten so that it holds every invariant itself and one step
    breaks one.  Which rewrite is decided by the configuration's action
    table, never by its file: where the table has ``BecomeLeader``, two
    leaders in one term; where it has not and has ``AdvanceCommitIndex``, a
    commit that a later leader's log lacks (``LeaderCompleteness``).
    Returns the parent and ``{orbit of a violating successor: names of the
    invariants it breaks}``, both judged by the plain reference."""
    bounds = Bounds(**cfg["bounds"])
    invs = {nm: invariants.REGISTRY[nm] for nm in cfg["invariants"]}
    key = canon.orbit_key(cfg["symmetry"], bounds.n_values)
    table = S.action_table(bounds, cfg["spec"])
    families = {a.family for a in table}
    if S.BECOMELEADER in families:
        rewrite = _two_leaders_in_a_term
    elif S.ADVANCECOMMIT in families:
        rewrite = _commit_a_later_leader_lacks
    else:
        raise ValueError(f"spec {cfg['spec']!r} has neither BecomeLeader nor "
                         "AdvanceCommitIndex: no planted fault is known for it")
    rng = random.Random(f"plant/{seed}")
    for s in rng.sample(level, len(level)):
        parent = rewrite(s, bounds, rng)
        if parent is None or not interp.constraint_ok(parent, bounds) \
                or not all(f(parent, bounds) for f in invs.values()):
            continue
        violators = {}
        for _a, nxt in interp.successors(parent, bounds, table):
            broken = [nm for nm, f in invs.items() if not f(nxt, bounds)]
            if broken:
                violators[key(nxt)] = broken
        if violators:
            return {"parent": parent, "violators": violators, "key": key}
    raise ValueError("no state of the reference level takes the planted "
                     "fault; the configuration lists no invariant it breaks")
