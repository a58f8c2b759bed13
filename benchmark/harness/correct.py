"""What decides ``correct``.  Every number compared is printed beside its
limit; all limits are 0, the comparisons are exact (counts, and states
compared as states — the reference never sees a fingerprint)."""

from __future__ import annotations

import random

from benchmark.reference import canon, interp, invariants
from benchmark.reference import spec as S
from benchmark.reference.bounds import Bounds

SAMPLE = 256             # parents drawn with --seed
MIN_LEVEL_STATES = 4096  # the reference BFS stops at the first such level
#                          (a configuration may say otherwise: the toy does)


# the parity-mode state: what crosses between the program's PyState and the
# reference's (two classes of the same shape, by design unrelated)
STATE_FIELDS = ("role", "term", "votedFor", "commitIndex", "log", "vResp",
                "vGrant", "nextIndex", "matchIndex", "msgs")


def _ref_state(s):
    return interp.PyState(**{f: getattr(s, f) for f in STATE_FIELDS})


def pass_checks(pass_list: list, pins: list, end_level: int) -> list:
    """(a) every pass's cumulative count at every level 0..B equals the
    pins and all passes agree; (b) no violation."""
    want = pins[:end_level + 1]
    mismatched = sum(
        sum(a != b for a, b in zip(p.levels, want))
        + abs(len(p.levels) - len(want)) for p in pass_list)
    tables = {tuple(p.levels) for p in pass_list}
    return [
        ("pass_level_mismatches", mismatched, 0),
        ("pass_tables_distinct_minus_1", len(tables) - 1, 0),
        ("passes_short_of_B", sum(not p.reached for p in pass_list), 0),
        ("violations", sum(p.violation is not None for p in pass_list), 0),
    ]


def fixpoint_checks(pass_list: list, pins: list) -> list:
    """A traffic whose passes run to their own end: every pass's result
    says ``complete = True``, and its count is the last pin, the space's
    total.  Where a pass's ``n_states`` is the total, its level table's own
    total is held to it too, levels past the last pin included (a table
    longer than the pins is held by check (a) at the pinned levels only)."""
    total = pins[-1]

    def off(p):
        table = (p.overshoot_levels or p.levels or [0])[-1]
        return abs((p.n_states or 0) - total) or abs(table - total)

    return [
        ("passes_incomplete",
         sum(p.complete is not True for p in pass_list), 0),
        ("fixpoint_total_diff", sum(off(p) for p in pass_list), 0),
    ]


def snapshot_checks(snapshot: dict, pass_list: list, pins: list,
                    end_level: int) -> list:
    """A traffic that starts at depth: the pass that wrote the snapshot met
    every pin 0..S; the snapshot holds the pin at S and whatever its stop
    overshot it by, at most 2 % of a pass's orbits; and every resumed
    engine's first count (its ``run_start``) is the count the snapshot was
    written with.  Levels 0..S of a resumed pass's table come out of the
    snapshot, so check (a) holds the snapshot itself to the pins."""
    s = snapshot["level"]
    first = [p.start_keys for p in pass_list]
    return [
        ("snapshot_pass_problems", int(snapshot["problem"] is not None), 0),
        ("snapshot_overshoot_orbits", abs(snapshot["keys"] - pins[s]),
         (pins[end_level] - pins[s]) // 50),
        ("snapshot_keys_diff",
         sum(snapshot["keys"] if k is None else abs(k - snapshot["keys"])
             for k in first), 0),
    ]


def reference_sample(cfg: dict, seed: int):
    """The plain reference's own BFS of the first levels (from the Init the
    configuration states, under its SYMMETRY axes), and the seeded sample of
    its deepest level with that sample's successor orbits."""
    bounds = Bounds(**cfg["bounds"])
    sym = cfg["symmetry"]
    cum, level, viol = canon.bfs_levels(
        bounds, cfg["spec"], sym, tuple(cfg["invariants"]),
        cfg.get("sample_min_level_states", MIN_LEVEL_STATES),
        init=canon.stated_init(bounds, cfg.get("init"), cfg["invariants"]))
    rng = random.Random(seed)
    parents = rng.sample(level, min(SAMPLE, len(level)))
    reps, n_trans, con = canon.successor_orbits(parents, bounds, cfg["spec"],
                                                sym)
    return {"cumulative": cum, "violations": viol, "level": level,
            "parents": parents, "orbits": reps, "n_transitions": n_trans,
            "constraint": con, "sym": sym, "n_values": bounds.n_values}


def sample_checks(ref: dict, got: dict, pins: list) -> list:
    """(c) the compiled segment's stream for the sampled parents against the
    reference: the same set of successor orbits (states canonicalised in
    plain Python), key and orbit in one-to-one correspondence (the 64-bit
    key is exact on the sample), the same transition count and constraint
    flags.  Where the segment ran on a mesh (``got["misrouted"]``), the
    stream is the union of the shards' streams and one number joins:
    ``owner_misrouted``, streamed keys found on a shard other than
    ``key_hi % ndev``."""
    key = canon.orbit_key(ref["sym"], ref.get("n_values", 0))
    streamed = [key(_ref_state(s)) for s in got["states"]]
    sset = set(streamed)
    con_wrong = sum(ref["constraint"].get(k) is not c
                    for k, c in zip(streamed, got["con"]) if k in ref["orbits"])
    # the stream may repeat a key (the device filter is lossy; the host
    # dedups exactly), but key and orbit must name each other one to one
    by_key, by_orbit = {}, {}
    for k, o in zip(got["keys"].tolist(), streamed):
        by_key.setdefault(k, set()).add(o)
        by_orbit.setdefault(o, set()).add(k)
    conflicts = sum(len(v) > 1 for v in by_key.values()) \
        + sum(len(v) > 1 for v in by_orbit.values())
    cum = ref["cumulative"]
    # on a mesh, the guarantee the exchange adds: every candidate is filtered
    # and streamed by the shard that owns its key
    owner = [("owner_misrouted", got["misrouted"], 0)] \
        if "misrouted" in got else []
    return [
        ("ref_bfs_level_mismatches",
         sum(a != b for a, b in zip(cum, pins)) + max(0, len(cum) - len(pins)),
         0),
        ("ref_bfs_violations", ref["violations"], 0),
        ("sample_orbits_missing", len(ref["orbits"] - sset), 0),
        ("sample_orbits_extra", len(sset - ref["orbits"]), 0),
        ("sample_key_orbit_conflicts", conflicts, 0),
        ("sample_transitions_diff",
         abs(got["n_transitions"] - ref["n_transitions"]), 0),
        ("sample_constraint_flags_wrong", con_wrong, 0),
        ("sample_segment_flags", int(got["fail"] != 0)
         + int(not got["done"]), 0),
    ] + owner


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


def planted_checks(plant: dict, got: dict) -> list:
    """(d) every invariant is evaluated by the compiled segment: run from
    the planted parent, the engine has to report a violation, and the state
    and invariant it names have to be among the reference's."""
    missed = got["invariant"] is None
    wrong = 0
    if not missed:
        names = plant["violators"].get(plant["key"](_ref_state(got["state"])))
        wrong = int(names is None or got["invariant"] not in names)
    return [("planted_violation_missed", int(missed), 0),
            ("planted_violation_misnamed", wrong, 0)]


def decide(checks: list, out=print) -> bool:
    ok = True
    for name, value, limit in checks:
        good = value <= limit
        ok = ok and good
        out(f"check {name}={value} limit={limit} {'ok' if good else 'FAIL'}")
    return ok
