"""The Paxos family on the device engine: Lamport's single-decree Paxos
(``Paxos.tla`` with its model ``MCPaxos.tla`` / ``MCPaxos.cfg`` of
tlaplus/Examples ``specifications/Paxos/``) run by the ``ddd`` engine from the
frontend IR (``--spec paxos --engine ddd``).

Both halves are here.  The reference half is ``benchmark/reference/paxos.py``
(the TLA+ text transcribed by hand, states kept as states, nothing of the
program): its BFS, successor states, the two invariants and the planted fault
(one value chosen at a lower ballot, and a "2a" message for the other value at
a higher ballot one ``Phase2b`` short of a quorum: that step breaks
``Consistency``).  The program half takes a state across and runs it.

What a configuration of this family states: ``"family": "paxos_ddd"``,
``"bounds": {"n_acceptors": n, "n_values": k, "max_ballot": B}``,
``"quorums"`` (the source's ``Quorum``, a list of lists of acceptor names
``a1 .. an``, taken as written and never recomputed as "the majorities"),
``"invariants"`` (of ``TypeOK``, ``Consistency``), ``"symmetry": []``, no
``"init"``, and ``cfg_text``: the source's cfg as remembered, held here to the
fields beside it.

The program symbols used here are the benchmark's frozen interface for this
family (README, "What the benchmark holds the program to"):
``config.Bounds`` / ``CheckConfig`` (``spec="paxos"``, ``n_servers`` = the
acceptor count, ``n_values``, ``max_term`` = the maximum ballot,
``Bounds.constants = (("Quorum", rows),)``, one 0/1 row a quorum);
``utils/cfgparse.parse_cfg`` (``.constants["Acceptor" | "Value" | "Quorum"]``,
``.invariants``, ``.specification``, ``.symmetry``) and
``cfgparse.set_of_subsets``;
``frontend/registry.resolve_model("paxos").engines`` (holds ``"ddd"`` where
the device engine runs the spec; a program that does not know the spec, or
lacks the engine, is refused by name, so a parent of the PR that brought it
fails at once); the row codec ``frontend/paxos.PaxosState`` / ``to_vec`` /
``from_vec``, ``engine.schema.pack`` / ``.unpack``, ``engine.lay.width``,
``engine.bounds``, ``engine.A``, ``engine.config.chunk``;
``check(init_override=)`` taking a ``PaxosState``, and
``EngineResult.violation.state`` being one; ``EngineResult.coverage`` keyed by
``Phase1a`` / ``Phase1b`` / ``Phase2a`` / ``Phase2b``; the named scope
``quorum`` (nested in ``expand``) on the ops of ``Phase2a``'s quorum guard;
``ops/kernels.step_signature`` (printed only).
"""

from __future__ import annotations

import random

from benchmark.harness import work
from benchmark.reference import paxos as px

SPEC = "paxos"
SPECIFICATIONS = (None, "Spec")


class NoDeviceEngine(NotImplementedError):
    """This program does not run the family's spec on the ``ddd`` engine."""


# ------------------------------------------------------- the program's side

def check_config(cfg: dict):
    """The program's ``CheckConfig`` for this configuration, its
    ``cfg_text`` held to the fields beside it; refused by name where this
    program has no ``paxos`` model or does not run it on the ``ddd``
    engine."""
    from raft_tla_tpu.config import Bounds, CheckConfig
    from raft_tla_tpu.frontend.registry import resolve_model
    from raft_tla_tpu.utils import cfgparse
    try:
        engines = resolve_model(SPEC).engines
    except ValueError as e:
        raise NoDeviceEngine(
            f"check_config({cfg.get('name')}): this program has no spec "
            f"{SPEC!r} ({str(e).split(';')[0]})") from None
    if "ddd" not in engines:
        raise NoDeviceEngine(
            f"check_config({cfg.get('name')}): this program runs spec "
            f"{SPEC!r} on {', '.join(engines)} only")
    m = bounds(cfg)
    stated_init(cfg)
    tlc = cfgparse.parse_cfg(cfg["cfg_text"])
    rows = cfgparse.set_of_subsets(tlc, "Quorum", "Acceptor")
    said = (len(tlc.constants.get("Acceptor", ())),
            len(tlc.constants.get("Value", ())),
            sorted(rows), sorted(tlc.invariants), sorted(tlc.symmetry))
    want = (m.n_acceptors, m.n_values, sorted(_rows(m)),
            sorted(cfg["invariants"]), sorted(cfg["symmetry"]))
    if said != want or tlc.specification not in SPECIFICATIONS:
        raise ValueError(
            f"config {cfg['name']}: cfg_text says {said} under "
            f"SPECIFICATION {tlc.specification!r}, the fields say {want} "
            f"under one of {SPECIFICATIONS}")
    return CheckConfig(
        bounds=Bounds(n_servers=m.n_acceptors, n_values=m.n_values,
                      max_term=m.max_ballot,
                      constants=(("Quorum", tuple(rows)),)),
        spec=SPEC, invariants=tuple(cfg["invariants"]), symmetry=(),
        chunk=cfg["chunk"])


def _rows(m) -> list:
    """The model's quorums as 0/1 rows over the acceptors."""
    return [tuple(int(a in q) for a in range(m.n_acceptors))
            for q in m.quorums]


def to_program(s):
    """A reference ``State`` as the program's ``PaxosState`` (the same
    fields and message tuples, two unrelated classes): what
    ``check(init_override=)`` takes."""
    from raft_tla_tpu.frontend.paxos import PaxosState
    return PaxosState(maxBal=tuple(s.maxBal), maxVBal=tuple(s.maxVBal),
                      maxVal=tuple(s.maxVal), msgs=frozenset(s.msgs))


def from_program(s):
    """The crossing back: the state a violation names, as a reference
    state."""
    return px.State(tuple(s.maxBal), tuple(s.maxVBal), tuple(s.maxVal),
                    frozenset(tuple(m) for m in s.msgs))


def pack_rows(eng, parents: list):
    """``parents`` (reference states) as the packed rows and constraint
    flags the compiled segment takes (the spec has no state constraint:
    every parent is expanded)."""
    import numpy as np
    from raft_tla_tpu.frontend import paxos as ppx
    rows = np.zeros((len(parents), eng.schema.P), np.int32)
    for k, s in enumerate(parents):
        rows[k] = eng.schema.pack(ppx.to_vec(to_program(s), eng.bounds), np)
    return rows, np.ones((len(parents),), bool)


def decode_rows(eng, orows) -> list:
    """The rows a segment streamed, as reference states."""
    import numpy as np
    from raft_tla_tpu.frontend import paxos as ppx
    return [from_program(ppx.from_vec(
        eng.schema.unpack(np.asarray(row), np), eng.bounds))
        for row in orows]


def gates(eng, cfg: dict) -> dict:
    """The construction-time gates of the step this engine was built with,
    as the program resolves them, and the shape of its row (printed, never
    compared)."""
    from raft_tla_tpu.ops import kernels
    sig = kernels.step_signature(eng.bounds, SPEC, tuple(cfg["invariants"]),
                                 (), None)
    return dict(sig[5:], lanes=eng.A, row_words=eng.lay.width,
                packed_words=eng.schema.P)


def scan_words(eng) -> int:
    """32-bit words the key pass touches in one chunk step: no sort is
    symmetric, so every candidate lane is fingerprinted once, as it is
    (lanes x row words, as TwoPhase's)."""
    return work.scan_words(eng.config.chunk, eng.A, eng.bounds.n_servers,
                           eng.lay.width, False)


# ------------------------------------------------------ the plain reference

def bounds(cfg: dict):
    """The model's constants (``reference.paxos.Model``): acceptor and value
    counts, the maximum ballot, and ``Quorum`` as the configuration writes
    it."""
    b = cfg["bounds"]
    n = b["n_acceptors"]
    if cfg.get("symmetry"):
        raise ValueError(
            f"configuration {cfg.get('name')}: this family reduces over no "
            f"SYMMETRY (a state names itself); the file says "
            f"{cfg['symmetry']}")
    names = {f"a{k + 1}": k for k in range(n)}
    quorums = []
    for q in cfg["quorums"]:
        bad = [x for x in q if x not in names]
        if bad or not q:
            raise ValueError(
                f"configuration {cfg.get('name')}: quorum {q} is empty or "
                f"names {bad[:1]}, no acceptor of a1..a{n}")
        quorums.append(frozenset(names[x] for x in q))
    return px.model(n, b["n_values"], b["max_ballot"], quorums)


def stated_init(cfg: dict):
    """``None``: the spec's own ``Init`` (a configuration of this family may
    not state another)."""
    if "init" in cfg:
        raise ValueError(f"configuration {cfg.get('name')} states an Init; "
                         "the Paxos family starts from the spec's Init")
    return None


def bfs_levels(cfg: dict, min_level_states: int):
    """``(cumulative counts, the states of the first level of
    ``min_level_states`` states, violations)`` from ``Init``."""
    stated_init(cfg)
    cum, level, viol, _trans = px.bfs_levels(
        bounds(cfg), tuple(cfg["invariants"]), min_level_states)
    return cum, level, viol


def successor_orbits(parents: list, cfg: dict):
    """``(successor states, transitions, {state: True})``: the spec has no
    state constraint, so every parent is expanded and every successor may
    be."""
    m = bounds(cfg)
    reps, n_trans = {}, 0
    for s in parents:
        for _a, t in px.successors(s, m):
            n_trans += 1
            reps[t] = True
    return set(reps), n_trans, reps


def orbit_key(cfg: dict):
    """No SYMMETRY (``bounds`` refuses a configuration that names one): a
    state names itself."""
    return _itself


def _itself(s):
    return s


def holds(s, cfg: dict) -> list:
    """Names of the configuration's invariants that ``s`` breaks."""
    m = bounds(cfg)
    return [nm for nm in cfg["invariants"] if not px.INVARIANTS[nm](s, m)]


def planted_fault(cfg: dict, level: list, seed: int) -> dict:
    """A state of the reference's level, drawn with the seed, its "2a" and
    "2b" messages replaced so that value v is chosen at ballot b (every
    member of one quorum has voted for it) and a "2a" message for another
    value w at a higher ballot c has the votes of all of a second quorum but
    one acceptor, whose ``maxBal`` still lets it vote: the parent holds
    ``Consistency`` (one value chosen), and that acceptor's ``Phase2b``
    breaks it (two).  No reachable state looks so: Paxos is safe.  Returns
    the parent and ``{violating successor: names of the invariants it
    breaks}``, judged by the plain reference."""
    m = bounds(cfg)
    if m.n_values < 2 or m.max_ballot < 1:
        raise ValueError("the planted fault needs two values and two "
                         "ballots")
    rng = random.Random(f"plant/{seed}")
    for s in rng.sample(level, len(level)):
        b, c = sorted(rng.sample(range(m.max_ballot + 1), 2))
        v, w = rng.sample(range(m.n_values), 2)
        q1, q2 = rng.choice(m.quorums), rng.choice(m.quorums)
        last = rng.choice(sorted(q2))
        votes = {a: [] for a in range(m.n_acceptors)}
        for a in q1:
            votes[a].append((b, v))
        for a in q2 - {last}:
            votes[a].append((c, w))
        msgs = {x for x in s.msgs if x[0] in ("1a", "1b")}
        msgs |= {("1a", b), ("1a", c), ("2a", b, v), ("2a", c, w)}
        msgs |= {("2b", a, bal, val) for a, vs in votes.items()
                 for bal, val in vs}
        top = [max(vs) if vs else (-1, None) for vs in votes.values()]
        # an acceptor's maxBal is at least its last vote's ballot; the
        # acceptor that has yet to vote stays at or below c
        max_bal = tuple(
            min(max(s.maxBal[a], top[a][0]), c) if a == last
            else max(s.maxBal[a], top[a][0])
            for a in range(m.n_acceptors))
        parent = px.State(max_bal, tuple(t[0] for t in top),
                          tuple(t[1] for t in top), frozenset(msgs))
        if holds(parent, cfg):
            continue
        violators = {}
        for _a, nxt in px.successors(parent, m):
            broken = holds(nxt, cfg)
            if broken:
                violators[nxt] = broken
        if violators:
            return {"parent": parent, "violators": violators,
                    "key": _itself}
    raise ValueError("no state of the reference level takes the planted "
                     "fault; the configuration lists no invariant it breaks")
