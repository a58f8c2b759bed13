"""The TwoPhase family: Lamport's two-phase commit, ``TwoPhase.tla`` /
``TwoPhase.cfg`` of tlaplus/Examples ``specifications/transaction_commit/``
(``RM = {r1, r2, r3}`` in the source, scaled in RM count as TLC users scale
it; ``INVARIANTS TPTypeOK TCConsistent``, no SYMMETRY).

The reference half is whole (``benchmark/reference/twophase.py``): it is what
``correct.reference_sample`` and ``correct.planted_fault`` need of a family,
and the proof that the seam ``families/raft.py`` sits behind takes a second
spec.  The program half is not there yet: no device engine takes its step from
the frontend IR (``ddd_engine._build_segment`` builds Raft's), so every name
of it raises ``NoDeviceEngine``, and no configuration or cell names this
family.  The ``model_config`` PR that brings the engine adds a family file of
its own beside this one (an accepted file is not edited), which takes this
file's reference half by import and brings a program half in place of the
refusals.

What a configuration of this family states: ``"bounds": {"n_rms": n}``,
``"invariants"`` (of ``TPTypeOK``, ``TCConsistent``), ``"symmetry": []``, no
``"init"``.
"""

from __future__ import annotations

import random

from benchmark.reference import twophase as tp


class NoDeviceEngine(NotImplementedError):
    """The program half of this family was asked for."""

    def __init__(self, what: str):
        super().__init__(
            f"families/twophase.{what}: no device engine runs this family "
            "yet (ROADMAP queue 2 A.1)")


# ------------------------------------------------------- the program's side

def check_config(cfg: dict):
    raise NoDeviceEngine("check_config")


def to_program(s):
    raise NoDeviceEngine("to_program")


def from_program(s):
    raise NoDeviceEngine("from_program")


def pack_rows(eng, parents: list):
    raise NoDeviceEngine("pack_rows")


def decode_rows(eng, orows) -> list:
    raise NoDeviceEngine("decode_rows")


def gates(eng, cfg: dict) -> dict:
    raise NoDeviceEngine("gates")


def scan_words(eng) -> int:
    raise NoDeviceEngine("scan_words")


# ------------------------------------------------------ the plain reference

def bounds(cfg: dict) -> int:
    """The number of resource managers; every other set of the spec follows
    from it."""
    n = cfg["bounds"]["n_rms"]
    if not isinstance(n, int) or n < 1:
        raise ValueError(f"configuration {cfg.get('name')}: n_rms {n!r}")
    if cfg.get("symmetry"):
        raise ValueError(
            f"configuration {cfg.get('name')}: the source's TwoPhase.cfg has "
            f"no SYMMETRY and the reference reduces over none; the file says "
            f"{cfg['symmetry']}")
    return n


def stated_init(cfg: dict):
    """``None``: the spec's own ``TPInit`` (a configuration of this family
    may not state another)."""
    if "init" in cfg:
        raise ValueError(f"configuration {cfg.get('name')} states an Init; "
                         "the TwoPhase family starts from TPInit")
    return None


def bfs_levels(cfg: dict, min_level_states: int):
    """``(cumulative counts, the states of the first level of
    ``min_level_states`` states, violations)`` from ``TPInit``."""
    stated_init(cfg)
    cum, level, viol, _trans = tp.bfs_levels(
        bounds(cfg), tuple(cfg["invariants"]), min_level_states)
    return cum, level, viol


def successor_orbits(parents: list, cfg: dict):
    """``(successor states, transitions, {state: True})``: the spec has no
    state constraint, so every parent is expanded and every successor may
    be."""
    reps, n_trans = {}, 0
    for s in parents:
        for _a, t in tp.successors(s):
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
    return [nm for nm in cfg["invariants"] if not tp.INVARIANTS[nm](s)]


def planted_fault(cfg: dict, level: list, seed: int) -> dict:
    """A state of the reference's level, drawn with the seed and rewritten
    so that the TM has committed (``[type |-> "Commit"]`` is in ``msgs``),
    one RM is prepared and another has aborted: it holds ``TCConsistent``
    itself (nobody has committed), and the prepared RM's ``RMRcvCommitMsg``
    breaks it.  No reachable state looks so: the TM commits only once every
    RM has prepared.  Returns the parent and ``{violating successor: names
    of the invariants it breaks}``, judged by the plain reference."""
    n = bounds(cfg)
    if n < 2:
        raise ValueError("the planted fault needs two resource managers")
    rng = random.Random(f"plant/{seed}")
    for s in rng.sample(level, len(level)):
        i, j = rng.sample(range(n), 2)
        rm = tuple(tp.PREPARED if k == i else tp.ABORTED if k == j
                   else tp.WORKING if r == tp.COMMITTED else r
                   for k, r in enumerate(s.rmState))
        parent = s._replace(rmState=rm, tmState=tp.TM_COMMITTED,
                            msgs=s.msgs | 1 << i | 1 << n)
        if holds(parent, cfg):
            continue
        violators = {}
        for _a, nxt in tp.successors(parent):
            broken = holds(nxt, cfg)
            if broken:
                violators[nxt] = broken
        if violators:
            return {"parent": parent, "violators": violators,
                    "key": _itself}
    raise ValueError("no state of the reference level takes the planted "
                     "fault; the configuration lists no invariant it breaks")
