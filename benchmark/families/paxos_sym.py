"""The Paxos family under its model's SYMMETRY: ``families/paxos_ddd.py``'s
spec, source and program half (Lamport's single-decree Paxos on the ``ddd``
engine from the frontend IR) with the configuration's ``"symmetry"`` handed to
the program, and a reference half that counts and compares **orbits** of
``Permutations(Acceptor) \\cup Permutations(Value)``.

The reference half is ``benchmark/reference/paxos_sym.py`` over
``benchmark/reference/paxos.py``: an orbit is named by sorting the acceptors'
columns under each value relabelling (``canonical``), a method that shares
nothing with the program's (the least fingerprint over the images of a state),
and the two have to agree one to one.  A streamed row is whichever member of
its orbit the program found first and is compared through ``orbit_key``; the
planted fault is ``paxos_ddd``'s, judged on orbits.

What a configuration of this family states: what ``paxos_ddd``'s does, with
``"family": "paxos_sym"``, ``"symmetry": ["Acceptor", "Value"]`` (both, in
this order: the reference names orbits of the whole group and of no subgroup)
and a ``cfg_text`` whose stanza says ``SYMMETRY Acceptor Value``; ``"quorums"``
has to be mapped onto itself by every permutation of the acceptors.

The program symbols used here, beyond ``paxos_ddd``'s (README, "What the
benchmark holds the program to"): ``frontend/registry.resolve_model("paxos")
.sorts`` (the symmetric sorts the program reduces that spec by, a tuple of
names; a program without the attribute, or whose sorts lack one the
configuration names, is refused by name, so a parent of the PR that brought
symmetry to frontend specs fails at once and never searches the unreduced
space); ``CheckConfig(symmetry=("Acceptor", "Value"))``; the stage scope
``orbit_scan`` on the ops of the key; ``group`` and ``images`` among the
``args`` of the ``ddd`` engine's ``segment`` spans.

``scan_shapes`` / ``scan_ops`` / ``scan_bytes`` count the work of the orbit
scan **from the configuration's declared shapes alone**, never from what the
program reports, so that a later implementation is read against the same
work (``symscan_roofline_pct``).
"""

from __future__ import annotations

import math

from benchmark.families import paxos_ddd as base
from benchmark.reference import paxos_sym as ps

SPEC = base.SPEC
SORTS = ("Acceptor", "Value")
NoDeviceEngine = base.NoDeviceEngine

# the crossing of a state and the row codec do not depend on the key
to_program = base.to_program
from_program = base.from_program
pack_rows = base.pack_rows
decode_rows = base.decode_rows
stated_init = base.stated_init


def _unreduced(cfg: dict) -> dict:
    """The configuration as ``paxos_ddd`` reads one (it refuses a
    SYMMETRY)."""
    return {**cfg, "symmetry": []}


# ------------------------------------------------------- the program's side

def check_config(cfg: dict):
    """The program's ``CheckConfig`` for this configuration: ``paxos_ddd``'s
    (the cfg text held to the fields beside it, the stanza included) with
    the symmetry handed over.  The program is asked first which sorts it
    reduces the spec by; one that names none, or not these, is refused by
    name before it is handed a space it would search unreduced."""
    import dataclasses
    from raft_tla_tpu.frontend.registry import resolve_model
    from raft_tla_tpu.utils import cfgparse
    bounds(cfg)                          # the family's own refusals first
    try:
        model = resolve_model(SPEC)
    except ValueError as e:
        raise NoDeviceEngine(
            f"check_config({cfg.get('name')}): this program has no spec "
            f"{SPEC!r} ({str(e).split(';')[0]})") from None
    sorts = tuple(getattr(model, "sorts", ()))
    missing = [s for s in cfg["symmetry"] if s not in sorts]
    if missing:
        raise NoDeviceEngine(
            f"check_config({cfg.get('name')}): this program reduces spec "
            f"{SPEC!r} by no symmetric sort {missing[0]!r} (it names "
            f"{', '.join(sorts) or 'none'}); the configuration's SYMMETRY "
            f"is {' '.join(cfg['symmetry'])}")
    said = sorted(cfgparse.parse_cfg(cfg["cfg_text"]).symmetry)
    if said != sorted(cfg["symmetry"]):
        raise ValueError(
            f"config {cfg['name']}: cfg_text says SYMMETRY {said}, the "
            f"fields say {sorted(cfg['symmetry'])}")
    plain = base.check_config(
        {**_unreduced(cfg),
         "cfg_text": _without_symmetry(cfg["cfg_text"])})
    return dataclasses.replace(plain, symmetry=tuple(cfg["symmetry"]))


def _without_symmetry(cfg_text: str) -> str:
    """``cfg_text`` less its SYMMETRY stanza (one line, as the family's
    configurations write it), for ``paxos_ddd``'s own reading of the
    rest."""
    return "".join(ln for ln in cfg_text.splitlines(keepends=True)
                   if not ln.strip().startswith("SYMMETRY"))


def group_order(cfg: dict) -> int:
    """|G| = |Acceptor|! * |Value|!, from the configuration alone."""
    b = cfg["bounds"]
    return math.factorial(b["n_acceptors"]) * math.factorial(b["n_values"])


def gates(eng, cfg: dict) -> dict:
    """As ``paxos_ddd.gates``, under the configuration's symmetry (printed,
    never compared)."""
    from raft_tla_tpu.ops import kernels
    sig = kernels.step_signature(eng.bounds, SPEC, tuple(cfg["invariants"]),
                                 tuple(cfg["symmetry"]), None)
    return dict(sig[5:], lanes=eng.A, row_words=eng.lay.width,
                packed_words=eng.schema.P, group=group_order(cfg))


def scan_words(eng) -> int:
    """32-bit words the key pass touches in one chunk step: every candidate
    lane is keyed once an image (lanes x row words x |G|)."""
    b = eng.bounds
    return eng.config.chunk * eng.A * eng.lay.width \
        * math.factorial(b.n_servers) * math.factorial(b.n_values)


# ---- the orbit scan's work, from the configuration's declared shapes alone

LIMB_ROWS = 8       # two 32-bit lanes of four base-256 limbs: rows an image
BLOCK_IMAGES = 8    # images one product takes (64 rows, the MXU's cheapest)


def scan_shapes(cfg: dict) -> dict:
    """``F`` features a lane (one a message flag, one a word a permutation
    only moves, one a value of a relabelled content), ``N`` lanes a chunk
    step (chunk x actions) and ``|G|``, from ``bounds`` and ``chunk``."""
    b = cfg["bounds"]
    n, v, nb = b["n_acceptors"], b["n_values"], b["max_ballot"] + 1
    flags = nb + n * nb * (1 + nb * v) + nb * v + n * nb * v
    moved = 2 * n                       # maxBal, maxVBal
    relabelled = n * v                  # maxVal: one a value
    actions = nb * (1 + n + v + n * v)
    return {"F": flags + moved + relabelled, "N": cfg["chunk"] * actions,
            "G": group_order(cfg), "actions": actions,
            "row_words": flags + 3 * n}


def scan_ops(cfg: dict) -> int:
    """``int8`` operations of the limb product, a chunk step: one multiply
    and one add a feature, a lane, a limb row and an image."""
    s = scan_shapes(cfg)
    return 2 * LIMB_ROWS * s["F"] * s["N"] * s["G"]


def scan_bytes(cfg: dict) -> int:
    """Bytes the scan must move, a chunk step: the ``int8`` feature matrix
    once a block of images, and the limb table once."""
    s = scan_shapes(cfg)
    blocks = -(-s["G"] // BLOCK_IMAGES)
    return s["F"] * s["N"] * blocks + s["G"] * LIMB_ROWS * s["F"]


# ------------------------------------------------------ the plain reference

def bounds(cfg: dict):
    """``paxos_ddd.bounds`` (the model's constants), for a configuration
    whose SYMMETRY is the whole group the reference names orbits of and
    whose ``Quorum`` every acceptor permutation maps onto itself."""
    if tuple(cfg.get("symmetry", ())) != SORTS:
        raise ValueError(
            f"configuration {cfg.get('name')}: this family reduces over "
            f"SYMMETRY {' '.join(SORTS)} and nothing less; the file says "
            f"{cfg.get('symmetry')}")
    m = base.bounds(_unreduced(cfg))
    if not ps.invariant_quorums(m):
        raise ValueError(
            f"configuration {cfg.get('name')}: Quorum is not mapped onto "
            "itself by every permutation of Acceptor; symmetry over it is "
            "unsound")
    return m


def bfs_levels(cfg: dict, min_level_states: int):
    """``(cumulative orbits a level, the orbits (canonical members) of the
    first level of ``min_level_states``, violations)`` from ``Init``."""
    stated_init(cfg)
    cum, level, viol, _trans = ps.bfs_orbit_levels(
        bounds(cfg), tuple(cfg["invariants"]), min_level_states)
    return cum, level, viol


def successor_orbits(parents: list, cfg: dict):
    """``(successor orbits, transitions, {orbit: True})``: every enabled
    step of every parent, its successor named by ``canonical``."""
    reps, n_trans = ps.successor_orbits(parents, bounds(cfg))
    return reps, n_trans, dict.fromkeys(reps, True)


def orbit_key(cfg: dict):
    """The function that names a state's orbit: ``canonical`` (sort-based,
    in plain Python; the program's key is never seen here)."""
    m = bounds(cfg)
    return lambda s: ps.canonical(s, m)


def holds(s, cfg: dict) -> list:
    """Names of the configuration's invariants that ``s`` breaks."""
    return base.holds(s, _unreduced(cfg))


def planted_fault(cfg: dict, level: list, seed: int) -> dict:
    """``paxos_ddd``'s planted fault, judged on orbits: the parent is a
    state, the violators are named by their orbits, and ``key`` names the
    orbit of the state the engine reports."""
    plant = base.planted_fault(_unreduced(cfg), level, seed)
    key = orbit_key(cfg)
    violators = {}
    for s, broken in plant["violators"].items():
        names = violators.setdefault(key(s), [])
        names += [nm for nm in broken if nm not in names]
    return {"parent": plant["parent"], "violators": violators, "key": key}

