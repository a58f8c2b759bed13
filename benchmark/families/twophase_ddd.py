"""The TwoPhase family on the device engine: Lamport's two-phase commit
(``TwoPhase.tla`` / ``TwoPhase.cfg`` of tlaplus/Examples
``specifications/transaction_commit/``) run by the ``ddd`` engine from the
frontend IR (``--spec twophase --engine ddd``).

The reference half is ``families/twophase.py``'s, taken by import and not
repeated: the plain reference ``benchmark/reference/twophase.py``, its BFS,
successor states, invariants and the planted fault (a prepared RM beside an
aborted one under a committed TM: ``RMRcvCommitMsg`` breaks ``TCConsistent``).
This file brings the program half that file refuses by name.

What a configuration of this family states: ``"family": "twophase_ddd"``,
``"bounds": {"n_rms": n}``, ``"invariants"`` (of ``TPTypeOK``,
``TCConsistent``), ``"symmetry": []``, no ``"init"``, and ``cfg_text``: the
source's cfg with ``RM`` widened, held here to the fields beside it.

The program symbols used here are the benchmark's frozen interface for this
family (README, "What the benchmark holds the program to"):
``config.Bounds`` / ``CheckConfig`` (``spec="twophase"``, ``n_servers`` = the
RM count, ``n_values=1``); ``utils/cfgparse.parse_cfg`` (``.constants["RM"]``,
``.invariants``, ``.specification``, ``.symmetry``);
``frontend/registry.resolve_model("twophase").engines`` (holds ``"ddd"`` where
the device engine runs the spec; a program without it is refused by name, so a
parent of the PR that brought it fails at once); the row codec
``frontend/twophase.TPState`` / ``to_vec`` / ``from_vec``,
``engine.schema.pack`` / ``.unpack``, ``engine.lay.width``,
``engine.bounds``, ``engine.A``, ``engine.config.chunk``; ``check(init_override=)`` taking a ``TPState``, and
``EngineResult.violation.state`` being one; ``ops/kernels.step_signature``
(printed only).
"""

from __future__ import annotations

from benchmark.families.twophase import (  # noqa: F401  (the reference half)
    NoDeviceEngine, bfs_levels, bounds, holds, orbit_key, planted_fault,
    stated_init, successor_orbits)
from benchmark.harness import work

SPEC = "twophase"
# the names TwoPhase.cfg gives its behaviour spec (absent: INIT / NEXT)
SPECIFICATIONS = (None, "TPSpec")


# ------------------------------------------------------- the program's side

def check_config(cfg: dict):
    """The program's ``CheckConfig`` for this configuration, its
    ``cfg_text`` held to the fields beside it; refused by name where this
    program's ``twophase`` model does not run on the ``ddd`` engine."""
    from raft_tla_tpu.config import Bounds, CheckConfig
    from raft_tla_tpu.frontend.registry import resolve_model
    from raft_tla_tpu.utils import cfgparse
    engines = resolve_model(SPEC).engines
    if "ddd" not in engines:
        raise NoDeviceEngine(
            f"check_config({cfg.get('name')}): this program runs spec "
            f"{SPEC!r} on {', '.join(engines)} only")
    n = bounds(cfg)
    stated_init(cfg)
    tlc = cfgparse.parse_cfg(cfg["cfg_text"])
    said = (len(tlc.constants.get("RM", ())), sorted(tlc.invariants),
            sorted(tlc.symmetry))
    want = (n, sorted(cfg["invariants"]), sorted(cfg["symmetry"]))
    if said != want or tlc.specification not in SPECIFICATIONS:
        raise ValueError(
            f"config {cfg['name']}: cfg_text says {said} under "
            f"SPECIFICATION {tlc.specification!r}, the fields say {want} "
            f"under one of {SPECIFICATIONS}")
    return CheckConfig(bounds=Bounds(n_servers=n, n_values=1), spec=SPEC,
                       invariants=tuple(cfg["invariants"]), symmetry=(),
                       chunk=cfg["chunk"])


def to_program(s):
    """A reference ``State`` (sets as bit masks) as the program's
    ``TPState`` (one flag a resource manager and message): what
    ``check(init_override=)`` takes."""
    from raft_tla_tpu.frontend.twophase import TPState
    n = len(s.rmState)
    return TPState(
        rmState=tuple(s.rmState), tmState=s.tmState,
        tmPrepared=tuple(s.tmPrepared >> k & 1 for k in range(n)),
        msgPrepared=tuple(s.msgs >> k & 1 for k in range(n)),
        msgCommit=s.msgs >> n & 1, msgAbort=s.msgs >> (n + 1) & 1)


def from_program(s):
    """The crossing back: the state a violation names, as a reference
    state."""
    from benchmark.reference.twophase import State
    n = len(s.rmState)
    mask = sum(int(b) << k for k, b in enumerate(s.tmPrepared))
    msgs = sum(int(b) << k for k, b in enumerate(s.msgPrepared)) \
        | int(s.msgCommit) << n | int(s.msgAbort) << (n + 1)
    return State(tuple(int(r) for r in s.rmState), int(s.tmState), mask,
                 msgs)


def pack_rows(eng, parents: list):
    """``parents`` (reference states) as the packed rows and constraint
    flags the compiled segment takes (the spec has no state constraint:
    every parent is expanded)."""
    import numpy as np
    from raft_tla_tpu.frontend import twophase as ptp
    rows = np.zeros((len(parents), eng.schema.P), np.int32)
    for k, s in enumerate(parents):
        rows[k] = eng.schema.pack(ptp.to_vec(to_program(s), eng.bounds), np)
    return rows, np.ones((len(parents),), bool)


def decode_rows(eng, orows) -> list:
    """The rows a segment streamed, as reference states."""
    import numpy as np
    from raft_tla_tpu.frontend import twophase as ptp
    return [from_program(ptp.from_vec(
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
    symmetric, so every candidate lane is fingerprinted once, as it is."""
    return work.scan_words(eng.config.chunk, eng.A, eng.bounds.n_servers,
                           eng.lay.width, False)
