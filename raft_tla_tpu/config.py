"""Bounds & check configuration — the L0/L5 layer of the checker.

The reference config (``raft.cfg:1-15``) binds ``Server = {s1,s2,s3}`` and
``Value = {v1,v2}`` but contains **no CONSTRAINT**, while the raw spec has an
infinite reachable state space: ``Timeout`` increments ``currentTerm`` without
bound (``raft.tla:180``), ``ClientRequest`` grows logs without bound
(``raft.tla:250``), and ``DuplicateMessage`` grows message multiplicities
without bound (``raft.tla:443-445``).  Exhaustive checking is therefore only
meaningful relative to a state constraint.  :class:`Bounds` is that constraint,
made first-class.

Capacity scheme (why ``*_cap = bound + 1``)
-------------------------------------------
TLC's CONSTRAINT semantics: a state that *violates* the constraint is still
generated, counted, and invariant-checked, but its successors are never
explored.  The tensor encoding must therefore be able to *represent* states one
step past each bound, because every expanded state satisfies the constraint and
each action moves a bound by at most one:

- ``Timeout`` bumps a term by exactly 1 (``raft.tla:180``); messages carry
  terms of senders that satisfied the constraint when they sent, so no value
  ever needs more than ``max_term + 1``.
- ``ClientRequest``/append grow a log by exactly 1 entry (``raft.tla:250``,
  ``raft.tla:383-388``).
- One action adds at most one *distinct* message to the bag (``Send``
  ``raft.tla:122``; ``Reply`` ``raft.tla:129-130`` removes one and adds one).
- ``DuplicateMessage`` bumps one multiplicity by 1 (``raft.tla:443-445``).

Any state that would exceed a *capacity* (not just a bound) indicates a bug in
this reasoning and must fail loudly — never clamp (SURVEY §4.5).
"""

from __future__ import annotations

import dataclasses

# Bit widths of packed message fields (ops/msgbits.py).  Caps must fit.
_MAX_TERM_CAP = 63      # 6-bit term fields
_MAX_INDEX_CAP = 62     # 6-bit index fields; nextIndex can reach log_cap + 1
_MAX_SERVERS = 14       # 4-bit src/dst fields; votedFor uses n+1 symbols
_MAX_VALUES = 15        # 4-bit value field; values are 1..V (0 = none)
# Multiplicities live in full int32 slots (never bit-packed); this cap only
# keeps counts sane for host-side displays and catches runaway configs.
_MAX_DUP_CAP = 1 << 20
# Faithful mode: log ranks+1 must fit the 14-bit mlog field and the allLogs
# bitmask must stay small (<= 32 int32 words).
_MAX_LOG_UNIVERSE = 1024


@dataclasses.dataclass(frozen=True)
class Bounds:
    """The model universe (``raft.cfg:5-15``) plus the state constraint.

    ``n_servers``/``n_values`` bind the CONSTANTS ``Server``/``Value``
    (``raft.tla:11,14``); the ``max_*`` fields are the StateConstraint the
    reference's cfg is missing (SURVEY §0 defect 2).
    """

    n_servers: int = 3
    n_values: int = 2
    max_term: int = 3      # constraint: \A i : currentTerm[i] <= max_term
    max_log: int = 2       # constraint: \A i : Len(log[i]) <= max_log
    max_msgs: int = 4      # constraint: Cardinality(DOMAIN messages) <= max_msgs
    max_dup: int = 1       # constraint: \A m : messages[m] <= max_dup
    # Faithful mode (SURVEY §7.0.3b): carry the proof-only history variables
    # (elections raft.tla:39, allLogs raft.tla:44, voterLog raft.tla:77, and
    # the mlog message fields raft.tla:220-222/297-299) as real fingerprinted
    # state, exactly as stock TLC does on the unmodified spec.  Off (parity
    # mode) they are stripped on both sides of every TLC comparison.
    history: bool = False
    # Capacity of the `elections` slot encoding.  The spec puts no bound on
    # the set (it is derived-finite under the constraint); exceeding the
    # capacity is a loud engine failure, never a clamp (SURVEY §4.5).
    max_elections: int = 6
    # Further CONSTANTS of a frontend spec's universe that are tables, not
    # set sizes: ``(name, rows as nested tuples)`` pairs, held to the spec's
    # ``schema.Const`` declarations when a step is built (Paxos' ``Quorum``,
    # one 0/1 row a quorum).  Raft binds none.  Like every field here it
    # joins the checkpoint digest when set.
    constants: tuple = ()

    def __post_init__(self) -> None:
        if not (1 <= self.n_servers <= _MAX_SERVERS):
            raise ValueError(f"n_servers must be in [1,{_MAX_SERVERS}], got {self.n_servers}")
        if not (1 <= self.n_values <= _MAX_VALUES):
            raise ValueError(f"n_values must be in [1,{_MAX_VALUES}], got {self.n_values}")
        if self.max_term < 1 or self.term_cap > _MAX_TERM_CAP:
            raise ValueError(f"max_term out of range: {self.max_term}")
        if self.max_log < 0 or self.log_cap + 1 > _MAX_INDEX_CAP:
            raise ValueError(f"max_log out of range: {self.max_log}")
        if self.max_msgs < 1:
            raise ValueError(f"max_msgs must be >= 1, got {self.max_msgs}")
        if self.max_dup < 1 or self.dup_cap > _MAX_DUP_CAP:
            raise ValueError(f"max_dup out of range: {self.max_dup}")
        if self.history:
            if not (1 <= self.max_elections <= 64):
                raise ValueError(
                    f"max_elections must be in [1,64], got {self.max_elections}")
            # Log-universe size gates the history encodings: ranks+1 must fit
            # the 14-bit mlog message field (ops/msgbits.py) and the allLogs
            # bitmask must stay a few dozen words (ops/loguniv.py).
            from raft_tla_tpu.ops.loguniv import LogUniverse
            uni = LogUniverse.of(self)
            if uni.size > _MAX_LOG_UNIVERSE:
                raise ValueError(
                    f"faithful mode needs a log universe <= "
                    f"{_MAX_LOG_UNIVERSE} (got {uni.size}: term_cap="
                    f"{self.term_cap} x {self.n_values} values, lengths 0.."
                    f"{self.log_cap}); shrink max_term/max_log/n_values")

    # -- capacities (representable range = one step past each bound) --------
    @property
    def term_cap(self) -> int:
        return self.max_term + 1

    @property
    def log_cap(self) -> int:
        return self.max_log + 1

    @property
    def msg_cap(self) -> int:
        """Number of message slots in the tensor encoding."""
        return self.max_msgs + 1

    @property
    def dup_cap(self) -> int:
        return self.max_dup + 1


@dataclasses.dataclass(frozen=True)
class CheckConfig:
    """A full checking run: universe + bounds + spec subset + invariants.

    ``spec`` selects the ``Next`` disjunct subset (models/spec.py); the
    reference's full ``Next`` is ``raft.tla:454-465``.  ``invariants`` are
    names resolved against the invariant registry (``models/invariants.py``);
    the reference cfg's ``INVARIANT NoTwoLeaders`` (``raft.cfg:3``) is
    *undefined in raft.tla* and is resolved to Election Safety by default
    (SURVEY §0 defect 1).
    """

    bounds: Bounds = dataclasses.field(default_factory=Bounds)
    spec: str = "full"                     # full | election | replication
    invariants: tuple = ("NoTwoLeaders",)  # registry names
    symmetry: tuple = ()                   # TLC SYMMETRY: Raft's axes ("Server",
    #   "Value") or the sorts a frontend schema declares ("Acceptor", ...)
    chunk: int = 1024                      # frontier states expanded per jit call
    check_deadlock: bool = False           # TLC -deadlock analog (off: Restart is always enabled anyway)
    view: str | None = None                # TLC VIEW analog: a registered
    #   exact view (models/views.py) folded into every dedup key; None =
    #   identity.  Joins the checkpoint digest when set.

    def __post_init__(self) -> None:
        if not self.bounds.history:
            from raft_tla_tpu.models.invariants import HISTORY_REGISTRY
            hist = [nm for nm in self.invariants if nm in HISTORY_REGISTRY]
            if hist:
                raise ValueError(
                    f"invariant(s) {hist} read the history variables; they "
                    "require faithful mode (Bounds.history / --faithful)")
        if self.view is not None:
            from raft_tla_tpu.models.views import REGISTRY
            if self.view not in REGISTRY:
                raise ValueError(
                    f"unknown view {self.view!r} "
                    f"(known: {sorted(REGISTRY)})")
