"""Lamport's two-phase commit as plain Python: ``TwoPhase.tla`` of
tlaplus/Examples, ``specifications/transaction_commit/`` (``TPNext``,
``TPTypeOK``, ``TCConsistent``), transcribed by hand from the TLA+ text.

A plain reference of the benchmark: it imports nothing of the program and
was not taken from the program's own oracle (``frontend/twophase.py``).  The
resource managers are ``RM = {r1 .. rn}``, numbered 0..n-1 here.

    VARIABLES rmState, tmState, tmPrepared, msgs

``msgs`` is the set of all messages ever sent: it only grows, a message is
received by being read, never removed.  The sets are kept as bit masks: bit
k of ``tmPrepared`` is ``rk+1 \\in tmPrepared``; bit k of ``msgs`` is the
message ``[type |-> "Prepared", rm |-> rk+1]``, bit n ``[type |-> "Commit"]``,
bit n + 1 ``[type |-> "Abort"]``.
"""

from __future__ import annotations

import sys
import time
from typing import NamedTuple

RM_STATES = ("working", "prepared", "committed", "aborted")
WORKING, PREPARED, COMMITTED, ABORTED = range(4)
TM_STATES = ("init", "committed", "aborted")
TM_INIT, TM_COMMITTED, TM_ABORTED = range(3)

ACTIONS = ("TMCommit", "TMAbort", "TMRcvPrepared", "RMPrepare",
           "RMChooseToAbort", "RMRcvCommitMsg", "RMRcvAbortMsg")


class State(NamedTuple):
    rmState: tuple      # one of RM_STATES' indices a resource manager
    tmState: int        # index into TM_STATES
    tmPrepared: int     # mask over RM
    msgs: int           # mask: Prepared(rm) for each rm, then Commit, Abort


def init_state(n: int) -> State:
    """``TPInit``: every RM working, the TM in init, nothing prepared, no
    message sent."""
    return State((WORKING,) * n, TM_INIT, 0, 0)


def _rm(s: State, rm: int, to: int) -> State:
    """``rmState' = [rmState EXCEPT ![rm] = to]``, the rest unchanged."""
    return s._replace(rmState=s.rmState[:rm] + (to,) + s.rmState[rm + 1:])


def successors(s: State) -> list:
    """Every enabled disjunct of ``TPNext`` in ``s``, in the order of
    ``ACTIONS`` (the TM's two, then the five of each RM in turn):
    ``[((action, rm or None), successor)]``.  A step that changes nothing
    (the TM receives a ``Prepared`` it has, an RM receives a decision it has
    acted on) is enabled in the spec and is listed: TLC counts it as a state
    generated."""
    n = len(s.rmState)
    commit, abort = 1 << n, 1 << (n + 1)
    out = []
    # TMCommit: tmState = "init" /\ tmPrepared = RM
    if s.tmState == TM_INIT and s.tmPrepared == (1 << n) - 1:
        out.append((("TMCommit", None),
                    s._replace(tmState=TM_COMMITTED, msgs=s.msgs | commit)))
    # TMAbort: tmState = "init"
    if s.tmState == TM_INIT:
        out.append((("TMAbort", None),
                    s._replace(tmState=TM_ABORTED, msgs=s.msgs | abort)))
    for rm in range(n):
        bit = 1 << rm
        # TMRcvPrepared(rm): tmState = "init" /\ Prepared(rm) \in msgs
        if s.tmState == TM_INIT and s.msgs & bit:
            out.append((("TMRcvPrepared", rm),
                        s._replace(tmPrepared=s.tmPrepared | bit)))
        if s.rmState[rm] == WORKING:
            # RMPrepare(rm): sends Prepared(rm)
            out.append((("RMPrepare", rm),
                        _rm(s, rm, PREPARED)._replace(msgs=s.msgs | bit)))
            # RMChooseToAbort(rm): sends nothing
            out.append((("RMChooseToAbort", rm), _rm(s, rm, ABORTED)))
        # RMRcvCommitMsg(rm): [type |-> "Commit"] \in msgs, whatever rmState[rm]
        if s.msgs & commit:
            out.append((("RMRcvCommitMsg", rm), _rm(s, rm, COMMITTED)))
        # RMRcvAbortMsg(rm): [type |-> "Abort"] \in msgs
        if s.msgs & abort:
            out.append((("RMRcvAbortMsg", rm), _rm(s, rm, ABORTED)))
    return out


def tp_type_ok(s: State) -> bool:
    """``TPTypeOK``."""
    n = len(s.rmState)
    return (all(0 <= r < len(RM_STATES) for r in s.rmState)
            and 0 <= s.tmState < len(TM_STATES)
            and 0 <= s.tmPrepared < 1 << n
            and 0 <= s.msgs < 1 << (n + 2))


def tc_consistent(s: State) -> bool:
    """``TCConsistent``: no RM aborted while another committed."""
    return not (ABORTED in s.rmState and COMMITTED in s.rmState)


INVARIANTS = {"TPTypeOK": tp_type_ok, "TCConsistent": tc_consistent}


def pack(s: State) -> int:
    """``s`` as one integer, one to one (what a long search keeps in
    ``seen`` in place of the state)."""
    n = len(s.rmState)
    x = (s.msgs << n | s.tmPrepared) << 2 | s.tmState
    for r in s.rmState:
        x = x << 2 | r
    return x


def bfs_levels(n: int, inv_names: tuple = ("TPTypeOK", "TCConsistent"),
               min_level_states: int | None = None):
    """Level-synchronous BFS from ``TPInit`` over ``n`` resource managers,
    to the first level of ``min_level_states`` states, or to the level that
    admits nothing where that is ``None``.

    Returns ``(cumulative distinct states per level, the last level's
    states, invariant violations seen, transitions taken)``; a transition
    is an enabled step out of an expanded state, one that changes nothing
    included.  The last level is not expanded."""
    invs = [INVARIANTS[nm] for nm in inv_names]
    init = init_state(n)
    seen = {pack(init)}
    violations = sum(not f(init) for f in invs)
    cumulative, frontier, transitions = [1], [init], 0
    while min_level_states is None or len(frontier) < min_level_states:
        nxt = []
        for s in frontier:
            for _a, t in successors(s):
                transitions += 1
                k = pack(t)
                if k in seen:
                    continue
                seen.add(k)
                violations += sum(not f(t) for f in invs)
                nxt.append(t)
        if not nxt:
            break
        cumulative.append(cumulative[-1] + len(nxt))
        frontier = nxt
    return cumulative, frontier, violations, transitions


def main(argv=None) -> int:
    """``python3 benchmark/reference/twophase.py N [N ...]``: the whole
    space for each N, off the clock (what a configuration's pins would be
    taken from)."""
    for n in (int(a) for a in (sys.argv[1:] if argv is None else argv)):
        t0 = time.monotonic()
        cum, _last, viol, trans = bfs_levels(n)
        levels = [b - a for a, b in zip([0] + cum, cum)]
        print(f"twophase n={n}: {cum[-1]} states, {len(cum)} levels "
              f"(diameter {len(cum) - 1}), widest level {max(levels)}, "
              f"{trans} transitions, {viol} violations, "
              f"{time.monotonic() - t0:.1f}s", flush=True)
        print(f"  levels {levels}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
