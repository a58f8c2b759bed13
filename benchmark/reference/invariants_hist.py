"""The proof's history invariants in plain Python, over the reference's own
state (``interp.PyState`` with ``Bounds(history=True)``): state -> bool, True
= holds.  Transcribed for the benchmark (PR 50) from ``raft.tla`` and from
Ongaro's dissertation (*Consensus: Bridging Theory and Practice*, 2014:
Figure 3.2 and the safety proof of Appendix B, which is stated over the
history variables ``raft.tla`` declares for it), not from the program's
``models/invariants.py``.  ``raft.tla`` itself defines none of the three
operators: the names are this repository's, each departure from the
statement transcribed is noted where it is made.
"""

from __future__ import annotations

from benchmark.reference import invariants
from benchmark.reference.bounds import Bounds


def election_safety_hist(s, bounds: Bounds) -> bool:
    """Election Safety (dissertation Figure 3.2: "at most one leader can be
    elected in a given term"; Appendix B, Lemma 2, over ``elections``):

        \\A e, f \\in elections : e.eterm = f.eterm => e.eleader = f.eleader

    ``elections`` (raft.tla:39) gains a record in ``BecomeLeader`` alone
    (raft.tla:237-242) and loses none, so the statement covers every leader
    ever elected, in office or not.  No departure."""
    return all(e[1] == f[1] for e in s.elections for f in s.elections
               if e[0] == f[0])


def leader_completeness_hist(s, bounds: Bounds) -> bool:
    """Leader Completeness (dissertation Figure 3.2: "if a log entry is
    committed in a given term, then that entry will be present in the logs of
    the leaders for all higher-numbered terms"; Appendix B, Theorem 1):

        \\A e \\in elections : \\A <<index, term>> \\in committed(t) :
            t < e.eterm => e.elog has that entry at that index

    Departure: the proof's ``committed(t)`` is defined over the history of
    the whole execution, and ``raft.tla`` keeps no commit term.  What a state
    does hold is ``commitIndex[j]``: the entries 1..commitIndex[j] of
    ``log[j]`` are committed, in a term no later than ``currentTerm[j]``
    (j's commitIndex moves through its own AdvanceCommitIndex at its current
    term, raft.tla:268-270, or through an accepted AppendEntries of its
    current term, raft.tla:356-365, and terms only grow).  So the check is:
    for every j and k <= commitIndex[j], every recorded election with
    ``eterm > currentTerm[j]`` has ``log[j][k]`` at k in its ``elog`` (the
    leader's log when it won, raft.tla:239).  The same bound on the commit
    term as ``invariants._py_leader_completeness``, which reads the leaders
    in office where this reads the elections on record."""
    for j in range(bounds.n_servers):
        for k in range(s.commitIndex[j]):
            for eterm, _leader, elog, _votes, _vlog in s.elections:
                if eterm > s.term[j] and (len(elog) <= k
                                          or elog[k] != s.log[j][k]):
                    return False
    return True


def all_logs_prefix_closed(s, bounds: Bounds) -> bool:
    """``allLogs`` is closed under dropping a log's last entry:

        \\A l \\in allLogs : l # <<>> => SubSeq(l, 1, Len(l) - 1) \\in allLogs

    Not a statement of the dissertation: a check of the history variable
    itself.  It follows from ``raft.tla``: a log grows by one entry a step
    (ClientRequest, raft.tla:250; the append of HandleAppendEntriesRequest,
    raft.tla:383-388), shrinks by one (raft.tla:375-382), and ``allLogs'
    = allLogs \\cup {log[i] : i \\in Server}`` is conjoined to every step
    with the unprimed logs (raft.tla:464-465), so a log enters the set one
    step after its server held it, by when the log it grew from is in."""
    seen = set(s.allLogs)
    return all(log[:-1] in seen for log in s.allLogs if log)


HISTORY = {
    "ElectionSafetyHist": election_safety_hist,
    "LeaderCompletenessHist": leader_completeness_hist,
    "AllLogsPrefixClosed": all_logs_prefix_closed,
}

# every invariant a configuration with history may list: the state
# invariants of ``invariants.py`` read no history variable and hold or fail
# on a state with history as on its parity projection
REGISTRY = {**invariants.REGISTRY, **HISTORY}
