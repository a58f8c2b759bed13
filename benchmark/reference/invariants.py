# COPY of the parity-mode Python predicates of raft_tla_tpu/models/invariants.py at commit
# 51d3f6c (PR 23); the jnp twins, the history predicates and the expression compiler are left out.
"""Plain-Python invariants of the reference: state -> bool (True = holds)."""

from __future__ import annotations

from benchmark.reference.bounds import Bounds
from benchmark.reference import spec as S

def _py_election_safety(s, bounds: Bounds) -> bool:
    n = bounds.n_servers
    return not any(
        s.role[i] == S.LEADER and s.role[j] == S.LEADER
        and s.term[i] == s.term[j]
        for i in range(n) for j in range(i + 1, n))


def _py_naive_no_two_leaders(s, bounds: Bounds) -> bool:
    return sum(1 for r in s.role if r == S.LEADER) <= 1


def _py_log_matching(s, bounds: Bounds) -> bool:
    """If two logs share (index, term), they agree on the whole prefix."""
    n = bounds.n_servers
    for i in range(n):
        for j in range(i + 1, n):
            li, lj = s.log[i], s.log[j]
            for k in range(min(len(li), len(lj))):
                if li[k][0] == lj[k][0] and li[:k + 1] != lj[:k + 1]:
                    return False
    return True


def _py_committed_within_log(s, bounds: Bounds) -> bool:
    """commitIndex never points past the log (sanity, provable from the spec)."""
    return all(s.commitIndex[i] <= len(s.log[i])
               for i in range(bounds.n_servers))


def _py_leader_completeness(s, bounds: Bounds) -> bool:
    """Leader Completeness (Raft Fig. 3): an entry committed in term T is
    present in the log of every leader of a term later than T.

    State-level reading without history variables: the *commit term* of an
    entry counted by ``commitIndex[j]`` is not recorded, but it is always
    <= ``currentTerm[j]`` — j's commitIndex moves only through its own
    AdvanceCommitIndex (commit term = currentTerm[j], ``raft.tla:268-270``)
    or an accepted AppendEntries with ``mterm = currentTerm[j]``
    (``raft.tla:356-365``), and terms only grow.  So the sound check is:
    for every j, k <= commitIndex[j], and every leader i with
    ``currentTerm[i] > currentTerm[j]``, the identical entry sits at k in
    log[i].  Comparing against the *entry* term instead would wrongly flag
    stale leaders of terms between the entry term and the commit term
    (reachable: a deposed-but-unaware leader elected before the commit).
    """
    n = bounds.n_servers
    for j in range(n):
        for k in range(s.commitIndex[j]):
            ent = s.log[j][k]
            for i in range(n):
                if (s.role[i] == S.LEADER and s.term[i] > s.term[j]
                        and (len(s.log[i]) <= k or s.log[i][k] != ent)):
                    return False
    return True


REGISTRY = {
    "NoTwoLeaders": _py_election_safety,
    "ElectionSafety": _py_election_safety,
    "NaiveNoTwoLeaders": _py_naive_no_two_leaders,
    "LogMatching": _py_log_matching,
    "CommittedWithinLog": _py_committed_within_log,
    "LeaderCompleteness": _py_leader_completeness,
}
