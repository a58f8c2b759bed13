# COPY of raft_tla_tpu/frontend/raft_schema.py at commit 51d3f6c (PR 23): the benchmark's frozen plain reference.
# Only the import lines were rewritten; it imports nothing of raft_tla_tpu.
"""Raft as frontend data: encodings, action-instance table, state schema.

This is ``models/spec.py``'s content relocated behind the frontend seam
(spec.py re-exports everything, so no import site changes): the integer
encodings for the spec's model values, the static successor fan-out, and
— new here — the Raft *state schema* as a declared
:class:`~raft_tla_tpu.frontend.schema.Schema` instance.  The schema
twin of ``ops/state.STATE_FIELDS`` (field names, order, shapes, declared
value ranges = ``analysis/intervals.envelope``) is what lets the generic
frontend paths (predicate compilation, schema linting) treat Raft like
any other loaded spec.  This module stays a leaf: it imports only
``config`` and ``frontend/schema``, never the kernels.

Encodings
---------
Roles (``CONSTANTS Follower, Candidate, Leader``, ``raft.tla:17``):
0/1/2.  ``Nil`` (``raft.tla:20``) is 0 in ``votedFor``; servers are 1..n
there, and 0..n-1 everywhere else.  Message types (``raft.tla:23-24``)
are 1..4, with 0 meaning "empty slot".

Action families — the ``Next`` disjuncts (``raft.tla:454-463``)
---------------------------------------------------------------
==============  ===========================  ==================
family          TLA action                   instances
==============  ===========================  ==================
RESTART         Restart(i)        :167-175   n
TIMEOUT         Timeout(i)        :178-187   n
REQUESTVOTE     RequestVote(i,j)  :190-199   n*n   (j may = i)
BECOMELEADER    BecomeLeader(i)   :229-243   n
CLIENTREQUEST   ClientRequest(i,v):246-253   n*V
ADVANCECOMMIT   AdvanceCommitIndex(i):259-276  n
APPENDENTRIES   AppendEntries(i,j):204-226   n*(n-1)  (i /= j)
RECEIVE         Receive(m)        :421-436   msg_cap slots
DUPLICATE       DuplicateMessage(m):443-445  msg_cap slots
DROP            DropMessage(m)    :448-450   msg_cap slots
==============  ===========================  ==================

``Receive``/``Duplicate``/``Drop`` quantify over ``DOMAIN messages``
(``raft.tla:461-463``); in the tensor encoding that is "occupied message
slot", and because slots are kept canonically sorted, slot index k
denotes the same message on both the interpreter and kernel sides.

Sub-specs ("model families", BASELINE.md measurement matrix):
``full`` is the whole ``Next``; ``election`` keeps Timeout + RequestVote
+ Receive + BecomeLeader (BASELINE config #2); ``replication`` keeps
ClientRequest + AppendEntries + Receive + AdvanceCommitIndex from a
preset single-leader initial state (BASELINE config #3).
"""

from __future__ import annotations

import dataclasses

from benchmark.reference.bounds import Bounds
from benchmark.reference.schema import Field, Schema

# Roles (raft.tla:17)
FOLLOWER, CANDIDATE, LEADER = 0, 1, 2
ROLE_NAMES = ("Follower", "Candidate", "Leader")

# votedFor: 0 = Nil (raft.tla:20), 1..n = server id + 1
NIL = 0

# Message types (raft.tla:23-24); 0 = empty slot
M_NONE = 0
M_RVREQ = 1   # RequestVoteRequest
M_RVRESP = 2  # RequestVoteResponse
M_AEREQ = 3   # AppendEntriesRequest
M_AERESP = 4  # AppendEntriesResponse
MTYPE_NAMES = ("None", "RequestVoteRequest", "RequestVoteResponse",
               "AppendEntriesRequest", "AppendEntriesResponse")

# Action families, in enumeration order.
RESTART = "Restart"
TIMEOUT = "Timeout"
REQUESTVOTE = "RequestVote"
BECOMELEADER = "BecomeLeader"
CLIENTREQUEST = "ClientRequest"
ADVANCECOMMIT = "AdvanceCommitIndex"
APPENDENTRIES = "AppendEntries"
RECEIVE = "Receive"
DUPLICATE = "DuplicateMessage"
DROP = "DropMessage"

ALL_FAMILIES = (RESTART, TIMEOUT, REQUESTVOTE, BECOMELEADER, CLIENTREQUEST,
                ADVANCECOMMIT, APPENDENTRIES, RECEIVE, DUPLICATE, DROP)

SPECS = {
    # The full Next relation (raft.tla:454-463).
    "full": frozenset(ALL_FAMILIES),
    # Election-only sub-spec (BASELINE config #2).
    "election": frozenset({TIMEOUT, REQUESTVOTE, RECEIVE, BECOMELEADER}),
    # Log-replication sub-spec from a preset leader (BASELINE config #3).
    "replication": frozenset({CLIENTREQUEST, APPENDENTRIES, RECEIVE,
                              ADVANCECOMMIT}),
}

# The parity-mode state schema — ops/state.STATE_FIELDS as a frontend
# declaration: same field order, same resolved shapes, value ranges from
# the claimed inductive envelope (analysis/intervals.envelope; the
# packed msgHi/msgLo words are checked per-subfield there, so the whole-
# word ranges here are the packed spans).  tests assert layout/width
# agreement with ops/state.Layout so the twin cannot drift.
RAFT_SCHEMA = Schema("raft", (
    Field("role", ("n",), 0, 2),
    Field("term", ("n",), 1, "term_cap", init=1),
    Field("votedFor", ("n",), 0, "n_servers"),
    Field("commitIndex", ("n",), 0, "log_cap"),
    Field("logLen", ("n",), 0, "log_cap"),
    Field("logTerm", ("n", "L"), 0, "term_cap"),
    Field("logVal", ("n", "L"), 0, "n_values"),
    Field("vResp", ("n",), 0, lambda b: (1 << b.n_servers) - 1),
    Field("vGrant", ("n",), 0, lambda b: (1 << b.n_servers) - 1),
    Field("nextIndex", ("n", "n"), 1, lambda b: b.log_cap + 1, init=1),
    Field("matchIndex", ("n", "n"), 0, "log_cap"),
    Field("msgHi", ("S",), 0, lambda b: (1 << 29) - 1),
    Field("msgLo", ("S",), 0,
          lambda b: (1 << (31 if b.history else 17)) - 1),
    Field("msgCount", ("S",), 0, "dup_cap"),
))


@dataclasses.dataclass(frozen=True)
class ActionInstance:
    """One successor lane: a family plus its bound parameters.

    ``i``/``j`` are server ids, ``v`` a value id (1..V), ``slot`` a message
    slot index — mirroring the existential quantifiers of ``raft.tla:454-463``.
    """
    family: str
    i: int = -1
    j: int = -1
    v: int = -1
    slot: int = -1

    def label(self) -> str:
        if self.family == RESTART:
            return f"Restart(s{self.i + 1})"
        if self.family == TIMEOUT:
            return f"Timeout(s{self.i + 1})"
        if self.family == REQUESTVOTE:
            return f"RequestVote(s{self.i + 1}, s{self.j + 1})"
        if self.family == BECOMELEADER:
            return f"BecomeLeader(s{self.i + 1})"
        if self.family == CLIENTREQUEST:
            return f"ClientRequest(s{self.i + 1}, v{self.v})"
        if self.family == ADVANCECOMMIT:
            return f"AdvanceCommitIndex(s{self.i + 1})"
        if self.family == APPENDENTRIES:
            return f"AppendEntries(s{self.i + 1}, s{self.j + 1})"
        return f"{self.family}(slot {self.slot})"


def action_table(bounds: Bounds, spec: str = "full") -> list[ActionInstance]:
    """The static, ordered successor fan-out for one state.

    Enumeration order mirrors the disjunct order of ``Next``
    (``raft.tla:454-463``).  Size A = 4n + n^2 + nV + n(n-1) + 3*msg_cap for
    the full spec.
    """
    fams = SPECS[spec]
    n, V, S = bounds.n_servers, bounds.n_values, bounds.msg_cap
    table: list[ActionInstance] = []
    if RESTART in fams:
        table += [ActionInstance(RESTART, i=i) for i in range(n)]
    if TIMEOUT in fams:
        table += [ActionInstance(TIMEOUT, i=i) for i in range(n)]
    if REQUESTVOTE in fams:
        table += [ActionInstance(REQUESTVOTE, i=i, j=j)
                  for i in range(n) for j in range(n)]
    if BECOMELEADER in fams:
        table += [ActionInstance(BECOMELEADER, i=i) for i in range(n)]
    if CLIENTREQUEST in fams:
        table += [ActionInstance(CLIENTREQUEST, i=i, v=v)
                  for i in range(n) for v in range(1, V + 1)]
    if ADVANCECOMMIT in fams:
        table += [ActionInstance(ADVANCECOMMIT, i=i) for i in range(n)]
    if APPENDENTRIES in fams:
        table += [ActionInstance(APPENDENTRIES, i=i, j=j)
                  for i in range(n) for j in range(n) if i != j]
    if RECEIVE in fams:
        table += [ActionInstance(RECEIVE, slot=s) for s in range(S)]
    if DUPLICATE in fams:
        table += [ActionInstance(DUPLICATE, slot=s) for s in range(S)]
    if DROP in fams:
        table += [ActionInstance(DROP, slot=s) for s in range(S)]
    return table
