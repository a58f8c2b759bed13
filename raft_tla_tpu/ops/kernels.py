"""Batched transition kernels — the L2 layer as branchless tensor ops.

Each of the spec's 10 action families (``Next`` disjuncts, ``raft.tla:454-463``)
and 7 message handlers (``raft.tla:284-418``) becomes a guarded functional
update on the tensor struct (ops/state.py).  :func:`build_expand` assembles
them into one jittable ``state -> (successors, valid, overflow)`` function with
the static fan-out of models/spec.py's action table; the engine vmaps it over
the frontier.

Design rules (SURVEY §7):

- **No data-dependent control flow.**  Every disjunct/branch computes its
  guard as a boolean and its effect unconditionally; ``jnp.where`` selects.
  Handler guards partition on ``mterm`` vs ``currentTerm`` (SURVEY §3.3), so
  the per-message dispatch is a branchless select over mutually exclusive
  masks.
- **Effects are functional one-hot updates** (``x.at[]`` is avoided in favor
  of mask arithmetic so the same code vmaps over action parameters).
- **Messages survive or die exactly as in the spec**: UpdateTerm, candidate
  step-down, conflict-truncate and append all *keep* the request in the bag
  (``raft.tla:411-412, 350, 382, 388``) — the multi-step convergence loop must
  not be fused (SURVEY §2.6).
- **Capacity overflow is loud**: ``bag_add`` reports when no slot is free;
  the engine asserts the flag never fires for states it expands (the +1
  capacity scheme of config.py makes that a theorem, the flag checks it).

The differential test (tests/test_kernels.py) compares every successor lane
against the reference interpreter on random bounded states and on reachable
prefixes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.models import spec as SP
from raft_tla_tpu.ops import loguniv
from raft_tla_tpu.ops import msgbits as mb
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops import fingerprint as fpr

I32 = jnp.int32


def _log_rank(bounds, s, i):
    """Rank of ``log[i]`` in the bounded log universe (faithful mode)."""
    uni = loguniv.LogUniverse.of(bounds)
    with jax.named_scope(HISTORY_SCOPE):
        return uni.log_id(s["logTerm"][i], s["logVal"][i], s["logLen"][i],
                          jnp)


def _popcount(x):
    """Branchless 32-bit popcount (Quorum test, ``raft.tla:99``)."""
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    return (x * 0x01010101) >> 24


def _onehot(i, n):
    return jnp.arange(n) == i


def _set1(arr, i, val):
    """arr with arr[i] = val (one-hot form, vmappable over traced i)."""
    return jnp.where(_onehot(i, arr.shape[0]), val, arr)


def _set_row(mat, i, val):
    """mat with row i set to the scalar val."""
    return jnp.where(_onehot(i, mat.shape[0])[:, None], val, mat)


def _set2(mat, i, j, val):
    mask = _onehot(i, mat.shape[0])[:, None] & _onehot(j, mat.shape[1])[None, :]
    return jnp.where(mask, val, mat)


def _last_term(s, i):
    """``LastTerm(log[i])`` (raft.tla:102)."""
    ln = s["logLen"][i]
    idx = jnp.clip(ln - 1, 0, s["logTerm"].shape[1] - 1)
    return jnp.where(ln > 0, s["logTerm"][i, idx], 0)


# -- bag operations (raft.tla:106-130) ---------------------------------------

def _slot_insert(match, empty):
    """Fixed-shape set/bag insert plan over slot arrays.

    Given exclusive masks for "element already in a slot" and "slot free",
    returns ``(ins, exists, overflow)``: the one-hot first-free-slot mask to
    write into (all-False when the element exists or nothing is free),
    whether it already exists, and whether insertion was impossible.
    Shared by the message bag and the faithful-mode elections set so the
    soundness-sensitive idiom has one definition site.
    """
    exists = jnp.any(match)
    has_empty = jnp.any(empty)
    ins = (~exists) & has_empty & _onehot(jnp.argmax(empty),
                                          empty.shape[0]) & empty
    return ins, exists, (~exists) & (~has_empty)


def bag_add(s, hi, lo):
    """``WithMessage`` (raft.tla:106-110). Returns (struct', overflow)."""
    H, L, C = s["msgHi"], s["msgLo"], s["msgCount"]
    match = (H == hi) & (L == lo) & (C > 0)
    ins, _exists, ovf = _slot_insert(match, C == 0)
    out = dict(s)
    out["msgHi"] = jnp.where(ins, hi, H).astype(I32)
    out["msgLo"] = jnp.where(ins, lo, L).astype(I32)
    out["msgCount"] = (C + match.astype(I32) + ins.astype(I32)).astype(I32)
    return out, ovf


def bag_remove(s, hi, lo):
    """``WithoutMessage`` (raft.tla:114-119); no-op when absent."""
    H, L, C = s["msgHi"], s["msgLo"], s["msgCount"]
    match = (H == hi) & (L == lo) & (C > 0)
    c2 = C - match.astype(I32)
    emptied = match & (c2 == 0)
    out = dict(s)
    out["msgHi"] = jnp.where(emptied, 0, H).astype(I32)
    out["msgLo"] = jnp.where(emptied, 0, L).astype(I32)
    out["msgCount"] = c2.astype(I32)
    return out


def reply(s, resp_hi, resp_lo, req_hi, req_lo):
    """``Reply`` (raft.tla:129-130): WithoutMessage(request, WithMessage(resp)).

    Evaluated remove-first: request and response always differ (mtype), so
    the two bag edits commute, and removing first avoids claiming a transient
    extra slot — overflow then fires iff the *final* bag exceeds capacity.
    """
    out = bag_remove(s, req_hi, req_lo)
    out, ovf = bag_add(out, resp_hi, resp_lo)
    return out, ovf


def _tree_select(branches, default):
    """Select among (guard, struct) branches; guards must be exclusive."""
    out = default
    for g, s in branches:
        out = jax.tree.map(lambda a, b: jnp.where(g, b, a), out, s)
    return out


# -- action families ---------------------------------------------------------

def k_restart(bounds, s, i):
    """``Restart(i)`` (raft.tla:167-175): always enabled."""
    out = dict(s)
    out["role"] = _set1(s["role"], i, SP.FOLLOWER)
    out["vResp"] = _set1(s["vResp"], i, 0)
    out["vGrant"] = _set1(s["vGrant"], i, 0)
    out["nextIndex"] = _set_row(s["nextIndex"], i, 1)
    out["matchIndex"] = _set_row(s["matchIndex"], i, 0)
    out["commitIndex"] = _set1(s["commitIndex"], i, 0)
    if "vLog" in s:   # voterLog[i] := empty map (raft.tla:171)
        with jax.named_scope(HISTORY_SCOPE):
            out["vLog"] = _set_row(s["vLog"], i, 0)
    return out, jnp.bool_(True), jnp.bool_(False)


def k_timeout(bounds, s, i):
    """``Timeout(i)`` (raft.tla:178-187)."""
    valid = (s["role"][i] == SP.FOLLOWER) | (s["role"][i] == SP.CANDIDATE)
    out = dict(s)
    out["role"] = _set1(s["role"], i, SP.CANDIDATE)
    out["term"] = _set1(s["term"], i, s["term"][i] + 1)
    out["votedFor"] = _set1(s["votedFor"], i, SP.NIL)
    out["vResp"] = _set1(s["vResp"], i, 0)
    out["vGrant"] = _set1(s["vGrant"], i, 0)
    if "vLog" in s:   # voterLog[i] := empty map (raft.tla:186)
        with jax.named_scope(HISTORY_SCOPE):
            out["vLog"] = _set_row(s["vLog"], i, 0)
    return out, valid, jnp.bool_(False)


def k_request_vote(bounds, s, i, j):
    """``RequestVote(i, j)`` (raft.tla:190-199); j may equal i."""
    valid = (s["role"][i] == SP.CANDIDATE) & (((s["vResp"][i] >> j) & 1) == 0)
    hi, lo = mb.rv_request(s["term"][i], _last_term(s, i), s["logLen"][i], i, j)
    out, ovf = bag_add(s, hi, lo)
    return out, valid, valid & ovf


def k_append_entries(bounds, s, i, j):
    """``AppendEntries(i, j)`` (raft.tla:204-226): <=1 entry, heartbeats incl."""
    Lcap = s["logTerm"].shape[1]
    valid = (i != j) & (s["role"][i] == SP.LEADER)
    ni = s["nextIndex"][i, j]
    prev_idx = ni - 1
    prev_term = jnp.where(
        prev_idx > 0, s["logTerm"][i, jnp.clip(prev_idx - 1, 0, Lcap - 1)], 0)
    last_entry = jnp.minimum(s["logLen"][i], ni)        # raft.tla:213
    has_ent = ni <= last_entry
    eidx = jnp.clip(ni - 1, 0, Lcap - 1)
    ent_term = jnp.where(has_ent, s["logTerm"][i, eidx], 0)
    ent_val = jnp.where(has_ent, s["logVal"][i, eidx], 0)
    mlog = _log_rank(bounds, s, i) if "allLogs" in s else 0  # raft.tla:220-222
    hi, lo = mb.ae_request(
        s["term"][i], prev_idx, prev_term, has_ent.astype(I32), ent_term,
        ent_val, jnp.minimum(s["commitIndex"][i], last_entry), i, j, mlog)
    out, ovf = bag_add(s, hi, lo)
    return out, valid, valid & ovf


def k_become_leader(bounds, s, i):
    """``BecomeLeader(i)`` (raft.tla:229-243); Quorum as popcount.

    In faithful mode also inserts [eterm, eleader, elog, evotes, evoterLog]
    into the ``elections`` slot set (raft.tla:237-242) — a set insert like
    ``bag_add``, minus multiplicities; slot exhaustion is a loud overflow.
    """
    n = bounds.n_servers
    valid = ((s["role"][i] == SP.CANDIDATE)
             & (2 * _popcount(s["vGrant"][i]) > n))
    out = dict(s)
    out["role"] = _set1(s["role"], i, SP.LEADER)
    out["nextIndex"] = _set_row(s["nextIndex"], i, s["logLen"][i] + 1)
    out["matchIndex"] = _set_row(s["matchIndex"], i, 0)
    ovf = jnp.bool_(False)
    if "eTerm" in s:
        with jax.named_scope(HISTORY_SCOPE):
            out, ovf = _elections_insert(bounds, s, out, i)
    return out, valid, valid & ovf


def _elections_insert(bounds, s, out, i):
    """``elections' = elections \\cup {[eterm, eleader, elog, evotes,
    evoterLog]}`` of ``BecomeLeader(i)`` (raft.tla:237-242), all from the
    unprimed state: ``out`` with the record in its first free slot, and
    whether there was none."""
    lid = _log_rank(bounds, s, i)
    vrow = s["vLog"][i]
    occ = s["eTerm"] > 0
    match = (occ & (s["eTerm"] == s["term"][i]) & (s["eLeader"] == i)
             & (s["eLog"] == lid) & (s["eVotes"] == s["vGrant"][i])
             & jnp.all(s["eVLog"] == vrow[None, :], axis=1))
    ins, _exists, ovf = _slot_insert(match, ~occ)
    out = dict(out)
    out["eTerm"] = jnp.where(ins, s["term"][i], s["eTerm"]).astype(I32)
    out["eLeader"] = jnp.where(ins, i, s["eLeader"]).astype(I32)
    out["eLog"] = jnp.where(ins, lid, s["eLog"]).astype(I32)
    out["eVotes"] = jnp.where(ins, s["vGrant"][i], s["eVotes"]).astype(I32)
    out["eVLog"] = jnp.where(ins[:, None], vrow[None, :],
                             s["eVLog"]).astype(I32)
    return out, ovf


def k_client_request(bounds, s, i, v):
    """``ClientRequest(i, v)`` (raft.tla:246-253)."""
    Lcap = s["logTerm"].shape[1]
    ln = s["logLen"][i]
    valid = s["role"][i] == SP.LEADER
    row = _onehot(i, bounds.n_servers)[:, None]
    col = (jnp.arange(Lcap) == ln)[None, :]
    out = dict(s)
    out["logTerm"] = jnp.where(row & col, s["term"][i], s["logTerm"]).astype(I32)
    out["logVal"] = jnp.where(row & col, v, s["logVal"]).astype(I32)
    out["logLen"] = _set1(s["logLen"], i, ln + 1)
    # ln == Lcap would silently drop the entry; the capacity scheme makes it
    # unreachable from constraint-satisfying states — flag, don't clamp.
    return out, valid, valid & (ln >= Lcap)


def k_advance_commit(bounds, s, i):
    """``AdvanceCommitIndex(i)`` (raft.tla:259-276).

    ``Agree(index) == {i} \\cup {k : matchIndex[i][k] >= index}``; commits
    ``Max(agreeIndexes)`` only if that entry's term is current
    (raft.tla:268-270).
    """
    n, Lcap = bounds.n_servers, s["logTerm"].shape[1]
    valid = s["role"][i] == SP.LEADER
    idxs = jnp.arange(1, Lcap + 1)                                   # [L]
    others = s["matchIndex"][i][None, :] >= idxs[:, None]            # [L, n]
    in_set = others | (jnp.arange(n)[None, :] == i)                  # {i} ∪ ...
    agree_cnt = jnp.sum(in_set.astype(I32), axis=1)
    agree_ok = (2 * agree_cnt > n) & (idxs <= s["logLen"][i])
    max_agree = jnp.max(jnp.where(agree_ok, idxs, 0))
    t_at = s["logTerm"][i, jnp.clip(max_agree - 1, 0, Lcap - 1)]
    commit = jnp.where((max_agree > 0) & (t_at == s["term"][i]),
                       max_agree, s["commitIndex"][i])
    out = dict(s)
    out["commitIndex"] = _set1(s["commitIndex"], i, commit)
    return out, valid, jnp.bool_(False)


# -- Receive(m): deterministic dispatch over one slot (raft.tla:421-436) -----

def k_receive(bounds, s, slot):
    n, Lcap = bounds.n_servers, s["logTerm"].shape[1]
    occupied = s["msgCount"][slot] > 0
    hi, lo = s["msgHi"][slot], s["msgLo"][slot]
    i, j = mb.dst(hi), mb.src(hi)
    mt, mty = mb.mterm(hi), mb.mtype(hi)
    ct = s["term"][i]
    role_i = s["role"][i]
    len_i = s["logLen"][i]
    ovf = jnp.bool_(False)

    # UpdateTerm (raft.tla:406-412): any type, message kept.
    g_upd = mt > ct
    s_upd = dict(s)
    s_upd["term"] = _set1(s["term"], i, mt)
    s_upd["role"] = _set1(s["role"], i, SP.FOLLOWER)
    s_upd["votedFor"] = _set1(s["votedFor"], i, SP.NIL)

    not_upd = ~g_upd  # below here mterm <= currentTerm[i]

    # HandleRequestVoteRequest (raft.tla:284-303)
    g_rvreq = not_upd & (mty == SP.M_RVREQ)
    log_ok_rv = ((mb.fa(hi) > _last_term(s, i))
                 | ((mb.fa(hi) == _last_term(s, i))
                    & (mb.fb(hi) >= len_i)))                  # raft.tla:285-287
    grant = ((mt == ct) & log_ok_rv
             & ((s["votedFor"][i] == SP.NIL)
                | (s["votedFor"][i] == j + 1)))               # raft.tla:288-290
    my_mlog = _log_rank(bounds, s, i) if "allLogs" in s else 0  # :297-299
    resp_hi, resp_lo = mb.rv_response(ct, grant.astype(I32), i, j, my_mlog)
    s_rvreq = dict(s)
    s_rvreq["votedFor"] = jnp.where(
        grant, _set1(s["votedFor"], i, j + 1), s["votedFor"])  # raft.tla:292
    s_rvreq, ovf_rv = reply(s_rvreq, resp_hi, resp_lo, hi, lo)
    ovf |= g_rvreq & ovf_rv

    # RequestVoteResponse: DropStaleResponse | HandleRequestVoteResponse
    g_rvresp_drop = not_upd & (mty == SP.M_RVRESP) & (mt < ct)   # raft.tla:415-418
    g_rvresp = not_upd & (mty == SP.M_RVRESP) & (mt == ct)       # raft.tla:307-321
    s_drop = bag_remove(s, hi, lo)
    s_rvresp = dict(s)
    s_rvresp["vResp"] = _set1(s["vResp"], i, s["vResp"][i] | (1 << j))
    s_rvresp["vGrant"] = jnp.where(
        mb.fa(hi) > 0,
        _set1(s["vGrant"], i, s["vGrant"][i] | (1 << j)), s["vGrant"])
    if "vLog" in s:
        # voterLog[i] @@ (j :> m.mlog): existing entry wins (raft.tla:316-317)
        with jax.named_scope(HISTORY_SCOPE):
            cur = s["vLog"][i, j]
            newv = jnp.where((mb.fa(hi) > 0) & (cur == 0), mb.fg(lo) + 1,
                             cur)
            s_rvresp["vLog"] = _set2(s["vLog"], i, j, newv)
    s_rvresp = bag_remove(s_rvresp, hi, lo)

    # HandleAppendEntriesRequest (raft.tla:327-389)
    prev_idx, prev_term = mb.fa(hi), mb.fb(hi)
    n_ent, ent_term, ent_val = mb.fc(lo), mb.fd(lo), mb.fe(lo)
    log_ok_ae = ((prev_idx == 0)
                 | ((prev_idx > 0) & (prev_idx <= len_i)
                    & (prev_term == s["logTerm"][
                        i, jnp.clip(prev_idx - 1, 0, Lcap - 1)])))  # :328-331
    is_ae = not_upd & (mty == SP.M_AEREQ)
    g_ae_reject = is_ae & ((mt < ct)
                           | ((mt == ct) & (role_i == SP.FOLLOWER)
                              & ~log_ok_ae))                        # :333-337
    rej_hi, rej_lo = mb.ae_response(ct, 0, 0, i, j)                 # :338-344
    s_ae_reject, ovf_rej = reply(s, rej_hi, rej_lo, hi, lo)
    ovf |= g_ae_reject & ovf_rej

    g_ae_step = is_ae & (mt == ct) & (role_i == SP.CANDIDATE)       # :346-350
    s_ae_step = dict(s)
    s_ae_step["role"] = _set1(s["role"], i, SP.FOLLOWER)            # msg kept

    accept = is_ae & (mt == ct) & (role_i == SP.FOLLOWER) & log_ok_ae
    index = prev_idx + 1
    t_at_index = s["logTerm"][i, jnp.clip(index - 1, 0, Lcap - 1)]
    g_ae_done = accept & ((n_ent == 0)
                          | ((len_i >= index) & (t_at_index == ent_term)))
    # already done (raft.tla:356-374): commitIndex := mcommitIndex (may
    # decrease, :361-363), Reply success.
    done_hi, done_lo = mb.ae_response(ct, 1, prev_idx + n_ent, i, j)
    s_ae_done = dict(s)
    s_ae_done["commitIndex"] = _set1(s["commitIndex"], i, mb.ff(lo))
    s_ae_done, ovf_done = reply(s_ae_done, done_hi, done_lo, hi, lo)
    ovf |= g_ae_done & ovf_done

    g_ae_conflict = accept & (n_ent > 0) & (len_i >= index) \
        & (t_at_index != ent_term)                                  # :375-382
    # conflict: drop exactly one entry off the TAIL; message kept.
    row = _onehot(i, n)[:, None]
    tail = (jnp.arange(Lcap) == (len_i - 1))[None, :]
    s_ae_conflict = dict(s)
    s_ae_conflict["logTerm"] = jnp.where(row & tail, 0, s["logTerm"]).astype(I32)
    s_ae_conflict["logVal"] = jnp.where(row & tail, 0, s["logVal"]).astype(I32)
    s_ae_conflict["logLen"] = _set1(s["logLen"], i, len_i - 1)

    g_ae_append = accept & (n_ent > 0) & (len_i == prev_idx)        # :383-388
    newcol = (jnp.arange(Lcap) == len_i)[None, :]
    s_ae_append = dict(s)
    s_ae_append["logTerm"] = jnp.where(row & newcol, ent_term,
                                       s["logTerm"]).astype(I32)
    s_ae_append["logVal"] = jnp.where(row & newcol, ent_val,
                                      s["logVal"]).astype(I32)
    s_ae_append["logLen"] = _set1(s["logLen"], i, len_i + 1)
    ovf |= g_ae_append & (len_i >= Lcap)

    # AppendEntriesResponse: DropStaleResponse | Handle (raft.tla:393-403)
    g_aeresp_drop = not_upd & (mty == SP.M_AERESP) & (mt < ct)
    g_aeresp = not_upd & (mty == SP.M_AERESP) & (mt == ct)
    succ_flag = mb.fa(hi) > 0
    match = mb.fb(hi)
    ni_new = jnp.where(succ_flag, match + 1,
                       jnp.maximum(s["nextIndex"][i, j] - 1, 1))
    s_aeresp = dict(s)
    s_aeresp["nextIndex"] = _set2(s["nextIndex"], i, j, ni_new)
    s_aeresp["matchIndex"] = jnp.where(
        succ_flag, _set2(s["matchIndex"], i, j, match), s["matchIndex"])
    s_aeresp = bag_remove(s_aeresp, hi, lo)

    branches = [
        (g_upd, s_upd),
        (g_rvreq, s_rvreq),
        (g_rvresp_drop, s_drop),
        (g_rvresp, s_rvresp),
        (g_ae_reject, s_ae_reject),
        (g_ae_step, s_ae_step),
        (g_ae_done, s_ae_done),
        (g_ae_conflict, s_ae_conflict),
        (g_ae_append, s_ae_append),
        (g_aeresp_drop, s_drop),
        (g_aeresp, s_aeresp),
    ]
    any_branch = functools.reduce(jnp.logical_or, (g for g, _ in branches))
    out = _tree_select(branches, s)
    valid = occupied & any_branch
    return out, valid, valid & ovf


def k_duplicate(bounds, s, slot):
    """``DuplicateMessage(m)`` (raft.tla:443-445)."""
    occupied = s["msgCount"][slot] > 0
    out = dict(s)
    out["msgCount"] = (s["msgCount"]
                       + (jnp.arange(s["msgCount"].shape[0]) == slot)
                       .astype(I32))
    return out, occupied, jnp.bool_(False)


def k_drop(bounds, s, slot):
    """``DropMessage(m)`` (raft.tla:448-450)."""
    occupied = s["msgCount"][slot] > 0
    out = bag_remove(s, s["msgHi"][slot], s["msgLo"][slot])
    return out, occupied, jnp.bool_(False)


# -- assembly ----------------------------------------------------------------

_FAMILY_KERNELS = {
    SP.RESTART: (k_restart, ("i",)),
    SP.TIMEOUT: (k_timeout, ("i",)),
    SP.REQUESTVOTE: (k_request_vote, ("i", "j")),
    SP.BECOMELEADER: (k_become_leader, ("i",)),
    SP.CLIENTREQUEST: (k_client_request, ("i", "v")),
    SP.ADVANCECOMMIT: (k_advance_commit, ("i",)),
    SP.APPENDENTRIES: (k_append_entries, ("i", "j")),
    SP.RECEIVE: (k_receive, ("slot",)),
    SP.DUPLICATE: (k_duplicate, ("slot",)),
    SP.DROP: (k_drop, ("slot",)),
}

# Which struct fields each family's kernel can write (beyond copying the
# input).  This is the kernel side of the width-safety contract: the
# static analyzer (analysis/widthcheck) keeps an abstract transfer twin
# per family and cross-checks the two write-sets, so a kernel growing a
# new write without the twin being re-proved fails the lint loudly.
# History-only fields are listed unconditionally; the analyzer filters
# by mode.  Keep in sync with the k_* bodies above.
TRANSFER_WRITES = {
    SP.RESTART: ("role", "vResp", "vGrant", "nextIndex", "matchIndex",
                 "commitIndex", "vLog"),
    SP.TIMEOUT: ("role", "term", "votedFor", "vResp", "vGrant", "vLog"),
    SP.REQUESTVOTE: ("msgHi", "msgLo", "msgCount"),
    SP.BECOMELEADER: ("role", "nextIndex", "matchIndex",
                      "eTerm", "eLeader", "eLog", "eVotes", "eVLog"),
    SP.CLIENTREQUEST: ("logTerm", "logVal", "logLen"),
    SP.ADVANCECOMMIT: ("commitIndex",),
    SP.APPENDENTRIES: ("msgHi", "msgLo", "msgCount"),
    SP.RECEIVE: ("term", "role", "votedFor", "vResp", "vGrant", "vLog",
                 "commitIndex", "logTerm", "logVal", "logLen",
                 "nextIndex", "matchIndex", "msgHi", "msgLo", "msgCount"),
    SP.DUPLICATE: ("msgCount",),
    SP.DROP: ("msgHi", "msgLo", "msgCount"),
}

# finish_expand's shared postlude writes (outside any single family):
# the faithful-mode allLogs union — raw 32-bit mask words, or-only.
POSTLUDE_WRITES = ("allLogs",)


def transfer_metadata() -> dict:
    """Per-family metadata for the static analyzer: parameter names and
    declared write-sets.  Raises KeyError (loudly, at lint time) if the
    two tables ever drift apart."""
    out = {}
    for fam, (_kern, params) in _FAMILY_KERNELS.items():
        out[fam] = {"params": params, "writes": TRANSFER_WRITES[fam]}
    for fam in TRANSFER_WRITES:
        if fam not in _FAMILY_KERNELS:
            raise KeyError(f"TRANSFER_WRITES names unknown family {fam}")
    return out


def group_instances(table):
    """Group contiguous instances of the same family for vectorized
    dispatch (shared by the dense and CP-sharded expansions)."""
    groups: list[tuple[str, list[SP.ActionInstance]]] = []
    for a in table:
        if groups and groups[-1][0] == a.family:
            groups[-1][1].append(a)
        else:
            groups.append((a.family, [a]))
    return groups


def grouped_dispatch(bounds, s, groups, family_kernels=None):
    """Evaluate the family kernels over grouped static instances:
    ``-> (succs list, valids list, ovfs list)`` of per-group arrays.

    ``family_kernels`` overrides the hand-written kernel table with one
    of the same shape (``{family: (kernel, params)}``) — the seam the
    frontend IR compiler plugs into (frontend/actions.compile_kernels);
    the dispatch, vmapping and broadcast semantics stay this one
    definition either way."""
    table = _FAMILY_KERNELS if family_kernels is None else family_kernels
    succs, valids, ovfs = [], [], []
    for fam, instances in groups:
        kern, params = table[fam]
        args = [jnp.asarray([getattr(a, p) for a in instances], dtype=I32)
                for p in params]
        fn = functools.partial(kern, bounds)
        batched = jax.vmap(fn, in_axes=(None,) + (0,) * len(args))
        out, valid, ovf = batched(s, *args)
        succs.append(out)
        valids.append(jnp.broadcast_to(valid, (len(instances),)))
        ovfs.append(jnp.broadcast_to(ovf, (len(instances),)))
    return succs, valids, ovfs


def finish_expand(bounds, s, succs, valids, ovfs):
    """Concatenate per-group lanes, apply the shared allLogs union
    (faithful mode), canonicalize every successor — the one definition
    of an expansion's postlude (dense and CP twins both end here)."""
    all_succs = jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=0), *succs)
    # st.canonicalize in two halves: the history's under its own scope
    all_succs = jax.vmap(lambda t: st.canonicalize_bag(t, jnp))(all_succs)
    if "allLogs" in s:
        with jax.named_scope(HISTORY_SCOPE):
            all_succs["allLogs"] = _alllogs_update(
                bounds, s, all_succs["allLogs"].shape[0])
            all_succs.update(jax.vmap(
                lambda t: st.canonicalize_elections(t, jnp))(all_succs))
    return all_succs, jnp.concatenate(valids), jnp.concatenate(ovfs)


def build_expand(bounds: Bounds, spec: str = "full", family_kernels=None):
    """Build ``expand(struct) -> (succs[A,...], valid[A], overflow[A])``.

    The A successor lanes follow models/spec.action_table order exactly;
    every successor is canonicalized (message slots sorted).  Pure function
    of a single state struct — vmap/jit at the call site.
    ``family_kernels`` swaps in an alternative kernel table (the IR
    compiler's output) under the same table order and postlude.
    """
    groups = group_instances(SP.action_table(bounds, spec))

    def expand(s):
        succs, valids, ovfs = grouped_dispatch(
            bounds, s, groups, family_kernels=family_kernels)
        return finish_expand(bounds, s, succs, valids, ovfs)

    return expand


def _alllogs_update(bounds, s, n_lanes):
    """``allLogs' = allLogs \\cup {log[i] : i \\in Server}``, conjoined
    with the UNPRIMED logs onto every disjunct (raft.tla:464-465) — one
    shared update broadcast across all ``n_lanes`` successor lanes."""
    uni = loguniv.LogUniverse.of(bounds)
    Wa = s["allLogs"].shape[0]
    ids = uni.log_id(s["logTerm"], s["logVal"], s["logLen"], jnp)
    word, bit = ids // 32, ids % 32
    shift = jnp.left_shift(jnp.int32(1), bit)           # [n]
    masks = jnp.where(jnp.arange(Wa)[None, :] == word[:, None],
                      shift[:, None], 0)                # [n, Wa]
    delta = masks[0]
    for t in range(1, masks.shape[0]):
        delta = delta | masks[t]
    new_all = (s["allLogs"] | delta).astype(I32)
    return jnp.broadcast_to(new_all, (n_lanes, Wa))


# The compiled step's stage scopes (``jax.named_scope``: HLO metadata
# only — no computation and no compile-cache key changes with them).  A
# device trace names each op's scope path, so per-stage device time is
# readable from a capture (benchmark ``stage_*_ms``) and stays readable
# when an edit renumbers the fusions.  ``unpack``/``expand``/``pack``
# open in the step builders, ``prescan`` (raw fingerprint + ladder
# compaction, its scans nested as ``orbit_scan``) to ``constraint`` in
# :func:`apply_stages`, ``filter_insert`` and ``stream`` (compaction into
# the segment buffers at the cursor) in ``ddd_engine._build_segment``.
STAGE_SCOPES = ("unpack", "expand", "pack", "prescan", "orbit_scan",
                "plain_fp", "invariants", "constraint", "filter_insert",
                "stream")
# Two scopes that open INSIDE a stage and are no stage themselves (an op's
# stage stays the innermost name of STAGE_SCOPES on its path, so the stage
# totals keep their meaning): ``history`` inside ``expand``, what faithful
# mode adds to a step (the allLogs union, the voterLog writes, the elections
# insert and sort, the mlog ranks; never opened in parity mode), and
# ``orbit_moved`` inside ``orbit_scan``, what the scan's linear key and
# ranked bag do not cover (ops/symmetry.build_orbit_fp): what the
# faithful-mode history adds to an image's key (``allLogs``' sums once a
# step, the ``elections`` records' keys and sums an image; since PR 51
# nothing of it is moved under Server symmetry alone) and the fields a
# value permutation still moves and canonicalises an image at a time
# (``logVal``, the history's log ranks).
HISTORY_SCOPE, ORBIT_MOVED_SCOPE = NESTED_SCOPES = ("history", "orbit_moved")


def _step_stages(bounds: Bounds, spec: str, invariants: tuple,
                 symmetry: tuple, view: str | None = None,
                 family_kernels=None):
    """The shared builder prologue of the dense and EP-routed steps:
    layout, fingerprint constants, the expansion, the invariant
    predicates, the orbit-fingerprint pipeline, and the dedup-key view.
    One definition site so the step variants can never disagree on key
    arithmetic (the parity and checkpoint-compatibility guarantees rest
    on bit-identical fingerprints)."""
    from raft_tla_tpu.models import invariants as inv_mod
    from raft_tla_tpu.ops import symmetry as sym

    lay = st.Layout.of(bounds)
    consts = jnp.asarray(fpr.lane_constants(lay.width))
    expand = build_expand(bounds, spec, family_kernels=family_kernels)
    inv_fns = [inv_mod.jnp_invariant(nm, bounds) for nm in invariants]
    # Scan-compiled orbit pass: ONE body iterated over the n!*V! group,
    # not n!*V! unrolled copies of the permute/canonicalize/fingerprint
    # pipeline (ops/symmetry.build_orbit_fp) — bit-identical keys.
    # Every engine's step builder flows through here.
    orbit_fp = sym.build_orbit_fp(bounds, symmetry, consts,
                                  "allLogs" in lay.shapes) \
        if symmetry else None
    # The lax.scan orbit pass above is the PERMANENT design (VERDICT r3
    # next #9, decided round 4): a VMEM-resident Pallas orbit kernel was
    # built in round 2, measured at speed parity (0.7-1.15x) where
    # Mosaic compiled it (P <= 6 unrolled perms), failed Mosaic
    # compilation at P=24 (kernel stack scales with the unrolled group;
    # 73 MB at P=120 vs the 16 MB scoped-vmem limit, and the P=24
    # remote-compile returned HTTP 500 — runs/pallas_orbit_p24.out),
    # and was deleted.  What the scan compiles to on the v5e, since PR
    # 42: a body a block of eight server permutations, two operations
    # of it over the lanes — one int8 matrix product on the MXU (the
    # block's 64 rows of limbs of the permuted constants times the
    # byte-wide feature matrix, int32), and one fusion that shifts the
    # limbs home, relabels and ranks the message bag of the block's
    # eight images side by side, finalises and keeps the least key.  No
    # state array is gathered, relabelled, sorted or packed in it, and
    # no image has a multiply-reduce of its own (PR 29-41: 244 us an
    # image at 344,064 lanes, 0.43-0.79 ns a lane, runs/prescan_ab.out,
    # which is what _prescan_enabled's rule for the ladder below was
    # derived from; what an image costs now: PERF.md, PR 42).
    # Mosaic findings: git show f293573:RESULTS.md "Pallas orbit
    # kernel", runs/pallas_orbit_p24.out.
    # The view folds into the DEDUP KEY only: stored rows, invariants and
    # the constraint all see the full successor (TLC VIEW semantics).
    viewer = None
    if view:
        from raft_tla_tpu.models import views as views_mod
        viewer = views_mod.jnp_view(view, bounds)
    return lay, consts, expand, inv_fns, orbit_fp, viewer


def build_step(bounds: Bounds, spec: str = "full", invariants: tuple = (),
               symmetry: tuple = (), view: str | None = None,
               family_kernels=None):
    """One fused frontier step: packed vecs -> everything the engine needs.

    ``step(vecs[B, W]) -> dict`` with packed successors ``svecs [B, A, W]``,
    ``valid``/``overflow`` ``[B, A]``, fingerprint lanes ``fp_hi/fp_lo``
    ``[B, A]`` (uint32), per-invariant truth ``inv_ok [B, A, n_inv]``, and
    StateConstraint satisfaction ``con_ok [B, A]``.  Everything downstream of
    the expansion fuses into one XLA computation — one device round-trip per
    frontier chunk.

    With ``symmetry=("Server",)`` the fingerprint lanes become the
    orbit-minimal fingerprint over all server permutations
    (ops/symmetry.py) — the dedup key that quotients the state space the
    way TLC's SYMMETRY stanza does.

    The compile signature — everything this builder specializes on,
    the prescan resolution included — is :func:`step_signature`; keep
    serving-side bin keys on that helper.
    """
    stages = _step_stages(bounds, spec, invariants, symmetry, view,
                          family_kernels=family_kernels)
    lay = stages[0]
    expand = stages[2]

    def step(vecs):
        with jax.named_scope("unpack"):
            structs = jax.vmap(lambda v: st.unpack(v, lay, jnp))(vecs)
        with jax.named_scope("expand"):
            succs, valid, ovf = jax.vmap(expand)(structs)
        with jax.named_scope("pack"):
            svecs = jax.vmap(jax.vmap(lambda t: st.pack(t, jnp)))(succs)
        # (EP-routed twin: build_step_routed compacts the valid lanes
        # before these per-candidate stages — same values, K-shaped.)
        fp_hi, fp_lo, inv_ok, con_ok = apply_stages(
            bounds, stages, symmetry, succs, svecs, valid)
        return {"svecs": svecs, "valid": valid, "overflow": ovf,
                "fp_hi": fp_hi, "fp_lo": fp_lo, "inv_ok": inv_ok,
                "con_ok": con_ok}

    return step


# Pre-orbit dedup compaction ladder: the orbit scan runs on the
# smallest static slot count the chunk's raw-unique candidates fit —
# N/4, then N/2, then the full N lanes.  What real chunks of 4,096
# frontier rows hold (runs/prescan_ab.out: valid_share, uniq_share_max):
# the dense step calls 0.40 of the flagship's lanes valid at level 15
# and their raw-distinct rows (+1 sentinel group for every invalid
# lane) are up to 23.2% of N, full5's at level 10 0.36 and 20.3% — the
# N/4 rung; elect5 at level 13 (0.66, 37.6%) and six servers at level
# 13 (0.67, 35.3%) land on N/2.  Raw-identical successors are the SAME
# state, so the group representative's canonical fingerprint is
# bit-identical to every member's — counts, discovery order and
# checkpoints are unchanged on every rung, and with no ladder at all.
_PRESCAN_RUNGS = (4, 2)      # divisors of N, tried in order


def _prescan_enabled(bounds, symmetry):
    """Whether a program gets the prescan ladder.  The code's choice,
    from the backend: the CPU's programs take it, a TPU's never do.

    The ladder is a fixed cost a lane — a raw fingerprint of every packed
    row, an N-lane lexsort, a scatter, one ``a[rep]`` gather a struct
    field — and what it saves is scan: |G| images a lane on the lanes a
    rung leaves out, half or three quarters of them.  It pays where that
    saving passes the fixed cost, so the rule follows what an image
    costs, and since PR 29 (the scan's body moves no state data) an
    image is cheap on the chip.

    On a TPU v5e (runs/prescan_ab.out: PR 32, one call, the whole fused
    step on real frontier chunks of 4,096 rows, ladder forced on / off /
    no orbit stage at all, ms a chunk):

    - |G| = 6, flagship3, 172,032 lanes: 18.06 / 6.59 / 5.03 — 0.37x;
    - |G| = 120, elect5, 155,648 lanes: 28.58 / 13.94 / 3.65 — 0.49x;
    - |G| = 120, full5, 344,064 lanes: 62.86 / 44.91 / 12.31 — 0.71x;
    - |G| = 720, six-server election, 208,896 lanes: 75.66 / 69.35 /
      4.40 — 0.92x.

    A full scan costs 0.43-0.79 ns a lane an image (1.5 at |G| = 6,
    where building the key table shows), the ladder 74-186 ns a lane
    (on less no-orbit-stage less the rung's share of the scan; it grows
    with the row's width and the rung's slots), and at depth both
    elections take the N/2 rung, which saves half.  So the ladder loses
    at every |G| ops/symmetry admits on one axis (MAX_SYM_SERVERS = 6),
    and in the benchmark it cost 12.8 and 16.5 ms of a 39.6 and 89.3 ms
    step (``stage_orbit_ms`` 22.53 -> 9.73 in elect5.passes, 45.86 ->
    29.33 in full5.passes: PERF.md section 6, PR 32).  Only a program
    with both axes at six servers (|G| >= 1,440), or a six-server one
    whose chunks fit the N/4 rung as full5's do, would gain by these
    figures; none is measured and no cell runs one, so the TPU's rule
    is never, and the ladder is the CPU backend's.  Since PR 42 the
    scan's linear part is one int8 matrix product a block of eight
    images and a full scan is 0.15-0.18 ns a lane an image at |G| = 120
    (PERF.md section 6): the ladder's fixed cost did not change, so it
    loses by more.

    On the CPU it is on for every symmetric program (runs/prescan_ab.py
    --cpu, PR 32, this repository's sandbox, the same step and chunks at
    shallower levels): 0.98x at |G| = 6 (flagship3, level 10), 2.42x at
    |G| = 120 (elect5, level 11), 2.38x at |G| = 720 (six servers, level
    10) — an image costs 80-190 ns a lane there, and the tier-1 suite's
    wall rests on it.

    ``RAFT_TLA_PRESCAN`` / ``--prescan {on,off}`` is the measuring
    override; keys are bit-identical either way."""
    if not _PRESCAN_RUNGS or not symmetry:
        return False
    import os
    force = os.environ.get("RAFT_TLA_PRESCAN", "auto")
    if force == "on":            # measurement override (runs/prescan_ab,
        return True              # in-engine bench A/B) — not for prod
    if force == "off":
        return False
    return jax.default_backend() == "cpu"


def step_signature(bounds, spec, invariants, symmetry, view):
    """Everything :func:`build_step` specializes the compiled step on —
    universe bounds, spec subset, invariant set, symmetry axes, the
    dedup-key view, and the construction-time gate resolutions
    (prescan / devdedup).  THE definition of step-compile
    identity: serve/batch.bin_key delegates here, so two jobs share a
    lane-packed bin (and a compile) iff this tuple matches — bins can
    never mix step variants when a gate flips between admissions.

    Gates resolve per call (env + backend), so compute the signature at
    the same time you build the step it stands for."""
    # call-time import: devdedup imports device_engine, which imports
    # this module — a top-level import would cycle
    from raft_tla_tpu.ops import devdedup
    return (bounds, spec, tuple(invariants), tuple(symmetry), view,
            ("prescan", _prescan_enabled(bounds, symmetry)),
            ("devdedup", devdedup.devdedup_backend()))


def _orbit_fp_prescan(orbit_fp, flat, raw_hi, raw_lo, N):
    """Orbit-scan only the first occurrence of each raw key, gather the
    canonical fingerprints back through the group map (see the
    _PRESCAN_RUNGS comment; _prescan_enabled says which programs take
    it and why).  Keys are (hi, lo) uint32 pairs — x64 is disabled, a
    u64 fuse would silently truncate."""
    # traced under apply_stages' ``prescan`` scope; the scans it places
    # are ``orbit_scan`` inside it
    idx = jnp.lexsort((raw_lo, raw_hi))
    sh, sl = raw_hi[idx], raw_lo[idx]
    first = jnp.concatenate(
        [jnp.ones((1,), bool),
         (sh[1:] != sh[:-1]) | (sl[1:] != sl[:-1])])
    gid_sorted = jnp.cumsum(first.astype(jnp.int32)) - 1
    gid = jnp.zeros((N,), jnp.int32).at[idx].set(gid_sorted)
    n_uniq = gid_sorted[-1] + 1

    def compact_at(K):
        def compact(_):
            # rep[g] = original index of group g's first sorted member
            # (built INSIDE the branch: untaken rungs must cost nothing)
            rep = jnp.zeros((K,), jnp.int32).at[
                jnp.where(first, gid_sorted, K)].set(
                idx.astype(jnp.int32), mode="drop")
            flat_k = jax.tree.map(lambda a: a[rep], flat)
            with jax.named_scope("orbit_scan"):
                fh_k, fl_k = orbit_fp(flat_k)
            return fh_k[gid], fl_k[gid]

        return compact

    def full(_):
        with jax.named_scope("orbit_scan"):
            return orbit_fp(flat)

    # build the elif chain inside-out: largest K wraps full first, so
    # the final test order is smallest-K-first (tightest rung wins)
    out = full
    for div in sorted(_PRESCAN_RUNGS):
        K = max(1, N // div)
        out = (lambda _, _c=compact_at(K), _o=out, _K=K:
               jax.lax.cond(n_uniq <= _K, _c, _o, None))
    return out(None)


def orbit_keys(bounds, symmetry, orbit_fp, consts, ksuccs, svecs, valid):
    """The orbit keys of ``[B, A]``-shaped successors, ``(fp_hi, fp_lo)``
    of that shape: ``orbit_fp`` over every lane, or where the program gets
    the prescan ladder (:func:`_prescan_enabled`) over the first occurrence
    of each raw key.  One definition for every step that reduces by a
    symmetry, Raft's (:func:`apply_stages`) and a schema-declared spec's
    (``frontend/actions.build_schema_step``)."""
    flat = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), ksuccs)
    N = valid.size
    vmask = valid.reshape(-1)
    if _prescan_enabled(bounds, symmetry):
        # raw keys hash the ALREADY-PACKED UN-VIEWED rows —
        # deliberate: zero extra pack cost, and raw grouping only
        # needs to REFINE canonical equality (under a view,
        # view-equal successors that differ in view-excluded fields
        # just occupy separate slots — less compaction, never
        # wrong).  In-chunk raw collisions are strictly inside the
        # globally-accepted fp-collision class; invalid lanes
        # collapse into one all-ones sentinel group
        with jax.named_scope("prescan"):
            rh, rl = fpr.fingerprint(svecs.reshape(N, -1), consts,
                                     jnp)
            rh = jnp.where(vmask, rh, ~jnp.uint32(0))
            rl = jnp.where(vmask, rl, ~jnp.uint32(0))
            fh, fl = _orbit_fp_prescan(orbit_fp, flat, rh, rl, N)
    else:
        with jax.named_scope("orbit_scan"):
            fh, fl = orbit_fp(flat)
    # invalid lanes: ZERO, not whichever garbage the sentinel
    # group's rep produced — deterministic across step variants
    # (the CP per-lane parity test compares every lane)
    fh = jnp.where(vmask, fh, 0)
    fl = jnp.where(vmask, fl, 0)
    return fh.reshape(svecs.shape[:2]), fl.reshape(svecs.shape[:2])


def apply_stages(bounds, stages, symmetry, succs, svecs, valid):
    """The per-candidate stage block on ``[B, A]``-shaped successors —
    view, orbit/plain fingerprints, invariants, StateConstraint.  One
    definition shared by the dense step and the CP-sharded step (the
    EP-routed step runs the same stages on its compacted ``[K]`` axis)."""
    lay, consts, _expand, inv_fns, orbit_fp, viewer = stages
    ksuccs, ksvecs = succs, svecs          # dedup-key inputs
    if viewer is not None:
        ksuccs = jax.vmap(jax.vmap(viewer))(succs)
        if not symmetry:
            ksvecs = jax.vmap(jax.vmap(
                lambda t: st.pack(t, jnp)))(ksuccs)
    if symmetry:
        fp_hi, fp_lo = orbit_keys(bounds, symmetry, orbit_fp, consts,
                                  ksuccs, svecs, valid)
    else:
        with jax.named_scope("plain_fp"):
            fp_hi, fp_lo = fpr.fingerprint(ksvecs, consts, jnp)
    with jax.named_scope("invariants"):
        if inv_fns:
            inv_ok = jnp.stack(
                [jax.vmap(jax.vmap(f))(succs) for f in inv_fns], axis=-1)
        else:
            inv_ok = jnp.ones(valid.shape + (0,), dtype=bool)
    with jax.named_scope("constraint"):
        con_ok = jax.vmap(jax.vmap(
            lambda t: st.constraint_ok(t, bounds, jnp)))(succs)
    return fp_hi, fp_lo, inv_ok, con_ok


def build_step_routed(bounds: Bounds, spec: str = "full",
                      invariants: tuple = (), symmetry: tuple = (),
                      k_rows: int = 0, view: str | None = None,
                      family_kernels=None):
    """EP-style routed frontier step (SURVEY §2.9, EP row): compact the
    enabled lanes, then run the expensive per-candidate stages densely.

    The dense :func:`build_step` evaluates pack/fingerprint/orbit/
    invariant/constraint on ALL ``B*A`` successor lanes, but measured
    transition density is ~6-10% of the fan-out (258.1M transitions over
    94.4M x 42 lanes on the flagship; RESULTS.md) — ~90% of the dominant
    orbit pass (|G| = n!*V! permutations, runs/xla_profile/SUMMARY.md) is
    spent on guard-disabled lanes.  This is the MoE-routing analog: the
    cheap elementwise expansion plays the router, a stable-order
    compaction (cumsum positions + scatter/gather, no sort) routes the
    enabled (state, action) pairs into ``k_rows`` dense slots, and the
    orbit/fingerprint/invariant "experts" see only live work.

    ``step(vecs[B, W], row_ok[B]) -> dict`` with the dense ``valid``/
    ``overflow`` ``[B, A]`` (the engine's deadlock/truncation logic reads
    these; NOT masked by ``row_ok``) plus the compacted candidate stream,
    ordered by flat lane index ``b*A + a`` — byte-identical discovery
    order to the dense step.  ``row_ok`` marks the chunk rows that are
    live (inside the block, constraint-satisfying): only their lanes
    consume routing slots — without it, the stale padded rows of a
    partial chunk would eat the budget and could trigger spurious
    ``route_ovf`` aborts.  Pass ``None`` when every row is live.

    - ``cidx [K] int32``: flat source index of each routed lane
      (``N = B*A`` for padding slots), strictly increasing on the live
      prefix;
    - ``cvalid [K]``: slot holds a routed lane;
    - ``csvecs [K, W]``, ``cfp_hi/cfp_lo [K]``, ``cinv_ok [K, n_inv]``,
      ``ccon_ok [K]``: exactly the dense step's values at ``cidx``;
    - ``route_ovf``: scalar bool — more than ``k_rows`` enabled lanes
      (the caller must abort loudly: candidates would be LOST, and
      "exhaustive" may not silently mean "sampled", SURVEY §4.5).

    Sizing: worst case is ``k_rows = B*A`` (full density — no saving, no
    loss); the measured regime makes ``B*A // 4`` a >=2.5x-headroom
    default.  Correct for parity AND faithful mode (the expansion twin
    carries the allLogs update; history fields ride the same gather).
    """
    (lay, consts, expand, inv_fns, orbit_fp,
     viewer) = _step_stages(bounds, spec, invariants, symmetry, view,
                            family_kernels=family_kernels)
    if k_rows <= 0:
        raise ValueError(f"k_rows={k_rows} must be positive")
    K = int(k_rows)

    def step(vecs, row_ok=None):
        B = vecs.shape[0]
        with jax.named_scope("unpack"):
            structs = jax.vmap(lambda v: st.unpack(v, lay, jnp))(vecs)
        with jax.named_scope("expand"):
            succs, valid, ovf = jax.vmap(expand)(structs)
        A = valid.shape[1]
        N = B * A
        live = valid if row_ok is None else valid & row_ok[:, None]
        fvalid = live.reshape(-1)
        # Stable compaction: slot k <- k-th enabled flat lane.  cumsum
        # preserves flat order, so the compacted stream IS the dense
        # stream with the dead lanes deleted — discovery order (hence
        # counts, coverage, traces, checkpoints) is engine-identical.
        pos = jnp.cumsum(fvalid.astype(I32)) - 1
        n_en = jnp.where(N > 0, pos[-1] + 1, 0)
        route_ovf = n_en > K
        slot = jnp.where(fvalid & (pos < K), pos, K)
        cidx = jnp.full((K,), N, dtype=I32).at[slot].set(
            jnp.arange(N, dtype=I32), mode="drop")
        cvalid = cidx < N
        gidx = jnp.minimum(cidx, N - 1)
        flat = jax.tree.map(lambda a: a.reshape((N,) + a.shape[2:]), succs)
        csucc = jax.tree.map(lambda a: a[gidx], flat)
        with jax.named_scope("pack"):
            csvecs = jax.vmap(lambda t: st.pack(t, jnp))(csucc)
        ksucc, ksvecs = csucc, csvecs      # dedup-key inputs
        if viewer is not None:
            ksucc = jax.vmap(viewer)(csucc)
            if not symmetry:
                ksvecs = jax.vmap(lambda t: st.pack(t, jnp))(ksucc)
        if symmetry:
            with jax.named_scope("orbit_scan"):
                cfp_hi, cfp_lo = orbit_fp(ksucc)
        else:
            with jax.named_scope("plain_fp"):
                cfp_hi, cfp_lo = fpr.fingerprint(ksvecs, consts, jnp)
        with jax.named_scope("invariants"):
            if inv_fns:
                cinv_ok = jnp.stack([jax.vmap(f)(csucc) for f in inv_fns],
                                    axis=-1)
            else:
                cinv_ok = jnp.ones((K, 0), dtype=bool)
        with jax.named_scope("constraint"):
            ccon_ok = jax.vmap(
                lambda t: st.constraint_ok(t, bounds, jnp))(csucc)
        return {"valid": valid, "overflow": ovf, "cidx": cidx,
                "cvalid": cvalid, "csvecs": csvecs, "cfp_hi": cfp_hi,
                "cfp_lo": cfp_lo, "cinv_ok": cinv_ok, "ccon_ok": ccon_ok,
                "route_ovf": route_ovf, "n_en": n_en}

    return step
