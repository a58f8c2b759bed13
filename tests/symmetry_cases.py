"""What the symmetry test files share (``test_symmetry.py``,
``test_symmetry_engines.py``, ``test_prescan.py``): the bounds they run at
and the bags of states the orbit scan is held to, case by case."""

import numpy as np

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.models import interp
from raft_tla_tpu.ops import msgbits as mb
from raft_tla_tpu.ops import state as st

B2 = Bounds(n_servers=2, n_values=1, max_term=2, max_log=0, max_msgs=2)
B3 = Bounds(n_servers=3, n_values=1, max_term=2, max_log=0, max_msgs=1)
_B3S = Bounds(n_servers=3, n_values=2, max_term=2, max_log=1, max_msgs=2)
_BH2 = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2,
              history=True, max_elections=4)
# faithful3's own bounds (benchmark/configs/faithful3.json), and bounds
# whose log universe has 259 ranks: a ``vLog`` word past a signed byte and
# an ``elections`` key of two words
_BH3 = Bounds(n_servers=3, n_values=2, max_term=2, max_log=1, max_msgs=2,
              history=True, max_elections=6)
_BH3W = Bounds(n_servers=3, n_values=2, max_term=2, max_log=2, max_msgs=2,
               history=True, max_elections=3)
# the benchmark's 5-server bounds (benchmark/configs/elect5.json, full5.json)
_ELECT5 = Bounds(n_servers=5, n_values=2, max_term=2, max_log=0, max_msgs=2,
                 max_dup=1)
_FULL5 = Bounds(n_servers=5, n_values=2, max_term=2, max_log=1, max_msgs=2,
                max_dup=1)


def bag(*ms):
    return tuple(sorted((m, 1) for m in ms))


def _scan_case_states(bounds, spec, depth, lane_cap, cap, first=False):
    """A bag of reachable states: BFS prefix via the interpreter, keeping
    ``lane_cap`` successors a level — every k-th one (late ones carry the
    deeper histories), or with ``first`` the first ones (the low action
    ids: timeouts, vote requests and their replies, where servers still
    look alike) with the constraint ignored."""
    frontier = [interp.init_state(bounds)]
    seen = list(frontier)
    for _ in range(depth):
        nxt = []
        for s in frontier:
            # a state past the constraint is counted, not expanded
            if first or interp.constraint_ok(s, bounds):
                nxt += [t for _i, t in interp.successors(s, bounds,
                                                         spec=spec)]
        stride = 1 if first else max(1, len(nxt) // lane_cap)
        frontier = nxt[::stride][:lane_cap]
        seen += frontier
    return seen[:cap]


def _random_states(bounds, n, seed):
    from test_state import random_pystate
    rng = np.random.default_rng(seed)
    return [random_pystate(rng, bounds) for _ in range(n)]


def _all_distinct_state():
    """No two servers interchangeable: every one of the 6 permutations
    gives another orbit member, so the min really ranges over the group."""
    return interp.init_state(_B3S)._replace(
        role=(0, 1, 2), term=(1, 2, 2), votedFor=(0, 2, 3))


def _distinct5():
    """Five servers no two of which are interchangeable, empty bag."""
    return interp.init_state(_FULL5)._replace(
        role=(0, 1, 2, 0, 1), term=(1, 2, 2, 3, 1), votedFor=(0, 2, 3, 0, 5))


def _bag_states():
    """Bags the scan has to rank as ``canonicalize`` sorts them: three
    occupied slots whose (dst, src) order a permutation changes; two
    slots equal in ``hi`` that differ in ``lo`` alone (the same
    AppendEntriesRequest but for its entry); a multiplicity of 2 beside a
    1; one message; none."""
    rv, ae = mb.rv_request, mb.ae_request
    bags = [
        bag(rv(2, 0, 0, 0, 4), rv(2, 0, 0, 3, 1), rv(2, 0, 0, 2, 2)),
        bag(ae(2, 0, 0, 1, 1, 1, 0, 1, 3), ae(2, 0, 0, 1, 2, 2, 0, 1, 3),
            rv(1, 0, 0, 4, 0)),
        tuple(sorted([(rv(2, 0, 0, 0, 1), 2), (rv(1, 0, 0, 4, 2), 1)])),
        bag(mb.rv_response(2, 1, 3, 0)),
        (),
    ]
    ae_hi = [hi for (hi, _lo), _c in bags[1] if mb.mtype(hi) == 3]
    assert len(ae_hi) == 2 and len(set(ae_hi)) == 1     # equal hi words
    return [_distinct5()._replace(msgs=b) for b in bags]


def _stale_slot_vecs():
    """Packed rows no ``to_vec`` writes: an EMPTY slot (``msgCount`` 0)
    that still holds content words, as a kernel that counts a message
    down to 0 may leave it — in front of, between and behind the
    occupied slots.  ``canonicalize`` zeroes it before it sorts; the
    scan must drop it from its ranking.  Row 0 is the clean state."""
    lay = st.Layout.of(_FULL5)
    rv = mb.rv_request
    clean = interp.to_vec(_distinct5()._replace(
        msgs=bag(rv(2, 0, 0, 0, 4), rv(2, 0, 0, 3, 1))), _FULL5)
    (h0, l0), (h1, l1) = sorted([rv(2, 0, 0, 0, 4), rv(2, 0, 0, 3, 1)])
    stale_hi, stale_lo = rv(2, 0, 0, 2, 2)[0], 0x155
    vecs = [clean]
    for slots in ([(stale_hi, stale_lo, 0), (h0, l0, 1), (h1, l1, 1)],
                  [(h0, l0, 1), (stale_hi, stale_lo, 0), (h1, l1, 1)],
                  [(h0, l0, 1), (h1, l1, 1), (stale_hi, stale_lo, 0)]):
        t = st.unpack(clean, lay, np)
        t["msgHi"], t["msgLo"], t["msgCount"] = (
            np.asarray(w, np.int32) for w in zip(*slots))
        vecs.append(st.pack(t, np))
    return np.stack(vecs)


def _elected_states(bounds, depth=3, cap=60):
    """Reachable states that hold an ``elections`` record: the shortest
    run of the election actions to a first ``BecomeLeader``, then a BFS
    prefix of the full ``Next`` from there (the record rides along while
    the servers' own words change)."""
    seen = {interp.init_state(bounds)}
    frontier = list(seen)
    won = None
    while won is None:
        nxt = []
        for s in frontier:
            for _i, t in interp.successors(s, bounds, spec="election"):
                if t.elections:
                    won = won or t
                if t not in seen and interp.constraint_ok(t, bounds):
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    out, frontier = [won], [won]
    for _ in range(depth):
        nxt = [t for s in frontier if interp.constraint_ok(s, bounds)
               for _i, t in interp.successors(s, bounds, spec="full")]
        frontier = nxt[::max(1, len(nxt) // cap)][:cap]
        out += frontier
    assert all(t.elections for t in out)
    return out


def _tied_record_states(bounds, seed):
    """Bounded random states whose ``elections`` hold 2..6 distinct
    records, the first two equal in ``eTerm``, ``eLeader`` and ``eLog``
    and apart in ``eVotes`` alone, the third apart from the first in one
    ``evoterLog`` column alone: orders a late key word decides, and a
    server permutation changes."""
    out = []
    for s in _random_states(bounds, 400, seed):
        if len(s.elections) < 2:
            continue
        (term, lead, log, votes, vlog), *rest = s.elections
        twin = (term, lead, log, votes ^ 1, vlog)
        col = len(out) % bounds.n_servers
        near = (term, lead, log, votes, vlog[:col]
                + (None if vlog[col] == () else (),) + vlog[col + 1:])
        recs = {s.elections[0], twin, near, *rest[1:]}
        out.append(s._replace(elections=tuple(sorted(
            recs, key=interp._election_key))[:bounds.max_elections]))
    assert any(len(s.elections) == bounds.max_elections for s in out)
    return out[:80]


def _stale_election_vecs():
    """Packed rows no ``to_vec`` writes: an EMPTY ``elections`` slot
    (``eTerm`` 0) that still holds words, in front of, between and behind
    the occupied ones.  No kernel leaves one (``elections`` only grows),
    but ``canonicalize_elections`` zeroes nothing: the loop sorts such a
    slot among the empty ones and its words join the key, so the scan
    has to key it the same way.  Row 0 is the clean state."""
    lay = st.Layout.of(_BH3)
    clean = next(s for s in _tied_record_states(_BH3, seed=53)
                 if len(s.elections) == 3)
    vecs = [interp.to_vec(clean, _BH3)]
    for at in (0, 1, 3):
        t = st.unpack(vecs[0], lay, np)
        stale = {"eTerm": 0, "eLeader": 2, "eLog": 5, "eVotes": 6}
        for f in ("eTerm", "eLeader", "eLog", "eVotes", "eVLog"):
            rows = list(t[f][:3])
            rows.insert(at, np.asarray([7, 0, 9]) if f == "eVLog"
                        else stale[f])
            t[f] = np.concatenate([np.asarray(rows, np.int32),
                                   t[f][4:]]).astype(np.int32)
            if at == 3:    # and a second stale slot behind it, another order
                t[f][5] = np.asarray([0, 8, 0]) if f == "eVLog" \
                    else {**stale, "eLeader": 1}[f]
        vecs.append(st.pack(t, np))
    return np.stack(vecs)


# name -> (bounds, axes, VIEW or None, states (or packed rows), at least
# this many)
_SCAN_CASES = {
    "3s-server": (_B3S, ("Server",), None,
                  lambda: _scan_case_states(_B3S, "full", 4, 40, 200), 100),
    "3s-value": (_B3S, ("Value",), None,
                 lambda: _scan_case_states(_B3S, "full", 4, 40, 200), 100),
    "3s-server-value": (
        _B3S, ("Server", "Value"), None,
        lambda: _scan_case_states(_B3S, "full", 4, 40, 200), 100),
    "2s-faithful-server-value": (
        _BH2, ("Server", "Value"), None,
        lambda: _scan_case_states(_BH2, "full", 4, 40, 200), 100),
    "2s-faithful-value": (
        _BH2, ("Value",), None,
        lambda: _scan_case_states(_BH2, "full", 6, 60, 300), 100),
    "elect5-server": (
        _ELECT5, ("Server",), None,
        lambda: _scan_case_states(_ELECT5, "election", 7, 60, 300), 300),
    "full5-server": (
        _FULL5, ("Server",), None,
        lambda: _scan_case_states(_FULL5, "full", 7, 60, 300), 300),
    # faithful mode under Server symmetry alone (PR 51): nothing is moved
    "3s-faithful-server": (
        _BH3, ("Server",), None,
        lambda: _elected_states(_BH3) + _tied_record_states(_BH3, seed=51)
        + _random_states(_BH3, 40, seed=52), 150),
    "3s-faithful-wide-ranks-server": (
        _BH3W, ("Server",), None,
        lambda: _elected_states(_BH3W, depth=2, cap=30)
        + _tied_record_states(_BH3W, seed=54)[:40], 60),
    "3s-faithful-stale-election-slots": (
        _BH3, ("Server",), None, _stale_election_vecs, 4),
    # the poles of the orbit: every permutation ties / none does
    "5s-all-identical": (
        _ELECT5, ("Server",), None,
        lambda: [interp.init_state(_ELECT5)] * 4, 4),
    "3s-all-distinct": (
        _B3S, ("Server",), None, lambda: [_all_distinct_state()], 1),
    "3s-first-lanes-server-value": (
        _B3S, ("Server", "Value"), None,
        lambda: _scan_case_states(_B3S, "full", 3, 60, 150, first=True), 100),
    "2s-faithful-first-lanes-server-value": (
        _BH2, ("Server", "Value"), None,
        lambda: _scan_case_states(_BH2, "full", 4, 60, 150, first=True), 100),
    # the engines hand the scan the VIEWED struct; random bounded states,
    # because votes on a server that is no candidate (what the view
    # folds) are rare in a BFS prefix
    "3s-view-server": (_B3S, ("Server",), "deadvotes",
                       lambda: _random_states(_B3S, 120, seed=28), 120),
    # the bag, which the scan ranks and the loop sorts (PR 29)
    "full5-bags": (_FULL5, ("Server",), None, _bag_states, 5),
    "full5-stale-slots": (_FULL5, ("Server",), None, _stale_slot_vecs, 4),
    "full5-random": (_FULL5, ("Server",), None,
                     lambda: _random_states(_FULL5, 60, seed=29), 60),
    "3s-random-server-value": (
        _B3S, ("Server", "Value"), None,
        lambda: _random_states(_B3S, 60, seed=30), 60),
}
