"""Server-permutation symmetry reduction (TLC SYMMETRY analog).

Correctness anchors: the orbit key is permutation-invariant; the
symmetry-reduced oracle count equals the brute-force orbit count of the
full space; the device engine under symmetry reproduces the reduced oracle
exactly; violations still surface with replayable traces.
"""

import itertools

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.device_engine import Capacities, DeviceEngine
from raft_tla_tpu.models import interp, refbfs, spec as S
from raft_tla_tpu.ops import msgbits as mb
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops import symmetry as sym

B2 = Bounds(n_servers=2, n_values=1, max_term=2, max_log=0, max_msgs=2)
B3 = Bounds(n_servers=3, n_values=1, max_term=2, max_log=0, max_msgs=1)


def bag(*ms):
    return tuple(sorted((m, 1) for m in ms))


def permute_py_state(s, p, bounds):
    """Reference permutation on the PyState view (independent impl)."""
    n = bounds.n_servers
    inv = [p.index(k) for k in range(n)]

    def vf(v):
        return 0 if v == 0 else p[v - 1] + 1

    def mask(m):
        out = 0
        for j in range(n):
            out |= ((m >> j) & 1) << p[j]
        return out

    msgs = []
    for (hi, lo), cnt in s.msgs:
        hi2 = mb.pack_hi(mb.mtype(hi), mb.mterm(hi), mb.fa(hi), mb.fb(hi),
                         p[mb.src(hi)], p[mb.dst(hi)])
        msgs.append(((hi2, lo), cnt))
    return s._replace(
        role=tuple(s.role[inv[k]] for k in range(n)),
        term=tuple(s.term[inv[k]] for k in range(n)),
        votedFor=tuple(vf(s.votedFor[inv[k]]) for k in range(n)),
        commitIndex=tuple(s.commitIndex[inv[k]] for k in range(n)),
        log=tuple(s.log[inv[k]] for k in range(n)),
        vResp=tuple(mask(s.vResp[inv[k]]) for k in range(n)),
        vGrant=tuple(mask(s.vGrant[inv[k]]) for k in range(n)),
        nextIndex=tuple(tuple(s.nextIndex[inv[k]][inv[j]] for j in range(n))
                        for k in range(n)),
        matchIndex=tuple(tuple(s.matchIndex[inv[k]][inv[j]]
                               for j in range(n)) for k in range(n)),
        msgs=tuple(sorted(msgs)))


def reachable_states(bounds, spec):
    table = S.action_table(bounds, spec)
    seen = {interp.init_state(bounds)}
    frontier = list(seen)
    while frontier:
        nxt = []
        for s in frontier:
            if not interp.constraint_ok(s, bounds):
                continue
            for _a, t in interp.successors(s, bounds, table):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    return seen


def test_orbit_key_is_permutation_invariant():
    states = list(reachable_states(B3, "election"))[:300]
    perms = list(itertools.permutations(range(3)))
    for s in states[:60]:
        keys = {sym.py_orbit_fingerprint(permute_py_state(s, p, B3), B3)
                for p in perms}
        assert len(keys) == 1


def test_oracle_orbit_count_matches_brute_force():
    cfg = CheckConfig(bounds=B2, spec="election", invariants=(),
                      symmetry=("Server",))
    reduced = refbfs.check(cfg)
    full = reachable_states(B2, "election")
    orbits = {sym.py_orbit_fingerprint(s, B2) for s in full}
    assert reduced.n_states == len(orbits) == 1514
    assert len(full) == 3014


def test_device_engine_symmetry_parity():
    cfg = CheckConfig(bounds=B3, spec="election",
                      invariants=("NoTwoLeaders",), symmetry=("Server",),
                      chunk=256)
    ref = refbfs.check(cfg)
    got = DeviceEngine(cfg, Capacities(n_states=1 << 16, levels=64)).check()
    assert got.n_states == ref.n_states
    assert got.diameter == ref.diameter
    assert got.levels == ref.levels
    assert got.n_transitions == ref.n_transitions
    assert got.coverage == ref.coverage
    assert got.violation is None
    # sanity: it actually reduced (full space is 142538 with 2 values /
    # this config's unreduced count is strictly larger)
    unred = refbfs.check(CheckConfig(bounds=B3, spec="election",
                                     invariants=("NoTwoLeaders",)))
    assert ref.n_states < unred.n_states


def test_symmetry_violation_trace_replayable():
    bounds = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                    max_msgs=4, max_dup=1)
    cfg = CheckConfig(bounds=bounds, spec="election",
                      invariants=("NaiveNoTwoLeaders",),
                      symmetry=("Server",), chunk=256)
    start = interp.init_state(bounds)._replace(
        role=(S.LEADER, S.FOLLOWER, S.CANDIDATE),
        term=(2, 3, 3), votedFor=(1, 3, 0),
        vGrant=(0b011, 0, 0b100),
        msgs=bag(mb.rv_response(3, 1, 1, 2)))
    ref = refbfs.check(cfg, init_override=start)
    got = DeviceEngine(cfg, Capacities(n_states=1 << 15, levels=64)
                       ).check(init_override=start)
    assert ref.violation is not None and got.violation is not None
    assert got.violation.state == ref.violation.state
    trace = got.violation.trace
    for (_l, prev), (_label, cur) in zip(trace, trace[1:]):
        succs = [t for _i, t in interp.successors(prev, bounds,
                                                  spec="election")]
        assert cur in succs


def test_too_many_servers_is_loud():
    with pytest.raises(ValueError, match="symmetry"):
        sym.permutations(Bounds(n_servers=7, n_values=1, max_term=2,
                                max_log=0, max_msgs=1))


def test_host_engine_symmetry_parity():
    """Regression: the host-dedup engine must apply the same orbit keys
    (it once silently skipped the reduction while printing the banner)."""
    from raft_tla_tpu import engine
    cfg = CheckConfig(bounds=B2, spec="election", invariants=(),
                      symmetry=("Server",), chunk=64)
    ref = refbfs.check(cfg)
    got = engine.check(cfg)
    assert got.n_states == ref.n_states == 1514
    assert got.levels == ref.levels


def test_value_symmetry_orbit_counts():
    """Value permutations (TLC Permutations(Value)) quotient further:
    values enter only through ClientRequest and flow inertly, so
    Server x Value orbits < Server orbits < raw states, same diameter."""
    bp = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2)

    def run(axes):
        return refbfs.check(CheckConfig(bounds=bp, spec="full",
                                        invariants=(), symmetry=axes))
    base, s_only, v_only, sv = (run(()), run(("Server",)), run(("Value",)),
                                run(("Server", "Value")))
    assert base.n_states == 74897
    assert (s_only.n_states, v_only.n_states, sv.n_states) == \
        (37472, 50515, 25281)
    assert base.diameter == s_only.diameter == v_only.diameter == sv.diameter


def test_value_symmetry_engine_parity():
    from raft_tla_tpu import engine
    bp = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2)
    cfg = CheckConfig(bounds=bp, spec="full", invariants=("NoTwoLeaders",),
                      symmetry=("Server", "Value"), chunk=512)
    ref = refbfs.check(cfg)
    got = engine.check(cfg)
    assert (got.n_states, got.diameter) == (ref.n_states, ref.diameter)
    assert got.coverage == ref.coverage and got.violation is None


def test_value_symmetry_faithful_mode():
    """Rank-table remaps + bitwise allLogs permutation: faithful spaces
    quotient under Server x Value too, engines in exact agreement."""
    from raft_tla_tpu import engine
    bh = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2,
                history=True, max_elections=4)
    cf = CheckConfig(bounds=bh, spec="full",
                     invariants=("NoTwoLeaders", "ElectionSafetyHist"),
                     symmetry=("Server", "Value"), chunk=512)
    ref = refbfs.check(cf)
    got = engine.check(cf)
    assert (ref.n_states, ref.diameter) == (28121, 32)  # of 84572 states
    assert (got.n_states, got.diameter) == (28121, 32)
    assert ref.violation is None and got.violation is None


_B3S = Bounds(n_servers=3, n_values=2, max_term=2, max_log=1, max_msgs=2)
_BH2 = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2,
              history=True, max_elections=4)
# the benchmark's 5-server bounds (benchmark/configs/elect5.json, full5.json)
_ELECT5 = Bounds(n_servers=5, n_values=2, max_term=2, max_log=0, max_msgs=2,
                 max_dup=1)
_FULL5 = Bounds(n_servers=5, n_values=2, max_term=2, max_log=1, max_msgs=2,
                max_dup=1)
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


@pytest.mark.parametrize("case", list(_SCAN_CASES))
def test_scan_orbit_fp_bit_identical_to_loop(case):
    """The scan-compiled orbit pass (build_orbit_fp — ONE transform
    iterated over the group, keying each image from its fields) must
    produce bit-identical (hi, lo) keys to the reference unrolled loop
    (orbit_fingerprint, which packs the row): checkpointed runs resume
    across the upgrade only if the keys are unchanged.  Under a VIEW the
    scan sees the device view of the struct and the loop the host view
    of the state."""
    import jax
    import jax.numpy as jnp
    from raft_tla_tpu.models import views
    from raft_tla_tpu.ops import fingerprint as fpr
    from raft_tla_tpu.ops import state as st

    bounds, axes, view, make, at_least = _SCAN_CASES[case]
    lay = st.Layout.of(bounds)
    consts = fpr.lane_constants(lay.width)
    seen = make()
    assert len(seen) >= at_least, len(seen)
    vecs = seen if isinstance(seen, np.ndarray) \
        else np.stack([interp.to_vec(s, bounds) for s in seen])
    structs = jax.vmap(lambda v: st.unpack(v, lay, jnp))(jnp.asarray(vecs))
    if view:
        structs = jax.vmap(views.jnp_view(view, bounds))(structs)
        host_view = views.py_view(view)
        raw = vecs
        vecs = np.stack([interp.to_vec(host_view(s, bounds), bounds)
                         for s in seen])
        assert (vecs != raw).any(axis=1).sum() >= len(seen) // 2
    faithful = "allLogs" in lay.shapes
    fn = sym.build_orbit_fp(bounds, axes, jnp.asarray(consts), faithful)
    hi_s, lo_s = (np.asarray(a) for a in jax.jit(fn)(structs))
    for k, s in enumerate(seen):
        struct = st.unpack(vecs[k], lay, np)
        hi_l, lo_l = sym.orbit_fingerprint(struct, bounds, consts, np, axes)
        assert (int(hi_s[k]), int(lo_s[k])) == (int(hi_l), int(lo_l)), \
            (axes, k, s)
    if case == "3s-all-distinct":
        # the case can see: the identity's image is not the orbit's min
        ih, il = fpr.fingerprint(vecs, consts, np)
        assert (int(ih[0]), int(il[0])) != (int(hi_s[0]), int(lo_s[0]))
    if case == "full5-stale-slots":
        # a stale slot is no message: every row keys as the clean one
        assert len({(int(h), int(l)) for h, l in zip(hi_s, lo_s)}) == 1


@pytest.mark.parametrize("name, bounds, n_perms", [
    ("3s", _B3S, None), ("elect5", _ELECT5, 20), ("full5", _FULL5, 20)])
def test_key_table_and_ranked_bag_equal_the_permuted_packed_row(
        name, bounds, n_perms):
    """The algebra of the scan's body, in NumPy alone (PR 29): for a
    server permutation ``p``, ``features(s) . table[p]`` plus the ranked
    bag's sum, finalised, is
    ``fingerprint(pack(canonicalize(permute_struct(s, p))))`` on both
    lanes — no field of ``s`` moved.  Every ``p`` at 3 servers, a sample
    at 5 (the identity and the reversal among them), on random bounded
    states: ``votedFor`` set, vote masks non-empty, index matrices and
    logs non-trivial, bags of up to three slots."""
    from raft_tla_tpu.ops import fingerprint as fpr

    lay = st.Layout.of(bounds)
    consts = fpr.lane_constants(lay.width)
    vecs = np.stack([interp.to_vec(s, bounds)
                     for s in _random_states(bounds, 40, seed=len(name))])
    batch = st.unpack(vecs, lay, np)
    assert (batch["votedFor"] > 0).any() and (batch["vGrant"] > 0).any()
    assert ((batch["msgCount"] > 0).sum(axis=1) == lay.S).any()
    assert len(np.unique(batch["nextIndex"])) > 1
    perms = sym.permutations(bounds)
    if n_perms is None:
        picked = range(len(perms))
    else:
        rng = np.random.default_rng(29)
        picked = sorted({0, len(perms) - 1,
                         *rng.choice(len(perms), n_perms, replace=False)})
    assert perms[0] == tuple(range(bounds.n_servers))
    assert perms[-1] == tuple(reversed(range(bounds.n_servers)))
    fields = sym._linear_fields(("Server",))
    phi = sym._key_features(batch, fields, np)
    assert phi.dtype == np.int8 and phi.shape[1] == len(vecs)
    assert 0 <= phi.min() and phi.max() <= sym._feature_cap(bounds, fields)
    table = sym._key_table(bounds, consts, fields,
                           tuple(perms[i] for i in picked))
    assert table.shape == (len(picked), 2, phi.shape[0])
    # the limb table the device multiplies: the same two sums, all of the
    # picked permutations in one product
    limbs = sym._key_limbs(table)
    assert limbs.dtype == np.int8 \
        and limbs.shape == (len(picked), 2, sym._N_LIMBS, phi.shape[0])
    limb_sums = sym._limb_sums(limbs, phi, np)
    assert limb_sums.dtype == np.uint32
    luts = sym._server_luts(bounds)
    fc = fpr.field_constants(lay.shapes, consts)
    cbag = np.stack([fc[f] for f in sym._BAG], axis=1)
    slots = {f: [batch[f][:, s] for s in range(lay.S)] for f in sym._BAG}
    for at, (row, i) in enumerate(zip(table, picked)):
        s1, s2 = sym._linear_sums(phi, row, np)
        assert (limb_sums[at, 0] == s1).all()
        assert (limb_sums[at, 1] == s2).all()
        hi = [sym._relabel_hi(w, luts["src"][i], luts["dst"][i], np)
              for w in slots["msgHi"]]
        b1, b2 = sym._bag_sums(hi, slots["msgLo"], slots["msgCount"], cbag,
                               np)
        got = fpr.finalise(s1 + b1, s2 + b2, np)
        for k in range(len(vecs)):
            image = st.canonicalize(sym.permute_struct(
                st.unpack(vecs[k], lay, np), perms[i], bounds, np), np)
            want = fpr.fingerprint(st.pack(image, np), consts, np)
            assert (int(got[0][k]), int(got[1][k])) \
                == (int(want[0]), int(want[1])), (perms[i], k)


# F = 4n + 2nL + 5n^2 of the benchmark's configurations and of six servers
_LIMB_SHAPES = {"flagship3": (3, 2), "elect5": (5, 1), "full5": (5, 2),
                "six-servers": (6, 2)}


@pytest.mark.parametrize("constants", ["all-ones", "top-bit", "zero",
                                       "random"])
@pytest.mark.parametrize("name", list(_LIMB_SHAPES))
def test_limb_sums_equal_linear_sums_at_the_extremes(name, constants):
    """The device's form of the linear sums, in NumPy alone: the table of
    permuted constants as four balanced base-256 digits (``int8``), one
    int32 matrix product with the features, the digits shifted home and
    added in uint32 — the same word, on every bit, as ``_linear_sums``'
    multiply-reduce in uint32.  At the extremes: every feature at the cap
    ``config.Bounds`` allows (63) and at the most an ``int8`` holds
    (127), constants whose digits all carry (0xFFFFFFFF), whose top digit
    is the one negative one (0x80000000), zero and random."""
    n, L = _LIMB_SHAPES[name]
    F = 4 * n + 2 * n * L + 5 * n * n
    rng = np.random.default_rng(F)
    table = {"all-ones": np.full((3, 2, F), 0xFFFFFFFF, np.uint32),
             "top-bit": np.full((3, 2, F), 0x80000000, np.uint32),
             "zero": np.zeros((3, 2, F), np.uint32),
             "random": rng.integers(0, 2**32, (3, 2, F), dtype=np.uint32),
             }[constants]
    limbs = sym._key_limbs(table)
    assert limbs.dtype == np.int8 and limbs.shape == (3, 2, 4, F)
    # the digits are the constant (mod 2^32)
    back = sum(limbs[:, :, l].astype(np.int64) << (8 * l) for l in range(4))
    assert ((back % 2**32).astype(np.uint32) == table).all()
    lanes = 64
    for cap in (63, 127):
        sym._check_limb_range(F, cap)
        for phi in (np.full((F, lanes), cap, np.int8),
                    rng.integers(0, cap + 1, (F, lanes)).astype(np.int8)):
            got = sym._limb_sums(limbs, phi, np)
            assert got.dtype == np.uint32 and got.shape == (3, 2, lanes)
            for p in range(3):
                want = sym._linear_sums(phi, table[p], np)
                assert (got[p, 0] == want[0]).all(), (name, constants, cap)
                assert (got[p, 1] == want[1]).all(), (name, constants, cap)


def test_limb_range_check_refuses_what_would_not_be_exact():
    """``build_orbit_fp`` checks once, at build time, that the product is
    exact: a feature past 127 does not fit the ``int8`` operand, and F
    features at the cap times a digit of 128 must stay inside ``int32``.
    The schemas in the tree are far inside both (full5: 165 features
    capped at 3)."""
    for bounds in (_B3S, _ELECT5, _FULL5):
        cap = sym._feature_cap(bounds, sym._linear_fields(("Server",)))
        assert cap == max(bounds.term_cap, bounds.log_cap + 1,
                          bounds.n_values)
        n, L = bounds.n_servers, bounds.log_cap
        sym._check_limb_range(4 * n + 2 * n * L + 5 * n * n, cap)
    sym._check_limb_range(165, 127)
    with pytest.raises(ValueError, match="int8"):
        sym._check_limb_range(165, 128)
    most = (2**31 - 1) // (63 * 128)              # 266,305 features
    sym._check_limb_range(most, 63)
    with pytest.raises(ValueError, match="int32"):
        sym._check_limb_range(most + 1, 63)


def _lowered_scan(bounds, lanes, axes=("Server",)):
    """``build_orbit_fp`` lowered for ``lanes`` states of ``bounds``: the
    StableHLO text and the struct's shapes."""
    import jax
    import jax.numpy as jnp
    from raft_tla_tpu.ops import fingerprint as fpr

    lay = st.Layout.of(bounds)
    consts = jnp.asarray(fpr.lane_constants(lay.width))
    struct = {f: jax.ShapeDtypeStruct((lanes,) + tuple(shape), jnp.int32)
              for f, shape in lay.shapes.items()}
    fn = sym.build_orbit_fp(bounds, axes, consts, False)
    return jax.jit(fn).lower(struct).as_text(), struct


def _dot_generals(text):
    """(lhs, rhs, result) tensor types of every ``stablehlo.dot_general``."""
    import re
    return re.findall(
        r"stablehlo\.dot_general .*: \(tensor<([^>]*)>, tensor<([^>]*)>\)"
        r" -> tensor<([^>]*)>", text)


def test_scan_body_builds_no_packed_row():
    """The row must not come back: lowered at 5 servers, the orbit scan
    holds no ``[lanes, W]`` tensor at all, so no ``concatenate`` (nor
    ``reshape``) of one in its loop body — PR 27 took it out of the body,
    where it was written to HBM and read back 120 times a chunk step.
    The packed form of the same states does show it (the test can see)."""
    import re

    import jax
    import jax.numpy as jnp

    lanes = 24
    for bounds in (_ELECT5, _FULL5):
        lay = st.Layout.of(bounds)
        row = re.compile(rf"tensor<{lanes}x{lay.width}xu?i32>")
        text, struct = _lowered_scan(bounds, lanes)
        assert "stablehlo.while" in text
        assert len(_dot_generals(text)) == 1
        assert not row.search(text), row.pattern
        packed = jax.jit(jax.vmap(lambda s: st.pack(s, jnp))) \
            .lower(struct).as_text()
        assert re.search(r"stablehlo\.concatenate.*" + row.pattern, packed)


def test_scan_moves_no_state_data():
    """Nor may the state move (PR 29): lowered at 5 servers under Server
    symmetry, the orbit scan — its loop over blocks of permutations and
    what is hoisted in front of it — holds no ``gather`` (PR 29's parent
    regathered every ``[lanes, n]`` / ``[lanes, n, n]`` field by the
    inverse permutation, once an image), no ``scatter`` /
    ``dynamic_update_slice`` and no ``sort`` (the message sort network's
    ``.at[..., i].set`` lowers to scatters, 21 ``dynamic-update-slice``
    an image in the chip's program).  Since PR 42 the linear fields'
    share of a block of eight images' keys is **one** ``dot_general``:
    the block's ``int8`` limbs ``[8 * 2 * 4, F]`` times the ``int8``
    feature matrix ``[F, lanes]``, accumulated in ``int32`` — no
    multiply-reduce an image is left — and the rest of an image is a
    ranking of the bag; the loop over the fifteen blocks is the one
    ``stablehlo.while``.  The forms that do move data show all of it (the
    test can see): ``canonicalize`` lowered alone, and the same scan
    under Value symmetry, where ``logVal`` keeps the data-moving path."""
    import re

    import jax
    import jax.numpy as jnp

    assert sym._block_perms(120, 4096 * 84) == 8      # full5's dense step
    assert sym._block_perms(6, 4096 * 42) == 6        # flagship3's
    assert sym._block_perms(720, 1 << 15) == 8
    # a product that would pass _PRODUCT_BYTES takes fewer permutations
    assert sym._block_perms(120, 1 << 20) == 3
    assert sym._block_perms(120, 1 << 24) == 1
    for bounds, lanes in ((_ELECT5, 24), (_FULL5, 24), (_FULL5, 4096 * 84)):
        lay = st.Layout.of(bounds)
        n, L = lay.n, lay.L
        text, struct = _lowered_scan(bounds, lanes)
        assert text.count("stablehlo.while") == 1
        for op in ("gather", "dynamic_update_slice", "stablehlo.sort",
                   "scatter"):
            assert op not in text, op
        # the one array that follows the lanes into the product is the
        # byte-wide feature matrix, lanes minor
        F = 4 * n + 2 * n * L + 5 * n * n
        assert f"tensor<{F}x{lanes}xi8>" in text
        assert "xui8>" not in text
        assert _dot_generals(text) == [
            (f"64x{F}xi8", f"{F}x{lanes}xi8", f"64x{lanes}xi32")]
        # no multiply-reduce over the features is left: nothing but the
        # product's operand holds both F and the lanes
        assert not re.search(rf"tensor<{F}x{lanes}xu?i32>", text)
        if lanes > 24:
            continue
        moved = jax.jit(jax.vmap(lambda s: st.canonicalize(s, jnp))) \
            .lower(struct).as_text()
        assert "stablehlo.scatter" in moved
        valued, _ = _lowered_scan(bounds, lanes, ("Server", "Value"))
        assert re.search(
            rf"stablehlo\.gather.*tensor<{lanes}x{n}x{L}xi32>", valued)
