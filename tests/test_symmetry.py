"""Server-permutation symmetry reduction (TLC SYMMETRY analog).

Correctness anchors: the orbit key is permutation-invariant; the
symmetry-reduced oracle count equals the brute-force orbit count of the
full space; the scan-compiled orbit pass keys every state as the unrolled
loop does.  The engines under SYMMETRY are held to the reduced oracle in
``test_symmetry_engines.py``.
"""

import itertools

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.models import interp, refbfs, spec as S
from raft_tla_tpu.ops import msgbits as mb
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops import symmetry as sym
from symmetry_cases import (
    _BH3, _BH3W, _ELECT5, _FULL5, _B3S, _SCAN_CASES, B2, B3, _random_states,
    _tied_record_states)


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


def test_too_many_servers_is_loud():
    with pytest.raises(ValueError, match="symmetry"):
        sym.permutations(Bounds(n_servers=7, n_values=1, max_term=2,
                                max_log=0, max_msgs=1))


@pytest.mark.parametrize("axes, orbits", [
    ((), 74897), (("Server",), 37472), (("Value",), 50515),
    (("Server", "Value"), 25281)])
def test_value_symmetry_orbit_counts(axes, orbits):
    """Value permutations (TLC Permutations(Value)) quotient further:
    values enter only through ClientRequest and flow inertly, so
    Server x Value orbits < Server orbits < raw states, same diameter."""
    bp = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2)
    got = refbfs.check(CheckConfig(bounds=bp, spec="full", invariants=(),
                                   symmetry=axes))
    assert (got.n_states, got.diameter) == (orbits, 32)


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
    if case == "3s-faithful-stale-election-slots":
        # canonicalize_elections zeroes nothing: a stale record's words
        # join the loop's key, and so the scan's (the case can see);
        # where the slot stood does not
        keys = [(int(h), int(l)) for h, l in zip(hi_s, lo_s)]
        assert keys[1] == keys[2] and len(set(keys)) == 3


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


@pytest.mark.parametrize("name, bounds, n_words", [
    ("faithful3", _BH3, 1), ("wide-ranks", _BH3W, 2)])
def test_faithful_key_parts_equal_the_permuted_packed_row(
        name, bounds, n_words):
    """The same algebra with the history in it (PR 51), in NumPy alone:
    for every server permutation ``p`` at three servers, ``features .
    table[p]`` (``vLog`` among the features, as two base-128 digits where
    the log universe has more than 127 ranks) plus the ranked bag, plus
    the ``elections`` records relabelled and ordered as packed keys, plus
    ``allLogs``' sum taken once, finalised, is
    ``fingerprint(pack(canonicalize(permute_struct(s, p))))`` on both
    lanes — no field of ``s`` moved.  On random bounded states and on
    states whose records tie on their leading key parts."""
    from raft_tla_tpu.ops import fingerprint as fpr

    lay = st.Layout.of(bounds)
    consts = fpr.lane_constants(lay.width)
    states = _random_states(bounds, 30, seed=51) \
        + _tied_record_states(bounds, seed=52)[:30]
    vecs = np.stack([interp.to_vec(s, bounds) for s in states])
    batch = st.unpack(vecs, lay, np)
    assert ((batch["eTerm"] > 0).sum(axis=1) == lay.E).any()
    assert (batch["vLog"] > 0).any() and (batch["allLogs"] != 0).any()
    forms = sym.scan_forms(bounds, ("Server",))
    assert forms["moved"] == () and forms["once"] == ("allLogs",)
    assert sorted(f for fs in forms.values() for f in fs) \
        == sorted(lay.fields)
    fields = forms["table"]
    assert fields == sym._linear_fields(("Server",), True) \
        and fields[-1] == "vLog"
    wide = sym._wide_fields(bounds, fields)
    assert wide == (("vLog",) if n_words == 2 else ())
    assert (batch["vLog"].max() > 127) == bool(wide)
    phi = sym._key_features(batch, fields, np, wide)
    assert phi.dtype == np.int8
    assert 0 <= phi.min() and phi.max() <= sym._feature_cap(bounds, fields)
    perms = sym.permutations(bounds)
    table = sym._key_table(bounds, consts, fields, perms)
    assert table.shape == (6, 2, phi.shape[0])
    sym._check_limb_range(table.shape[-1], sym._feature_cap(bounds, fields))
    limb_sums = sym._limb_sums(sym._key_limbs(table), phi, np)
    plan = sym._election_key_plan(bounds)
    assert plan[1] == n_words
    luts = {**sym._server_luts(bounds), **sym._election_luts(bounds, plan)}
    fc = fpr.field_constants(lay.shapes, consts)
    cbag = np.stack([fc[f] for f in sym._BAG], axis=1)
    slots = {f: [batch[f][:, s] for s in range(lay.S)] for f in sym._BAG}
    recs = sym._election_records(batch, plan, np)
    once = fpr.field_sums(batch, consts, np, forms["once"])
    for i, p in enumerate(perms):
        s1, s2 = sym._linear_sums(phi, table[i], np)
        assert (limb_sums[i, 0] == s1).all() and (limb_sums[i, 1] == s2).all()
        hi = [sym._relabel_hi(w, luts["src"][i], luts["dst"][i], np)
              for w in slots["msgHi"]]
        b1, b2 = sym._bag_sums(hi, slots["msgLo"], slots["msgCount"], cbag,
                               np)
        e1, e2 = sym._election_sums(
            recs, {k: v[i] for k, v in luts.items()}, plan, fc, np)
        got = fpr.finalise(s1 + b1 + e1 + once[0], s2 + b2 + e2 + once[1],
                           np)
        for k in range(len(vecs)):
            image = st.canonicalize(sym.permute_struct(
                st.unpack(vecs[k], lay, np), p, bounds, np), np)
            want = fpr.fingerprint(st.pack(image, np), consts, np)
            assert (int(got[0][k]), int(got[1][k])) \
                == (int(want[0]), int(want[1])), (p, k)


def test_scan_forms_say_what_each_symmetry_still_moves():
    """``build_orbit_fp`` chooses each field's form from the SYMMETRY axes
    and the layout alone and says so on the function it returns: nothing
    is moved under Server symmetry, with or without the history;
    ``logVal`` alone under Value symmetry in parity mode (``repl3``); and
    with a value permutation over the history its log ranks relabel, so
    the history keeps the data-moving path."""
    from raft_tla_tpu.ops import fingerprint as fpr
    import jax.numpy as jnp

    hist = st.HISTORY_FIELDS
    for bounds, axes, moved, once in (
            (_B3S, ("Server",), (), ()),
            (_B3S, ("Value",), ("logVal",), ()),
            (_B3S, ("Server", "Value"), ("logVal",), ()),
            (_BH3, ("Server",), (), ("allLogs",)),
            (_BH3, ("Value",), ("logVal",) + hist, ()),
            (_BH3, ("Server", "Value"), ("logVal",) + hist, ())):
        lay = st.Layout.of(bounds)
        fn = sym.build_orbit_fp(
            bounds, axes, jnp.asarray(fpr.lane_constants(lay.width)),
            lay.history)
        assert fn.forms == sym.scan_forms(bounds, axes)
        assert fn.forms["moved"] == moved and fn.forms["once"] == once
        assert ("vLog" in fn.forms["table"]) \
            == ("eTerm" in fn.forms["ranked"]) == (once != ())
        assert sorted(f for fs in fn.forms.values() for f in fs) \
            == sorted(lay.fields)


def test_a_key_part_the_limbs_cannot_carry_is_refused_by_name():
    """Features are signed bytes: ``_check_limb_range`` refuses a cap past
    127, and the wide digits are what keeps ``vLog`` under it at any
    universe ``Bounds`` admits (1,024 ranks: a high digit of 8)."""
    assert sym._feature_cap(_BH3W, ("vLog", "role")) == 127
    assert sym._word_caps(_BH3W, ("vLog",)) == {"vLog": 259}
    assert sym._word_caps(_BH3, ("vLog", "term")) == {"vLog": 43, "term": 3}
    with pytest.raises(ValueError, match="int8"):
        sym._check_limb_range(10, 128)


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
    fn = sym.build_orbit_fp(bounds, axes, consts, lay.history)
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


@pytest.mark.parametrize("bounds", [_BH3, _BH3W], ids=["faithful3", "wide"])
def test_faithful_server_scan_moves_no_history(bounds):
    """PR 51: in faithful mode under Server symmetry alone the scan holds
    what the parity scan holds — one ``while`` over the blocks, one
    ``dot_general``, no ``gather``, no ``scatter`` /
    ``dynamic_update_slice`` (the ``elections`` sort network's
    ``.at[..., i].set``), no ``sort`` — with ``vLog``'s ``n * n`` words
    among the byte-wide features (twice where a rank passes 127: two
    digits).  The same bounds under Server x Value keep the data-moving
    path and show all of it (the test can see)."""
    lanes = 24
    lay = st.Layout.of(bounds)
    n, L = lay.n, lay.L
    text, _struct = _lowered_scan(bounds, lanes)
    assert text.count("stablehlo.while") == 1
    for op in ("gather", "dynamic_update_slice", "stablehlo.sort", "scatter"):
        assert op not in text, op
    wide = bool(sym._wide_fields(bounds, ("vLog",)))
    F = 4 * n + 2 * n * L + 5 * n * n + (1 + wide) * n * n
    assert _dot_generals(text) == [
        (f"48x{F}xi8", f"{F}x{lanes}xi8", f"48x{lanes}xi32")]
    valued, _ = _lowered_scan(bounds, lanes, ("Server", "Value"))
    assert valued.count("stablehlo.while") > 1
    assert "stablehlo.gather" in valued and "stablehlo.scatter" in valued
