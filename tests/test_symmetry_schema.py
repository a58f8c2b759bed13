"""Symmetric sorts declared by a frontend schema (``frontend/schema.Sort`` /
``Over``) and the orbit key ``ops/symmetry`` builds from the declaration: the
plain loop that is the definition (permute, pack, fingerprint, least), and the
device form (``int8`` features, the table of permuted constants in limbs, one
product a block of images) held to it bit for bit, on seeded random Paxos
structs at three and five acceptors.  Raft's scan shares the limb, block and
least-key functions; its own pins are ``tests/test_symmetry*.py``, untouched.
"""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.frontend import paxos as px
from raft_tla_tpu.frontend import twophase
from raft_tla_tpu.frontend.schema import (Const, Field, Over, Schema, Sort,
                                          _resolve, check_schema)
from raft_tla_tpu.ops import fingerprint as fpr
from raft_tla_tpu.ops import symmetry as sym

BOTH = ("Acceptor", "Value")
PAIRS = ((1, 1, 0), (1, 0, 1), (0, 1, 1))


def bounds(n: int, max_ballot: int = 2, quorum=None) -> Bounds:
    rows = quorum or tuple(
        tuple(int(a in q) for a in range(n))
        for q in itertools.combinations(range(n), n // 2 + 1))
    return Bounds(n_servers=n, n_values=2, max_term=max_ballot,
                  constants=(("Quorum", rows),))


def random_structs(b: Bounds, count: int, seed: int) -> dict:
    """Seeded structs over the declared ranges (no reachable state looks so;
    the key is a function of the words)."""
    lay = px.SCHEMA.layout(b)
    rng = np.random.default_rng(seed)
    return {f.name: rng.integers(
        f.lo, _resolve(f.hi, b) + 1,
        size=(count,) + lay.shapes[f.name]).astype(np.int32)
        for f in px.SCHEMA.fields}


@functools.lru_cache(maxsize=None)
def _device_fp(n: int, sorts: tuple):
    b = bounds(n)
    consts = fpr.lane_constants(px.SCHEMA.layout(b).width)
    return jax.jit(sym.build_schema_orbit_fp(px.SCHEMA, b, sorts, consts))


# --------------------------------------------------------- the declaration

def test_an_over_moves_the_member_and_keeps_the_fixed_and_the_outer():
    assert list(Over("Value").image((1, 0), 2)) == [1, 0]
    # maxVal: 0 = None stays, 1 + v relabels
    assert list(Over("Value", fixed=1).image((1, 0), 3)) == [0, 2, 1]
    # the "1b" pair axis at ballots 0..1: k = 1 + mbal * 2 + v
    assert list(Over("Value", fixed=1).image((1, 0), 5)) == [0, 2, 1, 4, 3]
    assert list(Over("Acceptor").image((2, 0, 1), 3)) == [2, 0, 1]
    with pytest.raises(ValueError, match="whole copies of the 2 members"):
        Over("Value", fixed=1).image((1, 0), 4)


def test_paxos_declares_its_two_sorts_and_twophase_none():
    assert px.SCHEMA.sort_names == BOTH
    assert px.SCHEMA.sorts == (Sort("Acceptor", "n"), Sort("Value", "V"))
    assert twophase.SCHEMA.sorts == () and twophase.SCHEMA.sort_names == ()
    by = {f.name: f for f in px.SCHEMA.fields}
    assert by["maxVal"].content == Over("Value", fixed=1)
    assert by["msg1b"].overs() == ((0, Over("Acceptor")),
                                   (2, Over("Value", fixed=1)))
    assert by["msg1a"].overs() == () and by["msg1a"].content is None
    assert px.SCHEMA.consts[0].overs() == ((1, Over("Acceptor")),)
    for n in (3, 5):
        assert check_schema(px.SCHEMA, bounds(n)) == []


def test_a_schema_that_misdeclares_a_sort_is_refused_by_name():
    with pytest.raises(ValueError, match="follows the sort 'Value', which "
                                         "the schema does not declare"):
        Schema("bad", (Field("x", ("n",), 0, 1, axes=(Over("Value"),)),),
               sorts=(Sort("Acceptor", "n"),))
    with pytest.raises(ValueError, match="names 2 axes, its shape has 1"):
        Schema("bad", (Field("x", ("n",), 0, 1,
                             axes=(Over("Acceptor"), None)),),
               sorts=(Sort("Acceptor", "n"),))
    with pytest.raises(ValueError, match="follows the sort 'Value'"):
        Schema("bad", (Field("x", ("n",), 0, 2,
                             content=Over("Value", fixed=1)),))
    # contents that do not hold whole copies of the sort: a lint finding
    odd = Schema("odd", (Field("x", ("n",), 0, 3, axes=(Over("Acceptor"),),
                               content=Over("Value", fixed=1)),),
                 sorts=(Sort("Acceptor", "n"), Sort("Value", "V")))
    (finding,) = check_schema(odd, bounds(3))
    assert finding.code == "schema-sort-span" and finding.field == "x"


def test_a_constant_table_a_permutation_moves_is_found():
    assert px.SCHEMA.variant_const(bounds(3), BOTH) is None
    assert px.SCHEMA.variant_const(bounds(5), BOTH) is None
    lopsided = bounds(3, quorum=((1, 1, 0), (1, 0, 1)))
    assert px.SCHEMA.variant_const(lopsided, BOTH) == ("Quorum", "Acceptor")
    assert px.SCHEMA.variant_const(lopsided, ("Value",)) is None
    # a table with no free-length axis is compared as it lies
    fixed = Schema("t", (Field("x", ("n",), 0, 1, axes=(Over("Acceptor"),)),),
                   consts=(Const("W", ("n",), 0, 9,
                                 axes=(Over("Acceptor"),)),),
                   sorts=(Sort("Acceptor", "n"),))
    flat = Bounds(n_servers=3, constants=(("W", (4, 4, 4)),))
    skew = Bounds(n_servers=3, constants=(("W", (4, 4, 5)),))
    assert fixed.variant_const(flat, ("Acceptor",)) is None
    assert fixed.variant_const(skew, ("Acceptor",)) == ("W", "Acceptor")


# ----------------------------------------------------------------- the group

@pytest.mark.parametrize("n, sorts, order", [
    (3, BOTH, 12), (5, BOTH, 240), (5, ("Acceptor",), 120),
    (5, ("Value",), 2), (3, (), 1)])
def test_the_group_is_the_product_of_the_named_sorts(n, sorts, order):
    group = sym.schema_group(px.SCHEMA, bounds(n), sorts)
    assert len(group) == order == len({tuple(sorted(g.items()))
                                       for g in group})
    assert all(set(g) == set(sorts) for g in group)
    assert all(p == tuple(range(len(p))) for p in group[0].values())


def test_an_undeclared_sort_and_an_oversize_one_are_refused():
    with pytest.raises(ValueError, match="declares no symmetric sort "
                                         "'Server'"):
        sym.schema_group(px.SCHEMA, bounds(3), ("Server",))
    with pytest.raises(ValueError, match="declares no symmetric sort 'RM' "
                                         r"\(declared: none\)"):
        sym.schema_group(twophase.SCHEMA, Bounds(n_servers=3), ("RM",))
    with pytest.raises(ValueError, match="at most 6 members"):
        sym.schema_group(px.SCHEMA, bounds(7), ("Acceptor",))


def test_permuting_a_struct_is_the_programs_state_renamed():
    """``permute_schema_struct`` on a packed Paxos state is the state with
    its acceptors and values renamed, message by message."""
    b = bounds(3, 1)
    lay = px.SCHEMA.layout(b)
    s = px.PaxosState(
        maxBal=(1, 0, -1), maxVBal=(0, -1, -1), maxVal=(1, None, None),
        msgs=frozenset({("1a", 0), ("1a", 1), ("1b", 0, 1, 0, 1),
                        ("1b", 1, 0, -1, None), ("2a", 0, 1),
                        ("2b", 0, 0, 1)}))
    g = {"Acceptor": (2, 0, 1), "Value": (1, 0)}
    img = sym.permute_schema_struct(
        lay.unpack(px.to_vec(s, b), np), lay, g, np)
    got = px.from_vec(lay.pack(img, np), b)
    assert got == px.PaxosState(
        maxBal=(0, -1, 1), maxVBal=(-1, -1, 0), maxVal=(None, None, 0),
        msgs=frozenset({("1a", 0), ("1a", 1), ("1b", 2, 1, 0, 0),
                        ("1b", 0, 0, -1, None), ("2a", 0, 0),
                        ("2b", 2, 0, 0)}))
    # the identity moves nothing; a batch permutes row by row
    ident = sym.schema_group(px.SCHEMA, b, BOTH)[0]
    batch = random_structs(b, 5, 3)
    same = sym.permute_schema_struct(batch, lay, ident, np)
    assert all((same[k] == batch[k]).all() for k in batch)
    moved = sym.permute_schema_struct(batch, lay, g, np)
    one = sym.permute_schema_struct({k: v[3] for k, v in batch.items()},
                                    lay, g, np)
    assert all((moved[k][3] == one[k]).all() for k in batch)


# ------------------------------------------------------------------ the key

@pytest.mark.parametrize("n, sorts", [
    (3, BOTH), (5, BOTH), (5, ("Acceptor",)), (3, ("Value",))])
def test_the_device_form_is_the_plain_loop_bit_for_bit(n, sorts):
    b = bounds(n)
    lay = px.SCHEMA.layout(b)
    consts = fpr.lane_constants(lay.width)
    structs = random_structs(b, 384, seed=100 + n)
    want = sym.schema_orbit_fingerprint(structs, lay, consts, sorts, np)
    got = _device_fp(n, sorts)({k: jnp.asarray(v)
                                for k, v in structs.items()})
    assert (np.asarray(got[0]) == want[0]).all()
    assert (np.asarray(got[1]) == want[1]).all()
    # ... and the loop in jnp is the loop in numpy
    again = sym.schema_orbit_fingerprint(
        {k: jnp.asarray(v[:16]) for k, v in structs.items()}, lay,
        jnp.asarray(consts), sorts, jnp)
    assert (np.asarray(again[0]) == want[0][:16]).all()
    assert (np.asarray(again[1]) == want[1][:16]).all()


def test_the_table_times_the_features_is_the_sum_before_the_finaliser():
    """The algebra the device form rests on, stated plainly: for every group
    element, ``features . table[g]`` is the sum of the permuted, packed row
    times the lane constants."""
    b = bounds(3, 1)
    lay = px.SCHEMA.layout(b)
    consts = fpr.lane_constants(lay.width)
    group = sym.schema_group(px.SCHEMA, b, BOTH)
    table = sym._schema_key_table(lay, consts, group)
    structs = random_structs(b, 32, seed=9)
    phi = sym._schema_features(structs, lay, np)
    assert phi.dtype == np.int8 and phi.shape == (table.shape[-1], 32)
    assert sym._schema_feature_cap(lay) == 2      # ballots 0..1 stored + 1
    for g, row in zip(group, table):
        vec = lay.pack(sym.permute_schema_struct(structs, lay, g, np), np)
        with np.errstate(over="ignore"):
            want = [np.sum(fpr.fold(vec, np) * consts[k].astype(np.uint32),
                           axis=-1, dtype=np.uint32) for k in (0, 1)]
        got = sym._linear_sums(phi, row, np)
        assert (got[0] == want[0]).all() and (got[1] == want[1]).all()
    # the limbs carry the same sums (the functions Raft's scan runs)
    sums = sym._limb_sums(sym._key_limbs(table[:4]), phi, np)
    for k in range(4):
        lin = sym._linear_sums(phi, table[k], np)
        assert (sums[k, 0] == lin[0]).all() and (sums[k, 1] == lin[1]).all()


def test_the_feature_count_is_a_flag_a_moved_word_and_a_content_value():
    lay = px.SCHEMA.layout(bounds(5))
    structs = random_structs(bounds(5), 2, seed=1)
    assert sym._schema_features(structs, lay, np).shape[0] == 164
    assert lay.width == 159


@pytest.mark.parametrize("n", [3, 5])
def test_the_key_is_the_same_for_every_member_of_an_orbit(n):
    b = bounds(n)
    lay = px.SCHEMA.layout(b)
    structs = {k: jnp.asarray(v)
               for k, v in random_structs(b, 8, seed=n).items()}
    fp = _device_fp(n, BOTH)
    h0, l0 = (np.asarray(x) for x in fp(structs))
    for g in sym.schema_group(px.SCHEMA, b, BOTH):
        h, l = fp(sym.permute_schema_struct(structs, lay, g, jnp))
        assert (np.asarray(h) == h0).all() and (np.asarray(l) == l0).all()


def test_a_thousand_pairs_of_different_orbits_have_different_keys():
    b = bounds(3)
    lay = px.SCHEMA.layout(b)
    left = random_structs(b, 1000, seed=21)
    right = random_structs(b, 1000, seed=22)
    group = sym.schema_group(px.SCHEMA, b, BOTH)
    # no right state is an image of its left one (row for row)
    rvec = lay.pack(right, np)
    for g in group:
        lvec = lay.pack(sym.permute_schema_struct(left, lay, g, np), np)
        assert (lvec != rvec).any(axis=1).all()
    fp = _device_fp(3, BOTH)
    kl = fp({k: jnp.asarray(v) for k, v in left.items()})
    kr = fp({k: jnp.asarray(v) for k, v in right.items()})
    same = (np.asarray(kl[0]) == np.asarray(kr[0])) \
        & (np.asarray(kl[1]) == np.asarray(kr[1]))
    assert not same.any()
    # and within one side, a key names one orbit: equal keys only for rows
    # that are images of each other (none, among random rows)
    keys = fpr.to_u64(np.asarray(kl[0]), np.asarray(kl[1]))
    assert len(set(keys.tolist())) == 1000


def test_a_field_the_linear_key_cannot_carry_is_refused():
    neg = Schema("neg", (Field("x", ("n",), -1, 3, axes=(Over("Acceptor"),)),),
                 sorts=(Sort("Acceptor", "n"),))
    b = Bounds(n_servers=3)
    with pytest.raises(ValueError, match="field 'x' of schema 'neg' may "
                                         "hold -1 < 0"):
        sym.build_schema_orbit_fp(neg, b, ("Acceptor",),
                                  fpr.lane_constants(3))
    wide = Schema("wide", (Field("x", ("n",), 0, 200,
                                 axes=(Over("Acceptor"),)),),
                  sorts=(Sort("Acceptor", "n"),))
    with pytest.raises(ValueError, match="must fit int8"):
        sym.build_schema_orbit_fp(wide, b, ("Acceptor",),
                                  fpr.lane_constants(3))


# ----------------------------------------------------------------- the step

def _text(step, b, debug_info=True) -> str:
    lay = px.SCHEMA.layout(b)
    return jax.jit(step).lower(
        jnp.zeros((4, lay.width), jnp.int32)).as_text(debug_info=debug_info)


def test_the_step_keys_by_orbit_where_it_keyed_plainly():
    from raft_tla_tpu.frontend import actions
    b = bounds(3, 1)
    tables = px.SCHEMA.bind_consts(b, b.constants)

    def build(sorts):
        return actions.build_schema_step(
            px.SCHEMA, px.ACTIONS, px.action_table(b), b,
            const_tables=tables, sorts=sorts)

    plain, reduced = _text(build(()), b), _text(build(BOTH), b)
    assert "/plain_fp/" in plain and "/orbit_scan/" not in plain
    assert "/orbit_scan/" in reduced and "/plain_fp/" not in reduced
    # with no sort the program is the one a schema step always was: the
    # argument's default, op for op
    default = actions.build_schema_step(
        px.SCHEMA, px.ACTIONS, px.action_table(b), b, const_tables=tables)
    assert _text(default, b, False) == _text(build(()), b, False)
    assert _text(default, b, False) != _text(build(BOTH), b, False)


def test_the_steps_keys_are_the_orbit_keys_of_its_successors():
    from raft_tla_tpu.frontend import actions
    b = bounds(3, 1)
    lay = px.SCHEMA.layout(b)
    consts = fpr.lane_constants(lay.width)
    step = jax.jit(actions.build_schema_step(
        px.SCHEMA, px.ACTIONS, px.action_table(b), b,
        const_tables=px.SCHEMA.bind_consts(b, b.constants), sorts=BOTH))
    s = px.PaxosState((0, -1, -1), (-1, -1, -1), (None, None, None),
                      frozenset({("1a", 0), ("1a", 1),
                                 ("1b", 0, 0, -1, None)}))
    out = step(jnp.asarray(px.to_vec(s, b))[None])
    valid = np.asarray(out["valid"])[0]
    svecs = np.asarray(out["svecs"])[0]
    want = sym.schema_orbit_fingerprint(lay.unpack(svecs, np), lay, consts,
                                        BOTH, np)
    hi, lo = np.asarray(out["fp_hi"])[0], np.asarray(out["fp_lo"])[0]
    assert valid.sum() >= 4
    assert (hi[valid] == want[0][valid]).all()
    assert (lo[valid] == want[1][valid]).all()
    assert (hi[~valid] == 0).all() and (lo[~valid] == 0).all()
    # Phase1b(a2, 0) and Phase1b(a3, 0) give states of one orbit: one key
    table = px.action_table(b)
    lanes = [k for k, inst in enumerate(table)
             if inst.family == "Phase1b" and inst.b == 0 and inst.a in (1, 2)]
    assert len(lanes) == 2 and valid[lanes].all()
    assert hi[lanes[0]] == hi[lanes[1]] and lo[lanes[0]] == lo[lanes[1]]
    assert (svecs[lanes[0]] != svecs[lanes[1]]).any()
