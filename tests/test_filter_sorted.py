"""``ddd_engine._filter_insert_ordered`` in key-sorted space (PR 35) against
the stage it replaced.

The stage now sorts once with the lane ids as payload, probes only the
live prefix of that sort in tiles of ``_T_PROBE`` positions, and brings the
streamed positions back to batch order with a second sort.  What it must
not change is everything another layer can see: the streamed candidates of
every batch, their order (``compact[:n_stream]``), and both filter tables,
bit for bit, after every batch.  ``_reference`` is the parent's function
(commit d2364da) frozen here as the plain reference: every lane probed, in
lane order.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import raft_tla_tpu.ddd_engine as ddd_mod
from raft_tla_tpu.ddd_engine import _EMPTY, _filter_insert_ordered

pytestmark = pytest.mark.smoke

I32 = jnp.int32
U32 = jnp.uint32
T = 64                    # the tile under test (the shipped one is 2^14)
S_INS = 96                # the insert budget under test: some cases pass it


def _lane_mask(tbl_hi, tbl_lo, key_hi, key_lo, active):
    """The streamed candidates as a mask in lane order, built from the
    compaction order (the wrapper ``ddd_engine._filter_insert`` did this for
    the mesh step until PR 48 gave that step the compaction order
    itself)."""
    BA = key_hi.shape[0]
    tbl_hi, tbl_lo, n_stream, compact, _ = _filter_insert_ordered(
        tbl_hi, tbl_lo, key_hi, key_lo, active)
    lane = jnp.where(jnp.arange(BA, dtype=I32) < n_stream, compact, BA)
    return tbl_hi, tbl_lo, \
        jnp.zeros((BA,), bool).at[lane].set(True, mode="drop")


def _reference(tbl_hi, tbl_lo, key_hi, key_lo, active, s_ins):
    """The parent's ``_filter_insert_ordered``, less its barrier."""
    BA = key_hi.shape[0]
    TB, Sb = tbl_hi.shape
    bmask = jnp.uint32(TB - 1)
    skh = jnp.where(active, key_hi, _EMPTY)
    skl = jnp.where(active, key_lo, _EMPTY)
    perm = jnp.lexsort((skl, skh))
    ph, pl, pa = key_hi[perm], key_lo[perm], active[perm]
    same_as_prev = jnp.concatenate([
        jnp.zeros((1,), bool),
        (ph[1:] == ph[:-1]) & (pl[1:] == pl[:-1]) & pa[1:] & pa[:-1]])
    first_of_key = jnp.zeros((BA,), bool).at[perm].set(~same_as_prev)
    probe = active & first_of_key
    bidx = (key_lo & bmask).astype(I32)
    row_hi, row_lo = tbl_hi[bidx], tbl_lo[bidx]
    seen = jnp.any((row_hi == key_hi[:, None])
                   & (row_lo == key_lo[:, None]), axis=1)
    stream = probe & ~seen
    slot_empty = (row_hi == _EMPTY) & (row_lo == _EMPTY)
    has_empty = jnp.any(slot_empty, axis=1)
    evict = (key_hi % jnp.uint32(Sb)).astype(I32)
    wslot = jnp.where(has_empty, jnp.argmax(slot_empty, axis=1), evict)
    S = min(s_ins, BA)
    compact = jnp.argsort(~stream, stable=True)
    sel = compact[:S]
    ok = stream[sel]
    wb = jnp.where(ok, bidx[sel], TB)
    ws = wslot[sel]
    lin = wb * Sb + ws
    order = jnp.argsort(lin, stable=True)
    dup = jnp.concatenate(
        [jnp.zeros((1,), bool), lin[order][1:] == lin[order][:-1]])
    wb = jnp.where(jnp.zeros((S,), bool).at[order].set(~dup), wb, TB)
    tbl_hi = tbl_hi.at[wb, ws].set(key_hi[sel], mode="drop")
    tbl_lo = tbl_lo.at[wb, ws].set(key_lo[sel], mode="drop")
    return tbl_hi, tbl_lo, stream, compact


def _keys(rng, n, pool=None, bucket_bits=None):
    """``n`` random key pairs; from a ``pool`` of that many distinct keys
    (duplicates in a batch, re-sightings across batches), and with the
    bucket bits of ``key_lo`` cleared down to ``bucket_bits`` (many keys a
    bucket)."""
    if pool is not None:
        prng = np.random.default_rng(99)
        pk = prng.integers(0, 1 << 32, (pool, 2), dtype=np.uint32)
        k = pk[rng.integers(0, pool, n)]
    else:
        k = rng.integers(0, 1 << 32, (n, 2), dtype=np.uint32)
    hi, lo = k[:, 0].copy(), k[:, 1].copy()
    if bucket_bits is not None:
        lo &= np.uint32(0xFFF00000 | ((1 << bucket_bits) - 1))
    return hi, lo


def _live(rng, n, n_live):
    act = np.zeros(n, bool)
    act[rng.permutation(n)[:n_live]] = True
    return act


# name -> (lanes BA, table buckets TB, live lanes of a batch, key options)
CASES = {
    "live_0": (256, 64, lambda n: 0, {}),
    "live_1_lane": (256, 64, lambda n: 1, {}),
    "live_5pct": (256, 64, lambda n: n // 20, {}),
    "live_50pct": (256, 64, lambda n: n // 2, {}),
    "live_100pct": (256, 64, lambda n: n, {}),
    "duplicate_keys": (256, 64, lambda n: n // 2, {"pool": 40}),
    "resighted_keys": (256, 256, lambda n: n // 3, {"pool": 300}),
    "one_bucket": (256, 64, lambda n: n // 2, {"bucket_bits": 0}),
    "two_buckets_full_table": (256, 4, lambda n: n // 2,
                               {"bucket_bits": 1}),
    "n_live_T_minus_1": (256, 64, lambda n: T - 1, {}),
    "n_live_T": (256, 64, lambda n: T, {}),
    "n_live_T_plus_1": (256, 64, lambda n: T + 1, {}),
    "n_live_3T": (256, 64, lambda n: 3 * T, {}),
    "n_live_3T_duplicates": (256, 64, lambda n: 3 * T, {"pool": 150}),
    "lanes_not_a_multiple_of_T": (200, 64, lambda n: 130, {}),
    "narrower_than_T": (48, 16, lambda n: 20, {}),      # the routed width
    "narrower_than_T_all_live": (48, 16, lambda n: n, {"pool": 60}),
    "all_ones_key_live": (256, 64, lambda n: n // 4, {"ones": 3}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_sorted_space_filter_equals_the_lane_order_filter(case, monkeypatch):
    """Six batches through one partly filled table, the parent's stage
    beside the new one: equal tables after every batch, equal streamed
    sets, equal ``compact[:n_stream]``, equal lane-order mask, and the tile
    count the live lanes give."""
    BA, TB, n_live_of, opts = CASES[case]
    monkeypatch.setattr(ddd_mod, "_T_PROBE", T)
    monkeypatch.setattr(ddd_mod, "_S_INS", S_INS)
    opts = dict(opts)
    ones = opts.pop("ones", 0)
    Sb = 4
    rng = np.random.default_rng(sorted(CASES).index(case))
    # fresh callables: a trace made under another tile is never reused
    new = jax.jit(lambda *a: _filter_insert_ordered(*a))
    mask = jax.jit(lambda *a: _lane_mask(*a))
    ref = jax.jit(functools.partial(_reference, s_ins=S_INS))

    # a table a third full to start from: both sides get the same one
    fill = rng.random((TB, Sb)) < 0.35
    t_hi = np.where(fill, rng.integers(0, 1 << 32, (TB, Sb),
                                       dtype=np.uint32), _EMPTY)
    t_lo = np.where(fill, rng.integers(0, 1 << 32, (TB, Sb),
                                       dtype=np.uint32), _EMPTY)
    tables = (jnp.asarray(t_hi, U32), jnp.asarray(t_lo, U32))
    streamed = 0
    for batch in range(6):
        hi, lo = _keys(rng, BA, **opts)
        act = _live(rng, BA, n_live_of(BA))
        if ones:        # active lanes whose key is the table's sentinel
            at = rng.permutation(BA)[:ones]
            hi[at] = lo[at] = np.uint32(_EMPTY)
            act[at[:2]] = True
        args = (*tables, jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(act))
        w_hi, w_lo, w_stream, w_compact = ref(*args)
        g_hi, g_lo, n_stream, compact, n_tiles = new(*args)
        m_hi, m_lo, m_stream = mask(*args)

        where = f"{case}, batch {batch}"
        np.testing.assert_array_equal(g_hi, w_hi, err_msg=where)
        np.testing.assert_array_equal(g_lo, w_lo, err_msg=where)
        np.testing.assert_array_equal(m_hi, w_hi, err_msg=where)
        np.testing.assert_array_equal(m_lo, w_lo, err_msg=where)
        n = int(np.sum(w_stream))
        assert int(n_stream) == n, where
        np.testing.assert_array_equal(
            np.asarray(compact)[:n], np.asarray(w_compact)[:n],
            err_msg=where)
        np.testing.assert_array_equal(m_stream, w_stream, err_msg=where)
        assert np.all((np.asarray(compact) >= 0)
                      & (np.asarray(compact) < BA)), where
        if not ones:    # an all-ones key sorts among the dead lanes
            assert int(n_tiles) == -(-int(act.sum()) // min(T, BA)), where
        else:
            assert int(n_tiles) <= -(-BA // T), where
        tables = (g_hi, g_lo)
        streamed += n
    assert streamed > 0 or n_live_of(BA) == 0


def test_shipped_tile_takes_its_width_from_the_batch():
    """``_T_PROBE`` as shipped: a batch narrower than a tile is one tile
    of its own width, and an empty batch takes none."""
    TB, Sb, BA = 16, 4, 40
    assert ddd_mod._T_PROBE > BA
    empty = jnp.full((TB, Sb), _EMPTY, U32)
    hi = jnp.arange(BA, dtype=U32) * 7 + 1
    lo = jnp.arange(BA, dtype=U32) * 13 + 5
    for n_live, tiles in ((0, 0), (1, 1), (BA, 1)):
        act = jnp.arange(BA) < n_live
        _, _, n_stream, compact, n_tiles = _filter_insert_ordered(
            empty, empty, hi, lo, act)
        assert (int(n_stream), int(n_tiles)) == (n_live, tiles)
        np.testing.assert_array_equal(np.asarray(compact)[:n_live],
                                      np.arange(n_live))
