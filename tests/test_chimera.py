"""Filter-table chimera guard (VERDICT r4 weak #3 / next-round #4).

The DDD filter inserts streamed (hi, lo) key words with two scatters
sharing one compacted index vector.  Rounds 1-4 relied on XLA applying
duplicate-index updates in operand order identically in both ops; a
compiler drift could have fused a fabricated (hiA, loB) "chimera" key
aliasing a never-streamed candidate — silent state loss, the one
failure an exhaustive checker must never have.  Round 5 removed the
reliance (``_filter_insert_ordered`` dedups (bucket, slot) within each batch so
the scatter indices are duplicate-free); these tests construct the
adversarial colliding-keys case directly and would fail loudly if the
dedup regressed AND the backend's duplicate-update order ever drifted
between the two ops — plus a differential engine run under a
collision-slammed tiny table (ADVICE r4, ddd_engine.py:379 item).
"""

import jax.numpy as jnp
import numpy as np

from raft_tla_tpu import Bounds, CheckConfig
from raft_tla_tpu.ddd_engine import _EMPTY, DDDCapacities, DDDEngine, \
    _filter_insert_ordered
from raft_tla_tpu.models import refbfs

import pytest
# smoke tier: cross-section for mid-round changes (pytest -m smoke)
pytestmark = pytest.mark.smoke

U32 = jnp.uint32


def _filter_insert(tbl_hi, tbl_lo, key_hi, key_lo, active):
    """The stage with its streamed candidates as a mask in lane order,
    built here from the compaction order it returns."""
    tbl_hi, tbl_lo, n_stream, compact, _ = _filter_insert_ordered(
        tbl_hi, tbl_lo, key_hi, key_lo, active)
    stream = np.zeros(key_hi.shape[0], bool)
    stream[np.asarray(compact)[:int(n_stream)]] = True
    return tbl_hi, tbl_lo, stream


def _table_pairs(tbl_hi, tbl_lo):
    """All non-empty (hi, lo) pairs currently in the table."""
    hi = np.asarray(tbl_hi).ravel()
    lo = np.asarray(tbl_lo).ravel()
    live = ~((hi == np.uint32(_EMPTY)) & (lo == np.uint32(_EMPTY)))
    return set(zip(hi[live].tolist(), lo[live].tolist()))


def test_two_keys_same_bucket_slot_both_stream_no_chimera():
    """The literal adversarial case from the VERDICT: two distinct keys
    colliding on one (bucket, slot) in one batch.  Both must stream and
    the table must contain only genuine inserted keys afterwards."""
    TB, Sb, BA = 4, 2, 8
    tbl_hi = jnp.full((TB, Sb), _EMPTY, U32)
    tbl_lo = jnp.full((TB, Sb), _EMPTY, U32)
    # same bucket (lo & 3 == 1), same evict slot (hi % 2 == 0); the
    # shared gather sees the same empty row, so both pick slot 0.
    A = (0xAAAA0000, 0x00000001)
    B = (0xBBBB0000, 0x00000005)
    key_hi = jnp.zeros((BA,), U32).at[0].set(A[0]).at[1].set(B[0])
    key_lo = jnp.zeros((BA,), U32).at[0].set(A[1]).at[1].set(B[1])
    active = jnp.arange(BA) < 2
    tbl_hi, tbl_lo, stream = _filter_insert(
        tbl_hi, tbl_lo, key_hi, key_lo, active)
    assert bool(stream[0]) and bool(stream[1])      # both stream
    pairs = _table_pairs(tbl_hi, tbl_lo)
    assert pairs <= {A, B}, f"fabricated key in table: {pairs - {A, B}}"
    assert len(pairs) == 1          # in-batch (bucket,slot) dedup kept one


def test_many_colliding_keys_never_fabricate():
    """Randomized slam: hundreds of distinct keys forced into very few
    buckets across several batches.  Every table entry must always be a
    key that was actually presented, and every first-sighting of a key
    not already in the table must stream."""
    rng = np.random.default_rng(7)
    TB, Sb, BA = 2, 2, 64
    tbl_hi = jnp.full((TB, Sb), _EMPTY, U32)
    tbl_lo = jnp.full((TB, Sb), _EMPTY, U32)
    presented = set()
    for _ in range(6):
        hi = rng.integers(1, 1 << 32, BA, dtype=np.uint32)
        lo = rng.integers(1, 1 << 32, BA, dtype=np.uint32)
        active = rng.random(BA) < 0.9
        before = _table_pairs(tbl_hi, tbl_lo)
        tbl_hi, tbl_lo, stream = _filter_insert(
            tbl_hi, tbl_lo, jnp.asarray(hi), jnp.asarray(lo),
            jnp.asarray(active))
        stream = np.asarray(stream)
        seen_batch = set()
        for c in range(BA):
            k = (int(hi[c]), int(lo[c]))
            if not active[c]:
                assert not stream[c]
                continue
            first = k not in seen_batch
            seen_batch.add(k)
            if first and k not in before:
                assert stream[c], f"new key {k} failed to stream"
            presented.add(k)
        pairs = _table_pairs(tbl_hi, tbl_lo)
        assert pairs <= presented, \
            f"fabricated keys: {pairs - presented}"


def test_collision_slammed_table_engine_parity():
    """Differential guard (ADVICE r4): a single-bucket filter table
    forces (bucket, slot) collisions in essentially every batch;
    exploration metrics must still exactly match the pure oracle."""
    cfg = CheckConfig(
        bounds=Bounds(n_servers=2, n_values=1, max_term=2, max_log=0,
                      max_msgs=2),
        spec="election", invariants=("NoTwoLeaders",), chunk=128)
    caps = DDDCapacities(block=256, table=8, flush=1 << 9, levels=64)
    r = DDDEngine(cfg, caps).check()
    o = refbfs.check(cfg)
    assert r.violation is None and o.violation is None
    assert (r.n_states, r.diameter) == (o.n_states, o.diameter)
