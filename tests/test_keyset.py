"""Partitioned host dedup (utils/keyset.py, utils/flushq.py).

The partitioned master key set must be *observationally identical* to
the flat one — same first-occurrence new-index vectors flush for flush,
same contains/len/array — under any partition count, adversarial
duplicate patterns, empty partitions and all-duplicate flushes; that
equivalence is what lets the ddd engines swap implementations under the
RAFT_TLA_HOSTDEDUP gate without touching a single byte of discovery
order.  The budgeted compaction must bound per-flush merge data
movement and carry an interrupted merge's cursor across flushes to the
same final set.
"""

import os

import numpy as np
import pytest

from raft_tla_tpu.utils import flushq, keyset
from raft_tla_tpu.utils.keyset import (
    MasterKeys, PartitionedMasterKeys, master_from_keys)

pytestmark = pytest.mark.smoke


def _streams(rng, n_flushes=40):
    """Adversarial flush streams: tiny key pools (heavy duplicates),
    full-range uniform, everything jammed into one partition (63 empty),
    all-duplicate and empty flushes."""
    for it in range(n_flushes):
        n = int(rng.integers(0, 400))
        mode = it % 5
        if mode == 0:
            yield rng.integers(0, 40, n).astype(np.uint64)
        elif mode == 1:
            yield rng.integers(0, 2 ** 63, n, dtype=np.int64).astype(np.uint64)
        elif mode == 2:  # top bits fixed: one partition takes it all
            yield (np.uint64(0x7) << np.uint64(61)) \
                | rng.integers(0, 500, n).astype(np.uint64)
        elif mode == 3 and n:  # all duplicates of one key
            yield np.full(n, rng.integers(0, 2 ** 62), np.uint64)
        else:
            yield np.empty(0, np.uint64)


@pytest.mark.parametrize("parts", [1, 2, 4, 16, 64])
@pytest.mark.parametrize("budget", [None, 64, 4096])
def test_partitioned_equivalence(parts, budget):
    rng = np.random.default_rng(parts * 1000 + (budget or 0))
    flat = MasterKeys()
    part = PartitionedMasterKeys(parts=parts, merge_budget=budget)
    for flush in _streams(rng):
        got = part.dedup(flush.copy())
        want = flat.dedup(flush.copy())
        assert np.array_equal(got, want)
        assert len(flat) == len(part)
    assert np.array_equal(flat.array, part.array)
    probe = rng.integers(0, 2 ** 63, 2000, dtype=np.int64).astype(np.uint64)
    assert np.array_equal(flat.contains(probe), part.contains(probe))


def test_budget_bounds_merge_movement_and_carries_cursor():
    """A merge bigger than the budget must (a) never move more than the
    budget in one flush and (b) resume mid-merge across flushes until
    complete — with probes correct the whole way (both source runs stay
    visible until the spliced result replaces them)."""
    rng = np.random.default_rng(7)
    budget = 256
    flat = MasterKeys()
    part = PartitionedMasterKeys(parts=2, merge_budget=budget)
    saw_pending = False
    for _ in range(300):
        flush = rng.integers(0, 2 ** 63, 200, dtype=np.int64) \
            .astype(np.uint64)
        assert np.array_equal(part.dedup(flush.copy()),
                              flat.dedup(flush.copy()))
        assert part.last_flush_moved <= budget
        if part.pending_merges:
            saw_pending = True
            # mid-merge probes must still see every admitted key
            probe = flat.array[:: max(1, len(flat) // 97)]
            assert bool(np.all(part.contains(probe)))
    assert saw_pending, "budget never forced a carried merge cursor"
    # let later flushes finish the carried merges; final set identical
    for _ in range(200):
        flush = rng.integers(0, 2 ** 63, 200, dtype=np.int64) \
            .astype(np.uint64)
        part.dedup(flush.copy())
        flat.dedup(flush.copy())
    assert np.array_equal(flat.array, part.array)


def test_unbudgeted_partition_matches_flat_tier_structure():
    """With no budget, each partition compacts exactly like the flat
    geometric policy — the run-count bound (O(log N)) holds per
    partition."""
    rng = np.random.default_rng(11)
    part = PartitionedMasterKeys(parts=4, merge_budget=None)
    for _ in range(200):
        part.dedup(rng.integers(0, 2 ** 63, 500, dtype=np.int64)
                   .astype(np.uint64))
    assert part.pending_merges == 0
    assert part.n_runs <= 20
    for p in part._p:
        for a, b in zip(p.runs, p.runs[1:]):
            assert a.size > keyset._RATIO * b.size
            assert bool(np.all(a[1:] > a[:-1]))


def test_parts_must_be_power_of_two():
    with pytest.raises(ValueError):
        PartitionedMasterKeys(parts=3)
    with pytest.raises(ValueError):
        PartitionedMasterKeys(parts=0)


def test_constructor_rejects_unsorted_base():
    bad = np.asarray([3, 2, 5], np.uint64)
    with pytest.raises(ValueError, match="strictly sorted"):
        PartitionedMasterKeys(bad)
    ok = np.asarray([2, 3, 5], np.uint64)
    m = PartitionedMasterKeys(ok, parts=16)
    assert len(m) == 3 and np.array_equal(m.array, ok)


@pytest.mark.parametrize("partitioned", [False, True])
def test_master_from_keys_resume_build(partitioned):
    """The checkpoint-resume factory: unsorted unique log -> same set
    either arm; a duplicated key raises the stream-corrupt diagnostic
    naming the snapshot (NOT the constructor's sortedness error)."""
    rng = np.random.default_rng(3)
    keys = np.unique(rng.integers(0, 2 ** 63, 5000, dtype=np.int64)
                     .astype(np.uint64))
    rng.shuffle(keys)
    m = master_from_keys(keys, source="/tmp/snap.ckpt",
                         partitioned=partitioned)
    assert len(m) == keys.size
    assert np.array_equal(m.array, np.sort(keys))
    bad = np.concatenate([keys, keys[:1]])
    with pytest.raises(ValueError) as ei:
        master_from_keys(bad, source="/tmp/snap.ckpt",
                         partitioned=partitioned)
    assert "stream corrupt" in str(ei.value)
    assert "/tmp/snap.ckpt" in str(ei.value)
    assert "strictly sorted" not in str(ei.value)


def test_host_dedup_gate_resolution():
    assert keyset.host_dedup_enabled("on") is True
    assert keyset.host_dedup_enabled("off") is False
    # measured policy: auto = ON iff the host has >= 2 cores (the
    # partitioned path costs 0.72x in-engine single-threaded)
    auto_expect = (os.cpu_count() or 1) >= 2
    assert keyset.host_dedup_enabled("auto") is auto_expect
    assert keyset.host_dedup_enabled("AUTO") is auto_expect


@pytest.mark.parametrize("env, told", [
    ({}, True),
    ({"MALLOC_ARENA_MAX": "2"}, False),
    ({"GLIBC_TUNABLES": "glibc.malloc.trim_threshold=1"}, False),
])
def test_keep_freed_memory_once_and_never_over_the_environment(
        monkeypatch, env, told):
    """The allocator's thresholds are fixed once a process through
    ``mallopt``, by both factories, and a process whose environment sets its
    own is left as it was set."""
    calls = []

    class Libc:
        @staticmethod
        def mallopt(param, value):
            calls.append((param, value))
            return 1

    for k in [k for k in os.environ
              if k.startswith("MALLOC_") or k == "GLIBC_TUNABLES"]:
        monkeypatch.delenv(k)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(keyset, "_KEPT", None)
    monkeypatch.setattr(keyset.ctypes, "CDLL", lambda name: Libc)
    keyset.new_master(True)
    assert keyset.keep_freed_memory() is told
    keyset.master_from_keys(np.arange(5, dtype=np.uint64))
    assert calls == (list(keyset._MALLOPT) if told else [])
    assert dict(keyset._MALLOPT) == {-1: 2 ** 31 - 1, -3: 32 << 20,
                                     -2: 64 << 20}


def test_keep_freed_memory_without_mallopt_is_a_no_op(monkeypatch):
    monkeypatch.setattr(keyset, "_KEPT", None)
    for k in [k for k in os.environ
              if k.startswith("MALLOC_") or k == "GLIBC_TUNABLES"]:
        monkeypatch.delenv(k)
    monkeypatch.setattr(keyset.ctypes, "CDLL", lambda name: object())
    assert keyset.keep_freed_memory() is False
    assert keyset.keep_freed_memory() is False


def test_dedup_worker_ordered_depth1_and_exceptions():
    """flushq.DedupWorker: batches run in submission order, depth-1
    (submit i+1 blocks until i completes), drain settles everything,
    and a worker exception re-raises on the main thread."""
    seen = []

    def fn(batch):
        seen.append(batch)
        return batch

    w = flushq.DedupWorker(fn)
    for i in range(10):
        w.submit(i, n_keys=5)
    assert w.drain() == sum(range(10))
    assert seen == list(range(10))        # strict submission order
    assert w.backlog() == 0 and w.inclusive_extra() == 0
    w.close()

    def boom(batch):
        raise RuntimeError("kaboom")

    w2 = flushq.DedupWorker(boom)
    w2.submit(0, n_keys=1)
    with pytest.raises(RuntimeError, match="background dedup flush"):
        for _ in range(3):
            w2.submit(1, n_keys=1)
            w2.drain()
    w2.close()
