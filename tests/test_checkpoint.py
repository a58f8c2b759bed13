"""Checkpoint/resume: the search is a pure function of the carry, so a
resumed run must be bit-exact with an uninterrupted one (SURVEY §5 —
TLC's ``states/`` + ``-recover`` analog)."""

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.device_engine import Capacities, DeviceEngine

CFG = CheckConfig(bounds=Bounds(n_servers=2, n_values=1, max_term=2,
                                max_log=0, max_msgs=2),
                  spec="election", invariants=("NoTwoLeaders",), chunk=32)
CAPS = Capacities(n_states=1 << 13, levels=64)


def test_checkpoint_resume_bit_exact(tmp_path):
    ckpt = str(tmp_path / "search.ckpt")
    eng = DeviceEngine(CFG, CAPS, seg_chunks=8)
    eng.SEG_MAX = 8                      # force many segments on a small space
    straight = eng.check()
    # checkpoint_every_s=0: a snapshot after every segment; the file left
    # behind is a mid-search carry from just before the final segments.
    eng2 = DeviceEngine(CFG, CAPS, seg_chunks=8)
    eng2.SEG_MAX = 8
    res = eng2.check(checkpoint=ckpt, checkpoint_every_s=0.0)
    assert res.n_states == straight.n_states

    eng3 = DeviceEngine(CFG, CAPS, seg_chunks=8)
    eng3.SEG_MAX = 8
    resumed = eng3.check(resume=ckpt)
    assert resumed.n_states == straight.n_states
    assert resumed.diameter == straight.diameter
    assert resumed.levels == straight.levels
    assert resumed.n_transitions == straight.n_transitions
    assert resumed.coverage == straight.coverage
    assert resumed.violation is None


def test_checkpoint_shape_mismatch_is_loud(tmp_path):
    ckpt = str(tmp_path / "search.ckpt")
    eng = DeviceEngine(CFG, CAPS, seg_chunks=8)
    eng.SEG_MAX = 8
    eng.check(checkpoint=ckpt, checkpoint_every_s=0.0)
    other = DeviceEngine(CFG, Capacities(n_states=1 << 14, levels=64))
    with pytest.raises(ValueError, match="checkpoint"):
        other.check(resume=ckpt)


def test_checkpoint_file_is_atomic_npz(tmp_path):
    ckpt = str(tmp_path / "search.ckpt")
    eng = DeviceEngine(CFG, CAPS, seg_chunks=8)
    eng.SEG_MAX = 8
    eng.check(checkpoint=ckpt, checkpoint_every_s=0.0)
    with np.load(ckpt) as z:
        assert int(z["width"]) == eng.lay.width
        assert z["c0"].shape == (CAPS.n_states, eng.lay.width)
    assert not (tmp_path / "search.ckpt.tmp").exists()


def test_stream_rows_width_mismatch_rejected(tmp_path):
    """A packed-row layout change must refuse to resume old streams: the
    config digest does not cover the bit-pack schema (review finding)."""
    import numpy as np
    from raft_tla_tpu.utils import ckpt
    p = str(tmp_path / "s.rows")
    ckpt.stream_rows_out(p, lambda st, n: np.zeros((n, 3), np.int32), 5, 3)
    got = []
    ckpt.stream_rows_in(p, got.append, 5, expect_width=3)
    assert sum(b.shape[0] for b in got) == 5
    with pytest.raises(ValueError, match="row width"):
        ckpt.stream_rows_in(p, got.append, 5, expect_width=4)


@pytest.mark.slow      # virtual-mesh test (see test_shard_engine)
def test_shard_checkpoint_resume_bit_exact(tmp_path):
    """Same carry-purity argument on the 8-device mesh: a snapshot taken
    mid-search resumes to the identical result (and a different mesh size
    is rejected — the FP-ownership map depends on it)."""
    from raft_tla_tpu.parallel.shard_engine import (ShardCapacities,
                                                    ShardEngine, make_mesh)
    ck = str(tmp_path / "shard.ckpt")
    caps = ShardCapacities(n_states=1 << 12, levels=64)

    def eng(n=8):
        e = ShardEngine(CFG, make_mesh(n), caps, seg_chunks=8)
        e.SEG_MAX = 8
        return e

    straight = eng().check()
    res = eng().check(checkpoint=ck, checkpoint_every_s=0.0)
    assert res.n_states == straight.n_states
    resumed = eng().check(resume=ck)
    assert resumed.n_states == straight.n_states
    assert resumed.diameter == straight.diameter
    assert resumed.levels == straight.levels
    assert resumed.n_transitions == straight.n_transitions
    assert resumed.coverage == straight.coverage
    assert resumed.violation is None

    with pytest.raises(ValueError, match="checkpoint"):
        eng(4).check(resume=ck)


def test_digest_covers_deadlock_toggle():
    """Resuming a non-deadlock checkpoint under --deadlock would silently
    skip dead states in the explored region (review finding); the digest
    must split on the toggle — but stay stable when it is off (default
    omission keeps old checkpoints valid)."""
    import dataclasses
    from raft_tla_tpu.utils import ckpt
    base = ckpt.config_digest(CFG, CAPS, (1, 2))
    on = ckpt.config_digest(dataclasses.replace(CFG, check_deadlock=True),
                            CAPS, (1, 2))
    assert base != on


def test_stream_rows_append_incremental(tmp_path):
    """Append-only snapshot streams: extending in place must be byte-
    equivalent to a full rewrite, survive a torn append (garbage past the
    header count), cap at an older header, and reject nothing silently."""
    from raft_tla_tpu.utils import ckpt

    data = np.arange(20 * 3, dtype=np.int32).reshape(20, 3)

    def reader(start, n):
        return data[start:start + n]

    p = str(tmp_path / "s.rows")
    # fresh append == full write
    ckpt.stream_rows_append(p, reader, 8, 3)
    got = []
    ckpt.stream_rows_in(p, got.append, 8, expect_width=3)
    assert np.array_equal(np.concatenate(got), data[:8])
    # incremental extension
    ckpt.stream_rows_append(p, reader, 15, 3)
    got = []
    ckpt.stream_rows_in(p, got.append, 15, expect_width=3)
    assert np.array_equal(np.concatenate(got), data[:15])
    # torn append: garbage beyond the header count is dropped on the
    # next snapshot (truncate-to-header before appending)
    with open(p, "ab") as f:
        np.full((7,), -999, np.int32).tofile(f)
    ckpt.stream_rows_append(p, reader, 18, 3)
    got = []
    ckpt.stream_rows_in(p, got.append, 18, expect_width=3)
    assert np.array_equal(np.concatenate(got), data[:18])
    # width change falls back to a full rewrite
    data2 = np.arange(6 * 4, dtype=np.int32).reshape(6, 4)
    ckpt.stream_rows_append(p, lambda s, n: data2[s:s + n], 6, 4)
    got = []
    ckpt.stream_rows_in(p, got.append, 6, expect_width=4)
    assert np.array_equal(np.concatenate(got), data2)


def test_stream_append_shrink_and_stale_protection(tmp_path):
    """The shrink path (end below the current header) and the engine's
    stale-stream hygiene: a fresh run pointed at an existing checkpoint
    path must not inherit another run's stream prefix."""
    from raft_tla_tpu.utils import ckpt
    data = np.arange(20 * 3, dtype=np.int32).reshape(20, 3)

    def reader(start, n):
        return data[start:start + n]

    p = str(tmp_path / "s.rows")
    ckpt.stream_rows_append(p, reader, 15, 3)
    # shrink: trusted prefix capped below the header (resume from an
    # older npz), then re-extended — rows must be the reader's, readable
    ckpt.trim_stream(p, 10, 3)
    got = []
    ckpt.stream_rows_in(p, got.append, 10, expect_width=3)
    assert np.array_equal(np.concatenate(got), data[:10])
    ckpt.stream_rows_append(p, reader, 12, 3)
    got = []
    ckpt.stream_rows_in(p, got.append, 12, expect_width=3)
    assert np.array_equal(np.concatenate(got), data[:12])
    # append with end below header: file caps at end
    ckpt.stream_rows_append(p, reader, 5, 3)
    got = []
    ckpt.stream_rows_in(p, got.append, 5, expect_width=3)
    assert np.array_equal(np.concatenate(got), data[:5])

    # a FRESH DDDEngine run pointed at a path holding another run's
    # streams must rewrite them from scratch (not append-reuse)
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    caps = DDDCapacities(block=256, table=1 << 14, flush=1 << 10,
                         levels=64)
    ck = str(tmp_path / "fresh.ckpt")
    eng = DDDEngine(CFG, caps)
    # plant a bogus stream at the checkpoint path
    P = eng.schema.P
    ckpt.stream_rows_out(ck + ".rows", lambda s, n: np.full(
        (n, P), -7, np.int32), 100, P)
    straight = eng.check(checkpoint=ck, checkpoint_every_s=0.0)
    resumed = DDDEngine(CFG, caps).check(resume=ck)
    assert resumed.n_states == straight.n_states == 3014
    assert resumed.levels == straight.levels


# -- content-digest seal (campaign supervision satellite) -------------------
# atomic_savez embeds a sha over every array; load_npz_verified checks
# it — the integrity/identity split the campaign supervisor relies on
# (CheckpointCorrupt -> quarantine, ValueError -> operator error).


def test_content_digest_round_trip_and_atomicity(tmp_path):
    import os

    from raft_tla_tpu.utils import ckpt as C

    p = str(tmp_path / "s.npz")
    C.atomic_savez(p, a=np.arange(5), config_digest=np.uint64(3))
    assert not os.path.exists(p + ".tmp")        # rename committed
    with C.load_npz_verified(p) as z:
        assert "content_sha" in z.files
        np.testing.assert_array_equal(z["a"], np.arange(5))
    with C.load_npz_checked(p, 3) as z:          # identity also OK
        np.testing.assert_array_equal(z["a"], np.arange(5))


def test_truncated_npz_is_checkpoint_corrupt(tmp_path):
    import os

    from raft_tla_tpu.utils import ckpt as C

    p = str(tmp_path / "s.npz")
    C.atomic_savez(p, a=np.arange(100), config_digest=np.uint64(3))
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    with pytest.raises(C.CheckpointCorrupt, match="s.npz"):
        C.load_npz_verified(p)


def test_content_digest_mismatch_is_checkpoint_corrupt(tmp_path):
    from raft_tla_tpu.utils import ckpt as C

    p = str(tmp_path / "s.npz")
    # intact zip, lying seal: bit-rot the digest can see but zip can't
    np.savez(p, a=np.arange(5), config_digest=np.uint64(3),
             content_sha="0" * 64)
    with pytest.raises(C.CheckpointCorrupt, match="content digest"):
        C.load_npz_verified(p)


def test_legacy_snapshot_without_seal_still_loads(tmp_path):
    from raft_tla_tpu.utils import ckpt as C

    p = str(tmp_path / "s.npz")
    np.savez(p, a=np.arange(5), config_digest=np.uint64(3))
    with C.load_npz_verified(p) as z:            # pre-seal format
        np.testing.assert_array_equal(z["a"], np.arange(5))


def test_config_digest_mismatch_is_value_error_not_corrupt(tmp_path):
    from raft_tla_tpu.utils import ckpt as C

    p = str(tmp_path / "s.npz")
    C.atomic_savez(p, a=np.arange(5), config_digest=np.uint64(3))
    with pytest.raises(ValueError, match="different model config") \
            as exc:
        C.load_npz_checked(p, 4)
    assert not isinstance(exc.value, C.CheckpointCorrupt)
