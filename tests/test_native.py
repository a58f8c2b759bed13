"""C++ host runtime ≡ NumPy twins (SURVEY §2.8 native components).

The fingerprint MUST be bit-identical across the np reference, the device
path, and the C++ path — sharding routes states by fingerprint, so a single
differing bit mis-routes a state and silently breaks dedup exactness.
"""

import numpy as np
import pytest

from raft_tla_tpu.ops import fingerprint as fpr
from raft_tla_tpu.utils import native


def test_native_toolchain_available():
    """The image bakes g++; the C++ path must actually be exercised here."""
    assert native.HAS_NATIVE


def test_fingerprint_bit_identical_cpp_vs_numpy():
    rng = np.random.default_rng(7)
    rows = rng.integers(-2**31, 2**31 - 1, size=(4096, 60), dtype=np.int32)
    hi_np, lo_np = fpr.fingerprint(rows, fpr.lane_constants(60), np)
    hi_cc, lo_cc = native.fingerprint_rows(rows)
    np.testing.assert_array_equal(hi_np.astype(np.uint32), hi_cc)
    np.testing.assert_array_equal(lo_np.astype(np.uint32), lo_cc)


def test_fingerprint_bit_identical_cpp_vs_device():
    import jax.numpy as jnp
    rng = np.random.default_rng(8)
    rows = rng.integers(0, 2**20, size=(512, 33), dtype=np.int32)
    consts = fpr.lane_constants(33)
    hi_d, lo_d = fpr.fingerprint(jnp.asarray(rows), jnp.asarray(consts), jnp)
    hi_cc, lo_cc = native.fingerprint_rows(rows)
    np.testing.assert_array_equal(np.asarray(hi_d), hi_cc)
    np.testing.assert_array_equal(np.asarray(lo_d), lo_cc)


@pytest.mark.parametrize("cls", [native.HostStore, native.PyHostStore])
def test_store_roundtrip(cls):
    if cls is native.HostStore and not native.HAS_NATIVE:
        pytest.skip("no toolchain")
    st = cls(width=7)
    rng = np.random.default_rng(9)
    all_rows = []
    for n in (1, 100, 70000, 3):        # spans the 65536-row block boundary
        rows = rng.integers(-1000, 1000, size=(n, 7), dtype=np.int32)
        all_rows.append(rows)
        st.append(rows)
    ref = np.concatenate(all_rows)
    assert len(st) == ref.shape[0]
    np.testing.assert_array_equal(st.read(0, len(st)), ref)
    np.testing.assert_array_equal(st.read(65530, 20), ref[65530:65550])
    with pytest.raises(IndexError):
        st.read(len(st) - 1, 2)
    st.close()


@pytest.mark.parametrize("cls", [native.HostStore, native.PyHostStore])
def test_links_and_trace_chain(cls):
    if cls is native.HostStore and not native.HAS_NATIVE:
        pytest.skip("no toolchain")
    st = cls(width=1)
    # a BFS-ish parent forest: row 0 is the root
    parent = np.asarray([-1, 0, 0, 1, 3, 4, 2], np.int32)
    lane = np.asarray([-1, 5, 6, 7, 8, 9, 10], np.int32)
    st.append_links(parent[:4], lane[:4])
    st.append_links(parent[4:], lane[4:])
    p, l = st.read_links(2, 3)
    np.testing.assert_array_equal(p, parent[2:5])
    np.testing.assert_array_equal(l, lane[2:5])
    np.testing.assert_array_equal(st.trace_chain(5), [0, 1, 3, 4, 5])
    np.testing.assert_array_equal(st.trace_chain(6), [0, 2, 6])
    np.testing.assert_array_equal(st.trace_chain(0), [0])
    st.close()


def test_cpp_store_matches_py_store_on_random_ops():
    if not native.HAS_NATIVE:
        pytest.skip("no toolchain")
    rng = np.random.default_rng(10)
    a, b = native.HostStore(5), native.PyHostStore(5)
    for _ in range(20):
        rows = rng.integers(-50, 50, size=(int(rng.integers(1, 500)), 5),
                            dtype=np.int32)
        a.append(rows)
        b.append(rows)
    assert len(a) == len(b)
    start = int(rng.integers(0, len(a) // 2))
    n = int(rng.integers(1, len(a) - start))
    np.testing.assert_array_equal(a.read(start, n), b.read(start, n))
    a.close()


@pytest.mark.parametrize("cls", [native.HostStore, native.PyHostStore])
def test_store_concurrent_append_and_disjoint_reads(cls):
    """The one-appender + disjoint-range-reader contract the upload
    prefetch rests on (utils/prefetch.py): a reader of rows below a
    previously observed ``len()`` must see exactly those rows while an
    appender thread keeps publishing past them — native (atomic block
    directory, release-published size) and fallback (snapshot reads)
    alike.  Block size is 65536 rows, so 3000-row appends cross block
    and chunk-internal boundaries repeatedly."""
    if cls is native.HostStore and not native.HAS_NATIVE:
        pytest.skip("no toolchain")
    import threading
    width, n_batches, rows_per = 6, 64, 3000
    rng = np.random.default_rng(11)
    batches = [rng.integers(-9, 9, size=(rows_per, width), dtype=np.int32)
               for _ in range(n_batches)]
    ref = np.concatenate(batches)
    st = cls(width=width)
    st.append(batches[0])
    published = threading.Event()
    errors = []

    def appender():
        try:
            for b in batches[1:]:
                st.append(b)
                published.set()
        except BaseException as e:     # noqa: BLE001 — surfaced below
            errors.append(e)
            published.set()

    t = threading.Thread(target=appender)
    t.start()
    try:
        reads = 0
        while t.is_alive() or reads < 50:
            hi = len(st)               # observe a published size...
            lo = max(0, hi - 2048)
            got = st.read(lo, hi - lo)  # ...then read only below it
            np.testing.assert_array_equal(got, ref[lo:hi])
            reads += 1
            if not t.is_alive() and reads >= 50:
                break
    finally:
        t.join()
    assert not errors, errors
    assert len(st) == ref.shape[0]
    np.testing.assert_array_equal(st.read(0, len(st)), ref)
    st.close()


def test_store_bounds_error_messages_native_fallback_parity():
    """read / read_links / trace_chain must fail with the SAME
    IndexError text on both backends — the engines and the prefetch
    layer treat these as one store type."""
    if not native.HAS_NATIVE:
        pytest.skip("no toolchain")
    stores = [native.HostStore(3), native.PyHostStore(3)]
    rows = np.arange(30, dtype=np.int32).reshape(10, 3)
    parent = np.asarray([-1, 0, 1], np.int32)
    lane = np.asarray([-1, 4, 5], np.int32)
    msgs = []
    for st in stores:
        st.append(rows)
        st.append_links(parent, lane)
        got = []
        for fn in (lambda: st.read(8, 5),
                   lambda: st.read_links(1, 9),
                   lambda: st.trace_chain(7)):
            with pytest.raises(IndexError) as ei:
                fn()
            got.append(str(ei.value))
        msgs.append(got)
        st.close()
    assert msgs[0] == msgs[1], msgs


def test_filestore_truncated_stream_diagnostic(tmp_path):
    """A stream file shorter than its committed header (torn copy,
    partial restore) must fail loudly with path + expected/got rows,
    not die inside a reshape."""
    import os
    path = str(tmp_path / "trunc.rows")
    st = native.FileStore(path, width=4)
    st.append(np.arange(400, dtype=np.int32).reshape(100, 4))
    st.sync()
    st.close()
    size = os.path.getsize(path)
    os.truncate(path, size - 10 * 4 * 4)     # drop the last 10 rows
    st = native.FileStore(path, width=4)
    np.testing.assert_array_equal(
        st.read(0, 90),
        np.arange(360, dtype=np.int32).reshape(90, 4))
    with pytest.raises(ValueError) as ei:
        st.read(0, 100)
    msg = str(ei.value)
    assert path in msg and "expected 100 rows" in msg and "got 90" in msg
    st.close()


def test_scc_csr_native_matches_python_fallback():
    """Both scc_csr implementations must induce the same partition
    (component ids may differ; membership must not) on random digraphs."""
    import numpy as np

    from raft_tla_tpu.utils import native

    rng = np.random.default_rng(3)
    for n, m in ((1, 0), (8, 12), (64, 200), (300, 1500)):
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m).astype(np.int64)
        order = np.argsort(src, kind="stable")
        src, dst = src[order], dst[order]
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])

        comp_n, nc_n = native.scc_csr(indptr, dst)
        # force the Python fallback
        saved = native.HAS_NATIVE
        native.HAS_NATIVE = False
        try:
            comp_p, nc_p = native.scc_csr(indptr, dst)
        finally:
            native.HAS_NATIVE = saved
        assert nc_n == nc_p
        # same partition: the id-of-id mapping must be a bijection
        pairs = {(int(a), int(b)) for a, b in zip(comp_n, comp_p)}
        assert len(pairs) == nc_n
        assert len({a for a, _ in pairs}) == nc_n
        assert len({b for _, b in pairs}) == nc_n


def test_failed_native_build_is_an_error_not_a_quiet_numpy_fallback(
        monkeypatch):
    """A build that failed raises where the native path is called for,
    with the compiler's message; the NumPy store still serves a caller
    who asks for it by name, and clearing HAS_NATIVE (no build error)
    still selects the reference twins on purpose."""
    import numpy as np

    from raft_tla_tpu.utils import native

    monkeypatch.setattr(native, "HAS_NATIVE", False)
    monkeypatch.setattr(native, "_BUILD_ERROR",
                        OSError("g++ ... failed:\nhost_store.cc:1: boom"))
    for call in (lambda: native.make_store(3),
                 lambda: native.HostStore(3),
                 lambda: native.fingerprint_rows(np.zeros((2, 3), np.int32)),
                 lambda: native.scc_csr(np.zeros(2, np.int64),
                                        np.zeros(0, np.int64))):
        with pytest.raises(RuntimeError, match=r"(?s)did not build.*boom"):
            call()
    store = native.PyHostStore(3)            # asked for by name: fine
    store.append(np.ones((2, 3), np.int32))
    assert len(store) == 2
    monkeypatch.setattr(native, "_BUILD_ERROR", None)
    assert isinstance(native.make_store(3), native.PyHostStore)
