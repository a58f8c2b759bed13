"""The fingerprint scheme itself (ops/fingerprint.py, scheme 2 since PR 26).

Scheme 1 (no fold) gave two distinct 5-server orbits one key at BFS level 6
of the full ``Next``: their packed rows differ only in the ``src``/``dst``
fields of three message words (bits 21-28), where a multilinear sum mod
2^32 keeps 11 bits a lane.  These tests pin the pair, the fold's reach, what
it leaves as it was, and that a snapshot of another scheme's keys is refused.
"""

import dataclasses

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.models import interp
from raft_tla_tpu.ops import fingerprint as fpr
from raft_tla_tpu.ops import state as st
from raft_tla_tpu.ops import symmetry as sym_mod
from raft_tla_tpu.utils import ckpt

B5 = Bounds(n_servers=5, n_values=2, max_term=2, max_log=1, max_msgs=2,
            max_dup=1)
# three candidates of term 2, two followers of term 1, three RequestVote
# requests in flight (one past the constraint: counted, never expanded).
# (src, dst) = (0,1) (0,3) (1,4) against (0,1) (1,2) (2,3): one
# candidate-to-candidate edge against two, so no renaming maps one to the
# other
_BASE = dict(role=(1, 1, 1, 0, 0), term=(2, 2, 2, 1, 1))
PAIR = (((33554449, 0), 1), ((100663313, 0), 1), ((136314897, 0), 1)), \
       (((33554449, 0), 1), ((69206033, 0), 1), ((104857617, 0), 1))


def _scheme1(vec, consts):
    """The fingerprint of PRs <= 25, for comparison: no fold."""
    with np.errstate(over="ignore"):
        w = vec.astype(np.uint32)
        s1 = np.sum(w * consts[0], axis=-1, dtype=np.uint32)
        s2 = np.sum(w * consts[1], axis=-1, dtype=np.uint32)
        return (fpr._fmix32(s1 + fpr._LANE_SEEDS[0], np),
                fpr._fmix32(s2 + fpr._LANE_SEEDS[1], np))


def _pair_states():
    init = interp.init_state(B5)
    return [init._replace(msgs=m, **_BASE) for m in PAIR]


def test_the_level_six_pair_has_two_orbit_keys():
    a, b = _pair_states()
    ka = sym_mod.py_orbit_fingerprint(a, B5, ("Server",))
    kb = sym_mod.py_orbit_fingerprint(b, B5, ("Server",))
    assert ka != kb


def test_the_level_six_pair_collided_without_the_fold():
    """The record of the fault: under scheme 1 some renaming of each state
    hashes to the same orbit-minimal key, on rows that differ in three
    message words only, and only above bit 20."""
    lay = st.Layout.of(B5)
    consts = fpr.lane_constants(lay.width)
    best = []
    for s in _pair_states():
        struct = st.unpack(interp.to_vec(s, B5), lay, np)
        rows = np.stack([st.pack(st.canonicalize(
            sym_mod.permute_struct(struct, p, B5, np), np), np)
            for p in sym_mod.permutations(B5)])
        hi, lo = _scheme1(rows, consts)
        k = np.lexsort((lo, hi))[0]
        best.append(((int(hi[k]), int(lo[k])), rows[k]))
    (ka, ra), (kb, rb) = best
    assert ka == kb
    diff = np.flatnonzero(ra != rb)
    assert len(diff) == 3
    assert all((int(x) ^ int(y)) & ((1 << 21) - 1) == 0
               for x, y in zip(ra[diff], rb[diff]))
    # and under the shipped scheme the same two rows differ in both lanes
    h2, l2 = fpr.fingerprint(np.stack([ra, rb]), consts, np)
    assert h2[0] != h2[1] and l2[0] != l2[1]


def test_words_below_two_to_the_sixteen_hash_as_before():
    """The fold is the identity on small words: a state with no message in
    flight (Init among them) keeps its scheme-1 key."""
    rng = np.random.default_rng(1)
    rows = rng.integers(0, 1 << 16, size=(64, 114)).astype(np.int32)
    consts = fpr.lane_constants(114)
    got = fpr.fingerprint(rows, consts, np)
    want = _scheme1(rows, consts)
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])
    init = interp.init_state(B5)
    v = interp.to_vec(init, B5)
    assert fpr.fingerprint(v, consts, np) == _scheme1(v, consts)


@pytest.mark.parametrize("bits", [(21, 29), (16, 32), (25, 29)])
def test_high_bit_differences_reach_the_low_bits(bits):
    """Pairs of rows that differ in three words and only inside ``bits``:
    without the fold a lane keeps ``32 - lo`` bits of such a difference and
    collides about once in ``2^(32-lo)``; with it the difference reaches
    bits ``lo - 16`` up, and none of 200,000 pairs collides in either
    lane."""
    lo_bit, hi_bit = bits
    rng = np.random.default_rng(lo_bit * 100 + hi_bit)
    n, W = 200_000, 24
    base = rng.integers(0, 1 << 16, size=(n, W)).astype(np.uint32)
    other = base.copy()
    cols = rng.permuted(np.tile(np.arange(W), (n, 1)), axis=1)[:, :3]
    span = hi_bit - lo_bit
    for j in range(3):
        delta = rng.integers(1, 1 << span, size=n).astype(np.uint32) \
            << np.uint32(lo_bit)
        np.put_along_axis(other, cols[:, j:j + 1],
                          np.take_along_axis(base, cols[:, j:j + 1], 1)
                          ^ delta[:, None], 1)
    consts = fpr.lane_constants(W)
    a = fpr.fingerprint(base.view(np.int32), consts, np)
    b = fpr.fingerprint(other.view(np.int32), consts, np)
    assert int(np.sum(a[0] == b[0])) == 0 and int(np.sum(a[1] == b[1])) == 0
    a1 = _scheme1(base.view(np.int32), consts)
    b1 = _scheme1(other.view(np.int32), consts)
    old = int(np.sum(a1[0] == b1[0])) + int(np.sum(a1[1] == b1[1]))
    expect = 2 * n / 2 ** (32 - lo_bit)
    assert old > 0.3 * expect      # the fault was real, at about this rate


def test_the_fold_is_a_bijection_on_words():
    x = np.arange(0, 1 << 32, 65521, dtype=np.uint64).astype(np.uint32)
    y = x ^ (x >> np.uint32(16))
    assert np.array_equal(y ^ (y >> np.uint32(16)), x)   # its own inverse


def test_a_snapshot_of_another_scheme_is_refused(monkeypatch):
    cfg = CheckConfig(bounds=B5, spec="full", invariants=("NoTwoLeaders",),
                      chunk=64)

    @dataclasses.dataclass(frozen=True)
    class Caps:
        block: int = 256

    now = ckpt.config_digest(cfg, Caps(), (1, 2))
    monkeypatch.setattr(fpr, "SCHEME", 1)
    assert ckpt.config_digest(cfg, Caps(), (1, 2)) != now


# -- the key taken from the fields (PR 27) -----------------------------------

def _layout_bounds(n, faithful):
    if faithful:
        return Bounds(n_servers=n, n_values=2, max_term=2, max_log=1,
                      max_msgs=2, history=True, max_elections=3)
    return Bounds(n_servers=n, n_values=2, max_term=2, max_log=1, max_msgs=2,
                  max_dup=1)


def _reachable_vecs(bounds, cap=160):
    frontier = [interp.init_state(bounds)]
    seen = list(frontier)
    while len(seen) < cap:
        nxt = []
        for s in frontier:
            if interp.constraint_ok(s, bounds):     # counted, not expanded
                nxt += [t for _a, t in interp.successors(s, bounds,
                                                         spec="full")]
        frontier = nxt[:60]
        seen += frontier
    return np.stack([interp.to_vec(s, bounds) for s in seen[:cap]])


@pytest.mark.parametrize("source", ["random", "reachable"])
@pytest.mark.parametrize("n", [3, 5])
@pytest.mark.parametrize("faithful", [False, True],
                         ids=["parity", "faithful"])
@pytest.mark.parametrize("backend", ["numpy", "jnp"])
def test_fingerprint_fields_equals_fingerprint_of_the_packed_row(
        backend, faithful, n, source):
    """``fingerprint_fields(s) == fingerprint(pack(s))`` bit for bit: the
    orbit scan keys from the fields (ops/symmetry.build_orbit_fp), every
    other site from the packed row, and the two must be one key."""
    bounds = _layout_bounds(n, faithful)
    lay = st.Layout.of(bounds)
    consts = fpr.lane_constants(lay.width)
    if source == "random":      # any int32: the fold sees high halves too
        rng = np.random.default_rng(lay.width * 10 + n)
        vecs = rng.integers(-2**31, 2**31, size=(96, lay.width),
                            dtype=np.int64).astype(np.int32)
    else:
        vecs = _reachable_vecs(bounds)
    want = fpr.fingerprint(vecs, consts, np)
    if backend == "numpy":
        xp, rows = np, vecs
    else:
        import jax.numpy as xp
        rows = xp.asarray(vecs)
    struct = st.unpack(rows, lay, xp)
    assert list(struct) == list(lay.fields)
    assert np.array_equal(np.asarray(st.pack(
        {f: a[0] for f, a in struct.items()}, xp)), vecs[0])
    got = fpr.fingerprint_fields(struct, consts, xp)           # batched
    assert got[0].dtype == xp.uint32 and got[0].shape == (len(vecs),)
    assert np.array_equal(np.asarray(got[0]), want[0])
    assert np.array_equal(np.asarray(got[1]), want[1])
    one = fpr.fingerprint_fields({f: a[7] for f, a in struct.items()},
                                 consts, xp)                   # one state
    assert (int(one[0]), int(one[1])) == (int(want[0][7]), int(want[1][7]))
