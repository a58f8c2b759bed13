"""The engines under SYMMETRY: each reproduces the symmetry-reduced oracle
exactly, and violations still surface with replayable traces.  Below
them, the device's form of the orbit key's linear sums in NumPy alone
(``test_symmetry.py`` has the scan itself).

The two files are cut so that each holds twenty or more tests and one
half of the long oracle runs: the driver's ``--dist loadfile`` hands
files to its workers by test count, most first, so a file of one long
test would start last.
"""

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.device_engine import Capacities, DeviceEngine
from raft_tla_tpu.models import interp, refbfs, spec as S
from raft_tla_tpu.ops import msgbits as mb
from raft_tla_tpu.ops import symmetry as sym
from symmetry_cases import _B3S, _ELECT5, _FULL5, B2, B3, bag


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


def test_value_symmetry_engine_parity():
    from raft_tla_tpu import engine
    bp = Bounds(n_servers=2, n_values=2, max_term=2, max_log=1, max_msgs=2)
    cfg = CheckConfig(bounds=bp, spec="full", invariants=("NoTwoLeaders",),
                      symmetry=("Server", "Value"), chunk=512)
    ref = refbfs.check(cfg)
    got = engine.check(cfg)
    assert (got.n_states, got.diameter) == (ref.n_states, ref.diameter)
    assert got.coverage == ref.coverage and got.violation is None


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
