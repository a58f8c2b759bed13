"""Kernel/interpreter differential — SURVEY §4.2.

Every successor lane of the batched JAX kernel must agree with the reference
interpreter: same enabledness, same canonical successor state, on (a) random
bounded states (including unreachable corners like same-term leaders) and
(b) exact reachable prefixes from Init.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.models import interp, spec as SP
from raft_tla_tpu.ops import kernels, state as st

from test_state import random_pystate

B3 = Bounds(n_servers=3, n_values=2, max_term=3, max_log=2, max_msgs=4)


def _diff_on_states(states, bounds, spec="full"):
    table = SP.action_table(bounds, spec)
    expand = jax.jit(jax.vmap(kernels.build_expand(bounds, spec)))
    structs = [interp.to_struct(s, bounds) for s in states]
    batch = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *structs)
    succs, valid, ovf = expand(batch)
    succs = jax.tree.map(np.asarray, succs)
    valid = np.asarray(valid)
    ovf = np.asarray(ovf)

    for bi, s in enumerate(states):
        # The +1 capacity scheme guarantees representability only one step
        # past the constraint: overflow must never fire on states the engine
        # would actually expand (constraint-satisfying ones).  Faithful mode
        # is the exception: elections capacity is not constraint-governed
        # (config.py), so its genuineness is checked per-lane below instead.
        if interp.constraint_ok(s, bounds) and not bounds.history:
            assert not ovf[bi].any(), f"overflow on expandable state {s}"
        got_by_lane = {}
        for ai in range(len(table)):
            if valid[bi, ai] and not ovf[bi, ai]:
                lane = jax.tree.map(lambda x: x[bi, ai], succs)
                got_by_lane[ai] = interp.from_struct(lane, bounds)
        want_by_lane = dict(interp.successors(s, bounds, table))
        for ai in range(len(table)):
            if valid[bi, ai] and ovf[bi, ai]:
                # Lane flagged unrepresentable: the interpreter successor must
                # genuinely exceed tensor capacity (bag, log, or — in
                # faithful mode — elections slots).
                t = want_by_lane.pop(ai)
                assert len(t.msgs) > bounds.msg_cap or \
                    any(len(l) > bounds.log_cap for l in t.log) or \
                    (t.elections is not None
                     and len(t.elections) > bounds.max_elections)
        assert set(got_by_lane) == set(want_by_lane), (
            f"state {bi}: enabled lanes differ\n"
            f"kernel-only: {[table[a].label() for a in set(got_by_lane) - set(want_by_lane)]}\n"
            f"interp-only: {[table[a].label() for a in set(want_by_lane) - set(got_by_lane)]}\n"
            f"state: {s}")
        for ai, got in got_by_lane.items():
            assert got == want_by_lane[ai], (
                f"state {bi} lane {table[ai].label()}:\n"
                f"kernel: {got}\ninterp: {want_by_lane[ai]}\nfrom:   {s}")


def test_differential_random_states():
    rng = np.random.default_rng(7)
    states = [random_pystate(rng, B3) for _ in range(200)]
    _diff_on_states(states, B3)


def test_differential_reachable_prefix():
    bounds = Bounds(n_servers=3, n_values=1, max_term=2, max_log=1,
                    max_msgs=2)
    seen = {interp.init_state(bounds)}
    frontier = list(seen)
    for _level in range(4):
        nxt = []
        for s in frontier:
            if not interp.constraint_ok(s, bounds):
                continue
            for _a, t in interp.successors(s, bounds):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    states = sorted(seen, key=lambda s: interp.to_vec(s, bounds).tobytes())
    _diff_on_states(states[:400], bounds)


def test_differential_election_spec():
    bounds = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                    max_msgs=3)
    rng = np.random.default_rng(11)
    states = [random_pystate(rng, bounds) for _ in range(100)]
    _diff_on_states(states, bounds, spec="election")


def test_step_outputs_consistent():
    """build_step: fingerprints/invariants/constraints agree with host."""
    from raft_tla_tpu.ops import fingerprint as fpr
    from raft_tla_tpu.models import invariants as inv_mod

    bounds = B3
    lay = st.Layout.of(bounds)
    rng = np.random.default_rng(13)
    states = [random_pystate(rng, bounds) for _ in range(32)]
    vecs = np.stack([interp.to_vec(s, bounds) for s in states])
    step = jax.jit(kernels.build_step(bounds, "full",
                                      ("NoTwoLeaders", "LogMatching")))
    out = {k: np.asarray(v) for k, v in step(jnp.asarray(vecs)).items()}

    consts = fpr.lane_constants(lay.width)
    h1, h2 = fpr.fingerprint(out["svecs"], consts, np)
    np.testing.assert_array_equal(h1, out["fp_hi"])
    np.testing.assert_array_equal(h2, out["fp_lo"])

    es = inv_mod.py_invariant("NoTwoLeaders")
    lm = inv_mod.py_invariant("LogMatching")
    for bi in range(len(states)):
        for ai in range(out["valid"].shape[1]):
            if not out["valid"][bi, ai] or out["overflow"][bi, ai]:
                continue
            t = interp.from_struct(
                st.unpack(out["svecs"][bi, ai], lay, np), bounds)
            assert out["inv_ok"][bi, ai, 0] == es(t, bounds)
            assert out["inv_ok"][bi, ai, 1] == lm(t, bounds)
            assert out["con_ok"][bi, ai] == interp.constraint_ok(t, bounds)


def test_differential_5server_north_star_universe():
    """The north-star universe (BASELINE config #4: 5 servers, 2 values,
    default bounds): the 90-lane action table and kernels must agree with
    the interpreter on random bounded states, incl. the wider
    bitmask/quorum arithmetic and every message slot."""
    bounds = Bounds(n_servers=5, n_values=2, max_term=3, max_log=2,
                    max_msgs=4)
    table = SP.action_table(bounds, "full")
    assert len(table) == 5 + 5 + 25 + 5 + 10 + 5 + 20 + 3 * bounds.msg_cap
    rng = np.random.default_rng(21)
    states = [random_pystate(rng, bounds) for _ in range(24)]
    states.append(interp.init_state(bounds))
    _diff_on_states(states, bounds, "full")


# the benchmark's five-server bounds (benchmark/configs/elect5.json,
# full5.json): the routed step is what `full5.passes`' 5.7 %-live dense
# step is to be judged against (PERF.md section 7)
_ROUTED_CASES = {
    "3s-full": (B3, "full", ("NoTwoLeaders", "LogMatching"), 16),
    "elect5": (Bounds(n_servers=5, n_values=2, max_term=2, max_log=0,
                      max_msgs=2, max_dup=1), "election",
               ("NoTwoLeaders",), 8),
    "full5": (Bounds(n_servers=5, n_values=2, max_term=2, max_log=1,
                     max_msgs=2, max_dup=1), "full",
              ("NoTwoLeaders", "LogMatching"), 8),
}


@pytest.mark.parametrize("case", list(_ROUTED_CASES))
def test_routed_step_matches_dense(case):
    """build_step_routed (EP routing, SURVEY §2.9): the compacted stream
    is exactly the dense step's valid lanes, in flat order, with
    identical per-candidate values — and the budget overflow is loud."""
    bounds, spec, invs, n_states = _ROUTED_CASES[case]
    rng = np.random.default_rng(17)
    states = [random_pystate(rng, bounds) for _ in range(n_states)]
    vecs = jnp.asarray(np.stack([interp.to_vec(s, bounds) for s in states]))
    for sym in ((), ("Server",)):
        dense = jax.jit(kernels.build_step(bounds, spec, invs,
                                           sym))(vecs)
        A = dense["valid"].shape[1]
        N = len(states) * A
        routed = jax.jit(kernels.build_step_routed(
            bounds, spec, invs, sym, k_rows=N))(vecs)
        np.testing.assert_array_equal(dense["valid"], routed["valid"])
        np.testing.assert_array_equal(dense["overflow"],
                                      routed["overflow"])
        fvalid = np.asarray(dense["valid"]).reshape(-1)
        en = np.flatnonzero(fvalid)
        cidx = np.asarray(routed["cidx"])
        assert np.asarray(routed["cvalid"]).sum() == en.size
        np.testing.assert_array_equal(cidx[:en.size], en)
        assert (cidx[en.size:] == N).all()
        assert not bool(routed["route_ovf"])
        W = dense["svecs"].shape[-1]
        np.testing.assert_array_equal(
            np.asarray(routed["csvecs"])[:en.size],
            np.asarray(dense["svecs"]).reshape(N, W)[en])
        for dk, rk in (("fp_hi", "cfp_hi"), ("fp_lo", "cfp_lo"),
                       ("con_ok", "ccon_ok")):
            np.testing.assert_array_equal(
                np.asarray(routed[rk])[:en.size],
                np.asarray(dense[dk]).reshape(N)[en])
        np.testing.assert_array_equal(
            np.asarray(routed["cinv_ok"])[:en.size],
            np.asarray(dense["inv_ok"]).reshape(N, len(invs))[en])
    # a budget below the enabled count must flag, never silently drop
    tight = jax.jit(kernels.build_step_routed(
        bounds, spec, invs, k_rows=max(1, en.size // 2)))(vecs)
    assert bool(tight["route_ovf"])
    # row_ok: dead rows (stale padding / constraint-excluded parents)
    # must not consume routing slots — only live rows' lanes compact
    row_ok = np.arange(len(states)) % 2 == 0
    masked = jax.jit(kernels.build_step_routed(
        bounds, spec, invs, k_rows=N))(vecs, jnp.asarray(row_ok))
    np.testing.assert_array_equal(masked["valid"], dense["valid"])
    live = fvalid & np.repeat(row_ok, A)
    en_live = np.flatnonzero(live)
    assert np.asarray(masked["cvalid"]).sum() == en_live.size
    np.testing.assert_array_equal(
        np.asarray(masked["cidx"])[:en_live.size], en_live)
