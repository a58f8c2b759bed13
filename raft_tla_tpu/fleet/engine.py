"""The sharded walker-fleet engine: simulation as a mesh workload.

Random-walk checking is embarrassingly parallel — the cheapest path to
"as fast as the hardware allows" on any mesh — so the fleet engine
shard_maps the jitted walk segment over a 1-D device mesh and keeps the
host out of the loop: one fused device->host fetch of a few per-device
scalars per segment, walker/history buffers donated between dispatches.

Device-count invariance (the contract the tests pin):

- every walker owns a PRNG stream derived only from its GLOBAL id and
  the global step index — ``fold_in(fold_in(root, gid), step)`` — never
  from which device hosts it or how many devices exist;
- there is no early stop inside a segment: a violating or deadlocked
  walker freezes individually (its history stays replayable) while the
  rest of the fleet keeps walking, so every counter is a sum of
  per-walker terms, order-independent under resharding;
- the reported violation is the lexicographic minimum over
  (global step, global walker id) of all frozen walkers — computed as a
  per-device minimum plus a host-side merge, which equals the global
  minimum for any partitioning.

Hence the same (seed, walkers, depth, steps_per_dispatch) produces
bit-identical walks, counters and violation traces on 1, 2, or N
devices — the property that makes a fleet result auditable after a
mesh resize.

Steering (off by default): per-action visit counters are aggregated
across the mesh at segment boundaries, and the NEXT segment biases its
categorical lane sampling against over-visited actions with
``logits -= tau * log1p(count / mean_count)``.  Lanes are still
recorded, so exact replay is preserved; ``tau`` is a sampling policy
knob, not a spec change (enabledness is untouched).  Scenario weights
(``fault_weights``) multiply lane probabilities per action family the
same way.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from raft_tla_tpu.config import CheckConfig
from raft_tla_tpu.engine import DEADLOCK, Violation
from raft_tla_tpu.parallel.mesh import _AXIS, make_mesh
from raft_tla_tpu.simulate import resolve_sim_model

I32 = jnp.int32
F32 = jnp.float32
BIG = np.iinfo(np.int32).max


@dataclasses.dataclass
class FleetResult:
    """What a fleet run established — statistical, so the confidence
    block (states checked per invariant, coverage entropy) travels with
    the counts instead of masquerading as an exhaustive proof."""

    n_behaviors: int         # completed behaviors across the fleet
    n_states: int            # sampled transitions (states generated)
    max_depth_seen: int
    violation: Optional[Violation]
    wall_s: float
    n_devices: int
    walkers: int
    steer_tau: float
    coverage: dict           # action family -> sampled-transition count
    coverage_entropy: float  # normalized entropy of the action histogram
    device_states: list      # per-device sampled transitions (cumulative)
    walks: Optional[tuple] = None   # (hist, hlen) np arrays on request

    @property
    def states_per_sec(self) -> float:
        return self.n_states / self.wall_s if self.wall_s > 0 else float("inf")

    def confidence(self, invariants=()) -> dict:
        """The run_end ``sim`` payload (obs schema v3)."""
        return {
            "sampled_transitions": self.n_states,
            "max_depth": self.max_depth_seen,
            "walkers": self.walkers,
            "n_devices": self.n_devices,
            "coverage_entropy": round(self.coverage_entropy, 4),
            "steer_tau": self.steer_tau,
            "per_invariant": {nm: self.n_states for nm in invariants},
        }


def _coverage_entropy(counts: np.ndarray) -> float:
    """Normalized Shannon entropy of the per-action visit histogram:
    1.0 = uniform over all A lanes, 0.0 = a single lane (or no data)."""
    total = float(counts.sum())
    if total <= 0 or len(counts) < 2:
        return 0.0
    p = counts[counts > 0] / total
    return float(-(p * np.log(p)).sum() / math.log(len(counts)))


def _build_fleet_segment(config: CheckConfig, model, mesh, walkers: int,
                         depth: int, steps: int, W: int, A: int,
                         steer_tau: float):
    """One sharded dispatch: every device advances its walker shard by
    ``steps`` lockstep steps; returns updated walker shards plus small
    per-device summaries (one host fetch covers them all)."""
    bounds = config.bounds
    n_inv = len(config.invariants)
    expand = model.build_sim_expand(config)
    inv_fns = list(model.jnp_invariants(config))
    con_fn = model.jnp_constraint(bounds)
    _w, pack, unpack = model.sim_codec(bounds)
    ndev = mesh.devices.size
    B = walkers // ndev          # walkers per device
    BIGJ = jnp.int32(BIG)

    def device_seg(root_key, seg_base, cov, wvec, init_vec,
                   vecs, hist, hlen, viol_step, viol_inv, dead_step):
        # local (per-device) shapes: vecs[B, W], hist[B, depth], hlen[B].
        d = jax.lax.axis_index(_AXIS).astype(I32)
        gid = d * B + jnp.arange(B, dtype=I32)      # global walker ids
        # per-walker streams from the one root key: device-layout free
        wkeys = jax.vmap(lambda g: jax.random.fold_in(root_key, g))(gid)

        # static-per-segment sampling policy: scenario weights, then the
        # coverage-steering bias from SEGMENT-START global counts (the
        # same replicated input on every device, so fleets of any shape
        # compute the same logits).
        logw = jnp.where(wvec > 0,
                         jnp.log(jnp.maximum(wvec, 1e-30)), -jnp.inf)
        if steer_tau:            # python float; 0.0 compiles steering out
            r = cov / jnp.maximum(jnp.mean(cov), 1.0)
            logw = logw - F32(steer_tau) * jnp.log1p(r)
        init_b = jnp.broadcast_to(init_vec, (B, W))
        rows = jnp.arange(B)

        def one_step(i, carry):
            (vecs, hist, hlen, viol_step, viol_inv, dead_step,
             d_beh, d_st, maxd, cov_d, fail) = carry
            step_idx = (seg_base + i).astype(I32)
            keys = jax.vmap(
                lambda k: jax.random.fold_in(k, step_idx))(wkeys)
            structs = jax.vmap(unpack)(vecs)
            succs, valid, ovf = jax.vmap(expand)(structs)   # [B, A, ...]
            frozen = (viol_step < BIGJ) | (dead_step < BIGJ)

            logits = jnp.where(valid, logw[None, :], -jnp.inf)
            # weights are sampling policy, not spec: when every weighted
            # lane is disabled but some lane is valid, fall back to
            # uniform-over-valid instead of declaring a false deadlock.
            any_w = jnp.any(jnp.isfinite(logits), axis=-1)
            logits = jnp.where(any_w[:, None], logits,
                               jnp.where(valid, 0.0, -jnp.inf))
            lane = jax.vmap(jax.random.categorical)(keys, logits) \
                .astype(I32)
            enabled = jnp.any(valid, axis=-1)
            lane = jnp.where(enabled, lane, 0)
            live = enabled & ~frozen
            pick_s = jax.tree.map(lambda x: x[rows, lane], succs)
            pick = jax.vmap(pack)(pick_s)
            con_ok = jax.vmap(con_fn)(pick_s)
            # overflow on a taken lane is a soundness bug — loud abort
            fail = fail | jnp.any(live & ovf[rows, lane])
            if inv_fns:
                inv_ok = jnp.stack([jax.vmap(f)(pick_s) for f in inv_fns],
                                   axis=-1)                 # [B, nI]
            else:
                inv_ok = jnp.ones((B, 0), bool)

            # stuck: no enabled action at all on a live walker
            stuck = ~enabled & ~frozen
            if config.check_deadlock:
                new_dead = stuck & (dead_step == BIGJ)
                dead_step = jnp.where(new_dead, step_idx, dead_step)
            # invariant violation: the walker freezes individually (no
            # fleet-wide early stop — statistics stay device-invariant)
            bad = live & jnp.any(~inv_ok, axis=-1)
            new_viol = bad & (viol_step == BIGJ)
            viol_step = jnp.where(new_viol, step_idx, viol_step)
            first_inv = (jnp.argmax(~inv_ok, axis=-1).astype(I32)
                         if n_inv else jnp.zeros((B,), I32))
            viol_inv = jnp.where(new_viol, first_inv, viol_inv)

            hist = jnp.where(
                live[:, None]
                & (jnp.arange(depth)[None, :] == hlen[:, None]),
                lane[:, None], hist)
            hlen2 = jnp.where(live, hlen + 1, hlen)
            maxd = jnp.maximum(maxd, jnp.max(hlen2))
            d_st = d_st + jnp.sum(live.astype(I32))
            cov_d = cov_d.at[lane].add(live.astype(I32))

            # behavior end: depth bound, constraint-violating successor,
            # or (without check_deadlock) a stuck walker; frozen walkers
            # keep their state and history for replay.
            frozen2 = (viol_step < BIGJ) | (dead_step < BIGJ)
            done = ~frozen2 & ((live & (~con_ok | (hlen2 >= depth)))
                               | stuck)
            d_beh = d_beh + jnp.sum(done.astype(I32))
            vecs2 = jnp.where(
                frozen2[:, None], vecs,
                jnp.where(done[:, None], init_b,
                          jnp.where(live[:, None], pick, vecs)))
            hlen3 = jnp.where(frozen2, hlen2, jnp.where(done, 0, hlen2))
            return (vecs2, hist, hlen3, viol_step, viol_inv, dead_step,
                    d_beh, d_st, maxd, cov_d, fail)

        carry = (vecs, hist, hlen, viol_step, viol_inv, dead_step,
                 jnp.int32(0), jnp.int32(0), jnp.int32(0),
                 jnp.zeros((A,), I32), jnp.bool_(False))
        (vecs, hist, hlen, viol_step, viol_inv, dead_step,
         d_beh, d_st, maxd, cov_d, fail) = jax.lax.fori_loop(
            0, steps, one_step, carry)

        # per-device violation winner: min (step, gid) — merged with the
        # other devices' minima on the host into the global minimum
        vmin = jnp.min(viol_step)
        vgid = jnp.min(jnp.where(viol_step == vmin, gid, BIGJ))
        vidx = jnp.argmin(jnp.where(viol_step == vmin, gid, BIGJ))
        vinv = viol_inv[vidx]
        dmin = jnp.min(dead_step)
        dgid = jnp.min(jnp.where(dead_step == dmin, gid, BIGJ))

        one = lambda x: jnp.reshape(x, (1,))        # noqa: E731
        return (vecs, hist, hlen, viol_step, viol_inv, dead_step,
                one(d_beh), one(d_st), one(maxd),
                jnp.reshape(cov_d, (1, A)), one(fail),
                one(vmin), one(vgid), one(vinv), one(dmin), one(dgid))

    shard = P(_AXIS)
    shard2 = P(_AXIS, None)
    repl = P()
    seg = jax.shard_map(
        device_seg, mesh=mesh,
        in_specs=(repl, repl, repl, repl, repl,
                  shard2, shard2, shard, shard, shard, shard),
        out_specs=(shard2, shard2, shard, shard, shard, shard,
                   shard, shard, shard, shard2, shard,
                   shard, shard, shard, shard, shard),
        # like every sharded engine here: the walk loop's carry mixes
        # replicated initial values with per-device updates, which
        # jax.shard_map's varying-axes check (JAX 0.9) refuses to infer
        check_vma=False)
    # Donate the walker shards (args 5-10): off-CPU each dispatch then
    # reuses the buffers in place.  (CPU has no donation; gate it off
    # there to keep virtual-mesh runs warning-free.)
    donate = () if jax.default_backend() == "cpu" else tuple(range(5, 11))
    return jax.jit(seg, donate_argnums=donate)


class FleetSimulator:
    """Sharded batched random-behavior generator over a device mesh.

    ``walkers`` is the GLOBAL fleet size and must divide evenly over the
    mesh; results are a pure function of (seed, walkers, depth,
    steps_per_dispatch) — never of the mesh shape.  ``steer_tau`` > 0
    turns on coverage steering; ``fault_weights`` maps action-family
    names to sampling weights (missing families weigh 1.0).
    """

    def __init__(self, config: CheckConfig, mesh=None, walkers: int = 1024,
                 depth: int = 100, steps_per_dispatch: int = 64,
                 seed: int = 0, steer_tau: float = 0.0,
                 fault_weights: dict | None = None):
        if config.symmetry:
            raise ValueError("simulation mode ignores SYMMETRY; run without")
        self.config = config
        self.bounds = config.bounds
        self.model = resolve_sim_model(config)
        self.mesh = mesh if mesh is not None else make_mesh(None)
        if tuple(self.mesh.axis_names) != (_AXIS,):
            raise ValueError(
                f"fleet needs a 1-D ({_AXIS!r},) mesh "
                f"(got axes {self.mesh.axis_names}); slice meshes carry "
                "no benefit for independent walkers")
        self.n_devices = self.mesh.devices.size
        if walkers % self.n_devices:
            raise ValueError(
                f"walkers ({walkers}) must divide evenly over the mesh "
                f"({self.n_devices} devices); try "
                f"{walkers - walkers % self.n_devices} or "
                f"{walkers + self.n_devices - walkers % self.n_devices}")
        self.width, _pack, _unpack = self.model.sim_codec(self.bounds)
        self.table = self.model.action_table(self.bounds)
        self.A = len(self.table)
        self.walkers = walkers
        self.depth = depth
        self.steps = steps_per_dispatch
        self.seed = seed
        self.steer_tau = float(steer_tau)
        self.fault_weights = dict(fault_weights or {})
        self._weight_vec(None)       # validate constructor weights loudly
        self._segment = _build_fleet_segment(
            config, self.model, self.mesh, walkers, depth, self.steps,
            self.width, self.A, self.steer_tau)

    def _weight_vec(self, fault_weights: dict | None) -> np.ndarray:
        """Family-weight dict -> per-lane f32 vector, validated loudly."""
        fw = self.fault_weights if fault_weights is None else fault_weights
        fams = {a.family for a in self.table}
        unknown = sorted(set(fw) - fams)
        if unknown:
            raise ValueError(
                f"unknown action families {unknown} for spec "
                f"{self.config.spec!r} (known: {', '.join(sorted(fams))})")
        bad = sorted(f for f, w in fw.items() if w < 0)
        if bad:
            raise ValueError(f"negative fault weights for {bad}")
        return np.asarray([fw.get(a.family, 1.0) for a in self.table],
                          dtype=np.float32)

    def run(self, n_behaviors: int, init_override=None,
            max_wall_s: float | None = None, on_progress=None,
            events: str | None = None, fault_weights: dict | None = None,
            snapshot_walks: bool = False) -> FleetResult:
        t0 = time.monotonic()
        from raft_tla_tpu.obs import RunTelemetry
        tel = RunTelemetry("fleet", config=self.config,
                           on_progress=on_progress, events=events,
                           n_devices=self.n_devices, t0=t0)
        bounds = self.bounds
        init_py = init_override if init_override is not None \
            else self.model.init_py(bounds)
        init_vec = self.model.to_vec(init_py, bounds)
        tel.run_start()
        for nm in self.config.invariants:
            if not self.model.py_invariant(nm)(init_py, bounds):
                res = self._result(
                    0, 1, 0, Violation(nm, init_py, [(None, init_py)]),
                    t0, np.zeros(self.A, np.int64),
                    [0] * self.n_devices)
                self._end(tel, res, complete=True)
                return res

        wvec = jnp.asarray(self._weight_vec(fault_weights))
        root = jax.random.PRNGKey(self.seed)
        iv = jnp.asarray(init_vec, I32)
        vecs = jnp.broadcast_to(iv, (self.walkers, self.width))
        hist = jnp.zeros((self.walkers, self.depth), I32)
        hlen = jnp.zeros((self.walkers,), I32)
        viol_step = jnp.full((self.walkers,), BIG, I32)
        viol_inv = jnp.zeros((self.walkers,), I32)
        dead_step = jnp.full((self.walkers,), BIG, I32)
        cov_total = np.zeros(self.A, np.int64)
        dev_states = [0] * self.n_devices
        nb = nst = mx = 0
        base = 0
        complete = True
        while True:
            seg_t0 = time.monotonic()
            (vecs, hist, hlen, viol_step, viol_inv, dead_step,
             d_beh, d_st, maxd, cov_d, fail,
             vmin, vgid, vinv, dmin, dgid) = self._segment(
                root, jnp.int32(base), jnp.asarray(cov_total, F32),
                wvec, iv, vecs, hist, hlen, viol_step, viol_inv,
                dead_step)
            # ONE device->host fetch per segment: every per-device
            # summary lands in a single blocking transfer.
            (d_beh, d_st, maxd, cov_d, fail,
             vmin, vgid, vinv, dmin, dgid) = jax.device_get(
                (d_beh, d_st, maxd, cov_d, fail,
                 vmin, vgid, vinv, dmin, dgid))
            base += self.steps
            seg_wall = max(time.monotonic() - seg_t0, 1e-9)
            nb += int(d_beh.sum())
            nst += int(d_st.sum())
            mx = max(mx, int(maxd.max()))
            cov_total += cov_d.sum(axis=0).astype(np.int64)
            dev_states = [a + int(b) for a, b in zip(dev_states, d_st)]
            if fail.any():
                tel.stop_requested("tensor-encoding overflow",
                                   source="fleet")
                tel.close()
                raise RuntimeError(
                    "fleet simulation aborted: a sampled transition "
                    "overflowed the tensor encoding — bounds reasoning "
                    "violated (config.py capacity scheme)")
            if tel.active:
                tel.segment(nst, mx, nst,
                            device_rates=[round(float(s) / seg_wall, 1)
                                          for s in d_st])
            if int(vmin.min()) < BIG or int(dmin.min()) < BIG:
                viol = int(vmin.min()) < BIG
                steps_arr = vmin if viol else dmin
                gids_arr = vgid if viol else dgid
                smin = int(steps_arr.min())
                # global lexicographic-min (step, gid) winner
                cand = [(int(gids_arr[i]), i)
                        for i in range(self.n_devices)
                        if int(steps_arr[i]) == smin]
                w, dev = min(cand)
                name = (self.config.invariants[int(vinv[dev])]
                        if viol else DEADLOCK)
                trace = self._replay(init_py, np.asarray(hist[w]),
                                     int(hlen[w]))
                res = self._result(
                    nb, nst, mx,
                    Violation(name, trace[-1][1], trace),
                    t0, cov_total, dev_states)
                if snapshot_walks:
                    res.walks = (np.asarray(hist), np.asarray(hlen))
                self._end(tel, res, complete=True)
                return res
            if nb >= n_behaviors:
                break
            if max_wall_s is not None and \
                    time.monotonic() - t0 > max_wall_s:
                complete = False     # wall-bounded partial run
                break
        res = self._result(nb, nst, mx, None, t0, cov_total, dev_states)
        if snapshot_walks:
            res.walks = (np.asarray(hist), np.asarray(hlen))
        self._end(tel, res, complete=complete)
        return res

    def _result(self, nb, nst, mx, violation, t0, cov_total,
                dev_states) -> FleetResult:
        by_family: dict = {}
        for inst, cnt in zip(self.table, cov_total):
            by_family[inst.family] = by_family.get(inst.family, 0) \
                + int(cnt)
        return FleetResult(
            n_behaviors=nb, n_states=nst, max_depth_seen=mx,
            violation=violation, wall_s=time.monotonic() - t0,
            n_devices=self.n_devices, walkers=self.walkers,
            steer_tau=self.steer_tau, coverage=by_family,
            coverage_entropy=_coverage_entropy(np.asarray(cov_total)),
            device_states=list(dev_states))

    def _end(self, tel, res: FleetResult, complete: bool) -> None:
        tel.run_end_sim(
            n_states=res.n_states, n_behaviors=res.n_behaviors,
            max_depth=res.max_depth_seen, wall_s=res.wall_s,
            complete=complete, violation=res.violation,
            sim=res.confidence(self.config.invariants))
        tel.close()

    def _replay(self, init_py, lanes: np.ndarray, hlen: int) -> list:
        """Rebuild the winning walk exactly through the model's host
        interpreter (same contract as the solo simulator)."""
        chain = [(None, init_py)]
        cur = init_py
        for k in range(hlen):
            a = self.table[int(lanes[k])]
            nxt = self.model.host_apply(cur, a, self.bounds)
            assert nxt is not None, \
                "recorded lane must be enabled on replay"
            chain.append((a.label(), nxt))
            cur = nxt
        return chain
