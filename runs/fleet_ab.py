"""Sharded-vs-solo walker-fleet A/B (fleet/engine.FleetSimulator) —
the deciding measurement for the ISSUE 11 tentpole.

Five arms, one pinned simulation config (flagship bounds, spec full,
1024 global walkers, depth 100, 64 steps/dispatch, seed 0):

- ``solo-legacy``: simulate.Simulator with the pre-PR per-dispatch host
  sync storm (one ``bool()``/``int()`` device round-trip per scalar);
- ``solo-fused``: same engine, single fused ``device_get`` per dispatch
  (satellite 1 — this delta isolates the sync-storm cost);
- ``fleet-1 / fleet-2 / fleet-4``: the shard_mapped fleet over 1/2/4
  virtual CPU devices (XLA host-platform device count, set before jax
  import), same global walker count split over the mesh.

Protocol (r3/r4): warm every arm first (compile excluded), then REPS
interleaved rounds (arm order rotates per round so chip weather hits
all arms equally), median wall per arm; chip-state fiducials via
``bench.py --fiducial`` bracket the session.  Parity asserted:

- the three fleet arms must agree BIT-FOR-BIT on (n_behaviors,
  n_states, max_depth_seen, coverage) — the device-count-invariance
  contract;
- solo fused vs legacy must agree exactly (same walks, different
  fetch);
- solo vs fleet agree on states (walkers x depth completes either way)
  but not behaviors (different PRNG stream layouts — documented, not
  asserted equal).

Verdict gate: fleet-2 >= 1.6x fleet-1 sustained states/s.  On a
single-core container the XLA CPU mesh arms share one core, so an
honest refutation here is the expected outcome (same protocol as the
megakernel CPU refutation); the gate is for real multi-device parts.

Usage: python runs/fleet_ab.py [reps] [behaviors]
Artifact: appends one JSON line to runs/fleet_ab.out
(RESULTS.md "Fleet scaling A/B").
"""
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Virtual mesh must exist before any jax import touches a backend.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "host_platform_device_count" not in f]
os.environ["XLA_FLAGS"] = " ".join(
    _flags + ["--xla_force_host_platform_device_count=4"])

import jax

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.fleet import FleetSimulator
from raft_tla_tpu.parallel.mesh import make_mesh
from raft_tla_tpu.simulate import Simulator

_ints = [int(a) for a in sys.argv[1:] if a.isdigit()]
REPS = _ints[0] if _ints else 3
N_BEH = _ints[1] if len(_ints) > 1 else 4096

CFG = CheckConfig(
    bounds=Bounds(n_servers=3, n_values=2, max_term=2, max_log=1,
                  max_msgs=2, max_dup=1),
    spec="full", invariants=("NoTwoLeaders", "LogMatching"))
WALKERS, DEPTH, STEPS, SEED = 1024, 100, 64, 0


def _fiducial():
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench.py")
    try:
        out = subprocess.run(
            [sys.executable, bench, "--fiducial"], capture_output=True,
            text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}).stdout
        return json.loads(out.strip().splitlines()[-1])
    except Exception as e:                       # evidence, not a gate
        return {"fiducial_error": repr(e)}


def _key(res):
    """The bit-reproducibility fingerprint of one run."""
    return (res.n_behaviors, res.n_states, res.max_depth_seen)


arms = {
    "solo-legacy": Simulator(CFG, walkers=WALKERS, depth=DEPTH,
                             steps_per_dispatch=STEPS, seed=SEED,
                             fetch="legacy"),
    "solo-fused": Simulator(CFG, walkers=WALKERS, depth=DEPTH,
                            steps_per_dispatch=STEPS, seed=SEED),
}
for nd in (1, 2, 4):
    arms[f"fleet-{nd}"] = FleetSimulator(
        CFG, mesh=make_mesh(nd), walkers=WALKERS, depth=DEPTH,
        steps_per_dispatch=STEPS, seed=SEED)

results = {"platform": jax.devices()[0].platform,
           "n_host_devices": len(jax.devices()),
           "reps": REPS, "behaviors": N_BEH, "walkers": WALKERS,
           "depth": DEPTH, "steps_per_dispatch": STEPS, "seed": SEED,
           "arms": {}}
results["fiducial_start"] = _fiducial()
print("fiducial_start:", json.dumps(results["fiducial_start"]),
      flush=True)

keys, walls = {}, {name: [] for name in arms}
for name, sim in arms.items():                    # warm: compile + walks
    keys[name] = _key(sim.run(N_BEH))
    print(f"warm {name:12} -> beh/states/depth {keys[name]}", flush=True)

order = list(arms)
for rep in range(REPS):
    for name in order[rep % len(order):] + order[:rep % len(order)]:
        t0 = time.monotonic()
        res = arms[name].run(N_BEH)
        walls[name].append(time.monotonic() - t0)
        assert _key(res) == keys[name], \
            f"{name}: rep {rep} diverged from warm run"

for name in arms:
    ws = sorted(walls[name])
    wall = ws[len(ws) // 2]
    nb, ns, md = keys[name]
    results["arms"][name] = {
        "wall_s_median": round(wall, 3), "wall_s_all": [
            round(w, 3) for w in walls[name]],
        "n_behaviors": nb, "n_states": ns, "max_depth": md,
        "states_per_sec": round(ns / max(wall, 1e-9), 1)}
    print(f"{name:12} median {wall:7.3f} s  {ns} states  "
          f"({ns / max(wall, 1e-9):,.0f} states/s)", flush=True)

# -- parity gates ----------------------------------------------------------
assert keys["fleet-1"] == keys["fleet-2"] == keys["fleet-4"], \
    "device-count invariance violated: fleet arms disagree"
assert keys["solo-legacy"] == keys["solo-fused"], \
    "fetch-path parity violated: fused and legacy solo runs disagree"
results["fleet_bit_identical_1_2_4"] = True
results["solo_fetch_parity"] = True

r = results["arms"]
results["fleet2_vs_fleet1"] = round(
    r["fleet-2"]["states_per_sec"] / r["fleet-1"]["states_per_sec"], 3)
results["fleet4_vs_fleet1"] = round(
    r["fleet-4"]["states_per_sec"] / r["fleet-1"]["states_per_sec"], 3)
results["fused_vs_legacy"] = round(
    r["solo-fused"]["states_per_sec"]
    / r["solo-legacy"]["states_per_sec"], 3)
results["pass_ge_1.6x_at_2dev"] = results["fleet2_vs_fleet1"] >= 1.6
print(f"scaling: fleet-2 {results['fleet2_vs_fleet1']}x, fleet-4 "
      f"{results['fleet4_vs_fleet1']}x vs fleet-1; fused fetch "
      f"{results['fused_vs_legacy']}x vs legacy; 2-device >=1.6x gate: "
      f"{'PASS' if results['pass_ge_1.6x_at_2dev'] else 'REFUTED'}",
      flush=True)

results["fiducial_end"] = _fiducial()
print("fiducial_end:", json.dumps(results["fiducial_end"]), flush=True)
line = json.dumps(results)
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fleet_ab.out"), "a") as fh:
    fh.write(line + "\n")
print(line)
