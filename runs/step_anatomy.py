"""VERDICT r4 weak #2 / next #3: post-filter step anatomy.

The non-filter ~90% of the per-chunk step (31.3 ms of 34.5 ms at
flagship shapes, runs/filter_inengine.out "none" ablation) has had no
breakdown since round 2.  This ablates the fused step at the flagship
shape (3s/2v t2/l1/m2, SYMMETRY Server, chunk 4096) by DCE-fetching
output subsets and by rebuilding with stages removed:

  full        every output fetched (the engine's real program)
  no-inv      invariants=() rebuild           -> invariant-lane share
  fp-only     fetch (valid, fp) only          -> svecs-pack share (DCE)
  valid-only  fetch valid only                -> fingerprint+canon share
  no-sym      symmetry=() rebuild, fetch all  -> orbit-scan share
              (counts differ — this is a COST ablation, not a
              semantics-preserving variant)

Protocol: sync timing (block_until_ready between reps — the r3/r4
measured trap: async-loop timing amortizes the ~112 ms dispatch floor
of the rounds 2-5 machine — inherited, not re-measured on this one —
and lies about in-engine cost), median of reps, one warmup
compile per variant.  Run on CPU for a relative baseline, on the chip
(--tpu) for the authoritative shares.

Usage: python runs/step_anatomy.py [--tpu] [reps]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

if "--tpu" not in sys.argv:
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.models import interp
from raft_tla_tpu.ops import kernels

REPS = next((int(a) for a in sys.argv[1:] if a.isdigit()), 30)
B = 4096
BOUNDS = Bounds(n_servers=3, n_values=2, max_term=2, max_log=1,
                max_msgs=2, max_dup=1)
INVS = ("NoTwoLeaders", "LogMatching", "CommittedWithinLog",
        "LeaderCompleteness")

# a mid-depth-looking chunk: replicate init then advance a few times so
# rows are non-trivial (bags populated) — identical inputs per variant
init = interp.init_state(BOUNDS)
frontier = [init]
seen = {init}
pool = []
while len(pool) < B:        # B DISTINCT rows — a cycled pool inflates
    if not frontier:        # the in-chunk duplicate share
        raise SystemExit(
            f"space exhausted below {B} distinct rows per level — "
            "shrink B or widen BOUNDS")
    nxt = []
    for s in frontier:
        if not interp.constraint_ok(s, BOUNDS):
            continue
        for _i, t in interp.successors(s, BOUNDS, spec="full"):
            if t not in seen:
                seen.add(t)
                nxt.append(t)
    frontier = nxt
    pool = [s for s in frontier if interp.constraint_ok(s, BOUNDS)]
rows = np.stack([interp.to_vec(s, BOUNDS) for s in pool[:B]])
vecs = jnp.asarray(rows)

VARIANTS = {}


def _add(name, invs, symmetry, keys):
    raw = kernels.build_step(BOUNDS, "full", invs, symmetry)
    if keys is None:
        fn = jax.jit(raw)
    else:
        fn = jax.jit(lambda v, _r=raw, _k=keys: {k: _r(v)[k]
                                                 for k in _k})
    VARIANTS[name] = fn


_add("full", INVS, ("Server",), None)
_add("no-inv", (), ("Server",), None)
_add("fp-only", (), ("Server",), ("valid", "fp_hi", "fp_lo"))
_add("valid-only", (), ("Server",), ("valid",))
_add("no-sym", INVS, (), None)

out = {}
for name, fn in VARIANTS.items():
    r = fn(vecs)
    jax.block_until_ready(r)            # compile + warm
    times = []
    for _ in range(REPS):
        t0 = time.monotonic()
        jax.block_until_ready(fn(vecs))
        times.append(time.monotonic() - t0)
    med = sorted(times)[len(times) // 2]
    out[name] = med
    print(f"{name:11} {med * 1e3:8.2f} ms/chunk "
          f"({B / med:9,.0f} rows/s)", flush=True)

full = out["full"]
print(json.dumps({
    "platform": jax.devices()[0].platform, "chunk": B, "reps": REPS,
    "ms_full": round(full * 1e3, 2),
    "share_invariants": round(1 - out["no-inv"] / full, 3),
    "share_svecs_pack": round((out["no-inv"] - out["fp-only"]) / full, 3),
    "share_fp_canon": round((out["fp-only"] - out["valid-only"]) / full,
                            3),
    "share_orbit_scan_vs_nosym": round(1 - out["no-sym"] / full, 3),
    "share_expand_residual": round(out["valid-only"] / full, 3),
}))
