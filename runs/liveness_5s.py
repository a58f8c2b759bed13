"""BASELINE config #5 at 5 servers (VERDICT r3 next #6).

EventuallyLeader under weak fairness on the 5-server election sub-spec,
tightly bounded (t2/m1), through models/liveness.ddd_graph with
SYMMETRY Server — the orbit-quotient fair-lasso check at |G| = 5! = 120
(the exactness argument in ddd_graph's docstring: the registered
predicates are permutation-invariant, WF is per permutation-closed
family, and fair lassos project/lift through the quotient).

Also records the no-fairness verdict (the reference Spec's actual
situation, raft.tla:469: stuttering refutes every eventuality) as the
control.  CPU backend — set JAX_PLATFORMS=cpu via jax.config before
anything touches the device.

Writes one JSON line per verdict to stdout and appends to
runs/liveness_5s.out.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.ddd_engine import DDDCapacities
from raft_tla_tpu.models import liveness

CFG = CheckConfig(
    bounds=Bounds(n_servers=5, n_values=2, max_term=2, max_log=0,
                  max_msgs=1, max_dup=1),
    spec="election", invariants=(), symmetry=("Server",), chunk=1024)

CAPS = DDDCapacities(block=1 << 16, table=1 << 20, seg_rows=1 << 17,
                     flush=1 << 18, levels=256)


def main() -> None:
    t0 = time.time()
    graph = liveness.ddd_graph(CFG, CAPS)
    n = len(graph[0])
    n_edges = graph[1].n_edges
    print(json.dumps({"phase": "graph", "orbits": n, "edges": n_edges,
                      "wall_s": round(time.time() - t0, 1)}), flush=True)
    for prop, wf in (("EventuallyLeader", ("Next",)),
                     ("EventuallyLeader", ()),
                     ("InfinitelyOftenLeader", ("Next",))):
        t1 = time.time()
        r = liveness.check(CFG, prop, wf=wf, graph=graph)
        print(json.dumps({
            "prop": prop, "wf": list(wf), "holds": r.holds,
            "n_states": r.n_states, "n_edges": r.n_edges,
            "n_sccs_checked": r.n_sccs_checked,
            "wall_s": round(time.time() - t1, 1)}), flush=True)
    graph[0].close()


if __name__ == "__main__":
    main()
