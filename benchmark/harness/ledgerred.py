"""From the program's compile ledger (``raft_tla_tpu.obs.compiles``: one
``jax.monitoring`` listener inside the program, recording each jaxpr trace,
lowering, backend compile and persistent-cache load) to what set-up spent
compiling: the records that began before the first timed pass was called.

``backend`` already holds the persistent-cache load (JAX times the backend
call around the cache lookup), so ``cache_load`` is reported beside it and
never added to it.  A program without the ledger gives ``None``.
"""

from __future__ import annotations

import json


def reduce(records: list, t_call: float) -> dict:
    """Seconds and events per kind over the records with ``t0 < t_call``."""
    acc = {k: [0, 0.0] for k in ("trace", "lower", "backend", "cache_load")}
    for r in records:
        if r["t0"] < t_call and r["kind"] in acc:
            acc[r["kind"]][0] += 1
            acc[r["kind"]][1] += r["dur_s"]
    return {"setup_trace_s": acc["trace"][1] + acc["lower"][1],
            "setup_backend_s": acc["backend"][1],
            "setup_programs": acc["backend"][0],
            "cache_load_s": acc["cache_load"][1],
            "cache_loads": acc["cache_load"][0],
            "traces": acc["trace"][0]}


def of(ev: dict) -> dict | None:
    """The reduction for this run (once a run; prints its one line)."""
    if "ledgerred" not in ev:
        red = None
        try:
            from raft_tla_tpu.obs import compiles
        except ImportError:
            compiles = None
        if compiles is not None and ev["passes"]:
            snap = compiles.snapshot()
            red = reduce(snap["records"], ev["passes"][0].t_call)
            red["dropped"] = snap["dropped"]
            print("compile ledger before the first timed pass: "
                  + json.dumps(red), flush=True)
        ev["ledgerred"] = red
    return ev["ledgerred"]
