"""Device self time of the segment program's ops under the named scope
``quorum``: the quorum guards of a spec compiled from the frontend IR
(``\\E Q \\in Quorum`` over a constant table), lowered inside the step's
``expand`` stage.  ``stagered.STAGES`` is the accepted list of stage scopes and
keeps such an op under ``expand``, its innermost stage; this reduction reads
the same capture with one more name.

The scope is opened inside the step's vmaps, so an op's path names it as
``.../expand/vmap(vmap(quorum))/...`` (JAX wraps a scope in the transforms it
was traced under): a path part counts where it is ``quorum`` inside any
number of ``vmap(`` ``)``.  A fusion belongs to the scope its event names, as
in ``stagered``: what the compiler fuses into an op of another scope is that
scope's.
"""

from __future__ import annotations

import json
import re

from benchmark.harness import spanred, stagered, tracered

SCOPE = "quorum"
_PART = re.compile(r"(?:vmap\()*" + SCOPE + r"\)*\Z")


def in_scope(path: str) -> bool:
    """Whether an op's scope path passes through the ``quorum`` scope."""
    return any(_PART.match(part) for part in path.split("/"))


def scope_times(trace: dict, w0: int, w1: int) -> dict | None:
    """Self time under the scope and of the whole segment module inside
    ``[w0, w1]`` ns, averaged over the devices that ran the module; the ops
    under the scope by name, largest first.  ``None`` where no device ran the
    module inside the window."""
    per_dev = []
    for _plane, lines in sorted(trace["devices"].items()):
        got = stagered.module_self_times(lines, w0, w1, in_scope)
        if got is not None:
            per_dev.append(got[0])
    if not per_dev:
        return None
    n = len(per_dev)
    ops: dict = {}
    total = 0.0
    for by_op in per_dev:
        for (name, inside), ns in by_op.items():
            total += ns / n
            if inside:
                ops[name] = ops.get(name, 0) + ns / n
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])
    return {"devices": n, "scope_ns": sum(ops.values()), "total_ns": total,
            "ops": len(ops), "top_ops": [[k, v] for k, v in ranked[:8]]}


def of(ev: dict) -> dict | None:
    """The reduction of this run's traced pass (computed once a run and kept
    on the evidence; prints its one line the first time).  ``None`` where the
    run was not traced or the capture holds no anchor."""
    if "quorumred" in ev:
        return ev["quorumred"]
    p = spanred.traced_pass(ev)
    red = None
    if p is not None and p.trace_dir and p.anchor:
        trace = stagered.load_xplane(p.trace_dir, p.anchor[1])
        # the accepted stage table of the same capture, printed in this
        # cell's log too (its readers list other cells; one load serves both)
        stagered.of(ev, trace)
        if trace["anchor"] is not None:
            a_ns = trace["anchor"][1]
            red = scope_times(
                trace, tracered.to_trace_ns(p.t_a, p.anchor[0], a_ns),
                tracered.to_trace_ns(p.t_trace_end, p.anchor[0], a_ns))
            print(f"quorum scope pass {p.index}: " + json.dumps(red),
                  flush=True)
    ev["quorumred"] = red
    return red
