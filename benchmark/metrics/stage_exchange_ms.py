"""Device self time of the segment module's ops under the mesh step's
``exchange`` scope (the ``all_to_all`` and the packing around it, nested ops
counted), over the traced level's lockstep steps; mean over the chips
(benchmark/harness/meshred.py).  Nothing to read where no op names the scope:
a one-chip program has no exchange, and 0.0 would say it was free."""

from benchmark.harness import meshred


def read(ev):
    red = meshred.of(ev)
    if not red or not red["scope_ns"]:
        return None
    return red["scope_ns"] / 1e6 / ev["work"]["steps"]
