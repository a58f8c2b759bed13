"""Trips of the mesh exchange's gather loop a lockstep step
(``exchange_slabs`` / ``steps`` of the traced pass's ``level`` spans; the mesh
engine's ``exchange_slabs`` is the most any shard's loop ran, both stages of a
2-D mesh counted, summed over the level's segments): 1 unless some shard
packed more live lanes in one step than a slab holds.  Nothing to read where
the spans carry no count (a one-chip program, or one whose exchange scatters
its send blocks).  The sums are made here: ``lanered.reduce`` keeps a fixed
list of keys."""

from benchmark.harness import spanred


def read(ev):
    p = spanred.traced_pass(ev)
    if p is None:
        return None
    levels = [s["args"] for s in spanred.load(p.events)
              if s["name"] == "level" and "exchange_slabs" in s["args"]]
    steps = sum(a.get("steps", 0) for a in levels)
    if not steps:
        return None
    return sum(a["exchange_slabs"] for a in levels) / steps
