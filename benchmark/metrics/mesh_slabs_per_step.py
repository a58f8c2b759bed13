"""Slab writes of the mesh step's ``stream`` stage a lockstep step
(``stream_slabs`` / ``steps`` of the traced pass's ``level`` spans; the
mesh engine's ``stream_slabs`` is the most any shard wrote, summed over the
level's segments): 1 unless some shard streamed more rows in one step than a
slab holds.  Nothing to read where the spans carry no count (a program whose
mesh step scatters its stream)."""

from benchmark.harness import lanered


def read(ev):
    return lanered.ratio(lanered.of(ev), "stream_slabs", "steps")
