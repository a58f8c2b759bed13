"""Slab writes of the candidate stream a chunk step (``stream_slabs`` /
``steps`` of the traced pass's ``level`` spans): 1 unless a chunk streamed
more rows than one slab holds."""

from benchmark.harness import lanered


def read(ev):
    return lanered.ratio(lanered.of(ev), "stream_slabs", "steps")
