"""Main-thread wall in ``segment_wait`` spans inside the traced window A->A+1:
the part of ``export_wall_s`` that is waiting for the device."""

from benchmark.harness import spanred


def read(ev):
    red = spanred.of(ev)
    return red and red["segment_wait_s"]
