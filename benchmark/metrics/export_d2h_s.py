"""Main-thread wall in ``d2h`` spans inside the traced window A->A+1: the
segment buffers' transfer, the part of ``export_wall_s`` that is export."""

from benchmark.harness import spanred


def read(ev):
    red = spanred.of(ev)
    return red and red["export_d2h_s"]
