"""Device self time of the segment module's ops under the scope
``orbit_moved`` (inside the step's ``orbit_scan`` stage: the fields the scan
still moves and canonicalises an image at a time — the faithful-mode history
with its ``elections`` sort), over the traced level's chunk steps
(benchmark/harness/histred.py).  It is part of ``stage_orbit_ms``' total, not
beside it.  Nothing to read where the capture names no op under that scope."""

from benchmark.harness import histred


def read(ev):
    return histred.scope_ms_per_step(ev, "orbit_moved")
