"""Device self time of the segment module's ops under the step's stage scope
``orbit_scan`` (the key of a spec under SYMMETRY: the least fingerprint over
the images of every candidate lane), over the traced level's chunk steps
(benchmark/harness/symred.py).  Nothing to read where the capture names no op
under that scope: a program that keys plainly."""

from benchmark.harness import symred


def read(ev):
    s = symred.scope_s_per_step(ev)
    return None if s is None else 1e3 * s
