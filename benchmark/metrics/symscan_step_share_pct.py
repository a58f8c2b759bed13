"""Share of the segment program's device time, over the traced level, that is
self time of ops under the stage scope ``orbit_scan``
(benchmark/harness/symred.py): whether the symmetry mechanism does most of the
step's work.  The rest is expand (the quorum guards in it), pack, invariants,
filter insert and stream.  Nothing to read where the capture names no op under
that scope."""

from benchmark.harness import symred


def read(ev):
    red = symred.of(ev)
    tr = ev["trace"]
    if not red or not red["scope_ns"] or not tr \
            or not tr["segment_device_s"]:
        return None
    return 100.0 * red["scope_ns"] / 1e9 / tr["segment_device_s"]
