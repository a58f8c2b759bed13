"""Share of the segment program's device time, over the traced level, that is
self time of ops under the scopes ``history`` and ``orbit_moved``
(benchmark/harness/histred.py): how much of a chunk step the history
variables are, at the source's own width.  The rest is the parity step:
expand, pack, the linear part of the scan and the ranked bag, invariants,
filter insert and stream (whose rows the history widens: that is not in this
share).  Nothing to read where the capture names no op under either scope."""

from benchmark.harness import histred


def read(ev):
    tr = ev["trace"]
    ns = [histred.scope_ns(ev, s) for s in histred.SCOPES]
    if not any(ns) or not tr or not tr["segment_device_s"]:
        return None
    return 100.0 * sum(x or 0.0 for x in ns) / 1e9 / tr["segment_device_s"]
