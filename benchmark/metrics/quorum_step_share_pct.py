"""Share of the segment program's device time, over the traced level, that is
self time of ops under the named scope ``quorum``
(benchmark/harness/quorumred.py): what the quorum guards cost of a chunk
step.  The rest of the step is the successor lanes' flag writes and packing,
the plain fingerprint, the invariants, filter insert and stream.  Nothing to
read where the capture names no op under that scope."""

from benchmark.harness import quorumred


def read(ev):
    red = quorumred.of(ev)
    tr = ev["trace"]
    if not red or not red["scope_ns"] or not tr \
            or not tr["segment_device_s"]:
        return None
    return 100.0 * red["scope_ns"] / 1e9 / tr["segment_device_s"]
