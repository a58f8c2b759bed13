"""Device self time of the segment module's ops under the scope ``history``
(inside the step's ``expand`` stage: the ``allLogs`` union, the ``voterLog``
writes, the ``elections`` insert and sort, the ``mlog`` ranks), over the
traced level's chunk steps (benchmark/harness/histred.py).  It is part of
``stage_expand_ms``' total, not beside it.  Nothing to read where the capture
names no op under that scope: a parity-mode program."""

from benchmark.harness import histred


def read(ev):
    return histred.scope_ms_per_step(ev, "history")
