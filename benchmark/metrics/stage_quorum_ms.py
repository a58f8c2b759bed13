"""Device self time of the segment module's ops under the named scope
``quorum`` (the ``\\E Q \\in Quorum`` guards of a spec compiled from the
frontend IR, nested in the step's ``expand`` stage), over the traced level's
chunk steps (benchmark/harness/quorumred.py).  Nothing to read where the
capture names no op under that scope: a program without it."""

from benchmark.harness import quorumred


def read(ev):
    red = quorumred.of(ev)
    if not red or not red["scope_ns"]:
        return None
    return red["scope_ns"] / 1e6 / ev["work"]["steps"]
