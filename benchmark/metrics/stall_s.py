"""Pass ledger: seconds the run's sound untraced passes lost to stalls — the
sum of ``wall - m_k`` over the levels (and ``head``, ``tail``) whose wall
exceeds the run's low median m_k there by more than max(0.25 s, m_k).  0.0 in
a run that met none; each stall is a line in the run's log."""

from benchmark.harness import levelred


def read(ev):
    red = levelred.of(ev)
    return red and red["stall_s"]
