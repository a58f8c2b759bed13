"""Compile ledger: programs requested from the backend (compiled or loaded)
before the first timed pass was called."""

from benchmark.harness import ledgerred


def read(ev):
    red = ledgerred.of(ev)
    return red and red["setup_programs"]
