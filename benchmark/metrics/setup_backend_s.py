"""Compile ledger: seconds in the backend's compile call (persistent-cache load
included) in records that began before the first timed pass was called."""

from benchmark.harness import ledgerred


def read(ev):
    red = ledgerred.of(ev)
    return red and red["setup_backend_s"]
