"""Compile ledger: seconds of jaxpr tracing and lowering to MLIR in records
that began before the first timed pass was called."""

from benchmark.harness import ledgerred


def read(ev):
    red = ledgerred.of(ev)
    return red and red["setup_trace_s"]
