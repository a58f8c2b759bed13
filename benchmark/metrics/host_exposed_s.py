"""Pass ledger: median over the sound untraced passes of ``wall_s`` less the
sum of the levels' ``wait_s`` — the seconds of a pass in which the main thread
is not waiting for the device (with one segment in flight, as in every ramp
level, the device idles then)."""

from benchmark.harness import levelred


def read(ev):
    red = levelred.of(ev)
    return red and red["host_exposed_s"]
