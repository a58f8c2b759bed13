"""Share of the clocked span's block uploads (``upload`` spans under levels
A+1..B of the traced pass) that found their rows already staged on the device
by the prefetcher.  A level's first block cannot be (it is scheduled as the
level opens); nothing to read with the prefetcher off."""

from benchmark.harness import depthred


def read(ev):
    red = depthred.of(ev)
    if not red or not red["uploads"]:
        return None
    return 100.0 * red["prefetch_hits"] / red["uploads"]
