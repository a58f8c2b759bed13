"""Median per-level wall in ``upload`` spans over levels 1..A-1 of the traced
pass: the padded frontier block read, staged and handed to the device."""

from benchmark.harness import spanred


def read(ev):
    red = spanred.of(ev)
    return red and red["ramp_upload_ms"]
