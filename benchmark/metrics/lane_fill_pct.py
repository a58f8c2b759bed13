"""Enabled lanes over computed lanes (``n_valid`` / ``lanes`` of the traced
pass's ``level`` spans): how much of the dense step is enabled work."""

from benchmark.harness import lanered


def read(ev):
    fill = lanered.ratio(lanered.of(ev), "n_valid", "lanes")
    return None if fill is None else 100.0 * fill
