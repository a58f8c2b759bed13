"""Passes made in the run."""


def read(ev):
    return len(ev["passes"])
