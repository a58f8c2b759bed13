"""Backend-compile events (cache hits included) inside the timed passes."""


def read(ev):
    return sum(p.compiles for p in ev["passes"])
