"""Process start to the JAX backend open (benchmark clock)."""


def read(ev):
    return ev["clocks"]["open_s"]
