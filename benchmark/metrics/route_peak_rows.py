"""Most enabled lanes any one chunk step had in the traced pass (the
``level`` spans' ``route_peak``): what a routed step would have to hold."""

from benchmark.harness import lanered


def read(ev):
    red = lanered.of(ev)
    return red and red["route_peak"]
