"""Backend open to ready: engine build (trace + lower + compile or cache
load) and the warm pass over the cell's own shapes (benchmark clock)."""


def read(ev):
    return ev["clocks"]["compile_s"]
