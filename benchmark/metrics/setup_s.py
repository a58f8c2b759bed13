"""End to end: process start (from /proc) to the start of the first timed
pass — interpreter and imports, backend open, engine build, warm pass."""


def read(ev):
    return ev["clocks"]["setup_s"]
