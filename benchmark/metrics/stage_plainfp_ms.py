"""Device self time of the segment module's ops under the step's stage scope
``plain_fp`` (the key of a spec with no SYMMETRY: every candidate lane
fingerprinted once, as it is), over the traced level's chunk steps
(benchmark/harness/stagered.py).  Nothing to read where the capture names no
op under that scope: a program whose key is the orbit scan's."""

from benchmark.harness import stagered


def read(ev):
    red = stagered.of(ev)
    st = red and red["stages"]
    if not st or not st["stage_ns"].get("plain_fp"):
        return None
    return st["stage_ns"]["plain_fp"] / 1e6 / ev["work"]["steps"]
