"""(max - min) / max of the chips' busy time in the traced window: how
unevenly the mesh's chips worked while they stepped in lockstep.  Nothing to
read on one chip."""

from benchmark.harness import meshred


def read(ev):
    tr = ev["trace"]
    if not tr:
        return None
    return meshred.busy_skew_pct(tr["busy_by_device_s"])
