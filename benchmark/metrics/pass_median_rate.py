"""The at-depth reading: the MEDIAN of the run's untraced sound passes' span
rates, each (pinned count at B - pinned count at A) / (stamp B - stamp A).
Steadier than the end-to-end rate (one stall cannot move it) and blind to the
ramp, which is why it is a per-layer metric and not the end-to-end one."""


def read(ev):
    return ev["summary"]["median"] if ev["summary"] else None
