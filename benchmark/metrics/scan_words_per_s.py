"""Achieved orbit-scan rate: analytic words of the traced span
(chunk*A*|G|*width per chunk step, benchmark/harness/work.py) over the device
time of the segment program in the trace.  A rate, not a share: no integer-VPU
peak is published."""


def read(ev):
    tr = ev["trace"]
    if not tr or not tr["segment_device_s"]:
        return None
    w = ev["work"]
    return w["steps"] * w["words_per_step"] / tr["segment_device_s"]
