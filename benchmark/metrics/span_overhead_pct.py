"""The traced pass's ramp (spans and event log on, profiler not yet started)
over the median ramp of the untraced passes, less one: what tracing costs
when it is on."""

import statistics


def read(ev):
    traced = [p.ramp_s for p in ev["passes"]
              if p.traced and p.ramp_s is not None]
    plain = [p.ramp_s for p in ev["passes"]
             if not p.traced and p.ramp_s is not None]
    if not traced or not plain:
        return None
    return 100.0 * (traced[0] / statistics.median(plain) - 1.0)
