"""Pass ledger: over levels 1..A-1 of the sound untraced passes, the main
thread's CPU time (``cpu_s``) over ``wall_s - wait_s``: how much of the host's
fixed cost a level is computing (pad, NumPy, Python) and how much is blocked
(h2d, ``device_get``, a lock).  ``cpu_s`` is the kernel's account of the
thread (``getrusage(RUSAGE_THREAD)``), which on the v5e machines' host ticks
in 10 ms: one level of 23-39 ms reads 0, 10 or 20 ms, so the share is a sum
of ticks over the 117-266 ramp levels of a run and good to a few points
(23-38 % over thirteen readings of PR 38), not a reading of any one level."""

from benchmark.harness import levelred


def read(ev):
    red = levelred.of(ev)
    return red and red["level_cpu_share_pct"]
