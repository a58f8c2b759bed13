"""Device self time of the segment module's ops under the step's stage
scopes invariants and constraint, over the traced level's chunk steps
(benchmark/harness/stagered.py)."""

from benchmark.harness import stagered


def read(ev):
    return stagered.stage_ms_per_step(ev, "check")
