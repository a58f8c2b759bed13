"""Wall inside the traced pass's ``level`` spans after the peak whose
``new_states`` are under one chunk, the empty last one included: what the
way down to the fixpoint costs, a fixed cost a level for a handful of rows."""

from benchmark.harness import tailred


def read(ev):
    red = tailred.of(ev)
    return red and red["tail_wall_s"]
