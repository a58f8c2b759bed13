"""Levels in a sound pass's table, Init's included (a guard: every pass that
runs to its own end pays the per-level fixed cost this many times); nothing
where the sound passes disagree."""


def read(ev):
    counts = {len(p.levels) for p in ev["passes"]
              if p.fixpoint and p.problem is None}
    return counts.pop() if len(counts) == 1 else None
