"""Share of the admitted states that phase 2 found: over the untraced sound
passes, ``EngineResult.coverage`` (the count of new states by the action
family of the transition that first reached each) summed over ``Phase2a``
(the quorum-guarded action) and ``Phase2b`` (the one it enables), against all
of it.  The sibling of ``log_transitions_share_pct``; nothing to read where a
pass reports no coverage."""

PHASE2_FAMILIES = ("Phase2a", "Phase2b")


def read(ev):
    cov = [p.coverage for p in ev["passes"]
           if not p.traced and p.problem is None and p.coverage]
    total = sum(sum(c.values()) for c in cov)
    if not total:
        return None
    return 100.0 * sum(c.get(f, 0) for c in cov
                       for f in PHASE2_FAMILIES) / total
