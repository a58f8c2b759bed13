"""Share of the admitted orbits that a log action found: over the untraced
sound passes, ``EngineResult.coverage`` (the count of new states by the
action family of the transition that first reached each) summed over
``ClientRequest``, ``AppendEntries`` and ``AdvanceCommitIndex``, against all
of it.  ``Receive`` is not split by message type there and counts for none.
Near 0 where a cell bypasses the log actions; a resumed pass's coverage comes
out of its snapshot, so it counts from Init."""

LOG_FAMILIES = ("ClientRequest", "AppendEntries", "AdvanceCommitIndex")


def read(ev):
    cov = [p.coverage for p in ev["passes"]
           if not p.traced and p.problem is None and p.coverage]
    total = sum(sum(c.values()) for c in cov)
    if not total:
        return None
    return 100.0 * sum(c.get(f, 0) for c in cov for f in LOG_FAMILIES) / total
