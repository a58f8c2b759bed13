"""(max - min) / median of this run's untraced sound passes' A->B span
rates: how far one pass's at-depth reading lies from another's."""


def read(ev):
    return ev["summary"]["spread_pct"] if ev["summary"] else None
