"""Device-idle time of the traced span that falls inside the program's
main-thread ``dedup*`` spans: host dedup the device waited for."""


def read(ev):
    tr = ev["trace"]
    if not tr:
        return None
    return dict(tr["idle_gaps"]).get("dedup", 0.0)
