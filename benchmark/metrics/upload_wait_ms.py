"""Device-idle time of the traced span inside the program's ``upload``
spans: the frontier block staged while nothing ran."""


def read(ev):
    tr = ev["trace"]
    if not tr:
        return None
    return 1e3 * dict(tr["idle_gaps"]).get("upload", 0.0)
