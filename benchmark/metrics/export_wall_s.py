"""Main-thread wall inside the program's ``export`` spans (stats fetch and
d2h of the segment buffers) during the traced span."""


def read(ev):
    tr = ev["trace"]
    if not tr:
        return None
    return tr["span_wall_s"].get("export@MainThread", 0.0)
