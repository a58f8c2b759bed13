"""1 - (union of device-op intervals) / window, over the traced pass."""


def read(ev):
    tr = ev["trace"]
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
