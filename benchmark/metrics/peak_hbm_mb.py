"""Peak device memory on the fullest chip (memory_stats)."""


def read(ev):
    return ev["hbm_peak_bytes"] / 1e6 if ev["hbm_peak_bytes"] else None
