"""Share of the window the passes spent in levels 0..A (call to stamp A):
small levels, a fixed cost per level, what short jobs feel."""


def read(ev):
    return ev["window"]["ramp_share_pct"]
