"""Bytes the traced levels must move (frontier rows in, one filter bucket
per candidate lane, the exported candidate stream out; from shapes) over the
segment program's device time, against the chip's HBM peak."""

from benchmark.harness import work


def read(ev):
    tr = ev["trace"]
    if not tr or not tr["segment_device_s"]:
        return None
    w = ev["work"]
    moved = w["steps"] * w["bytes_per_step"] + work.export_bytes(
        w["traced_orbits"], w["packed_words"])
    return 100.0 * moved / tr["segment_device_s"] \
        / ev["peaks"]["hbm_bytes_per_s"]
