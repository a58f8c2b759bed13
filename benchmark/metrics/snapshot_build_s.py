"""Set-up's pass from Init to the snapshot's level, the snapshot's write
included (call -> return): what a traffic that starts at depth adds to
``setup_s``."""


def read(ev):
    snap = ev.get("snapshot")
    return snap["build_s"] if snap else None
