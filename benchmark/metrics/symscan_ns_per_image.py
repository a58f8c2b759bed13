"""Device self time under the stage scope ``orbit_scan`` over the images the
traced window's segments keyed (``images`` of the program's ``segment`` spans:
|G| x the lanes of the segment's steps; benchmark/harness/symred.py): what one
image of one lane costs, to set beside the Raft cells' 0.15-0.18 ns.  Nothing
to read where the scope is empty or the spans carry no ``images``."""

from benchmark.harness import symred


def read(ev):
    red = symred.of(ev)
    if not red or not red["scope_ns"] or not red["window"]["images"]:
        return None
    return red["scope_ns"] / red["window"]["images"]
