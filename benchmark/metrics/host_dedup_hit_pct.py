"""Rows the device streamed that the exact host key set rejected, over all
it streamed (``streamed_rows`` - ``new_states`` of the traced pass's ``level``
spans, levels A+1..B): what the lossy device filter let through."""

from benchmark.harness import depthred


def read(ev):
    red = depthred.of(ev)
    if not red or not red["streamed_rows"] or red["new_states"] is None:
        return None
    return 100.0 * (red["streamed_rows"] - red["new_states"]) \
        / red["streamed_rows"]
