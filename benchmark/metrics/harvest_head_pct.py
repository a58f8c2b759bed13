"""Share of the harvests that fetched the head of the shards' buffers and not
the whole buffers: over the clocked span A->B of the traced pass, 100 x the
main thread's ``d2h`` spans whose ``path`` is ``"head"`` over all its ``d2h``
spans (a span is counted where it starts).  Nothing to read where the pass's
log holds no ``d2h`` span inside the clocked span, or where the spans carry
no ``path`` (a program whose harvest has one path)."""

from benchmark.harness import spanred


def read(ev):
    p = spanred.traced_pass(ev)
    if p is None or p.t_b is None:
        return None
    paths = [s["args"].get("path") for s in spanred.load(p.events)
             if s["name"] == "d2h" and s["thread"] == spanred.MAIN
             and p.t_a <= s["t0"] < p.t_b]
    if not paths or None in paths:
        return None
    return 100.0 * paths.count("head") / len(paths)
