"""The most frontier blocks any level of the clocked span was expanded in
(``blocks`` of the traced pass's ``level`` spans, levels A+1..B): the guard
that a cell whose levels outgrow a block stays one (``blocks_per_level`` is
their mean)."""

from benchmark.harness import spanred


def read(ev):
    p = spanred.traced_pass(ev)
    if p is None or not ev.get("span_levels"):
        return None
    a, b = ev["span_levels"]
    blocks = [s["args"]["blocks"] for s in spanred.load(p.events)
              if s["name"] == "level" and "blocks" in s["args"]
              and a < (s["args"].get("level") or 0) <= b]
    return max(blocks) if blocks else None
