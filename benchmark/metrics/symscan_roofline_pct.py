"""The orbit scan's share of its roofline: the time the chip's peaks allow
for the scan's work a chunk step, over the measured device self time under the
stage scope ``orbit_scan`` a step.  The allowed time is the larger of the
``int8`` operations of the limb product over ``int8_ops_per_s`` and the bytes
the scan must move over ``hbm_bytes_per_s`` (benchmark/peaks/); the work is
counted by the configuration's family from its declared shapes alone
(``scan_ops`` / ``scan_bytes``: benchmark/harness/symred.py), so a later
implementation is read against the same work.  Nothing to read where the scope
is empty or the family counts no scan."""

from benchmark.harness import symred


def read(ev):
    red = symred.of(ev)
    s = symred.scope_s_per_step(ev)
    if s is None or not red["work"]:
        return None
    pk = ev["peaks"]
    allowed = max(red["work"]["ops"] / pk["int8_ops_per_s"],
                  red["work"]["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * allowed / s
