"""Generated speclint Pass-1 transfer twins — widthcheck from the IR.

``analysis/widthcheck.TRANSFERS`` is a hand-written abstract twin per
kernel family: the interval effect of one transition on every written
field, plus the message records it creates.  :func:`transfer_of`
*derives* that twin from the same :class:`~raft_tla_tpu.frontend.expr.
ActionDef` the runtime kernel is compiled from, by evaluating the def
over the interval domain:

- update values evaluate via each node's ``iv`` rule (``Where`` -> join,
  comparisons -> BOOL, ``bor`` -> ``Interval.or_``, reads -> envelope);
  conditional writes contribute the *written* value only, matching the
  hand twins' "interval of newly written values" convention;
- a branch is skipped when it is infeasible under the current message
  envelope (its ``mtype`` has no creation site, a scoped subfield is
  absent) or its declared guard ``refines`` meet is empty — the
  structural analog of the hand twins' ``if rec is not None`` /
  capacity-gated blocks;
- record creation sites become ``MsgRecord``s over the full subfield
  tables (missing subfields pack as 0 -> ``const(0)``), with declared
  relational ``facts`` and ``overrides`` passed through;
- bag effects reuse ``widthcheck._send_writes`` verbatim (packed-word
  arithmetic has ONE definition), and any remove op contributes the
  emptied-slot joins.

tests/test_frontend_ir.py pins ``transfer_of(adef) == TRANSFERS[fam]``
output-for-output across bounds, so the hand twins and the kernels can
only drift together — which is the point: speclint's width proof becomes
a property of the compiler, not of one hand-maintained table.
"""

from __future__ import annotations

from raft_tla_tpu.analysis import intervals as iv
from raft_tla_tpu.frontend import expr as E


def _record(msg, ictx):
    """A PackMsg site as a widthcheck MsgRecord under ``ictx``."""
    from raft_tla_tpu.analysis.widthcheck import MsgRecord
    from raft_tla_tpu.ops import msgbits as mb
    declared = dict(msg.fields)
    overrides = dict(msg.overrides)
    fields = {}
    for name in (*mb.HI_FIELDS, *mb.LO_FIELDS):
        if name == "mtype":
            fields[name] = iv.const(msg.mtype)
        elif name in overrides:
            # the subfield echoes a relational fact of the consumed
            # record (the done-reply's b = a+c of the request)
            rec = ictx.menv.get(ictx.mtype)
            if rec is None or overrides[name] not in rec:
                raise E.Infeasible(overrides[name])
            fields[name] = rec[overrides[name]]
        else:
            e = declared.get(name)
            fields[name] = iv.const(0) if e is None else e.iv(ictx)
    for fname, fn in msg.facts:
        fields[fname] = fn(ictx.bounds, ictx.env, ictx.menv)
    return MsgRecord(msg.mtype, fields)


def check_schema_writes(schema, defs, bounds) -> list:
    """The width gate of a schema-declared spec, from the IR: under the
    declared envelope, every value an action writes lies inside the
    written field's declared range (what the packed row carries), and
    every index of a :class:`~raft_tla_tpu.frontend.expr.SetAt` lies inside
    the field's axis.  Lint-style findings (empty = proved)."""
    from raft_tla_tpu.analysis import report
    from raft_tla_tpu.frontend import schema as sch
    env = sch.envelope(schema, bounds)
    const_iv = sch.const_envelope(schema, bounds)
    shapes = schema.layout(bounds).shapes
    findings = []

    def bad(code, text, field):
        findings.append(report.Finding(report.WIDTH, report.ERROR, code,
                                       text, field=field))

    for adef in defs:
        param_iv = {name: fn(bounds) for name, fn in adef.param_iv}
        for br in adef.branches:
            ictx = E.IvCtx(bounds, env, {}, param_iv, br.mtype, const_iv)
            for u in br.updates:
                got, want = u.val.iv(ictx), env[u.field]
                if got.lo < want.lo or got.hi > want.hi:
                    bad("schema-write-range",
                        f"{adef.family}: writes [{got.lo}, {got.hi}] to "
                        f"{u.field!r}, declared [{want.lo}, {want.hi}]",
                        u.field)
                if not isinstance(u, E.SetAt):
                    continue
                for axis, e in enumerate(u.idx):
                    at, dim = e.iv(ictx), shapes[u.field][axis]
                    if at.lo < 0 or at.hi >= dim:
                        bad("schema-index-range",
                            f"{adef.family}: index [{at.lo}, {at.hi}] on "
                            f"axis {axis} of {u.field!r}, which has {dim} "
                            "entries", u.field)
    return findings


def transfer_of(adef):
    """ActionDef -> ``transfer(bounds, env, menv) -> TransferResult``,
    the exact callable shape ``widthcheck.TRANSFERS`` holds (and
    ``check_widths(transfers=...)`` injects)."""

    def transfer(bounds, env, menv):
        from raft_tla_tpu.analysis.widthcheck import (TransferResult,
                                                      _send_writes)
        param_iv = {name: fn(bounds) for name, fn in adef.param_iv}
        writes: dict = {}

        def join_write(field, interval):
            cur = writes.get(field)
            writes[field] = interval if cur is None else cur.join(interval)

        sends = []
        # Structural, not envelope-gated: a spec whose action CAN remove
        # a message must always account for the emptied slot (the hand
        # t_receive/t_drop join these unconditionally).
        has_remove = any(isinstance(op, (E.BagRemove, E.Reply))
                         for br in adef.branches for op in br.ops)
        for br in adef.branches:
            try:
                benv = env
                if br.refines:
                    benv = dict(env)
                    for field, lo, hi in br.refines:
                        # empty meet (ValueError) = branch infeasible at
                        # these bounds, e.g. truncation with log_cap 0
                        benv[field] = benv[field].meet(iv.Interval(lo, hi))
                ictx = E.IvCtx(bounds, benv, menv, param_iv, br.mtype)
                if br.mtype is not None and br.mtype not in menv:
                    raise E.Infeasible(f"mtype {br.mtype} has no record")
                branch_writes = [(u.field, u.val.iv(ictx))
                                 for u in br.updates]
                branch_sends = [_record(op.msg, ictx) for op in br.ops
                                if isinstance(op, (E.BagAdd, E.Reply))]
            except (E.Infeasible, ValueError):
                continue
            for field, interval in branch_writes:
                join_write(field, interval)
            sends.extend(branch_sends)
        if sends:
            for field, interval in _send_writes(env, tuple(sends)).items():
                join_write(field, interval)
        if has_remove:
            join_write("msgHi", iv.const(0))
            join_write("msgLo", iv.const(0))
            join_write("msgCount", iv.Interval(0, env["msgCount"].hi))
        return TransferResult(writes, tuple(sends))

    return transfer
