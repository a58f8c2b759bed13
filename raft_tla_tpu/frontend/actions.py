"""The IR compiler: ActionDefs -> fused per-family kernels.

:func:`compile_kernels` lowers a spec's :class:`~raft_tla_tpu.frontend.
expr.ActionDef` table to kernels with the exact
``(bounds, s, *params) -> (out, valid, ovf)`` contract that
``ops/kernels.grouped_dispatch`` vmaps — so an IR-defined spec (or Raft
itself, via ``frontend/raft_ir``) rides the existing fused
expand→canonicalize→dedup step untouched.  The lowering deliberately
calls the hand-written helper layer (``_set1``/``_set2``/``bag_add``/
``reply``/``_tree_select``) rather than re-deriving it: equal IR
semantics then produce *bit-identical* lanes, which is what the Raft
parity tests pin down.

:func:`build_schema_step` is the generic step builder for specs declared
purely as a schema + IR (no hand kernels at all): same step-dict
contract as ``kernels.build_step`` — plain lane fingerprints, vmapped
predicate invariants, identity canonicalization unless the spec
declares one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from raft_tla_tpu.frontend import expr as E
from raft_tla_tpu.ops import fingerprint as fpr
from raft_tla_tpu.ops import kernels as K

I32 = jnp.int32


def _as_array_bool(v):
    """Python bools (a Lit(True) validity) become traced scalars so the
    dispatch loop can broadcast them like the hand kernels'
    ``jnp.bool_(True)``."""
    return jnp.bool_(v) if isinstance(v, bool) else v


def _set_at(arr, idx, val):
    """arr with arr[idx...] = val: ``_set1`` / ``_set2`` at any rank (the
    same one-hot form, one mask an axis)."""
    if len(idx) != arr.ndim:
        raise ValueError(f"SetAt: {len(idx)} indices for {arr.ndim} axes")
    mask = None
    for axis, i in enumerate(idx):
        shape = [1] * arr.ndim
        shape[axis] = arr.shape[axis]
        hot = jnp.reshape(jnp.arange(arr.shape[axis]), shape) == i
        mask = hot if mask is None else (mask & hot)
    return jnp.where(mask, val, arr)


def _apply_update(ctx, out, u):
    """One field write on the branch struct; values read the pre-state
    through ``ctx`` (the hand kernels' functional idiom)."""
    arr = out[u.field]
    if isinstance(u, E.Set1):
        written = K._set1(arr, u.i.ev(ctx), u.val.ev(ctx))
    elif isinstance(u, E.SetRow):
        return K._set_row(arr, u.i.ev(ctx), u.val.ev(ctx))
    elif isinstance(u, E.Set2):
        written = K._set2(arr, u.i.ev(ctx), u.j.ev(ctx), u.val.ev(ctx))
    elif isinstance(u, E.SetAt):
        written = _set_at(arr, [e.ev(ctx) for e in u.idx], u.val.ev(ctx))
    else:
        raise TypeError(f"unknown update node {type(u).__name__}")
    cond = getattr(u, "cond", None)
    if cond is None:
        return written
    return jnp.where(cond.ev(ctx), written, arr)


def _pack_words(ctx, msg):
    """Evaluate a PackMsg into the (hi, lo) packed int32 words —
    value-identical to the ``ops/msgbits`` constructors (same shifts,
    OR-composition of non-negative subfields)."""
    from raft_tla_tpu.ops import msgbits as mb
    vals = {"mtype": msg.mtype}
    for name, e in msg.fields:
        v = e.ev(ctx)
        if hasattr(v, "dtype") and v.dtype == jnp.bool_:
            v = v.astype(I32)
        vals[name] = v
    words = []
    for table in (mb.HI_FIELDS, mb.LO_FIELDS):
        w = None
        for name, (shift, _width) in table.items():
            v = vals.get(name)
            if v is None:
                continue
            t = (v << shift) if shift else v
            w = t if w is None else (w | t)
        words.append(jnp.int32(0) if w is None else w)
    return words[0], words[1]


def _branch_effects(ctx, s, br):
    """Apply one branch: field updates, then bag ops in order.  Returns
    (out_struct, ovf_or_None)."""
    out = dict(s)
    for u in br.updates:
        out[u.field] = _apply_update(ctx, out, u)
    ovf = None
    for op in br.ops:
        if isinstance(op, E.BagAdd):
            hi, lo = _pack_words(ctx, op.msg)
            out, o = K.bag_add(out, hi, lo)
        elif isinstance(op, E.BagRemove):
            mhi, mlo = ctx.msg_words()
            out = K.bag_remove(out, mhi, mlo)
            continue
        elif isinstance(op, E.Reply):
            hi, lo = _pack_words(ctx, op.msg)
            mhi, mlo = ctx.msg_words()
            out, o = K.reply(out, hi, lo, mhi, mlo)
        else:
            raise TypeError(f"unknown bag op {type(op).__name__}")
        ovf = o if ovf is None else (ovf | o)
    if br.overflow is not None:
        o = br.overflow.ev(ctx)
        ovf = o if ovf is None else (ovf | o)
    return out, ovf


def _compile_action(adef, const_tables=None):
    """ActionDef -> kernel(bounds, s, *params) with the grouped_dispatch
    contract; ``const_tables`` are the schema's bound constant tables,
    closed over."""

    def kern(bounds, s, *args):
        ctx = E.Ctx(bounds, s, dict(zip(adef.params, args)), jnp,
                    const_tables)
        valid = _as_array_bool(adef.valid.ev(ctx))
        if len(adef.branches) == 1 and adef.branches[0].guard is None:
            out, contrib = _branch_effects(ctx, s, adef.branches[0])
            total = contrib
        else:
            pairs, guards, total = [], [], None
            for br in adef.branches:
                g = br.guard.ev(ctx)
                b_out, contrib = _branch_effects(ctx, s, br)
                pairs.append((g, b_out))
                guards.append(g)
                if contrib is not None:
                    t = g & contrib
                    total = t if total is None else (total | t)
            out = K._tree_select(pairs, s)
            if adef.any_guard_valid:
                valid = valid & functools.reduce(jnp.logical_or, guards)
        ovf = jnp.bool_(False) if total is None else (valid & total)
        return out, valid, ovf

    kern.__name__ = f"ir_{adef.family.lower()}"
    return kern


def compile_kernels(defs, const_tables=None):
    """IR table -> ``{family: (kernel, params)}``, the shape
    ``grouped_dispatch(..., family_kernels=...)`` consumes."""
    return {adef.family: (_compile_action(adef, const_tables), adef.params)
            for adef in defs}


def build_schema_expand(schema, defs, table, bounds, const_tables=None):
    """The expand half of :func:`build_schema_step` on its own:
    ``expand(struct) -> (succs[A, ...], valid[A], ovf[A])`` in
    action_table order — the same contract as ``kernels.build_expand``,
    which is what the simulation engines vmap per walker (they sample
    one lane per step instead of fingerprinting the whole fan-out)."""
    fam_kernels = compile_kernels(defs, const_tables)
    groups = K.group_instances(table)

    def expand(s):
        succs, valids, ovfs = K.grouped_dispatch(
            bounds, s, groups, family_kernels=fam_kernels)
        all_succs = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *succs)
        return (all_succs,
                jnp.concatenate(valids, axis=0),
                jnp.concatenate(ovfs, axis=0))

    return expand


def build_schema_step(schema, defs, table, bounds, predicates=(),
                      const_tables=None, sorts=()):
    """Generic fused step for a schema-declared spec.

    ``table`` is the action-instance list (objects with ``.family`` and
    the per-family param attributes), ``predicates`` the compiled
    invariant :class:`~raft_tla_tpu.frontend.predicate.Predicate` probes
    (order = CheckConfig.invariants).  Returns ``step(vecs[B, W]) ->
    dict`` with the exact key set/shapes ``kernels.build_step``
    produces: svecs, valid, overflow, fp_hi/fp_lo (uint32 lanes),
    inv_ok, con_ok.  Canonicalization is the identity (a schema spec
    declares no bag-slot permutation) and ``con_ok`` is all-true; both
    are points where a future schema hook can slot in.
    ``const_tables`` are the schema's constant tables as the run binds
    them (``Schema.bind_consts``), read by ``ConstTab`` nodes.  ``sorts``
    names the symmetric sorts of the schema the run reduces by (the cfg's
    SYMMETRY): with none the key of a lane is its plain fingerprint, and
    the program is what it was before a schema could declare a sort; with
    some it is the least fingerprint over the lane's images
    (``ops/symmetry.build_schema_orbit_fp``), under the ``orbit_scan``
    scope where ``plain_fp`` stood.
    """
    lay = schema.layout(bounds)
    host_consts = fpr.lane_constants(lay.width)
    consts = jnp.asarray(host_consts)
    expand = build_schema_expand(schema, defs, table, bounds, const_tables)
    orbit_fp = None
    if sorts:
        from raft_tla_tpu.ops import symmetry as sym
        orbit_fp = sym.build_schema_orbit_fp(schema, bounds, tuple(sorts),
                                             host_consts)

    def step(vecs):
        # the step's stage scopes (kernels.STAGE_SCOPES), as build_step
        # opens them: metadata for the device trace, no computation
        with jax.named_scope("unpack"):
            structs = jax.vmap(lambda v: lay.unpack(v, jnp))(vecs)
        with jax.named_scope("expand"):
            succs, valid, ovf = jax.vmap(expand)(structs)
        with jax.named_scope("pack"):
            svecs = jax.vmap(jax.vmap(lambda t: lay.pack(t, jnp)))(succs)
        if orbit_fp is None:
            with jax.named_scope("plain_fp"):
                fp_hi, fp_lo = fpr.fingerprint(svecs, consts, jnp)
        else:
            fp_hi, fp_lo = K.orbit_keys(bounds, tuple(sorts), orbit_fp,
                                        consts, succs, svecs, valid)
        with jax.named_scope("invariants"):
            if predicates:
                inv_ok = jnp.stack(
                    [jax.vmap(jax.vmap(lambda t, p=p: p.ev(t, jnp)))(succs)
                     for p in predicates], axis=-1)
            else:
                inv_ok = jnp.ones(valid.shape + (0,), dtype=bool)
        return {"svecs": svecs, "valid": valid, "overflow": ovf,
                "fp_hi": fp_hi, "fp_lo": fp_lo, "inv_ok": inv_ok,
                "con_ok": jnp.ones_like(valid)}

    return step
