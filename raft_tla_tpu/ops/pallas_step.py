"""Pallas megakernel for the fused frontier step.

One ``pl.pallas_call`` runs the ENTIRE per-chunk pipeline — unpack,
successor expansion, canonicalize, orbit-minimal fingerprint, invariant
probes, StateConstraint — over a VMEM-resident block of candidate rows,
emitting only the per-lane ``(fp_hi, fp_lo)`` key lanes, the
``valid``/``overflow``/``inv_ok``/``con_ok`` masks and the packed
survivor vectors.  The XLA step (ops/kernels.build_step) lowers the same
stages as separate fusions with the ``[B, A, W]`` candidate block
round-tripping HBM between them; here a 1-D grid walks row blocks of the
chunk and each block's candidates stay on-core across all stages.

Construction — staged, not re-derived
-------------------------------------
The kernel body does not reimplement the step: it *stages the XLA step's
own jaxpr* (``jax.make_jaxpr`` over one row block) into the Pallas call,
re-evaluating it inside the kernel via ``jax.core.eval_jaxpr``.  Two
consequences, both load-bearing:

- **Bit-identity by construction.**  The kernel evaluates literally the
  same program the XLA path runs (same orbit scan, same prescan ladder
  and sig-prune gates resolved at build time, same invalid-lane
  zeroing), so the parity suite (tests/test_pallas_step.py) is a check
  on the staging machinery, not on a hand-kept twin that could drift.
  All three orbit-scan variants — full scan, prescan-grouped, sig-prune
  — ride along for free, selected by the same construction-time gates
  as the XLA step (the prescan's in-block grouping compacts per row
  block here; its outputs are bit-identical at any grouping scope by
  the rung argument in ops/kernels._PRESCAN_RUNGS).
- **Constants become kernel inputs.**  Pallas kernels may not close
  over array constants (ops/pallas_fp.i32_const), so the jaxpr's consts
  — permutation LUTs, fingerprint lane multipliers, action-parameter
  tables — are passed as broadcast inputs (whole-array BlockSpecs,
  index map pinned to the origin), normalized to int32 on the way in
  (Mosaic has no unsigned ops; same-bits reinterpret both ways).

VMEM blocking scheme
--------------------
Grid = ``(ceil(B / block_rows),)`` with ``block_rows`` = 128 by default:
per grid step the resident set is one ``[block, W]`` input slab, the
``[block, A, W]`` candidate block plus its masks/keys, and the LUT
inputs — ~``block * W * (A + 1) * 4`` bytes plus stage temporaries.  At
the flagship shape (3s/2v: W = 60, A = 42) a 128-row block is ~1.3 MB
of named slabs against the ~16 MB/core VMEM budget, leaving Mosaic
headroom for the scan carries; rows pad up to the block multiple with
zero rows (sliced off the outputs, so padding never changes a lane).

Mosaic status: REFUSED on the chip (chip_smoke.py kernels phase, TPU v5
lite, JAX 0.9.0 / libtpu 0.0.34, PR 21).  Building the staged flagship step
with ``interpret=False`` fails in Mosaic's gather lowering
(``jax/_src/pallas/mosaic/lowering.py:_gather_lowering_rule``):
``ValueError: Shape mismatch in input, indices and output`` — that rule
takes only a 2-D ``take_along_axis``-shaped gather whose input, indices and
output share one shape, and the staged step's permutation-LUT gathers are
not that.  So this module runs only under the Pallas interpreter
(ops/pallas_compat; the CPU parity-test path), ``--megakernel on`` on the
chip exits non-zero with that message (check.py surfaces it, nothing falls
back), and the gate stays auto=OFF (ops/kernels._megakernel_enabled).
Whether the kernel is rewritten to Mosaic's gather shape or deleted is a
later PR's call; ops/pallas_fp.py DOES compile under Mosaic and is
bit-equal to the jnp fingerprint on the same chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.ops import pallas_compat as pc
from raft_tla_tpu.ops import state as st

_BLOCK_ROWS = 128          # grid-block rows; chunks pad up to a multiple

I32 = jnp.int32

# The megakernel's whole-step write surface per spec subset — the fused
# analog of the per-family ops/kernels.TRANSFER_WRITES contract, for the
# width-safety analyzer: the fused kernel must not be able to write a
# packed field the per-family transfer twins never proved.  The analyzer
# (analysis/widthcheck.check_fused_coverage) cross-checks each tuple
# against the union of the families' declared write-sets plus the
# expansion postlude, so a family growing a new write — or a spec subset
# gaining a family — fails the lint loudly until this table is re-kept.
# History-only fields are listed unconditionally; the analyzer filters
# by mode.  Hand-maintained: do NOT derive from TRANSFER_WRITES (that
# would make the cross-check vacuous).
FUSED_WRITES = {
    "full": (
        "allLogs", "commitIndex", "eLeader", "eLog", "eTerm", "eVLog",
        "eVotes", "logLen", "logTerm", "logVal", "matchIndex", "msgCount",
        "msgHi", "msgLo", "nextIndex", "role", "term", "vGrant", "vLog",
        "vResp", "votedFor",
    ),
    # Receive alone already writes most of the schema, so the election
    # subset's union coincides with full.
    "election": (
        "allLogs", "commitIndex", "eLeader", "eLog", "eTerm", "eVLog",
        "eVotes", "logLen", "logTerm", "logVal", "matchIndex", "msgCount",
        "msgHi", "msgLo", "nextIndex", "role", "term", "vGrant", "vLog",
        "vResp", "votedFor",
    ),
    # No BecomeLeader in the replication subset: the election-history
    # fields are out of the fused write surface.
    "replication": (
        "allLogs", "commitIndex", "logLen", "logTerm", "logVal",
        "matchIndex", "msgCount", "msgHi", "msgLo", "nextIndex", "role",
        "term", "vGrant", "vLog", "vResp", "votedFor",
    ),
}


def _normalize(c):
    """Constants cross the Pallas boundary as int32 (same bits)."""
    if c.dtype in (jnp.uint32, jnp.bool_):
        return c.astype(I32)
    return c


def _restore(x, dtype):
    if dtype == jnp.uint32:
        return x.astype(jnp.uint32)
    if dtype == jnp.bool_:
        return x != 0
    return x


def _origin_map(ndim):
    return lambda i: (0,) * ndim


def _row_map(ndim):
    return lambda i: (i,) + (0,) * (ndim - 1)


def build_step_megakernel(bounds: Bounds, spec: str = "full",
                          invariants: tuple = (), symmetry: tuple = (),
                          view: str | None = None, *,
                          block_rows: int | None = None,
                          interpret: bool | None = None):
    """The megakernel twin of ops/kernels.build_step — same contract.

    ``step(vecs[B, W]) -> dict`` with exactly the dense step's keys and
    dtypes (``svecs``/``valid``/``overflow``/``fp_hi``/``fp_lo``/
    ``inv_ok``/``con_ok``), bit-identical lane for lane.  ``interpret``
    follows ops/pallas_compat: ``None`` auto-selects Mosaic on TPU and
    the interpreter elsewhere (there is no silent jnp fallback here —
    the jnp path IS the gate-off default a level above, in
    ``build_step``).
    """
    from raft_tla_tpu.ops import kernels

    block = int(block_rows or _BLOCK_ROWS)
    lay = st.Layout.of(bounds)
    W = lay.width
    n_inv = len(invariants)
    # The staged program: the XLA step itself (megakernel=False — this
    # builder IS the gate-on branch of build_step) over one row block,
    # masks/keys normalized to int32 for the kernel boundary.
    xla_step = kernels.build_step(bounds, spec, invariants, symmetry,
                                  view, megakernel=False)

    def _stage(vecs):
        out = xla_step(vecs)
        outs = (out["svecs"], out["valid"].astype(I32),
                out["overflow"].astype(I32), out["fp_hi"].astype(I32),
                out["fp_lo"].astype(I32))
        if n_inv:                   # zero-lane outputs can't cross Pallas
            outs += (out["inv_ok"].astype(I32),)
        return outs + (out["con_ok"].astype(I32),)

    closed = jax.make_jaxpr(_stage)(jnp.zeros((block, W), I32))
    consts = [jnp.asarray(c) for c in closed.consts]
    const_dtypes = [c.dtype for c in consts]
    out_avals = [v.aval for v in closed.jaxpr.outvars]
    A = out_avals[0].shape[1]
    n_c = len(consts)
    mode = pc.resolve(interpret, jnp_fallback=False)

    def kernel(*refs):
        c_refs, vec_ref = refs[:n_c], refs[n_c]
        out_refs = refs[n_c + 1:]
        cs = [_restore(r[...], dt) for r, dt in zip(c_refs, const_dtypes)]
        outs = jax.core.eval_jaxpr(closed.jaxpr, cs, vec_ref[...])
        for r, o in zip(out_refs, outs):
            r[...] = o

    @functools.partial(jax.jit, static_argnames=("Bp",))
    def _call(Bp, *args):
        from jax.experimental import pallas as pl

        in_specs = [pl.BlockSpec(c.shape, _origin_map(c.ndim))
                    for c in consts]
        in_specs.append(pl.BlockSpec((block, W), _row_map(2)))
        out_specs = [pl.BlockSpec((block,) + a.shape[1:],
                                  _row_map(a.ndim)) for a in out_avals]
        out_shape = [jax.ShapeDtypeStruct((Bp,) + a.shape[1:], a.dtype)
                     for a in out_avals]
        return pl.pallas_call(
            kernel, grid=(Bp // block,), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            interpret=mode == pc.INTERPRET)(*args)

    norm_consts = [_normalize(c) for c in consts]

    def step(vecs):
        B = vecs.shape[0]
        Bp = -(-B // block) * block
        vp = vecs if Bp == B else \
            jnp.zeros((Bp, W), I32).at[:B].set(vecs)
        outs = _call(Bp, *norm_consts, vp)
        outs = [o[:B] for o in outs]
        if n_inv:
            (svecs, valid, ovf, fp_hi, fp_lo, inv_ok, con_ok) = outs
            inv_ok = inv_ok != 0
        else:
            (svecs, valid, ovf, fp_hi, fp_lo, con_ok) = outs
            inv_ok = jnp.ones((B, A, 0), dtype=bool)
        return {"svecs": svecs, "valid": valid != 0, "overflow": ovf != 0,
                "fp_hi": fp_hi.astype(jnp.uint32),
                "fp_lo": fp_lo.astype(jnp.uint32),
                "inv_ok": inv_ok, "con_ok": con_ok != 0}

    return step
