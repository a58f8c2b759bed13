"""``resolve_model(spec)`` — one spec name, one model adapter.

The engines, serve lanes, and CLI never hard-code a protocol; they ask
the registry for a *model adapter* and go through its uniform surface:

- ``layout(bounds)`` / ``action_table(bounds)`` / ``build_step(config)``
  — the compiled step (same fused contract for every model);
  ``bit_schema(bounds)`` — the packed row the ddd engine stores and
  uploads (``ops/bitpack.BitSchema``);
- ``init_py`` / ``to_vec`` / ``from_vec`` / ``init_fingerprint`` /
  ``constraint_ok`` / ``py_invariant`` — the host-side half of the BFS
  (roots, trace decoding, frontier invariant probes);
- ``build_sim_expand`` / ``sim_codec`` / ``jnp_invariants`` /
  ``jnp_constraint`` / ``host_apply`` — the simulation surface (present
  when ``"simulate" in engines``): the per-state action fan-out the
  walker engines sample from, the struct<->vec codec, traced invariant /
  constraint probes, and the host interpreter one lane at a time for
  exact violation replay;
- ``render_state`` / ``render_trace`` — violation reporting;
- ``check_widths(bounds)`` — the admission-time width/validity gate;
- ``resolve_check_config(cfg, opts, path)`` — cfg-file -> CheckConfig
  for models that own their cfg mapping (non-Raft specs).

Raft resolves to :class:`RaftModel` (pure delegation to the existing
modules — zero behavior change), with ``ir-full`` / ``ir-election`` /
``ir-replication`` the same model stepped through
``frontend/raft_ir``-compiled kernels instead of the hand-written ones
(pinned bit-identical by tests).  ``twophase`` resolves to the bundled
two-phase-commit spec and ``paxos`` to single-decree Paxos, both compiled
entirely from frontend declarations; a schema that declares symmetric sorts
(``paxos``: ``Acceptor``, ``Value``) takes the cfg's SYMMETRY stanza.

Everything heavy imports inside methods: this module sits under
``frontend/__init__`` which ``models/spec.py``'s re-export pulls in, so
module level must stay light to avoid import cycles.
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from raft_tla_tpu.config import Bounds, CheckConfig


@dataclasses.dataclass(frozen=True)
class RaftModel:
    """The built-in Raft model; ``sub`` is the Next-subset family table
    name (``full``/``election``/``replication``), ``use_ir`` swaps the
    hand-written family kernels for the IR-compiled ones."""

    name: str
    sub: str
    use_ir: bool = False
    is_raft: bool = True
    engines: tuple = ("device", "host", "ref", "simulate")

    def layout(self, bounds):
        from raft_tla_tpu.ops import state as st
        return st.Layout.of(bounds)

    def action_table(self, bounds):
        from raft_tla_tpu.models import spec as S
        return S.action_table(bounds, self.sub)

    def build_step(self, config: CheckConfig):
        from raft_tla_tpu.ops import kernels
        fk = None
        if self.use_ir:
            from raft_tla_tpu.frontend import raft_ir
            fk = raft_ir.family_kernels(config.bounds)
        return kernels.build_step(
            config.bounds, self.sub, tuple(config.invariants),
            tuple(config.symmetry), view=config.view, family_kernels=fk)

    def bit_schema(self, bounds):
        from raft_tla_tpu.ops import bitpack
        return bitpack.BitSchema(bounds)

    def init_py(self, bounds):
        from raft_tla_tpu.models import interp
        return interp.init_state(bounds)

    def to_vec(self, py, bounds):
        from raft_tla_tpu.models import interp
        return interp.to_vec(py, bounds)

    def from_vec(self, vec, bounds):
        from raft_tla_tpu.models import interp
        from raft_tla_tpu.ops import state as st
        return interp.from_struct(
            st.unpack(vec, st.Layout.of(bounds), np), bounds)

    def init_fingerprint(self, config, init_py, init_vec):
        from raft_tla_tpu.ops import symmetry as sym_mod
        return sym_mod.init_fingerprint(config, init_py, init_vec)

    def group_order(self, config: CheckConfig) -> int:
        """|G| of the SYMMETRY axes the run reduces by (1 with none)."""
        from raft_tla_tpu.ops import symmetry as sym_mod
        b, axes = config.bounds, config.symmetry
        return (len(sym_mod.permutations(b)) if "Server" in axes else 1) \
            * (len(sym_mod.value_permutations(b)) if "Value" in axes else 1)

    def scan_moved_fields(self, config: CheckConfig) -> int | None:
        """How many fields the orbit scan still permutes and canonicalises
        an image at a time (``ops/symmetry.scan_forms``); ``None`` with no
        SYMMETRY: no scan."""
        from raft_tla_tpu.ops import symmetry as sym_mod
        if not config.symmetry:
            return None
        return len(sym_mod.scan_forms(config.bounds,
                                      tuple(config.symmetry))["moved"])

    def constraint_ok(self, py, bounds) -> bool:
        from raft_tla_tpu.models import interp
        return bool(interp.constraint_ok(py, bounds))

    def py_invariant(self, name):
        from raft_tla_tpu.models import invariants as inv_mod
        return inv_mod.py_invariant(name)

    def render_state(self, py, bounds, indent="    "):
        from raft_tla_tpu.utils import render
        return render.render_state(py, bounds, indent)

    def render_trace(self, violation, bounds):
        from raft_tla_tpu.utils import render
        return render.render_trace(violation, bounds)

    def check_widths(self, bounds):
        from raft_tla_tpu.analysis import widthcheck
        return widthcheck.check_widths(bounds, self.sub)

    # -- simulation surface (walker engines) --------------------------------

    def build_sim_expand(self, config: CheckConfig):
        from raft_tla_tpu.ops import kernels
        fk = None
        if self.use_ir:
            from raft_tla_tpu.frontend import raft_ir
            fk = raft_ir.family_kernels(config.bounds)
        return kernels.build_expand(config.bounds, self.sub,
                                    family_kernels=fk)

    def sim_codec(self, bounds):
        import jax.numpy as jnp
        from raft_tla_tpu.ops import state as st
        lay = st.Layout.of(bounds)
        return (lay.width,
                lambda t: st.pack(t, jnp),
                lambda v: st.unpack(v, lay, jnp))

    def jnp_invariants(self, config: CheckConfig):
        from raft_tla_tpu.models import invariants as inv_mod
        return tuple(inv_mod.jnp_invariant(nm, config.bounds)
                     for nm in config.invariants)

    def jnp_constraint(self, bounds):
        import jax.numpy as jnp
        from raft_tla_tpu.ops import state as st
        return lambda t: st.constraint_ok(t, bounds, jnp)

    def host_apply(self, py, inst, bounds):
        from raft_tla_tpu.models import interp
        return interp.apply_action(py, inst, bounds)


class SchemaModel:
    """What every model declared purely as frontend data shares: the
    delegation of layout, action table, packed row, Init, row codec and
    rendering to its declaration module (``_mod()``: a ``SCHEMA``, an
    ``action_table``, ``init_state`` / ``to_vec`` / ``from_vec``,
    ``render_state`` / ``render_trace``).  The step, the invariants and the
    cfg mapping are each spec's own."""

    is_raft = False
    use_ir = True

    def layout(self, bounds):
        return self._mod().SCHEMA.layout(bounds)

    def action_table(self, bounds):
        return self._mod().action_table(bounds)

    def bit_schema(self, bounds):
        from raft_tla_tpu.ops import bitpack
        return bitpack.BitSchema.of_schema(self._mod().SCHEMA, bounds)

    def init_py(self, bounds):
        return self._mod().init_state(bounds)

    def to_vec(self, py, bounds):
        return self._mod().to_vec(py, bounds)

    def from_vec(self, vec, bounds):
        return self._mod().from_vec(vec, bounds)

    @property
    def sorts(self) -> tuple:
        """The symmetric sorts this model can reduce by (the names a cfg's
        SYMMETRY stanza may list): what its schema declares."""
        return self._mod().SCHEMA.sort_names

    def init_fingerprint(self, config, init_py, init_vec):
        # views are rejected at config time; under SYMMETRY the key of
        # Init is its orbit's (the plain loop, on the host), else the
        # generic lane-constants branch — either way the fingerprint the
        # compiled schema step computes on device.
        from raft_tla_tpu.ops import symmetry as sym_mod
        if not config.symmetry:
            return sym_mod.init_fingerprint(config, init_py, init_vec)
        lay = self.layout(config.bounds)
        hi, lo = sym_mod.schema_orbit_fingerprint(
            lay.unpack(np.asarray(init_vec, np.int32), np), lay,
            sym_mod._host_consts(lay.width), tuple(config.symmetry), np)
        return int(hi), int(lo)

    def group_order(self, config: CheckConfig) -> int:
        """|G| of the sorts the run reduces by (1 with none)."""
        from raft_tla_tpu.ops import symmetry as sym_mod
        return len(sym_mod.schema_group(
            self._mod().SCHEMA, config.bounds, tuple(config.symmetry)))

    def scan_moved_fields(self, config: CheckConfig) -> int | None:
        """0 under SYMMETRY — a schema's whole key is ``features . table``
        (``ops/symmetry.build_schema_orbit_fp``) — and ``None`` with none."""
        return 0 if config.symmetry else None

    def constraint_ok(self, py, bounds) -> bool:
        return True      # the state space is finite with no constraint

    def render_state(self, py, bounds, indent="    "):
        return self._mod().render_state(py, bounds, indent)

    def render_trace(self, violation, bounds):
        return self._mod().render_trace(violation, bounds)

    def emit_tla(self, out_dir, bounds, invariants=(), symmetry=()):
        if not symmetry:
            return self._mod().emit_tla(out_dir, bounds, invariants)
        return self._mod().emit_tla(out_dir, bounds, invariants,
                                    symmetry=tuple(symmetry))

    def _refuse_raft_options(self, cfg, opts) -> None:
        """VIEW and faithful mode are Raft's: refused by name.  So is
        SYMMETRY for a schema that declares no symmetric sort."""
        if (cfg.symmetry or opts.symmetry) and not self.sorts:
            raise ValueError("symmetry reduction is not supported for "
                             f"{self.name}")
        if cfg.view or opts.view:
            raise ValueError(f"views are not supported for {self.name}")
        if opts.faithful:
            raise ValueError("faithful mode (history variables) is "
                             "Raft-specific")

    def _symmetry(self, cfg, opts, bounds, where: str) -> tuple:
        """The sorts a run reduces by, in the schema's order: those the
        cfg's SYMMETRY stanza names (the repository's convention: the
        sort's own name, ``SYMMETRY Acceptor Value``), every declared one
        under ``--symmetry``.  Refused by name: a sort the schema does not
        declare, and a bound constant table with an axis over a named sort
        that some permutation of it does not map onto itself."""
        schema = self._mod().SCHEMA
        # the emitted twin's own name for a union of Permutations:
        # Sym + the sorts' names (SymAcceptorValue), as Raft's SymServer
        unions = {"Sym" + "".join(c): c
                  for k in range(1, len(self.sorts) + 1)
                  for c in itertools.combinations(self.sorts, k)}
        named = set(self.sorts) if opts.symmetry else set()
        for nm in cfg.symmetry:
            named |= set(unions.get(nm, (nm,)))
        unknown = sorted(named - set(self.sorts))
        if unknown:
            raise ValueError(
                f"{where}: SYMMETRY {unknown[0]} not supported: "
                f"{self.name} declares the symmetric sorts "
                f"{', '.join(self.sorts)} (name them so, or as the "
                f"emitted twin does: {', '.join(sorted(unions))})")
        sorts = tuple(s for s in self.sorts if s in named)
        variant = schema.variant_const(bounds, sorts) if sorts else None
        if variant:
            raise ValueError(
                f"{where}: SYMMETRY {variant[1]} is unsound here: the "
                f"constant {variant[0]} is not invariant under the "
                f"permutations of {variant[1]} (some permutation maps it "
                "onto another table; TLC would not check this)")
        return sorts


class TwoPhaseModel(SchemaModel):
    """Bounded two-phase commit, compiled from frontend declarations
    (``frontend/twophase``): schema layout, IR-built step, predicate
    invariants.  ``bounds.n_servers`` is the RM count; the other bound
    knobs are inert for this state space."""

    name = "twophase"
    sub = "twophase"
    engines = ("host", "ddd", "simulate")

    def _mod(self):
        from raft_tla_tpu.frontend import twophase
        return twophase

    def universe_line(self, bounds) -> str:
        return f"{bounds.n_servers} resource managers"

    def _predicate(self, name: str):
        from raft_tla_tpu.frontend.predicate import (compile_predicate,
                                                     is_expression)
        tp = self._mod()
        text = tp.INVARIANTS.get(name)
        if text is None:
            if not is_expression(name):
                raise ValueError(
                    f"unknown twophase invariant {name!r} (known: "
                    f"{', '.join(sorted(tp.INVARIANTS))}; or write a "
                    "predicate expression over the state fields)")
            text = name
        return compile_predicate(text, fields=tp.SCHEMA.field_names)

    def build_step(self, config: CheckConfig):
        from raft_tla_tpu.frontend import actions
        tp = self._mod()
        preds = tuple(self._predicate(nm) for nm in config.invariants)
        return actions.build_schema_step(
            tp.SCHEMA, tp.ACTIONS, tp.action_table(config.bounds),
            config.bounds, predicates=preds)

    def py_invariant(self, name):
        tp = self._mod()
        pred = self._predicate(name)

        def check(py, bounds) -> bool:
            lay = tp.SCHEMA.layout(bounds)
            struct = lay.unpack(tp.to_vec(py, bounds), np)
            return bool(pred.ev(struct, np))

        return check

    def check_widths(self, bounds):
        from raft_tla_tpu.frontend.schema import check_schema
        return check_schema(self._mod().SCHEMA, bounds)

    # -- simulation surface (walker engines) --------------------------------

    def build_sim_expand(self, config: CheckConfig):
        from raft_tla_tpu.frontend import actions
        tp = self._mod()
        return actions.build_schema_expand(
            tp.SCHEMA, tp.ACTIONS, tp.action_table(config.bounds),
            config.bounds)

    def sim_codec(self, bounds):
        import jax.numpy as jnp
        lay = self._mod().SCHEMA.layout(bounds)
        return (lay.width,
                lambda t: lay.pack(t, jnp),
                lambda v: lay.unpack(v, jnp))

    def jnp_invariants(self, config: CheckConfig):
        import jax.numpy as jnp
        preds = tuple(self._predicate(nm) for nm in config.invariants)
        return tuple((lambda t, p=p: p.ev(t, jnp)) for p in preds)

    def jnp_constraint(self, bounds):
        import jax.numpy as jnp
        return lambda t: jnp.bool_(True)   # finite space, no constraint

    def host_apply(self, py, inst, bounds):
        return self._mod().apply_instance(py, inst, bounds)

    def resolve_check_config(self, cfg, opts, path=None):
        """TLC cfg -> (CheckConfig, properties) for the twophase model —
        the non-Raft face of ``serve/jobs.resolve_check_config``."""
        tp = self._mod()
        where = path or "cfg"
        # the bounded twin's names (emit_tla) and the source's own
        # (TwoPhase.tla: TPSpec == TPInit /\ [][TPNext]_vars), so that the
        # source's TwoPhase.cfg runs as it is written
        if cfg.specification not in (None, "Spec", "TPSpec"):
            raise ValueError(
                f"{where}: twophase checks SPECIFICATION Spec (or the "
                f"source's TPSpec) only (got {cfg.specification!r})")
        if cfg.init not in (None, "Init", "TPInit") \
                or cfg.next not in (None, "Next", "TPNext"):
            raise ValueError(
                f"{where}: twophase supports INIT Init / NEXT Next (or "
                "the source's TPInit / TPNext) only")
        if cfg.properties:
            raise ValueError(
                f"{where}: temporal properties are not supported for "
                "twophase")
        if cfg.constraints:
            raise ValueError(
                f"{where}: twophase is finite; CONSTRAINT is not supported")
        self._refuse_raft_options(cfg, opts)
        rms = cfg.constants.get("RM", cfg.constants.get("Server"))
        if not isinstance(rms, list) or not rms:
            raise ValueError(
                f"{where}: twophase needs CONSTANT RM = {{r1, ...}} "
                "(a nonempty finite set)")
        invariants = tuple(cfg.invariants) or (tp.DEFAULT_INVARIANT,)
        for nm in invariants:        # parse/validate now, fail loudly here
            self._predicate(nm)
        bounds = Bounds(n_servers=len(rms), n_values=1)
        config = CheckConfig(
            bounds=bounds, spec="twophase", invariants=invariants,
            symmetry=(), chunk=opts.chunk, check_deadlock=opts.deadlock,
            view=None)
        return config, ()


class PaxosModel(SchemaModel):
    """Lamport's single-decree Paxos, compiled from frontend declarations
    (``frontend/paxos``), the twin of :class:`TwoPhaseModel`.
    ``bounds.n_servers`` / ``n_values`` are the acceptor and value counts,
    ``bounds.max_term`` the maximum ballot, and ``bounds.constants`` binds
    the ``Quorum`` table from the cfg; the other bound knobs are inert."""

    name = "paxos"
    sub = "paxos"
    engines = ("host", "ddd")

    def _mod(self):
        from raft_tla_tpu.frontend import paxos
        return paxos

    def _consts(self, bounds) -> dict:
        return self._mod().SCHEMA.bind_consts(bounds, bounds.constants)

    def _predicate(self, name: str, bounds):
        from raft_tla_tpu.frontend.predicate import (compile_predicate,
                                                     is_expression)
        px = self._mod()
        if name in px.INVARIANTS:
            text = px.INVARIANTS[name](bounds)
        elif is_expression(name):
            text = name
        else:
            raise ValueError(
                f"unknown paxos invariant {name!r} (known: "
                f"{', '.join(sorted(px.INVARIANTS))}; or write a "
                "predicate expression over the state fields)")
        return compile_predicate(text, fields=px.SCHEMA.field_names,
                                 consts=self._consts(bounds))

    def universe_line(self, bounds) -> str:
        return (f"{bounds.n_servers} acceptors, {bounds.n_values} values, "
                f"ballots 0..{bounds.max_term}, "
                f"{len(dict(bounds.constants)['Quorum'])} quorums")

    def build_step(self, config: CheckConfig):
        from raft_tla_tpu.frontend import actions
        px, b = self._mod(), config.bounds
        preds = tuple(self._predicate(nm, b) for nm in config.invariants)
        return actions.build_schema_step(
            px.SCHEMA, px.ACTIONS, px.action_table(b), b,
            predicates=preds, const_tables=self._consts(b),
            sorts=tuple(config.symmetry))

    def py_invariant(self, name):
        px = self._mod()
        compiled = {}           # bounds -> predicate (the table is bound)

        def check(py, bounds) -> bool:
            if bounds not in compiled:
                compiled[bounds] = self._predicate(name, bounds)
            struct = px.SCHEMA.layout(bounds).unpack(
                px.to_vec(py, bounds), np)
            return bool(compiled[bounds].ev(struct, np))

        return check

    def check_widths(self, bounds):
        from raft_tla_tpu.frontend.schema import check_schema
        from raft_tla_tpu.frontend.widthgen import check_schema_writes
        px = self._mod()
        return check_schema(px.SCHEMA, bounds) + check_schema_writes(
            px.SCHEMA, px.ACTIONS, bounds)

    def resolve_check_config(self, cfg, opts, path=None):
        """TLC cfg -> (CheckConfig, properties) for the paxos model.  The
        cfg binds ``Acceptor``, ``Value`` and ``Quorum`` (a set of sets over
        ``Acceptor``, taken as written); ``Ballot <- MCBallot`` is recorded
        by the parser and stands for ``0..MaxBallot``: the cfg's
        ``MaxBallot = N`` where it binds one (the emitted twin does), else
        ``--max-term``."""
        from raft_tla_tpu.utils import cfgparse
        px = self._mod()
        where = path or "cfg"
        if cfg.specification not in (None, "Spec"):
            raise ValueError(
                f"{where}: paxos checks SPECIFICATION Spec only (got "
                f"{cfg.specification!r})")
        if cfg.init not in (None, "Init") or cfg.next not in (None, "Next"):
            raise ValueError(
                f"{where}: paxos supports INIT Init / NEXT Next only")
        if cfg.properties:
            raise ValueError(
                f"{where}: temporal properties are not supported for paxos "
                f"(got {cfg.properties}; the refinement V!Spec waits on "
                "ROADMAP queue 2 A.5)")
        if cfg.constraints:
            raise ValueError(
                f"{where}: paxos is bounded by its ballots; CONSTRAINT is "
                "not supported")
        self._refuse_raft_options(cfg, opts)
        accs = cfg.constants.get("Acceptor")
        vals = cfg.constants.get("Value")
        for nm, v in (("Acceptor", accs), ("Value", vals)):
            if not isinstance(v, list) or not v \
                    or not all(isinstance(x, str) for x in v):
                raise ValueError(
                    f"{where}: paxos needs CONSTANT {nm} = {{...}} (a "
                    "nonempty finite set of model values)")
        rows = cfgparse.set_of_subsets(cfg, "Quorum", "Acceptor", path)
        max_ballot = opts.max_term
        if "MaxBallot" in cfg.constants:
            text = cfg.constants["MaxBallot"]
            if not (isinstance(text, str) and text.isdigit()):
                raise ValueError(f"{where}: MaxBallot = {text!r} is not a "
                                 "natural number")
            max_ballot = int(text)
        bounds = Bounds(n_servers=len(accs), n_values=len(vals),
                        max_term=max_ballot,
                        constants=(("Quorum", tuple(rows)),))
        invariants = tuple(cfg.invariants) or (px.DEFAULT_INVARIANT,)
        for nm in invariants:        # parse/validate now, fail loudly here
            self._predicate(nm, bounds)
        config = CheckConfig(
            bounds=bounds, spec="paxos", invariants=invariants,
            symmetry=self._symmetry(cfg, opts, bounds, where),
            chunk=opts.chunk, check_deadlock=opts.deadlock, view=None)
        return config, ()


_RAFT_SUBS = ("full", "election", "replication")


def known_specs() -> tuple:
    return _RAFT_SUBS + tuple(f"ir-{s}" for s in _RAFT_SUBS) + (
        "raft", "twophase", "paxos")


def resolve_model(spec: str):
    """Spec name -> model adapter.  Unknown names raise with a
    did-you-mean, mirroring the cfg-name diagnostics."""
    if spec in _RAFT_SUBS:
        return RaftModel(spec, spec)
    if spec == "raft":
        return RaftModel("raft", "full")
    if spec.startswith("ir-") and spec[3:] in _RAFT_SUBS:
        return RaftModel(spec, spec[3:], use_ir=True)
    if spec == "twophase":
        return TwoPhaseModel()
    if spec == "paxos":
        return PaxosModel()
    from raft_tla_tpu.utils import cfgparse
    hints = cfgparse.suggest(spec, known_specs())
    hint_txt = f" (did you mean: {', '.join(hints)}?)" if hints else ""
    raise ValueError(
        f"unknown spec {spec!r}{hint_txt}; known: "
        f"{', '.join(sorted(known_specs()))}")
