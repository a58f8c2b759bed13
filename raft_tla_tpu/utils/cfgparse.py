"""Parser for TLC model config files (the L5 layer).

Byte-compatible with the reference's ``raft.cfg:1-15``, whose grammar is:

- ``SPECIFICATION Spec``            (``raft.cfg:1``)
- ``INVARIANT NoTwoLeaders``        (``raft.cfg:3``)
- ``CONSTANTS`` followed by indented ``Name = binding`` lines with optional
  ``\\*`` end-of-line comments (``raft.cfg:5-15``), where a binding is either
  a model value (``Follower = "Follower"`` / ``Nil = Nil``), a finite set
  (``Server = {s1, s2, s3}``), or a set of sets of model values (``Quorum =
  {{a1, a2}, {a1, a3}, {a2, a3}}``, a list of lists); ``Name <- Definition``
  is recorded in :attr:`TLCConfig.substitutions` and not followed (the
  definition lives in a module this parser never reads: the model that takes
  the cfg says what the name stands for).

Additionally understood (the TLC stanzas the reference does not use but the
checker supports): ``INVARIANTS``, ``CONSTRAINT``, ``PROPERTY``,
``CONSTANT`` (singular), so configs written for stock TLC parse unchanged.

The parsed cfg is mapped onto the built-in compiled Raft model: the cardinality
of ``Server``/``Value`` becomes :class:`raft_tla_tpu.config.Bounds`
``n_servers``/``n_values``; invariant names resolve against the invariant
registry.  Bound parameters (MaxTerm &c.) come from CLI/:class:`Bounds`, and
``models/tla_export.py`` emits the matching ``CONSTRAINT`` module for stock
TLC parity runs.

Diagnostics are load-bearing here: a typo'd stanza keyword or invariant name
must fail *loudly at parse/resolve time* with the offending line number and
the known names (unknown names silently checking nothing is the classic TLC
footgun).  The parser records the source line of every name it reads
(:attr:`TLCConfig.lines`) so both the hard-error path
(:func:`resolve_names`, used by check.py) and the diagnostic path
(analysis/cfglint Pass 2) can point at the exact line.
"""

from __future__ import annotations

import dataclasses
import difflib
import re

_STANZAS = (
    "SPECIFICATION",
    "INVARIANTS",
    "INVARIANT",
    "CONSTANTS",
    "CONSTANT",
    "CONSTRAINTS",
    "CONSTRAINT",
    "PROPERTIES",
    "PROPERTY",
    "INIT",
    "NEXT",
    "SYMMETRY",
    "VIEW",
)


@dataclasses.dataclass
class TLCConfig:
    specification: str | None = None
    init: str | None = None
    next: str | None = None
    invariants: list[str] = dataclasses.field(default_factory=list)
    properties: list[str] = dataclasses.field(default_factory=list)
    constraints: list[str] = dataclasses.field(default_factory=list)
    # Name -> python value: list[str] for set bindings (list[list[str]] for
    # a set of sets), str for model values.
    constants: dict = dataclasses.field(default_factory=dict)
    # Name -> the definition it is replaced by (``Ballot <- MCBallot``)
    substitutions: dict = dataclasses.field(default_factory=dict)
    symmetry: list[str] = dataclasses.field(default_factory=list)
    view: str | None = None
    # (kind, name) -> 1-based source line, e.g. ("invariant", "NoTwoLeaders")
    # -> 3.  Kinds: invariant, property, constraint, constant, symmetry,
    # view, specification, init, next.  Diagnostics only; equality and the
    # model mapping ignore it.
    lines: dict = dataclasses.field(default_factory=dict, compare=False)

    def server_names(self) -> list[str]:
        v = self.constants.get("Server")
        if not isinstance(v, list):
            raise ValueError("cfg does not bind Server to a finite set")
        return v

    def value_names(self) -> list[str]:
        v = self.constants.get("Value")
        if not isinstance(v, list):
            raise ValueError("cfg does not bind Value to a finite set")
        return v

    def line_of(self, kind: str, name: str) -> int | None:
        return self.lines.get((kind, name))


def _strip_comment(line: str) -> str:
    # TLA+ end-of-line comment: \* ... (also tolerate (* ... *) on one line)
    line = re.sub(r"\(\*.*?\*\)", " ", line)
    idx = line.find("\\*")
    if idx >= 0:
        line = line[:idx]
    return line.strip()


def _parse_set(text: str) -> list:
    """``{a, b}`` -> ``["a", "b"]``; an element may itself be a set literal
    (``{{a, b}, {c}}`` -> ``[["a", "b"], ["c"]]``), to any depth."""
    inner = text.strip()
    if not (inner.startswith("{") and inner.endswith("}")):
        raise ValueError(f"not a set literal: {text!r}")
    toks, depth, start = [], 0, 1
    for pos in range(1, len(inner) - 1):
        ch = inner[pos]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth < 0:
                raise ValueError(f"unbalanced braces in set literal: "
                                 f"{text!r}")
        elif ch == "," and depth == 0:
            toks.append(inner[start:pos].strip())
            start = pos + 1
    if depth:
        raise ValueError(f"unbalanced braces in set literal: {text!r}")
    last = inner[start:-1].strip()
    if not toks and not last:
        return []
    toks.append(last)
    if any(not t for t in toks):
        raise ValueError(f"empty element in set literal: {text!r}")
    return [_parse_set(t) if t.startswith("{") else t for t in toks]


def set_of_subsets(cfg: "TLCConfig", name: str, of: str,
                   path: str | None = None) -> list:
    """The constant ``name`` as a set of nonempty subsets of the constant
    ``of`` (``Quorum`` over ``Acceptor``): one 0/1 row an element of
    ``name``, one entry a member of ``of`` in the order ``of`` binds them.
    Refuses, with the binding's line, a ``name`` that is not a set of sets,
    an element that is empty, and a member that ``of`` does not hold."""
    line = cfg.line_of("constant", name)
    where = f"{path or 'cfg'}{f' line {line}' if line else ''}: "
    base = cfg.constants.get(of)
    if not isinstance(base, list) or not base \
            or not all(isinstance(x, str) for x in base):
        raise ValueError(f"{path or 'cfg'}: {name} needs CONSTANT {of} = "
                         "{...} (a nonempty finite set of model values)")
    sets = cfg.constants.get(name)
    if not isinstance(sets, list) or not sets \
            or not all(isinstance(q, list) for q in sets):
        raise ValueError(
            f"{where}{name} has to be a nonempty set of sets over {of}, "
            f"e.g. {name} = {{{{{base[0]}}}}}; got {sets!r}")
    rows = []
    for q in sets:
        if not q:
            raise ValueError(f"{where}{name} holds the empty set: every "
                             f"element has to name a member of {of}")
        bad = [x for x in q if x not in base]
        if bad:
            raise ValueError(
                f"{where}{name} element {{{', '.join(map(str, q))}}}: "
                f"{bad[0]} is not in {of} = {{{', '.join(base)}}}")
        rows.append(tuple(int(x in q) for x in base))
    return rows


def parse_cfg(text: str) -> TLCConfig:
    cfg = TLCConfig()
    mode: str | None = None
    for lineno, raw in enumerate(text.splitlines(), start=1):
        line = _strip_comment(raw)
        if not line:
            continue
        # A stanza keyword may start the line, optionally with an inline value
        # (separated by any whitespace — stock TLC accepts tabs too).
        parts = line.split(None, 1)
        if parts[0] in _STANZAS:
            mode = parts[0]
            line = parts[1].strip() if len(parts) > 1 else ""
            if not line:
                continue
        if mode in ("SPECIFICATION",):
            cfg.specification = line
            cfg.lines[("specification", line)] = lineno
        elif mode == "INIT":
            cfg.init = line
            cfg.lines[("init", line)] = lineno
        elif mode == "NEXT":
            cfg.next = line
            cfg.lines[("next", line)] = lineno
        elif mode in ("INVARIANT", "INVARIANTS"):
            # Bare registry names may share a line like stock TLC; any
            # line that is NOT all bare identifiers is one whole-line
            # predicate EXPRESSION (frontend/predicate.py grammar).
            from raft_tla_tpu.frontend.predicate import is_expression
            names = line.split()
            if any(is_expression(nm) for nm in names):
                text = " ".join(names)
                cfg.invariants.append(text)
                cfg.lines[("invariant", text)] = lineno
            else:
                for name in names:
                    cfg.invariants.append(name)
                    cfg.lines[("invariant", name)] = lineno
        elif mode in ("PROPERTY", "PROPERTIES"):
            # temporal FORMULAS (<>P, []<>P, P ~> Q) are one property
            # per line; bare names may share a line like INVARIANTS
            if "<>" in line or "~>" in line:
                formula = " ".join(line.split())
                cfg.properties.append(formula)
                cfg.lines[("property", formula)] = lineno
            else:
                for name in line.split():
                    cfg.properties.append(name)
                    cfg.lines[("property", name)] = lineno
        elif mode in ("CONSTRAINT", "CONSTRAINTS"):
            for name in line.split():
                cfg.constraints.append(name)
                cfg.lines[("constraint", name)] = lineno
        elif mode == "SYMMETRY":
            for name in line.split():
                cfg.symmetry.append(name)
                cfg.lines[("symmetry", name)] = lineno
        elif mode == "VIEW":
            cfg.view = line
            cfg.lines[("view", line)] = lineno
        elif mode in ("CONSTANT", "CONSTANTS"):
            sub = re.fullmatch(r"(\w+)\s*<-\s*(\w+)", line)
            if sub:
                # a substitution is recorded, never followed
                cfg.substitutions[sub.group(1)] = sub.group(2)
                cfg.lines[("constant", sub.group(1))] = lineno
                continue
            if "=" not in line:
                raise ValueError(
                    f"line {lineno}: bad CONSTANTS binding: {raw!r}")
            name, _, val = line.partition("=")
            name, val = name.strip(), val.strip()
            if val.startswith("{"):
                try:
                    cfg.constants[name] = _parse_set(val)
                except ValueError as e:
                    raise ValueError(f"line {lineno}: {e}") from None
            else:
                cfg.constants[name] = val.strip('"')
            cfg.lines[("constant", name)] = lineno
        else:
            raise ValueError(
                f"line {lineno}: line outside any stanza: {raw!r} "
                f"(known stanzas: {', '.join(_STANZAS)})")
    return cfg


def suggest(name: str, known) -> list[str]:
    """Did-you-mean candidates for an unknown cfg name."""
    return difflib.get_close_matches(name, sorted(known), n=3, cutoff=0.5)


def unknown_names(names, known) -> list[tuple[str, list[str]]]:
    """The subset of ``names`` not in ``known``, each with suggestions.
    Non-raising — analysis/cfglint turns these into findings."""
    known = set(known)
    return [(n, suggest(n, known)) for n in names if n not in known]


def resolve_names(names, known, kind: str, *, cfg: TLCConfig | None = None,
                  path: str | None = None) -> list[str]:
    """Validate cfg names against a registry, raising on the first unknown
    with the offending source line, a did-you-mean, and the full registry
    (shared by check.py config resolution and the Pass 2 lint)."""
    bad = unknown_names(names, known)
    if not bad:
        return list(names)
    name, hints = bad[0]
    where = ""
    if cfg is not None:
        lineno = cfg.line_of(kind, name)
        if lineno is not None:
            where = f"{path or 'cfg'} line {lineno}: "
    hint_txt = f" (did you mean: {', '.join(hints)}?)" if hints else ""
    raise ValueError(
        f"{where}unknown {kind} {name!r}{hint_txt}; "
        f"known: {', '.join(sorted(known))}")


def load_cfg(path: str) -> TLCConfig:
    with open(path, "r", encoding="utf-8") as f:
        return parse_cfg(f.read())
