"""BENCHMARK.json and the files it names: everything a cell is made of is
found by name, so a later PR adds a cell, a configuration, a traffic mix, a
per-layer metric or a spec family as new files plus new manifest entries, and
edits nothing."""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
# everything a run writes (level files, events, traces) goes here; the JAX
# compile cache sits beside it at the program's own <checkout>/.jax_cache
SCRATCH = os.path.join(ROOT, ".bench_scratch")

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


# the engines a configuration may name (``"engine"``; absent = ``ddd``), each
# with the level its boundary record carries: the ddd engine reports a level's
# close before it opens the next (``level`` = the level just counted), the
# mesh engine after (``level`` = the level about to open)
ENGINES = {"ddd": {"record_level_ahead": 0},
           "ddd-shard": {"record_level_ahead": 1}}


def engine_of(cfg: dict, chips: int | None = None) -> tuple:
    """``(engine name, devices it spans)`` as the configuration states them,
    refused by name where the engine is unknown, its capacities are missing,
    or the cell (``chips``) holds fewer chips than the mesh needs.  Touches
    no device."""
    name = cfg.get("engine", "ddd")
    if name not in ENGINES:
        raise ValueError(
            f"configuration {cfg['name']}: unknown engine {name!r} "
            f"(known: {', '.join(sorted(ENGINES))})")
    devices = cfg.get("devices", 1)
    if name == "ddd" and devices != 1:
        raise ValueError(f"configuration {cfg['name']}: engine 'ddd' runs "
                         f"on one device, the file says {devices}")
    if name not in cfg["engine_caps"]:
        raise ValueError(f"configuration {cfg['name']}: no engine_caps "
                         f"for its engine {name!r}")
    if chips is not None and chips < devices:
        raise ValueError(
            f"configuration {cfg['name']} spans {devices} devices "
            f"({name}); the cell holds {chips} chip(s)")
    return name, devices


# how a traffic's pass ends (``"end"``; absent = ``pin``): stopped by the first
# SIGINT at the pinned count of ``end_level``, or left to run to its own end
ENDS = ("pin", "fixpoint")


def end_of(traffic: dict, cfg: dict, traffic_name: str) -> str:
    """How this traffic's passes end on this configuration: ``"pin"`` or
    ``"fixpoint"`` (the pass is stopped by nothing and ``check()`` returns
    its verdict).  A fixpoint traffic is refused by name where its
    ``end_level`` is not the last level the configuration pins (the last
    that admits anything), where it also starts from a snapshot, and on the
    mesh engine: no cell needs either yet.  Touches no device."""
    end = traffic.get("end", "pin")
    if end not in ENDS:
        raise ValueError(f"traffic {traffic_name}: unknown end {end!r} "
                         f"(known: {', '.join(ENDS)})")
    if end == "pin":
        return end
    last = len(cfg["level_pins"]) - 1
    if traffic["end_level"] != last:
        raise ValueError(
            f"traffic {traffic_name} runs to the fixpoint: its end_level "
            f"{traffic['end_level']} has to be the last level configuration "
            f"{cfg['name']} pins, {last}")
    if traffic.get("start", "init") != "init":
        raise ValueError(
            f"traffic {traffic_name} runs to the fixpoint and starts from a "
            "snapshot: no cell has driven a resumed pass to its end yet")
    if cfg.get("engine", "ddd") != "ddd":
        raise ValueError(
            f"traffic {traffic_name} runs to the fixpoint; configuration "
            f"{cfg['name']} names the engine {cfg['engine']!r}: only 'ddd' "
            "has been driven to its own end")
    return end


_FAMILY = re.compile(r"^[A-Za-z][A-Za-z0-9_]{0,63}$")


def family(cfg: dict):
    """``benchmark/families/<name>.py``, the module behind the
    configuration's ``"family"`` (absent = ``raft``): the spec's plain
    reference, the crossing of a state to the program and back, the planted
    fault and a chunk step's counts, under the names ``families/raft.py``
    lists.  An unknown family is refused by name.  Touches no device."""
    name = cfg.get("family", "raft")
    there = os.path.join(BENCH, "families")
    if not (isinstance(name, str) and _FAMILY.match(name)
            and os.path.isfile(os.path.join(there, name + ".py"))):
        known = sorted(f[:-3] for f in os.listdir(there)
                       if f.endswith(".py") and not f.startswith("_"))
        raise ValueError(
            f"configuration {cfg.get('name')}: unknown family {name!r} "
            f"(known: {', '.join(known)})")
    return importlib.import_module("benchmark.families." + name)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def read_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


def cell(manifest: dict, workload: str) -> dict:
    """The cell's entry with its configuration and traffic files read in."""
    hits = [w for w in manifest["workloads"] if w["name"] == workload]
    if not hits:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise KeyError(f"unknown workload {workload!r} (known: {known})")
    w = dict(hits[0])
    cfg = next(c for c in manifest["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, cfg["file"]), encoding="utf-8") as f:
        w["config_data"] = json.load(f)
    w["traffic_data"] = read_json("traffic", w["traffic"] + ".json")
    return w


def metric_names(manifest: dict, workload: str, kind: str) -> list:
    """Names of the ``end_to_end`` / ``per_layer`` metrics this cell reports:
    those with no ``workloads`` key, or that list the cell."""
    return [m["name"] for m in manifest[kind]
            if "workloads" not in m or workload in m["workloads"]]


def metric_reader(name: str):
    """``benchmark/metrics/<name>.py``'s ``read(evidence)`` — end-to-end
    and per-layer metrics alike."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics." + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """``benchmark/peaks/<device kind, spaces as _>.json``: published peaks
    with their source.  A kind with no file is an error, not a default."""
    path = os.path.join(BENCH, "peaks", device_kind.replace(" ", "_") + ".json")
    if not os.path.isfile(path):
        raise KeyError(f"device kind {device_kind!r} has no {path}; add it "
                       "with the source of its peaks")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def problems(manifest: dict) -> list:
    """What the contract would refuse before any run (the checkable part)."""
    bad = []

    def name_ok(s, what):
        if not isinstance(s, str) or not _NAME.match(s):
            bad.append(f"{what}: bad name {s!r}")

    configs = {c["name"] for c in manifest["configs"]}
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"] for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for c in manifest["configs"]:
        name_ok(c["name"], "config")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
        if not os.path.isfile(os.path.join(ROOT, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        if not any(w["config"] == c["name"] for w in manifest["workloads"]):
            bad.append(f"config {c['name']} is used by no cell")
    pairs = set()
    for w in manifest["workloads"]:
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], "traffic")
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad.append(f"cell {w['name']}: why is not one line of <= 200")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"cell {w['name']}: config/traffic pair repeats")
        pairs.add((w["config"], w["traffic"]))
        if not os.path.isfile(os.path.join(
                BENCH, "traffic", w["traffic"] + ".json")):
            bad.append(f"cell {w['name']}: no traffic file")
        # the engine the configuration names: known, and the cell holds
        # the chips its mesh spans
        path = os.path.join(ROOT, files.get(w["config"], ""))
        if os.path.isfile(path):
            with open(path, encoding="utf-8") as f:
                cfg = json.load(f)
            try:
                family(cfg)
                engine_of(cfg, w["chips"])
                tpath = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
                if os.path.isfile(tpath):
                    end_of(read_json("traffic", w["traffic"] + ".json"),
                           cfg, w["traffic"])
            except ValueError as e:
                bad.append(f"cell {w['name']}: {e}")
    if sum(w["chips"] == 4 for w in manifest["workloads"]) > \
            max(1, len(cells) // 2):
        bad.append("too many four-chip cells")
    names = cells + sorted(configs)
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            name_ok(m["name"], kind)
            names.append(m["name"])
            if not _UNIT.match(m["unit"]):
                bad.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{m['name']}: better={m['better']!r}")
            if m["source"] not in _SOURCES:
                bad.append(f"{m['name']}: source={m['source']!r}")
            for wl in m.get("workloads", ()):
                if wl not in cells:
                    bad.append(f"{m['name']}: unknown cell {wl}")
            allowed = {"name", "unit", "better", "source", "workloads"} | (
                {"bound"} if kind == "end_to_end" else {"layer", "moves"})
            if set(m) - allowed:
                bad.append(f"{m['name']}: extra keys {set(m) - allowed}")
        if kind == "end_to_end":
            for m in manifest[kind]:
                if not 0.01 <= m["bound"] <= 0.25:
                    bad.append(f"{m['name']}: bound {m['bound']}")
                if m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"{m['name']}: end-to-end source")
        else:
            for m in manifest[kind]:
                if m["moves"] not in e2e:
                    bad.append(f"{m['name']}: moves unknown {m['moves']}")
        for m in manifest[kind]:
            if not os.path.isfile(os.path.join(
                    BENCH, "metrics", m["name"] + ".py")):
                bad.append(f"{m['name']}: no reader file")
    if len(set(names)) != len(names):
        bad.append("a name is used twice")
    for w in manifest["workloads"]:
        if len(metric_names(manifest, w["name"], "end_to_end")) < 2:
            bad.append(f"cell {w['name']}: fewer than two end-to-end metrics")
        if not metric_names(manifest, w["name"], "per_layer"):
            bad.append(f"cell {w['name']}: no per-layer metric")
    if not 1 <= manifest["run_seconds"] <= 51:
        bad.append(f"run_seconds {manifest['run_seconds']}")
    return bad
