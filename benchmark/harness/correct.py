"""What decides ``correct``.  Every number compared is printed beside its
limit; all limits are 0, the comparisons are exact (counts, and states
compared as states — the reference never sees a fingerprint).

The harness's half is here: the pins, the fixpoint and snapshot numbers, the
seeded draw, the comparison of a stream with the reference's successor
orbits, ``decide``.  The spec's half — the plain reference itself, how its
states are named and which fault is planted — is the configuration's family
(``manifest.family``); every state this module sees is a reference state."""

from __future__ import annotations

import random

from benchmark.harness import manifest as mf

SAMPLE = 256             # parents drawn with --seed
MIN_LEVEL_STATES = 4096  # the reference BFS stops at the first such level
#                          (a configuration may say otherwise: the toy does)


def __getattr__(name: str):
    # ``tests/test_full5.py`` reads the Raft family's STATE_FIELDS here, and
    # a benchmark PR may not edit it (PERF.md, Open questions)
    if name == "STATE_FIELDS":
        return mf.family({}).STATE_FIELDS
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def pass_checks(pass_list: list, pins: list, end_level: int) -> list:
    """(a) every pass's cumulative count at every level 0..B equals the
    pins and all passes agree; (b) no violation."""
    want = pins[:end_level + 1]
    mismatched = sum(
        sum(a != b for a, b in zip(p.levels, want))
        + abs(len(p.levels) - len(want)) for p in pass_list)
    tables = {tuple(p.levels) for p in pass_list}
    return [
        ("pass_level_mismatches", mismatched, 0),
        ("pass_tables_distinct_minus_1", len(tables) - 1, 0),
        ("passes_short_of_B", sum(not p.reached for p in pass_list), 0),
        ("violations", sum(p.violation is not None for p in pass_list), 0),
    ]


def fixpoint_checks(pass_list: list, pins: list) -> list:
    """A traffic whose passes run to their own end: every pass's result
    says ``complete = True``, and its count is the last pin, the space's
    total.  Where a pass's ``n_states`` is the total, its level table's own
    total is held to it too, levels past the last pin included (a table
    longer than the pins is held by check (a) at the pinned levels only)."""
    total = pins[-1]

    def off(p):
        table = (p.overshoot_levels or p.levels or [0])[-1]
        return abs((p.n_states or 0) - total) or abs(table - total)

    return [
        ("passes_incomplete",
         sum(p.complete is not True for p in pass_list), 0),
        ("fixpoint_total_diff", sum(off(p) for p in pass_list), 0),
    ]


def snapshot_checks(snapshot: dict, pass_list: list, pins: list,
                    end_level: int) -> list:
    """A traffic that starts at depth: the pass that wrote the snapshot met
    every pin 0..S; the snapshot holds the pin at S and whatever its stop
    overshot it by, at most 2 % of a pass's orbits; and every resumed
    engine's first count (its ``run_start``) is the count the snapshot was
    written with.  Levels 0..S of a resumed pass's table come out of the
    snapshot, so check (a) holds the snapshot itself to the pins."""
    s = snapshot["level"]
    first = [p.start_keys for p in pass_list]
    return [
        ("snapshot_pass_problems", int(snapshot["problem"] is not None), 0),
        ("snapshot_overshoot_orbits", abs(snapshot["keys"] - pins[s]),
         (pins[end_level] - pins[s]) // 50),
        ("snapshot_keys_diff",
         sum(snapshot["keys"] if k is None else abs(k - snapshot["keys"])
             for k in first), 0),
    ]


def reference_sample(cfg: dict, seed: int):
    """The plain reference's own BFS of the first levels (from the Init the
    configuration states, under its SYMMETRY axes), and the seeded sample of
    its deepest level with that sample's successor orbits."""
    fam = mf.family(cfg)
    cum, level, viol = fam.bfs_levels(
        cfg, cfg.get("sample_min_level_states", MIN_LEVEL_STATES))
    rng = random.Random(seed)
    parents = rng.sample(level, min(SAMPLE, len(level)))
    reps, n_trans, con = fam.successor_orbits(parents, cfg)
    return {"cumulative": cum, "violations": viol, "level": level,
            "parents": parents, "orbits": reps, "n_transitions": n_trans,
            "constraint": con, "key": fam.orbit_key(cfg)}


def _sample_key(ref: dict):
    """The function that names the orbit of a state of this sample.
    ``reference_sample`` states it; a sample put together by hand the way
    ``tests/test_full5.py`` does says Raft's ``sym`` instead."""
    if "key" in ref:
        return ref["key"]
    return mf.family({}).orbit_key(
        {"symmetry": ref["sym"],
         "bounds": {"n_values": ref.get("n_values", 0)}})


def sample_checks(ref: dict, got: dict, pins: list) -> list:
    """(c) the compiled segment's stream for the sampled parents against the
    reference (``got["states"]``: the streamed rows as reference states):
    the same set of successor orbits (states canonicalised in plain
    Python), key and orbit in one-to-one correspondence (the 64-bit
    key is exact on the sample), the same transition count and constraint
    flags.  Where the segment ran on a mesh (``got["misrouted"]``), the
    stream is the union of the shards' streams and one number joins:
    ``owner_misrouted``, streamed keys found on a shard other than
    ``key_hi % ndev``."""
    key = _sample_key(ref)
    streamed = [key(s) for s in got["states"]]
    sset = set(streamed)
    con_wrong = sum(ref["constraint"].get(k) is not c
                    for k, c in zip(streamed, got["con"]) if k in ref["orbits"])
    # the stream may repeat a key (the device filter is lossy; the host
    # dedups exactly), but key and orbit must name each other one to one
    by_key, by_orbit = {}, {}
    for k, o in zip(got["keys"].tolist(), streamed):
        by_key.setdefault(k, set()).add(o)
        by_orbit.setdefault(o, set()).add(k)
    conflicts = sum(len(v) > 1 for v in by_key.values()) \
        + sum(len(v) > 1 for v in by_orbit.values())
    cum = ref["cumulative"]
    # on a mesh, the guarantee the exchange adds: every candidate is filtered
    # and streamed by the shard that owns its key
    owner = [("owner_misrouted", got["misrouted"], 0)] \
        if "misrouted" in got else []
    return [
        ("ref_bfs_level_mismatches",
         sum(a != b for a, b in zip(cum, pins)) + max(0, len(cum) - len(pins)),
         0),
        ("ref_bfs_violations", ref["violations"], 0),
        ("sample_orbits_missing", len(ref["orbits"] - sset), 0),
        ("sample_orbits_extra", len(sset - ref["orbits"]), 0),
        ("sample_key_orbit_conflicts", conflicts, 0),
        ("sample_transitions_diff",
         abs(got["n_transitions"] - ref["n_transitions"]), 0),
        ("sample_constraint_flags_wrong", con_wrong, 0),
        ("sample_segment_flags", int(got["fail"] != 0)
         + int(not got["done"]), 0),
    ] + owner


def planted_fault(cfg: dict, level: list, seed: int) -> dict:
    """The planted fault, as the configuration's family plants it: a state
    of the reference's level, drawn with the seed and rewritten so that it
    holds every invariant itself and one step breaks one.  Returns the
    parent, ``violators`` = ``{orbit of a violating successor: names of the
    invariants it breaks}`` and ``key``, the function that names a state's
    orbit, all three judged by the plain reference."""
    return mf.family(cfg).planted_fault(cfg, level, seed)


def planted_checks(plant: dict, got: dict) -> list:
    """(d) every invariant is evaluated by the compiled segment: run from
    the planted parent, the engine has to report a violation, and the state
    (``got["state"]``, as a reference state) and invariant it names have to
    be among the reference's."""
    missed = got["invariant"] is None
    wrong = 0
    if not missed:
        names = plant["violators"].get(plant["key"](got["state"]))
        wrong = int(names is None or got["invariant"] not in names)
    return [("planted_violation_missed", int(missed), 0),
            ("planted_violation_misnamed", wrong, 0)]


def decide(checks: list, out=print) -> bool:
    ok = True
    for name, value, limit in checks:
        good = value <= limit
        ok = ok and good
        out(f"check {name}={value} limit={limit} {'ok' if good else 'FAIL'}")
    return ok
