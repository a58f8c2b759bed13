"""The checker CLI — the L6 layer (SURVEY §1), ``python -m raft_tla_tpu.check``.

Drives a full checking run from a stock TLC model config (the reference's
``raft.cfg:1-15`` parses unchanged), mirroring the TLC invocation surface the
reference relies on (``.vscode/settings.json:3-4``): spec + cfg in,
pass/violation + trace out, per-action coverage (TLC's ``-coverage``), and
exit codes distinguishing success, violation, and error (TLC's own
convention: 0 ok, 12 safety violation).

The model universe (``Server``/``Value``) comes from the cfg; the state
constraint — which stock TLC leaves to the missing ``CONSTRAINT`` stanza
(SURVEY §0 defect 2) — comes from ``--max-*`` flags.  ``--emit-tlc DIR``
writes the matching ``MCraft.tla``/``MCraft.cfg`` pair so the identical
bounded model can be run under stock TLC on a JVM host (oracle parity,
SURVEY §4.3).

Engines (``--engine``), six: ``device`` (default; full search resident on
the accelerator), ``ddd`` (delayed duplicate detection: exact dedup in host
RAM, the engine the benchmark measures), ``shard`` (multi-device mesh over
ICI), ``ddd-shard`` (``ddd`` sharded over a mesh), ``host`` (per-chunk jit,
host dedup), ``ref`` (pure-Python oracle BFS).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

EXIT_OK = 0
EXIT_DEADLOCK = 11       # TLC's exit code for deadlock
EXIT_VIOLATION = 12      # TLC's exit code for safety-property violations
EXIT_LIVENESS = 13       # TLC's exit code for liveness-property violations
EXIT_ERROR = 1
EXIT_STOPPED = 14        # ours: stopped before exhaustion (resumable) —
#                          no verdict; the campaign supervisor keys on it


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m raft_tla_tpu.check",
        description="TPU-native exhaustive checker for the Raft TLA+ spec")
    p.add_argument("cfg", help="TLC model config (e.g. the reference "
                               "raft.cfg); binds Server/Value/INVARIANT")
    p.add_argument("--spec", default="full",
                   choices=("full", "election", "replication", "twophase",
                            "paxos"),
                   help="loaded spec: a Raft Next-disjunct subset (default: "
                        "full raft.tla:454-465), the bundled twophase "
                        "(two-phase commit; cfg binds CONSTANT RM) or the "
                        "bundled paxos (single-decree Paxos; cfg binds "
                        "Acceptor, Value and Quorum, a set of sets; "
                        "--max-term is the maximum ballot); both "
                        "frontend-compiled, --engine host or ddd")
    p.add_argument("--engine", default="device",
                   choices=("device", "ddd", "shard", "ddd-shard", "host",
                            "ref"),
                   help="device: search resident in HBM; ddd: delayed "
                        "duplicate detection — host-streamed frontier "
                        "blocks, exact dedup on the host, no device "
                        "fingerprint-table ceiling (for spaces past ~2^28 "
                        "distinct states); shard: multi-chip mesh; "
                        "ddd-shard: mesh-sharded DDD — host-exact dedup "
                        "partitioned over the fingerprint-owner map (the "
                        "scale engine's multi-chip composition); host: "
                        "per-chunk jit; ref: pure-Python oracle")
    p.add_argument("--max-term", type=int, default=3,
                   help="CONSTRAINT: currentTerm[i] <= N (default 3)")
    p.add_argument("--max-log", type=int, default=2,
                   help="CONSTRAINT: Len(log[i]) <= N (default 2)")
    p.add_argument("--max-msgs", type=int, default=4,
                   help="CONSTRAINT: Cardinality(DOMAIN messages) <= N")
    p.add_argument("--max-dup", type=int, default=1,
                   help="CONSTRAINT: messages[m] <= N")
    p.add_argument("--deadlock", action="store_true",
                   help="check for deadlocks (a reachable state with no "
                        "successor) like stock TLC does by default; exit "
                        "code 11 on one. Off by default: the full Next "
                        "cannot deadlock (Restart is always enabled, "
                        "raft.tla:167-175), only sub-specs can")
    p.add_argument("--faithful", action="store_true",
                   help="carry the proof-only history variables (elections/"
                        "allLogs/voterLog/mlog, raft.tla:39,44,77) as real "
                        "fingerprinted state, as stock TLC does on the "
                        "unmodified spec; enables the *Hist invariants "
                        "(default: parity mode, history stripped)")
    p.add_argument("--max-elections", type=int, default=6,
                   help="elections-history slot capacity (--faithful only); "
                        "exceeding it aborts loudly")
    p.add_argument("--chunk", type=int, default=1024,
                   help="frontier states expanded per device step")
    p.add_argument("--cap", type=int, default=1 << 20,
                   help="expected distinct-state capacity: store rows for "
                        "device/shard; filter-table sizing (2 slots per "
                        "state) for ddd/ddd-shard, whose store itself is "
                        "host-RAM-bounded")
    p.add_argument("--levels", type=int, default=256,
                   help="max BFS depth (device/shard engines)")
    p.add_argument("--devices", type=int, default=None,
                   help="mesh size for --engine shard (default: all)")
    p.add_argument("--seg-chunks", type=int, default=256,
                   help="initial chunk expansions per device dispatch for "
                        "--engine shard (the adaptive pacer tunes it from "
                        "there; small values force frequent segment "
                        "boundaries, hence more checkpoint opportunities)")
    p.add_argument("--route", type=int, default=0, metavar="K",
                   help="--engine ddd only: EP-routed step with K "
                        "compacted candidate slots per chunk (the "
                        "expensive orbit/invariant stages then run on K "
                        "rows instead of chunk*A; size from the "
                        "route_peak stat of a dense run; overflow aborts "
                        "loudly; 0 = dense step)")
    p.add_argument("--reshard-to", type=int, default=None, metavar="NDEV",
                   help="shard/ddd/ddd-shard: instead of searching, "
                        "rewrite the --resume checkpoint for an "
                        "NDEV-device mesh, save it to the --checkpoint "
                        "path, print a summary, and exit (a pod-size "
                        "change no longer discards a run; --engine ddd "
                        "migrates a single-chip DDD campaign onto a "
                        "ddd-shard mesh)")
    p.add_argument("--reshard-cap", type=int, default=None, metavar="N",
                   help="with --reshard-to (shard engine): grow the "
                        "destination per-device store to N rows (rescues "
                        "a run near FAIL_STORE/FAIL_PROBE; default: keep "
                        "the source capacities)")
    p.add_argument("--block", type=int, default=None, metavar="ROWS",
                   help="ddd/ddd-shard: frontier window rows per shard "
                        "(default: 2^20 for ddd, the smallest chunk-"
                        "multiple >= 2^18 for ddd-shard; must match the "
                        "source run when resuming or resharding — the "
                        "reshard summary prints the value to resume "
                        "with)")
    p.add_argument("--retention", default="full",
                   choices=("full", "frontier"),
                   help="--engine ddd / ddd-shard: 'frontier' keeps "
                        "master keys "
                        "in RAM and only the current+next BFS level of "
                        "rows in disk-backed level files, with NO trace "
                        "links (violations report the state, not a path "
                        "— TLC -noTrace).  ~16 B/state instead of ~76: "
                        "the campaign mode for 10^9+-state spaces")
    p.add_argument("--keep-levels", action="store_true",
                   help="--retention frontier: retain ALL level files "
                        "(TLC's states/ disk regime) so a violation "
                        "reconstructs a full trace by backward "
                        "re-search; costs the rows-stream disk "
                        "footprint")
    p.add_argument("--cp-lanes", action="store_true",
                   help="--engine ddd-shard only: CP mode — shard the "
                        "bag-scan ACTION lanes across the mesh instead "
                        "of the frontier rows (window replicated; "
                        "measured 1.51x slower than row sharding on the "
                        "8-virtual-device CPU mesh, never on chips)")
    from raft_tla_tpu.models.views import REGISTRY as _view_registry
    p.add_argument("--view", default=None,
                   choices=tuple(sorted(_view_registry)),
                   help="TLC VIEW analog: fold a registered EXACT view "
                        "into every dedup key (models/views.py carries "
                        "the soundness argument; deadvotes: zero "
                        "votesResponded/votesGranted of non-Candidates — "
                        "collapses dead vote-set freight, same verdicts)")
    p.add_argument("--slices", type=int, default=None,
                   help="multi-slice scale-out for shard/ddd-shard: build "
                        "a 2-D (dcn, ici) mesh of N slices x (devices/N) "
                        "chips with the hierarchical dedup exchange "
                        "(default: single-slice 1-D mesh)")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend (virtual devices for shard)")
    p.add_argument("--emit-tlc", metavar="DIR",
                   help="also write MCraft.tla/MCraft.cfg for a stock-TLC "
                        "parity run, then continue")
    p.add_argument("--property", action="append", default=[],
                   metavar="NAME_OR_FORMULA",
                   help="temporal property to check under weak fairness: "
                        "a registered name (models/liveness.PROPERTIES) "
                        "or a formula '<>P', '[]<>P', 'P ~> Q' over "
                        "registered predicates (models/liveness."
                        "PREDICATES). Also read from the cfg's PROPERTY "
                        "stanza")
    p.add_argument("--wf", default="Next",
                   help="comma-separated action families assumed weakly "
                        "fair for --property (default: Next = the whole "
                        "relation; 'none' = no fairness, the reference "
                        "spec's actual Spec, raft.tla:469)")
    p.add_argument("--checkpoint", metavar="PATH",
                   help="periodically snapshot the search (every device "
                        "engine); resume later with --resume")
    p.add_argument("--checkpoint-every", type=float, default=120.0,
                   metavar="SECONDS")
    p.add_argument("--resume", metavar="PATH",
                   help="resume a --checkpoint snapshot")
    p.add_argument("--deadline", type=float, default=None,
                   metavar="SECONDS",
                   help="stop losslessly at the first segment boundary "
                        "past this wall budget (exit 14, snapshot "
                        "flushed; --engine ddd only) — the campaign "
                        "supervisor's session-wall policy knob")
    p.add_argument("--no-trace", action="store_true",
                   help="suppress the counterexample trace on violation")
    p.add_argument("--coverage", action="store_true",
                   help="print per-action coverage (TLC -coverage analog)")
    p.add_argument("--symmetry", action="store_true",
                   help="quotient the state space by Server permutation "
                        "symmetry (TLC SYMMETRY analog; also enabled by a "
                        "cfg SYMMETRY stanza)")
    p.add_argument("--prescan", default=None,
                   choices=("auto", "on", "off"),
                   help="device-side duplicate prescan of candidate blocks "
                        "before the host sees them (ops/kernels."
                        "_prescan_enabled). Sets RAFT_TLA_PRESCAN "
                        "process-wide so every engine inherits one "
                        "decision; default: leave the env/auto policy "
                        "alone")
    p.add_argument("--host-dedup", default=None,
                   choices=("auto", "on", "off"),
                   help="partitioned + background host dedup for the ddd "
                        "engines: the master key set splits into 2^k "
                        "high-bit partitions with budgeted compaction (no "
                        "O(N) merge spike in any single flush) and the "
                        "flush runs on a depth-1 ordered worker thread "
                        "that overlaps device compute — discovery stays "
                        "byte-identical (utils/keyset.py has the ordering "
                        "argument). Sets RAFT_TLA_HOSTDEDUP process-wide; "
                        "default: leave the env/auto policy alone (auto "
                        "= on iff nproc >= 2 — 0.72x in-engine at "
                        "nproc=1 on the CPU, not measured on the chip)")
    p.add_argument("--prefetch", default=None,
                   choices=("auto", "on", "off"),
                   help="double-buffered upload prefetch for the ddd "
                        "engines: a background thread reads block k+1's "
                        "rows + constraint column and stages them onto "
                        "the device while block k expands, so block "
                        "boundaries swap to a resident buffer instead of "
                        "paying drain+read+pad+h2d (utils/prefetch.py; "
                        "relies on the host stores' disjoint-range "
                        "append+read contract, utils/native.py) — "
                        "discovery stays byte-identical, hit or miss. "
                        "Sets RAFT_TLA_PREFETCH process-wide; default: "
                        "leave the env/auto policy alone (auto = on iff "
                        "nproc >= 2 — 1.29x full / 0.91x frontier "
                        "retention at nproc=1 on the CPU, not measured "
                        "on the chip)")
    p.add_argument("--device-dedup", default=None,
                   choices=("auto", "on", "off", "hash", "sort"),
                   help="device-resident exact within-level fingerprint "
                        "dedup for the ddd engines (ops/devdedup.py): "
                        "each segment's output buffers are filtered "
                        "against an HBM set of the keys already streamed "
                        "this level, so within-level duplicates never "
                        "cross d2h — the host LSM keyset stays the exact "
                        "cold tier and discovery stays byte-identical. "
                        "'on'/'hash' uses the open-addressing table "
                        "(device_engine's insert-if-absent protocol), "
                        "'sort' the portable sorted-set arm. Sets "
                        "RAFT_TLA_DEVDEDUP process-wide; default: leave "
                        "the env/auto policy alone (auto is currently "
                        "OFF — 0.44x warm rate on the CPU, not measured "
                        "on the chip)")
    p.add_argument("--lint", default="warn", choices=("warn", "strict"),
                   help="static width-safety pass (analysis/widthcheck) "
                        "before any step build: prove no transition can "
                        "overflow a packed field for these bounds. 'warn' "
                        "(default) prints findings and proceeds; 'strict' "
                        "makes any finding fatal. The full three-pass "
                        "analyzer is `python -m raft_tla_tpu.lint`")
    p.add_argument("--no-lint", action="store_true",
                   help="skip the static width-safety pass")
    p.add_argument("--stats", action="store_true",
                   help="emit one JSON line of run stats per search segment "
                        "on stderr (every device engine)")
    p.add_argument("--events", metavar="PATH",
                   help="append the versioned JSONL run-event log "
                        "(run_start/segment/level_end/checkpoint/"
                        "violation/run_end — obs/events.py) to PATH; "
                        "tail it live with raft-tla-monitor. Sets "
                        "RAFT_TLA_EVENTS process-wide so liveness "
                        "re-runs inherit the same log")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="persistent JAX compilation-cache directory "
                        "(also via RAFT_TLA_COMPILE_CACHE): repeated "
                        "runs of the same bounds skip XLA compilation "
                        "entirely — the serve daemon's warm-start knob, "
                        "useful for single checks too")
    p.add_argument("--trace", action="store_true",
                   help="emit schema-v8 span events (trace spans with "
                        "nesting and thread attribution) into the "
                        "--events log; merge and export with "
                        "raft-tla-trace. Unlike --phase-timers this adds "
                        "no device syncs — spans record host-side "
                        "dispatch walls. Distinct from --no-trace, which "
                        "suppresses counterexample trace RENDERING. Also "
                        "RAFT_TLA_TRACE=1")
    p.add_argument("--phase-timers", action="store_true",
                   help="attribute wall time to search phases (upload/"
                        "expand/export/dedup/snapshot, plus dedup_submit/"
                        "dedup_wait when background host dedup is on) in "
                        "each segment "
                        "event, at the cost of a device sync per phase — "
                        "the ddd engines lose their two-deep dispatch "
                        "overlap while this is on. Off by default so jit "
                        "pipelining is untouched; also RAFT_TLA_"
                        "PHASE_TIMERS=1")
    p.add_argument("--simulate", type=int, metavar="N", default=None,
                   help="TLC -simulate analog: instead of exhaustive "
                        "search, sample N random behaviors (batched "
                        "walkers on device), invariants checked on every "
                        "generated state")
    p.add_argument("--depth", type=int, default=100,
                   help="--simulate: maximum behavior length (TLC's "
                        "-depth; default 100)")
    p.add_argument("--walkers", type=int, default=1024,
                   help="--simulate: parallel walkers per device step "
                        "(with --fleet: the GLOBAL fleet size, split "
                        "evenly over the mesh)")
    p.add_argument("--seed", type=int, default=0,
                   help="--simulate: PRNG seed (same seed = same walks; "
                        "with --fleet, the same walks at any device "
                        "count)")
    p.add_argument("--fleet", action="store_true",
                   help="--simulate: shard the walker fleet over the "
                        "device mesh (--devices; statistical checking "
                        "at serving scale, bit-reproducible across "
                        "mesh shapes)")
    p.add_argument("--steer", type=float, default=0.0, metavar="TAU",
                   help="--fleet: coverage-steering temperature — bias "
                        "lane sampling against over-visited actions by "
                        "TAU * log1p(visits/mean) (default 0 = off; "
                        "exact replay preserved)")
    p.add_argument("--fault-weights", default=None, metavar="F=W,...",
                   help="--fleet: per-action-family sampling weights, "
                        "e.g. 'Restart=2,DropMessage=0.5' (sampling "
                        "policy only; enabledness untouched)")
    return p


def _resolve_config(args):
    # One code path with the serve/ admission gate: the CLI flags become a
    # JobOptions and the shared builder does every validation.
    from raft_tla_tpu.serve.jobs import JobOptions, resolve_check_config
    from raft_tla_tpu.utils.cfgparse import load_cfg

    opts = JobOptions(
        spec=args.spec, max_term=args.max_term, max_log=args.max_log,
        max_msgs=args.max_msgs, max_dup=args.max_dup,
        faithful=args.faithful, max_elections=args.max_elections,
        chunk=args.chunk, symmetry=args.symmetry, view=args.view,
        deadlock=args.deadlock, properties=tuple(args.property))
    return resolve_check_config(load_cfg(args.cfg), opts, path=args.cfg)


def _stats_cb(args):
    if not args.stats:
        return None
    import json

    def cb(stats):
        print(json.dumps(stats), file=sys.stderr)
    return cb


def _parse_fault_weights(text):
    """``Fam=W,Fam=W`` -> dict; raises ValueError on malformed cells
    (family-name validity is checked by the fleet engine, which knows
    the spec's action table)."""
    if not text:
        return None
    out = {}
    for cell in text.split(","):
        fam, eq, w = cell.partition("=")
        if not eq or not fam.strip():
            raise ValueError(f"bad --fault-weights cell {cell!r} "
                             "(want Family=Weight,...)")
        out[fam.strip()] = float(w)
    return out


def _simulate(args, config):
    """TLC -simulate analog; returns a TLC-compatible exit code."""
    from raft_tla_tpu.engine import DEADLOCK
    if args.fleet:
        from raft_tla_tpu.fleet import FleetSimulator
        from raft_tla_tpu.parallel.mesh import make_mesh
        sim = FleetSimulator(config, mesh=make_mesh(args.devices),
                             walkers=args.walkers, depth=args.depth,
                             seed=args.seed, steer_tau=args.steer,
                             fault_weights=_parse_fault_weights(
                                 args.fault_weights))
    else:
        from raft_tla_tpu.simulate import Simulator
        sim = Simulator(config, walkers=args.walkers, depth=args.depth,
                        seed=args.seed)
    # --stats/--events flow through the same RunTelemetry facade as the
    # exhaustive engines (the events path rides the env set in main()).
    res = sim.run(args.simulate, on_progress=_stats_cb(args))
    print(f"{res.n_behaviors} behaviors generated ({res.n_states} states, "
          f"deepest {res.max_depth_seen}), {res.wall_s:.2f}s "
          f"({res.states_per_sec:,.0f} states/s).")
    if args.fleet:
        print(f"Fleet: {res.n_devices} devices x "
              f"{res.walkers // res.n_devices} walkers"
              + (f", steer tau={res.steer_tau:g}" if res.steer_tau
                 else "")
              + f"; action-coverage entropy {res.coverage_entropy:.3f}")
    if res.violation is None:
        print("Model checking completed. No error has been found.")
        print(f"  (simulation: {args.simulate} behaviors of depth "
              f"<= {args.depth}; not exhaustive)")
        if args.fleet:
            conf = res.confidence(config.invariants)
            per = conf["per_invariant"]
            for nm in config.invariants:
                print(f"  {nm}: held on {per[nm]:,} sampled states")
        return EXIT_OK
    is_deadlock = res.violation.invariant == DEADLOCK
    if args.no_trace:
        print("Error: Deadlock reached." if is_deadlock else
              f"Error: Invariant {res.violation.invariant} is violated.")
    else:
        from raft_tla_tpu.frontend import resolve_model
        model = resolve_model(config.spec)
        print(model.render_trace(res.violation, config.bounds))
    return EXIT_DEADLOCK if is_deadlock else EXIT_VIOLATION



def _ddd_shard_block(chunk: int) -> int:
    """Smallest chunk-multiple >= 2^18: the default ddd-shard window
    slice (block needs chunk alignment, not a power of two)."""
    return chunk * max(1, -(-(1 << 18) // chunk))


def _make_cli_mesh(args):
    """1-D mesh, or the 2-D (dcn, ici) slice mesh when --slices is given."""
    import jax

    from raft_tla_tpu.parallel.mesh import make_mesh, make_slice_mesh
    if args.slices is None:
        return make_mesh(args.devices)
    nd = args.devices if args.devices is not None else len(jax.devices())
    if nd % args.slices:
        raise SystemExit(
            f"--devices {nd} not divisible by --slices {args.slices}")
    return make_slice_mesh(args.slices, nd // args.slices)


def _needs_device(args) -> bool:
    """False for the paths that never compute on a device: the pure-
    Python oracle, and the DDD-family checkpoint rewrite (host arrays
    only — the campaign supervisor runs it beside a live child)."""
    if args.reshard_to is not None:
        return args.engine == "shard"
    return args.engine != "ref" or args.simulate is not None


def _run(args, config):
    if args.engine == "ref":
        from raft_tla_tpu.models import refbfs
        return refbfs.check(config)
    if args.engine == "host":
        from raft_tla_tpu import engine
        return engine.check(config)
    if args.engine == "ddd":
        from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
        from raft_tla_tpu.frontend import resolve_model
        # the filter table is a traffic optimization, not a capacity
        # bound — size it to the expected state count, capped at the
        # 2 GiB single-buffer limit the exact tables live under
        # (inherited, not re-measured on this machine)
        table = 1 << max(10, min(28, (2 * args.cap - 1).bit_length()))
        # segment output buffers must hold at least one chunk's worst-case
        # candidate stream (chunk * action fan-out)
        A = len(resolve_model(config.spec).action_table(config.bounds))
        seg_rows = max(1 << 19, 2 * args.chunk * A)
        if args.route and args.route > seg_rows:
            seg_rows = args.route
        eng = DDDEngine(config, DDDCapacities(
            block=args.block or 1 << 20, table=table, seg_rows=seg_rows,
            levels=args.levels, route_rows=args.route,
            retention=args.retention, keep_levels=args.keep_levels))
        return eng.check(on_progress=_stats_cb(args),
                         checkpoint=args.checkpoint,
                         checkpoint_every_s=args.checkpoint_every,
                         resume=args.resume,
                         deadline_s=args.deadline)
    if args.engine == "ddd-shard":
        from raft_tla_tpu.models import spec as S
        from raft_tla_tpu.parallel.ddd_shard_engine import (
            DDDShardCapacities, DDDShardEngine)
        mesh = _make_cli_mesh(args)
        nd = mesh.devices.size
        # per-shard filter share of the expected state count (traffic
        # only); per-shard output buffers must hold one chunk's
        # worst-case post-exchange stream (ndev * chunk * fan-out)
        A = len(S.action_table(config.bounds, config.spec))
        table = 1 << max(10, min(26, ((2 * args.cap + nd - 1) // nd - 1)
                                 .bit_length()))
        seg_rows = max(1 << 19, 2 * nd * args.chunk * A)
        blk = args.block or _ddd_shard_block(args.chunk)
        eng = DDDShardEngine(config, mesh, DDDShardCapacities(
            block=blk, table=table, seg_rows=seg_rows,
            levels=args.levels, cp=args.cp_lanes,
            retention=args.retention, keep_levels=args.keep_levels))
        return eng.check(on_progress=_stats_cb(args),
                         checkpoint=args.checkpoint,
                         checkpoint_every_s=args.checkpoint_every,
                         resume=args.resume)
    if args.engine == "shard":
        from raft_tla_tpu.parallel.shard_engine import (
            ShardCapacities, ShardEngine)
        mesh = _make_cli_mesh(args)
        eng = ShardEngine(config, mesh,
                          ShardCapacities(n_states=args.cap,
                                          levels=args.levels),
                          seg_chunks=args.seg_chunks)
        return eng.check(checkpoint=args.checkpoint,
                         checkpoint_every_s=args.checkpoint_every,
                         resume=args.resume, on_progress=_stats_cb(args))
    from raft_tla_tpu.device_engine import Capacities, DeviceEngine
    eng = DeviceEngine(config, Capacities(n_states=args.cap,
                                          levels=args.levels))
    return eng.check(checkpoint=args.checkpoint,
                     checkpoint_every_s=args.checkpoint_every,
                     resume=args.resume, on_progress=_stats_cb(args))


def _finish_run(args, p, config, props, model, b) -> int:
    """Run + report for non-Raft (frontend-compiled) specs: the shared
    tail of main() minus the Raft-only paths (liveness, reshard,
    simulate), with trace rendering routed through the model."""
    if args.reshard_to is not None:
        print(f"Error: --reshard-to is not supported for --spec "
              f"{args.spec}", file=sys.stderr)
        return EXIT_ERROR
    t0 = time.monotonic()
    try:
        result = _run(args, config)
    except Exception as e:
        print(f"Error: {e}", file=sys.stderr)
        return EXIT_ERROR
    wall = time.monotonic() - t0
    print(f"{result.n_states} distinct states found, diameter "
          f"{result.diameter}, {result.n_transitions} transitions, "
          f"{wall:.2f}s ({result.n_states / max(wall, 1e-9):,.0f} states/s).")
    if args.coverage:
        for fam, cnt in sorted(result.coverage.items()):
            print(f"  {fam}: {cnt} new states")
    if result.violation is None:
        if not result.complete:
            print("Model checking stopped before completion (state space "
                  "not exhausted); resume from the checkpoint to "
                  "continue.")
            return EXIT_STOPPED
        print("Model checking completed. No error has been found.")
        return EXIT_OK
    from raft_tla_tpu.engine import DEADLOCK
    is_deadlock = result.violation.invariant == DEADLOCK
    if args.no_trace:
        print("Error: Deadlock reached." if is_deadlock else
              f"Error: Invariant {result.violation.invariant} is violated.")
    else:
        print(model.render_trace(result.violation, b))
    return EXIT_DEADLOCK if is_deadlock else EXIT_VIOLATION


def main(argv=None) -> int:
    p = build_argparser()
    args = p.parse_args(argv)
    if args.prescan is not None:
        # Process-wide, BEFORE any step build: the gate is read at step-
        # construction time (ops/kernels._prescan_enabled), and liveness
        # re-runs build engines of their own.
        import os
        os.environ["RAFT_TLA_PRESCAN"] = args.prescan
    if args.host_dedup is not None:
        # Same contract as --prescan: resolved at engine construction
        # (utils/keyset.host_dedup_enabled) by the ddd engine families.
        import os
        os.environ["RAFT_TLA_HOSTDEDUP"] = args.host_dedup
    if args.prefetch is not None:
        # Same contract: resolved at engine construction
        # (utils/prefetch.prefetch_enabled) by the ddd engine families.
        import os
        os.environ["RAFT_TLA_PREFETCH"] = args.prefetch
    if args.device_dedup is not None:
        # Same contract: resolved at engine construction
        # (ops/devdedup.devdedup_backend) by the ddd engine families.
        import os
        os.environ["RAFT_TLA_DEVDEDUP"] = args.device_dedup
    _DEVICE_ENGINES = ("device", "ddd", "shard", "ddd-shard")
    if args.view and args.simulate:
        p.error("--view does not compose with --simulate (random walks "
                "replay concrete states; a view only folds dedup keys)")
    if args.reshard_cap and not (args.reshard_to and
                                 args.engine == "shard"):
        p.error("--reshard-cap only applies to --reshard-to with "
                "--engine shard (the DDD snapshots carry no per-device "
                "store capacity); dropping it silently would ignore "
                "the configured rescue")
    if args.route and args.engine != "ddd":
        p.error(f"--route requires --engine ddd (got {args.engine}); "
                "the routed step is not built for other engines — "
                "dropping it silently would run a different program "
                "than configured")
    if (args.checkpoint or args.resume) and \
            args.engine not in _DEVICE_ENGINES:
        p.error(f"--checkpoint/--resume require a device-class engine "
                f"(got {args.engine}); other engines would silently "
                "ignore them")
    if args.deadline is not None and args.engine != "ddd":
        p.error(f"--deadline requires --engine ddd (got {args.engine}); "
                "only the ddd engine stops losslessly at a segment "
                "boundary — dropping it silently would run unbounded")
    if args.stats and args.engine not in _DEVICE_ENGINES:
        p.error(f"--stats requires a device-class engine "
                f"(got {args.engine})")
    if (args.events or args.phase_timers or args.trace) and \
            args.engine not in _DEVICE_ENGINES:
        p.error(f"--events/--phase-timers/--trace require a device-class "
                f"engine (got {args.engine}); other engines emit no run "
                "events")
    from raft_tla_tpu.obs.events import events_path
    if args.trace and not events_path(args.events):
        p.error("--trace requires --events PATH (spans are rows in the "
                "run-event log; without a log there is nowhere to put "
                "them)")
    if args.events or args.phase_timers or args.trace:
        # Process-wide, like --prescan: every engine an invocation
        # builds (including liveness re-runs) reads the same env gate.
        import os
        from raft_tla_tpu.obs.events import ENV_EVENTS
        from raft_tla_tpu.obs.phases import ENV_PHASE_TIMERS
        from raft_tla_tpu.obs.trace import ENV_TRACE
        if args.events:
            os.environ[ENV_EVENTS] = args.events
        if args.phase_timers:
            os.environ[ENV_PHASE_TIMERS] = "1"
        if args.trace:
            os.environ[ENV_TRACE] = "1"
    try:
        config, props = _resolve_config(args)
    except (OSError, ValueError) as e:
        print(f"Error: {e}", file=sys.stderr)
        return EXIT_ERROR
    from raft_tla_tpu.frontend import resolve_model
    model = resolve_model(args.spec)
    if not model.is_raft and args.engine not in model.engines:
        p.error(f"--engine {args.engine} does not support spec "
                f"{args.spec!r} (supported: {', '.join(model.engines)})")
    if not model.is_raft and args.route:
        p.error(f"--route does not support spec {args.spec!r}: the routed "
                "step is Raft's (the dense step runs every spec)")
    if args.simulate is not None and "simulate" not in model.engines:
        p.error(f"--simulate is not supported by spec {args.spec!r} "
                f"(supported engines: {', '.join(model.engines)})")
    if args.fleet and args.simulate is None:
        p.error("--fleet requires --simulate N (fleets are a "
                "simulation-mode engine)")
    if args.steer and not args.fleet:
        p.error("--steer requires --fleet (coverage steering lives in "
                "the sharded fleet engine)")
    if args.fault_weights and not args.fleet:
        p.error("--fault-weights requires --fleet")

    if not args.no_lint:
        # Width-safety (analysis Pass 1) before any step build: for these
        # exact bounds, no transition can write a value the bit-pack would
        # truncate.  Warn-only by default — the proof failing means the
        # analyzer and kernels disagree, which deserves eyes, not a wall —
        # but --lint strict turns any finding into a hard stop.  Non-Raft
        # models route to their schema validity gate.
        from raft_tla_tpu.analysis import report as _report
        try:
            _lint = model.check_widths(config.bounds)
        except Exception as e:      # analyzer bug: report, don't block
            _lint = [_report.Finding(
                _report.WIDTH, _report.ERROR, "lint-internal-error",
                f"width pass crashed: {e!r}")]
        if _lint:
            print(_report.render(
                _lint, header="speclint (width pass):"), file=sys.stderr)
            if args.lint == "strict":
                return EXIT_ERROR

    dev_line = None
    if _needs_device(args):
        # One decision, before the first device op: --cpu, an explicit
        # JAX_PLATFORMS, or a TPU — never a silent fall-back to the CPU
        # (utils/device.py).  The header states where the run executes.
        from raft_tla_tpu.serve.sched import enable_compile_cache
        from raft_tla_tpu.utils import device
        try:
            dev = device.select_device(args.cpu, args.devices)
        except device.DeviceError as e:
            print(f"Error: {e}", file=sys.stderr)
            return EXIT_ERROR
        dev_line = "Device: " + device.describe(dev)
        enable_compile_cache(args.compile_cache, platform=dev["platform"])

    b = config.bounds
    if not model.is_raft:
        print(f"raft_tla_tpu {__import__('raft_tla_tpu').__version__} — "
              f"exhaustive check of spec {args.spec} (frontend-compiled)")
        print(f"Universe: {model.universe_line(b)} (from {args.cfg})")
        if dev_line:
            print(dev_line)
        print(f"Invariants: {', '.join(config.invariants) or '(none)'}")
        if config.symmetry:
            print(f"Symmetry: {' x '.join(config.symmetry)} permutations, "
                  f"|G| = {model.group_order(config)} (counting orbits)")
        if args.emit_tlc:
            try:
                tla, cfgp = model.emit_tla(args.emit_tlc, b,
                                           config.invariants,
                                           symmetry=config.symmetry)
            except (OSError, ValueError) as e:
                print(f"Error: {e}", file=sys.stderr)
                return EXIT_ERROR
            print(f"TLC parity artifacts: {tla}, {cfgp}")
        if args.simulate is not None:
            if props:
                print(f"Error: PROPERTY {list(props)} cannot be checked "
                      "in --simulate mode (liveness needs exhaustive "
                      "search)", file=sys.stderr)
                return EXIT_ERROR
            try:
                return _simulate(args, config)
            except Exception as e:
                print(f"Error: {e}", file=sys.stderr)
                return EXIT_ERROR
        return _finish_run(args, p, config, props, model, b)
    print(f"raft_tla_tpu {__import__('raft_tla_tpu').__version__} — "
          f"exhaustive check of Spec (raft.tla:469), subset: {args.spec}")
    print(f"Universe: {b.n_servers} servers, {b.n_values} values "
          f"(from {args.cfg})")
    print(f"Constraint: MaxTerm={b.max_term} MaxLogLen={b.max_log} "
          f"MaxMsgs={b.max_msgs} MaxDup={b.max_dup}")
    if dev_line:
        print(dev_line)
    if b.history:
        print("Faithful mode: history variables (elections/allLogs/"
              f"voterLog/mlog) carried; elections capacity {b.max_elections}")
    print(f"Invariants: {', '.join(config.invariants) or '(none)'}")
    if config.symmetry:
        print(f"Symmetry: {' x '.join(config.symmetry)} permutations "
              "(counting orbits)")
    if config.view:
        # registered views are EXACT (bisimulations, models/views.py),
        # so the view quotient is transition-faithful and liveness on
        # it is sound for the view-invariant registered predicates —
        # see the lift argument in liveness.ddd_graph
        print(f"View: {config.view} (counting view-quotient states)")

    if args.emit_tlc:
        from raft_tla_tpu.models import tla_export
        try:
            tla, cfgp = tla_export.export(args.emit_tlc, b,
                                          config.invariants,
                                          parity_view=not b.history,
                                          symmetry=config.symmetry,
                                          view=config.view,
                                          spec=config.spec,
                                          properties=tuple(props),
                                          wf=_parse_wf(args))
        except (OSError, ValueError) as e:
            print(f"Error: {e}", file=sys.stderr)
            return EXIT_ERROR
        print(f"TLC parity artifacts: {tla}, {cfgp}")

    if args.simulate is not None:
        if props:
            # Liveness needs the full behavior graph; sampling cannot check
            # it — reject rather than silently report OK.
            print(f"Error: PROPERTY {list(props)} cannot be checked in "
                  "--simulate mode (liveness needs exhaustive search)",
                  file=sys.stderr)
            return EXIT_ERROR
        try:
            return _simulate(args, config)
        except Exception as e:
            print(f"Error: {e}", file=sys.stderr)
            return EXIT_ERROR

    if args.reshard_to is not None:
        if args.engine not in ("shard", "ddd", "ddd-shard"):
            print("Error: --reshard-to requires --engine shard, ddd or "
                  "ddd-shard", file=sys.stderr)
            return EXIT_ERROR
        if not args.resume or not args.checkpoint:
            print("Error: --reshard-to needs --resume SRC and "
                  "--checkpoint DST", file=sys.stderr)
            return EXIT_ERROR
        if args.engine == "shard":
            from raft_tla_tpu.parallel.shard_engine import (
                ShardCapacities, reshard_checkpoint)
            caps_src = ShardCapacities(n_states=args.cap,
                                       levels=args.levels)
            caps_dst = ShardCapacities(
                n_states=args.reshard_cap,
                levels=args.levels) if args.reshard_cap else None
            try:
                info = reshard_checkpoint(
                    config, caps_src, args.resume, args.checkpoint,
                    args.reshard_to, caps_dst=caps_dst)
            except Exception as e:
                print(f"Error: {e}", file=sys.stderr)
                return EXIT_ERROR
            print(f"resharded {info['ndev_src']} -> {info['ndev_dst']} "
                  f"devices: {info['n_states']} states, per-device "
                  f"{info['per_device']}, window {info['window']} -> "
                  f"{args.checkpoint}")
            return EXIT_OK
        # DDD family: the streams are mesh-independent history; only
        # window accounting + digest change.  Source geometry is what
        # this CLI itself would run: single-chip ddd uses block 2^20
        # with ndev=1; ddd-shard derives its block from --chunk and its
        # mesh size from --devices.  The destination block preserves the
        # GLOBAL window size, so every snapshot boundary is shared.
        from raft_tla_tpu.parallel.ddd_shard_engine import (
            DDDShardCapacities, reshard_ddd_checkpoint)
        if args.engine == "ddd":
            ndev_src, blk_src = 1, args.block or 1 << 20
        else:
            if not args.devices:
                print("Error: ddd-shard reshard needs --devices "
                      "(the source mesh size)", file=sys.stderr)
                return EXIT_ERROR
            ndev_src = args.devices
            blk_src = args.block or _ddd_shard_block(args.chunk)
        # CP-mode windows are block rows regardless of mesh size (the
        # window replicates), so the window math is ndev-independent
        cp = args.engine == "ddd-shard" and args.cp_lanes
        w_src = blk_src if cp else ndev_src * blk_src
        # destination block: prefer preserving the GLOBAL window size
        # (every snapshot boundary shared), else keep the source block;
        # either way it must be chunk-aligned or the mesh engine would
        # reject the digest-pinned block at resume — refuse loudly here
        # instead of writing an unusable snapshot
        cand = [blk_src] if cp else (
            ([w_src // args.reshard_to]
             if w_src % args.reshard_to == 0 else []) + [blk_src])
        blk_dst = next((b for b in cand
                        if b > 0 and b % args.chunk == 0), None)
        if blk_dst is None:
            print(f"Error: neither {cand} rows is a multiple of "
                  f"--chunk {args.chunk}; no chunk-aligned destination "
                  "block preserves the source window boundaries — use a "
                  "chunk that divides the source window (power-of-two "
                  "chunks always do)", file=sys.stderr)
            return EXIT_ERROR
        try:
            info = reshard_ddd_checkpoint(
                config,
                DDDShardCapacities(block=blk_src, levels=args.levels,
                                   cp=cp),
                args.resume, args.checkpoint, ndev_src, args.reshard_to,
                caps_dst=DDDShardCapacities(block=blk_dst,
                                            levels=args.levels, cp=cp))
        except Exception as e:
            print(f"Error: {e}", file=sys.stderr)
            return EXIT_ERROR
        print(f"resharded DDD {info['ndev_src']} -> {info['ndev_dst']} "
              f"devices: {info['n_states']} states, "
              f"{info['rows_done']} frontier rows done "
              f"({info['blocks_done_dst']} windows) -> "
              f"{args.checkpoint}  [resume with --engine ddd-shard "
              f"--devices {info['ndev_dst']} --block {blk_dst}"
              f"{' --cp-lanes' if cp else ''}]")
        return EXIT_OK

    t0 = time.monotonic()
    try:
        result = _run(args, config)
    except Exception as e:
        print(f"Error: {e}", file=sys.stderr)
        return EXIT_ERROR
    wall = time.monotonic() - t0

    print(f"{result.n_states} distinct states found, diameter "
          f"{result.diameter}, {result.n_transitions} transitions, "
          f"{wall:.2f}s ({result.n_states / max(wall, 1e-9):,.0f} states/s).")
    ledger = getattr(result, "level_log", None) or {}
    if "elections_peak" in ledger:
        # faithful mode on a ddd engine: the pass ledger's two counts
        words = {lv["row_words"] for lv in ledger["levels"]}
        print(f"History: packed rows of {'/'.join(map(str, sorted(words)))} "
              f"words; elections peak {ledger['elections_peak']} of "
              f"{config.bounds.max_elections} slots"
              + (f"; the orbit scan moves {ledger['scan_moved_fields']} "
                 "field(s) an image." if "scan_moved_fields" in ledger
                 else "."))
    if args.coverage:
        for fam, cnt in sorted(result.coverage.items()):
            print(f"  {fam}: {cnt} new states")

    if result.violation is None and not result.complete:
        # A lossless stop (SIGINT, --deadline, capacity policy): no
        # verdict was reached, so neither "no error found" nor liveness
        # (which needs the full graph) may be claimed.
        print("Model checking stopped before completion (state space "
              "not exhausted); resume from the checkpoint to continue.")
        return EXIT_STOPPED
    if result.violation is None and props:
        code = _check_liveness(args, config, props)
        if code != EXIT_OK:
            return code
    if result.violation is None:
        print("Model checking completed. No error has been found.")
        return EXIT_OK
    from raft_tla_tpu.engine import DEADLOCK
    is_deadlock = result.violation.invariant == DEADLOCK
    if args.no_trace:
        print("Error: Deadlock reached." if is_deadlock else
              f"Error: Invariant {result.violation.invariant} is violated.")
    else:
        from raft_tla_tpu.utils.render import render_trace
        print(render_trace(result.violation, b))
    return EXIT_DEADLOCK if is_deadlock else EXIT_VIOLATION


def _parse_wf(args) -> tuple:
    """--wf families; 'none' = no fairness (the raw reference Spec).
    One definition for the checker AND the TLC twin emitter, so the
    emitted FairSpec always encodes the same fairness as the verdict."""
    if args.wf.strip().lower() == "none":
        return ()
    return tuple(f.strip() for f in args.wf.split(",") if f.strip())


def _check_liveness(args, config, props) -> int:
    from raft_tla_tpu.models import liveness
    from raft_tla_tpu.utils.render import render_state

    wf = _parse_wf(args)
    # Build the behavior graph once for all properties.  Symmetric runs
    # and the DDD engines use the DDD-store export (orbit-quotient
    # soundness argument in liveness.ddd_graph; no device-table
    # ceiling); other device engines keep the device_engine export; host
    # engines use the interpreter.
    try:
        if args.engine in ("host", "ref") and not config.view:
            graph = liveness.explore_graph(config)
        elif config.view or config.symmetry or args.engine in (
                "ddd", "ddd-shard"):
            from raft_tla_tpu.ddd_engine import DDDCapacities
            from raft_tla_tpu.models import spec as S
            if config.symmetry:
                print("Symmetry: liveness runs on the orbit-quotient "
                      "graph (exact for the registered properties — "
                      "models/liveness.ddd_graph); the lasso, if any, "
                      "is a quotient witness")
            A = len(S.action_table(config.bounds, config.spec))
            graph = liveness.ddd_graph(config, DDDCapacities(
                block=args.block or 1 << 20,
                table=1 << max(10, min(26, (2 * args.cap - 1)
                                       .bit_length())),
                seg_rows=max(1 << 19, 2 * args.chunk * A),
                levels=args.levels))
        else:
            from raft_tla_tpu.device_engine import Capacities
            graph = liveness.engine_graph(config, Capacities(
                n_states=args.cap, levels=args.levels))
    except (ValueError, RuntimeError) as e:
        print(f"Error: {e}", file=sys.stderr)
        return EXIT_ERROR
    try:
        return _report_liveness(args, config, props, wf, graph)
    finally:
        if isinstance(graph[0], liveness.StatesView):
            graph[0].close()        # the retained DDD host store


def _report_liveness(args, config, props, wf, graph) -> int:
    from raft_tla_tpu.models import liveness
    from raft_tla_tpu.utils.render import render_state

    for nm in props:
        t0 = time.monotonic()
        try:
            res = liveness.check(config, nm, wf=wf, graph=graph)
        except ValueError as e:
            print(f"Error: {e}", file=sys.stderr)
            return EXIT_ERROR
        wall = time.monotonic() - t0
        pspec = liveness.parse_property(nm)
        shape = f"{pspec.pred_names[0]} ~> {pspec.pred_names[1]}" \
            if pspec.form == liveness.LEADS_TO \
            else f"{pspec.form}{pspec.pred_names[0]}"
        shape_txt = f" ({shape})" if shape != nm else ""
        wf_txt = ", ".join(wf) if wf else "no fairness (raw Spec)"
        print(f"Property {nm}{shape_txt} under WF({wf_txt}): "
              f"{res.n_states} states, {res.n_edges} transitions, "
              f"{wall:.2f}s.")
        if res.holds:
            print(f"Property {nm} is satisfied.")
            continue
        print(f"Error: Property {nm} is violated.")
        if not args.no_trace:
            print("Error: The following behavior, repeated forever, "
                  "refutes it:")
            v = res.violation
            for k, (label, state) in enumerate(v.prefix, start=1):
                head = "<Initial predicate>" if label is None                     else f"<{label}>"
                print(f"State {k}: {head}")
                print(render_state(state, config.bounds))
            n0 = len(v.prefix)
            for k, (label, state) in enumerate(v.cycle, start=n0 + 1):
                print(f"State {k}: <{label}>  (loop)")
                print(render_state(state, config.bounds))
            print(f"(the loop returns to State {n0 + 1})")
        return EXIT_LIVENESS
    return EXIT_OK


def entry() -> None:
    """Console-script entry point (pyproject ``raft-tla-check``)."""
    sys.exit(main())


if __name__ == "__main__":
    entry()
