"""Generate the ready-to-run TLC twin bundles (VERDICT r3 next #8).

The headline configurations, one directory each under runs/tlc_twin/,
so the BASELINE "invariant parity against TLC" gate closes in minutes on
any JVM host: copy the (unmodified) reference ``raft.tla`` next to the
bundle's MCraft.* pair and run the one-liner in RUN.txt.  The reference
itself is CC-BY-4.0 and read-only here; the bundles deliberately do NOT
vendor it.

Regenerate after any exporter change:  python runs/make_tlc_twins.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.models import tla_export

RUNS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(RUNS, "tlc_twin")

FULL_INVS = ("NoTwoLeaders", "LogMatching", "CommittedWithinLog",
             "LeaderCompleteness")
FAITHFUL_INVS = FULL_INVS + ("ElectionSafetyHist",
                             "LeaderCompletenessHist",
                             "AllLogsPrefixClosed")

BUNDLES = {
    # the reference raft.cfg universe, SYMMETRY Server — the flagship
    # exhaustive result: 94,396,461 orbits, diameter 57, all 4 hold
    "flagship_3s2v": dict(
        bounds=Bounds(n_servers=3, n_values=2, max_term=2, max_log=1,
                      max_msgs=2, max_dup=1),
        invariants=FULL_INVS, parity_view=True, symmetry=("Server",),
        spec="full",
        expect="94,396,461 distinct states (the checker's Server-orbit "
               "count; TLC with this SYMMETRY explores the same "
               "quotient), diameter 57, no violation"),
    # BASELINE config #2: the elect5 campaign's exact universe
    "elect5_t2m2": dict(
        bounds=Bounds(n_servers=5, n_values=2, max_term=2, max_log=0,
                      max_msgs=2, max_dup=1),
        invariants=("NoTwoLeaders", "CommittedWithinLog"),
        parity_view=True, symmetry=("Server",), spec="election",
        expect="the campaign's orbit count (RESULTS.md round-4 "
               "endpoint); >2e9 orbits — expect a multi-day TLC run "
               "with a large -fpmem"),
    # full Next, 2 servers, FAITHFUL mode (history variables in the
    # state identity): 84,572 states, diameter 32
    "full2s_faithful": dict(
        bounds=Bounds(n_servers=2, n_values=2, max_term=2, max_log=1,
                      max_msgs=2, max_dup=1, history=True),
        invariants=FAITHFUL_INVS, parity_view=False, symmetry=(),
        spec="full",
        expect="84,572 distinct states, diameter 32, all 7 hold"),
    # the benchmark's faithful3 (runs/MC3s2v_faithful.cfg): the reference
    # raft.cfg universe with every variable of vars in the state identity,
    # SYMMETRY Server, the seven invariants; no VIEW stanza
    "faithful3_3s2v": dict(
        bounds=Bounds(n_servers=3, n_values=2, max_term=2, max_log=1,
                      max_msgs=2, max_dup=1, history=True),
        invariants=FAITHFUL_INVS, parity_view=False, symmetry=("Server",),
        spec="full",
        expect="per level from Init, cumulative distinct states under "
               "SYMMETRY Server: 1, 3, 8, 24, 79, 243, 678, 1591, 3452, "
               "7595, ... 1,011,645 at depth 17, 1,605,204 at depth 18 "
               "(benchmark/configs/faithful3.json level_pins; the whole "
               "space has not been run), all 7 hold"),
    # round-5 temporal surface: leads-to under WF(Next) on the faithful
    # 2-server full space (TLC checks liveness on real states, so the
    # twin omits the VIEW; the checker's verdict is the refutation)
    "full2s_temporal": dict(
        bounds=Bounds(n_servers=2, n_values=1, max_term=2, max_log=0,
                      max_msgs=2, max_dup=1),
        invariants=("NoTwoLeaders",), parity_view=True, symmetry=(),
        spec="full",
        properties=("SomeCandidate ~> SomeLeader", "EventuallyLeader"),
        wf=("Next",),
        expect="TemporalProp1 and EventuallyLeader both VIOLATED under "
               "WF_vars(Next) (the fair crash-loop lasso: repeated "
               "Restart) — TLC exits with a liveness counterexample, "
               "matching the checker's exit 13"),
}

RUN_TXT = """\
TLC twin bundle: {name}
Generated by runs/make_tlc_twins.py from raft_tla_tpu.models.tla_export.

1. Copy the reference spec (unmodified) next to this file:
       cp /path/to/raft-tla/raft.tla .
2. Run stock TLC (needs a JVM; -workers/-fpmem to taste):
       java -XX:+UseParallelGC -jar tla2tools.jar -config MCraft.cfg \\
           -workers auto MCraft.tla
3. Expected: {expect}.

The checker-side twin of this run:
       {cli}
"""

CLI = {
    "flagship_3s2v": "python -m raft_tla_tpu.check /root/reference/"
                     "raft.cfg --engine ddd --symmetry --coverage",
    "elect5_t2m2": "python runs/elect5_ddd.py resume   (campaign; or "
                   "python -m raft_tla_tpu.check runs/MC5s2v.cfg "
                   "--spec election --engine ddd-shard --devices 8)",
    "full2s_faithful": "python -m raft_tla_tpu.check runs/tlc_twin/"
                       "full2s_faithful/MCraft.cfg --faithful "
                       "--engine ddd",
    "faithful3_3s2v": "python -m raft_tla_tpu.check runs/"
                      "MC3s2v_faithful.cfg --faithful --engine ddd "
                      "--symmetry --max-term 2 --max-log 1 --max-msgs 2 "
                      "--chunk 4096 --cap 2097152",
    "full2s_temporal": "python -m raft_tla_tpu.check <cfg with PROPERTY "
                       "SomeCandidate ~> SomeLeader> --spec full "
                       "--engine ref --wf Next   (exit 13 + lasso)",
}


def main() -> None:
    for name, spec in BUNDLES.items():
        outdir = os.path.join(ROOT, name)
        expect = spec.pop("expect")
        tla, cfg = tla_export.export(outdir, **spec)
        with open(os.path.join(outdir, "RUN.txt"), "w") as f:
            f.write(RUN_TXT.format(name=name, expect=expect,
                                   cli=CLI[name]))
        print(f"{name}: {tla}, {cfg}")


if __name__ == "__main__":
    main()
