"""The reading: one pass = one ``check()`` to the pinned count at level B,
from Init or (a traffic with ``start.snapshot_level``) resumed from the run's
own level-pinned snapshot.  A run's rate is all the orbits its passes admitted
over the whole window they ran in; each pass is also clocked over its at-depth
span, for the per-layer readings: between the two progress records whose
``n_states`` equal the pinned counts at levels A and B, or, resumed, from the
engine's ``run_start`` (the snapshot is loaded, the first upload is next) to
the record at B.  A traffic with ``"end": "fixpoint"`` lets the pass run to its
own end instead: nothing stops it, B is the last level that admits anything,
and the span ends where ``check()`` returns with its verdict."""

from __future__ import annotations

import dataclasses
import itertools
import json
import signal
import statistics
import time

# the traced window of a resumed pass: closed at the harvest of the segment
# that brings the chunk steps since the resume to this many (a whole level at
# depth is 256 steps and several million op events)
TRACED_STEPS = 40


@dataclasses.dataclass
class Pass:
    """What one pass left behind.  Times are ``time.monotonic()`` seconds."""

    index: int
    t_call: float
    t_a: float | None = None        # stamp at the pinned count of level A
    t_b: float | None = None        # stamp at the pinned count of level B
    t_return: float | None = None
    traced: bool = False
    levels: list = dataclasses.field(default_factory=list)   # cumulative
    overshoot_levels: list = dataclasses.field(default_factory=list)
    violation: str | None = None
    problem: str | None = None      # why the pass counts as failed
    compiles: int = 0               # compile events between call and return
    events: str | None = None       # the pass's run-event log, if any
    trace_dir: str | None = None
    anchor: tuple | None = None     # (monotonic ns, annotation name)
    t_trace_end: float | None = None   # the capture covers t_a..t_trace_end
    resumed: bool = False           # check(resume=): t_a is its run_start
    start_keys: int | None = None   # resumed: the engine's first count
    n_states: int | None = None     # the result's count at the return
    coverage: dict | None = None    # the result's count by action family
    fixpoint: bool = False          # ran to its own end: t_b is the return
    t_last: float | None = None     # fixpoint: first record at the total
    complete: bool | None = None    # the result's own word on its verdict

    @property
    def reached(self) -> bool:
        return self.t_a is not None and self.t_b is not None

    def rate(self, orbits: int) -> float | None:
        return orbits / (self.t_b - self.t_a) if self.reached else None

    @property
    def ramp_s(self):
        return None if self.t_a is None else self.t_a - self.t_call

    @property
    def span_s(self):
        return self.t_b - self.t_a if self.reached else None

    @property
    def overshoot_s(self):
        if self.t_b is None or self.t_return is None:
            return None
        return self.t_return - self.t_b

    @property
    def verdict_s(self):
        """``check()``'s call to its return, of a pass that ended by itself
        with ``complete = True``; None of any other pass."""
        if not self.fixpoint or self.complete is not True \
                or self.t_return is None:
            return None
        return self.t_return - self.t_call

    @property
    def close_s(self):
        """Fixpoint: the first record at the space's total -> the return
        (the empty last expansion, the final flush, the result)."""
        if self.t_last is None or self.t_return is None:
            return None
        return self.t_return - self.t_last


class SpanClock:
    """The ``on_progress`` callback of one pass.  Stamps the benchmark's own
    clock at the two level-boundary records whose count equals the pins, then
    asks the engine for its lossless stop (first SIGINT = stop at the next
    segment/window boundary: ``ddd_engine.install_sigint_boundary_stop``).

    A record's ``n_states`` is exact at a level boundary only, so a stamp
    also needs the record's ``level`` to be the boundary's.  A resumed pass
    has no record at A (its first boundary is A + 1): ``run_start`` of the
    pass's event log is its stamp A, read after the return.

    ``level_ahead``: how far the engine's boundary record runs ahead of the
    level it closes.  The ddd engine reports before it opens the next level
    (0); the mesh engine after (1), and then repeats that pair of level and
    count from inside the next level's window until its drain, so the hook
    ``after_first_level`` fires at the first such record only.

    ``fixpoint``: the pass runs to its own end.  Stamp A as ever; at the
    first record that carries the pin of B (the space's total) the clock
    stamps ``t_last`` and raises nothing: ``check()`` returns by itself, and
    that return is stamp B (``finish``).  Only a record whose count passes
    the total stops the pass, failed: a search that overruns its pins must
    not run for an hour."""

    def __init__(self, p: Pass, pins: list, level_a: int, level_b: int,
                 at_a=None, after_first_level=None, level_ahead: int = 0,
                 fixpoint: bool = False):
        self.p = p
        self.pins = pins
        self.level_a, self.level_b = level_a, level_b
        self.level_ahead = level_ahead
        self.fixpoint = fixpoint
        # the traced pass's capture: at_a() opens it at stamp A,
        # after_first_level(now) closes it at the boundary of level A + 1
        self.at_a, self.after_first_level = at_a, after_first_level

    def __call__(self, rec: dict) -> None:
        now = time.monotonic()
        p = self.p
        level = rec["level"] - self.level_ahead
        if self.fixpoint and p.problem is None \
                and rec["n_states"] > self.pins[self.level_b]:
            p.problem = (f"the search ran past the last pin "
                         f"{self.pins[self.level_b]} (level {self.level_b}): "
                         f"now at {rec['n_states']}, level {level}")
            signal.raise_signal(signal.SIGINT)
            return
        if not 0 <= level < len(self.pins) \
                or rec["n_states"] != self.pins[level]:
            if level > self.level_b and p.t_b is None and p.problem is None \
                    and not self.fixpoint:
                # past B with no boundary record at the pin: stop, failed
                p.problem = (f"no level-{self.level_b} boundary record at "
                             f"the pinned count {self.pins[self.level_b]}; "
                             f"now at {rec['n_states']}, level {level}")
                signal.raise_signal(signal.SIGINT)
            return
        hook = None
        if p.t_a is not None and level == self.level_a + 1:
            hook, self.after_first_level = self.after_first_level, None
        if level == self.level_a and p.t_a is None:
            p.t_a = now
            if self.at_a is not None:
                self.at_a()
                p.t_a = time.monotonic()    # the span starts after the hook
        elif level == self.level_b and p.t_b is None and p.t_last is None \
                and (p.t_a is not None or p.resumed):
            if self.fixpoint:
                p.t_last = now      # nothing is raised: the pass ends itself
            else:
                p.t_b = now
            if hook is not None:
                hook(now)
            if not self.fixpoint:
                signal.raise_signal(signal.SIGINT)
        elif hook is not None:
            hook(now)


def run_start(events_path: str) -> dict | None:
    """``{"mono", "n_states"}`` of the log's ``run_start`` event: the
    engine's monotonic clock and key count when its level loop begins (after
    a resume: the snapshot loaded, the key set rebuilt)."""
    with open(events_path, encoding="utf-8") as f:
        for line in f:
            ev = json.loads(line)
            if ev.get("event") == "run_start":
                return {"mono": ev["anchor"]["mono"],
                        "n_states": ev.get("n_states")}
    return None


def finish(p: Pass, result, pins: list, end_level: int,
           fixpoint: bool = False) -> Pass:
    """Fill the pass from the engine's result and hold it to the pins.
    ``fixpoint``: the pass ran to its own end; its return is stamp B, and it
    is held to ``complete is True``, a count equal to the last pin, the whole
    level table equal to the pins and no level past them."""
    p.t_return = time.monotonic()
    p.n_states = result.n_states
    p.coverage = dict(result.coverage)
    p.complete = getattr(result, "complete", None)
    if fixpoint:
        p.fixpoint = True
        if p.t_last is not None:
            p.t_b = p.t_return
    if p.resumed:
        began = run_start(p.events)
        if began is not None:
            p.t_a, p.start_keys = began["mono"], began["n_states"]
    p.levels = list(itertools.accumulate(result.levels))
    p.violation = result.violation.invariant if result.violation else None
    # what the engine did past B before it stopped (at most a segment) is
    # overshoot: kept apart, bounded by the pins
    p.levels, p.overshoot_levels = (p.levels[:end_level + 1],
                                    p.levels[end_level + 1:])
    want = pins[:end_level + 1]
    over_pins = pins[end_level + 1:]
    if p.problem is None and any(
            k >= len(over_pins) or got > over_pins[k]
            for k, got in enumerate(p.overshoot_levels)):
        p.problem = (f"overshoot levels {p.overshoot_levels} exceed the pins "
                     f"{over_pins[:len(p.overshoot_levels)]}")
    if p.problem is None and fixpoint:
        if p.complete is not True:
            p.problem = (f"check() returned complete = {p.complete}, not a "
                         "verdict")
        elif p.n_states != pins[end_level]:
            p.problem = (f"the pass ended with {p.n_states} orbits, the "
                         f"last pin is {pins[end_level]}")
    if p.problem is None:
        if not p.reached:
            p.problem = "the pass ended before the pinned count at B"
        elif p.violation:
            p.problem = f"violation {p.violation}"
        elif p.levels != want:
            k = next((i for i, (a, b) in enumerate(zip(p.levels, want))
                      if a != b), min(len(p.levels), len(want)))
            p.problem = (f"level table differs from the pins at level {k}: "
                         f"got {p.levels[k:k + 1]}, pinned {want[k:k + 1]}")
    return p


def window_rate(made: list, orbits_a_pass: int, window_s: float) -> dict:
    """The end-to-end reading: every orbit the window's sound passes
    admitted (a pass from Init to level B admits the pinned count at B, a
    resumed one that count less the keys its snapshot held; a failed pass
    counts for nothing) over ALL the window's time, from the first pass's
    call to the last one's return: ramp or resume, span, overshoot and
    whatever lies between passes, stalls included."""
    orbits = orbits_a_pass * sum(p.problem is None for p in made)
    # the ramp's share is read on untraced passes: a traced pass's span
    # holds the capture's write-out
    plain = [p for p in made if not p.traced and p.ramp_s is not None]
    wall = sum(p.t_return - p.t_call for p in plain)
    return {"window_s": window_s, "orbits": orbits,
            "rate": orbits / window_s if orbits else None,
            "ramp_share_pct": 100.0 * sum(p.ramp_s for p in plain) / wall
            if wall else None}


def summarise(rates: list) -> dict:
    """Median and spread of a run's A->B span rates (sound, untraced
    passes): the at-depth reading, per layer."""
    med = statistics.median(rates)
    return {"median": med, "min": min(rates), "max": max(rates),
            "spread_pct": 100.0 * (max(rates) - min(rates)) / med}


def room_for_another(elapsed: float, last_pass_s: float, seconds: float,
                     made: int, min_passes: int) -> bool:
    """Whole passes until ``seconds`` of run time are used, at least
    ``min_passes``: a pass is started only if one as long as the last still
    ends inside the window."""
    return made < min_passes or elapsed + last_pass_s <= seconds
