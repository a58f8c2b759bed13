"""The frontier block's upload ships the rows it has (ISSUE 39).

``DDDEngine._check_impl`` keeps the frontier block resident on the device and
sends a block's live prefix in pieces of ``_up_rows`` rows, or the whole buffer
past ``_up_whole`` piece-rounded rows.  Rows of the resident buffer at and past
a dispatch's ``block_rows`` are whatever an earlier block left there, so
everything here runs with the buffer **poisoned**: every row holds a packed
state that looks live (constraint bit set), breaks the checked invariant and
has successors — if anything read past ``block_rows`` a run would report a
violation or count transitions the reference does not have.
"""

import json
import math
import threading

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.ddd_engine import (_UP_PIECES, _UP_WHOLE, DDDCapacities,
                                     DDDEngine, _upload_plan)
from raft_tla_tpu.models import interp, refbfs
from raft_tla_tpu.models import spec as S
from raft_tla_tpu.obs import compiles
from raft_tla_tpu.utils import prefetch as prefetch_mod

BOUNDS = Bounds(n_servers=2, n_values=1, max_term=2, max_log=0, max_msgs=2)
# the toy's frontiers: 1, 2, 7, 20, 44, 88, 140, 156, 220, 384, 306, 294, 472,
# 340, 194, 210, 112, 24 rows.  A case picks (chunk, block) so that one block
# of one of them has the fill it names: {name: (chunk, block, the block's
# rows, pieces it goes in; 0 = one whole-buffer transfer)}.  At block 256 a
# piece is max(chunk, 8) rows and pieces cover at most 128.
FILLS = {
    "one_row": (8, 256, 1, 1),
    "piece_less_one": (8, 256, 7, 1),
    "piece": (20, 256, 20, 1),
    "piece_plus_one": (19, 256, 20, 2),
    "at_the_crossing": (8, 256, 128, 16),    # 384 = a full block + 128
    "over_the_crossing": (8, 256, 140, 0),   # 18 pieces would cover 144
    "full_block": (8, 256, 256, 0),
    "partial_last_block": (8, 256, 50, 7),   # 306 = a full block + 50
}


def _cfg(chunk, invariants=("NoTwoLeaders",), bounds=BOUNDS):
    return CheckConfig(bounds=bounds, spec="election",
                       invariants=invariants, chunk=chunk)


def _poison_row(eng, role, term):
    """One packed state of ``eng``'s schema with every server in ``role``
    at ``term``: two leaders of one term where the invariant forbids it."""
    bounds = eng.bounds
    n = bounds.n_servers
    bad = interp.init_state(bounds)._replace(role=(role,) * n,
                                             term=(term,) * n)
    return eng.schema.pack(np.asarray(interp.to_vec(bad, bounds), np.int32),
                           np)


def _poisoned(eng, role=S.LEADER, term=2):
    """``eng`` with every resident frontier block born full of live-looking
    violating rows.  Returns the engine."""
    import jax
    row = _poison_row(eng, role, term)

    def make_block():
        return (jax.device_put(np.tile(row, (eng.caps.block, 1))),
                jax.device_put(np.ones((eng.caps.block,), bool)))
    eng._alloc_block = make_block
    return eng


_ENGINES = {}


def _engine(chunk, block):
    """One poisoned engine a (chunk, block): both prefetch arms and the
    whole-buffer reference run on it (``_prefetch`` and ``_up_whole`` are
    read at each ``check()``), so a case compiles once."""
    key = (chunk, block)
    if key not in _ENGINES:
        _ENGINES[key] = _poisoned(DDDEngine(
            _cfg(chunk), DDDCapacities(block=block, table=1 << 14,
                                       flush=1 << 10, levels=64)))
    return _ENGINES[key]


def _stores(eng):
    host, constore, keystore, n = eng.retained
    try:
        par, lane = host.read_links(0, n)
        return (host.read(0, n).tobytes(), par.tobytes(), lane.tobytes(),
                constore.read(0, n).tobytes(),
                keystore.read(0, n).tobytes())
    finally:
        for s in (host, constore, keystore):
            s.close()


def _uploads(log):
    return [e["args"] for e in map(json.loads, open(log))
            if e["event"] == "span" and e["name"] == "upload"]


@pytest.fixture(scope="module")
def toy_ref():
    return refbfs.check(_cfg(32))


def test_upload_plan_is_a_constant_of_the_shapes():
    """One piece size an engine, from ``block`` and ``chunk`` alone; pieces
    never overrun the buffer, whatever the two are."""
    assert _upload_plan(1 << 20, 4096) == (1 << 15, 1 << 19)
    assert 0 < _UP_WHOLE <= _UP_PIECES
    for block, chunk in ((256, 8), (256, 19), (128, 6), (64, 64), (32, 32),
                         (1 << 20, 1 << 16)):
        piece, whole_above = _upload_plan(block, chunk)
        assert chunk <= piece <= block and whole_above <= block
        # the last piece of any block that goes in pieces ends inside
        assert (whole_above // piece) * piece <= block


@pytest.mark.parametrize("prefetch", [True, False], ids=["pf_on", "pf_off"])
@pytest.mark.parametrize("fill", list(FILLS))
def test_block_fill_is_exact_with_a_poisoned_resident_block(
        fill, prefetch, toy_ref, tmp_path, monkeypatch):
    """Every block fill, both ``RAFT_TLA_PREFETCH`` arms: the counts a level
    are the reference's, the stores hold the bytes a run that sends every
    block whole (what the engine did before) leaves, the named fill really
    went the way it names, and the upload spans tell what was sent."""
    chunk, block, rows, pieces = FILLS[fill]
    eng = _engine(chunk, block)
    piece, whole_above = eng._up_rows, eng._up_whole
    assert (piece, whole_above) == (max(chunk, block // _UP_PIECES),
                                    block * _UP_WHOLE // _UP_PIECES)
    # the reference for the stores: every block one whole-buffer transfer
    monkeypatch.setattr(eng, "_up_whole", 0)
    monkeypatch.setattr(eng, "_prefetch", False)
    whole = eng.check(retain_store=True)
    want = _stores(eng)
    assert whole.levels == toy_ref.levels
    monkeypatch.setattr(eng, "_up_whole", whole_above)
    monkeypatch.setattr(eng, "_prefetch", prefetch)
    monkeypatch.setenv("RAFT_TLA_TRACE", "1")
    log = str(tmp_path / "ev.jsonl")
    got = eng.check(retain_store=True, events=log)
    assert got.violation is None and got.complete
    assert got.levels == toy_ref.levels
    assert got.n_states == toy_ref.n_states == 3014
    assert got.n_transitions == toy_ref.n_transitions
    assert got.coverage == toy_ref.coverage
    assert _stores(eng) == want
    ups = _uploads(log)
    row_bytes = 4 * eng.schema.P + 1
    for up in ups:
        assert 0 < up["rows"] <= up["padded_rows"] <= block
        assert up["bytes"] == up["padded_rows"] * row_bytes
        assert "prefetch_hit" in up
        n = math.ceil(up["rows"] / piece)
        if n * piece <= whole_above:
            assert (up["pieces"], up["padded_rows"]) == (n, n * piece)
        else:
            assert (up["pieces"], up["padded_rows"]) == (1, block)
    mine = [up for up in ups if up["rows"] == rows]
    assert mine, (fill, sorted({up["rows"] for up in ups}))
    assert all(up["padded_rows"] == (pieces * piece if pieces else block)
               for up in mine)
    # the ledger's sums a level are the spans' (one engine object)
    for lv, lv_ups in zip(got.level_log["levels"], _by_level(ups, got)):
        assert lv["uploads"] == len(lv_ups)
        assert lv["upload_bytes"] == sum(u["bytes"] for u in lv_ups)
        assert lv["upload_pieces"] == sum(u["pieces"] for u in lv_ups)


def _by_level(ups, res):
    """The upload spans' args grouped by level: a level of ``r`` rows has
    ``ceil(r / block)`` uploads whose rows sum to ``r``, in order."""
    it, out = iter(ups), []
    for r in res.levels:
        mine, left = [], r
        while left > 0:
            up = next(it)
            mine.append(up)
            left -= up["rows"]
        assert left == 0
        out.append(mine)
    assert next(it, None) is None
    return out


def test_poison_is_live_if_anything_reads_it():
    """The control of the poison itself: the same rows inside ``block_rows``
    are expanded and do break the invariant, so a run that stays clean
    above proves the mask, not a dud poison."""
    eng = _engine(8, 256)
    bad = interp.init_state(BOUNDS)._replace(role=(S.LEADER,) * 2,
                                             term=(2, 2))
    got = eng.check(init_override=bad)
    assert got.violation is not None
    assert got.violation.invariant == "NoTwoLeaders"


@pytest.mark.parametrize("prefetch", [True, False], ids=["pf_on", "pf_off"])
def test_planted_violation_trace_is_the_references(prefetch, monkeypatch):
    """A planted fault found behind poisoned blocks, in pieces: the stop is
    the reference's to the state, the trace its trace."""
    from raft_tla_tpu.ops import msgbits as mb
    bounds = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                    max_msgs=4, max_dup=1)
    cfg = _cfg(4, ("NaiveNoTwoLeaders",), bounds)
    start = interp.init_state(bounds)._replace(
        role=(S.LEADER, S.FOLLOWER, S.CANDIDATE), term=(2, 3, 3),
        votedFor=(1, 3, 0), vGrant=(0b011, 0, 0b100),
        msgs=tuple(sorted((m, 1) for m in (mb.rv_response(3, 1, 1, 2),))))
    ref = refbfs.check(cfg, init_override=start)
    key = ("violation", 4, 64)
    if key not in _ENGINES:
        _ENGINES[key] = _poisoned(DDDEngine(cfg, DDDCapacities(
            block=64, table=1 << 17, flush=1 << 12, levels=64)), term=3)
    eng = _ENGINES[key]
    monkeypatch.setattr(eng, "_prefetch", prefetch)
    got = eng.check(init_override=start)
    assert ref.violation is not None and got.violation is not None
    assert got.violation.invariant == ref.violation.invariant
    assert got.n_states == ref.n_states          # refbfs-exact stop
    assert got.violation.state == ref.violation.state
    assert got.violation.trace == ref.violation.trace
    # some block did go in several pieces before the stop
    assert any(lv["upload_pieces"] > lv["uploads"]
               for lv in got.level_log["levels"])


def _stop_inside_the_partial_block(eng, ck):
    """A pass of the (8, 256) engine that a SIGINT stops from inside an
    upload, between the first and the second piece of level 384's second
    block (a full block, then 128 rows in 16 pieces: the fourth block of
    the pass that goes in more than one piece, after the levels of 20, 44
    and 88 rows).  Returns the stopped result.

    With the prefetcher the upload runs on its thread while the main one
    expands the level's first block, so the two are held to one order: the
    main thread waits at that block's second dispatch (the first full block
    of the pass) until the flag is up, and the upload waits after raising it
    until the stop path is in ``invalidate()``.  The flag is then seen
    inside the first block, after a harvest of it, and ``invalidate()``
    finds the upload in flight, on any host."""
    place, segment = eng._place, eng._segment
    invalidate = prefetch_mod.BlockPrefetcher.invalidate
    flagged, invalidating = threading.Event(), threading.Event()
    second_pieces, full_block_dispatches, found_in_flight = [], [], []

    def place_and_stop(fbuf, fcon, rows, con, at):
        out = place(fbuf, fcon, rows, con, at)
        if int(at) == eng._up_rows:
            second_pieces.append(at)
            if len(second_pieces) == 4:
                invalidating.clear()
                eng._sigint = True
                flagged.set()
                if eng._prefetch:
                    found_in_flight.append(invalidating.wait(60.0))
        return out

    def segment_after_the_flag(fc, bufs, fbuf, fcon, budget, b_rows):
        if eng._prefetch and int(b_rows) == eng.caps.block:
            full_block_dispatches.append(b_rows)
            if len(full_block_dispatches) == 2:
                assert flagged.wait(60.0)
        return segment(fc, bufs, fbuf, fcon, budget, b_rows)

    def invalidate_and_say_so(self):
        invalidating.set()
        invalidate(self)

    eng._place, eng._segment = place_and_stop, segment_after_the_flag
    prefetch_mod.BlockPrefetcher.invalidate = invalidate_and_say_so
    try:
        got = eng.check(checkpoint=ck, checkpoint_every_s=3600.0)
    finally:
        eng._place, eng._segment = place, segment
        prefetch_mod.BlockPrefetcher.invalidate = invalidate
    assert found_in_flight == ([True] if eng._prefetch else [])
    return got


@pytest.mark.parametrize("prefetch", [True, False], ids=["pf_on", "pf_off"])
def test_stop_between_two_pieces_is_lossless(prefetch, toy_ref, tmp_path,
                                             monkeypatch):
    """A stop that lands between two pieces of a block: the upload runs to
    its end (on the prefetcher's thread the stop path's ``invalidate()``
    waits for it), nothing of the half-placed block is ever dispatched, the
    snapshot resumes to the reference's counts and to the uninterrupted
    run's stores.  Without the prefetcher the stop is inside the level's
    second block, so the snapshot holds ``blocks_done`` = 1."""
    eng = _engine(8, 256)
    monkeypatch.setattr(eng, "_prefetch", prefetch)
    straight = eng.check(retain_store=True)
    want = _stores(eng)
    ck = str(tmp_path / "stop.ckpt")
    got = _stop_inside_the_partial_block(eng, ck)
    assert not got.complete and got.violation is None
    # the stop is inside the level that expands the 384-row frontier
    assert got.levels[:10] == toy_ref.levels[:10]
    assert sum(toy_ref.levels[:10]) <= got.n_states < straight.n_states
    with np.load(ck) as z:
        assert int(z["blocks_done"]) == (0 if prefetch else 1)
    resumed = eng.check(resume=ck, retain_store=True)
    assert resumed.complete and resumed.violation is None
    assert resumed.levels == toy_ref.levels
    assert resumed.n_states == straight.n_states
    assert resumed.n_transitions == straight.n_transitions
    assert resumed.coverage == straight.coverage
    assert _stores(eng) == want


@pytest.mark.parametrize("prefetch", [True, False], ids=["pf_on", "pf_off"])
def test_resume_from_a_done_block_uploads_the_partial_one(
        prefetch, toy_ref, tmp_path, monkeypatch):
    """``blocks_done`` > 0: the resume's first upload is the level's
    partial second block, in pieces, into a fresh (poisoned) resident
    block — in either arm."""
    eng = _engine(8, 256)
    monkeypatch.setattr(eng, "_prefetch", False)
    ck = str(tmp_path / "blocks.ckpt")
    _stop_inside_the_partial_block(eng, ck)
    with np.load(ck) as z:
        assert int(z["blocks_done"]) == 1
    monkeypatch.setattr(eng, "_prefetch", prefetch)
    monkeypatch.setenv("RAFT_TLA_TRACE", "1")
    log = str(tmp_path / "ev.jsonl")
    resumed = eng.check(resume=ck, events=log)
    first = _uploads(log)[0]
    assert (first["rows"], first["pieces"], first["padded_rows"]) \
        == (128, 16, 128)
    assert resumed.complete and resumed.violation is None
    assert resumed.levels == toy_ref.levels
    assert resumed.n_transitions == toy_ref.n_transitions
    assert resumed.coverage == toy_ref.coverage


def test_no_compile_inside_a_pass_over_levels_of_many_sizes(toy_ref):
    """The compile ledger over a second pass of an engine whose levels
    take 1, 2, 3, 6, 7, 11, 16 pieces and whole buffers: nothing is traced,
    lowered or compiled — one ``_place`` program whatever a level holds."""
    eng = _engine(8, 256)
    eng.check()                                   # builds every program
    before = compiles.snapshot()
    got = eng.check()
    after = compiles.snapshot()
    sizes = {(lv["upload_pieces"], lv["upload_bytes"])
             for lv in got.level_log["levels"]}
    assert len(sizes) >= 4 and got.levels == toy_ref.levels
    assert after == before


def test_the_segment_program_is_the_parents():
    """The upload changed around the segment program, not in it: the toy's
    ``_segment`` lowers to the StableHLO (locations stripped) it lowered to
    at c8a16f7, the commit before ISSUE 39 — same arguments, same shapes,
    so the compiled programs of every configuration are the ones the
    compile cache holds.  A later change that does alter the step moves
    this digest on purpose, and says so."""
    import hashlib

    import jax
    import jax.numpy as jnp
    eng = DDDEngine(_cfg(32), DDDCapacities(block=256, table=1 << 14,
                                            flush=1 << 10, levels=64))
    sd = jax.ShapeDtypeStruct
    text = eng._segment.lower(
        jax.eval_shape(eng._init_filter), jax.eval_shape(eng._make_bufs),
        sd((256, eng.schema.P), jnp.int32), sd((256,), jnp.bool_),
        sd((), jnp.int32), sd((), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "ad0e79e6f3707fa6"
