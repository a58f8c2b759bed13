"""frontend/twophase: the second bundled spec, end to end.

The acceptance bar from the frontend PR: a protocol that is NOT Raft,
declared entirely as frontend schema + IR, checked through the same
engine/serve/obs stack, with every count pinned against an independent
NumPy BFS oracle (``twophase.reference_check``) at two bound settings —
and the n=3 state count (288) agreeing with TLC's published figure for
the TwoPhase module at RM cardinality 3.
"""

import json

import numpy as np
import pytest

from raft_tla_tpu import engine
from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.frontend import twophase as tp
from raft_tla_tpu.frontend.registry import TwoPhaseModel, resolve_model
from raft_tla_tpu.frontend.schema import Field, Schema, check_schema
from raft_tla_tpu.serve import CheckJob, JobOptions, admit
from raft_tla_tpu.serve.batch import BatchExecutor
from raft_tla_tpu.serve.service import load_jobs, run_service

# Pinned oracle outputs (independently BFS'd; 288 at n=3 matches TLC).
ORACLE = {1: (12, 4, 19), 2: (56, 7, 153), 3: (288, 10, 1145)}

CFG_2PC = ("SPECIFICATION Spec\n"
           "CONSTANT RM = {r1, r2}\n"
           "INVARIANT TCConsistent\n")


def _config(n, invariants=("TCConsistent",), **kw):
    return CheckConfig(bounds=Bounds(n_servers=n, n_values=1),
                       spec="twophase", invariants=invariants,
                       chunk=256, **kw)


# -- oracle and engine parity -------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3])
def test_reference_oracle_pinned(n):
    ref = tp.reference_check(n)
    assert (ref.n_states, ref.diameter, ref.n_transitions) == ORACLE[n]
    assert ref.consistent


@pytest.mark.parametrize("n", [2, 3])
def test_engine_matches_oracle(n):
    ref = tp.reference_check(n)
    got = engine.check(_config(n))
    assert got.violation is None
    assert got.n_states == ref.n_states
    assert got.diameter == ref.diameter
    assert got.n_transitions == ref.n_transitions


def test_never_deadlocks():
    # Terminal states keep self-successors (verdict messages redeliver),
    # so TLC's -deadlock analog finds nothing anywhere in the space.
    got = engine.check(_config(2, check_deadlock=True))
    assert got.violation is None
    assert got.n_states == ORACLE[2][0]


# -- codec and schema ---------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3])
def test_state_codec_roundtrip(n):
    b = Bounds(n_servers=n, n_values=1)
    lay = tp.SCHEMA.layout(b)
    assert lay.width == 3 * n + 3
    init = tp.init_state(b)
    vec = tp.to_vec(init, b)
    assert vec.shape == (lay.width,)
    assert tp.from_vec(vec, b) == init
    # pack/unpack consistent with the codec: struct fields mirror TPState
    struct = lay.unpack(vec, np)
    assert list(struct["rmState"]) == list(init.rmState)
    assert int(struct["tmState"][0]) == init.tmState
    # a non-init state round-trips too
    s = init._replace(rmState=(tp.PREPARED,) * n,
                      tmPrepared=(1,) * n, msgPrepared=(1,) * n)
    assert tp.from_vec(tp.to_vec(s, b), b) == s


def test_check_schema_rejects_invalid():
    bad = Schema("bad", (
        Field("x", ("n",), lo=0, hi=2, init=0),
        Field("y", (), lo=5, hi=2, init=5),          # hi < lo
    ))
    codes = [f.code for f in check_schema(bad, Bounds(n_servers=2))]
    assert codes                                     # at least one finding
    assert any("schema" in c for c in codes)
    assert check_schema(tp.SCHEMA, Bounds(n_servers=3)) == []


# -- violations and rendering -------------------------------------------------

def test_expression_invariant_violation_trace():
    """`~any(rmState = 2)` ("no RM ever commits") is falsifiable; the
    trace renders TLC-style through the twophase renderer."""
    got = engine.check(_config(2, invariants=("~any(rmState = 2)",)))
    assert got.violation is not None
    assert got.violation.invariant == "~any(rmState = 2)"
    model = resolve_model("twophase")
    text = model.render_trace(got.violation, Bounds(n_servers=2, n_values=1))
    assert "Invariant ~any(rmState = 2) is violated" in text
    assert "State 1: <Initial predicate>" in text
    assert "rmState" in text and "tmState" in text
    # the final state must actually falsify the predicate
    assert tp.COMMITTED in got.violation.state.rmState


def test_tc_consistent_holds_everywhere():
    ref = tp.reference_check(2)
    assert ref.consistent
    assert engine.check(_config(2)).violation is None


# -- serve: admission, batching, service --------------------------------------

def test_admission_admits_twophase():
    adm = admit(CheckJob("2pc", JobOptions(spec="twophase"),
                         cfg_text=CFG_2PC))
    assert adm.admitted and adm.reason is None
    assert adm.config.spec == "twophase"
    assert adm.config.bounds.n_servers == 2
    assert adm.config.invariants == ("TCConsistent",)


def test_admission_rejects_unknown_spec():
    adm = admit(CheckJob("typo", JobOptions(spec="twophse"),
                         cfg_text=CFG_2PC))
    assert not adm.admitted and adm.reason == "spec-unknown"
    [f] = [f for f in adm.findings if f.code == "spec-unknown"]
    assert "did you mean: twophase" in f.message


def test_admission_rejects_bad_expression():
    bad = CFG_2PC.replace("TCConsistent", "all(bogus = 1)")
    adm = admit(CheckJob("bad", JobOptions(spec="twophase"), cfg_text=bad))
    assert not adm.admitted and adm.reason == "cfg-invalid"


def test_admission_rejects_unsupported_stanzas():
    for extra, frag in [("SYMMETRY Server\n", "symmetry"),
                        ("PROPERTY EventuallyLeader\n", "propert")]:
        adm = admit(CheckJob("x", JobOptions(spec="twophase"),
                             cfg_text=CFG_2PC + extra))
        assert not adm.admitted and adm.reason == "cfg-invalid", extra
        assert any(frag in f.message for f in adm.findings), extra


def test_batch_mixed_raft_and_twophase():
    """One executor, raft and twophase tenants in separate bins; each
    lane's counts equal its solo run."""
    raft_cfg = CheckConfig(
        bounds=Bounds(n_servers=2, n_values=1, max_term=2, max_log=0,
                      max_msgs=2),
        spec="election", invariants=("NoTwoLeaders",), chunk=256)
    out = BatchExecutor(chunk=256).run(
        [("raft", raft_cfg), ("2pc-a", _config(2)), ("2pc-b", _config(3))])
    assert out["raft"].status == "completed"
    assert out["raft"].result.n_states == 3014
    for jid, n in (("2pc-a", 2), ("2pc-b", 3)):
        assert out[jid].status == "completed"
        assert out[jid].result.n_states == ORACLE[n][0]
        assert out[jid].result.n_transitions == ORACLE[n][2]


def test_service_end_to_end_twophase(tmp_path):
    from raft_tla_tpu.obs import monitor, validate_event

    (tmp_path / "2pc.cfg").write_text(CFG_2PC)
    manifest = tmp_path / "manifest.jsonl"
    manifest.write_text(json.dumps(
        {"id": "2pc", "cfg": "2pc.cfg", "spec": "twophase"}) + "\n")
    out_dir = tmp_path / "out"
    records = run_service(load_jobs(str(manifest)), str(out_dir),
                          chunk=256, quiet=True)
    [rec] = records
    assert rec["status"] == "completed"
    assert rec["n_states"] == ORACLE[2][0]
    events = [json.loads(l) for l in open(rec["events"])]
    assert not [e for d in events for e in validate_event(d)]
    assert events[0]["event"] == "run_start"
    assert events[0]["spec"] == "twophase"
    assert events[-1]["event"] == "run_end"
    hb = monitor.heartbeat(monitor.summarize(
        monitor.load_stream(rec["events"])))
    assert "ok" in hb


# -- CLI-facing model surface -------------------------------------------------

def test_model_engine_gate():
    model = resolve_model("twophase")
    assert model.engines == ("host", "ddd", "simulate")
    assert not model.is_raft


# the source's own TwoPhase.cfg (tlaplus/Examples, transaction_commit/), as
# it is written: its spec is TPSpec, its invariants TPTypeOK and TCConsistent
CFG_SOURCE = ("CONSTANT RM = {r1, r2, r3}\n"
              "INVARIANTS TPTypeOK TCConsistent\n"
              "SPECIFICATION TPSpec\n")


def test_the_sources_cfg_is_admitted_as_it_is_written():
    adm = admit(CheckJob("2pc", JobOptions(spec="twophase"),
                         cfg_text=CFG_SOURCE))
    assert adm.admitted and adm.reason is None
    assert adm.config.bounds.n_servers == 3
    assert adm.config.invariants == ("TPTypeOK", "TCConsistent")
    init_next = CFG_SOURCE.replace("SPECIFICATION TPSpec",
                                   "INIT TPInit\nNEXT TPNext")
    assert admit(CheckJob("2pc", JobOptions(spec="twophase"),
                          cfg_text=init_next)).admitted
    other = admit(CheckJob("2pc", JobOptions(spec="twophase"),
                           cfg_text=CFG_SOURCE.replace("TPSpec", "TCSpec")))
    assert not other.admitted and other.reason == "cfg-invalid"


def test_tp_type_ok_is_the_declared_ranges():
    b = Bounds(n_servers=3, n_values=1)
    check = resolve_model("twophase").py_invariant("TPTypeOK")
    init = tp.init_state(b)
    assert check(init, b)
    for bad in (init._replace(tmState=3), init._replace(msgAbort=2),
                init._replace(rmState=(0, 4, 0)),
                init._replace(tmPrepared=(0, 0, -1))):
        assert not check(bad, b)
    # every field of the schema is held, by its own declaration
    for f in tp.SCHEMA.fields:
        assert f"all({f.name} <= {f.hi})" in tp.INVARIANTS["TPTypeOK"]
    got = engine.check(_config(3, invariants=("TPTypeOK", "TCConsistent")))
    assert got.violation is None and got.n_states == ORACLE[3][0]


def test_cli_runs_the_sources_cfg_on_ddd_and_refuses_the_mesh_and_the_route(
        tmp_path, capsys):
    from raft_tla_tpu import check
    cfg = tmp_path / "TwoPhase.cfg"
    cfg.write_text(CFG_SOURCE)
    base = [str(cfg), "--spec", "twophase", "--chunk", "64", "--cpu"]
    assert check.main(base + ["--engine", "ddd", "--coverage"]) == 0
    out = capsys.readouterr().out
    assert "288 distinct states found, diameter 10, 1145 transitions" in out
    assert "Invariants: TPTypeOK, TCConsistent" in out
    assert "RMRcvAbortMsg: 152 new states" in out
    assert "No error has been found" in out
    for extra, said in ((["--engine", "ddd-shard"],
                         "--engine ddd-shard does not support spec "
                         "'twophase' (supported: host, ddd, simulate)"),
                        (["--engine", "ddd", "--route", "512"],
                         "--route does not support spec 'twophase'")):
        with pytest.raises(SystemExit) as e:
            check.main(base + extra)
        assert e.value.code == 2
        assert said in capsys.readouterr().err


def test_emit_tla(tmp_path):
    model = TwoPhaseModel()
    paths = model.emit_tla(str(tmp_path), Bounds(n_servers=3, n_values=1),
                           invariants=("TCConsistent",))
    texts = {p.rsplit("/", 1)[-1]: open(p).read() for p in paths}
    assert set(texts) == {"MC2pc.tla", "MC2pc.cfg"}
    cfg = texts["MC2pc.cfg"]
    assert "SPECIFICATION Spec" in cfg
    assert "RM = {r1, r2, r3}" in cfg
    assert "INVARIANT" in cfg and "TCConsistent" in cfg
    # the template defines TPTypeOK: both of the source's invariants emit
    both = model.emit_tla(str(tmp_path), Bounds(n_servers=3, n_values=1),
                          invariants=("TPTypeOK", "TCConsistent"))
    assert "INVARIANT TPTypeOK\nINVARIANT TCConsistent" in open(both[1]).read()
    tla = texts["MC2pc.tla"]
    assert "MODULE MC2pc" in tla
    assert "TCConsistent" in tla
    # expression invariants have no TLA name to emit — refuse loudly
    with pytest.raises(ValueError, match="expression"):
        model.emit_tla(str(tmp_path), Bounds(n_servers=2, n_values=1),
                       invariants=("all(rmState <= 3)",))
