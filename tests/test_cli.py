"""CLI (check.py), TLC export (models/tla_export.py), and trace rendering.

The CLI is the checker's L6 layer (SURVEY §1): stock cfg in, TLC-style
report out, TLC-compatible exit codes.  No JVM exists here, so the TLC
artifacts are validated structurally and by cfgparse round-trip
(tla_export module docstring).
"""

import io
import os
import re
from contextlib import redirect_stdout

import pytest

from raft_tla_tpu import check as cli
from raft_tla_tpu.config import Bounds, CheckConfig
from raft_tla_tpu.models import refbfs, spec as S, tla_export
from raft_tla_tpu.models import interp
from raft_tla_tpu.ops import msgbits as mb
from raft_tla_tpu.utils import render
from raft_tla_tpu.utils.cfgparse import parse_cfg

REF_CFG = "/root/reference/raft.cfg"
if not os.path.exists(REF_CFG):     # not mounted here: the vendored copy
    REF_CFG = os.path.join(os.path.dirname(__file__), "fixtures", "raft.cfg")



_CFG_CONSTANTS = (
    "CONSTANTS\n"
    "    Server = {%s}\n    Value = {v1}\n"
    '    Follower = "Follower"\n    Candidate = "Candidate"\n'
    '    Leader = "Leader"\n    Nil = "Nil"\n'
    '    RequestVoteRequest = "RequestVoteRequest"\n'
    '    RequestVoteResponse = "RequestVoteResponse"\n'
    '    AppendEntriesRequest = "AppendEntriesRequest"\n'
    '    AppendEntriesResponse = "AppendEntriesResponse"\n')


def write_cfg(path, servers="s1, s2", extra=""):
    path.write_text("SPECIFICATION Spec\nINVARIANT NoTwoLeaders\n"
                    + extra + _CFG_CONSTANTS % servers)
    return str(path)


def run_cli(*argv):
    buf = io.StringIO()
    with redirect_stdout(buf):
        code = cli.main(list(argv))
    return code, buf.getvalue()


def test_cli_ref_engine_pass():
    code, out = run_cli(REF_CFG, "--engine", "ref", "--spec", "election",
                        "--max-term", "2", "--max-log", "0",
                        "--max-msgs", "1", "--coverage")
    assert code == cli.EXIT_OK
    assert "No error has been found" in out
    m = re.search(r"(\d+) distinct states found, diameter (\d+)", out)
    assert m, out
    # same numbers the engines' parity tests pin for this config
    cc = CheckConfig(bounds=Bounds(n_servers=3, n_values=2, max_term=2,
                                   max_log=0, max_msgs=1),
                     spec="election", invariants=("NoTwoLeaders",))
    ref = refbfs.check(cc)
    assert (int(m.group(1)), int(m.group(2))) == (ref.n_states, ref.diameter)
    assert "BecomeLeader" in out          # --coverage section


def test_cli_device_engine_pass():
    code, out = run_cli(REF_CFG, "--engine", "device", "--cpu",
                        "--spec", "election", "--max-term", "2",
                        "--max-log", "0", "--max-msgs", "1",
                        "--cap", str(1 << 18), "--chunk", "256")
    assert code == cli.EXIT_OK and "No error has been found" in out


def test_cli_bad_cfg_and_bad_invariant(tmp_path):
    code, _ = run_cli(str(tmp_path / "missing.cfg"))
    assert code == cli.EXIT_ERROR
    bad = tmp_path / "bad.cfg"
    bad.write_text("SPECIFICATION Spec\nINVARIANT NoSuchThing\nCONSTANTS\n"
                   "    Server = {s1}\n    Value = {v1}\n")
    code, _ = run_cli(str(bad))
    assert code == cli.EXIT_ERROR


def test_cli_capacity_error_is_loud(tmp_path):
    code, _ = run_cli(REF_CFG, "--engine", "device", "--cpu",
                      "--spec", "election", "--max-term", "2",
                      "--max-log", "0", "--max-msgs", "1",
                      "--cap", "512", "--chunk", "64")
    assert code == cli.EXIT_ERROR


def bag(*ms):
    return tuple(sorted((m, 1) for m in ms))


@pytest.fixture(scope="module")
def seeded_violation():
    """The seeded NaiveNoTwoLeaders violation from the engine tests."""
    bounds = Bounds(n_servers=3, n_values=1, max_term=3, max_log=0,
                    max_msgs=4, max_dup=1)
    cfg = CheckConfig(bounds=bounds, spec="election",
                      invariants=("NaiveNoTwoLeaders",), chunk=256)
    start = interp.init_state(bounds)._replace(
        role=(S.LEADER, S.FOLLOWER, S.CANDIDATE),
        term=(2, 3, 3), votedFor=(1, 3, 0),
        vGrant=(0b011, 0, 0b100),
        msgs=bag(mb.rv_response(3, 1, 1, 2)))
    res = refbfs.check(cfg, init_override=start)
    assert res.violation is not None
    return res.violation, bounds


def test_render_trace_tlc_style(seeded_violation):
    violation, bounds = seeded_violation
    text = render.render_trace(violation, bounds)
    assert "Error: Invariant NaiveNoTwoLeaders is violated." in text
    assert "State 1: <Initial predicate>" in text
    # every subsequent step names its action
    n_states = len(violation.trace)
    for k in range(2, n_states + 1):
        assert f"State {k}: <" in text
    # TLA-style variable conjunctions with reference variable names
    for var in ("messages", "currentTerm", "state", "votedFor", "log",
                "commitIndex", "votesResponded", "votesGranted",
                "nextIndex", "matchIndex"):
        assert f"/\\ {var} = " in text
    # the final state really shows two leaders
    assert text.count("Leader") >= 2


def test_render_messages_have_schema_fields(seeded_violation):
    violation, bounds = seeded_violation
    text = render.render_trace(violation, bounds)
    assert "mtype |-> RequestVoteResponse" in text
    assert "mvoteGranted |-> TRUE" in text


def test_tla_export_structure(tmp_path):
    bounds = Bounds(n_servers=3, n_values=2, max_term=3, max_log=2,
                    max_msgs=4, max_dup=1)
    tla, cfgp = tla_export.export(str(tmp_path), bounds,
                                  ("NoTwoLeaders", "LogMatching"))
    mod = open(tla).read()
    assert mod.startswith("---------------------------- MODULE MCraft ")
    assert "EXTENDS raft" in mod
    assert "NoTwoLeaders ==" in mod and "LogMatching ==" in mod
    assert "currentTerm[i] <= 3" in mod and "Len(log[i]) <= 2" in mod
    assert "Cardinality(DOMAIN messages) <= 4" in mod
    assert "ParityView" in mod and "StripMsg" in mod
    assert mod.rstrip().endswith("=" * 77)

    # cfg round-trips through our own byte-compatible parser
    cfg = parse_cfg(open(cfgp).read())
    assert cfg.specification == "Spec"
    assert cfg.invariants == ["NoTwoLeaders", "LogMatching"]
    assert cfg.constraints == ["StateConstraint"]
    assert cfg.server_names() == ["s1", "s2", "s3"]
    assert cfg.value_names() == ["v1", "v2"]
    assert cfg.constants["Follower"] == "Follower"


def test_tla_export_unknown_invariant(tmp_path):
    with pytest.raises(ValueError, match="no TLA\\+ export"):
        tla_export.emit_module(Bounds(), ("NotAnInvariant",))


def test_cli_liveness_property_stanza(tmp_path):
    """cfg PROPERTY stanza drives liveness; refuted -> TLC exit 13."""
    cfgp = write_cfg(tmp_path / "live.cfg",
                     extra="PROPERTY EventuallyLeader\n")
    code, out = run_cli(cfgp, "--engine", "ref", "--spec", "full",
                        "--max-term", "2", "--max-log", "1",
                        "--max-msgs", "2", "--wf", "Next", "--no-trace")
    assert code == 13
    assert "Property EventuallyLeader is violated" in out
    # satisfied on the election subset under the same fairness
    code2, out2 = run_cli(cfgp, "--engine", "ref", "--spec",
                          "election", "--max-term", "2", "--max-log", "0",
                          "--max-msgs", "2", "--wf", "Next")
    assert code2 == cli.EXIT_OK
    assert "Property EventuallyLeader is satisfied" in out2


def test_cli_symmetry_flag(tmp_path):
    cfgp = write_cfg(tmp_path / "sym.cfg")
    args = (cfgp, "--engine", "ref", "--spec", "election",
            "--max-term", "2", "--max-log", "0", "--max-msgs", "2")
    code, out = run_cli(*args, "--symmetry")
    assert code == cli.EXIT_OK
    assert "Symmetry: Server permutations" in out
    m = re.search(r"(\d+) distinct states found", out)
    assert m, out
    assert int(m.group(1)) == 1514          # orbits of the 3014-state space


def test_cli_faithful_mode(tmp_path):
    """--faithful carries history state; *Hist invariants resolve; the TLC
    twin drops the ParityView (TLC fingerprints full states, as we do)."""
    cfg = write_cfg(tmp_path / "h.cfg",
                    extra="INVARIANTS ElectionSafetyHist "
                          "AllLogsPrefixClosed\n")
    out_tlc = tmp_path / "tlc"
    code, out = run_cli(cfg, "--engine", "ref", "--faithful",
                        "--max-term", "2", "--max-log", "1",
                        "--max-msgs", "2", "--emit-tlc", str(out_tlc))
    assert code == cli.EXIT_OK
    assert "Faithful mode" in out
    # 2s/1v full-spec faithful count (vs 48041-state... parity run is v=1:
    # both pinned by refbfs in tests/test_history.py)
    m = re.search(r"(\d+) distinct states found, diameter (\d+)", out)
    assert m and int(m.group(2)) == 32
    mod = open(out_tlc / "MCraft.tla").read()
    assert "ElectionSafetyHist ==" in mod and "AllLogsPrefixClosed ==" in mod
    assert "ParityView" not in mod
    cfgp = parse_cfg(open(out_tlc / "MCraft.cfg").read())
    assert cfgp.view is None
    assert cfgp.constraints == ["StateConstraint"]


def test_cli_faithful_required_for_hist_invariants(tmp_path):
    cfg = write_cfg(tmp_path / "h2.cfg",
                    extra="INVARIANT ElectionSafetyHist\n")
    code, _out = run_cli(cfg, "--engine", "ref")
    assert code == cli.EXIT_ERROR


def test_cli_faithful_rejects_parity_view(tmp_path):
    """A parity-emitted cfg (VIEW ParityView) contradicts --faithful."""
    cfg = write_cfg(tmp_path / "v.cfg", extra="VIEW ParityView\n")
    tiny = ("--spec", "election", "--max-term", "2", "--max-log", "0",
            "--max-msgs", "1")
    code, _ = run_cli(cfg, "--engine", "ref", *tiny)   # parity: accepted
    assert code == cli.EXIT_OK
    code, _ = run_cli(cfg, "--engine", "ref", "--faithful", *tiny)
    assert code == cli.EXIT_ERROR


def test_cli_init_next_stanzas(tmp_path):
    """INIT/NEXT-style configs: the spec's own operator names pass, any
    other name is rejected (it would silently run a different model)."""
    tiny = ("--spec", "election", "--max-term", "2", "--max-log", "0",
            "--max-msgs", "1")
    template = open(write_cfg(tmp_path / "t.cfg")).read()
    (tmp_path / "a.cfg").write_text(
        template.replace("SPECIFICATION Spec", "INIT Init\nNEXT Next"))
    code, _ = run_cli(str(tmp_path / "a.cfg"), "--engine", "ref", *tiny)
    assert code == cli.EXIT_OK
    (tmp_path / "b.cfg").write_text(
        template.replace("SPECIFICATION Spec", "NEXT LiveNext"))
    code, _ = run_cli(str(tmp_path / "b.cfg"), "--engine", "ref", *tiny)
    assert code == cli.EXIT_ERROR


@pytest.mark.parametrize("argv, said", [
    (("--engine", "paged"), "invalid choice: 'paged'"),
    (("--engine", "streamed"), "invalid choice: 'streamed'"),
    (("--engine", "pagedshard"), "invalid choice: 'pagedshard'"),
    (("--ring", "4096"), "unrecognized arguments: --ring 4096"),
])
def test_cli_refuses_a_removed_engine_or_flag(argv, said, tmp_path, capsys):
    """The three engines removed in PR 46 and their ``--ring`` are unknown
    to argparse like any other word: exit 2, its own message, no alias."""
    cfg = write_cfg(tmp_path / "e.cfg")
    with pytest.raises(SystemExit) as e:
        cli.main([cfg, *argv])
    assert e.value.code == 2
    assert said in capsys.readouterr().err


def test_cli_ddd_engine(tmp_path):
    """The DDD engine runs end-to-end from the CLI with the standard
    report and exit code."""
    cfg = write_cfg(tmp_path / "e.cfg")
    code, out = run_cli(cfg, "--engine", "ddd", "--spec", "election",
                        "--max-term", "2", "--max-log", "0",
                        "--max-msgs", "2", "--chunk", "64",
                        "--cap", "65536")
    assert code == 0 and "3014 distinct states" in out


def test_cli_ddd_routed(tmp_path):
    """--route K drives the EP-routed step from the CLI; counts match
    the dense run."""
    cfg = write_cfg(tmp_path / "e.cfg")
    code, out = run_cli(cfg, "--engine", "ddd", "--spec", "election",
                        "--max-term", "2", "--max-log", "0",
                        "--max-msgs", "2", "--chunk", "64",
                        "--cap", "65536", "--route", "704")
    assert code == 0 and "3014 distinct states" in out


def test_cli_reshard(tmp_path):
    """--reshard-to rewrites a shard checkpoint for a new mesh size from
    the CLI; the resumed search finishes with identical counts."""
    cfg = write_cfg(tmp_path / "e.cfg")
    ck2 = str(tmp_path / "m2.ckpt")
    code, out = run_cli(cfg, "--engine", "shard", "--spec", "election",
                        "--max-term", "2", "--max-log", "0",
                        "--max-msgs", "2", "--chunk", "64",
                        "--cap", "4096", "--levels", "64",
                        "--devices", "2", "--checkpoint", ck2,
                        "--checkpoint-every", "0", "--seg-chunks", "8")
    assert code == 0 and "3014 distinct states" in out
    ck4 = str(tmp_path / "m4.ckpt")
    code, out = run_cli(cfg, "--engine", "shard", "--spec", "election",
                        "--max-term", "2", "--max-log", "0",
                        "--max-msgs", "2", "--chunk", "64",
                        "--cap", "4096", "--levels", "64",
                        "--reshard-to", "4", "--resume", ck2,
                        "--checkpoint", ck4)
    assert code == 0 and "resharded 2 -> 4 devices" in out
    code, out = run_cli(cfg, "--engine", "shard", "--spec", "election",
                        "--max-term", "2", "--max-log", "0",
                        "--max-msgs", "2", "--chunk", "64",
                        "--cap", "4096", "--levels", "64",
                        "--devices", "4", "--resume", ck4)
    assert code == 0 and "3014 distinct states" in out
    # misuse is a clean error, not a traceback
    code, _ = run_cli(cfg, "--engine", "shard", "--reshard-to", "4")
    assert code != 0
