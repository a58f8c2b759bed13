"""Rehearsal of chip_smoke.py on the CPU, at toy size.

chip_smoke.py is the proof that the three entry points still start on the
chip; chip time is budgeted, so every phase FUNCTION it is made of runs here
first, under ``JAX_PLATFORMS=cpu``, against the same pins scaled down: the
flagship's level table on a few seconds of window, the oracle on the
2-server toy, the real (small) counterexample, a two-job manifest, the toy
campaign under the default mesh plan, a cold and a warm process against one
cache directory, the Pallas kernels under the interpreter, and the sharded
engine on two virtual devices.  The script itself — invoked as the driver
invokes it, with no option — must refuse this sandbox.  The two bring-up
rules that need a fresh interpreter to observe (no silent CPU fallback; a
supervisor that never opens a backend) are tested here too.

Named to sort last on purpose: tier-1 is time-boxed and this file is ~2.5
minutes of child processes, so it must not push the faster suites out of the
window.  Run it by name before spending chip time:
``pytest tests/test_zz_chip_smoke.py``.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

TOY_SPACE = (("toy", cs.CFG_TOY, cs.TOY_FLAGS + ("--chunk", "32"),
              (3014, 17)),)


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    # the children keep their compile cache where the machine says: a
    # fresh directory makes the cold run cold whatever ran before
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    c = cs.Ctx(out=str(tmp_path / "out"), platform="cpu")
    c.device = {"platform": "cpu", "kind": "cpu", "count": 2}
    return c


def test_script_refuses_a_machine_without_a_chip():
    """As the driver runs it here: JAX finds no accelerator, so a
    non-zero exit and no result line — and no option to say otherwise."""
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "platform is 'cpu', not 'tpu'" in p.stdout


def test_one_failed_phase_fails_the_script(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(cs, "OUT", str(tmp_path))
    ok = lambda *a, **k: {}                              # noqa: E731

    def probe(c):
        c.device = {"platform": "tpu", "kind": "fake", "count": 1}
        return {}

    def boom(*a, **k):
        raise cs.Failed("planted")
    for name in ("phase_complete", "phase_flagship", "phase_counterexample",
                 "phase_campaign", "phase_warm", "phase_kernels",
                 "phase_multichip"):
        monkeypatch.setattr(cs, name, ok)
    monkeypatch.setattr(cs, "phase_probe", probe)
    monkeypatch.setattr(cs, "phase_serve", boom)
    assert cs.main() == 1
    out = capsys.readouterr().out
    assert "[serve] FAILED" in out and '"ok"' not in out
    monkeypatch.setattr(cs, "phase_serve", ok)
    assert cs.main() == 0
    assert capsys.readouterr().out.strip().splitlines()[-1] == \
        '{"ok": true, "device": {"platform": "tpu", "kind": "fake", ' \
        '"count": 1}}'


def test_probe_reports_device_gates_and_native(ctx):
    info = cs.phase_probe(ctx)
    assert info["device"]["platform"] == "cpu" and info["has_native"]
    assert set(info["gates"]) == {"prescan", "devdedup", "host_dedup",
                                  "prefetch"}
    tpu = cs.Ctx(out=ctx.out, platform="tpu")
    with pytest.raises(cs.Failed, match="not 'tpu'"):
        cs.phase_probe(tpu)


def test_level_table_check():
    assert cs.check_levels("t", [1, 1, 5, 16, 10], complete=False) == 4
    with pytest.raises(cs.Failed, match="level 3"):
        cs.check_levels("t", [1, 1, 5, 17, 10], complete=False)
    with pytest.raises(cs.Failed, match="partial level"):
        cs.check_levels("t", [1, 1, 5, 16, 56], complete=False)


def test_flagship_window_rehearsal(ctx):
    got = cs.phase_flagship(ctx, deadline_s=4, chunk=128, min_orbits=100)
    assert got["levels_checked"] >= 5
    with pytest.raises(cs.Failed, match="orbits <"):
        cs.phase_flagship(ctx, deadline_s=0.0, chunk=128,
                          min_orbits=10 ** 9)


def test_complete_space_then_warm_process_rehearsal(ctx):
    """Also the cache rule's last leg: a second process compiling the
    same toy step adds no entry to the cache directory (phase_warm fails
    otherwise) and is set up sooner."""
    cold = cs.phase_complete(ctx, spaces=TOY_SPACE)
    assert cold["toy"]["cache_added"] > 0
    warm = cs.phase_warm(ctx, cold, spaces=TOY_SPACE)
    assert warm["toy"]["warm_setup_s"] < warm["toy"]["cold_setup_s"]
    assert os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"])


def test_counterexample_replay_rehearsal(ctx):
    got = cs.phase_counterexample(ctx)
    assert got["trace_states"] == 19            # depth 18, as recorded
    # a doctored trace must not replay
    out = ctx.path("naive3.out")
    text = open(out).read().replace("s2 :> Candidate", "s2 :> Leader", 1)
    open(out, "w").write(text)
    with pytest.raises(cs.Failed, match="replay"):
        name, _cfg, _flags, bounds, spec, inv = cs.COUNTEREXAMPLE
        cs.run_snippet(ctx, name + ".replay", cs._REPLAY,
                       [out, json.dumps(bounds), spec, inv],
                       env=cs.cpu_env())


def test_serve_and_campaign_rehearsal(ctx):
    cs.phase_serve(ctx, jobs=(cs.SERVE_JOBS[0], cs.SERVE_JOBS[3]),
                   chunk=256)
    cs.phase_campaign(ctx)                       # the DEFAULT mesh plan


def test_kernels_rehearsal_under_the_interpreter(ctx):
    got = cs.phase_kernels(ctx, chunk=128, interpret=True)
    assert got["pallas_fp"] == {"mosaic_ok": True, "bit_equal": True}


def test_multichip_rehearsal_on_two_virtual_devices(ctx, capsys):
    got = cs.phase_multichip(ctx, n_devices=2, chunk=128, stop_at=2000,
                             extra=("--cpu",))
    assert got["devices"] == 2 and got["levels_checked"] >= 5
    ctx.device = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert cs.phase_multichip(ctx)["skipped"]
    assert "multichip: skipped (1 device)" in capsys.readouterr().out


def test_symmetric_election_with_the_prescan_ladder_off(monkeypatch):
    """The step the CHIP selects, at every |G| since PR 32: on the CPU the
    prescan ladder is on for every symmetric run, so without this tier-1
    never runs a cell's on-chip program."""
    monkeypatch.setenv("RAFT_TLA_PRESCAN", "off")
    from raft_tla_tpu.config import Bounds, CheckConfig
    from raft_tla_tpu.ddd_engine import DDDCapacities, DDDEngine
    cfg = CheckConfig(bounds=Bounds(n_servers=3, n_values=1, max_term=2,
                                    max_log=0, max_msgs=1),
                      spec="election", invariants=("NoTwoLeaders",),
                      symmetry=("Server",), chunk=256)
    got = DDDEngine(cfg, DDDCapacities(block=1 << 13, table=1 << 14,
                                       flush=1 << 14, levels=64)).check()
    assert (got.n_states, got.diameter, got.complete) == (23902, 31, True)
    assert got.violation is None


def _run_py(argv, env=None):
    return subprocess.run(argv, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)


def test_cli_states_its_device_and_refuses_a_silent_cpu_fallback(tmp_path):
    """No fallback that hides the device: with neither --cpu nor an
    explicit JAX_PLATFORMS a device engine requires a TPU and exits
    EXIT_ERROR naming --cpu (JAX alone would warn and run on the CPU);
    with either, the header states platform, kind and count."""
    cfg = tmp_path / "toy.cfg"
    cfg.write_text(cs.CFG_TOY)
    argv = [sys.executable, "-m", "raft_tla_tpu.check", str(cfg),
            "--spec", "election", "--max-term", "2", "--max-log", "0",
            "--max-msgs", "1", "--chunk", "64", "--engine"]
    bare = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    p = _run_py(argv + ["ddd"], env=bare)
    assert p.returncode == 1                        # check.EXIT_ERROR
    assert "device unavailable: no TPU found" in p.stderr
    assert "--cpu" in p.stderr and "distinct states" not in p.stdout
    p = _run_py(argv + ["ddd", "--cpu"], env=bare)
    assert p.returncode == 0
    assert "Device: cpu (cpu) x 1" in p.stdout
    assert "524 distinct states found" in p.stdout
    # the pure-Python oracle touches no device and is held to no rule
    p = _run_py(argv + ["ref"], env=bare)
    assert p.returncode == 0 and "Device:" not in p.stdout


def test_default_mesh_plan_never_opens_a_backend_in_the_supervisor(tmp_path):
    """One process per chip: with mesh_plan=None the supervisor used to
    call jax.devices() itself and then spawn the child that needs the
    device.  Now a probe child counts the devices; after a whole toy
    campaign the supervising process still holds no backend.  (A fresh
    interpreter: this pytest process opened its backend in conftest.)"""
    cfg = tmp_path / "toy.cfg"
    cfg.write_text(cs.CFG_TOY)
    code = f"""
import sys
from raft_tla_tpu.campaign import CampaignPolicy, CampaignSpec, Supervisor
spec = CampaignSpec(cfg_path={str(cfg)!r}, spec="election", window=128,
                    chunk=32, cap=1 << 14, levels=64, cpu=True,
                    options=dict(max_term=2, max_log=0, max_msgs=2))
sup = Supervisor(spec, {str(tmp_path / "camp")!r},
                 policy=CampaignPolicy(checkpoint_every_s=0.0),
                 mesh_plan=None, quiet=True)
res = sup.run()
assert (res.outcome, res.n_states, res.attempts) == ("ok", 3014, 1), res
if "jax" in sys.modules:
    from jax._src import xla_bridge
    assert not xla_bridge._backends, xla_bridge._backends
print("supervisor-off-device")
"""
    p = _run_py([sys.executable, "-c", code])
    assert p.returncode == 0, p.stderr[-2000:]
    assert "supervisor-off-device" in p.stdout
