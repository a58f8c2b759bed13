"""Which device a process computes on — decided once, and said out loud.

A TPU chip belongs to one process at a time, and JAX falls back to the
CPU without failing when it finds no accelerator.  Both facts used to be
handled (or not) wherever a CLI happened to touch JAX; they live here:

- :func:`select_device` — the no-fallback rule every front applies
  before its first device op: ``--cpu`` means the CPU, an explicit
  ``JAX_PLATFORMS`` means whatever it names, and with neither a TPU is
  *required* — a run that would silently land on the CPU raises
  :class:`DeviceError` naming ``--cpu`` instead.
- :func:`device_info` / :func:`describe` — platform, device kind and
  device count as JAX reports them; every run states them once (CLI
  header, ``run_start.host``).
- :func:`probe_devices` — the same dict taken by a short-lived child,
  for supervising parents (campaign supervisor, serve pool) that must
  learn the device count WITHOUT opening the device their children need.
- :func:`chip_env` — the libtpu environment that binds one worker
  process to one chip of a multi-chip host.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

# Every DeviceError message starts with this, and so does the line a CLI
# prints for one: serve/supervise.classify_death keys on it to tell "the
# worker could not open its backend" (environment) from "the job killed
# the worker" (poison).
UNAVAILABLE = "device unavailable:"


class DeviceError(RuntimeError):
    """No usable device under the no-fallback rule."""

    def __init__(self, detail: str):
        super().__init__(f"{UNAVAILABLE} {detail}")


def backends_initialized() -> bool:
    """True once this process has opened a JAX backend (and so holds the
    chip).  False when jax was never imported."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend.  Opens
    the backend: call only from the process that is going to compute."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def describe(info: dict) -> str:
    return f"{info['platform']} ({info['kind']}) x {info['count']}"


def select_device(cpu: bool = False, n_cpu_devices: int | None = None) -> dict:
    """Apply the no-fallback rule and return :func:`device_info`.

    ``cpu`` selects the CPU backend (``n_cpu_devices`` virtual devices
    for the sharded engines).  Otherwise an explicit ``JAX_PLATFORMS``
    is honoured as given, and with none set the default backend must be
    a TPU.  Raises :class:`DeviceError` when the backend cannot be
    opened, when ``cpu`` was asked for after another backend went live,
    or when JAX fell back to a platform nobody named.
    """
    import jax
    if cpu:
        try:
            jax.config.update("jax_platforms", "cpu")
            if n_cpu_devices:
                jax.config.update("jax_num_cpu_devices", n_cpu_devices)
        except RuntimeError:
            pass        # a backend is already live; the check below decides
    try:
        info = device_info()
    except RuntimeError as e:
        raise DeviceError(f"cannot open the JAX backend ({e}); pass --cpu "
                          "to run on the CPU") from e
    if cpu and info["platform"] != "cpu":
        raise DeviceError("--cpu requested but JAX backends are already "
                          f"initialized on {describe(info)}")
    if not cpu and not jax.config.jax_platforms \
            and info["platform"] != "tpu":
        raise DeviceError(f"no TPU found — JAX fell back to "
                          f"{describe(info)}; pass --cpu (or set "
                          "JAX_PLATFORMS) to run there on purpose")
    return info


def probe_devices(cpu: bool = False, timeout: float = 300.0) -> dict:
    """:func:`select_device` run in a child that has exited — and so
    released the device — before this returns.  The caller never imports
    jax, which is the point: a supervising parent that opened the backend
    would hold the chip its worker children need."""
    code = ("import json; from raft_tla_tpu.utils import device; "
            f"print(json.dumps(device.select_device(cpu={bool(cpu)})))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired as e:
        raise DeviceError(f"device probe timed out after {timeout:.0f}s") \
            from e
    if proc.returncode != 0:
        lines = proc.stderr.strip().splitlines()
        detail = lines[-1] if lines else f"exit {proc.returncode}"
        if UNAVAILABLE in detail:
            detail = detail.split(UNAVAILABLE, 1)[1].strip()
        raise DeviceError(f"device probe failed: {detail}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def chip_env(index: int | None) -> dict:
    """Environment for a worker bound to chip ``index`` of this host and
    no other (libtpu's one-chip-per-process settings), so N workers on an
    N-chip host each open their own device instead of fighting over all
    of them.  ``None`` binds nothing: a plain copy of this environment."""
    env = dict(os.environ)
    if index is not None:
        env.update(TPU_VISIBLE_CHIPS=str(index),
                   TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                   TPU_PROCESS_BOUNDS="1,1,1")
    return env
