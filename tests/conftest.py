"""Test harness config: force an 8-device virtual CPU mesh.

The checker's "multi-node without a cluster" story (SURVEY §4.4): real TPU
pods are not available under test, so JAX's host-platform device emulation
exercises the sharded dedup/all-to-all paths single-host.  Must run before
jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"     # inherited by every child a test spawns

import jax  # noqa: E402

# Also through the live config, in case something imported jax before this
# file ran; then open the backend NOW so the 8 virtual devices are fixed
# before any test (an in-process ``check.main([... "--cpu", "--devices",
# "2"])`` would otherwise shrink the mesh for every test after it).
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
assert len(jax.devices()) == 8
