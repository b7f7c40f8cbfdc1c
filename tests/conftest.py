"""Test env: CPU backend with 8 virtual devices (the fake-mesh layer for
distributed logic tests — SURVEY.md §4 implication (c)).

The tests run on the CPU whatever the machine holds: the platform is forced
(env default AND jax config) before any backend initializes, so a test
process never claims a chip — one process holds a chip at a time, and the
only thing that runs there is ``chip_smoke.py``.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# golden tests compare against float64 numpy: pin full-precision matmuls
# (the library default stays fast/bf16 on TPU)
jax.config.update("jax_default_matmul_precision", "highest")


import pytest


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    """Each test starts without an installed mesh / comm groups (tests that
    need one call init_hybrid_mesh themselves)."""
    yield
    from paddle_tpu.distributed import collective, fleet, mesh as mesh_mod

    mesh_mod._global_mesh = None
    collective._default_group = None
    collective._groups.clear()
    fleet._state = fleet._FleetState()
