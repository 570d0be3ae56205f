"""Test fixture: run the suite on a virtual 8-device CPU mesh.

Role of the reference's localhost fake-cluster test mechanism
(``test_dist_base.py:1041`` spawning trainers with env-injected topology):
instead of subprocesses, JAX gives us N virtual devices in one process via
``--xla_force_host_platform_device_count``, so every multi-chip sharding test
runs single-process on CPU. Behaviour on real chips is exercised by
chip_smoke.py and benchmarks/run.py on the chip.

This file must set the env vars BEFORE jax is imported anywhere.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# Force CPU for tests even when the session env points at an accelerator:
# sharding tests need 8 virtual devices.
os.environ["JAX_PLATFORMS"] = "cpu"
# Keep CPU feature autotuning quiet/deterministic in CI.
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs[:8]
