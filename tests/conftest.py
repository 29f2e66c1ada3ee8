"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference simulates multi-GPU with forked torch.multiprocessing workers
(tests/unit/common.py:16-106). The TPU-native equivalent is a single-process
multi-device mesh: XLA's host platform exposes 8 virtual CPU devices, so every
sharding/collective path (ZeRO, pipeline, tensor-parallel) compiles and runs
exactly as it would on an 8-chip slice — no processes to fork, no hangs to
watch for.

Env vars must be set before jax is imported anywhere; conftest import time is
the earliest hook pytest gives us.
"""

import os

# Set here as well as by the tier-1 command, so that a bare `pytest` on a
# machine with a chip still runs on the CPU and leaves the chip alone; the
# config is updated too in case a pytest plugin imported jax first.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.default_backend() == "cpu", \
    "tests must run on the virtual CPU mesh, got {}".format(jax.default_backend())

# The persistent compilation cache stays OFF inside pytest, whatever
# JAX_COMPILATION_CACHE_DIR says (the root scripts place it:
# deepspeed_tpu/utils/compile_cache.py). Tests compile what they test, and
# tests/unit/test_chip_compile.py compiles for a described chip, whose
# executables cannot be read back here.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture
def eight_devices():
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    return devices


def pytest_configure(config):
    config.addinivalue_line("markers", "tpu_only: requires real TPU hardware")
    config.addinivalue_line(
        "markers",
        "slow: multi-minute model-tier training runs, excluded from the "
        "tier-1 sweep (-m 'not slow'); run tests/model explicitly")
