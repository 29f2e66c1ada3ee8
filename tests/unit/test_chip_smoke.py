"""chip_smoke.py and what it rests on, at a size the CPU can afford.

The phases run here at ``TINY`` size with Pallas in interpret mode (so no
kernel presence is asked for: ``expect_kernels=False``); the chip runs them
at ``FULL`` size. Beside them: the script fails without a TPU, the compile
cache is placed from outside, peaks are keyed by device kind, the flash
backward is chosen from shapes alone, the topology solver's failure raises,
and the launcher's parent never takes the chip.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.parallel.mesh import build_mesh

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("ds_chip_smoke", _SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ phases

def test_train_phase_tiny(smoke):
    r = smoke.train_phase(smoke.TINY, build_mesh(devices=jax.devices()[:1]),
                          expect_kernels=False)
    assert len(r["losses"]) == 1 + smoke.TINY.train_steps + 2
    assert r["losses"][-1] < r["losses"][0]
    assert abs(r["losses"][0] - r["reference_first_loss"]) <= smoke.LOSS_TOL
    json.dumps(r)


def test_layer_phase_tiny(smoke):
    r = smoke.layer_phase(smoke.TINY, expect_kernels=False)
    assert abs(r["dropout_kept_share"] - 0.9) <= 0.01
    json.dumps(r)


def test_serve_phase_tiny(smoke):
    r = smoke.serve_phase(smoke.TINY, expect_kernels=False)
    assert r["requests"] == smoke.TINY.n_requests
    assert r["compile_count"] == 1
    assert max(r["max_token_margin"]) <= smoke.TOKEN_MARGIN_TOL
    json.dumps(r)


def test_zero2_phase_on_four_of_eight_devices(smoke, eight_devices):
    r = smoke.zero2_phase(smoke.TINY, build_mesh(devices=eight_devices[:4]),
                          expect_kernels=False)
    assert r["moments_sharded_4way"] > 0
    assert np.allclose(r["losses"], r["one_device_losses"],
                       atol=smoke.LOSS_TOL)
    assert r["collectives"]
    json.dumps(r)


def test_fleet_phase_on_four_devices(smoke, eight_devices):
    r = smoke.fleet_phase(smoke.TINY)
    assert len(set(r["replica_devices"])) == 4
    json.dumps(r)


def test_failed_phase_fails_the_run(smoke, capsys):
    """A raising phase is reported on its own line and turns the verdict;
    the phases after it still run."""
    def boom():
        raise AssertionError("kernel gave way to a reference")

    ok = smoke._run_phases([("a", boom), ("b", lambda: {"x": 1})])
    lines = [json.loads(line)
             for line in capsys.readouterr().out.strip().splitlines()]
    assert ok is False
    assert lines[0] == {"phase": "a", "ok": False,
                        "error": "AssertionError: kernel gave way to a "
                                 "reference"}
    assert lines[1]["phase"] == "b" and lines[1]["ok"] and lines[1]["x"] == 1


def test_chip_smoke_fails_without_a_tpu():
    """On the CPU the script exits non-zero in seconds, its last line says
    ok: false, and no 355M phase starts."""
    r = subprocess.run([sys.executable, _SMOKE], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert not any('"phase"' in line for line in lines)


# ----------------------------------------------------------- compile cache

@pytest.fixture()
def cache_config():
    """tests/conftest.py wants the cache off inside pytest: put the config
    back whatever the helper did."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_the_environment(monkeypatch, cache_config):
    from deepspeed_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/somewhere/else")
    assert compile_cache.place_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    from deepspeed_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    first = compile_cache.place_compile_cache()
    assert first == os.path.join(_REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.place_compile_cache() == first


# ------------------------------------------------------------------- peaks

def test_peaks_are_keyed_by_device_kind():
    from deepspeed_tpu.telemetry.xray import DEVICE_PEAKS, device_peaks

    row = device_peaks("TPU v5 lite")
    assert row is DEVICE_PEAKS["TPU v5 lite"]
    assert row["flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "tpu", "cpu"])
def test_unknown_device_kind_raises(kind):
    from deepspeed_tpu.telemetry.xray import device_peaks

    with pytest.raises(KeyError, match="no peaks row"):
        device_peaks(kind)


# ---------------------------------------------------- no hidden fallbacks

@pytest.mark.parametrize("t_kv,d,dtype,want", [
    (1024, 64, jnp.bfloat16, "fused"),     # 355M training: 1 MB resident
    (4096, 64, jnp.bfloat16, "fused"),     # BERT-large sparse: 4 MB
    (8192, 128, jnp.bfloat16, "split"),    # 16 MB: over the budget
    (4096, 128, jnp.float32, "split"),     # fp32 doubles it: 12 MB
])
def test_bwd_mode_decides_from_shapes_alone(t_kv, d, dtype, want,
                                            monkeypatch):
    from deepspeed_tpu.ops.transformer.kernels import attention

    monkeypatch.delenv("DS_TPU_FLASH_BWD", raising=False)
    # Nothing is run on a device to decide: no backend query at all.
    monkeypatch.setattr(jax, "default_backend", lambda: pytest.fail(
        "_bwd_mode asked for the backend"))
    assert attention._bwd_mode(t_kv, d, dtype) == want
    assert not hasattr(attention, "_fused_bwd_supported")


def test_arrange_reraises(monkeypatch):
    """The topology solver's failure is the caller's failure: no flat
    reshape behind a warning."""
    import jax.experimental.mesh_utils

    from deepspeed_tpu.parallel import mesh as mesh_lib

    class FakeTpu:
        platform = "tpu"
        slice_index = 0

    def broken(shape, devices=None):
        raise RuntimeError("no topology")

    monkeypatch.setattr(jax.experimental.mesh_utils, "create_device_mesh",
                        broken)
    with pytest.raises(RuntimeError, match="no topology"):
        mesh_lib._arrange([FakeTpu() for _ in range(4)], (1, 4, 1, 1),
                          explicit=False)


def test_interpret_mode_is_decided_in_one_place():
    """Every kernel file launches through ops.pallas_mode: kernel_call
    names the kernel and decides interpret mode, and no file passes an
    ``interpret=`` of its own."""
    from deepspeed_tpu.ops import pallas_mode

    assert pallas_mode.interpret() is True   # the CPU test mesh
    ops = os.path.join(_REPO, "deepspeed_tpu", "ops")
    kernels = [os.path.join(ops, "sparse_attention", "kernels.py")] + [
        os.path.join(ops, "transformer", "kernels", name)
        for name in ("attention.py", "decode_attention.py", "dropout.py",
                     "gelu.py", "layer_norm.py", "softmax.py")]
    for path in kernels:
        with open(path) as f:
            text = f.read()
        assert "pallas_mode.kernel_call(" in text, path
        assert "pl.pallas_call(" not in text, path
        assert "interpret=" not in text, path
        assert "def _interpret" not in text, path


# --------------------------------------------------------- one process/chip

def test_launcher_parents_never_take_the_chip():
    """launch.py and runner.py are parents of the user's script. Importing
    them initialises no JAX backend, so the child can have the chip. (The
    package's __init__ imports jax, so "jax" is in sys.modules; what holds
    a chip is a backend, and none is made.)"""
    code = ("import deepspeed_tpu.launcher.launch, "
            "deepspeed_tpu.launcher.runner\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   cwd=_REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
