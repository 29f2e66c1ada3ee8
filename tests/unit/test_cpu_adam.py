"""CPU-Adam / op_builder / ZeRO-Offload tests (mirror reference
tests/unit/test_cpu_adam.py numeric parity + tests/perf/adam_test.py shape,
plus offload engine integration).
"""

import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from deepspeed_tpu.op_builder import ALL_OPS, CPUAdamBuilder, UtilsBuilder
from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam


def _ref_adam(params, grads, m, v, step, lr, beta1=0.9, beta2=0.999,
              eps=1e-8, wd=0.0, adamw=True, bias_correction=True):
    """Plain numpy Adam for cross-checking the C++ kernel."""
    g = grads.copy()
    if not adamw and wd > 0:
        g = g + wd * params
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    if bias_correction:
        bc1 = 1 - beta1 ** step
        bc2s = np.sqrt(1 - beta2 ** step)
    else:
        bc1, bc2s = 1.0, 1.0
    upd = (m / bc1) / (np.sqrt(v) / bc2s + eps)
    if adamw and wd > 0:
        upd = upd + wd * params
    return params - lr * upd, m, v


def test_builder_registry_covers_reference_ops():
    # reference op_builder/__init__.py:12-21
    for op in ("cpu_adam", "fused_adam", "fused_lamb", "transformer",
               "stochastic_transformer", "sparse_attn", "utils"):
        assert op in ALL_OPS


def test_cpu_adam_builder_compiles():
    builder = CPUAdamBuilder()
    assert builder.is_compatible(), builder.compatible_reason()
    lib = builder.load()
    assert hasattr(lib, "ds_adam_step")
    # cache hit: second load returns the same object
    assert builder.load() is lib


@pytest.mark.parametrize("n", [64, 1000, 4099])
@pytest.mark.parametrize("adamw", [True, False])
def test_cpu_adam_matches_numpy(n, adamw):
    rng = np.random.RandomState(n)
    p = rng.randn(n).astype(np.float32)
    g = rng.randn(n).astype(np.float32)
    m = np.zeros(n, np.float32)
    v = np.zeros(n, np.float32)
    opt = DeepSpeedCPUAdam(lr=1e-2, weight_decay=0.01, adamw_mode=adamw)
    assert opt.ds_opt_adam is not None, "C++ op should build in this image"

    p_ref, m_ref, v_ref = p.copy(), m.copy(), v.copy()
    for step in range(1, 4):
        opt.step_flat(p, g, m, v, step=step)
        p_ref, m_ref, v_ref = _ref_adam(p_ref, g, m_ref, v_ref, step,
                                        lr=1e-2, wd=0.01, adamw=adamw)
    np.testing.assert_allclose(p, p_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(m, m_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(v, v_ref, rtol=1e-5, atol=1e-6)


def test_cpu_adam_fused_bf16_copy():
    n = 256
    rng = np.random.RandomState(0)
    p = rng.randn(n).astype(np.float32)
    g = rng.randn(n).astype(np.float32)
    m = np.zeros(n, np.float32)
    v = np.zeros(n, np.float32)
    out = np.zeros(n, np.uint16)
    opt = DeepSpeedCPUAdam(lr=1e-2)
    opt.step_flat(p, g, m, v, step=1, bf16_out=out)
    # out is bf16(p): reinterpret and compare with ~1e-2 relative tolerance
    recon = (out.astype(np.uint32) << 16).view(np.float32)
    np.testing.assert_allclose(recon, p, rtol=1e-2, atol=1e-3)


def test_cpu_adam_norm_and_scale():
    opt = DeepSpeedCPUAdam()
    x = np.arange(8, dtype=np.float32)
    assert abs(opt.l2_norm(x) - np.linalg.norm(x)) < 1e-4
    opt.scale_(x, 0.5)
    np.testing.assert_allclose(x, np.arange(8) * 0.5)


def test_utils_flatten_unflatten():
    lib = UtilsBuilder().load()
    rng = np.random.RandomState(1)
    tensors = [rng.randn(s).astype(np.float32) for s in (3, 7, 16)]
    total = sum(t.size for t in tensors)
    flat = np.empty(total, np.float32)
    UtilsBuilder.flatten_into(lib, flat, tensors)
    np.testing.assert_array_equal(flat, np.concatenate(tensors))

    outs = [np.zeros_like(t) for t in tensors]
    UtilsBuilder.unflatten_into(lib, outs, flat)
    for o, t in zip(outs, tensors):
        np.testing.assert_array_equal(o, t)


def _make_offload_engine(tmpdir=None, gas=1):
    from deepspeed_tpu.models.simple import SimpleModel
    return deepspeed.initialize(
        model=SimpleModel(hidden_dim=8),
        config_params={
            "train_batch_size": 8 * gas,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2, "cpu_offload": True},
        })[0]


def test_engine_selects_cpu_adam_for_offload():
    engine = _make_offload_engine()
    assert isinstance(engine.optimizer, DeepSpeedCPUAdam)
    assert engine.zero_cpu_offload()


def test_offload_staging_uses_flatten_op():
    """The staging pack in _offload_step consumes the C++ ds_flatten op
    (the op must have a runtime consumer)."""
    engine = _make_offload_engine()
    rng = np.random.RandomState(0)
    x = rng.randn(8, 8).astype(np.float32)
    y = rng.randint(0, 8, size=(8,))
    try:
        UtilsBuilder().load()
    except Exception as e:  # toolchain-less host: numpy fallback is correct
        pytest.skip("utils op cannot build here ({})".format(e))
    loss = engine(x, y)
    engine.backward(loss)
    engine.step()
    # The lazy loader ran during stage(); the op built above, so the
    # engine must have taken the C++ pack path, not the fallback.
    assert getattr(engine, "_host_pack_lib_cache", None) is not None
    assert not getattr(engine, "_host_pack_failed", False)


def test_offload_trains_and_matches_device_adam():
    """Offload path loss trajectory ~= device FusedAdam trajectory."""
    from deepspeed_tpu.models.simple import SimpleModel
    rng = np.random.RandomState(0)
    x = rng.randn(8, 8).astype(np.float32)
    y = rng.randint(0, 8, size=(8,))

    def run(cpu_offload):
        cfg = {
            "train_batch_size": 8,
            "optimizer": {"type": "Adam",
                          "params": {"lr": 1e-2, "betas": [0.9, 0.999],
                                     "eps": 1e-8}},
        }
        if cpu_offload:
            cfg["bf16"] = {"enabled": True}
            cfg["zero_optimization"] = {"stage": 2, "cpu_offload": True}
        engine, _, _, _ = deepspeed.initialize(
            model=SimpleModel(hidden_dim=8), config_params=cfg)
        losses = []
        for _ in range(6):
            loss = engine(x, y)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        return losses

    host = run(True)
    device = run(False)
    assert host[-1] < host[0]
    # same trajectory modulo fp32-vs-fused rounding and bias-correction config
    np.testing.assert_allclose(host, device, rtol=0.05, atol=0.02)


def _run_offload(stream, steps=6, clip=0.0):
    import jax

    from deepspeed_tpu.models.simple import SimpleModel
    from deepspeed_tpu.parallel import mesh as mesh_lib

    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2, "cpu_offload": True,
                              "stream_gradients": stream},
    }
    if clip:
        cfg["gradient_clipping"] = clip
    # Streaming targets single-chip capacity: pin a 1-device mesh.
    mesh = mesh_lib.build_mesh(devices=jax.devices()[:1])
    engine, _, _, _ = deepspeed.initialize(
        model=SimpleModel(hidden_dim=8), mesh=mesh, config_params=cfg)
    rng = np.random.RandomState(0)
    x = rng.randn(8, 8).astype(np.float32)
    y = rng.randint(0, 8, size=(8,))
    losses = []
    for _ in range(steps):
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("clip", [0.0, 0.5])
def test_stream_gradients_matches_materialized_offload(clip):
    """The grad-streaming offload tier (io_callback during backward,
    donated params) must train the same trajectory as the materialized
    offload path — same host Adam, same clipping, different transport."""
    base = _run_offload(stream=False, clip=clip)
    stream = _run_offload(stream=True, clip=clip)
    np.testing.assert_allclose(stream, base, rtol=2e-3, atol=1e-3)
    assert stream[-1] < stream[0]


def test_stream_gradients_fp16_overflow_skip_recovers():
    """fp16 + stream_gradients: an overflow-skipped step must restore the
    donated device params from the host master — the next forward would
    otherwise feed deleted arrays into jit."""
    import jax

    from deepspeed_tpu.models.simple import SimpleModel
    from deepspeed_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.build_mesh(devices=jax.devices()[:1])
    engine, _, _, _ = deepspeed.initialize(
        model=SimpleModel(hidden_dim=8), mesh=mesh,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "fp16": {"enabled": True, "loss_scale": 0,
                     "initial_scale_power": 32},
            "zero_optimization": {"stage": 2, "cpu_offload": True,
                                  "stream_gradients": True},
        })
    rng = np.random.RandomState(0)
    x = rng.randn(8, 8).astype(np.float32)
    y = rng.randint(0, 8, size=(8,))
    # Scale 2^32 on fp16 grads overflows -> the first steps skip.
    loss = engine(x, y)
    engine.backward(loss)
    engine.step()
    assert engine.skipped_steps >= 1
    # The next forward/step must run on restored params, then converge
    # once the scaler has backed off.
    for _ in range(40):
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    assert engine.skipped_steps < 41
    assert np.isfinite(float(loss))


def test_offload_timing_reports_phase_timeline():
    """_offload_step must publish its chunk timeline (stage/adam/upload
    sums, wall, overlap ratio) — the observability the double-buffered
    staging is judged by."""
    engine = _make_offload_engine()
    assert engine.offload_timing() is None  # nothing ran yet
    rng = np.random.RandomState(0)
    x = rng.randn(8, 8).astype(np.float32)
    y = rng.randint(0, 8, size=(8,))
    loss = engine(x, y)
    engine.backward(loss)
    engine.step()
    t = engine.offload_timing()
    assert t is not None and t["chunks"] >= 1
    assert t["wall_s"] > 0
    for k in ("stage_s", "adam_s", "upload_s"):
        assert t[k] >= 0
    assert t["overlap_ratio"] > 0


def test_offload_checkpoint_roundtrip(tmp_path):
    from deepspeed_tpu.models.simple import SimpleModel
    rng = np.random.RandomState(1)
    x = rng.randn(8, 8).astype(np.float32)
    y = rng.randint(0, 8, size=(8,))
    engine = _make_offload_engine()
    for _ in range(3):
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    engine.save_checkpoint(str(tmp_path))
    m_before = engine._offload["m"].copy()

    engine2 = _make_offload_engine()
    loss0 = engine2(x, y)  # init params lazily before load
    engine2.load_checkpoint(str(tmp_path))
    assert int(engine2.opt_state["step"]) == 3
    np.testing.assert_allclose(engine2._offload["m"], m_before, rtol=1e-6)
    # resume training
    loss = engine2(x, y)
    engine2.backward(loss)
    engine2.step()
    assert int(engine2.opt_state["step"]) == 4


def test_offload_checkpoint_preserves_fp32_master(tmp_path):
    """Resume must keep FULL master precision (reference saves
    single_partition_of_fp32_groups, stage2.py:1704): a save/load round-trip
    restores the fp32 master bitwise, NOT a bf16-truncated rebuild from the
    module params."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.simple import SimpleModel
    rng = np.random.RandomState(2)
    x = rng.randn(8, 8).astype(np.float32)
    y = rng.randint(0, 8, size=(8,))
    engine = _make_offload_engine()
    for _ in range(3):
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    master_before = engine._offload["master"].copy()
    # the master must hold precision a bf16 round-trip would destroy
    bf16_roundtrip = np.asarray(master_before.astype(jnp.bfloat16),
                                dtype=np.float32)
    assert not np.array_equal(master_before, bf16_roundtrip)
    engine.save_checkpoint(str(tmp_path))

    engine2 = _make_offload_engine()
    engine2(x, y)
    engine2.load_checkpoint(str(tmp_path))
    np.testing.assert_array_equal(engine2._offload["master"], master_before)
