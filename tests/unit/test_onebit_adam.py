"""1-bit Adam tests (mirror reference tests/onebitadam/test_com_reduce_*.py:
the compressed allreduce is checked against an independent numpy simulation,
plus warmup/freeze optimizer semantics and engine integration).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

import deepspeed_tpu as deepspeed
from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
from deepspeed_tpu.runtime.custom_collectives import (
    allgather_cuda, allgather_host, allgather_tpu, compressed_allreduce,
    corrected_size, gather_cuda, gather_host, gather_tpu, pack_signs,
    quantize_error_feedback, unpack_signs)
from deepspeed_tpu.runtime.fp16.onebit_adam import (OnebitAdam,
                                                    init_onebit_adam_state)


def test_pack_unpack_roundtrip_matches_numpy():
    rng = np.random.RandomState(0)
    x = rng.randn(64).astype(np.float32)
    packed = np.asarray(pack_signs(jnp.asarray(x)))
    np_packed = np.packbits(x >= 0)
    np.testing.assert_array_equal(packed, np_packed)
    unpacked = np.asarray(unpack_signs(jnp.asarray(packed)))
    np.testing.assert_array_equal(unpacked, np.where(x >= 0, 1.0, -1.0))


def test_corrected_size():
    # divisible by world_size and chunks divisible by 8
    for w in (1, 2, 4, 8):
        for n in (7, 64, 100, 1000):
            c = corrected_size(n, w)
            assert c >= n and c % w == 0 and (c // w) % 8 == 0


def _numpy_compressed_allreduce(buffers, worker_errors, server_errors):
    """Independent simulation of the reference algorithm
    (onebit_adam.py:104-233) for W workers."""
    w, n = buffers.shape
    chunk = n // w
    outs_signs = np.zeros((w, chunk))
    outs_scales = np.zeros(w)
    new_we = np.zeros_like(worker_errors)
    new_se = np.zeros_like(server_errors)
    # worker-side
    comp = buffers + worker_errors
    scales = np.linalg.norm(comp, axis=1) / np.sqrt(n)
    signs = np.where(comp >= 0, 1.0, -1.0)
    new_we = comp - scales[:, None] * signs
    # server-side: rank r averages chunk r of everyone
    for r in range(w):
        server_m = np.mean(
            signs[:, r * chunk:(r + 1) * chunk] * scales[:, None], axis=0)
        server_m = server_m + server_errors[r]
        sscale = np.linalg.norm(server_m) / np.sqrt(chunk)
        ssign = np.where(server_m >= 0, 1.0, -1.0)
        new_se[r] = server_m - sscale * ssign
        outs_signs[r] = ssign
        outs_scales[r] = sscale
    out = (outs_signs * outs_scales[:, None]).reshape(-1)
    return out, new_we, new_se


def test_gather_phase_names_are_real_collectives(eight_devices):
    """Reference name parity (custom_collectives.py:10-155): the four
    gather/allgather variants must be WORKING phase implementations (one
    XLA impl serves cuda+host), not shims — phase 1 delivers chunk r of
    every worker's packed signs to worker r, phase 2 rebroadcasts."""
    assert gather_cuda is gather_host is gather_tpu
    assert allgather_cuda is allgather_host is allgather_tpu
    w, chunk = 8, 16
    rng = np.random.RandomState(0)
    packed = rng.randint(0, 256, size=(w, w, chunk // 8)).astype(np.uint8)
    scales = rng.rand(w).astype(np.float32)
    mesh = Mesh(np.array(eight_devices), ("data",))

    def per_device(p, s):
        recv, all_scales = gather_tpu("data", p[0], s[0])
        gathered, gscales = allgather_tpu("data", recv[0], all_scales[0])
        return recv[None], all_scales[None], gathered[None], gscales[None]

    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(P("data"), P("data")),
                   out_specs=(P("data"), P("data"), P("data"), P("data")))
    recv, all_scales, gathered, gscales = jax.jit(fn)(packed, scales)
    # Worker r's phase-1 result row p is worker p's chunk r.
    for r in range(w):
        for p in range(w):
            np.testing.assert_array_equal(np.asarray(recv)[r, p],
                                          packed[p, r])
        np.testing.assert_allclose(np.asarray(all_scales)[r], scales)
        # Phase 2: every worker ends with worker 0's chunk-0 row
        # rebroadcast (per_device gathered recv[0] = chunk from peer 0).
        np.testing.assert_array_equal(np.asarray(gathered)[r, 0],
                                      packed[0, 0])


def test_compressed_allreduce_matches_numpy_sim(eight_devices):
    w = 8
    n = corrected_size(200, w)
    rng = np.random.RandomState(1)
    buffers = rng.randn(w, n).astype(np.float32)
    werr = rng.randn(w, n).astype(np.float32) * 0.1
    serr = rng.randn(w, n // w).astype(np.float32) * 0.1

    mesh = Mesh(np.array(eight_devices), ("data",))

    def per_device(b, we, se):
        # shard_map delivers [1, n] blocks; the collective works on [n].
        out, nwe, nse = compressed_allreduce(b[0], we[0], se[0], "data")
        return out[None], nwe[None], nse[None]

    fn = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P("data", None), P("data", None), P("data", None)),
        out_specs=(P("data", None), P("data", None), P("data", None)))

    out, new_we, new_se = jax.jit(fn)(buffers, werr, serr)
    # each device returns the same full averaged vector → rows identical
    ref_out, ref_we, ref_se = _numpy_compressed_allreduce(buffers, werr, serr)
    for r in range(w):
        np.testing.assert_allclose(np.asarray(out)[r], ref_out,
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_we), ref_we, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_se), ref_se, rtol=1e-5, atol=1e-5)


def test_error_feedback_converges_to_mean(eight_devices):
    """Repeated compressed allreduce of the same buffers: error feedback makes
    the time-average of outputs approach the true mean."""
    w = 8
    n = corrected_size(64, w)
    rng = np.random.RandomState(2)
    buffers = rng.randn(w, n).astype(np.float32)
    true_mean = buffers.mean(0)
    werr = np.zeros((w, n), np.float32)
    serr = np.zeros((w, n // w), np.float32)
    outs = []
    for _ in range(30):
        out, werr, serr = _numpy_compressed_allreduce(buffers, werr, serr)
        outs.append(out)
    avg = np.mean(outs, axis=0)
    # time-averaged compressed output tracks the true mean
    assert np.abs(avg - true_mean).mean() < 0.15 * np.abs(true_mean).mean() + 0.05


def test_quantize_error_feedback():
    x = jnp.asarray(np.random.RandomState(3).randn(64).astype(np.float32))
    err = jnp.zeros(64)
    total = jnp.zeros(64)
    for i in range(50):
        q, err = quantize_error_feedback(x, err)
        total = total + q
    # running average of quantized values approaches x
    np.testing.assert_allclose(np.asarray(total / 50), np.asarray(x),
                               atol=0.25)


def test_onebit_warmup_matches_adam():
    """Before freeze_step, 1-bit Adam == Adam without bias correction."""
    rng = np.random.RandomState(4)
    params = {"w": jnp.asarray(rng.randn(10).astype(np.float32))}
    grads = {"w": jnp.asarray(rng.randn(10).astype(np.float32))}
    opt = OnebitAdam(lr=1e-2, freeze_step=100)
    state = opt.init_state(params)
    p1, s1 = opt.update(params, grads, state)
    # manual Adam (no bias correction, reference onebit_adam.py:319-324)
    m = 0.1 * np.asarray(grads["w"])
    v = 0.001 * np.asarray(grads["w"]) ** 2
    expect = np.asarray(params["w"]) - 1e-2 * m / (np.sqrt(v) + 1e-8)
    np.testing.assert_allclose(np.asarray(p1["w"]), expect, rtol=1e-5)
    assert int(s1["step"]) == 1


def test_onebit_frozen_phase_freezes_variance():
    rng = np.random.RandomState(5)
    params = {"w": jnp.asarray(rng.randn(16).astype(np.float32))}
    grads = {"w": jnp.asarray(rng.randn(16).astype(np.float32))}
    opt = OnebitAdam(lr=1e-2, freeze_step=1)
    state = opt.init_state(params)
    p1, s1 = opt.update(params, grads, state)      # step 1: warmup
    v_after_warmup = np.asarray(s1["exp_avg_sq"]["w"]).copy()
    p2, s2 = opt.update(p1, grads, s1)             # step 2: frozen
    np.testing.assert_array_equal(np.asarray(s2["exp_avg_sq"]["w"]),
                                  v_after_warmup)
    # momentum is quantized: every element is ±scale
    m = np.asarray(s2["exp_avg"]["w"])
    mags = np.unique(np.round(np.abs(m), 5))
    assert len(mags) <= 2  # single scale magnitude (padding may add zeros)
    # error buffers engaged
    assert np.abs(np.asarray(s2["worker_error"]["w"])).sum() > 0


def test_onebit_notify_step_disables_allreduce():
    class FakeEngine:
        enable_backward_allreduce = True
        dp_world_size = 1
    eng = FakeEngine()
    opt = OnebitAdam(deepspeed=eng, freeze_step=5)
    opt.notify_step(4)
    assert eng.enable_backward_allreduce
    opt.notify_step(5)
    assert not eng.enable_backward_allreduce
    assert opt.adam_freeze_key


def test_onebit_adam_trains_under_engine():
    from deepspeed_tpu.models.simple import SimpleModel
    engine, _, _, _ = deepspeed.initialize(
        model=SimpleModel(hidden_dim=8),
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "OneBitAdam",
                          "params": {"lr": 1e-2, "freeze_step": 3}},
        })
    rng = np.random.RandomState(0)
    x = rng.randn(8, 8).astype(np.float32)
    y = rng.randint(0, 8, size=(8,))
    losses = []
    for _ in range(8):
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert not engine.enable_backward_allreduce  # frozen at step 3
    assert int(engine.opt_state["step"]) == 8


def test_onebit_adam_convergence_vs_dense():
    """Compression phase still converges on a quadratic problem."""
    rng = np.random.RandomState(7)
    target = rng.randn(32).astype(np.float32)

    def run(opt, steps=60):
        params = {"w": jnp.zeros(32)}
        state = opt.init_state(params)
        for _ in range(steps):
            grads = {"w": params["w"] - jnp.asarray(target)}
            params, state = opt.update(params, grads, state)
        return np.asarray(params["w"])

    dense = run(FusedAdam(lr=0.05, bias_correction=False))
    onebit = run(OnebitAdam(lr=0.05, freeze_step=20))
    assert np.abs(onebit - target).mean() < np.abs(target).mean() * 0.5
    assert np.abs(dense - target).mean() < np.abs(target).mean() * 0.5


def _spmd_engine(freeze_step, lr=1e-2):
    from deepspeed_tpu.models.simple import SimpleModel
    engine, _, _, _ = deepspeed.initialize(
        model=SimpleModel(hidden_dim=16),
        config_params={
            "train_batch_size": 16,
            "optimizer": {"type": "OneBitAdam",
                          "params": {"lr": lr, "freeze_step": freeze_step}},
        })
    return engine


def test_onebit_engine_hot_path_compresses_the_wire(eight_devices):
    """The ENGINE's train_batch compression phase must exchange sign-packed
    uint8 (n/8 bytes + scales), not dense fp32 gradients (reference: 1-bit
    Adam's 5x comm saving, README + custom_collectives igather/allgather).

    Asserts on the compiled frozen program's collectives: the momentum
    exchange is uint8 all_to_all/all_gather, and the ONLY f32 all_reduce
    left is the scalar loss pmean — the dense gradient average is gone."""
    engine = _spmd_engine(freeze_step=1)
    assert engine._onebit_spmd_eligible()
    rng = np.random.RandomState(0)
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randint(0, 16, size=(16,))
    engine.train_batch(batch=(x, y))   # warmup step; freeze flips after
    engine.train_batch(batch=(x, y))   # frozen program traces + runs
    assert engine.optimizer.adam_freeze_key

    from deepspeed_tpu.parallel import mesh as mesh_lib
    inputs = mesh_lib.shard_batch(engine.mesh,
                                  (jnp.asarray(x), jnp.asarray(y)))

    def collectives(frozen):
        fn = engine._fused_step_cache[("onebit", 2, frozen)]
        hlo = fn.lower(engine.params, engine.opt_state, inputs,
                       jax.random.PRNGKey(0), jnp.float32(1e-2),
                       jnp.float32(0.9), jnp.float32(0.999)).as_text()
        return {op: [l for l in hlo.splitlines() if "stablehlo." + op in l]
                for op in ("all_to_all", "all_gather", "all_reduce")}

    frozen = collectives(True)
    # Phase-1 momentum scatter: uint8 on the wire, one per param leaf.
    assert frozen["all_to_all"], "no all_to_all in the frozen program"
    for line in frozen["all_to_all"]:
        assert "ui8" in line, "momentum scatter is not sign-packed: " + line
    # Phase-2 rebroadcast: uint8 chunks present among the gathers.
    assert any("ui8" in l for l in frozen["all_gather"])
    # f32 gathers may only carry the per-worker scales ([1] -> [W]).
    for line in (l for l in frozen["all_gather"] if "f32" in l):
        assert "tensor<1xf32>" in line, "dense f32 gather: " + line
    # The ONLY all_reduce is the scalar loss pmean — no dense grad average.
    assert len(frozen["all_reduce"]) == 1
    # Contrast: the warmup program DOES carry dense f32 all_reduces (the
    # explicit gradient pmean), proving the saving is phase-specific.
    warmup = collectives(False)
    assert len(warmup["all_reduce"]) > 1


def test_onebit_engine_hot_path_loss_parity_with_dense_adam(eight_devices):
    """Through and past the freeze boundary, the compressed engine path
    tracks dense Adam (error feedback keeps the trajectory close on a
    smooth objective; reference test strategy: convergence parity, not
    bitwise equality)."""
    from deepspeed_tpu.models.simple import SimpleModel
    rng = np.random.RandomState(3)
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randint(0, 16, size=(16,))

    def run(cfg_opt):
        engine, _, _, _ = deepspeed.initialize(
            model=SimpleModel(hidden_dim=16),
            config_params={"train_batch_size": 16, "optimizer": cfg_opt})
        return [float(engine.train_batch(batch=(x, y))) for _ in range(20)]

    onebit = run({"type": "OneBitAdam",
                  "params": {"lr": 1e-2, "freeze_step": 5}})
    dense = run({"type": "Adam",
                 "params": {"lr": 1e-2, "betas": [0.9, 0.999]}})
    assert onebit[-1] < onebit[0]
    # Same ballpark at the end of training (quantization noise allowed).
    assert onebit[-1] < dense[-1] + 0.5 * abs(dense[0] - dense[-1])


def test_onebit_resume_past_freeze_selects_frozen_program(
        eight_devices, tmp_path):
    """Checkpoint resume past freeze_step must run the FROZEN (compressed)
    program from its first step — the host flag is restored from the
    checkpointed counters, not left at its warmup default."""
    rng = np.random.RandomState(0)
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randint(0, 16, size=(16,))
    engine = _spmd_engine(freeze_step=2)
    for _ in range(4):
        engine.train_batch(batch=(x, y))
    assert engine.optimizer.adam_freeze_key
    engine.save_checkpoint(str(tmp_path))

    fresh = _spmd_engine(freeze_step=2)
    fresh.load_checkpoint(str(tmp_path))
    assert fresh.optimizer.adam_freeze_key, \
        "freeze flag not restored on resume"
    assert not fresh.enable_backward_allreduce
    fresh.train_batch(batch=(x, y))
    keys = list(fresh._fused_step_cache)
    assert ("onebit", 2, True) in keys, keys
    assert ("onebit", 2, False) not in keys, \
        "resume ran a warmup-phase step past freeze: {}".format(keys)


def test_onebit_rollback_to_prefreeze_reenters_warmup(
        eight_devices, tmp_path):
    """Rolling an engine already past freeze back to a PRE-freeze
    checkpoint must clear the compression phase (and re-enable the dense
    allreduce), not stay frozen with a warmup-era exp_avg_sq."""
    rng = np.random.RandomState(1)
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randint(0, 16, size=(16,))
    engine = _spmd_engine(freeze_step=10)
    engine.train_batch(batch=(x, y))  # 1 warmup step
    engine.save_checkpoint(str(tmp_path))  # pre-freeze checkpoint

    engine2 = _spmd_engine(freeze_step=2)
    for _ in range(4):
        engine2.train_batch(batch=(x, y))
    assert engine2.optimizer.adam_freeze_key  # frozen now
    engine2.optimizer.freeze_step = 10  # same schedule as the checkpoint
    engine2.load_checkpoint(str(tmp_path))
    assert not engine2.optimizer.adam_freeze_key, "rollback stayed frozen"
    assert engine2.enable_backward_allreduce


def test_onebit_update_shard_map_local_grads(eight_devices):
    """The shard_map path: per-worker local grads, momentum exchanged via the
    two-phase compressed collective; resulting params identical on all
    workers."""
    from deepspeed_tpu.runtime.fp16.onebit_adam import onebit_adam_update

    w = 8
    n = 64
    padded = corrected_size(n, w)
    rng = np.random.RandomState(11)
    params = {"w": jnp.asarray(rng.randn(n).astype(np.float32))}
    local_grads = rng.randn(w, n).astype(np.float32)
    state = {
        "step": jnp.zeros((), jnp.int32),
        "exp_avg": {"w": jnp.zeros(n)},
        "exp_avg_sq": {"w": jnp.full((n,), 0.01)},
        "worker_error": {"w": jnp.zeros(padded)},
        "server_error": {"w": jnp.zeros(padded // w)},
    }
    mesh = Mesh(np.array(eight_devices), ("data",))

    def step_fn(frozen):
        def f(params, grads, state):
            grads = {"w": grads[0]}
            st = dict(state)
            st["server_error"] = {"w": state["server_error"]["w"][0]}
            new_p, new_s = onebit_adam_update(
                params, grads, st, lr=0.01, axis_name="data",
                freeze_step=0 if frozen else 10**9, frozen=frozen)
            return new_p, new_s["exp_avg"]["w"]
        return shard_map(
            f, mesh=mesh,
            in_specs=(P(), P("data", None), {
                "step": P(), "exp_avg": {"w": P()}, "exp_avg_sq": {"w": P()},
                "worker_error": {"w": P()},
                "server_error": {"w": P("data", None)}}),
            out_specs=(P(), P()), check_vma=False)

    state_sm = dict(state)
    state_sm["server_error"] = {
        "w": jnp.tile(state["server_error"]["w"][None], (w, 1))}

    # warmup traces and runs
    p1, m1 = jax.jit(step_fn(False))(params, jnp.asarray(local_grads),
                                     state_sm)
    assert p1["w"].shape == (n,)
    # frozen phase: compressed collective path traces and runs
    p2, m2 = jax.jit(step_fn(True))(params, jnp.asarray(local_grads),
                                    state_sm)
    # momentum after exchange is ±scale quantized
    mags = np.unique(np.round(np.abs(np.asarray(m2)), 6))
    assert len(mags) <= w + 1  # one scale per server chunk


# --------------------------------------------------------------- PP x DP
# BASELINE config #5: PipelineModule (PP x DP) + 1-bit Adam compressed
# allreduce. The reference's compression machinery is optimizer-level and
# composes with any engine (custom_collectives.py:10-155); the pipeline
# engine must run the frozen-phase momentum exchange compressed over each
# stage's data-axis submesh.


class _DenseTanh(__import__("flax").linen.Module):
    """tanh keeps every unit alive: 1-bit's frozen phase gives EVERY
    element a +-scale momentum, so elements whose exp_avg_sq is exactly
    zero (dead ReLU paths under a short warmup) get scale/eps-sized
    updates — faithful to the reference formula (onebit_adam.py:319-355),
    which relies on long warmups to populate v. The test regime must not."""
    features: int = 32

    @__import__("flax").linen.compact
    def __call__(self, x):
        import flax.linen as nn
        return nn.tanh(nn.Dense(self.features)(x))


def _pipe_engine(opt_cfg, num_stages=2, gas=2):
    from deepspeed_tpu.models.simple import DenseOut, ce_loss
    from deepspeed_tpu.pipe import LayerSpec, PipelineModule
    layers = [LayerSpec(_DenseTanh, 32), LayerSpec(_DenseTanh, 32),
              LayerSpec(_DenseTanh, 32), LayerSpec(DenseOut, 8)]
    model = PipelineModule(layers=layers, num_stages=num_stages,
                           loss_fn=ce_loss, seed_layers=True, base_seed=42,
                           partition_method="uniform")
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params={
            "train_batch_size": 8 * gas,
            "gradient_accumulation_steps": gas,
            "optimizer": opt_cfg,
        })
    return engine


def _pipe_data(steps, gas, seed0=7):
    rng = np.random.RandomState(seed0)
    return [[(rng.randn(8, 16).astype(np.float32),
              rng.randint(0, 8, size=(8,)))
             for _ in range(gas)] for _ in range(steps)]


def test_onebit_pipe_loss_parity_with_dense_adam(eight_devices):
    """PP x DP 1-bit trains stably through and past the freeze boundary
    and stays near the dense-Adam trajectory (error feedback bounds the
    drift on a smooth objective — same bar as the base-engine parity
    test; exact update semantics are pinned separately by
    test_onebit_pipe_update_matches_numpy_sim)."""
    gas, steps, freeze = 2, 8, 3
    data = _pipe_data(steps, gas)

    onebit = _pipe_engine({"type": "OneBitAdam",
                           "params": {"lr": 1e-2, "freeze_step": freeze}})
    assert onebit._onebit_pp_capable()
    dense = _pipe_engine({"type": "Adam", "params": {"lr": 1e-2}})

    lo, ld = [], []
    for step in range(steps):
        lo.append(onebit.train_batch(data_iter=iter(list(data[step]))))
        ld.append(dense.train_batch(data_iter=iter(list(data[step]))))
        if step + 1 > freeze:
            assert onebit.optimizer.adam_freeze_key
    lo, ld = np.asarray(lo), np.asarray(ld)
    assert np.isfinite(lo).all(), lo
    # No blow-up past the boundary, and the compressed trajectory stays
    # within a loose band of dense Adam's.
    assert lo.max() < 2.0 * ld.max(), (lo, ld)
    assert abs(lo[-3:].mean() - ld[-3:].mean()) < 1.0, (lo, ld)


def test_onebit_pipe_update_matches_numpy_sim(eight_devices, monkeypatch):
    """The pipeline's compressed per-stage update must implement EXACTLY
    the reference's error-compensated exchange: capture one frozen-phase
    update's (params, [dp,...] local-grad rows, state) and replay it in
    a from-scratch numpy simulation of Compressed_Allreduce + the frozen
    Adam step (reference onebit_adam.py:104-233, :319-355)."""
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

    cap = {}
    orig = PipelineEngine._get_stage_opt_jit

    def spy(self, sid, idxs, compressed):
        fn = orig(self, sid, idxs, compressed)
        if not compressed:
            return fn

        def wrapped(ps, gs, ss, *sc):
            first = sid not in cap
            if first:
                cap[sid] = [jax.device_get(ps), jax.device_get(gs),
                            jax.device_get(ss)]
            out = fn(ps, gs, ss, *sc)
            if first:
                cap[sid].append(jax.device_get(out[0]))
            return out
        return wrapped

    monkeypatch.setattr(PipelineEngine, "_get_stage_opt_jit", spy)
    lr, freeze, gas = 1e-3, 1, 1
    engine = _pipe_engine({"type": "OneBitAdam",
                           "params": {"lr": lr, "freeze_step": freeze}},
                          gas=gas)
    data = _pipe_data(2, gas)
    for step in range(2):
        engine.train_batch(data_iter=iter(list(data[step])))
    assert cap, "compressed update never ran"

    def numpy_onebit(p, grows, m, v, b1=0.9, eps=1e-8):
        w = grows.shape[0]
        n = p.size
        pad = corrected_size(n, w)
        chunk = pad // w
        mloc = b1 * m.reshape(-1)[None, :] + \
            (1 - b1) * grows.reshape(w, -1)
        buf = np.zeros((w, pad), np.float32)
        buf[:, :n] = mloc
        scales = np.linalg.norm(buf, axis=1) / np.sqrt(pad)
        signs = np.where(buf >= 0, 1.0, -1.0)
        out = np.zeros(pad, np.float32)
        for r in range(w):
            sm = np.mean(signs[:, r * chunk:(r + 1) * chunk] *
                         scales[:, None], axis=0)
            sscale = np.linalg.norm(sm) / np.sqrt(chunk)
            out[r * chunk:(r + 1) * chunk] = sscale * np.where(
                sm >= 0, 1.0, -1.0)
        mnew = out[:n].reshape(p.shape)
        return p - lr * mnew / (np.sqrt(v) + eps)

    checked = 0
    for sid, (ps, gs, ss, new_ps) in sorted(cap.items()):
        for li in range(len(ps)):
            for p, g, m, v, pn in zip(
                    jax.tree_util.tree_leaves(ps[li]),
                    jax.tree_util.tree_leaves(gs[li]),
                    jax.tree_util.tree_leaves(ss[li]["exp_avg"]),
                    jax.tree_util.tree_leaves(ss[li]["exp_avg_sq"]),
                    jax.tree_util.tree_leaves(new_ps[li])):
                exp = numpy_onebit(np.asarray(p), np.asarray(g),
                                   np.asarray(m), np.asarray(v))
                scale = max(float(np.abs(exp).max()), 1e-9)
                np.testing.assert_allclose(np.asarray(pn), exp,
                                           atol=1e-5 * scale, rtol=1e-4)
                checked += 1
    assert checked >= 4


def test_onebit_pipe_frozen_wire_is_compressed(eight_devices, monkeypatch):
    """HLO assertion, pipeline edition (mirrors the base-engine test):
    past freeze_step (a) the per-stage optimizer update's only collectives
    are the sign-packed uint8 all_to_all / all_gather (+ [1] f32 scale
    gathers) with NO dense f32 all_reduce, and (b) the local-grad
    backward program carries NO all_reduce at all — the dense gradient
    average is gone from the wire."""
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

    opt_calls = {}
    bwd_calls = {}
    orig_opt = PipelineEngine._get_stage_opt_jit
    orig_bwd = PipelineEngine._get_stage_bwd_local

    def spy_opt(self, sid, idxs, compressed):
        fn = orig_opt(self, sid, idxs, compressed)
        if not compressed:
            return fn

        def wrapped(*a):
            opt_calls.setdefault(sid, (fn, jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), a)))
            return fn(*a)
        return wrapped

    def spy_bwd(self, sid):
        fn = orig_bwd(self, sid)

        def wrapped(*a):
            bwd_calls.setdefault(sid, (fn, jax.tree_util.tree_map(
                lambda x: None if x is None else
                jax.ShapeDtypeStruct(x.shape, x.dtype), a,
                is_leaf=lambda l: l is None)))
            return fn(*a)
        return wrapped

    monkeypatch.setattr(PipelineEngine, "_get_stage_opt_jit", spy_opt)
    monkeypatch.setattr(PipelineEngine, "_get_stage_bwd_local", spy_bwd)

    gas, freeze = 2, 1
    engine = _pipe_engine({"type": "OneBitAdam",
                           "params": {"lr": 1e-2, "freeze_step": freeze}})
    data = _pipe_data(3, gas)
    for step in range(3):
        engine.train_batch(data_iter=iter(list(data[step])))
    assert engine.optimizer.adam_freeze_key
    assert opt_calls and bwd_calls, "compressed path never engaged"

    def collectives(hlo):
        return {op: [l for l in hlo.splitlines() if "stablehlo." + op in l]
                for op in ("all_to_all", "all_gather", "all_reduce")}

    for sid, (fn, spec) in opt_calls.items():
        c = collectives(fn.lower(*spec).as_text())
        assert c["all_to_all"], "stage %d: no all_to_all" % sid
        for line in c["all_to_all"]:
            assert "ui8" in line, "momentum scatter not sign-packed: " + line
        assert any("ui8" in l for l in c["all_gather"])
        for line in (l for l in c["all_gather"] if "f32" in l):
            assert "tensor<1xf32>" in line, "dense f32 gather: " + line
        assert not c["all_reduce"], \
            "stage %d frozen update has a dense all_reduce" % sid

    for sid, (fn, spec) in bwd_calls.items():
        c = collectives(fn.lower(*spec).as_text())
        assert not c["all_reduce"], \
            "stage %d local backward still all_reduces grads" % sid
