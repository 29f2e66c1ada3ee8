"""Engine-level sequence parallelism tests (beyond the reference: v0.3.10
has no sequence/context parallelism — SURVEY §0; the TPU build adds it as
a first-class config, "sequence_parallel": {"enabled": true}).
"""

import jax
import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.parallel import mesh as mesh_lib


_PARAMS = {}


def _fresh(key, model, *probe):
    """The model's parameters from ONE jitted init a ``key``, a fresh copy
    an engine: serial and sequence-parallel configurations hold the same
    tree, and an engine left to initialise itself at its first forward
    does so operation by operation (seconds a tiny model)."""
    import jax.numpy as jnp
    if key not in _PARAMS:
        _PARAMS[key] = jax.jit(lambda: model.init(
            jax.random.PRNGKey(0), *probe)["params"])()
    return jax.tree_util.tree_map(jnp.array, _PARAMS[key])


def _gpt2_params():
    import jax.numpy as jnp
    return _fresh("gpt2", GPT2LMHeadModel(GPT2Config.tiny(
        dropout=0.0, use_flash_attention=False)), jnp.zeros((1, 8), jnp.int32))


def _train(config_extra=None, sp_axis=None, steps=5, batch=4, seq=32,
           lr=1e-2):
    cfg = GPT2Config.tiny(dropout=0.0, use_flash_attention=True,
                          sequence_parallel_axis=sp_axis)
    model = GPT2LMHeadModel(cfg)
    config = {
        "train_batch_size": batch,
        "optimizer": {"type": "AdamW", "params": {"lr": lr}},
    }
    config.update(config_extra or {})
    engine, _, _, _ = deepspeed.initialize(
        model=model, model_parameters=_gpt2_params(), config_params=config)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq))
    losses = []
    for i_step in range(steps):
        loss = engine(ids, ids)
        engine.backward(loss)
        if i_step == 0:
            # Pre-optimizer gradients of the initial params, for the
            # direct-gradient parity test.
            engine.first_backward_grads = jax.device_get(
                engine._cached_grads)
        engine.step()
        losses.append(float(loss))
    return engine, losses


_BASELINES = {}


def _baseline(sp, steps, batch):
    """The canonical batch-8 run — serial or sp=8 — memoized: with
    dropout=0 and the same fixed batch every step the run is
    deterministic, and a shorter run is a prefix of a longer one, so
    every vs-serial test shares one baseline. Returns (engine, losses);
    the engine carries .first_backward_grads for the direct-gradient
    test."""
    key = (sp, batch)
    have = _BASELINES.get(key)
    if have is None or len(have[1]) < steps:
        extra = ({"sequence_parallel": {"enabled": True, "size": 8},
                  "train_batch_size": batch} if sp else None)
        have = _train(extra, sp_axis="seq" if sp else None,
                      steps=steps, batch=batch)
        _BASELINES[key] = have
    return have[0], have[1][:steps]


def _serial_losses(steps, batch):
    return _baseline(False, steps, batch)[1]


def test_sp_mesh_rebuilt_from_config():
    # Config/mesh plumbing only (steps=0 skips the compile): the sp=8
    # program itself is exercised end to end by
    # test_sp_loss_matches_serial.
    engine, _ = _train(
        {"sequence_parallel": {"enabled": True, "size": 8},
         "train_batch_size": 4},
        sp_axis="seq", steps=0)
    assert engine.sequence_parallel_enabled()
    assert engine.sequence_parallel_size() == 8
    assert mesh_lib.dp_size(engine.mesh) == 1


def test_sp_loss_matches_serial():
    """sp=8 training must reproduce the serial loss trajectory: same
    function, different device decomposition."""
    serial = _serial_losses(steps=5, batch=8)
    sp = _baseline(True, steps=5, batch=8)[1]
    # Step 1 is the same function evaluated two ways (tight); later
    # steps amplify fp32 summation-order differences through the
    # optimizer (loose trajectory bound).
    np.testing.assert_allclose(sp[0], serial[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(sp, serial, rtol=1e-2, atol=1e-2)
    assert sp[-1] < sp[0]


def test_sp_composes_with_dp():
    """dp=2 x sp=4 over 8 devices tracks the serial curve."""
    serial = _serial_losses(steps=4, batch=8)
    _, sp = _train({"sequence_parallel": {"enabled": True, "size": 4},
                    "train_batch_size": 8}, sp_axis="seq", steps=4,
                   batch=8)
    np.testing.assert_allclose(sp[0], serial[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(sp, serial, rtol=1e-2, atol=1e-2)


def test_sp_ulysses_mode_matches_serial():
    """sequence_parallel_mode='ulysses' (all-to-all head swaps) through
    the engine: sp=4 x dp=2, 4 heads — tracks the serial curve like the
    ring mode."""
    serial = _serial_losses(steps=4, batch=8)

    cfg = GPT2Config.tiny(dropout=0.0, sequence_parallel_axis="seq",
                          sequence_parallel_mode="ulysses")
    model = GPT2LMHeadModel(cfg)
    engine, _, _, _ = deepspeed.initialize(
        model=model, model_parameters=_gpt2_params(),
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
            "sequence_parallel": {"enabled": True, "size": 4},
        })
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(8, 32))
    uly = []
    for _ in range(4):
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        uly.append(float(loss))
    np.testing.assert_allclose(uly[0], serial[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(uly, serial, rtol=1e-2, atol=1e-2)


def test_sp_composes_with_zero2():
    serial = _serial_losses(steps=4, batch=8)
    _, sp = _train({"sequence_parallel": {"enabled": True, "size": 4},
                    "train_batch_size": 8,
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 2}},
                   sp_axis="seq", steps=4, batch=8)
    # bf16 compute on the SP side: coarser bound than the fp32 pairings.
    np.testing.assert_allclose(sp[0], serial[0], rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(sp, serial, rtol=5e-2, atol=5e-2)


def test_sp_gradients_match_serial():
    """DIRECT gradient comparison (not loss trajectories — Adam is
    invariant to constant grad rescaling, so trajectory parity cannot
    catch an sp-times scale bug in the shard_map reduction). Reads the
    first-backward gradients the shared baseline runs captured before
    their optimizer ever stepped."""
    eng_serial, l_serial = _baseline(False, steps=5, batch=8)
    eng_sp, l_sp = _baseline(True, steps=5, batch=8)
    loss_serial, g_serial = l_serial[0], eng_serial.first_backward_grads
    loss_sp, g_sp = l_sp[0], eng_sp.first_backward_grads
    np.testing.assert_allclose(loss_sp, loss_serial, rtol=2e-4)
    flat_s = jax.tree_util.tree_leaves(g_serial)
    flat_p = jax.tree_util.tree_leaves(g_sp)
    for a, b in zip(flat_p, flat_s):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        # Elementwise: decomposition noise only (ring-merge softmax vs
        # single-block flash round differently in fp32) — an sp-times
        # scale bug would blow both bounds by ~8x.
        np.testing.assert_allclose(a, b, rtol=5e-2, atol=1e-3)
        # Norm-level: tighter than elementwise (noise partially averages
        # out; small leaves still carry ~0.3% scatter) — a scale bug
        # would be ~700% here.
        np.testing.assert_allclose(np.linalg.norm(a), np.linalg.norm(b),
                                   rtol=1e-2, atol=1e-6)


def test_sp_pg_correctness_check_passes():
    """pg_correctness_test under SP: the sharded program must match the
    forced-serial fp32 reference (this is the guard that catches grad
    scale/reduction bugs at the step they occur)."""
    from deepspeed_tpu.runtime import engine as engine_mod

    # The check reads the engine (a forward, no step): the shared sp=8
    # baseline engine and its compiled program serve, whatever it trained.
    engine, _ = _baseline(True, steps=5, batch=8)
    ids = np.random.RandomState(0).randint(0, 1024, size=(8, 32))
    engine_mod.pg_correctness_test = True
    try:
        loss = engine(ids, ids)  # raises if sharded grads diverge
    finally:
        engine_mod.pg_correctness_test = False
    assert np.isfinite(float(loss))


def test_sp_rejects_indivisible_token_dim():
    """A token dim not divisible by sp must raise — silent down-sharding
    would run the SP model paths on a wrong decomposition."""
    # Raised while the call is traced, before anything runs: the shared
    # sp=8 baseline engine serves.
    engine, _ = _baseline(True, steps=5, batch=8)
    ids = np.random.RandomState(0).randint(0, 1024, size=(8, 33))
    with pytest.raises(ValueError, match="not\\s+divisible by sp"):
        engine(ids, ids)


def test_sp_composes_with_fp16_and_grad_accumulation():
    """fp16 dynamic loss scaling + gas=2 under SP: the scaler's overflow
    bookkeeping and the host-side grad accumulation both run OUTSIDE the
    shard_map program and must compose with it."""
    cfg = GPT2Config.tiny(dropout=0.0, sequence_parallel_axis="seq")
    engine, _, _, _ = deepspeed.initialize(
        model=GPT2LMHeadModel(cfg), model_parameters=_gpt2_params(),
        config_params={
            "train_batch_size": 8,
            "train_micro_batch_size_per_gpu": 4,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "fp16": {"enabled": True, "initial_scale_power": 8},
            "sequence_parallel": {"enabled": True, "size": 8},
        })
    rng = np.random.RandomState(0)
    losses = []
    for step in range(6):
        ids = rng.randint(0, cfg.vocab_size, size=(4, 32))
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        if engine.is_gradient_accumulation_boundary():
            losses.append(float(loss))
    assert engine.skipped_steps == 0
    assert losses[-1] < losses[0] + 0.05, losses


def test_sp_requires_sequence_shardable_model():
    """A model without sequence_parallel_axis must be rejected loudly —
    sharding a serial model's tokens would train a different function."""
    with pytest.raises(ValueError, match="sequence-shardable"):
        _train({"sequence_parallel": {"enabled": True, "size": 8},
                "train_batch_size": 4}, sp_axis=None, steps=1)


def test_sp_user_mesh_must_have_seq_axis():
    model = GPT2LMHeadModel(GPT2Config.tiny(dropout=0.0,
                                            sequence_parallel_axis="seq"))
    with pytest.raises(ValueError, match="seq"):
        deepspeed.initialize(
            model=model,
            mesh=mesh_lib.build_mesh(),  # no seq axis
            config_params={
                "train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "sequence_parallel": {"enabled": True},
            })


def test_bert_sp_loss_matches_serial():
    """BERT MLM+NSP under sp=8 reproduces the serial loss (encoder ring
    attention with a rotating padding mask, psum'd MLM mean, [CLS]
    broadcast for the NSP head)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.bert import BertConfig, BertForPreTraining

    def run(sp):
        cfg = BertConfig.tiny(hidden_dropout_prob=0.0,
                              attention_probs_dropout_prob=0.0,
                              use_fused_layer=False,
                              dtype=jnp.float32,
                              sequence_parallel_axis="seq" if sp else None)
        config = {
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        }
        if sp:
            config["sequence_parallel"] = {"enabled": True, "size": 8}
        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, size=(8, 32))
        attn_mask = (rng.rand(8, 32) > 0.1).astype(np.int32)
        attn_mask[:, 0] = 1  # keep [CLS]
        labels = np.where(rng.rand(8, 32) < 0.15, ids, -1)
        nsp = rng.randint(0, 2, size=(8,))
        model = BertForPreTraining(cfg)
        engine, _, _, _ = deepspeed.initialize(
            model=model, config_params=config,
            model_parameters=_fresh(
                "bert", model, jnp.asarray(ids[:1]),
                jnp.asarray(attn_mask[:1]), None, jnp.asarray(labels[:1]),
                jnp.asarray(nsp[:1])))
        losses = []
        for _ in range(3):
            loss = engine(ids, jnp.asarray(attn_mask), None,
                          jnp.asarray(labels), jnp.asarray(nsp))
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        return losses

    serial = run(False)
    sp = run(True)
    np.testing.assert_allclose(sp[0], serial[0], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(sp, serial, rtol=1e-2, atol=1e-2)


def test_bert_sp_rejects_fused_layer():
    import jax.numpy as jnp

    from deepspeed_tpu.models.bert import BertConfig, BertForPreTraining

    cfg = BertConfig.tiny(use_fused_layer=True,
                          sequence_parallel_axis="seq")
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, size=(8, 32))
    labels = np.full((8, 32), -1)
    labels[:, ::4] = 1
    model = BertForPreTraining(cfg)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        model_parameters=_fresh("bert-fused", model, jnp.asarray(ids[:1]),
                                None, None, jnp.asarray(labels[:1]), None),
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "sequence_parallel": {"enabled": True, "size": 8},
        })
    with pytest.raises(ValueError, match="use_fused_layer"):
        engine(ids, None, None, jnp.asarray(labels), None)


def test_sp_eval_loss_matches_train_function():
    """eval (deterministic) under SP returns the same loss as the serial
    model on identical params."""
    # Any trained params work for this identity — reuse the shared sp=8
    # baseline engine instead of training a fresh one.
    engine, _ = _baseline(True, steps=5, batch=8)
    rng = np.random.RandomState(3)
    ids = rng.randint(0, 1024, size=(8, 32))
    engine.eval()
    try:
        sp_loss = float(engine(ids, ids))
    finally:
        engine.train()

    serial_model = GPT2LMHeadModel(GPT2Config.tiny(dropout=0.0))
    serial_loss = float(jax.jit(serial_model.apply)(
        {"params": jax.device_get(engine.params)}, ids, ids))
    np.testing.assert_allclose(sp_loss, serial_loss, rtol=2e-4, atol=2e-4)