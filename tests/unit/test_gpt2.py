"""Flagship GPT-2 model: trains under the engine, loss decreases, ZeRO shards."""

import numpy as np

import deepspeed_tpu as deepspeed
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel


def make_batch(batch, seq, vocab, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, size=(batch, seq))
    return ids, ids.copy()


def test_gpt2_tiny_trains():
    cfg = GPT2Config.tiny()
    model = GPT2LMHeadModel(cfg)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-3, "weight_decay": 0.01}},
            "bf16": {"enabled": True},
        })
    losses = []
    for i in range(10):
        ids, labels = make_batch(8, 32, cfg.vocab_size, seed=i % 2)
        loss = engine(ids, labels)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_gpt2_zero2_fused(eight_devices):
    cfg = GPT2Config.tiny()
    model = GPT2LMHeadModel(cfg)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
        })
    losses = []
    for i in range(10):
        ids, labels = make_batch(8, 32, cfg.vocab_size, seed=i % 2)
        loss = engine.train_batch(batch=(ids, labels))
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    # optimizer moments must actually be sharded over the data axis
    import jax
    sharded = [
        x for x in jax.tree_util.tree_leaves(engine.opt_state["exp_avg"])
        if not x.sharding.is_fully_replicated
    ]
    assert len(sharded) > 0, "ZeRO-2: no optimizer state sharded"


def test_gpt2_remat():
    cfg = GPT2Config.tiny(remat=True)
    model = GPT2LMHeadModel(cfg)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
        })
    ids, labels = make_batch(8, 32, cfg.vocab_size)
    loss = engine(ids, labels)
    engine.backward(loss)
    engine.step()
    assert np.isfinite(float(loss))


def test_flash_strip_gauges_say_what_the_launcher_resolved():
    """``flash_subtile`` / ``flash_tiles_visited_share``: the training
    engine's gauges read what flash attention's launcher resolved from the
    shapes of the last call traced: at T 256 the block is the sequence, the
    rule takes it in two strips of 128 rows, 3 of its 4 tiles computed."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer.kernels.attention import (
        flash_attention)
    from deepspeed_tpu.telemetry.exporters import prometheus_text

    engine, _, _, _ = deepspeed.initialize(
        model=GPT2LMHeadModel(GPT2Config.tiny()),
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
        })
    x = jnp.ones((1, 2, 256, 16), jnp.float32)
    assert np.isfinite(np.asarray(flash_attention(x, x, x, causal=True))).all()
    snap = engine.telemetry.snapshot()
    assert snap["flash_subtile"] == 128
    assert snap["flash_tiles_visited_share"] == 0.75
    text = prometheus_text(engine.telemetry)
    assert "ds_tpu_flash_subtile" in text
    assert "ds_tpu_flash_tiles_visited_share" in text


def test_flash_attention_path_matches_dense():
    """cfg.use_flash_attention=True routes through the Pallas flash kernel and
    agrees with the dense XLA path (fwd loss + grads finite)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 256, size=(2, 64)))
    base = GPT2Config.tiny(dropout=0.0, dtype=jnp.float32)

    def loss_and_grad(flash):
        cfg = dataclasses.replace(base, use_flash_attention=flash)
        model = GPT2LMHeadModel(cfg)
        params = model.init(jax.random.PRNGKey(1), ids, ids)

        def loss_fn(p):
            return model.apply(p, ids, ids)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        return float(loss), grads

    l_dense, g_dense = loss_and_grad(False)
    l_flash, g_flash = loss_and_grad(True)
    assert abs(l_dense - l_flash) < 1e-3
    for a, b in zip(jax.tree_util.tree_leaves(g_dense),
                    jax.tree_util.tree_leaves(g_flash)):
        assert np.all(np.isfinite(np.asarray(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-2, atol=5e-3)
