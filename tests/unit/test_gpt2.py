"""Flagship GPT-2 model: trains under the engine, loss decreases, ZeRO shards."""

import numpy as np
import pytest

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
    assert snap["flash_lane_pack"] == 0          # the head-major entry ran
    text = prometheus_text(engine.telemetry)
    assert "ds_tpu_flash_subtile" in text
    assert "ds_tpu_flash_tiles_visited_share" in text
    # ``flash_lane_pack``: the heads a 128-lane tile where the last call
    # took the projection's own layout (two of 64, as both training cells).
    x = jnp.ones((1, 256, 3 * 128), jnp.float32)
    assert np.isfinite(np.asarray(
        flash_attention(x, heads=2, head_dim=64, causal=True))).all()
    assert engine.telemetry.snapshot()["flash_lane_pack"] == 2
    assert "ds_tpu_flash_lane_pack" in prometheus_text(engine.telemetry)


def _eqns_under(jaxpr, scope, inside=False):
    """Every equation of ``jaxpr`` and of the jaxprs its equations carry
    (jit, custom_vjp, remat, cond, scan: NOT a Pallas kernel's body, which
    is the kernel's own business) whose name stack, or an enclosing
    equation's, lies under ``scope``."""
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        if here:
            yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_under(sub, scope, here)


@pytest.mark.parametrize("heads,batch", [(16, 4), (25, 8)])
def test_flash_branch_transposes_no_activation(heads, batch):
    """``CausalSelfAttention``'s flash branch hands c_attn's output to the
    kernels as it comes and their output to c_proj: the jaxpr of the loss
    and its gradient holds, under ``block/attn``, no ``transpose`` of a 4-D
    operand but the arranging of c_attn's WEIGHT (its C rows by 3 C
    columns, whole tiles of them; an activation is ``batch`` x T x C or
    more), for GPT-2
    355M's 16 heads and for GPT-2 XL's 25 (12.5 lane tiles), and both
    flash launches take the packed layout."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer.kernels import attention

    t, c = 1024, heads * 64
    cfg = GPT2Config(vocab_size=256, n_positions=t, n_embd=c, n_layer=1,
                     n_head=heads, dropout=0.0, use_flash_attention=True)
    model = GPT2LMHeadModel(cfg)
    ids = jax.ShapeDtypeStruct((batch, t), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((batch, t), jnp.int32),
                           jnp.zeros((batch, t), jnp.int32)))

    def loss_and_grad(params, ids):
        return jax.value_and_grad(
            lambda p: model.apply(p, ids, ids))(params)

    jaxpr = jax.make_jaxpr(loss_and_grad)(params, ids).jaxpr
    under = list(_eqns_under(jaxpr, "block/attn"))
    launches = [e.params["name"] for e in under
                if e.primitive.name == "pallas_call"]
    assert sorted(launches) == ["flash_bwd_fused", "flash_fwd"]
    assert attention.last_walk()["lane_pack"] == 2
    transposed = [e.invars[0].aval.shape for e in under
                  if e.primitive.name == "transpose"
                  and len(e.invars[0].aval.shape) >= 4]
    assert transposed, "c_attn's weight is arranged under block/attn"
    assert all(shape[0] == c and np.prod(shape) < batch * t * c
               for shape in transposed), transposed   # [C, .., .., 128]
    # ... and nothing of an activation's size is split into heads at all.
    assert not [e for e in under if e.primitive.name == "reshape"
                and len(e.outvars[0].aval.shape) >= 4
                and np.prod(e.outvars[0].aval.shape) >= batch * t * c]


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
