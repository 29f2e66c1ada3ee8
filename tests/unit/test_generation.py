"""KV-cache generation (models/generation.py) — parity against the
training forward. The decode program re-implements the block math over
the trained param tree, so these tests are the contract that keeps the
two in lockstep: prefill logits vs model.apply, cached greedy decode vs
a no-cache argmax loop, EOS freezing, and sampling determinism."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.generation import generate, init_cache, _forward
from deepspeed_tpu.models.generation import _GenCfg
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel


@functools.lru_cache(maxsize=None)
def make(dtype=jnp.float32, flash=False, seed=0):
    """One compiled init a configuration: no case writes its parameters."""
    cfg = GPT2Config.tiny(dropout=0.0, dtype=dtype,
                          use_flash_attention=flash)
    model = GPT2LMHeadModel(cfg)
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, size=(2, 12))
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.asarray(ids))["params"]
    return cfg, model, params, ids


# The primitives under ONE ``jax.jit`` each (the configuration is static): no
# case here is about calling them operation by operation.
forward = jax.jit(_forward, static_argnums=1)


def gencfg(cfg):
    return _GenCfg(cfg.n_layer, cfg.n_head, cfg.n_embd, cfg.n_positions,
                   cfg.dtype, cfg.layer_norm_epsilon)


@pytest.mark.parametrize("flash", [False, True])
def test_prefill_logits_match_training_forward(flash):
    cfg, model, params, ids = make(flash=flash)
    train_logits = jax.jit(model.apply)({"params": params},
                                        jnp.asarray(ids))
    cache = init_cache(gencfg(cfg), 2, ids.shape[1])
    gen_logits, cache = forward(params, gencfg(cfg), jnp.asarray(ids),
                                cache)
    np.testing.assert_allclose(np.asarray(gen_logits),
                               np.asarray(train_logits),
                               rtol=2e-4, atol=2e-4)
    assert (np.asarray(cache["pos"]) == ids.shape[1]).all()


def test_cached_greedy_matches_no_cache_loop():
    """Token-by-token cached decode == argmax over the full re-forward at
    every step (the O(T^2) no-cache reference)."""
    cfg, model, params, ids = make()
    steps = 6
    out = generate(model, params, ids, steps, temperature=0.0)

    seq = jnp.asarray(ids)
    want = []
    apply = jax.jit(model.apply)    # a compile a length, not a dispatch an op
    for _ in range(steps):
        logits = apply({"params": params}, seq)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        want.append(np.asarray(nxt))
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.stack(want, axis=1))


def test_eos_rows_freeze():
    cfg, model, params, ids = make()
    out0 = np.asarray(generate(model, params, ids, 8, temperature=0.0))
    eos = int(out0[0, 2])  # force an early "EOS" for row 0
    out = np.asarray(generate(model, params, ids, 8, temperature=0.0,
                              eos_token_id=eos))
    hit = np.where(out[0] == eos)[0]
    assert hit.size
    assert (out[0, hit[0]:] == eos).all()


def test_sampling_deterministic_per_key():
    cfg, model, params, ids = make()
    a = generate(model, params, ids, 5, temperature=0.9, top_k=8,
                 rng=jax.random.PRNGKey(7))
    b = generate(model, params, ids, 5, temperature=0.9, top_k=8,
                 rng=jax.random.PRNGKey(7))
    c = generate(model, params, ids, 5, temperature=0.9, top_k=8,
                 rng=jax.random.PRNGKey(8))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(a) != np.asarray(c)).any()


def test_bf16_decode_finite_and_in_vocab():
    cfg, model, params, ids = make(dtype=jnp.bfloat16)
    out = np.asarray(generate(model, params, ids, 6, temperature=0.7,
                              top_k=4, rng=jax.random.PRNGKey(3)))
    assert out.shape == (2, 6)
    assert (out >= 0).all() and (out < cfg.vocab_size).all()


def test_append_forward_chunked_matches_whole_prefill():
    """The chunked-prefill primitive: consuming a prompt in ragged
    chunks through append_forward yields the same logits and the same
    cache contents (up to the frontier) as one whole-prompt _forward —
    the mathematical core of the engine's chunked/whole parity."""
    from deepspeed_tpu.models.generation import append_forward, init_cache

    cfg, model, params, _ = make()
    g = gencfg(cfg)
    rng = np.random.RandomState(7)
    T, C = 13, 5                    # 13 = 5 + 5 + 3: last chunk ragged
    ids = rng.randint(0, cfg.vocab_size, size=(1, T)).astype(np.int32)
    plane = T + C                   # slack so pad-column writes never clamp

    ref_cache = init_cache(g, 1, plane)
    ref_logits, ref_cache = forward(params, g, jnp.asarray(ids), ref_cache)

    cache = init_cache(g, 1, plane)
    got = []
    append = jax.jit(append_forward, static_argnums=1)
    for s in range(0, T, C):
        n = min(C, T - s)
        sl = np.zeros((1, C), np.int32)
        sl[0, :n] = ids[0, s:s + n]
        logits, cache = append(params, g, jnp.asarray(sl), cache,
                               n_valid=jnp.asarray([n]))
        got.append(np.asarray(logits)[0, :n])  # pad-row logits are garbage
        assert int(cache["pos"][0]) == s + n  # frontier moved by n, not C

    np.testing.assert_allclose(np.concatenate(got, axis=0),
                               np.asarray(ref_logits)[0],
                               rtol=2e-4, atol=2e-4)
    assert int(cache["pos"][0]) == T
    # The cache below the frontier is the whole-prefill cache exactly
    # (identical writes); pad columns beyond T may hold garbage.
    np.testing.assert_array_equal(np.asarray(cache["k"])[:, :, :, :T],
                                  np.asarray(ref_cache["k"])[:, :, :, :T])
    np.testing.assert_array_equal(np.asarray(cache["v"])[:, :, :, :T],
                                  np.asarray(ref_cache["v"])[:, :, :, :T])
