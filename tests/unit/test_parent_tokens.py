"""The families the benchmark already serves emit, token for token, what they
emitted on the parent of PR 38 (``tests/fixtures/parent_tokens_pr38.json``,
recorded at e8ffd98 BEFORE the first edit by this file's ``served``): GPT-2's
block, the OLMoE-shaped and the Granite-shaped decoder at tiny sizes, through
the gather path (pages of 8) and through the interpreted kernels (pages of
128). Latent attention, the dense layer, YaRN and the sigmoid router are
static branches that are off for them, so nothing they lower may move.

PR 42 (a linear-attention mixer, a latent plane in a subset of the layers
beside a recurrent state) pins the same three again and a DeepSeek-shaped
decoder with them (latent plane, a leading dense layer, the sigmoid grouped
router over a share of the experts), as recorded at 5869b3c before its first
edit (``parent_tokens_pr42.json``): the new kind of layer and the composed
cache are static branches that are off for all four.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

FIXTURES = os.path.join(os.path.dirname(__file__), "..", "fixtures")
RECORDED = {pr: json.load(open(os.path.join(
    FIXTURES, "parent_tokens_{}.json".format(pr))))["tokens"]
    for pr in ("pr38", "pr42")}

OLMOE = DecoderConfig(
    vocab_size=256, n_layer=2, n_head=4, head_dim=16, hidden_size=64,
    n_positions=256, n_experts=8, experts_per_token=2, expert_width=32,
    dtype=jnp.float32)
GRANITE = DecoderConfig(
    vocab_size=256, n_layer=4, n_head=4, head_dim=16, hidden_size=64,
    n_positions=256, n_experts=8, experts_per_token=3, expert_width=32,
    qk_norm=False, norm_topk_prob=True, tie_word_embeddings=True,
    dtype=jnp.float32, initializer_range=0.05, n_kv_head=2, rope=False,
    attn_scale=1 / 16.0, embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=4.0, shared_width=48, experts_held=(0, 4),
    layer_types=("mamba", "attention", "mamba", "mamba"), mamba_heads=4,
    mamba_head_dim=8, mamba_state=16, mamba_conv=4, mamba_chunk=8)
DEEPSEEK = DecoderConfig(      # as tests/unit/test_mla.py sizes it
    vocab_size=256, n_layer=3, n_head=4, head_dim=24, hidden_size=64,
    n_positions=4096, n_experts=16, experts_per_token=3, expert_width=32,
    rms_norm_eps=1e-6, qk_norm=False, norm_topk_prob=True,
    dtype=jnp.float32, initializer_range=0.15, shared_width=32,
    experts_held=(0, 8), kv_lora_rank=32, q_lora_rank=24, qk_nope_dim=16,
    qk_rope_dim=8, v_head_dim=16, rope_yarn=(40.0, 64, 32.0, 1.0, 1.0, 1.0),
    dense_layers=1, dense_width=96, router_scoring="sigmoid", n_group=4,
    topk_group=2, routed_scaling=2.5)


def built(family):
    if family == "gpt2":
        cfg = GPT2Config.tiny()
        model = GPT2LMHeadModel(cfg)
        return model, jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros(
            (1, 8), jnp.int32))["params"], cfg.vocab_size
    cfg = {"olmoe": OLMOE, "granite": GRANITE, "deepseek": DEEPSEEK}[family]
    model = DecoderLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))["params"]
    if family == "deepseek":    # the selection bias drawn, not zero
        shape = params["moe"]["router_bias"].shape
        params["moe"] = dict(params["moe"], router_bias=0.1
                             * jax.random.normal(jax.random.PRNGKey(38), shape))
    if family == "granite":     # as tests/unit/test_hybrid.py scales them
        params = dict(params, embed=params["embed"] * 0.2,
                      final_norm=params["final_norm"] * 25.0)
    return model, params, cfg.vocab_size


@functools.lru_cache(maxsize=None)
def served(family, kernels):
    """What ``family`` serves through one path: the same run whichever
    recording it is held to (three families are in both)."""
    model, params, vocab = built(family)
    eng = InferenceEngine(model, params, config=dict(
        max_slots=3, max_len=128 if kernels else 64, chunk_size=4,
        prefill_chunk=8, use_flash_decode=kernels, paged_kv=True,
        kv_page_len=128 if kernels else 8))
    rs = np.random.RandomState(7)
    reqs = [eng.submit(rs.randint(0, vocab, size=n).astype(np.int32),
                       max_new_tokens=m)
            for n, m in ((5, 12), (13, 9), (8, 16), (3, 7))]
    eng.run()
    return [[int(t) for t in r.tokens] for r in reqs]


@pytest.mark.parametrize("pr, case", [
    (pr, case) for pr in sorted(RECORDED) for case in sorted(RECORDED[pr])])
def test_the_served_tokens_are_the_parents(pr, case):
    family, path = case.split(".")
    assert served(family, path == "kernels") == RECORDED[pr][case]
