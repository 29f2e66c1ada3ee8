"""The group-limited sigmoid router (``moe/routed.py`` ``route_grouped``:
DeepSeek-V3's ``noaux_tc``) against a reference written as loops, and the
chip's SHARE of an expert layer tied to the uncut model: the routed parts of
all the shares, with the shared expert counted once, add up to the uncut
reference's layer (model-configs guide, section 4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import deepseek_v3 as reference
from deepspeed_tpu.models import decoder
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from deepspeed_tpu.moe import routed

builder = harness.load_by_name("model_builders", "deepseek_v3")


def looped(logits, bias, k, n_group, topk_group, scale, renormalise=True):
    """The router a token, an expert and a group at a time, from the
    report's words (``benchmark/reference/deepseek_v3.py`` docstring)."""
    t, e = logits.shape
    size = e // n_group
    weights, experts = [], []
    for row in np.asarray(logits, np.float64):
        s = 1.0 / (1.0 + np.exp(-row))
        biased = s + np.asarray(bias, np.float64)
        group_score = []
        for g in range(n_group):
            members = sorted(biased[g * size:(g + 1) * size])
            group_score.append(members[-1] + members[-2])
        kept = sorted(range(n_group), key=lambda g: -group_score[g])[
            :topk_group]
        eligible = [biased[i] if i // size in kept else 0.0
                    for i in range(e)]
        chosen = sorted(range(e), key=lambda i: -eligible[i])[:k]
        w = np.asarray([s[i] for i in chosen])
        if renormalise:
            w = w / (w.sum() + 1e-20)
        weights.append(w * scale)
        experts.append(chosen)
    return np.asarray(weights), np.asarray(experts)


def as_gate(weights, experts, e):
    gate = np.zeros((len(weights), e))
    for t, (w, idx) in enumerate(zip(weights, experts)):
        gate[t, idx] = w
    return gate


@pytest.mark.parametrize("e, n_group, topk_group, k, seed", [
    (256, 8, 4, 8, 0), (16, 4, 2, 3, 1), (32, 1, 1, 4, 2)])
def test_the_grouped_sigmoid_router_is_the_looped_one(e, n_group, topk_group,
                                                      k, seed):
    rs = np.random.RandomState(seed)
    logits = jnp.asarray(rs.randn(40, e) * 1.7, jnp.float32)
    bias = jnp.asarray(rs.randn(e) * 0.1, jnp.float32)
    w, idx = routed.route_grouped(logits, bias, k, n_group, topk_group, 2.5)
    want_w, want_idx = looped(logits, bias, k, n_group, topk_group, 2.5)
    assert idx.dtype == jnp.int32 and w.shape == (40, k)
    np.testing.assert_allclose(as_gate(np.asarray(w), np.asarray(idx), e),
                               as_gate(want_w, want_idx, e), atol=2e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)


def test_a_tokens_best_experts_in_a_cut_group_are_not_chosen():
    """Group 0 holds the single best expert and nothing else of note; groups
    1 and 2 each hold two good ones. With 2 of 4 groups kept, group 0 is cut
    (its 2 largest sum to less), and its best expert with it."""
    logits = np.full((1, 16), -4.0, np.float32)
    logits[0, 0] = 6.0                      # the best expert, alone in group 0
    logits[0, [4, 5]] = 3.0                 # group 1
    logits[0, [8, 9]] = 2.5                 # group 2
    w, idx = routed.route_grouped(jnp.asarray(logits), jnp.zeros((16,)), 3, 4,
                                  2, 1.0)
    assert 0 not in np.asarray(idx) and set(np.asarray(idx)[0]) <= {4, 5, 8, 9}
    ungrouped, idx1 = routed.route_grouped(jnp.asarray(logits),
                                           jnp.zeros((16,)), 3, 1, 1, 1.0)
    assert 0 in np.asarray(idx1)


def test_the_bias_changes_the_choice_and_not_the_weight():
    """Expert 3 scores a little under expert 2; a bias lifts it over. It is
    then chosen, and weighted by its score WITHOUT the bias."""
    logits = np.full((1, 8), -3.0, np.float32)
    logits[0, :4] = [2.0, 1.5, 1.0, 0.9]
    plain_w, plain = routed.route_grouped(jnp.asarray(logits),
                                          jnp.zeros((8,)), 3, 1, 1, 1.0,
                                          renormalise=False)
    assert set(np.asarray(plain)[0]) == {0, 1, 2}
    bias = jnp.zeros((8,)).at[3].set(0.2)
    w, idx = routed.route_grouped(jnp.asarray(logits), bias, 3, 1, 1, 1.0,
                                  renormalise=False)
    assert set(np.asarray(idx)[0]) == {0, 1, 3}
    at = list(np.asarray(idx)[0]).index(3)
    sigmoid = 1.0 / (1.0 + np.exp(-0.9))
    np.testing.assert_allclose(float(w[0, at]), sigmoid, rtol=1e-6)


def test_the_softmax_router_is_what_it_was():
    logits = jnp.asarray(np.random.RandomState(3).randn(20, 8), jnp.float32)
    w, idx = routed.route(logits, 2)
    probs = np.asarray(jax.nn.softmax(logits, -1))
    np.testing.assert_allclose(np.asarray(w), np.sort(probs, -1)[:, :-3:-1],
                               rtol=1e-6)


# ------------------------------------------------------------- the shares

CFG = DecoderConfig(
    vocab_size=128, n_layer=2, n_head=4, head_dim=24, hidden_size=64,
    n_positions=512, n_experts=32, experts_per_token=4, expert_width=32,
    rms_norm_eps=1e-6, qk_norm=False, norm_topk_prob=True,
    dtype=jnp.float32, initializer_range=0.15, shared_width=32,
    kv_lora_rank=32, q_lora_rank=24, qk_nope_dim=16, qk_rope_dim=8,
    v_head_dim=16, dense_layers=1, dense_width=96, router_scoring="sigmoid",
    n_group=4, topk_group=2, routed_scaling=2.5)


def test_the_32_shares_add_up_to_the_uncut_layer():
    """32 chips share the expert layer, one expert each here (8 of 256 at
    the cell's size): the PROGRAM's routed part for each share ``(first,
    1)``, summed over the 32, plus the shared expert once, is the uncut
    REFERENCE's feed-forward for the same tokens; and one share of the
    program is that share of the reference."""
    key = jax.random.PRNGKey(2)
    whole = builder.rescaled(DecoderLM(CFG).init(key)["params"], key, 1.0,
                             0.1)
    x = jnp.asarray(np.random.RandomState(4).randn(1, 24, 64), jnp.float32)
    moe = {k: v[0] for k, v in whole["moe"].items()}
    layer = dict(moe, ffn_norm=whole["layers"]["ffn_norm"][1])

    def program(first, count, shared=True):
        cfg = CFG._replace(experts_held=(first, count),
                           shared_width=CFG.shared_width if shared else 0)
        share = dict(layer, w_gate_up=layer["w_gate_up"][first:first + count],
                     w_down=layer["w_down"][first:first + count])
        out, load, absent = decoder.moe(share, cfg, x)
        return np.asarray(out - x)[0], float(load.sum()), float(absent)

    h = reference._rms(x[0], layer["ffn_norm"], CFG.rms_norm_eps)
    hyper = builder.hyper(CFG)
    names = list(builder.published_names(whole, CFG)["layers"])[1]
    with jax.default_matmul_precision("highest"):
        kept, _ = reference.router(h, names, hyper)
        want, = reference.feed_forward([h], [kept], names, hyper)
    parts, routed_tokens = np.zeros((24, 64), np.float32), 0.0
    for first in range(32):
        out, load, absent = program(first, 1, shared=False)
        parts += out
        routed_tokens += load
        assert load + absent == 24 * 4
    assert routed_tokens == 24 * 4          # every choice lands on one share
    shared_once = program(0, 1)[0] - program(0, 1, shared=False)[0]
    np.testing.assert_allclose(parts + shared_once, np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # a share of 8, as the cell holds: the reference given the same share
    held8 = dict(whole, moe=dict(whole["moe"], **{
        k: whole["moe"][k][:, 8:16] for k in ("w_gate_up", "w_down")}))
    names8 = list(builder.published_names(held8, CFG)["layers"])[1]
    want8, = reference.feed_forward([h], [kept], names8,
                                    dict(hyper, held=(8, 8)))
    np.testing.assert_allclose(
        program(8, 8)[0], np.asarray(want8), rtol=2e-4, atol=2e-4)
