"""Generation by DIFFUSION OVER BLOCKS (``DecoderConfig.block_length`` > 1):
the one visibility rule, a pass through the paged cache, the engine's scan
against the plain reference's generation loop token for token AND pass for
pass, the counters, what ``bind`` refuses, and that a next-token model's
step is the program it was.

The reference is the benchmark's (``benchmark/reference/sdar_moe.py``, float32,
no cache, no kernels, nothing of ``deepspeed_tpu``); the model here is its
block at a size a CPU runs, in float32 so that the two agree to rounding.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from benchmark import harness
from benchmark.reference import sdar_moe as reference
from deepspeed_tpu.inference.adapters import DecoderAdapter
from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from deepspeed_tpu.ops.transformer.kernels import decode_attention as da

builder = harness.load_by_name("model_builders", "sdar_moe")

LENGTH, MASK = 4, 96
CFG = DecoderConfig(
    vocab_size=97, n_layer=2, n_head=4, head_dim=16, hidden_size=32,
    n_positions=256, n_experts=8, experts_per_token=2, expert_width=16,
    rms_norm_eps=1e-6, rope_theta=1e6, qk_norm="head", norm_topk_prob=True,
    dtype=jnp.float32, n_kv_head=2, block_length=LENGTH, mask_token_id=MASK,
    initializer_range=0.2)
ENGINE = dict(max_slots=3, max_len=64, chunk_size=5, paged_kv=True,
              kv_page_len=8, prefill_chunk=8, use_flash_decode=False)
# (prompt length, max_new_tokens, denoising steps): a prompt that ends inside
# a block, one shorter than a block, a budget that ends inside a block, a
# prompt of whole lane slices (its tail rides one more), every step count.
REQUESTS = ((5, 9, 2), (3, 6, 1), (8, 8, 4), (17, 7, 2), (2, 3, 4),
            (12, 10, 2), (16, 4, 1), (1, 1, 2))


@pytest.fixture(scope="module")
def params():
    return DecoderLM(CFG).init(jax.random.PRNGKey(0))["params"]


@pytest.fixture(scope="module")
def tree(params):
    return builder.published_names(params, CFG)


@pytest.fixture(scope="module")
def served(params):
    """ONE engine, every request of ``REQUESTS`` through it at once (3 slots:
    they queue, share steps and reuse slots), read by the cases below."""
    engine = deepspeed.init_inference(
        model=DecoderLM(CFG), params=params, config={"inference": ENGINE})
    rng = np.random.RandomState(0)
    handles = []
    for p, n, s in REQUESTS:
        prompt = rng.randint(0, MASK, size=p)
        handles.append((prompt, n, s, engine.submit(
            prompt, max_new_tokens=n, denoising_steps=s)))
    engine.run()
    return engine, handles


def test_the_rule_is_the_causal_one_at_block_length_1():
    q_pos = jnp.arange(10)
    assert da.visible_upto(q_pos, 1) is q_pos
    np.testing.assert_array_equal(
        da.visible_upto(q_pos, 4), [3, 3, 3, 3, 7, 7, 7, 7, 11, 11])
    np.testing.assert_array_equal(
        np.asarray(reference.visible(np.arange(6), np.arange(6), 2)),
        np.arange(6)[None, :] <= np.asarray([1, 1, 3, 3, 5, 5])[:, None])


def test_the_references_block_forward_is_its_own_pass_block_by_block(tree):
    """(2a) against (2b): a noisy copy with nothing masked reads the clean
    logits; a masked block reads what the whole-sequence forward reads with
    that block masked and NOTHING after it (a noisy query sees the clean
    blocks before it and its own)."""
    sizes = builder.hyper(CFG)
    ids = np.random.RandomState(1).randint(0, MASK, size=12)
    clean = np.asarray(reference.logits(tree, ids, MASK, **sizes))
    none = np.zeros(12, bool)
    np.testing.assert_allclose(np.asarray(reference.noisy_logits(
        tree, ids, ids, none, MASK, **sizes)), clean, atol=2e-5)
    masked = np.zeros(12, bool)
    masked[[4, 6, 8, 9, 10, 11]] = True
    noisy = np.asarray(reference.noisy_logits(tree, ids, ids, masked, MASK,
                                              **sizes))
    for first in (4, 8):
        upto = np.zeros(first + 4, bool)
        upto[first:] = masked[first:first + 4]
        want = np.asarray(reference.logits(tree, ids[:first + 4], MASK, upto,
                                           **sizes))[first:]
        np.testing.assert_allclose(noisy[first:first + 4], want, atol=2e-5)
    assert np.abs(noisy[4:8] - clean[4:8]).max() > 1e-3


def _passes_through_the_cache(cfg, params, prompt, block_ids, masked, page):
    """Logits of a denoising pass and of the commit pass after it, through a
    paged cache: the prompt's whole blocks prefilled, then ``block_ids`` with
    ``masked`` positions as the mask id, then the finished block."""
    from deepspeed_tpu.inference import kv_pool

    adapter = DecoderAdapter.from_model(
        DecoderLM(cfg), use_flash_decode=cfg.use_flash_decode).bind(
        InferenceConfig.from_dict(dict(
            max_slots=1, max_len=128, paged_kv=True, kv_page_len=page,
            prefill_chunk=8, use_flash_decode=cfg.use_flash_decode)), None)
    pool = kv_pool.init_pool(adapter.cache_spec(), 1, page, slack=page,
                             page_len=page, num_pages=2)
    cache = dict(kv_pool.cache_view(pool), block_tbl=jnp.asarray([[1, 2]]),
                 **adapter.aux_state())
    lane = np.zeros((1, 8), np.int32)
    lane[0, :len(prompt)] = prompt

    @jax.jit
    def passes(cache, lane, noisy_ids, final_ids):
        _, cache = adapter.prefill_append(
            params, lane, cache, n_valid=jnp.asarray([len(prompt)]))
        noisy, cache = adapter.block_pass(params, noisy_ids, cache)
        final, after = adapter.block_pass(params, final_ids, cache)
        return noisy[0], final[0], cache["pos"], after["pos"]

    noisy, final, pos, after = passes(
        cache, jnp.asarray(lane),
        jnp.asarray([np.where(masked, cfg.mask_token_id, block_ids)]),
        jnp.asarray([block_ids]))
    assert int(pos[0]) == int(after[0]) == len(prompt)  # a pass moves nothing
    return np.asarray(noisy), np.asarray(final)


@pytest.mark.parametrize("kernel", [False, True], ids=["einsum", "paged"])
def test_passes_through_the_paged_cache_give_the_references_logits(
        params, tree, kernel):
    """Prefill, a denoising pass and the commit pass THROUGH the paged cache
    against the reference's noisy logits: the einsum path (a page of 8), and
    the paged kernel interpreted (a page of 128; 2 query heads a stored head
    x 4 positions = 8 rows share one read of a slot's keys, ``prefill_attn``
    and ``kv_append`` under the same rule)."""
    cfg = CFG._replace(use_flash_decode=kernel)
    rng = np.random.RandomState(2)
    prompt, block = rng.randint(0, MASK, size=8), rng.randint(0, MASK, size=4)
    masked = np.asarray([True, False, True, True])
    noisy, final = _passes_through_the_cache(
        cfg, params, prompt, block, masked, 128 if kernel else 8)
    ids = np.concatenate([prompt, block])
    sizes = builder.hyper(CFG)
    flags = np.concatenate([np.zeros(8, bool), masked])
    np.testing.assert_allclose(noisy, np.asarray(reference.noisy_logits(
        tree, ids, ids, flags, MASK, **sizes))[8:], atol=3e-5)
    np.testing.assert_allclose(final, np.asarray(reference.logits(
        tree, ids, MASK, **sizes))[8:], atol=3e-5)


@pytest.mark.parametrize("at", range(len(REQUESTS)),
                         ids=["p{}_new{}_S{}".format(*r) for r in REQUESTS])
def test_the_engines_tokens_and_passes_are_the_references(served, tree, at):
    """The engine's scan (three requests a step, slots reused, the lane's
    tail columns, a last block cut short) against the reference's generation
    loop: the same tokens, each unmasked in the same pass of its block."""
    prompt, n, steps, handle = served[1][at]
    tokens, passes, _ = reference.generate(tree, prompt, n, steps, MASK,
                                           **builder.hyper(CFG))
    assert handle.done and len(handle.tokens) == n
    assert handle.tokens == tokens.tolist()
    assert list(handle.passes) == passes.tolist()
    assert not handle.open_lanes


def test_counters_are_exact_and_one_program_serves_every_request(served,
                                                                 tree):
    engine, handles = served
    want = {"passes": 0, "commit_passes": 0, "tokens_unmasked": 0}
    for prompt, n, steps, _ in handles:
        counts = reference.generate(tree, prompt, n, steps, MASK,
                                    **builder.hyper(CFG))[2]
        want = {k: want[k] + counts[k] for k in want}
    c = engine.counters
    assert c["diffusion_passes"] == want["passes"]
    assert c["diffusion_commit_passes"] == want["commit_passes"] \
        == c["diffusion_blocks_committed"]
    assert c["diffusion_tokens_unmasked"] == want["tokens_unmasked"] \
        == c["tokens_out"] == sum(n for _, n, _ in REQUESTS)
    # a LIVE slot an iteration is occupied, a commit pass too
    assert c["occupied_slot_steps"] == want["passes"]
    assert engine.compile_count == 1
    m = engine.metrics()
    assert sum(m["unmasked_per_pass_hist"]) == want["passes"]
    assert m["unmasked_per_pass_hist"][0] >= want["commit_passes"]
    assert "diffusion_unmasked_per_pass" in engine.prometheus()
    assert all(h.first_token_time is not None for *_, h in handles)


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_tokens_per_pass_is_block_over_steps_plus_one_once_full(params,
                                                                steps):
    engine = deepspeed.init_inference(
        model=DecoderLM(CFG), params=params,
        config={"inference": dict(ENGINE, denoising_steps=steps)})
    prompt = np.arange(8) % MASK
    handles = [engine.submit(prompt, max_new_tokens=24) for _ in range(3)]
    engine.run()
    assert all(len(h.tokens) == 24 for h in handles)
    m = engine.metrics()
    assert m["tokens_per_pass"] == pytest.approx(LENGTH / (steps + 1.0),
                                                 abs=1e-3)
    assert m["commit_pass_share"] == pytest.approx(1.0 / (steps + 1),
                                                   abs=1e-3)


REFUSED = {
    "spec_decode": (dict(spec_decode=True), "speculative decoding"),
    "prefix_cache": (dict(prefix_cache=True, prefix_len=16,
                          min_prefix_len=4), "the prefix cache"),
    "int8_kv": (dict(int8_kv=True), "int8 planes"),
    "host_offload": (dict(host_offload=True), "host offload"),
    "page_of_broken_blocks": (dict(paged_kv=True, kv_page_len=6),
                              "whole blocks"),
    "steps_that_do_not_divide": (dict(denoising_steps=3), "must divide"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_bind_refuses_by_name_what_cannot_compose_yet(name):
    keys, words = REFUSED[name]
    config = InferenceConfig.from_dict(dict(
        max_slots=2, max_len=64, prefill_chunk=8, **keys))
    with pytest.raises(ValueError, match=words):
        DecoderAdapter.from_model(DecoderLM(CFG)).bind(config, None)


def test_submit_refuses_what_the_rule_does_not_build(served, params):
    engine = served[0]
    for kw in (dict(temperature=0.7), dict(top_k=4), dict(eos_token_id=3),
               dict(denoising_steps=3)):
        with pytest.raises(ValueError):
            engine.submit(np.arange(4), max_new_tokens=4, **kw)
    plain = deepspeed.init_inference(
        model=DecoderLM(CFG._replace(block_length=1, mask_token_id=None)),
        params=params, config={"inference": dict(max_slots=1, max_len=64)})
    with pytest.raises(ValueError, match="one a pass"):
        plain.submit(np.arange(4), max_new_tokens=4, denoising_steps=2)


# sha256 of the lowered text of the ONE mixed step, as the commit before block
# diffusion (ed63260) lowers it on this installation's CPU: tiny GPT-2 and
# tiny OLMoE, dense and paged pools. A model of block length 1 must trace the
# program it always has (``visible_upto`` returns its argument, the lane and
# the scan are picked at trace time).
PARENT_LOWERS = {
    ("olmoe", False):
        "21fbaab82ab66dac14454218e9d1499f08ca47fd57c130d17bf93b2c343d9ad6",
    ("olmoe", True):
        "c531d2871d9d25c70b695c0f446297da400601710ec763d4b5d8a401cf67ee91",
    ("gpt2", False):
        "5c1807fd0d0ee974856c1e0acde2c96ee5e6dfc5621462f163605c47fa3dfe95",
    ("gpt2", True):
        "3d3d67a9d8b21fc57c7cdef48dfbc69aef63874f7a3e24b2afb3fd7dc7196351",
}


def _lowered_step(model, params, paged):
    e = deepspeed.init_inference(model=model, params=params, config={
        "inference": dict(perf_xray=False, max_slots=2, max_len=64,
                          chunk_size=2, prefill_chunk=8, paged_kv=paged,
                          kv_page_len=8, use_flash_decode=False)})
    return e._mixed.lower(
        e._params, e._adapter, e.config.chunk_size, e._spec, e._pool,
        jnp.zeros((1, 8), jnp.int32), jnp.int32(0), jnp.int32(0),
        jnp.int32(0), jnp.asarray(False), jnp.asarray(False), jnp.int32(1),
        jnp.int32(-1), jnp.float32(0), jnp.int32(0),
        jnp.uint32(0)).as_text()


@pytest.mark.parametrize("family, paged", sorted(PARENT_LOWERS))
def test_block_length_1_lowers_the_step_the_parent_lowers(family, paged):
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    if family == "olmoe":
        model = DecoderLM(DecoderConfig(
            vocab_size=64, n_layer=2, n_head=4, head_dim=8, hidden_size=32,
            n_positions=128, n_experts=4, experts_per_token=2,
            expert_width=16, dtype=jnp.float32))
        params = model.init(jax.random.PRNGKey(0))["params"]
    else:
        model = GPT2LMHeadModel(GPT2Config(
            vocab_size=64, n_positions=128, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32))
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    text = _lowered_step(model, params, paged)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_LOWERS[family, paged]


def test_a_replay_after_a_fault_makes_an_open_block_again_whole(params, tree):
    """Recovery drops the tokens of a block that was open when the pool died
    (the one place a handle's tokens shrink) and the replayed stream is the
    reference's all the same."""
    engine = deepspeed.init_inference(
        model=DecoderLM(CFG), params=params,
        config={"inference": dict(ENGINE, chunk_size=2,
                                  fault_injection=True)})
    from deepspeed_tpu.inference.faults import Fault, FaultPlan

    prompt = np.arange(6) % MASK
    handle = engine.submit(prompt, max_new_tokens=14, denoising_steps=2)
    engine.inject_faults(FaultPlan((Fault("raise", 3),)))
    engine.run()
    tokens, _, _ = reference.generate(tree, prompt, 14, 2, MASK,
                                      **builder.hyper(CFG))
    assert engine.counters["recoveries"] == 1
    assert handle.done and handle.tokens == tokens.tolist()
