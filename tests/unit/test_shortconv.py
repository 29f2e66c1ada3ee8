"""The gated short convolution (``models/shortconv.py``) and the stack it
serves in (``models/decoder.py``: conv layers whose only state is a two-row
tail a slot beside rotary grouped-query attention with a QK norm a head over
a paged cache as deep as the attention layers only, a leading dense layer,
the sigmoid router over experts held whole) against the plain reference
(``benchmark/reference/lfm2_moe.py``) at a small size in float32: hidden 512,
4 layers (conv, conv, attention, conv) of which the first dense, 8 query heads
over 2 stored heads of 64 (so the paged arena packs ``g = 2`` stored heads a
lane tile and ``rep = 4`` query heads share each, the cell's own packing and
grouping), 8 experts, top-2.

Tolerances: float32 end to end. The program sums a convolution's three taps
in the reference's order and rounds ``v`` to the tail's type before either
reads it, so the two agree to a rounding of the projections (1e-5 on outputs
of order 0.1); whole logits 2e-4 on a spread of 0.7 (measured 6e-6). What
must not move does not move by one bit, and a prompt's tails are the same
bit for bit however it was chunked.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import lfm2_moe as reference
from deepspeed_tpu.inference import InferenceEngine, kv_pool
from deepspeed_tpu.inference.adapters import DecoderAdapter
from deepspeed_tpu.models import decoder, shortconv
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from deepspeed_tpu.ops.transformer.kernels import decode_attention as da
from tests.unit.compiled import compiled, served_alone
from tests.unit.test_telemetry import _parse_prom

builder = harness.load_by_name("model_builders", "lfm2_moe")

CFG = DecoderConfig(
    vocab_size=256, n_layer=4, n_head=8, head_dim=64, hidden_size=512,
    n_positions=4096, n_experts=8, experts_per_token=2, expert_width=64,
    rope_theta=1e6, qk_norm="head", norm_topk_prob=True,
    tie_word_embeddings=True, dtype=jnp.float32, initializer_range=0.05,
    n_kv_head=2, layer_types=("shortconv", "shortconv", "attention",
                              "shortconv"),
    dense_layers=1, dense_width=128, router_scoring="sigmoid")
TOL = dict(rtol=2e-4, atol=2e-4)
SAME = dict(rtol=1e-5, atol=1e-5)
STATE = ("slot_shortconv0", "slot_shortconv1", "slot_shortconv2")


@pytest.fixture(scope="module")
def model():
    m = DecoderLM(CFG)
    key = jax.random.PRNGKey(0)
    # the selection bias drawn, not zero: choosing with it and weighting
    # without it then differ; the norms a head not at 1: a norm over the
    # whole width with the same numbers would then differ
    params = builder.rescaled(jax.jit(m.init)(key)["params"], key, 1.0, 0.6,
                              0.1)
    attn = params["attn"]
    params["attn"] = dict(
        attn, q_norm=1.0 + 0.3 * jax.random.normal(key, attn["q_norm"].shape),
        k_norm=1.0 + 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                              attn["k_norm"].shape))
    return m, params


@pytest.fixture(scope="module")
def adapter(model):
    return DecoderAdapter.from_model(model[0], use_flash_decode=False)


def tokens(n, seed=0, rows=1):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, size=(rows, n)).astype(np.int32)


def engine(model, **kw):
    kw = dict(dict(max_slots=3, max_len=64, chunk_size=4, prefill_chunk=8,
                   use_flash_decode=False, paged_kv=True, kv_page_len=8),
              **kw)
    return InferenceEngine(model[0], model[1], config=kw)


def alone(model, prompt, n, **kw):
    return served_alone(engine, model, prompt, n, **kw)


def layer(dtype=jnp.float32, seed=0, c=64):
    cfg = CFG._replace(hidden_size=c, dtype=dtype)
    return cfg, shortconv.init_layer(jax.random.PRNGKey(seed), cfg)


def through(cfg, p, hid, chunk, lane=None, tail=None):
    """``hid`` [B, T, C] through the mixer in slices of ``chunk`` tokens,
    each padded to ``lane`` columns with ``n_valid`` the real ones (as the
    prefill lane hands a prompt over): (out [B, T, C], the tail after)."""
    b, t, c = hid.shape
    lane = lane or chunk
    if tail is None:
        tail = jnp.zeros((b, cfg.shortconv_kernel - 1, c), cfg.dtype)
    outs = []
    for lo in range(0, t, chunk):
        n = min(chunk, t - lo)
        piece = jnp.pad(hid[:, lo:lo + n], ((0, 0), (0, lane - n), (0, 0)),
                        constant_values=7.0)         # a pad column is junk
        out, tail = shortconv.mixer(
            p, cfg, piece, tail, jnp.full((b,), lo, jnp.int32),
            jnp.full((b,), n, jnp.int32))
        outs.append(out[:, :n])
    return jnp.concatenate(outs, axis=1), tail


# ------------------------------------------------------------- the mixer


def test_the_mixer_is_the_references_operator():
    cfg, p = layer()
    hid = jax.random.normal(jax.random.PRNGKey(1), (2, 37, 64))
    got, tail = through(cfg, p, hid, 37)
    names = {"in_proj": p["in_proj"], "conv": p["conv_w"],
             "out_proj": p["out_proj"]}
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            seen = {}
            want = reference.short_conv(hid[b], names, {}, seen=seen)
            np.testing.assert_allclose(np.asarray(got[b]), want, **SAME)
            np.testing.assert_allclose(np.asarray(tail[b]), seen["tail"],
                                       **SAME)
    assert float(jnp.abs(got).max()) > 0.01


def test_the_rolling_one_token_form_is_the_whole_sequence_form():
    """At EVERY position: 37 one-token calls, each reading the two rows the
    last ones left, against one call over the sequence."""
    cfg, p = layer()
    hid = jax.random.normal(jax.random.PRNGKey(2), (2, 37, 64))
    whole, tail = through(cfg, p, hid, 37)
    rolled, rolled_tail = through(cfg, p, hid, 1)
    np.testing.assert_allclose(np.asarray(rolled), np.asarray(whole), **SAME)
    np.testing.assert_array_equal(np.asarray(rolled_tail), np.asarray(tail))


@pytest.mark.parametrize("chunk", [1, 2, 3, 128])
def test_a_prompts_tail_and_output_do_not_depend_on_its_chunking(chunk):
    """In bf16, as the cell serves: ``v`` is rounded to the tail's type
    before the convolution reads it, so a slice shorter than the kernel
    (1, 2: the tail then crosses TWO boundaries) reads what a whole slice
    read, bit for bit in the tail."""
    cfg, p = layer(jnp.bfloat16)
    hid = jax.random.normal(jax.random.PRNGKey(3), (2, 37, 64)).astype(
        jnp.bfloat16)
    want, want_tail = through(cfg, p, hid, 37)
    got, tail = through(cfg, p, hid, chunk, lane=max(chunk, 2))
    np.testing.assert_array_equal(np.asarray(tail, np.float32),
                                  np.asarray(want_tail, np.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-3)


def test_a_frozen_row_does_not_move_and_a_fresh_row_starts_from_zeros():
    cfg, p = layer()
    key = jax.random.PRNGKey(4)
    hid = jax.random.normal(key, (3, 5, 64))
    held = jax.random.normal(jax.random.fold_in(key, 1), (3, 2, 64))
    held = held.at[2].set(jnp.nan)           # a slot may hold anything
    pos = jnp.asarray([9, 0, 0], jnp.int32)
    # row 0 decodes on, row 1 is frozen AT frontier 0, row 2 starts anew
    for s in (1, 5):
        out, tail = shortconv.mixer(p, cfg, hid[:, :s], held, pos,
                                    jnp.asarray([s, 0, s], jnp.int32))
        np.testing.assert_array_equal(np.asarray(tail[1]),
                                      np.asarray(held[1]))
        assert np.isfinite(np.asarray(out[2])).all()
        zeros, fresh = shortconv.mixer(
            p, cfg, hid[2:, :s], jnp.zeros((1, 2, 64)), pos[2:],
            jnp.asarray([s], jnp.int32))
        np.testing.assert_array_equal(np.asarray(out[2]),
                                      np.asarray(zeros[0]))
        np.testing.assert_array_equal(np.asarray(tail[2]),
                                      np.asarray(fresh[0]))
        assert np.abs(np.asarray(tail[0] - held[0])).max() > 0


def test_a_pad_column_never_enters_the_tail():
    cfg, p = layer()
    hid = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 64))
    held = jax.random.normal(jax.random.PRNGKey(6), (2, 2, 64))
    pos = jnp.asarray([4, 4], jnp.int32)
    n_valid = jnp.asarray([1, 6], jnp.int32)
    _, tail = shortconv.mixer(p, cfg, hid, held, pos, n_valid)
    for b, n in enumerate((1, 6)):
        _, want = shortconv.mixer(p, cfg, hid[b:b + 1, :n], held[b:b + 1],
                                  pos[:1], jnp.asarray([n], jnp.int32))
        np.testing.assert_array_equal(np.asarray(tail[b]),
                                      np.asarray(want[0]))
    # one real column of eight: the older of the two rows is the old tail's
    np.testing.assert_array_equal(np.asarray(tail[0, 0]),
                                  np.asarray(held[0, 1]))


def test_a_recurrent_kind_names_any_number_of_states():
    """Mamba-2, KDA and Mamba-1 carry a state and a tail a layer, the short
    convolution its tail alone; ``cache_spec`` names them all."""
    assert {kind: len(module.state_keys(0))
            for kind, module in decoder.RECURRENT.items()} == \
        {"mamba": 2, "kda": 2, "shortconv": 1, "mamba1": 2}
    spec = decoder.cache_spec(CFG)
    assert [s[0] for s in spec.slot_state] == list(STATE)
    assert all(shape == (2, 512) for _, shape, _ in spec.slot_state)
    assert (spec.n_layer, spec.n_head, spec.n_embd) == (1, 2, 128)
    assert kv_pool.slot_state_nbytes(spec) == 3 * 2 * 512 * 4
    # the cell's own: 9 layers x 2 rows x 2048 x 2 bytes = 72 KB a slot
    whole = decoder.cache_spec(CFG._replace(
        n_layer=12, hidden_size=2048, n_head=32, n_kv_head=8,
        dtype=jnp.bfloat16, layer_types=CFG.layer_types * 3))
    assert kv_pool.slot_state_nbytes(whole) == 73728
    assert 128 * kv_pool.slot_state_nbytes(whole) == 9437184


# ------------------------------------- grouped-query rows, packed heads


@pytest.mark.parametrize("s, name", [(1, None), (5, None),
                                     (128, "prefill_attn")])
def test_32_query_heads_over_8_packed_heads_of_64_through_the_kernels(
        s, name):
    """``kv_append`` then ``paged_decode`` (interpreted) at the cell's own
    heads: 8 stored heads of 64 lie two a lane tile (``g = 2``, 4 tiles a
    page) and 4 query heads share each (``rep = 4``), for the scan's one
    row, a verify's five and the lane's 128, against the gather path and
    against the dense reference by hand."""
    heads, kv_heads, d = 32, 8, 64
    rng = np.random.RandomState(s)
    b, page, n_lp, layers = 3, 128, 3, 2
    g = da.lane_pack(d, kv_heads)
    assert g == 2
    arenas = tuple(jnp.asarray(rng.randn(layers, 1 + b * n_lp, kv_heads // g,
                                         page, g * d), jnp.float32)
                   for _ in "kv")
    assert da.query_group(arenas[0], heads, d) == 4
    tbl = 1 + jnp.arange(b * n_lp, dtype=jnp.int32).reshape(b, n_lp)
    pos = jnp.asarray([5, 130, 127], jnp.int32)
    new = tuple(jnp.asarray(rng.randn(b, kv_heads, s, d), jnp.float32)
                for _ in "kv")
    q = jnp.asarray(rng.randn(b, heads, s, d), jnp.float32)
    k, v = da.kv_append(arenas, new, tbl, pos, layer=1)
    # the append is the scatter, bit for bit, and touches no other layer
    for got, old, x in zip((k, v), arenas, new):
        want = old[1]
        for row in range(b):
            for i in range(s):
                at = int(pos[row]) + i
                want = want.at[tbl[row, at // page], :, at % page].set(
                    da.pack_heads(x[row, :, i:i + 1], g)[:, 0])
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(old[0]))
    got = da.flash_decode_attention_paged(q, k, v, tbl, pos, scale=0.125,
                                          name=name, layer=1)
    want = da.decode_attention_paged_reference(q, k[1], v[1], tbl, pos,
                                               scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and by hand: query head j reads stored head j // 4
    planes = [jnp.repeat(da.gather_pages(a[1], tbl, kv_heads, g), 4, 1)
              for a in (k, v)]
    byhand = da.decode_attention_reference(q, *planes, pos, scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(byhand),
                               rtol=2e-5, atol=2e-5)


def test_the_norm_a_head_is_not_the_norm_over_the_width(model):
    """``qk_norm`` ``"head"``: one weight [64] over each head's lanes,
    before the rotation, against the reference's attention on the same
    normed stream; OLMoE's norm over the whole width would differ."""
    from deepspeed_tpu.models import generation

    weights = {k: v[0] for k, v in model[1]["attn"].items()}
    assert weights["q_norm"].shape == weights["k_norm"].shape == (64,)
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 11, 512))

    def mix(cfg, weights):
        cache = decoder.init_cache(cfg, 1, 16)
        attend = generation.CacheAttention(cfg, cache, 11)
        rope = decoder.rope_angles(attend.q_pos, cfg.head_dim,
                                   cfg.rope_theta)
        return decoder.attention_mix(weights, cfg, h, 0, rope, attend,
                                     attend.planes)[0][0]

    cfg = CFG._replace(use_flash_decode=False)
    names = builder.published_names(model[1], CFG)
    third = [layer for layer in names["layers"]][2]
    with jax.default_matmul_precision("highest"):
        want = reference.attention(h[0], third, builder.hyper(CFG))
    np.testing.assert_allclose(np.asarray(mix(cfg, weights)), want, **TOL)
    wide = dict(weights, q_norm=jnp.tile(weights["q_norm"], 8),
                k_norm=jnp.tile(weights["k_norm"], 2))
    other = mix(cfg._replace(qk_norm=True), wide)
    assert float(jnp.abs(other - want).max()) > 0.01


# -------------------------------------------------------------- the engine


def test_whole_sequence_logits_are_the_references(model):
    ids = tokens(24, seed=1, rows=2)
    want = builder.reference_logits(model[1], ids, CFG)
    got = jax.jit(model[0].apply)({"params": model[1]}, jnp.asarray(ids))
    assert 0.3 < want.std(axis=-1).mean() < 1.0
    np.testing.assert_allclose(np.asarray(got), want, **TOL)


def test_prefill_then_paged_decode_is_the_full_forward_pass(model, adapter):
    """A prompt through the lane in slices of 8, then tokens one at a time
    through the PAGED pool's views, against the reference's full forward
    pass teacher-forced on the same tokens."""
    ids = tokens(29, seed=2)
    want = builder.reference_logits(model[1], ids, CFG)[0]
    eng = engine(model, max_slots=2)
    req = eng.submit(ids[0, :13], max_new_tokens=16)
    eng.run()
    seq = np.concatenate([ids[0, :13], req.tokens])[None]
    rows = builder.reference_logits(model[1], seq, CFG)[0][12:-1]
    assert float(np.max(rows.max(axis=1) - rows[
        np.arange(len(req.tokens)), req.tokens])) <= 1e-3
    # and the logits themselves, through the adapter's own two calls
    cache = adapter.init_cache(1, 32)
    logits, cache = compiled(adapter, "prefill_append")(
        model[1], jnp.asarray(ids[:, :13]), cache)
    out = [logits[0]]
    for i in range(13, 29):
        step, cache = compiled(adapter, "decode_step")(
            model[1], jnp.asarray(ids[:, i]), cache)
        out.append(step)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(out)), want, **TOL)


def test_the_engine_serves_it_in_one_program_alone_or_among_neighbours(
        model):
    prompts = [tokens(n, seed=30 + n)[0] for n in (5, 20, 9, 12, 7)]
    eng = engine(model)
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    assert eng.compile_count == 1 and eng.metrics()["adapter"] == "decoder"
    # three lane slices while neighbours decode, and a late admission
    assert reqs[1].tokens == alone(model, prompts[1], 10)
    assert reqs[4].tokens == alone(model, prompts[4], 10)


def test_a_reused_slot_gives_the_stream_it_gives_alone(model):
    first, second = tokens(9, seed=5)[0], tokens(13, seed=6)[0]
    eng = engine(model, max_slots=1)
    a = eng.submit(first, max_new_tokens=7)
    b = eng.submit(second, max_new_tokens=7)
    eng.run()
    assert eng.compile_count == 1 and a.tokens
    # against an engine of its own; no reset from the host
    assert b.tokens == alone(model, second, 7, fresh=True)


def test_the_kernel_path_serves_what_the_gather_path_serves(model):
    """``kv_append`` / ``prefill_attn`` / ``paged_decode`` (interpreted) at
    ``g = 2``, ``rep = 4`` beside the tail's select."""
    prompts = [tokens(n, seed=40 + n)[0] for n in (5, 13, 9)]
    served = {}
    for flash in (False, True):
        eng = engine(model, use_flash_decode=flash, kv_page_len=128,
                     max_len=256)
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run()
        assert eng.compile_count == 1
        served[flash] = [r.tokens for r in reqs]
    assert served[True] == served[False]


def test_the_gauges_read_the_tails_and_the_query_group(model):
    eng = engine(model, kv_page_len=128, max_len=256)
    assert eng._pool["k"].shape[0] == 1          # as deep as attention
    assert eng._pool["k"].shape[2:] == (1, 128, 128)   # two heads a tile
    assert all(eng._pool[name].shape == (3, 2, 512) for name in STATE)
    assert eng.metrics()["kv_lane_pack"] == 2
    assert eng.metrics()["kv_query_group"] == 4
    eng._adapter.observe(kv_pool.harvest_snapshot(eng._pool), eng.telemetry)
    kinds, samples = _parse_prom(eng.prometheus())
    assert kinds["ds_tpu_kv_query_group"] == "gauge"

    def sample(name):
        return [v for (n, _), v in samples.items() if n == name]

    assert sample("ds_tpu_kv_query_group") == [4]
    assert sample("ds_tpu_ssm_state_bytes") == [3 * 3 * 2 * 512 * 4]
    assert sample("ds_tpu_moe_experts_held") == [8]
    assert len(sample("ds_tpu_moe_expert_load")) == 8
    # a model whose every query head stores a key of its own
    plain = DecoderLM(CFG._replace(n_layer=1, layer_types=None,
                                   n_kv_head=None, dense_layers=0))
    assert engine((plain, plain.init(jax.random.PRNGKey(0))["params"]),
                  kv_page_len=128, max_len=256).metrics()[
        "kv_query_group"] == 1


@pytest.mark.parametrize("key, mechanism", [
    ("spec_decode", "speculative decoding"),
    ("prefix_cache", "prefix cache"), ("int8_kv", "int8 planes")])
def test_what_needs_a_snapshot_of_the_tail_is_refused_by_name(model, key,
                                                              mechanism):
    with pytest.raises(ValueError, match=mechanism) as e:
        engine(model, **{key: True})
    assert "recurrent state a slot (3 shortconv layers)" in str(e.value)
    assert "Mamba" not in str(e.value) and "kda" not in str(e.value)


def test_verify_forward_is_refused(model, adapter):
    with pytest.raises(NotImplementedError, match="recurrent state"):
        adapter.verify_forward(model[1], jnp.zeros((1, 3), jnp.int32),
                               adapter.init_cache(1, 16))


def test_preempt_then_resume_continues_token_for_token(model):
    """The hierarchy's capture ships the tails with the slot's pages."""
    prompts = [tokens(n, seed=20 + n)[0] for n in (6, 9, 5)]
    eng = engine(model, host_offload=True, swap_slots=2)
    reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
    while not (reqs[0].phase == "decoding" and reqs[0].tokens):
        eng.step()
    assert eng.preempt(reqs[0]) and reqs[0].phase == "swapped"
    record = eng._hier.swap_store.records[reqs[0].rid]
    assert all(record[name].shape == (2, 512)
               and np.abs(record[name]).max() > 0 for name in STATE)
    for _ in range(6):
        eng.step()
    eng.release_preempted(reqs[0])
    eng.run()
    assert eng.compile_count == 1
    undisturbed = engine(model, host_offload=True, swap_slots=2)
    same = [undisturbed.submit(p, max_new_tokens=20) for p in prompts]
    undisturbed.run()
    assert [r.tokens for r in reqs] == [r.tokens for r in same]
