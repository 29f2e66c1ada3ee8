"""The Mamba-1 selective scan (``models/mamba1.py``) and the stack it serves in
(``models/decoder.py``: selective-scan layers with a ``[N, W]`` float32 state
and a three-row tail a slot beside MULTI-QUERY attention without positions
over a paged cache as deep as the attention layers only, a dense feed-forward
in every layer and NO expert layer) against the plain reference
(``benchmark/reference/jamba.py``) at a small size in float32: hidden 640, 3
layers (mamba1, attention, mamba1: the attention layer NOT at index 0), 5
query heads over ONE stored head of 128, ``W`` 1280, ``N`` 16, a step
through rank 40.

The weights are drawn so that nothing the equations hold can hide: ``A`` a
channel AND a state index, the convolution's and the step's biases away from
0, the three inner norms' weights away from 1.

Tolerances: float32 end to end; whole logits 2e-4 on a spread of 0.6. What
must not move does not move by one bit; a prompt's state is the same however
it was chunked, to float32 rounding (a one-row matmul and a 37-row one round
apart on a CPU, so not bit for bit).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import jamba as reference
from deepspeed_tpu.inference import InferenceEngine, kv_pool
from deepspeed_tpu.inference.adapters import DecoderAdapter
from deepspeed_tpu.models import decoder, mamba1
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from deepspeed_tpu.ops.transformer.kernels import decode_attention as da
from tests.unit.compiled import compiled
from tests.unit.test_telemetry import _parse_prom

builder = harness.load_by_name("model_builders", "jamba")

CFG = DecoderConfig(
    vocab_size=256, n_layer=3, n_head=5, head_dim=128, hidden_size=640,
    n_positions=4096, n_experts=0, experts_per_token=0, expert_width=0,
    rms_norm_eps=1e-6, qk_norm=False, tie_word_embeddings=True,
    dtype=jnp.float32, initializer_range=0.05, n_kv_head=1, rope=False,
    layer_types=("mamba1", "attention", "mamba1"),
    dense_layers=3, dense_width=256, mamba_state=16, mamba_conv=4,
    mamba_expand=2, mamba_dt_rank=40)
TOL = dict(rtol=2e-4, atol=2e-4)
SAME = dict(rtol=2e-5, atol=2e-5)
W, N, K = 1280, 16, 4
T = 37      # tokens of the mixer's tests


def unruly(p, key):
    """A Mamba-1 tree (one layer's or the stack's) with ``A`` drawn a
    channel and a state index and the inner norms' weights away from 1."""
    ks = jax.random.split(key, 4)
    return dict(
        p, A_log=jnp.log(jax.random.uniform(ks[0], p["A_log"].shape,
                                            jnp.float32, 0.5, 16.0)),
        **{name: 1.0 + 0.3 * jax.random.normal(k, p[name].shape)
           for name, k in zip(("dt_norm", "b_norm", "c_norm"), ks[1:])})


@pytest.fixture(scope="module")
def model():
    m = DecoderLM(CFG)
    key = jax.random.PRNGKey(0)
    params = builder.rescaled(jax.jit(m.init)(key)["params"], 1.0, 0.5)
    params["mamba1"] = unruly(params["mamba1"], key)
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
    eng = engine(model, **kw)
    req = eng.submit(prompt, max_new_tokens=n)
    eng.run()
    assert eng.compile_count == 1
    return req.tokens


@pytest.fixture(scope="module")
def layer():
    p = unruly(mamba1.init_layer(jax.random.PRNGKey(0), CFG),
               jax.random.PRNGKey(1))
    assert float(jnp.abs(p["conv_b"]).min()) > 0 and \
        float(jnp.abs(p["dt_bias"]).min()) > 0
    hid = jax.random.normal(jax.random.PRNGKey(2), (2, T, 640))
    return p, hid


_mixer = jax.jit(mamba1.mixer, static_argnums=(1,))


def through(p, hid, chunk, lane=None, state=None):
    """``hid`` [B, T, C] through the mixer in slices of ``chunk`` tokens,
    each padded to ``lane`` columns with ``n_valid`` the real ones (as the
    prefill lane hands a prompt over): (out [B, T, C], state, tail)."""
    b, t, _ = hid.shape
    lane = lane or chunk
    ssm, tail = state or (jnp.zeros((b, N, W), jnp.float32),
                          jnp.zeros((b, K - 1, W), CFG.dtype))
    outs = []
    for lo in range(0, t, chunk):
        n = min(chunk, t - lo)
        piece = jnp.pad(hid[:, lo:lo + n], ((0, 0), (0, lane - n), (0, 0)),
                        constant_values=7.0)         # a pad column is junk
        out, ssm, tail = _mixer(
            p, CFG, piece, ssm, tail, jnp.full((b,), lo, jnp.int32),
            jnp.full((b,), n, jnp.int32))
        outs.append(out[:, :n])
    return jnp.concatenate(outs, axis=1), ssm, tail


def published(p):
    return dict(p, dt_layernorm=p["dt_norm"], b_layernorm=p["b_norm"],
                c_layernorm=p["c_norm"], A_log=p["A_log"].T)


# ------------------------------------------------------------- the mixer


def test_the_prompt_form_is_the_references_sequential_scan(layer):
    p, hid = layer
    got, ssm, tail = through(p, hid, 37)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            seen = {}
            want = reference.mamba(hid[b], published(p), N, 40, 1e-6,
                                   seen=seen)
            np.testing.assert_allclose(np.asarray(got[b]), want, **SAME)
            np.testing.assert_allclose(np.asarray(ssm[b]), seen["state"].T,
                                       **SAME)
            np.testing.assert_allclose(np.asarray(tail[b]),
                                       seen["x_in"][-(K - 1):], **SAME)
            # a decay a channel AND a state index, and a state that remembers
            assert float(jnp.std(seen["A"], axis=0).min()) > 0
            assert float(jnp.std(seen["A"], axis=1).min()) > 0
    assert float(jnp.abs(got).max()) > 0.01 < float(jnp.abs(ssm).max())


def test_the_one_token_form_is_the_reference_at_every_position(layer):
    """37 one-token calls, each reading the state and the three rows the
    call before left, against the reference's whole sequence, and so the
    prompt form's too."""
    p, hid = layer
    whole, ssm_w, tail_w = through(p, hid, 37)
    rolled, ssm, tail = through(p, hid, 1)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.mamba(hid[b], published(p), N, 40, 1e-6)
                          for b in range(2)])
    np.testing.assert_allclose(np.asarray(rolled), want, **SAME)
    np.testing.assert_allclose(np.asarray(rolled), np.asarray(whole), **SAME)
    np.testing.assert_allclose(np.asarray(ssm), np.asarray(ssm_w), **SAME)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(tail_w), **SAME)


@pytest.mark.parametrize("chunk", [1, 2, 3, 128])
def test_a_prompts_state_tail_and_output_do_not_depend_on_its_chunking(
        layer, chunk):
    """The tail crosses chunks shorter than the kernel (1, 2, 3 < 4), and a
    lane of 128 columns holds 91 pad columns of junk."""
    p, hid = layer
    want, ssm_w, tail_w = through(p, hid, 37)
    got, ssm, tail = through(p, hid, chunk, lane=max(chunk, 4))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **SAME)
    np.testing.assert_allclose(np.asarray(ssm), np.asarray(ssm_w), **SAME)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(tail_w), **SAME)


def test_a_frozen_row_does_not_move_and_a_fresh_row_starts_from_zeros(layer):
    p, hid = layer
    key = jax.random.PRNGKey(5)
    ssm = jax.random.normal(key, (3, N, W))
    tail = jax.random.normal(jax.random.fold_in(key, 1), (3, K - 1, W))
    hid = jnp.concatenate([hid, hid[:1]])[:, :1]
    # row 0 decodes at frontier 9, row 1 is frozen, row 2 is at frontier 0
    out, ssm2, tail2 = mamba1.mixer(
        p, CFG, hid, ssm, tail, jnp.asarray([9, 9, 0], jnp.int32),
        jnp.asarray([1, 0, 1], jnp.int32))
    np.testing.assert_array_equal(np.asarray(ssm2[1]), np.asarray(ssm[1]))
    np.testing.assert_array_equal(np.asarray(tail2[1]), np.asarray(tail[1]))
    assert float(jnp.abs(ssm2[0] - ssm[0]).max()) > 0
    # whatever the slot held: the stream a row at frontier 0 gives alone
    fresh, ssm_f, tail_f = through(p, hid[2:], 1)
    np.testing.assert_allclose(np.asarray(out[2]), np.asarray(fresh[0]),
                               **SAME)
    np.testing.assert_allclose(np.asarray(ssm2[2]), np.asarray(ssm_f[0]),
                               **SAME)
    np.testing.assert_allclose(np.asarray(tail2[2]), np.asarray(tail_f[0]),
                               **SAME)


def test_a_pad_column_moves_neither_state(layer):
    p, hid = layer
    want, ssm_w, tail_w = through(p, hid[:, :5], 5)
    got, ssm, tail = through(p, hid[:, :5], 5, lane=24)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(tail_w), **SAME)
    np.testing.assert_allclose(np.asarray(ssm), np.asarray(ssm_w), **SAME)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **SAME)


# ------------------------------------------- multi-query rows, K > 1 a pair


@pytest.mark.parametrize("heads, s, name, pages", [
    (20, 1, None, 8), (5, 128, "prefill_attn", 8)])
def test_query_heads_over_one_stored_head_of_128_through_the_kernels(
        heads, s, name, pages):
    """``kv_append`` then ``paged_decode`` / ``prefill_attn`` (interpreted)
    over ONE stored head of 128 (``g = 1``, ``rep = heads``) in bf16, where
    a page of the pair is 64 KB and a unit joins EIGHT of them (the cell's
    lane, 20 x 128 query rows, has room in VMEM for two): a row of
    ten live pages (two units, the second of two pages), one of six (one
    unit, short) and one of one, against the gather path and by hand."""
    d, b, page, n_lp, layers = 128, 3, 128, 12, 2
    rng = np.random.RandomState(heads + s)
    arenas = tuple(jnp.asarray(rng.randn(layers, 1 + b * n_lp, 1, page, d),
                               jnp.bfloat16) for _ in "kv")
    assert da.lane_pack(d, 1) == 1
    assert da.query_group(arenas[0], heads, d) == heads
    assert da.unit_pages(arenas, heads, d, n_lp, jnp.bfloat16, s) == pages
    assert da.unit_pages(arenas, 20, d, n_lp, jnp.bfloat16, 128) == 2
    tbl = 1 + jnp.arange(b * n_lp, dtype=jnp.int32).reshape(b, n_lp)
    pos = jnp.asarray([5, 1200, 700], jnp.int32)
    new = tuple(jnp.asarray(rng.randn(b, 1, s, d), jnp.bfloat16)
                for _ in "kv")
    q = jnp.asarray(rng.randn(b, heads, s, d), jnp.bfloat16)
    k, v = da.kv_append(arenas, new, tbl, pos, layer=1)
    for got, old, x in zip((k, v), arenas, new):
        want = old[1]
        for row in range(b):
            at = int(pos[row]) + np.arange(s)
            for lp in np.unique(at // page):
                sel = at // page == lp
                want = want.at[tbl[row, lp], 0, at[sel] % page].set(
                    x[row, 0, np.nonzero(sel)[0]])
        np.testing.assert_array_equal(np.asarray(got[1], np.float32),
                                      np.asarray(want, np.float32))
        np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                      np.asarray(old[0], np.float32))
    got = da.flash_decode_attention_paged(q, k, v, tbl, pos, scale=128 ** -.5,
                                          name=name, layer=1)
    want = da.decode_attention_paged_reference(q, k[1], v[1], tbl, pos,
                                               scale=128 ** -.5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
    # and by hand: every query head reads the one stored head
    planes = [jnp.repeat(da.gather_pages(a[1], tbl, 1, 1), heads, 1)
              for a in (k, v)]
    byhand = da.decode_attention_reference(
        *(x.astype(jnp.float32) for x in (q, *planes)), pos,
        scale=128 ** -.5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(byhand), rtol=2e-2, atol=2e-2)


# --------------------------------------------------- the stack, the engine


def test_a_stack_without_experts_has_no_moe_tree_and_routes_nothing(model):
    m, params = model
    assert CFG.expert_layers == 0
    assert sorted(params) == ["attn", "dense", "embed", "final_norm",
                              "layers", "mamba1"]
    assert sorted(params["layers"]) == ["attn_norm", "ffn_norm"]
    assert params["dense"]["w_gate_up"].shape == (3, 640, 512)
    assert params["attn"]["wqkv"].shape == (1, 640, 640 + 2 * 128)
    adapter = DecoderAdapter.from_model(m, use_flash_decode=False)
    assert adapter.aux_state() == {}
    assert not [k for k in adapter.init_cache(1, 16) if k.startswith("aux_")]
    # a stack WITH experts keeps what it had
    some = CFG._replace(n_experts=4, experts_per_token=2, expert_width=32,
                        dense_layers=1)
    assert some.expert_layers == 2
    tree = jax.eval_shape(lambda: DecoderLM(some).init(
        jax.random.PRNGKey(0))["params"])
    assert tree["moe"]["router"].shape == (2, 640, 4)
    assert sorted(DecoderAdapter.from_model(
        DecoderLM(some), use_flash_decode=False).aux_state()) == [
        "aux_moe_load", "aux_moe_routed"]


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
    ids = tokens(23, seed=2)
    want = builder.reference_logits(model[1], ids, CFG)[0]
    eng = engine(model, max_slots=2)
    req = eng.submit(ids[0, :13], max_new_tokens=10)
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
    decode = jax.jit(adapter.decode_step)
    for i in range(13, 23):
        step, cache = decode(model[1], jnp.asarray(ids[:, i]), cache)
        out.append(step)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(out)), want, **TOL)


def test_a_reused_slot_starts_from_zeros_among_neighbours(model):
    prompts = [tokens(n, seed=30 + n)[0] for n in (5, 20, 9, 12)]
    eng = engine(model, max_slots=2)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run()
    assert eng.compile_count == 1 and eng.metrics()["adapter"] == "decoder"
    # a slot used before, admitted while a neighbour decodes (no reset from
    # the host), after a prompt of three lane slices
    assert reqs[3].tokens == alone(model, prompts[3], 8)


def test_the_kernel_path_serves_what_the_gather_path_serves(model):
    """``kv_append`` / ``prefill_attn`` / ``paged_decode`` (interpreted) at
    ``rep = 5`` over the one stored head beside the state's select."""
    prompts = [tokens(n, seed=40 + n)[0] for n in (5, 13)]
    served = {}
    for flash in (False, True):
        eng = engine(model, use_flash_decode=flash, kv_page_len=128,
                     max_len=256, max_slots=2)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run()
        assert eng.compile_count == 1
        served[flash] = [r.tokens for r in reqs]
    assert served[True] == served[False]


def test_the_gauges_read_the_state_and_no_expert(model):
    eng = engine(model, kv_page_len=128, max_len=1152)
    assert eng._pool["k"].shape[0] == 1          # as deep as attention
    assert eng._pool["k"].shape[2:] == (1, 128, 128)    # ONE stored head
    assert all(eng._pool["slot_sel{}".format(j)].shape == (3, N, W)
               and eng._pool["slot_selconv{}".format(j)].shape
               == (3, K - 1, W) for j in range(2))
    assert not [k for k in eng._pool if k.startswith("aux_")]
    metrics = eng.metrics()
    assert metrics["kv_lane_pack"] == 1 and metrics["kv_query_group"] == 5
    # float32 here: 128 KB a page of the pair, four under the 1 MiB unit
    # (bf16, the cell's: eight; the kernels' test above)
    assert metrics["kv_unit_pages"] == 4
    eng._adapter.observe(kv_pool.harvest_snapshot(eng._pool), eng.telemetry)
    _, samples = _parse_prom(eng.prometheus())

    def sample(name):
        return [v for (n, _), v in samples.items() if n == name]

    assert sample("ds_tpu_ssm_state_bytes") == [
        3 * 2 * (N * W * 4 + (K - 1) * W * 4)]
    assert not [n for n, _ in samples if "moe_" in n]


@pytest.mark.parametrize("key, mechanism", [
    ("spec_decode", "speculative decoding"),
    ("prefix_cache", "prefix cache"), ("int8_kv", "int8 planes")])
def test_what_needs_a_snapshot_of_the_state_is_refused_by_name(model, key,
                                                               mechanism):
    with pytest.raises(ValueError, match=mechanism) as e:
        engine(model, **{key: True})
    assert "recurrent state a slot (2 mamba1 layers)" in str(e.value)
    assert "kda" not in str(e.value) and "shortconv" not in str(e.value)


def test_a_float32_stream_parts_less_from_the_reference_than_a_bf16_one():
    """``residual_fp32``: matrices and matmul inputs stay bf16, the stream
    and what a Mamba-1 or dense branch hands it are float32. Twelve layers
    deep the logits then stand nearer the float32 reference's (the cell's 28
    layers: 0.0206 -> 0.0133 rms on the chip, PERF.md PR 48), and a stack
    without the flag lowers as it did."""
    cfg = CFG._replace(
        n_layer=12, n_head=2, hidden_size=256, dtype=jnp.bfloat16,
        layer_types=("mamba1", "attention") + ("mamba1",) * 10,
        dense_layers=12, mamba_dt_rank=16)
    params = DecoderLM(cfg).init(jax.random.PRNGKey(3))["params"]
    ids = tokens(32, seed=9, rows=2)
    want = builder.reference_logits(params, ids, cfg)

    def parted(c):
        got = jax.jit(DecoderLM(c).apply)({"params": params},
                                          jnp.asarray(ids))
        return float(np.std(np.asarray(got, np.float32) - want))

    plain, wide = parted(cfg), parted(cfg._replace(residual_fp32=True))
    assert wide < 0.9 * plain, (plain, wide)
    out = jax.eval_shape(
        lambda p, h: mamba1.mixer(
            {k: v[0] for k, v in p.items()}, cfg._replace(residual_fp32=True),
            h, jnp.zeros((1, N, 512)), jnp.zeros((1, K - 1, 512), cfg.dtype),
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32))[0],
        params["mamba1"], jnp.zeros((1, 1, 256), cfg.dtype))
    assert out.dtype == jnp.float32
    # ONE statement of the stream's type, and the feed-forward's sums in it
    for c, stream in ((cfg, jnp.bfloat16),
                      (cfg._replace(residual_fp32=True), jnp.float32)):
        assert c.stream_dtype == stream
        assert jax.eval_shape(
            lambda p, h: decoder.dense_mix(
                {k: v[0] for k, v in p.items()}, c, h),
            params["dense"], jnp.zeros((1, 1, 256), cfg.dtype)
        ).dtype == stream
