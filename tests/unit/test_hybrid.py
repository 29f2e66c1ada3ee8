"""The hybrid stack (``models/decoder.py`` with ``layer_types``: Mamba-2
layers with a recurrent state a slot beside grouped-query attention, a
chip's share of the routed experts, a shared expert; Granite 4.0-H's block)
against its plain reference (``benchmark/reference/granitemoehybrid.py``) at
a tiny size in float32: hidden 64, layers [mamba, attention, mamba, mamba],
8 experts top-3 with 4 held, 4 Mamba heads of 8, state 16, chunk 8.

Tolerances: float32 end to end, so the cache-free pass, the chunked lane and
the one-token scan differ from the reference's token-by-token recurrence by
the order of their sums only: 2e-4 on logits that spread 1 (measured 3e-6);
paths of the PROGRAM that must agree with each other do so to 2e-5, and what
must resume (capture and restore) does bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import granitemoehybrid as reference
from deepspeed_tpu.inference import InferenceEngine, kv_pool
from deepspeed_tpu.inference.adapters import DecoderAdapter
from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.inference.kv_hierarchy import offload
from deepspeed_tpu.models import decoder, mamba2
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from deepspeed_tpu.moe import routed
from deepspeed_tpu.ops.transformer.kernels import decode_attention as da
from tests.unit.compiled import compiled, served_alone

builder = harness.load_by_name("model_builders", "granitemoehybrid")

CFG = DecoderConfig(
    vocab_size=256, n_layer=4, n_head=4, head_dim=16, hidden_size=64,
    n_positions=256, n_experts=8, experts_per_token=3, expert_width=32,
    qk_norm=False, norm_topk_prob=True, tie_word_embeddings=True,
    dtype=jnp.float32, initializer_range=0.05, n_kv_head=2, rope=False,
    attn_scale=1 / 16.0, embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=4.0, shared_width=48, experts_held=(0, 4),
    layer_types=("mamba", "attention", "mamba", "mamba"), mamba_heads=4,
    mamba_head_dim=8, mamba_state=16, mamba_conv=4, mamba_chunk=8)
TOL = dict(rtol=2e-4, atol=2e-4)
SAME = dict(rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def model():
    m = DecoderLM(CFG)
    # the token table at 0.01 and the last norm at 25, as the benchmark's
    # builder scales its random weights: logits that spread about 1
    params = jax.jit(m.init)(jax.random.PRNGKey(0))["params"]
    return m, builder.rescaled(params, 0.01 / CFG.initializer_range, 25.0)


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


# ------------------------------------------------- against the reference


def test_the_cache_free_pass_is_the_reference(model):
    ids = tokens(20, rows=2)
    want = builder.reference_logits(model[1], ids, CFG)
    got = jax.jit(model[0].apply)({"params": model[1]}, jnp.asarray(ids))
    assert want.std() > 0.5          # logits that could tell a token apart
    np.testing.assert_allclose(np.asarray(got), want, **TOL)


def test_unequal_prefill_chunks_then_decode_are_the_references_one_pass(
        model, adapter):
    """Chunks of 7, 5 and 4 (the last with 2 pad columns), then 6 tokens
    through the one-token step, on a paged pool's cache."""
    ids = tokens(20, seed=1)
    want = builder.reference_logits(model[1], ids, CFG)[0]
    pool = kv_pool.init_pool(adapter.cache_spec(), 1, 64, slack=8, page_len=8)
    cache = dict(kv_pool.cache_view(pool), **adapter.aux_state())
    cache["block_tbl"] = 1 + jnp.arange(
        pool["block_tbl"].shape[1], dtype=jnp.int32)[None]
    del cache["n_valid"]
    got = []
    for lo, hi, pad in ((0, 7, 0), (7, 12, 0), (12, 14, 2)):
        chunk = np.concatenate([ids[:, lo:hi], tokens(pad, seed=9)], axis=1)
        logits, cache = compiled(adapter, "prefill_append")(
            model[1], jnp.asarray(chunk), cache,
            n_valid=jnp.asarray([hi - lo]))
        got.append(np.asarray(logits[0, :hi - lo]))
    assert int(cache["pos"][0]) == 14
    for t in range(14, 20):
        logits, cache = compiled(adapter, "decode_step")(
            model[1], jnp.asarray(ids[:, t]), cache)
        got.append(np.asarray(logits))
    np.testing.assert_allclose(np.concatenate(got), want, **TOL)


def test_a_prompts_state_does_not_depend_on_how_it_was_chunked(model,
                                                               adapter):
    ids = jnp.asarray(tokens(19, seed=2))

    def state(cuts):
        cache = adapter.init_cache(1, 32)
        for lo, hi in zip((0,) + cuts, cuts + (19,)):
            _, cache = compiled(adapter, "prefill_append")(
                model[1], ids[:, lo:hi], cache)
        return {k: np.asarray(v) for k, v in cache.items()
                if k.startswith("slot_")}

    one, other = state(()), state((3, 11, 12))
    assert set(one) == {"slot_ssm0", "slot_ssm1", "slot_ssm2", "slot_conv0",
                        "slot_conv1", "slot_conv2"}
    assert np.abs(one["slot_ssm2"]).max() > 1e-3
    for name in one:
        np.testing.assert_allclose(one[name], other[name], **SAME)


def test_the_mixers_state_is_the_references(model):
    """One Mamba layer on one sequence: the chunked form's output and final
    state against the reference's token-by-token scan."""
    p = jax.tree_util.tree_map(lambda a: a[0], model[1]["mamba"])
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 21, CFG.hidden_size))
    ssm = jnp.zeros((1, CFG.mamba_state, 32))
    tail = jnp.zeros((1, 3, 32 + 2 * CFG.mamba_state))
    out, ssm, _ = jax.jit(mamba2.mixer, static_argnums=1)(
        p, CFG, h, ssm, tail, jnp.zeros((1,), jnp.int32), jnp.asarray([21]))
    want, state = reference.mamba(h[0], p, CFG.mamba_heads, CFG.mamba_state,
                                  CFG.rms_norm_eps, with_state=True)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want), **TOL)
    # the program keeps [N, heads x P], the reference [heads, P, N]
    np.testing.assert_allclose(
        np.asarray(ssm[0]).reshape(CFG.mamba_state, 4, 8),
        np.asarray(state).transpose(2, 0, 1), **TOL)


# ------------------------------------------------------- the state a slot


def test_pad_columns_and_idle_rows_leave_the_state_untouched(model, adapter):
    ids = jnp.asarray(tokens(12, seed=4, rows=2))
    cache = adapter.init_cache(2, 32)
    append = compiled(adapter, "prefill_append")
    _, cache = append(model[1], ids[:, :8], cache)
    before = {k: np.asarray(v) for k, v in cache.items()}
    # row 0 appends 4 real columns, row 1 none (all four are padding)
    _, after = append(model[1], ids[:, 8:], cache,
                      n_valid=jnp.asarray([4, 0]))
    # and a decode step in which only row 0 is live
    _, after = compiled(adapter, "decode_step")(
        model[1], ids[:, 0], dict(after, n_valid=jnp.asarray([1, 0])))
    for name in ("slot_ssm0", "slot_ssm2", "slot_conv0", "slot_conv2"):
        got = np.asarray(after[name])
        np.testing.assert_array_equal(got[1], before[name][1])
        assert np.abs(got[0] - before[name][0]).max() > 0


def test_the_pool_holds_the_state_a_slot_and_counts_it(adapter):
    spec = adapter.cache_spec()
    assert (spec.n_layer, spec.n_head, spec.n_embd) == (1, 2, 32)
    pool = kv_pool.init_pool(spec, 3, 64, slack=8, page_len=8)
    assert pool["k"].shape[0] == 1          # as deep as the layers with keys
    assert all(pool["slot_ssm{}".format(j)].shape == (3, 16, 32)
               and pool["slot_ssm{}".format(j)].dtype == jnp.float32
               for j in range(3))
    assert pool["slot_conv1"].shape == (3, 3, 64)
    state = 3 * (3 * 16 * 32 * 4 + 3 * 3 * 64 * 4)
    flat = kv_pool.init_pool(spec._replace(slot_state=()), 3, 64, slack=8,
                             page_len=8)
    assert kv_pool.pool_nbytes(pool) - kv_pool.pool_nbytes(flat) == state
    view = kv_pool.cache_view(dict(pool, active=jnp.asarray(
        [True, False, True])))
    np.testing.assert_array_equal(view["n_valid"], [1, 0, 1])
    lane = kv_pool.slot_cache_view(pool, 1, jnp.zeros((1,), jnp.int32))
    assert lane["slot_ssm1"].shape == (1, 16, 32)
    lane["slot_ssm1"] = lane["slot_ssm1"] + 1.0
    back = kv_pool.write_slot_cache(pool, 1, lane)
    np.testing.assert_array_equal(
        np.asarray(back["slot_ssm1"]).sum(axis=(1, 2)), [0, 16 * 32, 0])
    assert not np.asarray(back["slot_ssm0"]).any()


def test_a_reused_slot_gives_the_stream_it_gives_alone(model):
    first, second = tokens(9, seed=5)[0], tokens(13, seed=6)[0]
    eng = engine(model, max_slots=1)
    a = eng.submit(first, max_new_tokens=7)
    b = eng.submit(second, max_new_tokens=7)
    eng.run()
    assert eng.compile_count == 1
    # each against an engine of its own that has served nothing before it;
    # no reset from the host
    assert a.tokens == alone(model, first, 7, fresh=True)
    assert b.tokens == alone(model, second, 7, fresh=True)


def test_a_row_prefilling_beside_rows_that_decode_is_not_disturbed(model):
    """A prompt of three lane chunks (20 tokens at 8 a step) admitted while
    two neighbours decode: the scan runs twice between its chunks."""
    short = [tokens(n, seed=10 + n)[0] for n in (5, 6)]
    long = tokens(20, seed=7)[0]
    eng = engine(model)
    reqs = [eng.submit(p, max_new_tokens=12) for p in short]
    eng.step()
    eng.step()
    late = eng.submit(long, max_new_tokens=9)
    eng.run()
    assert eng.compile_count == 1
    assert late.tokens == alone(model, long, 9)
    for p, r in zip(short, reqs):
        assert r.tokens == alone(model, p, 12)
    # the reference agrees with every served token (teacher forcing)
    seq = np.concatenate([long, late.tokens])[None]
    want = builder.reference_logits(model[1], seq, CFG)[0]
    rows = want[len(long) - 1:len(long) - 1 + len(late.tokens)]
    assert float(np.max(rows.max(axis=1)
                        - rows[np.arange(len(late.tokens)), late.tokens])) \
        <= 1e-3


def test_preempt_then_resume_continues_bit_for_bit(model):
    prompts = [tokens(n, seed=20 + n)[0] for n in (6, 9, 5)]
    eng = engine(model, host_offload=True, swap_slots=2)
    reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
    while not (reqs[0].phase == "decoding" and reqs[0].tokens):
        eng.step()
    assert eng.preempt(reqs[0]) and reqs[0].phase == "swapped"
    record = eng._hier.swap_store.records[reqs[0].rid]
    assert record["slot_ssm2"].shape == (16, 32)         # the slot's slice
    assert all(np.abs(record["slot_ssm{}".format(j)]).max() > 0
               for j in range(3))
    for _ in range(6):
        eng.step()
    eng.release_preempted(reqs[0])
    eng.run()
    assert eng.compile_count == 1
    for p, r in zip(prompts, reqs):
        assert r.tokens == alone(model, p, 20, host_offload=True,
                                 swap_slots=2)


def test_capture_and_restore_carry_the_state_with_the_slot(model):
    eng = engine(model)
    for n in (6, 9):
        eng.submit(tokens(n, seed=n)[0], max_new_tokens=16)
    eng.step()
    eng.step()
    pool, pager = eng._pool, eng._pager
    rows = [pager.row_pages(s) for s in (0, 1)]
    rec = offload.capture_slot_paged(pool, 0, rows[0])
    fresh = pager.alloc_pages(len(rows[0]))
    restored = offload.restore_slot_paged(pool, 2, rec, fresh)
    for name in ("slot_ssm0", "slot_ssm1", "slot_ssm2", "slot_conv0",
                 "slot_conv1", "slot_conv2"):
        np.testing.assert_array_equal(np.asarray(restored[name][2]),
                                      np.asarray(pool[name][0]))
        np.testing.assert_array_equal(rec[name], np.asarray(pool[name][0]))
    batched = offload.capture_slots_paged(pool, [0, 1], rows)
    np.testing.assert_array_equal(batched[1]["slot_ssm1"],
                                  np.asarray(pool["slot_ssm1"][1]))
    assert not any(k.startswith("aux_") for k in rec)


def test_the_engine_serves_it_in_one_program_alone_or_among_neighbours(
        model):
    prompts = [tokens(n, seed=30 + n)[0] for n in (5, 20, 9, 12, 7)]
    eng = engine(model)
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    assert eng.compile_count == 1 and eng.metrics()["adapter"] == "decoder"
    for p, r in zip(prompts, reqs):
        assert r.tokens == alone(model, p, 10)
    from deepspeed_tpu.telemetry.exporters import prometheus_text

    text = prometheus_text(eng.telemetry)
    gauges = {}
    for line in text.splitlines():
        if line.startswith("ds_tpu_") and "expert=" not in line:
            name, value = line.rsplit(" ", 1)
            gauges[name.split("{")[0][len("ds_tpu_"):]] = float(value)
    assert text.count("moe_expert_load{") == 4
    assert all('expert="{}"'.format(e) in text for e in range(4))
    assert gauges["moe_experts_held"] == 4
    # about half of all choices fall on the experts held elsewhere
    routed_, absent = gauges["moe_tokens_routed"], gauges["moe_tokens_absent"]
    assert 0.3 < absent / (routed_ + absent) < 0.7
    assert gauges["ssm_state_bytes"] == 3 * (3 * 16 * 32 * 4
                                             + 3 * 3 * 64 * 4)
    assert gauges["kv_pool_bytes"] > gauges["ssm_state_bytes"]


# ----------------------------------------------------------- the refusals


@pytest.mark.parametrize("key, value, mechanism", [
    ("spec_decode", True, "speculative decoding"),
    ("prefix_cache", True, "prefix cache"),
    ("int8_kv", True, "int8")])
def test_what_needs_a_snapshot_of_the_state_is_refused_by_name(
        model, key, value, mechanism):
    with pytest.raises(ValueError, match=mechanism) as e:
        engine(model, **{key: value})
    assert "recurrent state" in str(e.value)
    # the same key serves a model whose rows carry keys only
    plain = DecoderLM(CFG._replace(layer_types=None, n_layer=1))
    InferenceEngine(plain, plain.init(jax.random.PRNGKey(0))["params"],
                    config=dict(max_slots=2, max_len=64, chunk_size=2,
                                use_flash_decode=False, **{key: value}))


def test_verify_forward_is_refused(model, adapter):
    with pytest.raises(NotImplementedError, match="recurrent state"):
        adapter.verify_forward(model[1], jnp.zeros((1, 3), jnp.int32),
                               adapter.init_cache(1, 16))
    assert adapter.bind(InferenceConfig()) is not None


# ------------------------------------------------------------- the shares


def test_the_two_expert_shares_add_up_to_the_uncut_layer(model):
    """The layer's output from experts 0-3 plus that from experts 4-7, the
    shared expert and the residual counted once, is the uncut reference
    layer: by the reference's own parts, and by the program's."""
    params, i = model[1], 2
    whole_cfg = CFG._replace(experts_held=None)
    key = jax.random.PRNGKey(11)
    full = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
    other = decoder.init_params(key, CFG)["layers"]
    full = dict(full, w_gate_up=jnp.concatenate(
        [full["w_gate_up"], other["w_gate_up"][i]]), w_down=jnp.concatenate(
            [full["w_down"], other["w_down"][i]]))
    tree = dict(params, layers=jax.tree_util.tree_map(
        lambda a: a[None], full), mamba=jax.tree_util.tree_map(
            lambda a: a[1:2], params["mamba"]))
    one = whole_cfg._replace(n_layer=1, layer_types=("mamba",))
    names = next(iter(builder.published_names(tree, one)["layers"]))
    x = jax.random.normal(key, (10, CFG.hidden_size))
    args = dict(kind="mamba", n_head=4, n_kv=2, scale=CFG.attn_scale,
                mamba_heads=4, d_state=16, top_k=3, eps=CFG.rms_norm_eps,
                residual=CFG.residual_multiplier)
    want, _ = reference.block(x, names, held=(0, 8), **args)

    def share(first):
        sub = dict(names, **{k: names[k][first:first + 4] for k in
                             ("gate_proj", "up_proj", "down_proj")})
        return reference.block(x, sub, held=(first, 4), parts=True, **args)

    mixed, _, low, shared = share(0)
    _, h, high, _ = share(4)
    np.testing.assert_allclose(
        np.asarray(mixed + CFG.residual_multiplier * (low + high + shared)),
        np.asarray(want), rtol=1e-5, atol=1e-5)

    # the program: its two shares' feed-forward halves against the same
    def program(first):
        cfg = CFG._replace(experts_held=(first, 4))
        layer = dict(full, w_gate_up=full["w_gate_up"][first:first + 4],
                     w_down=full["w_down"][first:first + 4])
        out, counts, absent = decoder.moe(layer, cfg, mixed[None])
        assert float(jnp.sum(counts) + absent) == 10 * 3
        return out[0] - mixed

    got = mixed + program(0) + program(4) - CFG.residual_multiplier * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_dispatch_counts_only_the_held_experts():
    weights = jnp.asarray([[0.6, 0.4], [0.7, 0.3]])
    experts = jnp.asarray([[1, 6], [5, 2]], jnp.int32)
    gate, load = routed.dispatch(weights, experts, 4, first=4)
    np.testing.assert_allclose(gate, [[0, 0, 0.4, 0], [0, 0.7, 0, 0]])
    np.testing.assert_array_equal(load, [0, 1, 1, 0])
    gate, load = routed.dispatch(weights, experts, 8)
    assert float(jnp.sum(gate)) == pytest.approx(2.0) and load.sum() == 4


# ------------------------------------------------------------ the kernels


def test_the_one_token_step_is_the_chunked_recurrence():
    """Ten one-token steps against the chunked form over the same ten
    tokens, and a row handed ``dt`` 0 keeps its state bit for bit."""
    rng = np.random.RandomState(0)
    b, n, h, p = 3, 16, 4, 8
    a = -jnp.exp(jnp.asarray(rng.randn(h), jnp.float32))
    x = jnp.asarray(rng.randn(10, b, h, p), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (10, b, h)), jnp.float32)
    bs, cs = (jnp.asarray(rng.randn(10, b, n), jnp.float32) for _ in "bc")
    ssm = jnp.zeros((b, n, h * p))
    ys = []
    for t in range(10):
        y, ssm = mamba2.step(x[t], dt[t], a, bs[t], cs[t], ssm)
        ys.append(y)
    chunked, end = mamba2.ssd(x.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
                              a, bs.transpose(1, 0, 2), cs.transpose(1, 0, 2),
                              jnp.zeros((b, n, h * p)), chunk=4)
    np.testing.assert_allclose(np.stack(ys, 1), chunked, **SAME)
    np.testing.assert_allclose(np.asarray(ssm), end, **SAME)
    # a row that must not move: dt 0 is decay 1 and input 0
    _, still = mamba2.step(x[0], dt[0].at[2].set(0.0), a, bs[0], cs[0], ssm)
    np.testing.assert_array_equal(np.asarray(still[2]), np.asarray(ssm[2]))
    assert np.abs(np.asarray(still[0] - ssm[0])).max() > 0


@pytest.mark.parametrize("heads, kv_heads, d, s", [
    (4, 1, 128, 1), (4, 2, 128, 8), (8, 2, 32, 1), (4, 2, 64, 3)])
def test_grouped_query_heads_through_the_paged_kernels(heads, kv_heads, d, s):
    """``kv_append`` then ``paged_decode`` (interpreted) with fewer stored
    heads than query heads, at head dims that fill a lane tile and that
    share one, against the gather path."""
    rng = np.random.RandomState(heads + d + s)
    b, page, n_lp, layers = 3, 128, 2, 2
    g = da.lane_pack(d, kv_heads)
    hp = -(-kv_heads // g)
    arenas = tuple(jnp.asarray(rng.randn(layers, 1 + b * n_lp, hp, page,
                                         g * d), jnp.float32)
                   for _ in "kv")
    tbl = 1 + jnp.arange(b * n_lp, dtype=jnp.int32).reshape(b, n_lp)
    pos = jnp.asarray([5, 130, 127], jnp.int32)
    new = tuple(jnp.asarray(rng.randn(b, kv_heads, s, d), jnp.float32)
                for _ in "kv")
    q = jnp.asarray(rng.randn(b, heads, s, d), jnp.float32)
    k, v = da.kv_append(arenas, new, tbl, pos, layer=1)
    got = da.flash_decode_attention_paged(q, k, v, tbl, pos, scale=0.1,
                                          layer=1)
    want = da.decode_attention_paged_reference(q, k[1], v[1], tbl, pos,
                                               scale=0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and by hand: query head j reads stored head j // rep
    rep = heads // kv_heads
    planes = [jnp.repeat(da.gather_pages(a[1], tbl, kv_heads, g), rep, 1)
              for a in (k, v)]
    byhand = da.decode_attention_reference(q, *planes, pos, scale=0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(byhand),
                               rtol=2e-5, atol=2e-5)


def test_the_kernel_path_serves_what_the_gather_path_serves(model):
    """The engine with the kernels on (interpreted): ``kv_append`` /
    ``paged_decode`` with grouped-query heads beside the state's step."""
    prompts = [tokens(n, seed=40 + n)[0] for n in (6, 11)]
    kw = dict(max_slots=2, max_len=192, chunk_size=2, prefill_chunk=8,
              kv_page_len=128)
    eng = engine(model, use_flash_decode=True, **kw)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    assert eng.compile_count == 1
    for p, r in zip(prompts, reqs):
        assert r.tokens == alone(model, p, 6, **kw)
