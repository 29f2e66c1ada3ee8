"""The ONE-BRANCH stack (Nemotron-H: ``models/decoder.py`` with ``"moe"``
layers, Mamba-2 at more groups than one, ungated relu2 experts) against the
plain reference ``benchmark/reference/nemotron_h.py``, at a size a CPU runs:
the cache-free pass and prefill then decode through a paged cache, whatever
the chunking; the grouped recurrence's two forms against each other and one
group bit for bit as it was; the relu2 experts against a loop over tokens;
the eight shares adding up to the uncut layer; a slot reused, pad columns and
idle rows; the tree, the gauges and what is refused by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import nemotron_h as reference
from deepspeed_tpu.inference import InferenceEngine, kv_pool
from deepspeed_tpu.inference.adapters import DecoderAdapter
from deepspeed_tpu.models import decoder, mamba2
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from deepspeed_tpu.moe import routed
from tests.unit.compiled import compiled, served_alone

builder = harness.load_by_name("model_builders", "nemotron_h")

PATTERN = "MEM*EME"
CFG = DecoderConfig(
    vocab_size=256, n_layer=7, n_head=4, head_dim=16, hidden_size=64,
    n_positions=256, n_experts=8, experts_per_token=3, expert_width=32,
    qk_norm=False, norm_topk_prob=True, tie_word_embeddings=False,
    dtype=jnp.float32, initializer_range=0.05, n_kv_head=2, rope=False,
    shared_width=48, experts_held=(0, 4),
    layer_types=reference.layer_kinds(PATTERN), mamba_heads=8,
    mamba_head_dim=16, mamba_state=16, mamba_conv=4, mamba_chunk=8,
    mamba_groups=4, mamba_dt_apart=True, expert_act="relu2",
    router_scoring="sigmoid", routed_scaling=2.5)
TOL = dict(rtol=2e-4, atol=2e-4)
SAME = dict(rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def model():
    m = DecoderLM(CFG)
    params = jax.jit(m.init)(jax.random.PRNGKey(0))["params"]
    # the head scaled and the selection bias drawn, as the benchmark's
    # builder does: logits that spread about 0.65, a bias that chooses
    return m, jax.jit(lambda p: builder.rescaled(
        p, jax.random.PRNGKey(1), 1.0, 1.6, 1.0, 0.1))(params)


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


@pytest.mark.parametrize("cuts", [
    ((0, 7, 0), (7, 12, 0), (12, 14, 2)), ((0, 14, 0),),
    ((0, 3, 5), (3, 4, 0), (4, 14, 1))], ids=["7-5-2", "14", "3-1-10"])
def test_prefill_chunks_then_decode_are_the_references_one_pass(
        model, adapter, cuts):
    """A prompt of 14 in chunks (some with pad columns), then 6 tokens
    through the one-token step, on a paged pool's cache."""
    ids = tokens(20, seed=1)
    want = builder.reference_logits(model[1], ids, CFG)[0]
    pool = kv_pool.init_pool(adapter.cache_spec(), 1, 64, slack=16,
                             page_len=8)
    cache = dict(kv_pool.cache_view(pool), **adapter.aux_state())
    cache["block_tbl"] = 1 + jnp.arange(
        pool["block_tbl"].shape[1], dtype=jnp.int32)[None]
    del cache["n_valid"]
    got = []
    for lo, hi, pad in cuts:
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
    # every one of the 3 expert layers counted every row it computed, and no
    # other layer counted anything
    rows = sum(hi - lo + pad for lo, hi, pad in cuts) + 6
    assert float(cache["aux_moe_routed"] + cache["aux_moe_absent"]) == \
        rows * CFG.experts_per_token * 3


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
    """One Mamba layer on one sequence: what the builder's state probe
    reads, the program's recurrence against the reference's scan, a group's
    state apart."""
    names = list(builder.published_names(model[1], CFG)["layers"])[0]
    h = jax.random.normal(jax.random.PRNGKey(3), (24, CFG.hidden_size))
    seen = {}
    with jax.default_matmul_precision("highest"):
        reference.mamba(h, names, CFG.mamba_heads, CFG.mamba_groups,
                        CFG.mamba_state, CFG.rms_norm_eps, seen)
    assert seen["state"].shape == (8, 16, 16) and seen["B"].shape == (24, 4,
                                                                      16)
    assert builder.state_error(CFG, seen).max() < 1e-5
    assert builder.state_error(CFG, seen, jnp.bfloat16).max() > 1e-3


# ------------------------------------------------- the grouped recurrence


def _ssd_before_groups(x, dt, a, bmat, cmat, state, chunk):
    """``mamba2.ssd`` as it stood before it took groups (one group), word
    for word: what one group must still give bit for bit."""
    highest = jax.lax.Precision.HIGHEST
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    state = state.reshape(b, n, h, p)
    ys = []
    for lo in range(0, s, chunk):
        sl = slice(lo, min(lo + chunk, s))
        xc, dtc, bc, cc = x[:, sl], dt[:, sl], bmat[:, sl], cmat[:, sl]
        ln = xc.shape[1]
        cum = jnp.cumsum(dtc * a, axis=1)
        seg = cum[:, :, None, :] - cum[:, None, :, :]
        causal = jnp.tril(jnp.ones((ln, ln), bool))[None, :, :, None]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        g = jnp.einsum("btn,bsn->bts", cc, bc, precision=highest)
        m = g[..., None] * decay * dtc[:, None, :, :]
        y = jnp.einsum("btsh,bshp->bthp", m, xc, precision=highest)
        y = y + jnp.einsum("btn,bnhp->bthp", cc, state,
                           precision=highest) * jnp.exp(cum)[..., None]
        rest = jnp.exp(cum[:, -1:, :] - cum) * dtc
        state = jnp.exp(cum[:, -1])[:, None, :, None] * state + jnp.einsum(
            "bsn,bshp->bnhp", bc, rest[..., None] * xc, precision=highest)
        ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
    return y, state.reshape(b, n, h * p)


def _step_before_groups(x, dt, a, bvec, cvec, state):
    b, h, p = x.shape
    decay = jnp.repeat(jnp.exp(dt * a), p, axis=1)
    dtx = (dt[..., None] * x).reshape(b, h * p)
    state = state * decay[:, None, :] + bvec[:, :, None] * dtx[:, None, :]
    y = jnp.sum(state * cvec[:, :, None], axis=1)
    return y.reshape(b, h, p), state


def _recurrence_inputs(groups, t=10, b=3, n=16, h=8, p=8):
    rng = np.random.RandomState(groups)
    a = -jnp.exp(jnp.asarray(rng.randn(h), jnp.float32))
    x = jnp.asarray(rng.randn(b, t, h, p), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (b, t, h)), jnp.float32)
    shape = (b, t, n) if groups == 1 else (b, t, groups, n)
    bs, cs = (jnp.asarray(rng.randn(*shape), jnp.float32) for _ in "bc")
    zero = jnp.zeros((b, n, h * p) if groups == 1
                     else (b, groups, n, h * p // groups))
    return x, dt, a, bs, cs, zero


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_one_token_step_is_the_chunked_recurrence(groups):
    """Ten one-token steps against the chunked form over the same ten
    tokens, and a row handed ``dt`` 0 keeps its state bit for bit."""
    x, dt, a, bs, cs, ssm = _recurrence_inputs(groups)
    ys = []
    for t in range(10):
        y, ssm = mamba2.step(x[:, t], dt[:, t], a, bs[:, t], cs[:, t], ssm)
        ys.append(y)
    chunked, end = mamba2.ssd(x, dt, a, bs, cs, jnp.zeros_like(ssm), chunk=4)
    assert end.shape == ssm.shape
    np.testing.assert_allclose(np.stack(ys, 1), chunked, **SAME)
    np.testing.assert_allclose(np.asarray(ssm), end, **SAME)
    _, still = mamba2.step(x[:, 0], dt[:, 0].at[2].set(0.0), a, bs[:, 0],
                           cs[:, 0], ssm)
    np.testing.assert_array_equal(np.asarray(still[2]), np.asarray(ssm[2]))
    assert np.abs(np.asarray(still[0] - ssm[0])).max() > 0


@pytest.mark.parametrize("groups", [2, 8])
def test_groups_that_share_one_b_and_c_are_one_group(groups):
    """``G`` groups handed the SAME ``B`` and ``C`` are the one-group
    recurrence: head ``j`` reads group ``j // (H / G)`` and nothing else of
    the grouping reaches the numbers."""
    x, dt, a, bs, cs, zero = _recurrence_inputs(1)
    want, end = mamba2.ssd(x, dt, a, bs, cs, zero, chunk=4)
    tile = lambda v: jnp.repeat(v[:, :, None], groups, axis=2)  # noqa: E731
    b, n, w = zero.shape
    got, state = mamba2.ssd(x, dt, a, tile(bs), tile(cs), jnp.zeros(
        (b, groups, n, w // groups)), chunk=4)
    np.testing.assert_allclose(got, want, **SAME)
    np.testing.assert_allclose(
        np.asarray(state).transpose(0, 2, 1, 3).reshape(b, n, w), end, **SAME)


@pytest.mark.parametrize("form", ["ssd", "step"])
def test_one_group_gives_bit_for_bit_what_it_gave(form):
    x, dt, a, bs, cs, zero = _recurrence_inputs(1)
    if form == "ssd":
        got = jax.jit(mamba2.ssd, static_argnums=6)(x, dt, a, bs, cs, zero, 4)
        want = jax.jit(_ssd_before_groups, static_argnums=6)(
            x, dt, a, bs, cs, zero, 4)
    else:
        state = mamba2.ssd(x, dt, a, bs, cs, zero, 4)[1]
        args = (x[:, 0], dt[:, 0], a, bs[:, 0], cs[:, 0], state)
        got, want = jax.jit(mamba2.step)(*args), jax.jit(
            _step_before_groups)(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_one_groups_tree_and_state_are_as_they_were():
    one = CFG._replace(mamba_groups=1, mamba_dt_apart=False)
    tree = jax.eval_shape(lambda: mamba2.init_layer(jax.random.PRNGKey(0),
                                                    one))
    assert "dt_proj" not in tree
    assert tree["in_proj"].shape == (64, 2 * 128 + 2 * 16 + 8)
    shapes = dict((k, s) for k, s, _ in mamba2.state_shapes(one))
    assert shapes["slot_ssm0"] == (16, 128)
    assert shapes["slot_conv0"] == (3, 128 + 2 * 16)
    apart = jax.eval_shape(lambda: mamba2.init_layer(
        jax.random.PRNGKey(0), one._replace(mamba_dt_apart=True)))
    assert apart["in_proj"].shape == (64, 288) and \
        apart["dt_proj"].shape == (64, 8)


# ------------------------------------------------- the relu2 experts


@pytest.mark.parametrize("first, held", [(0, 8), (0, 4), (4, 4), (6, 2)])
def test_relu2_experts_against_a_loop_over_tokens(first, held):
    """``expert_ffn`` in its ungated form against a token's own chosen
    experts, one at a time, over the experts held."""
    rng = np.random.RandomState(first + held)
    t, c, f, e, k = 9, 16, 24, 8, 3
    x = jnp.asarray(rng.randn(t, c), jnp.float32)
    up = jnp.asarray(rng.randn(e, c, f), jnp.float32) * 0.3
    down = jnp.asarray(rng.randn(e, f, c), jnp.float32) * 0.3
    logits = jnp.asarray(rng.randn(t, e), jnp.float32)
    bias = jnp.asarray(rng.randn(e), jnp.float32) * 0.1
    weights, experts = routed.route_grouped(logits, bias, k, 1, 1, 2.5, True)
    gate, load = routed.dispatch(weights, experts, held, first)
    got = routed.expert_ffn(x, gate, up[first:first + held],
                            down[first:first + held], "relu2")
    want = np.zeros((t, c), np.float32)
    for row in range(t):
        for w, ex in zip(np.asarray(weights[row]), np.asarray(experts[row])):
            if first <= ex < first + held:
                hidden = np.maximum(np.asarray(x[row] @ up[ex]), 0.0) ** 2
                want[row] += w * (hidden @ np.asarray(down[ex]))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    assert float(load.sum()) == np.isin(
        np.asarray(experts), np.arange(first, first + held)).sum()
    # the weights: the chosen scores without the bias, over their sum, x 2.5
    scores = 1 / (1 + np.exp(-np.asarray(logits)))
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)
    np.testing.assert_array_equal(
        np.sort(np.asarray(experts), -1),
        np.sort(np.argsort(-(scores + np.asarray(bias)), -1)[:, :k], -1))


def test_the_shared_expert_is_ungated_too():
    rng = np.random.RandomState(5)
    x, up, down = (jnp.asarray(rng.randn(*s), jnp.float32) * 0.3
                   for s in ((7, 16), (16, 24), (24, 16)))
    np.testing.assert_allclose(
        np.asarray(routed.shared_ffn(x, up, down, "relu2")),
        (np.maximum(np.asarray(x @ up), 0.0) ** 2) @ np.asarray(down),
        rtol=1e-4, atol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer(model):
    """The routed parts of the shares 0..7 (one expert each here) plus the
    shared expert ONCE are the uncut reference's expert layer: by the
    reference's own parts, and by the program's ``moe`` a share."""
    params, at = model[1], 1
    whole = CFG._replace(experts_held=None)
    other = decoder.init_params(jax.random.PRNGKey(11), CFG)["moe"]
    stack = {k: v[at] for k, v in params["moe"].items()}
    full = dict(stack, ffn_norm=params["layers"]["norm"][CFG.moe_layers[at]],
                w_up=jnp.concatenate([stack["w_up"], other["w_up"][at]]),
                w_down=jnp.concatenate([stack["w_down"],
                                        other["w_down"][at]]))
    names = {"norm": full["ffn_norm"], "gate": full["router"],
             "e_score_correction_bias": full["router_bias"],
             "up_proj": full["w_up"], "down_proj": full["w_down"],
             "shared_up": full["shared_up"],
             "shared_down": full["shared_down"]}
    hyper = builder.hyper(whole)
    x = jax.random.normal(jax.random.PRNGKey(12), (10, CFG.hidden_size))
    uncut, shared = reference.expert_parts(x, names, hyper)

    def share(first):
        sub = dict(names, up_proj=names["up_proj"][first:first + 1],
                   down_proj=names["down_proj"][first:first + 1])
        return reference.expert_parts(x, sub, dict(hyper, held=(first, 1)))

    parts = [share(first) for first in range(8)]
    np.testing.assert_allclose(np.asarray(sum(p[0] for p in parts)),
                               np.asarray(uncut), rtol=1e-5, atol=1e-5)
    for _, again in parts:      # every share holds the shared expert whole
        np.testing.assert_array_equal(np.asarray(again), np.asarray(shared))

    def program(first):
        cfg = CFG._replace(experts_held=(first, 1))
        layer = dict(full, w_up=full["w_up"][first:first + 1],
                     w_down=full["w_down"][first:first + 1])
        out, counts, absent = decoder.moe(layer, cfg, x[None])
        assert float(jnp.sum(counts) + absent) == 10 * 3
        return out[0] - x

    got = sum(program(first) for first in range(8)) - 7 * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(uncut + shared),
                               **TOL)


# ------------------------------------------------- the state a slot


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


def test_the_pool_holds_a_groups_state_apart_and_counts_it(adapter):
    spec = adapter.cache_spec()
    assert (spec.n_layer, spec.n_head, spec.n_embd) == (1, 2, 32)
    pool = kv_pool.init_pool(spec, 3, 64, slack=8, page_len=8)
    assert pool["k"].shape[0] == 1          # as deep as the layers with keys
    assert all(pool["slot_ssm{}".format(j)].shape == (3, 4, 16, 32)
               and pool["slot_ssm{}".format(j)].dtype == jnp.float32
               for j in range(3))
    # the tail is W + 2 G N wide
    assert pool["slot_conv1"].shape == (3, 3, 128 + 2 * 4 * 16)
    state = 3 * (3 * 16 * 128 * 4 + 3 * 3 * 256 * 4)
    flat = kv_pool.init_pool(spec._replace(slot_state=()), 3, 64, slack=8,
                             page_len=8)
    assert kv_pool.pool_nbytes(pool) - kv_pool.pool_nbytes(flat) == state


def test_a_reused_slot_gives_the_stream_it_gives_alone(model):
    first, second = tokens(9, seed=5)[0], tokens(13, seed=6)[0]
    eng = engine(model, max_slots=1)
    a = eng.submit(first, max_new_tokens=7)
    b = eng.submit(second, max_new_tokens=7)
    eng.run()
    assert eng.compile_count == 1
    # each against an engine of its own that has served nothing before it;
    # no reset from the host: a row at frontier 0 starts from zeros
    assert a.tokens == alone(model, first, 7, fresh=True)
    assert b.tokens == alone(model, second, 7, fresh=True)


def test_the_engine_serves_it_in_one_program_among_neighbours(model):
    """A prompt of three lane chunks admitted while two neighbours decode,
    and every served token the reference's own choice."""
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
    seq = np.concatenate([long, late.tokens])[None]
    want = builder.reference_logits(model[1], seq, CFG)[0]
    rows = want[len(long) - 1:len(long) - 1 + len(late.tokens)]
    assert float(np.max(rows.max(axis=1)
                        - rows[np.arange(len(late.tokens)), late.tokens])) \
        <= 1e-3
    from deepspeed_tpu.telemetry.exporters import prometheus_text

    text = prometheus_text(eng.telemetry)
    assert "ds_tpu_moe_expert_layers" in text
    kinds = [line for line in text.splitlines()
             if line.startswith("ds_tpu_stack_layers")]
    assert sorted(float(line.rsplit(" ", 1)[1]) for line in kinds) == \
        [1, 3, 3]
    assert all(any('kind="{}"'.format(k) in line for line in kinds)
               for k in ("mamba", "moe", "attention"))
    gauges = {line.split("{")[0].split(" ")[0][len("ds_tpu_"):]: float(
        line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith("ds_tpu_moe_") and "expert=" not in line}
    # the 3 expert layers and no other count: a row's 3 choices a layer
    routed_ = gauges["moe_tokens_routed"] + gauges["moe_tokens_absent"]
    assert gauges["moe_expert_layers"] == 3 and routed_ % 9 == 0


# ------------------------------------------------- the tree and the gauges


def test_a_layer_is_one_branch_and_the_expert_stacks_are_as_deep_as_they(
        model):
    tree = jax.tree_util.tree_map(lambda a: a.shape, model[1])
    assert CFG.one_branch and CFG.moe_layers == (1, 4, 6)
    assert CFG.expert_layers == 3 and CFG.kv_layers == (3,)
    assert CFG.mamba_layers == (0, 2, 5)
    assert tree["layers"] == {"norm": (7, 64)}        # ONE norm a layer
    assert tree["moe"]["w_up"] == (3, 4, 64, 32)      # not (7, ..)
    assert tree["moe"]["w_down"] == (3, 4, 32, 64)
    assert tree["moe"]["shared_up"] == (3, 64, 48)
    assert tree["moe"]["router"] == (3, 64, 8)
    assert tree["moe"]["router_bias"] == (3, 8)
    assert "w_gate_up" not in tree["moe"] and "dense" not in tree
    assert tree["attn"]["wqkv"] == (1, 64, (4 + 2 * 2) * 16)
    # dt's columns apart: 2 x 128 + 2 x 4 x 16 + 8 = 392 are no whole tiles
    assert tree["mamba"]["in_proj"] == (3, 64, 384)
    assert tree["mamba"]["dt_proj"] == (3, 64, 8)
    assert tree["lm_head"] == (64, 256)
    # a stack of two-branch layers is what it was
    two = CFG._replace(layer_types=None, expert_act="swiglu")
    assert not two.one_branch and two.expert_layers == 7
    assert two.moe_layers == ()


def test_the_gauges_count_the_expert_layers_and_the_kinds(adapter):
    class Registry(object):
        def __init__(self):
            self.values = {}

        def gauge(self, name, **labels):
            key = (name,) + tuple(sorted(labels.items()))
            return type("G", (), {"set": lambda _, v: self.values.__setitem__(
                key, v)})()

    registry = Registry()
    snap = dict(adapter.aux_state(), pos=np.zeros(3, np.int32))
    adapter.observe(snap, registry)
    v = registry.values
    assert v[("moe_expert_layers",)] == 3 and v[("moe_experts_held",)] == 4
    assert v[("stack_layers", ("kind", "mamba"))] == 3
    assert v[("stack_layers", ("kind", "moe"))] == 3
    assert v[("stack_layers", ("kind", "attention"))] == 1
    assert v[("ssm_state_bytes",)] == 3 * kv_pool.slot_state_nbytes(
        adapter.cache_spec())
    assert ("moe_tokens_absent",) in v


@pytest.mark.parametrize("key, value, mechanism", [
    ("spec_decode", True, "speculative decoding"),
    ("prefix_cache", True, "prefix cache"),
    ("int8_kv", True, "int8")])
def test_what_needs_a_snapshot_of_the_state_is_refused_by_name(
        model, key, value, mechanism):
    with pytest.raises(ValueError, match=mechanism) as e:
        engine(model, **{key: value})
    assert "recurrent state" in str(e.value)
    assert "3 mamba layers" in str(e.value)
