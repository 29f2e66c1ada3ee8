"""Kimi Delta Attention (``models/kda.py``) and the stack it serves in
(``models/decoder.py``: KDA layers with a matrix state a slot beside latent
attention without positions over a plane as deep as the MLA layers only, a
leading dense layer, the sigmoid router over a chip's share of the experts)
against the plain reference (``benchmark/reference/kimi_linear.py``: a
token-by-token recurrence, the expanded MLA) at a tiny size in float32:
hidden 64, 4 layers (KDA, KDA, KDA, MLA) of which the first dense, 4 KDA heads
of 16, 4 MLA heads of nope 16 / shared 8 / value 16 over a latent of 32, 16
experts, top-3, 8 held.

Tolerances: float32 end to end. The chunked form solves a sub-chunk's delta
rule at once and folds the decay pairwise, the one-token form reads the state
once for both products (``o = S'^T q + (k . q) u``), the reference loops
token by token as published: the three differ by the order of their sums,
2e-5 relative on states and outputs of order 0.1 to 1 (measured 4e-7); the
absorbed MLA reassociates two matmuls a head: 2e-4 on logits that spread 0.8
(measured 5e-6). What must not move does not move by one bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import kimi_linear as reference
from deepspeed_tpu.inference import InferenceEngine, kv_pool
from deepspeed_tpu.inference.adapters import DecoderAdapter
from deepspeed_tpu.models import decoder, kda
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from tests.unit.compiled import served_alone

builder = harness.load_by_name("model_builders", "kimi_linear")

CFG = DecoderConfig(
    vocab_size=256, n_layer=4, n_head=4, head_dim=24, hidden_size=64,
    n_positions=4096, n_experts=16, experts_per_token=3, expert_width=32,
    qk_norm=False, norm_topk_prob=True, dtype=jnp.float32,
    initializer_range=0.1, rope=False, shared_width=32, experts_held=(0, 8),
    layer_types=("kda", "kda", "kda", "attention"), kv_lora_rank=32,
    q_lora_rank=0, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    dense_layers=1, dense_width=96, router_scoring="sigmoid",
    routed_scaling=2.446, kda_heads=4, kda_head_dim=16)
TOL = dict(rtol=2e-4, atol=2e-4)
SAME = dict(rtol=2e-5, atol=2e-5)
STATE = ("slot_kda0", "slot_kda2", "slot_kdaconv0", "slot_kdaconv2")


@pytest.fixture(scope="module")
def model():
    m = DecoderLM(CFG)
    key = jax.random.PRNGKey(0)
    # the selection bias drawn, not zero: choosing with it and weighting
    # without it then differ
    return m, builder.shared.rescaled(jax.jit(m.init)(key)["params"], key, 1.0,
                                      0.1)


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


def recurrence_inputs(t, h=3, d=16, seed=0, batch=2):
    """q, k, v, g [B, T, H, d] and beta [B, T, H] as a KDA layer makes them:
    unit keys, queries scaled, decays between a token and a thousand."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (batch, t, h, d)) for key in ks[:3])
    g = -jnp.exp(jax.random.uniform(ks[3], (batch, t, h, d), minval=-7.0,
                                    maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, t, h)))
    return kda.l2norm(q) * d ** -0.5, kda.l2norm(k), v, g, beta


# ------------------------------------------------------ the recurrence


@pytest.mark.parametrize("chunks", [1, 3, 8, 37], ids=[
    "token_by_token", "threes", "eights", "whole"])
def test_step_and_chunked_are_the_references_token_loop(chunks):
    """37 tokens through the program in slices of ``chunks`` (a slice of one
    token takes ``step``, a longer one ``chunked``, whose sub-chunks of 16
    a slice of 37 crosses twice): outputs and the state after are the
    reference's, so a prompt's state does not depend on how it was
    chunked."""
    q, k, v, g, beta = recurrence_inputs(37)
    want_o, want_s = zip(*(reference.delta_rule(*(x[b] for x in
                                                  (q, k, v, g, beta)))
                           for b in range(2)))
    state, outs = jnp.zeros((2, 3, 16, 16)), []
    for lo in range(0, 37, chunks):
        sl = slice(lo, lo + chunks)
        if chunks == 1:
            o, state = kda.step(q[:, lo], k[:, lo], v[:, lo], g[:, lo],
                                beta[:, lo], state)
            o = o[:, None]
        else:
            o, state = kda.chunked(q[:, sl], k[:, sl], v[:, sl], g[:, sl],
                                   beta[:, sl], state)
        outs.append(o)
    assert float(jnp.abs(jnp.stack(want_s)).max()) > 0.1
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)),
                               np.asarray(jnp.stack(want_o)), **SAME)
    np.testing.assert_allclose(np.asarray(state), np.asarray(
        jnp.stack(want_s)), **SAME)


def test_a_channel_that_forgets_everything_overflows_nothing():
    """A log-decay of -60 a token: e^-960 a sub-chunk. Folded into q and k
    apart, ``exp(-G)`` is infinite in float32; folded pairwise the chunked
    form is the token loop."""
    q, k, v, g, beta = recurrence_inputs(20, seed=1, batch=1)
    g = g.at[..., :4].set(-60.0)
    want_o, want_s = reference.delta_rule(*(x[0] for x in (q, k, v, g, beta)))
    o, state = kda.chunked(q, k, v, g, beta, jnp.zeros((1, 3, 16, 16)))
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(state).all())
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want_o), **SAME)
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(want_s),
                               **SAME)


# ---------------------------------------------- the one-token kernel
# ``step`` at a shape that TAKES ``kda_update`` (a float32 state of whole
# tiles: d 128), through the Pallas interpreter: the same sums in the same
# order as the plain form, so the two agree to float32 rounding (measured:
# bit for bit here) and both are the reference's token loop, far under the
# benchmark's state probe (2e-3).

KERNEL_D = 128
CLOSE = dict(rtol=1e-5, atol=1e-5)


def rel_err(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def one_token(batch, h, seed=0):
    """One token's q, k, v, g, beta and a state of order 1, at d 128."""
    q, k, v, g, beta = (x[:, 0] for x in recurrence_inputs(
        1, h=h, d=KERNEL_D, seed=seed, batch=batch))
    state = jax.random.normal(jax.random.PRNGKey(seed + 100),
                              (batch, h, KERNEL_D, KERNEL_D))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("shape, heads", [
    ((128, 32, 128, 128), 32),        # the cell's pool: 4 x 2 MiB of blocks
    ((1, 32, 128, 128), 32),          # the benchmark's state probe
    ((4, 3, 128, 128), 3), ((2, 64, 128, 128), 32), ((2, 7, 256, 256), 7),
    ((2, 96, 128, 256), 24), ((2, 4, 8, 128), 4),
    # the plain form: a tiny d, lanes or sublanes that are no whole tile
    ((3, 4, 16, 16), 0), ((2, 4, 128, 64), 0), ((2, 4, 12, 128), 0)])
def test_the_shape_rule_decides_kernel_or_plain_form_and_the_unit(shape,
                                                                   heads):
    from deepspeed_tpu.ops.transformer.kernels import kda_update

    assert kda_update.unit_heads(shape, jnp.float32) == heads
    assert kda_update.supported(shape, jnp.float32) == (heads > 0)
    # a state kept in bf16 is not the kernel's, whatever its shape
    assert kda_update.unit_heads(shape, jnp.bfloat16) == 0


def _calls_kernel(fn, *args):
    return "kda_update" in jax.jit(fn).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("batch, h", [(1, 32), (1, 3), (4, 4), (3, 5)],
                         ids=["probe_b1_h32", "b1_h3", "b4_h4", "b3_h5"])
def test_step_through_the_kernel_is_the_plain_form(batch, h):
    args = one_token(batch, h, seed=batch + h)
    assert _calls_kernel(kda.step, *args)
    assert not _calls_kernel(kda.step_plain, *args)
    o, state = jax.jit(kda.step)(*args)
    want_o, want_state = jax.jit(kda.step_plain)(*args)
    assert rel_err(o, want_o) < 1e-6 and rel_err(state, want_state) < 1e-6
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), **CLOSE)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state),
                               **CLOSE)


def test_on_a_mesh_every_shard_runs_the_kernel_on_whole_rows_and_heads():
    """Under ``kernels_on_mesh`` (a 2x2 'data' x 'model' mesh) the launch is
    shard-local with every operand whole: the pool keeps a slot's state
    replicated, and the result is the plain form's on one device."""
    from deepspeed_tpu.ops.transformer.kernels.attention import (
        kernels_on_mesh)
    from deepspeed_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.build_mesh(devices=jax.devices()[:4], num_mp=2, num_dp=2)
    args = one_token(2, 4, seed=11)
    fresh = jnp.asarray([True, False])

    def on_mesh(*a):
        with kernels_on_mesh(mesh):
            return kda.step(*a, fresh=fresh)

    assert _calls_kernel(on_mesh, *args)
    o, state = jax.jit(on_mesh)(*args)
    want_o, want_state = kda.step_plain(*args, fresh=fresh)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), **CLOSE)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state),
                               **CLOSE)


def test_a_tiny_d_runs_the_plain_form():
    q, k, v, g, beta = (x[:, 0] for x in recurrence_inputs(1))
    state = jnp.ones((2, 3, 16, 16))
    assert not _calls_kernel(kda.step, q, k, v, g, beta, state)
    o, after = kda.step(q, k, v, g, beta, state)
    want_o, want = kda.step_plain(q, k, v, g, beta, state)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
    np.testing.assert_array_equal(np.asarray(after), np.asarray(want))


@pytest.mark.parametrize("batch, h, t", [(1, 4, 300), (3, 2, 200)],
                         ids=["probe_b1", "scan_b3"])
def test_the_kernel_in_a_scan_is_the_references_token_loop(batch, h, t):
    """``t`` tokens a token at a time inside ``lax.scan`` with the state as
    the carry (as the decode scan and the benchmark's state probe hold it):
    outputs and the state after are the reference's."""
    q, k, v, g, beta = recurrence_inputs(t, h=h, d=KERNEL_D, seed=7,
                                         batch=batch)

    @jax.jit
    def run(q, k, v, g, beta):
        def token(state, x):
            o, state = kda.step(*x, state)
            return state, o

        state, o = jax.lax.scan(
            token, jnp.zeros((batch, h, KERNEL_D, KERNEL_D)),
            tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1), state

    assert "kda_update" in run.lower(q, k, v, g, beta).as_text(
        debug_info=True)
    o, state = run(q, k, v, g, beta)
    want_o, want_s = zip(*(reference.delta_rule(*(x[b] for x in
                                                  (q, k, v, g, beta)))
                           for b in range(batch)))
    assert rel_err(state, jnp.stack(want_s)) < 2e-5
    assert rel_err(o, jnp.stack(want_o)) < 2e-5
    np.testing.assert_allclose(np.asarray(state), np.asarray(
        jnp.stack(want_s)), **SAME)
    np.testing.assert_allclose(np.asarray(o), np.asarray(jnp.stack(want_o)),
                               **SAME)


def test_idle_rows_stay_bit_identical_beside_moving_rows_in_one_call():
    """``g = 0`` and ``beta = 0`` (what ``gates`` hands a row that is not
    decoding): ``S * 1 + k * 0``, the state bit for bit, whatever the
    row's q, k and v, beside rows that move."""
    q, k, v, g, beta, state = one_token(4, 3, seed=3)
    idle = jnp.asarray([False, True, False, True])
    g = jnp.where(idle[:, None, None], 0.0, g)
    beta = jnp.where(idle[:, None], 0.0, beta)
    _, after = jax.jit(kda.step)(q, k, v, g, beta, state)
    got, before = np.asarray(after), np.asarray(state)
    for b in range(4):
        if idle[b]:
            assert got[b].tobytes() == before[b].tobytes()
        else:
            assert np.abs(got[b] - before[b]).max() > 1e-3


def test_a_fresh_row_starts_from_zeros_with_its_slot_full_of_nan():
    """The frontier-0 flag is a SELECT inside the kernel: a fresh row's
    slot may hold anything, NaN included, and the rows beside it keep
    theirs."""
    q, k, v, g, beta, state = one_token(3, 4, seed=5)
    fresh = jnp.asarray([False, True, False])
    dirty = state.at[1].set(jnp.nan)
    o, after = jax.jit(kda.step)(q, k, v, g, beta, dirty, fresh=fresh)
    want_o, want = kda.step_plain(q, k, v, g, beta,
                                  state.at[1].set(0.0))
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(after).all())
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), **CLOSE)
    np.testing.assert_allclose(np.asarray(after), np.asarray(want), **CLOSE)
    # a fresh row's state after one token is the rank-one write alone
    np.testing.assert_allclose(
        np.asarray(after[1]), np.asarray(
            k[1][:, :, None] * (beta[1][:, None] * v[1])[:, None, :]),
        **CLOSE)
    # without the flag (the probe passes none) no row is fresh
    _, kept = jax.jit(kda.step)(q, k, v, g, beta, state)
    np.testing.assert_allclose(np.asarray(kept), np.asarray(
        kda.step_plain(q, k, v, g, beta, state)[1]), **CLOSE)


def test_the_mixer_hands_the_kernel_its_fresh_rows():
    """``mixer`` at a kernel shape, one token: the frontier-0 select is the
    kernel's (no ``select`` of a whole state outside it), a fresh row with
    a slot full of NaN comes out as from zeros, an idle row keeps its
    bits."""
    cfg = CFG._replace(kda_heads=2, kda_head_dim=KERNEL_D)
    p = kda.init_layer(jax.random.PRNGKey(0), cfg)
    hid = jax.random.normal(jax.random.PRNGKey(1), (3, 1, cfg.hidden_size))
    state = jax.random.normal(jax.random.PRNGKey(2),
                              (3, 2, KERNEL_D, KERNEL_D))
    tail = jnp.zeros((3, cfg.kda_conv - 1, 3 * 2 * KERNEL_D))
    pos, n_valid = jnp.asarray([0, 5, 5]), jnp.asarray([1, 1, 0])
    mix = jax.jit(lambda *a: kda.mixer(p, cfg, *a))
    out, after, _ = mix(hid, state.at[0].set(jnp.nan), tail, pos, n_valid)
    want_out, want, _ = mix(hid, state.at[0].set(0.0), tail, pos, n_valid)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    np.testing.assert_array_equal(np.asarray(after), np.asarray(want))
    assert np.asarray(after[2]).tobytes() == np.asarray(state[2]).tobytes()
    assert np.abs(np.asarray(after[1] - state[1])).max() > 1e-6


def test_the_mixers_state_and_tails_are_the_references(model):
    """The whole mixer of a KDA layer on the reference's normed stream: what
    it adds to the stream, the state after the last token and the three rows
    the convolutions keep."""
    ids = tokens(21, seed=2)
    seen = {}
    builder.reference_logits(model[1], ids, CFG, watch=lambda layer, b, s:
                             seen.setdefault(layer, s))
    for layer, j in ((0, 0), (2, 2)):
        p = jax.tree_util.tree_map(lambda a: a[j], model[1]["kda"])
        out, state, tail = kda.mixer(
            p, CFG, seen[layer]["mix_in"][None], jnp.zeros((1, 4, 16, 16)),
            jnp.zeros((1, 3, 192)), jnp.zeros((1,), jnp.int32),
            jnp.asarray([21]))
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(
            seen[layer]["mix_out"]), **SAME)
        np.testing.assert_allclose(np.asarray(state[0]), np.asarray(
            seen[layer]["state"]), **SAME)
        np.testing.assert_allclose(np.asarray(tail[0]), np.asarray(
            seen[layer]["tail"]), **SAME)


# ------------------------------------------------- against the reference


def test_the_cache_free_pass_is_the_reference(model):
    ids = tokens(40, rows=2)
    want = builder.reference_logits(model[1], ids, CFG)
    got = np.asarray(jax.jit(model[0].apply)({"params": model[1]},
                                             jnp.asarray(ids)))
    assert want.std() > 0.5          # logits of order 1, so TOL means it
    np.testing.assert_allclose(got, want, **TOL)


def paged_cache(adapter, rows, page, max_len):
    """The engine's pool (``kv_pool.init_pool``: the latent plane, the
    state and the tails a slot) as the cache a step is handed."""
    pool = kv_pool.init_pool(adapter.cache_spec(), rows, max_len, slack=page,
                             page_len=page)
    n_lp = pool["block_tbl"].shape[1]
    tbl = 1 + jnp.arange(rows * n_lp, dtype=jnp.int32).reshape(rows, n_lp)
    assert "v" not in pool and pool["k"].shape[0] == 1
    return dict({name: pool[name] for name in pool
                 if name.startswith("slot_")}, k=pool["k"], block_tbl=tbl,
                pos=jnp.zeros((rows,), jnp.int32), **adapter.aux_state())


@pytest.mark.parametrize("kernels, page", [(False, 8), (True, 128)],
                         ids=["gather", "interpreted_kernels"])
def test_prefill_then_decode_through_three_caches_is_the_reference(
        model, kernels, page):
    """The prompt's 40 tokens through the lane in unequal slices (the state
    and the tails carried between them, the latents appended to the paged
    plane), then 16 tokens a step at a time: every position's LOGITS are the
    reference's full forward pass. Through the scatter, the gather and the
    einsums (pages of 8), and through ``kv_append`` and ``latent_decode``
    interpreted (pages of 128)."""
    ids = tokens(56, seed=3, rows=2)
    want = builder.reference_logits(model[1], ids, CFG)
    adapter = DecoderAdapter.from_model(model[0], use_flash_decode=kernels)
    prefill, decode = jax.jit(adapter.prefill_append), \
        jax.jit(adapter.decode_step)
    cache, out, lo = paged_cache(adapter, 2, page, 256), [], 0
    for n in (5, 16, 3, 16):
        logits, cache = prefill(model[1], ids[:, lo:lo + n], cache)
        out.append(logits)
        lo += n
    for t in range(lo, ids.shape[1]):
        logits, cache = decode(model[1], ids[:, t], cache)
        out.append(logits[:, None])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(out, axis=1)),
                               want, **TOL)
    assert "v" not in cache and cache["k"].shape[2:] == (1, page, 128)
    assert cache["slot_kda1"].shape == (2, 4, 16, 16)


def test_the_reference_imports_nothing_of_the_program():
    import inspect

    assert "deepspeed_tpu" not in inspect.getsource(reference).replace(
        "``deepspeed_tpu``", "")


# ------------------------------------------------------- the state a slot


def test_pad_columns_and_idle_rows_leave_state_and_tails_bit_identical(
        model, adapter):
    ids = jnp.asarray(tokens(12, seed=4, rows=2))
    prefill, decode = jax.jit(adapter.prefill_append), \
        jax.jit(adapter.decode_step)
    cache = adapter.init_cache(2, 32)
    _, cache = prefill(model[1], ids[:, :8], cache, jnp.asarray([8, 8]))
    before = {k: np.asarray(v) for k, v in cache.items()}
    # row 0 appends 4 real columns, row 1 none (all four are padding)
    _, after = prefill(model[1], ids[:, 8:], cache, jnp.asarray([4, 0]))
    # and a decode step in which only row 0 is live
    _, after = decode(model[1], ids[:, 0],
                      dict(after, n_valid=jnp.asarray([1, 0])))
    for name in STATE:
        got = np.asarray(after[name])
        assert got[1].tobytes() == before[name][1].tobytes()
        assert np.abs(got[0] - before[name][0]).max() > 0
    # two real columns then two of padding are the two columns alone
    _, padded = prefill(model[1], ids[:, 8:], cache, jnp.asarray([2, 2]))
    _, short = prefill(model[1], ids[:, 8:10], cache, jnp.asarray([2, 2]))
    for name in STATE:
        np.testing.assert_allclose(np.asarray(padded[name]),
                                   np.asarray(short[name]), **SAME)
    np.testing.assert_array_equal(np.asarray(padded["slot_kdaconv1"]),
                                  np.asarray(short["slot_kdaconv1"]))


def test_a_row_at_frontier_zero_starts_from_zeros_whatever_its_slot_holds(
        model, adapter):
    ids = jnp.asarray(tokens(9, seed=5, rows=2))
    clean = adapter.init_cache(2, 32)
    dirty = dict(clean, **{
        name: jnp.full_like(clean[name], 3.0) for name in clean
        if name.startswith("slot_")})
    prefill = jax.jit(adapter.prefill_append)
    want, after_clean = prefill(model[1], ids, clean)
    got, after_dirty = prefill(model[1], ids, dirty)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for name in STATE:
        np.testing.assert_array_equal(np.asarray(after_dirty[name]),
                                      np.asarray(after_clean[name]))


def test_the_pool_holds_three_caches_and_counts_them(adapter):
    spec = adapter.cache_spec()
    assert (spec.n_layer, spec.n_head, spec.n_embd, spec.latent) == \
        (1, 1, 128, 32)
    pool = kv_pool.init_pool(spec, 3, 64, slack=8, page_len=8)
    assert "v" not in pool and pool["k"].shape == (1, 3 * 9 + 1, 1, 8, 128)
    assert all(pool["slot_kda{}".format(j)].shape == (3, 4, 16, 16)
               and pool["slot_kda{}".format(j)].dtype == jnp.float32
               and pool["slot_kdaconv{}".format(j)].shape == (3, 3, 192)
               for j in range(3))
    state = 3 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert 3 * kv_pool.slot_state_nbytes(spec) == state
    flat = kv_pool.init_pool(spec._replace(slot_state=()), 3, 64, slack=8,
                             page_len=8)
    assert kv_pool.pool_nbytes(pool) - kv_pool.pool_nbytes(flat) == state
    view = kv_pool.cache_view(dict(pool, active=jnp.asarray(
        [True, False, True])))
    assert "v" not in view and view["k"] is pool["k"]
    np.testing.assert_array_equal(view["n_valid"], [1, 0, 1])
    assert set(kv_pool.fold_cache(pool, view)) == set(pool)


# ----------------------------------------------------------- the refusals


@pytest.mark.parametrize("key, mechanism, whose", [
    ("int8_kv", "int8 planes", "latent-attention cache"),
    ("prefix_cache", "prefix cache", "latent-attention cache"),
    ("spec_decode", "speculative decoding", "recurrent state")])
def test_a_latent_and_recurrent_model_gets_both_sets_of_refusals(
        model, key, mechanism, whose):
    with pytest.raises(ValueError, match=mechanism) as e:
        engine(model, **{key: True})
    assert whose in str(e.value)
    # the state's own refusals name the layers that carry it, not Mamba
    recurrent = DecoderAdapter.from_model(model[0], use_flash_decode=False)
    assert recurrent.recurrent and recurrent.latent
    if whose == "recurrent state":
        assert "3 kda layers" in str(e.value)
        assert "Mamba" not in str(e.value)
    # the same key serves the block with keys and values a head
    plain = DecoderLM(CFG._replace(
        kv_lora_rank=0, head_dim=32, n_layer=1, dense_layers=0,
        layer_types=None))
    InferenceEngine(plain, plain.init(jax.random.PRNGKey(0))["params"],
                    config=dict(max_slots=2, max_len=64, chunk_size=2,
                                use_flash_decode=False, **{key: True}))


def test_verify_forward_is_refused(model, adapter):
    with pytest.raises(NotImplementedError, match="recurrent state"):
        adapter.verify_forward(model[1], jnp.zeros((1, 3), jnp.int32),
                               adapter.init_cache(1, 16))


# ------------------------------------------------------------- the shares


def test_the_sixteen_shares_add_up_to_the_uncut_layer(model):
    """An expert layer's feed-forward from each of 16 chips' shares (one
    expert of the router's 16 each), the shared expert counted once, is the
    uncut reference layer: by the reference's own parts, and by the
    program's."""
    whole_cfg = CFG._replace(experts_held=None)
    key = jax.random.PRNGKey(11)
    layer = jax.tree_util.tree_map(
        lambda a: a[1], builder.shared.rescaled(
            decoder.init_params(key, whole_cfg), key, 1.0, 0.1)["moe"])
    assert layer["w_gate_up"].shape[0] == 16
    f = CFG.expert_width
    h = jax.random.normal(key, (10, CFG.hidden_size))
    names = {"gate": layer["router"],
             "e_score_correction_bias": layer["router_bias"],
             "gate_proj": layer["w_gate_up"][:, :, :f],
             "up_proj": layer["w_gate_up"][:, :, f:],
             "down_proj": layer["w_down"],
             "shared_gate": layer["shared_gate_up"][:, :CFG.shared_width],
             "shared_up": layer["shared_gate_up"][:, CFG.shared_width:],
             "shared_down": layer["shared_down"]}
    hyper = builder.hyper(whole_cfg)
    kept, _ = reference.router(h, names, hyper)
    assert int((kept > 0).sum()) == 10 * 3
    want, = reference.feed_forward([h], [kept], names, hyper)
    zero = dict(names, **{k: jnp.zeros_like(names[k]) for k in
                          ("shared_gate", "shared_up", "shared_down")})
    shared, = reference.feed_forward([h], [jnp.zeros_like(kept)], names,
                                     hyper)

    def share(first):
        sub = dict(zero, **{k: names[k][first:first + 1] for k in
                            ("gate_proj", "up_proj", "down_proj")})
        return reference.feed_forward(
            [h], [kept], sub, dict(hyper, held=(first, 1)))[0]

    np.testing.assert_allclose(
        np.asarray(sum(share(e) for e in range(16)) + shared),
        np.asarray(want), rtol=1e-5, atol=1e-5)

    # the program: each share's feed-forward half on the same stream (its
    # norm at 1, so the normed stream is the stream's own norm)
    x = h[None]
    layer = dict(layer, ffn_norm=jnp.ones((CFG.hidden_size,)))
    normed = decoder._rms32(x, layer["ffn_norm"], CFG.rms_norm_eps)[0]
    kept, _ = reference.router(normed, names, hyper)
    want, = reference.feed_forward([normed], [kept], names, hyper)
    shared, = reference.feed_forward([normed], [jnp.zeros_like(kept)], names,
                                     hyper)

    def program(first):
        cfg = CFG._replace(experts_held=(first, 1))
        part = dict(layer, w_gate_up=layer["w_gate_up"][first:first + 1],
                    w_down=layer["w_down"][first:first + 1])
        out, counts, absent = decoder.moe(part, cfg, x)
        assert float(jnp.sum(counts) + absent) == 10 * 3
        return out[0] - h

    got = sum(program(e) for e in range(16)) - 15 * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
