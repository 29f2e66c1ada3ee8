"""Flash-decode kernel (ops/transformer/kernels/decode_attention.py) —
parity against the dense einsum reference over RAGGED frontiers, and
through the decode-step program in models/generation.py. Off-TPU the
Pallas kernel runs in interpret mode, so these tests exercise the real
kernel body (masking, online-softmax rescale, block clamping) on CPU."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.paging import TRASH_PAGE as TRASH
from deepspeed_tpu.models.generation import (
    _forward, as_gencfg, decode_step, generate, init_cache)
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.ops.transformer.kernels import decode_attention as da
from deepspeed_tpu.ops.transformer.kernels.decode_attention import (
    BLOCK_MIN, decode_attention_q8_reference, decode_attention_reference,
    decode_supported, dequantize_kv, flash_decode_attention,
    flash_decode_attention_q8, kv_append, pad_cache_len, planned_block_k,
    quantize_kv, resolve_decode_block)


# The generation primitives under ONE ``jax.jit`` each (the configuration is
# static): the cases below are about what they compute with the kernel on
# and off, not about calling them operation by operation.
forward = jax.jit(_forward, static_argnums=1, static_argnames="last_only")
step = jax.jit(decode_step, static_argnums=1)
# and the paged kernels' launchers with the references beside them, where a
# case only calls them (the launcher's work list and the reference's gather
# are dozens of small operations); ``layer`` and ``block`` are static
paged = jax.jit(da.flash_decode_attention_paged,
                static_argnames=("layer", "block"))
paged_q8 = jax.jit(da.flash_decode_attention_paged_q8,
                   static_argnames=("layer",))
paged_reference = jax.jit(da.decode_attention_paged_reference,
                          static_argnames=("block",))
paged_q8_reference = jax.jit(da.decode_attention_paged_q8_reference)


def qkv(rng, b, h, s, t, d, dtype=jnp.float32):
    q = jnp.asarray(rng.randn(b, h, s, d), dtype)
    k = jnp.asarray(rng.randn(b, h, t, d), dtype)
    v = jnp.asarray(rng.randn(b, h, t, d), dtype)
    return q, k, v


# ------------------------------------------------------------ kernel parity


@pytest.mark.parametrize("block_k", [64, 128])
def test_decode_parity_ragged_frontiers(block_k):
    """S=1 decode rows at wildly different frontiers — including 0 (only
    the row's own key visible) and T-1 (every block active) — in one
    batch: the per-row clamp/mask must hold independently per row."""
    rng = np.random.RandomState(0)
    b, h, t, d = 4, 2, 256, 32
    q, k, v = qkv(rng, b, h, 1, t, d)
    pos = jnp.asarray([0, 3, 128, 255], jnp.int32)
    out = flash_decode_attention(q, k, v, pos, block_k=block_k)
    ref = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_decode_parity_under_jit():
    rng = np.random.RandomState(1)
    q, k, v = qkv(rng, 3, 2, 1, 128, 16)
    pos = jnp.asarray([5, 63, 127], jnp.int32)
    f = jax.jit(lambda *a: flash_decode_attention(*a, block_k=64))
    np.testing.assert_allclose(f(q, k, v, pos),
                               decode_attention_reference(q, k, v, pos),
                               rtol=1e-5, atol=1e-5)


def test_prefill_rows_non_sublane_aligned():
    """S=24 (a prefill bucket, not a multiple of the 8-row sublane): the
    launcher pads the query dim and slices the pad back off; the
    intra-row causal stagger (key t visible to row i iff t <= pos+i)
    must match the reference exactly."""
    rng = np.random.RandomState(2)
    b, h, s, t, d = 3, 2, 24, 128, 32
    q, k, v = qkv(rng, b, h, s, t, d)
    pos = jnp.asarray([0, 50, 104], jnp.int32)  # pos + s <= t
    out = flash_decode_attention(q, k, v, pos, block_k=64)
    ref = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_append_chunk_rows_at_deep_frontiers():
    """Chunked-prefill append shapes: a [B, C] chunk of queries landing
    MID-CACHE (frontier well past 0 — the engine's second and later
    prompt chunks), including a frontier whose chunk exactly fills the
    plane. The per-row stagger must hold at every depth."""
    rng = np.random.RandomState(6)
    b, h, s, t, d = 3, 2, 32, 256, 32
    q, k, v = qkv(rng, b, h, s, t, d)
    pos = jnp.asarray([32, 131, 224], jnp.int32)  # 224 + 32 == t exactly
    out = flash_decode_attention(q, k, v, pos, block_k=64)
    ref = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    # A ragged, non-sublane chunk (the prompt's last slice) mid-cache.
    q2 = q[:, :, :5]
    out = flash_decode_attention(q2, k, v, pos, block_k=64)
    ref = decode_attention_reference(q2, k, v, pos)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_append_forward_flag_parity():
    """append_forward (the chunked-prefill primitive) through both
    attention paths: appending a chunk at a non-zero frontier under the
    flash kernel matches the einsum path's logits."""
    from deepspeed_tpu.models.generation import append_forward

    cfg = GPT2Config.tiny(dropout=0.0, dtype=jnp.float32,
                          use_flash_attention=False)
    model = GPT2LMHeadModel(cfg)
    rng = np.random.RandomState(7)
    ids = rng.randint(0, cfg.vocab_size, size=(1, 12)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.asarray(ids))["params"]
    chunk = rng.randint(0, cfg.vocab_size, size=(1, 8)).astype(np.int32)

    outs = {}
    append = jax.jit(append_forward, static_argnums=1)
    for flash in (False, True):
        g = as_gencfg(cfg, use_flash_decode=flash)
        cache = init_cache(g, 1, 128)  # kernel quantum so flash engages
        _, cache = forward(params, g, jnp.asarray(ids), cache)
        logits, cache = append(params, g, jnp.asarray(chunk), cache,
                               n_valid=jnp.asarray([5]))
        assert int(cache["pos"][0]) == 12 + 5
        outs[flash] = np.asarray(logits)[0, :5]
    np.testing.assert_allclose(outs[True], outs[False],
                               rtol=2e-4, atol=2e-4)


def test_single_kv_block_path():
    """block_k == T collapses to the direct-softmax branch (no scratch)."""
    rng = np.random.RandomState(3)
    q, k, v = qkv(rng, 2, 2, 1, 128, 32)
    pos = jnp.asarray([0, 127], jnp.int32)
    out = flash_decode_attention(q, k, v, pos, block_k=128)
    ref = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_bf16_parity():
    rng = np.random.RandomState(4)
    q, k, v = qkv(rng, 2, 2, 1, 256, 32, jnp.bfloat16)
    pos = jnp.asarray([7, 255], jnp.int32)
    out = flash_decode_attention(q, k, v, pos, block_k=128)
    ref = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_custom_scale_honored():
    rng = np.random.RandomState(5)
    q, k, v = qkv(rng, 2, 1, 1, 128, 16)
    pos = jnp.asarray([64, 100], jnp.int32)
    out = flash_decode_attention(q, k, v, pos, scale=0.5, block_k=64)
    ref = decode_attention_reference(q, k, v, pos, scale=0.5)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# ------------------------------------------------- block policy / fallback


def test_pad_cache_len_and_supported():
    assert pad_cache_len(1) == BLOCK_MIN
    assert pad_cache_len(128) == 128
    assert pad_cache_len(129) == 256
    assert decode_supported(256) and not decode_supported(100)


def test_unsupported_length_falls_back_to_reference():
    """T not a multiple of BLOCK_MIN and no explicit block: the public
    entry must return the dense reference, bit-for-bit."""
    rng = np.random.RandomState(6)
    q, k, v = qkv(rng, 2, 2, 1, 100, 16)
    pos = jnp.asarray([0, 99], jnp.int32)
    out = flash_decode_attention(q, k, v, pos)
    ref = decode_attention_reference(q, k, v, pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_env_block_override(monkeypatch):
    rng = np.random.RandomState(7)
    q, k, v = qkv(rng, 2, 1, 1, 256, 16)
    pos = jnp.asarray([10, 200], jnp.int32)
    monkeypatch.setenv("DS_TPU_FLASH_DECODE_BLOCK", "64")
    assert resolve_decode_block(q, k) == 64
    out = flash_decode_attention(q, k, v, pos)
    np.testing.assert_allclose(out, decode_attention_reference(q, k, v, pos),
                               rtol=1e-5, atol=1e-5)
    # An illegal override (does not divide T) means dense fallback, not
    # a crash at pallas_call.
    monkeypatch.setenv("DS_TPU_FLASH_DECODE_BLOCK", "96")
    assert resolve_decode_block(q, k) is None


def test_explicit_block_clamped_to_plane():
    rng = np.random.RandomState(8)
    q, k, _ = qkv(rng, 1, 1, 1, 128, 16)
    assert resolve_decode_block(q, k, block_k=512) == 128  # min(bk, T)
    assert resolve_decode_block(q, k, block_k=96) is None  # 128 % 96 != 0


def test_planned_block_k_table_or_default():
    # No table entry for this made-up shape: the default (256 when it
    # divides T, else the largest legal candidate).
    assert planned_block_k(2, 2, 1, 512, 32, jnp.float32) == 256
    assert planned_block_k(2, 2, 1, 128, 32, jnp.float32) == 128
    assert planned_block_k(2, 2, 1, 100, 32, jnp.float32) is None


# ------------------------------------------- decode-step program parity


@functools.lru_cache(maxsize=None)
def tiny_model(seed=0):
    """One compiled init a seed: no case writes the parameters."""
    cfg = GPT2Config.tiny(dropout=0.0, dtype=jnp.float32,
                          use_flash_attention=False)
    model = GPT2LMHeadModel(cfg)
    ids = np.random.RandomState(seed).randint(0, cfg.vocab_size,
                                              size=(3, 12))
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.asarray(ids))["params"]
    return cfg, model, params, ids


def test_decode_step_flag_parity_ragged():
    """decode_step with flash on vs off at a 128-slot cache plane and
    ragged per-row frontiers: fp32 logits match and greedy argmax is
    IDENTICAL (the token-identity acceptance criterion, one step)."""
    cfg, model, params, ids = tiny_model()
    on = as_gencfg(cfg, use_flash_decode=True)
    off = as_gencfg(cfg, use_flash_decode=False)
    assert on.use_flash_decode and not off.use_flash_decode

    tok = jnp.asarray(ids[:, 0])
    outs = []
    for gcfg in (on, off):
        cache = init_cache(gcfg, 3, 128)
        # Ragged frontiers incl. 0 and max_len-1: both paths read the
        # same (zero) cache planes, so parity is deterministic.
        cache["pos"] = jnp.asarray([0, 7, 120], jnp.int32)
        logits, cache2 = step(params, gcfg, tok, cache)
        assert (np.asarray(cache2["pos"]) == [1, 8, 121]).all()
        outs.append(np.asarray(logits))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(outs[0].argmax(-1), outs[1].argmax(-1))


def test_prefill_forward_flag_parity():
    """Prefill (S=12, last_only) through _forward: flash on vs off."""
    cfg, model, params, ids = tiny_model()
    outs = []
    for flag in (True, False):
        gcfg = as_gencfg(cfg, use_flash_decode=flag)
        cache = init_cache(gcfg, 3, 128)
        logits, _ = forward(params, gcfg, jnp.asarray(ids), cache,
                            last_only=True)
        outs.append(np.asarray(logits))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-4)


def test_decode_step_multiblock_env(monkeypatch):
    """Force a multi-block split (block_k=64 over a 128 plane) through
    the real decode-step program via the env override."""
    cfg, model, params, ids = tiny_model()
    tok = jnp.asarray(ids[:, 0])
    outs = []
    for env in ("64", None):
        if env is None:
            monkeypatch.delenv("DS_TPU_FLASH_DECODE_BLOCK", raising=False)
        else:
            monkeypatch.setenv("DS_TPU_FLASH_DECODE_BLOCK", env)
        cache = init_cache(as_gencfg(cfg, use_flash_decode=True), 3, 128)
        cache["pos"] = jnp.asarray([0, 65, 127], jnp.int32)
        # a trace an override: the block is read where the step is traced
        logits, _ = jax.jit(decode_step, static_argnums=1)(
            params, as_gencfg(cfg, use_flash_decode=True), tok, cache)
        outs.append(np.asarray(logits))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-4)


def test_generate_flag_parity_tokens_identical():
    """Full generate() (prefill + scan) flag on vs off: greedy tokens
    identical. Flag-on pads the cache plane to BLOCK_MIN — padding must
    be inert."""
    cfg, model, params, ids = tiny_model()
    cfg_on = GPT2Config.tiny(dropout=0.0, dtype=jnp.float32,
                             use_flash_attention=False,
                             use_flash_decode=True)
    out_off = np.asarray(generate(model, params, ids, 6, temperature=0.0))
    model_on = GPT2LMHeadModel(cfg_on)
    out_on = np.asarray(generate(model_on, params, ids, 6, temperature=0.0))
    np.testing.assert_array_equal(out_on, out_off)


# ------------------------------------------------- int8 KV (q8 family)


def _q8_operands(rng, b, h, s, t, d, dtype=jnp.float32):
    q = jnp.asarray(rng.randn(b, h, s, d), dtype)
    k = jnp.asarray(rng.randn(b, h, t, d), dtype)
    v = jnp.asarray(rng.randn(b, h, t, d), dtype)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    return q, k, v, kq, ks, vq, vs


def test_quantize_roundtrip_error_bound():
    """The pinned dequant bound: |dequant(quantize(x)) - x| <= scale/2
    per element, scale = amax/127 per (batch, head, position) row —
    the contract engine int8 serving leans on."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 4, 32, 16) * 3.0, jnp.float32)
    codes, scale = quantize_kv(x)
    assert codes.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert scale.shape == x.shape[:-1]
    err = np.abs(np.asarray(dequantize_kv(codes, scale)) - np.asarray(x))
    bound = np.asarray(scale)[..., None] / 2.0 + 1e-6
    assert (err <= bound).all(), \
        "max dequant error {} exceeds scale/2".format(err.max())


@pytest.mark.parametrize("block_k", [64, 128])
def test_q8_kernel_matches_q8_reference_ragged(block_k):
    """The q8 Pallas kernel (in-block dequant) against the dequantize-
    then-dense reference over ragged frontiers: same codes, same scales,
    same math — tight parity, not a quantization-noise tolerance."""
    rng = np.random.RandomState(4)
    q, _, _, kq, ks, vq, vs = _q8_operands(rng, 4, 2, 1, 256, 32)
    pos = jnp.asarray([0, 3, 128, 255], jnp.int32)
    out = flash_decode_attention_q8(q, kq, vq, ks, vs, pos,
                                    block_k=block_k)
    ref = decode_attention_q8_reference(q, kq, vq, ks, vs, pos)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_q8_kernel_under_jit():
    rng = np.random.RandomState(5)
    q, _, _, kq, ks, vq, vs = _q8_operands(rng, 3, 2, 1, 128, 16)
    pos = jnp.asarray([5, 63, 127], jnp.int32)
    f = jax.jit(lambda *a: flash_decode_attention_q8(*a, block_k=64))
    np.testing.assert_allclose(
        f(q, kq, vq, ks, vs, pos),
        decode_attention_q8_reference(q, kq, vq, ks, vs, pos),
        rtol=1e-5, atol=1e-5)


def test_q8_append_rows_multi_query():
    """The speculative-verify / chunked-append shape (S>1): the q8
    kernel's intra-row causal stagger must match the reference's."""
    rng = np.random.RandomState(6)
    q, _, _, kq, ks, vq, vs = _q8_operands(rng, 2, 2, 5, 128, 16)
    pos = jnp.asarray([17, 99], jnp.int32)
    out = flash_decode_attention_q8(q, kq, vq, ks, vs, pos, block_k=64)
    ref = decode_attention_q8_reference(q, kq, vq, ks, vs, pos)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_q8_close_to_fp_within_quantization_noise():
    """q8 against the FP reference on the original planes: the output
    error is bounded by quantization noise (loose tolerance — int8 is
    lossy by design; this pins 'close', the engine tests pin 'does not
    collapse')."""
    rng = np.random.RandomState(7)
    q, k, v, kq, ks, vq, vs = _q8_operands(rng, 2, 2, 1, 128, 32)
    pos = jnp.asarray([64, 127], jnp.int32)
    out = flash_decode_attention_q8(q, kq, vq, ks, vs, pos, block_k=64)
    ref = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(out, ref, rtol=0.0, atol=0.05)


def test_q8_unsupported_length_falls_back_to_reference():
    """T below the kernel minimum: dispatch must land on the q8 dense
    fallback, not crash — and the numbers are the reference's exactly."""
    rng = np.random.RandomState(8)
    t = BLOCK_MIN // 2
    q, _, _, kq, ks, vq, vs = _q8_operands(rng, 2, 2, 1, t, 16)
    pos = jnp.asarray([0, t - 1], jnp.int32)
    out = flash_decode_attention_q8(q, kq, vq, ks, vs, pos)
    ref = decode_attention_q8_reference(q, kq, vq, ks, vs, pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ----------------------------------------- paged arena: in-place append
#
# The write half and the read half of "a per-layer value of the paged
# arena is never formed": ``kv_append`` against the XLA scatter it
# replaced, over the WHOLE arena, and the layer-indexed paged kernels
# against the references on ``arena[layer]``. Page 128 is a kernel block,
# so these run the Pallas bodies (interpret mode here).

_PAGE = 128


def _stored(x, g, scales=False):
    """Rows ``[..., H, T, D]`` as a paged pool stores them, ``g`` heads a
    lane tile: ``[..., ceil(H / g), T, g * D]``, head ``g * p + a`` in lanes
    ``a * D ..`` of packed head ``p`` (written out here without the
    program's ``pack_heads``). ``scales`` ``[..., H, T]`` stay a head of
    the model each and only gain the zero head of an ``H`` that ``g`` does
    not divide."""
    x = np.asarray(x)
    h_axis = x.ndim - (2 if scales else 3)
    hp = -(-x.shape[h_axis] // g)
    widths = [(0, 0)] * x.ndim
    widths[h_axis] = (0, hp * g - x.shape[h_axis])
    x = np.pad(x, widths)
    if scales:
        return jnp.asarray(x)
    return jnp.asarray(np.concatenate(
        [np.take(x, np.arange(a, hp * g, g), axis=h_axis) for a in range(g)],
        axis=-1))


def _scatter_reference(arena, new, tbl, pos, layer):
    """What ``models/generation.py`` ``_forward`` did before the kernel:
    ``arena.at[layer, pg, :, off, :].set(new)`` through the block table
    (``arena`` and ``new`` in the same form: both stored, or both not).
    A position past the row's plane has no page and is dropped."""
    s = new.shape[2]
    w_pos = pos[:, None] + jnp.arange(s)[None]
    w_pg = tbl[jnp.arange(new.shape[0])[:, None],
               jnp.minimum(w_pos // _PAGE, tbl.shape[1] - 1)]
    w_pg = jnp.where(w_pos < tbl.shape[1] * _PAGE, w_pg, arena.shape[1])
    w_off = w_pos % _PAGE
    if arena.ndim == 5:
        return arena.at[layer, w_pg, :, w_off, :].set(
            new.transpose(0, 2, 1, 3), mode="drop")
    return arena.at[layer, w_pg, :, w_off].set(new.transpose(0, 2, 1),
                                               mode="drop")


def _append_case(s, pos, int8, layer, frozen=(), n_layer=3, h=2, d=8,
                 n_lp=3, seed=0, stored=False, planes=2, unit=None):
    """``stored``: the arenas in the shape ``init_pool`` gives a model of
    ``h`` heads of ``d`` (``lane_pack`` heads a lane tile, a zero head where
    that does not divide ``h``); the new values stay ``[B, H, S, D]``.
    ``planes`` 1: a latent cache's one arena. ``unit``: the rows a unit of
    the one-row walk holds, where the rule would take them all (the VMEM
    budget shrunk until ``append_unit_rows`` says so)."""
    rng = np.random.RandomState(seed)
    g = da.lane_pack(d, h) if stored else 1
    b = len(pos)
    n_pages = b * n_lp + 1
    tbl = (1 + rng.permutation(n_pages - 1)).reshape(b, n_lp)
    for row in frozen:
        tbl[row] = 0                       # a freed row: all on the trash page
    tbl = jnp.asarray(tbl, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)

    def rows(shape):
        if int8:
            return jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        return jnp.asarray(rng.randn(*shape), jnp.bfloat16)

    arenas = [rows((n_layer, n_pages, h, _PAGE, d)) for _ in range(planes)]
    new = [rows((b, h, s, d)) for _ in range(planes)]
    if int8:
        arenas += [jnp.asarray(rng.rand(n_layer, n_pages, h, _PAGE),
                               jnp.float32) for _ in range(2)]
        new += [jnp.asarray(rng.rand(b, h, s), jnp.float32)
                for _ in range(2)]
    arenas = [_stored(a, g, scales=a.ndim == 4) for a in arenas]
    assert arenas[0].shape[2:] == (-(-h // g), _PAGE, g * d)
    budget = da._PAGED_VMEM_BUDGET
    if unit is not None:
        assert s == 1 and da._append_walks(arenas)
        while da.append_unit_rows(arenas, b) > unit:
            da._PAGED_VMEM_BUDGET = da._PAGED_VMEM_BUDGET * 3 // 4
        assert da.append_unit_rows(arenas, b) == unit
    try:
        got = jax.jit(lambda a, n: kv_append(tuple(a), tuple(n), tbl, pos,
                                             layer))(arenas, new)
    finally:
        da._PAGED_VMEM_BUDGET = budget
    assert len(got) == len(arenas)
    for arena, x, out in zip(arenas, new, got):
        want = np.array(_scatter_reference(
            arena, _stored(x, g, scales=x.ndim == 3), tbl, pos, layer)
            .astype(jnp.float32))
        out = np.array(out.astype(jnp.float32))
        assert out.dtype == want.dtype and out.shape == want.shape
        # Bit for bit over the whole arena: every layer, every page. Only
        # the trash page's written layer is unchecked when frozen rows
        # share it (their order of arrival there is nobody's business).
        if frozen:
            out[layer, 0], want[layer, 0] = 0, 0
        np.testing.assert_array_equal(out, want)


APPEND_CASES = {
    # name: (S, frontiers, int8, layer, frozen rows)
    "decode_one_row_first_layer": (1, [0, 127, 128, 300], False, 0, ()),
    "decode_one_row_last_layer": (1, [31, 32, 255, 383], False, 2, ()),
    "verify_5_rows_straddling_a_page": (5, [0, 125, 126, 251], False, 1, ()),
    "lane_128_rows_page_aligned": (128, [0, 128, 256], False, 1, ()),
    "lane_128_rows_from_mid_page": (128, [7, 100, 255], False, 2, ()),
    "lane_40_rows_padded_to_a_tile": (40, [100, 3, 250], False, 0, ()),
    "lane_200_rows_in_two_calls": (200, [0, 60, 184], False, 1, ()),
    "int8_one_row": (1, [0, 127, 128, 300], True, 2, ()),
    "int8_verify_5_rows_straddling": (5, [0, 125, 126, 251], True, 0, ()),
    "int8_lane_128_rows_from_mid_page": (128, [7, 100, 255], True, 1, ()),
    "frozen_rows_share_the_trash_page": (
        1, [5, 77, 200, 0, 129, 9], False, 1, (0, 3, 5)),
    "frozen_rows_share_the_trash_page_verify": (
        5, [5, 126, 200, 0, 129, 9], True, 2, (0, 3, 5)),
    # The arena as the pool STORES it, (heads, head dim) after the frozen
    # rows: g = 2 heads of 64 a lane tile, 4 of 32, 1 of 128 (nothing
    # packed), and a head count g does not divide (gpt2-xl's 25 of 64: a
    # zero head). Frontiers straddle a page; a freed row.
    "stored_g2_one_row": (1, [0, 127, 128, 300], False, 1, (), 4, 64),
    "stored_g2_verify_5_rows_straddling": (
        5, [0, 125, 126, 251], False, 2, (), 4, 64),
    "stored_g2_lane_128_rows_from_mid_page": (
        128, [7, 100, 255], False, 0, (), 4, 64),
    "stored_g2_int8_one_row": (1, [0, 127, 128, 300], True, 2, (), 4, 64),
    "stored_g2_int8_verify_5_rows_straddling": (
        5, [0, 125, 126, 251], True, 0, (), 4, 64),
    "stored_g2_int8_lane_128_rows_from_mid_page": (
        128, [7, 100, 255], True, 1, (), 4, 64),
    "stored_g2_frozen_rows": (
        1, [5, 77, 200, 0, 129, 9], False, 1, (0, 3, 5), 4, 64),
    "stored_g4_one_row": (1, [0, 127, 128, 300], False, 0, (), 8, 32),
    "stored_g4_int8_verify_5_rows_straddling": (
        5, [0, 125, 126, 251], True, 1, (), 8, 32),
    "stored_g1_one_row_d128": (1, [31, 32, 255, 383], False, 2, (), 2, 128),
    "stored_25_heads_one_row": (1, [0, 127, 128], False, 1, (), 25, 64),
    "stored_25_heads_int8_verify_5_rows": (
        5, [0, 125, 251], True, 0, (), 25, 64),
    "stored_5_heads_of_32_lane_40_rows": (
        40, [100, 3, 250], False, 2, (), 5, 32),
    # The decode scan's one row a slot: ONE launch walks its rows (arenas
    # whose minor dim is whole lane tiles; the cases above with 8 lanes go
    # a page a row by block spec). After (heads, head dim): the rows a unit
    # holds where not all, and the planes. Frontiers at offsets 0, 7, 8,
    # 15, 16, 31 and 127 of a page: both sides of an 8-row tile's edge and
    # of the 16- and 32-row tiles the form could have taken.
    "walk_tile_edges_d64_g2": (
        1, [0, 135, 264, 15, 144, 287, 127], False, 1, (), 4, 64),
    "walk_tile_edges_d128": (
        1, [128, 7, 8, 271, 16, 31, 383], False, 2, (), 2, 128),
    "walk_tile_edges_int8_d64_g2": (
        1, [0, 135, 264, 15, 144, 287, 127], True, 0, (), 4, 64),
    "walk_tile_edges_int8_d128": (
        1, [128, 7, 8, 271, 16, 31, 383], True, 1, (), 2, 128),
    "walk_5_rows_in_units_of_4": (
        1, [0, 15, 16, 31, 127], False, 1, (), 4, 64, 4),
    "walk_5_rows_in_units_of_2_int8": (
        1, [300, 15, 16, 31, 255], True, 2, (), 2, 128, 2),
    "walk_frozen_rows_beside_live_in_one_unit": (
        1, [5, 77, 200, 0, 129, 9], False, 1, (0, 3, 5), 4, 64, 4),
    "walk_frozen_rows_beside_live_all_rows_a_unit_int8": (
        1, [5, 77, 200, 0, 129, 9], True, 0, (1, 2), 4, 64),
    "walk_no_live_row": (1, [5, 77, 200, 0], False, 1, (0, 1, 2, 3), 4, 64),
    "walk_no_live_row_in_units_of_2": (
        1, [5, 77, 200, 0, 31], False, 2, (0, 1, 2, 3, 4), 2, 128, 2),
    "walk_a_frontier_past_the_plane_writes_nothing": (
        1, [3 * 128, 77, 3 * 128 + 5], False, 1, (), 4, 64),
    # Granite's grouped-query rows: 8 stored heads of 128 under 32 query
    # heads (the append sees the stored heads only).
    "walk_grouped_query_8_stored_heads_d128": (
        1, [0, 127, 128, 300, 8], False, 0, (), 8, 128),
    "walk_grouped_query_in_units_of_2": (
        1, [0, 127, 128, 300, 8], False, 2, (), 8, 128, 2),
    # DeepSeek's latent cache: ONE arena of one stored head, 640 lanes.
    "walk_latent_one_arena_w640": (
        1, [0, 7, 8, 127, 128, 300], False, 1, (2,), 1, 640, None, 1),
    "walk_latent_one_arena_in_units_of_4": (
        1, [0, 7, 8, 127, 128, 300], False, 0, (), 1, 640, 4, 1),
}


@pytest.mark.parametrize("name", sorted(APPEND_CASES))
def test_kv_append_is_the_scatter_bit_for_bit(name):
    """The in-place append equals ``arena.at[layer, pg, :, off, :].set``
    over the whole arena — nothing else in it may change: other layers,
    other pages, the rows of a frontier page below and above the write.
    On a stored (packed) arena the scatter is of the new values regrouped
    as the arena holds heads; no lane of a neighbouring head may change."""
    s, pos, int8, layer, frozen, *shape = APPEND_CASES[name]
    kw = {key: value for key, value in zip(("h", "d", "unit", "planes"),
                                           shape) if value is not None}
    _append_case(s, pos, int8, layer, frozen=frozen, stored=bool(shape), **kw)


def _shapes(shape, n=2, dtype=jnp.bfloat16):
    return [jax.ShapeDtypeStruct(shape, dtype)] * n


@pytest.mark.parametrize("name, arenas, slots, walks, unit", [
    # the four serving cells' pools: every row of the scan in ONE unit
    ("gpt2", _shapes((24, 145, 8, 128, 128)), 16, True, 16),
    ("olmoe", _shapes((8, 545, 16, 128, 128)), 32, True, 32),
    ("granite", _shapes((1, 1217, 8, 128, 128)), 64, True, 64),
    ("dsv3_latent", _shapes((6, 3073, 1, 128, 640), 1), 128, True, 128),
    # int8 codes beside a scale a head of the model: 8 rows all the same
    ("gpt2_int8", _shapes((24, 145, 8, 128, 128), 2, jnp.int8)
     + _shapes((24, 145, 16, 128), 2, jnp.float32), 16, True, 16),
    # past the budget the rows go in units: 96 KB a row of OLMoE's shape
    # fit 128 times in 12 MiB; 1.5 MB a row of a wide float32 pool 8 times
    ("olmoe_512_slots", _shapes((8, 8193, 16, 128, 128)), 512, True, 128),
    ("wide_float32_pool", _shapes((2, 65, 64, 128, 256), 2, jnp.float32),
     12, True, 8),
    # a minor dim that is not whole lane tiles: no slice of it in HBM
    ("unpacked_d64", _shapes((24, 145, 16, 128, 64)), 16, False, 0),
    ("head_dim_96", _shapes((4, 33, 12, 128, 96)), 8, False, 0),
], ids=lambda x: x if isinstance(x, str) else None)
def test_rows_a_unit_of_the_one_row_append_come_from_the_shapes(
        name, arenas, slots, walks, unit):
    """R, the rows one launch (or one grid step of it) of the one-row
    ``kv_append`` walks, and the tile a row brings: 8 rows of a page, one
    tile of the arena in HBM whatever the dtype packs. From shapes and
    dtypes alone; what the engine reports as ``kv_append_unit_rows``."""
    assert da._APPEND_ROWS == 8
    assert da._append_walks(arenas) == walks
    assert da.append_unit_rows(arenas, slots) == (unit if walks else 0)
    # fewer rows than fit are all one unit, whatever their count
    assert da.append_unit_rows(arenas, 5) == (5 if walks else 0)


@pytest.mark.parametrize("name, first_pages, pos, rows", [
    ("every_row_live", [3, 1, 2, 7], [0, 5, 383, 128], [0, 1, 2, 3]),
    ("freed_rows_between", [0, 4, 0, 0, 2, 0], [9, 9, 9, 9, 9, 9], [1, 4]),
    ("a_frontier_past_the_plane", [3, 1, 2], [384, 383, 500], [1]),
    ("no_live_row", [0, 0, 0], [1, 2, 3], []),
])
def test_the_one_row_appends_list_of_live_rows(name, first_pages, pos, rows):
    """``_live_rows``: the rows whose table does not start on the trash
    page and whose frontier lies inside the plane (3 pages of 128 here), in
    order, and how many there are up to each row: what the walk's loops run
    over, so a freed row costs it nothing."""
    tbl = np.zeros((len(pos), 3), np.int32)
    tbl[:, 0] = first_pages
    got, ends = (np.asarray(x) for x in da._live_rows(
        jnp.asarray(tbl), jnp.asarray(pos, jnp.int32), _PAGE))
    assert ends.dtype == got.dtype == np.int32
    assert list(got[:ends[-1]]) == rows
    assert list(ends) == [sum(r <= b for r in rows)
                          for b in range(len(pos))]
    assert got.max(initial=0) < len(pos)


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("s", [1, 5])
def test_layer_indexed_paged_decode_matches_reference(s, int8):
    """The paged kernels given the arena WHOLE and a non-zero ``layer``
    equal the paged reference on ``arena[layer]``, and equal themselves
    on the sliced layer bit for bit (same body, same DMAs)."""
    rng = np.random.RandomState(3)
    n_layer, b, h, d, n_lp, layer = 3, 3, 2, 16, 3, 2
    n_pages = b * n_lp + 1
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    tbl = jnp.asarray((1 + rng.permutation(n_pages - 1)).reshape(b, n_lp),
                      jnp.int32)
    pos = jnp.asarray([0, 130, 3 * _PAGE - s], jnp.int32)
    kf = jnp.asarray(rng.randn(n_layer, n_pages, h, _PAGE, d), jnp.float32)
    vf = jnp.asarray(rng.randn(n_layer, n_pages, h, _PAGE, d), jnp.float32)
    if int8:
        (k, ks), (v, vs) = da.quantize_kv(kf), da.quantize_kv(vf)
        got = paged_q8(q, k, v, ks, vs, tbl, pos, layer=layer)
        sliced = paged_q8(q, k[layer], v[layer], ks[layer], vs[layer], tbl,
                          pos)
        want = paged_q8_reference(q, k[layer], v[layer], ks[layer],
                                  vs[layer], tbl, pos)
    else:
        got = paged(q, kf, vf, tbl, pos, layer=layer)
        sliced = paged(q, kf[layer], vf[layer], tbl, pos)
        want = paged_reference(q, kf[layer], vf[layer], tbl, pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(sliced))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [1, 5, 128])
def test_paged_decode_and_kv_append_at_both_head_dims_in_bf16(s, d):
    """The serving cells' two head dims, in the type they serve in: GPT-2's
    64 (half a lane tile) and OLMoE's 128 (a whole one). ``kv_append`` then
    the layer-indexed paged kernel, one decode row, a five-row verify and a
    128-row lane, against the scatter and the paged reference."""
    rng = np.random.RandomState(d + s)
    n_layer, b, h, n_lp, layer = 2, 2, 2, 3, 1
    n_pages = b * n_lp + 1
    tbl = jnp.asarray((1 + rng.permutation(n_pages - 1)).reshape(b, n_lp),
                      jnp.int32)
    pos = jnp.asarray([3, 2 * _PAGE - 1 if s == 1 else _PAGE + 5], jnp.int32)
    ka, va = (jnp.asarray(rng.randn(n_layer, n_pages, h, _PAGE, d),
                          jnp.bfloat16) for _ in range(2))
    q, k, v = (jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
               for _ in range(3))
    ka2, va2 = jax.jit(kv_append, static_argnums=4)(
        (ka, va), (k, v), tbl, pos, layer)
    for arena, new, got in ((ka, k, ka2), (va, v, va2)):
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(_scatter_reference(arena, new, tbl, pos, layer)
                       .astype(jnp.float32)))
    got = paged(q, ka2, va2, tbl, pos, layer=layer)
    want = paged_reference(
        q.astype(jnp.float32), ka2[layer].astype(jnp.float32),
        va2[layer].astype(jnp.float32), tbl, pos)
    assert got.dtype == jnp.bfloat16
    # bf16 keeps 8 bits: outputs of order 1, probabilities rounded to bf16
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want), rtol=0, atol=3e-2)


# -------------------------- paged arena: a page of all heads, live pages only
#
# The paged kernel's unit of work is a page of ALL heads and its grid is
# the list of live (row, page) pairs (``_paged_units``). The cases below
# run that body (interpret mode here) against the paged references; the
# last tests hold the grid's shape, so the rows x heads x pages grid cannot
# come back unseen on a CPU.


def _paged_operands(d, s, pos, int8, dtype, shared=0, frozen=(), h=4,
                    n_lp=3, n_layer=2, seed=0, stored=False):
    """q, arenas (k, v[, k_scale, v_scale]) WHOLE, table, frontiers.
    ``shared``: the first ``shared`` pages of every live row are row 0's
    (a prefix installed by reference). ``frozen`` rows: table all trash.
    ``stored``: the arenas as ``init_pool`` shapes them for ``h`` heads of
    ``d`` (``_stored``); without it one head a minor dim (g = 1)."""
    rng = np.random.RandomState(seed)
    b = len(pos)
    n_pages = b * n_lp + 1
    tbl = (1 + rng.permutation(n_pages - 1)).reshape(b, n_lp)
    tbl[:, :shared] = tbl[0, :shared]
    tbl[list(frozen)] = TRASH
    q = jnp.asarray(rng.randn(b, h, s, d), dtype)
    arenas = tuple(jnp.asarray(rng.randn(n_layer, n_pages, h, _PAGE, d),
                               jnp.float32) for _ in range(2))
    if int8:
        (k, ks), (v, vs) = (da.quantize_kv(a) for a in arenas)
        arenas = (k, v, ks, vs)
    else:
        arenas = tuple(a.astype(dtype) for a in arenas)
    if stored:
        g = da.lane_pack(d, h)
        arenas = tuple(_stored(a, g, scales=a.ndim == 4) for a in arenas)
        assert arenas[0].shape[2:] == (-(-h // g), _PAGE, g * d)
    return (q, arenas, jnp.asarray(tbl, jnp.int32),
            jnp.asarray(pos, jnp.int32))


def _paged_kernel_and_reference(q, arenas, tbl, pos, layer):
    if len(arenas) == 4:
        got = paged_q8(q, *arenas, tbl, pos, layer=layer)
        want = paged_q8_reference(
            q.astype(jnp.float32), *(a[layer] for a in arenas), tbl, pos)
    else:
        got = paged(q, *arenas, tbl, pos, layer=layer)
        want = paged_reference(
            q.astype(jnp.float32),
            *(a[layer].astype(jnp.float32) for a in arenas), tbl, pos)
    assert got.dtype == q.dtype and got.shape == q.shape
    return np.asarray(got.astype(jnp.float32)), np.asarray(want)


# Frontiers, for a plane of 3 pages of 128: page 0 only; the LAST row of a
# page (s rows ending at 127 and at 255); the first row of the next; deep.
def _frontiers(s):
    return [0, 128 - s, 128, 256 - s, 3 * _PAGE - s]


PAGED_CASES = {
    # name: (d, S, int8, dtype, shared pages, frozen rows)
    "decode_1_row_d64_bf16": (64, 1, False, jnp.bfloat16, 0, ()),
    "decode_1_row_d128_bf16": (128, 1, False, jnp.bfloat16, 0, ()),
    "verify_5_rows_d64_bf16": (64, 5, False, jnp.bfloat16, 0, ()),
    "verify_5_rows_d128_bf16": (128, 5, False, jnp.bfloat16, 0, ()),
    "lane_128_rows_d64_bf16": (64, 128, False, jnp.bfloat16, 0, ()),
    "lane_128_rows_d128_bf16": (128, 128, False, jnp.bfloat16, 0, ()),
    "decode_1_row_d64_int8": (64, 1, True, jnp.bfloat16, 0, ()),
    "decode_1_row_d128_int8": (128, 1, True, jnp.bfloat16, 0, ()),
    "verify_5_rows_d128_int8": (128, 5, True, jnp.bfloat16, 0, ()),
    "lane_128_rows_d64_int8": (64, 128, True, jnp.bfloat16, 0, ()),
    "decode_1_row_d64_float32": (64, 1, False, jnp.float32, 0, ()),
    "verify_5_rows_d128_float32_int8": (128, 5, True, jnp.float32, 0, ()),
    "shared_prefix_page_d64_bf16": (64, 1, False, jnp.bfloat16, 1, ()),
    "shared_prefix_pages_d128_int8": (128, 5, True, jnp.bfloat16, 2, ()),
    "frozen_rows_between_live_d64_bf16": (
        64, 1, False, jnp.bfloat16, 0, (1, 3)),
    "frozen_rows_first_and_last_d128_int8": (
        128, 5, True, jnp.bfloat16, 0, (0, 4)),
    "every_row_frozen_d64_bf16": (
        64, 1, False, jnp.bfloat16, 0, (0, 1, 2, 3, 4)),
    # The arena as the pool STORES it (the number of heads after the frozen
    # rows): the query goes in block-diagonal, g heads' rows in one tile.
    "stored_g2_decode_1_row_bf16": (64, 1, False, jnp.bfloat16, 0, (), 4),
    "stored_g2_verify_5_rows_bf16": (64, 5, False, jnp.bfloat16, 0, (), 4),
    "stored_g2_lane_128_rows_bf16": (64, 128, False, jnp.bfloat16, 0, (), 4),
    "stored_g2_decode_1_row_int8": (64, 1, True, jnp.bfloat16, 0, (), 4),
    "stored_g2_verify_5_rows_int8": (64, 5, True, jnp.bfloat16, 0, (), 4),
    "stored_g2_lane_128_rows_int8": (64, 128, True, jnp.bfloat16, 0, (), 4),
    "stored_g2_decode_1_row_float32": (64, 1, False, jnp.float32, 0, (), 4),
    "stored_g2_verify_5_rows_float32_int8": (
        64, 5, True, jnp.float32, 0, (), 4),
    "stored_g2_shared_prefix_page_frozen_rows_bf16": (
        64, 1, False, jnp.bfloat16, 1, (1, 3), 4),
    "stored_g2_frozen_rows_first_and_last_int8": (
        64, 5, True, jnp.bfloat16, 0, (0, 4), 4),
    "stored_g4_decode_1_row_bf16": (32, 1, False, jnp.bfloat16, 0, (), 8),
    "stored_g4_verify_5_rows_int8": (32, 5, True, jnp.bfloat16, 0, (), 8),
    "stored_g4_lane_128_rows_float32": (32, 128, False, jnp.float32, 0, (), 8),
    "stored_g1_decode_1_row_d128_bf16": (
        128, 1, False, jnp.bfloat16, 0, (), 4),
    "stored_25_heads_decode_1_row_bf16": (
        64, 1, False, jnp.bfloat16, 0, (2,), 25),
    "stored_25_heads_verify_5_rows_int8": (
        64, 5, True, jnp.bfloat16, 0, (), 25),
    "stored_5_heads_of_32_decode_1_row_float32": (
        32, 1, False, jnp.float32, 0, (), 5),
}


@pytest.mark.parametrize("name", sorted(PAGED_CASES))
def test_paged_body_matches_the_paged_reference(name):
    """A page of all heads a unit, live pages only: ragged frontiers (page
    0 only, a page's last row, the next page's first, the plane's end),
    shared prefix pages, frozen rows, both head dims, the three query
    shapes, bf16 / float32 / int8. A frozen row's output is zeros. The
    ``stored_*`` cases hand the kernels the arena as the pool keeps it
    (2 heads of 64 or 4 of 32 a lane tile, 25 heads with their zero head)
    and the reference the same arena, ungrouped by its own gather."""
    d, s, int8, dtype, shared, frozen, *h = PAGED_CASES[name]
    kw = dict(h=h[0], stored=True) if h else {}
    q, arenas, tbl, pos = _paged_operands(d, s, _frontiers(s), int8, dtype,
                                          shared=shared, frozen=frozen, **kw)
    got, want = _paged_kernel_and_reference(q, arenas, tbl, pos, layer=1)
    live = [r for r in range(len(pos)) if r not in frozen]
    # bf16 keeps 8 bits (outputs of order 1, probabilities rounded to it);
    # float32 differs from the reference by the order of its sums only.
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    assert not got[list(frozen)].any()


@pytest.mark.parametrize("s, dtype", [(4, jnp.float32), (4, jnp.bfloat16),
                                      (128, jnp.bfloat16)])
def test_paged_body_under_block_visibility(s, dtype):
    """``visible_upto`` in the paged body's straddle mask (a model that
    generates by diffusion over blocks of 4): 8 query heads over 2 stored
    heads of 128, so ``rep x S`` = 16 rows of a pass (512 of a lane's slice)
    share one read of a row's pages, at frontiers that start a block (a
    page's last block, the next page's first, the plane's end). The body
    against the gather reference under the same rule, which is NOT the
    causal one: a query sees the positions of its block after it."""
    rng = np.random.RandomState(3)
    pos = [0, 128 - s, 128, 256 - s, 3 * _PAGE - s]
    q, arenas, tbl, pos = _paged_operands(128, s, pos, False, dtype, h=2)
    q = jnp.asarray(rng.randn(len(pos), 8, s, 128), dtype)
    got = paged(q, *arenas, tbl, pos, layer=1, block=4)
    want, causal = (paged_reference(
        q.astype(jnp.float32), *(a[1].astype(jnp.float32) for a in arenas),
        tbl, pos, block=b) for b in (4, 1))
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want), rtol=tol, atol=tol)
    assert np.abs(np.asarray(want) - np.asarray(causal)).max() > 0.1
    # the last position of a block sees what the causal rule shows it
    np.testing.assert_allclose(np.asarray(want)[:, :, 3::4],
                               np.asarray(causal)[:, :, 3::4], atol=1e-6)


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("s", [1, 5])
def test_frozen_rows_leave_live_rows_bit_for_bit(s, int8):
    """Frozen rows (table all trash, ``pos`` pinned deep in the plane)
    between live rows: the live rows are bit for bit what the same call
    gives without the frozen ones."""
    frozen = (0, 2, 3, 6)
    pos = [300, 5, 383 - s, 200, 127, 128, 2 * _PAGE - s]
    q, arenas, tbl, pos = _paged_operands(64, s, pos, int8, jnp.bfloat16,
                                          frozen=frozen, seed=s)
    live = np.asarray([r for r in range(len(pos)) if r not in frozen])
    run = paged_q8 if int8 else paged
    with_frozen = run(q, *arenas, tbl, pos, layer=0)
    without = run(q[live], *arenas, tbl[live], pos[live], layer=0)
    np.testing.assert_array_equal(
        np.asarray(with_frozen.astype(jnp.float32))[live],
        np.asarray(without.astype(jnp.float32)))
    assert not np.asarray(with_frozen.astype(jnp.float32))[
        list(frozen)].any()


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("int8", [False, True])
def test_paged_grid_is_the_list_of_live_pairs_and_has_no_head_axis(int8):
    """The launched call's grid is (head groups = 1, units): no axis over
    heads, no axis over a row's ``n_lp`` pages, and the unit count is not a
    static extent but the work list's length. The list itself holds each
    live row's pages ``0 .. frontier page`` in order and nothing for a
    frozen row: rows x pages brought in are the live pairs."""
    frozen = (1, 4)
    pos = [0, 700, 127, 128, 300, 3 * _PAGE - 1]
    q, arenas, tbl, pos = _paged_operands(64, 1, pos, int8, jnp.bfloat16,
                                          frozen=frozen, h=16)
    run = (da.flash_decode_attention_paged_q8 if int8
           else da.flash_decode_attention_paged)
    jaxpr = jax.make_jaxpr(
        lambda *a: run(*a, layer=1))(q, *arenas, tbl, pos)
    (call,) = _pallas_calls(jaxpr.jaxpr)
    grid = call.params["grid_mapping"].grid
    assert len(grid) == 2 and grid[0] == 1, grid
    assert not isinstance(grid[1], int), grid          # a dynamic bound
    # Blocks: q and the output hold all 16 heads of a row, an arena's block
    # all 16 heads of one page.
    shapes = [tuple(bm.block_shape) for bm in
              call.params["grid_mapping"].block_mappings]
    assert all(16 in [getattr(x, "block_size", None) for x in shape]
               for shape in shapes), shapes

    rows, js, pages, live, n = (np.asarray(x) for x in da._paged_units(
        tbl, pos, 1, _PAGE))
    want_live = [1, 0, 1, 2, 0, 3]
    assert live.tolist() == want_live
    pairs = [(r, j) for r, k in enumerate(want_live) for j in range(k)]
    assert n == len(pairs) == 7              # the live pairs, nothing else
    assert list(zip(rows[:n].tolist(), js[:n].tolist())) == pairs
    tbl = np.asarray(tbl)
    assert pages[:n].tolist() == [tbl[r, j] for r, j in pairs]
    assert TRASH not in pages[:n].tolist()
    # Past the end the list repeats its last unit (no new block).
    assert set(zip(rows[n:].tolist(), js[n:].tolist())) == {pairs[-1]}
    assert len(rows) == tbl.size
    # No live row at all: one unit, a row without a live page, so the grid
    # is never empty and the kernel attends nothing.
    rows, js, pages, live, n = (np.asarray(x) for x in da._paged_units(
        jnp.zeros_like(tbl), pos, 1, _PAGE))
    assert n == 1 and not live.any() and pages[0] == TRASH
    assert 0 <= rows[0] < len(pos) and js[0] == 0


def test_paged_heads_per_unit_from_shapes_alone():
    """All heads a unit at every cell's shape; fewer only where the blocks
    would pass the VMEM the call plans into (a divisor of H, and a whole
    sublane tile of scales for an int8 pool)."""
    pick = da._paged_heads_per_unit
    assert pick(16, 16, 128, 64, jnp.bfloat16, jnp.bfloat16) == 16
    assert pick(16, 16, 128, 128, jnp.bfloat16, jnp.bfloat16) == 16
    assert pick(16, 128, 128, 128, jnp.bfloat16, jnp.bfloat16) == 16
    assert pick(16, 128, 128, 64, jnp.bfloat16, jnp.int8) == 16
    assert pick(4, 16, 128, 128, jnp.bfloat16, jnp.bfloat16) == 4   # TP
    few = pick(32, 512, 128, 128, jnp.bfloat16, jnp.bfloat16)
    assert few < 32 and 32 % few == 0
    few = pick(32, 256, 128, 128, jnp.bfloat16, jnp.int8)
    assert few < 32 and 32 % few == 0 and few % 8 == 0


def test_paged_head_groups_match_all_heads_at_once(monkeypatch):
    """The fallback path (an outer grid axis over head groups) gives what
    all heads a unit give, bit for bit, at the same pages a unit (one:
    heads in groups never join pages, and pages joined round otherwise)."""
    q, arenas, tbl, pos = _paged_operands(
        64, 5, _frontiers(5), False, jnp.bfloat16, frozen=(2,), h=4)
    monkeypatch.setattr(da, "_UNIT_BYTES", 0)
    whole = da.flash_decode_attention_paged(q, *arenas, tbl, pos, layer=1)
    monkeypatch.setattr(da, "_paged_heads_per_unit", lambda h, *a: 2)
    grouped = da.flash_decode_attention_paged(q, *arenas, tbl, pos, layer=1)
    np.testing.assert_array_equal(np.asarray(whole.astype(jnp.float32)),
                                  np.asarray(grouped.astype(jnp.float32)))


def test_layer_indexed_paged_decode_falls_back_on_small_pages():
    """A page that is no kernel block takes the gather + reference path,
    with the layer sliced there (the CPU test geometries)."""
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(2, 2, 1, 4), jnp.float32)
    k = jnp.asarray(rng.randn(3, 5, 2, 8, 4), jnp.float32)
    v = jnp.asarray(rng.randn(3, 5, 2, 8, 4), jnp.float32)
    tbl = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([3, 12], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(da.flash_decode_attention_paged(q, k, v, tbl, pos,
                                                   layer=1)),
        np.asarray(da.decode_attention_paged_reference(q, k[1], v[1], tbl,
                                                       pos)))
