"""The kernels of the main path, compiled for the v5e at GPT-2 355M /
BERT-large widths — without a chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``), so what Mosaic
would refuse on the chip — a slice off the tiling, too much VMEM, a
primitive with no TPU lowering — fails here at no chip time. Interpret mode
is forced off IN THE TEST (the program has no option for it), and every case
asserts that the compiled program holds a ``tpu_custom_call``: a kernel that
gave way to a ``jnp`` reference fails the case. A compile that passes is not
a chip run; ``chip_smoke.py`` is.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from deepspeed_tpu.ops import pallas_mode  # noqa: E402
from deepspeed_tpu.ops.sparse_attention import (  # noqa: E402
    FixedSparsityConfig)
from deepspeed_tpu.ops.sparse_attention.kernels import (  # noqa: E402
    block_sparse_attention)
from deepspeed_tpu.ops.transformer.kernels import (  # noqa: E402
    attention, decode_attention as da, fused_bias_dropout_residual, gelu,
    kda_update, layer_norm, softmax)
from deepspeed_tpu.ops.transformer.kernels import (  # noqa: E402
    dropout as ds_dropout)


@pytest.fixture(scope="module")
def host():
    """The four described chips of a v5e 2x2 host, with the compilation
    cache off around the module (a described-device executable is written
    to the cache but cannot be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip("cannot describe a v5e here: {}".format(e))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(host):
    """One described v5e chip."""
    return SingleDeviceSharding(host[0])


BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
# GPT-2 355M: batch 8, 16 heads, T 1024, head dim 64. Serving: 16 slots.
QKV = ((8, 16, 1024, 64), BF16)
SLOTS, HEADS, T_KV, HD = 16, 16, 1024, 64


def _fwd_bwd(fn, n):
    """Output and the gradients of its sum w.r.t. the first n arguments: the
    forward result is returned so that no forward kernel is dead code."""
    def run(*args):
        out, vjp = jax.vjp(lambda *a: fn(*a, *args[n:]), *args[:n])
        return out, vjp(jnp.ones_like(out))
    return run


def _flash_fwd(q, k, v):
    return attention._flash_fwd_pallas(q, k, v, None, 0.125, True, 1024, 1024)


def _flash_causal(q, k, v):
    return attention.flash_attention(q, k, v, causal=True)


def _flash_bert(q, k, v, mask):
    return attention.flash_attention(q, k, v, mask=mask)


def _flash_packed(heads):
    """The call of ``CausalSelfAttention``'s flash branch: c_attn's output
    as it comes, ``[B, T, tiles x 3 x 128]`` (two heads of 64 a lane tile,
    tile p's q | k | v side by side), under the region's name."""
    def attend(qkv):
        with jax.named_scope("attn"):
            return attention.flash_attention(qkv, heads=heads, head_dim=HD,
                                             causal=True)
    return attend


def _paged_args(page_len, rows, int8=False):
    n_lp = T_KV // page_len
    arena = ((SLOTS * n_lp + 1, HEADS, page_len, HD), I8 if int8 else BF16)
    scale = ((SLOTS * n_lp + 1, HEADS, page_len), F32)
    return ([((SLOTS, HEADS, rows, HD), BF16), arena, arena]
            + ([scale, scale] if int8 else [])
            + [((SLOTS, n_lp), I32), ((SLOTS,), I32)])


# The serving cells' paged pool: 24 layers, 16 slots x 9 pages + trash. The
# pool STORES it packed, g = 2 heads of 64 a lane tile (``da.lane_pack``):
# [24, 145, 8, 128, 128], scales a head of the model. The unpacked form
# (g = 1, the minor dim 64 padded to a tile on the chip) is what a caller
# with an arena of its own may still hand the launchers.
LAYERS, PAGES, PAGE = 24, SLOTS * (T_KV + 128) // 128 + 1, 128
ARENA = ((LAYERS, PAGES, HEADS, PAGE, HD), BF16)
ARENA_Q8 = ((LAYERS, PAGES, HEADS, PAGE, HD), I8)
ARENA_SCALE = ((LAYERS, PAGES, HEADS, PAGE), F32)
PACK = 128 // HD
PACKED = ((LAYERS, PAGES, HEADS // PACK, PAGE, PACK * HD), BF16)
PACKED_Q8 = (PACKED[0], I8)


def _whole_arena_args(rows, slots, int8=False, packed=False):
    """q, the arenas WHOLE, table, frontiers: what ``_forward`` hands the
    layer-indexed paged kernels."""
    rows_arena, codes = (PACKED, PACKED_Q8) if packed else (ARENA, ARENA_Q8)
    return ([((slots, HEADS, rows, HD), BF16)]
            + ([codes] * 2 + [ARENA_SCALE] * 2 if int8 else [rows_arena] * 2)
            + [((slots, PAGES // SLOTS), I32), ((slots,), I32)])


def _paged_whole(q, k, v, tbl, pos):
    return da.flash_decode_attention_paged(q, k, v, tbl, pos,
                                           layer=LAYERS - 1)


def _paged_whole_q8(q, k, v, ks, vs, tbl, pos):
    return da.flash_decode_attention_paged_q8(q, k, v, ks, vs, tbl, pos,
                                              layer=LAYERS - 1)


def _append_args(rows, slots, int8=False, packed=False):
    rows_arena, codes = (PACKED, PACKED_Q8) if packed else (ARENA, ARENA_Q8)
    new = ((slots, HEADS, rows, HD), I8 if int8 else BF16)
    new_scale = ((slots, HEADS, rows), F32)
    return ([new] * 2 + ([new_scale] * 2 if int8 else [])
            + ([codes] * 2 + [ARENA_SCALE] * 2 if int8 else [rows_arena] * 2)
            + [((slots, PAGES // SLOTS), I32), ((slots,), I32)])


def _append(*args):
    n = (len(args) - 2) // 2
    return da.kv_append(args[n:2 * n], args[:n], args[-2], args[-1],
                        layer=LAYERS - 1)


# OLMoE-1B-7B (serve-olmoe-decode-closed): 8 layers, 16 heads of 128, 32
# slots x 17 pages of 128 + trash. The first head dim that is a whole lane
# tile (GPT-2's 64 is half of one).
O_LAYERS, O_SLOTS, O_HD = 8, 32, 128
O_PAGES = O_SLOTS * (2048 + 128) // 128 + 1
O_ARENA = ((O_LAYERS, O_PAGES, HEADS, PAGE, O_HD), BF16)


def _olmoe_decode_args(rows, slots):
    return [((slots, HEADS, rows, O_HD), BF16), O_ARENA, O_ARENA,
            ((slots, O_PAGES // O_SLOTS), I32), ((slots,), I32)]


def _olmoe_decode(name=None):
    def run(q, k, v, tbl, pos):
        return da.flash_decode_attention_paged(q, k, v, tbl, pos, name=name,
                                               layer=O_LAYERS - 1)
    return run


def _olmoe_append_args(rows, slots):
    new = ((slots, HEADS, rows, O_HD), BF16)
    return [new, new, O_ARENA, O_ARENA,
            ((slots, O_PAGES // O_SLOTS), I32), ((slots,), I32)]


def _olmoe_append(k, v, ka, va, tbl, pos):
    return da.kv_append((ka, va), (k, v), tbl, pos, layer=O_LAYERS - 1)


# DeepSeek-V3's latent cache (serve-dsv3-decode-closed): ONE arena of 640
# stored lanes a token, 6 layers, 128 slots x 24 pages of 128 + trash; 128
# query heads read its one stored head, values the first 512 lanes.
D_SLOTS, D_HEADS, D_W, D_RANK = 128, 128, 640, 512
D_ARENA = ((6, D_SLOTS * 24 + 1, 1, PAGE, D_W), BF16)


def _latent_args(rows, slots):
    return [((slots, D_HEADS, rows, D_W), BF16), D_ARENA,
            ((slots, 24), I32), ((slots,), I32)]


def _latent(name=None):
    def run(q, arena, tbl, pos):
        return da.latent_decode(q, arena, tbl, pos, D_RANK, 0.135, name=name,
                                layer=5)
    return run


def _latent_append_args(rows, slots):
    return [((slots, 1, rows, D_W), BF16), D_ARENA,
            ((slots, 24), I32), ((slots,), I32)]


def _latent_append(new, arena, tbl, pos):
    return da.kv_append((arena,), (new,), tbl, pos, layer=5)


# Granite 4.0-H's one attention layer of ten (serve-granite4h-decode-closed):
# 8 stored heads of 128 under 32 query heads, 64 slots x 19 pages + trash.
G_SLOTS = 64
G_ARENA = ((1, G_SLOTS * 19 + 1, 8, PAGE, 128), BF16)


def _granite_append_args(rows, slots):
    new = ((slots, 8, rows, 128), BF16)
    return [new, new, G_ARENA, G_ARENA, ((slots, 19), I32), ((slots,), I32)]


def _granite_append(k, v, ka, va, tbl, pos):
    return da.kv_append((ka, va), (k, v), tbl, pos, layer=0)


# LFM2-8B-A1B's three attention layers of twelve (serve-lfm2moe-decode-
# closed): 8 stored heads of 64 under 32 query heads, so the arena packs
# g = 2 stored heads a lane tile (4 tiles a page) while rep = 4 query heads
# share each; 128 slots x 24 pages + trash. The first g = 2 x rep = 4.
L_SLOTS, L_HEADS, L_KV, L_HD = 128, 32, 8, 64
L_ARENA = ((3, L_SLOTS * 24 + 1, L_KV // 2, PAGE, 2 * L_HD), BF16)


def _lfm2_decode_args(rows, slots):
    return [((slots, L_HEADS, rows, L_HD), BF16), L_ARENA, L_ARENA,
            ((slots, 24), I32), ((slots,), I32)]


def _lfm2_decode(name=None):
    def run(q, k, v, tbl, pos):
        return da.flash_decode_attention_paged(q, k, v, tbl, pos, name=name,
                                               layer=2)
    return run


def _lfm2_append_args(rows, slots):
    new = ((slots, L_KV, rows, L_HD), BF16)
    return [new, new, L_ARENA, L_ARENA, ((slots, 24), I32), ((slots,), I32)]


def _lfm2_append(k, v, ka, va, tbl, pos):
    return da.kv_append((ka, va), (k, v), tbl, pos, layer=2)


# Jamba2-3B's attention (the cell serve-jamba2-decode-closed): MULTI-QUERY,
# 20 query heads over ONE stored head of 128 (g = 1, rep = 20), 2 layers that
# hold keys; 128 slots x 23 pages + trash. A page of the one head is 32 KB an
# arena: the scan's call joins eight (K > 1 on a k/v PAIR for the first
# time), the lane's 20 x 128 rows leave room for two.
J_SLOTS, J_HEADS, J_HD = 128, 20, 128
J_ARENA = ((2, J_SLOTS * 23 + 1, 1, PAGE, J_HD), BF16)


def _jamba_decode_args(rows, slots):
    return [((slots, J_HEADS, rows, J_HD), BF16), J_ARENA, J_ARENA,
            ((slots, 23), I32), ((slots,), I32)]


def _jamba_decode(name=None):
    def run(q, k, v, tbl, pos):
        return da.flash_decode_attention_paged(q, k, v, tbl, pos, name=name,
                                               layer=1)
    return run


def _jamba_append_args(rows, slots):
    new = ((slots, 1, rows, J_HD), BF16)
    return [new, new, J_ARENA, J_ARENA, ((slots, 23), I32), ((slots,), I32)]


def _jamba_append(k, v, ka, va, tbl, pos):
    return da.kv_append((ka, va), (k, v), tbl, pos, layer=1)


def _kda_update_args(rows):
    """Kimi Linear's 32 heads of 128: q, k, v, g [B, H, d], beta [B, H], the
    float32 state [B, H, d_k, d_v], the frontier-0 flags [B]."""
    return [((rows, 32, 128), F32)] * 4 + [
        ((rows, 32), F32), ((rows, 32, 128, 128), F32),
        ((rows,), jnp.bool_)]


def _dense_decode_args(rows, int8=False):
    plane = ((SLOTS, HEADS, T_KV, HD), I8 if int8 else BF16)
    scale = ((SLOTS, HEADS, T_KV), F32)
    return ([((SLOTS, HEADS, rows, HD), BF16), plane, plane]
            + ([scale, scale] if int8 else []) + [((SLOTS,), I32)])


def _sparse(q, k, v):
    layout = FixedSparsityConfig(
        num_heads=16, block=64,
        attention="bidirectional").make_layout(q.shape[2])
    return block_sparse_attention(q, k, v, np.asarray(layout), 64)


SPARSE_QKV = ((2, 16, 4096, 64), BF16)   # the BERT-large sparse shape
LN_X = ((8, 512, 1024), BF16)
VEC = ((1024,), F32)

# name -> (function, [(shape, dtype), ...], environment)
CASES = {
    "flash_fwd": (_flash_fwd, [QKV] * 3, {}),
    "flash_fwd_bwd_auto": (_fwd_bwd(_flash_causal, 3), [QKV] * 3, {}),
    "flash_fwd_bwd_split": (_fwd_bwd(_flash_causal, 3), [QKV] * 3,
                            {"DS_TPU_FLASH_BWD": "split"}),
    # The two training cells' calls, in the projection's own layout (PR
    # 49): one block of 1024 x 1024 a head, two heads a grid step, taken in
    # strips to the diagonal, forward and fused backward (GPT-2 XL's 25
    # heads are 12.5 tiles: 13, the last half dead); a sequence of two
    # blocks a side (strips in the diagonal blocks, the block before them
    # whole), head-major and packed; and the packed split backward.
    "flash_train_gpt2m_1chip_fwd_bwd": (
        _fwd_bwd(_flash_packed(16), 1), [((16, 1024, 8 * 384), BF16)], {}),
    "flash_train_gpt2xl_dp4_fwd_bwd": (
        _fwd_bwd(_flash_packed(25), 1), [((4, 1024, 13 * 384), BF16)], {}),
    "flash_t2048_two_blocks_fwd_bwd": (
        _fwd_bwd(_flash_causal, 3), [((4, 16, 2048, 64), BF16)] * 3, {}),
    "flash_packed_t2048_two_blocks_fwd_bwd": (
        _fwd_bwd(_flash_packed(25), 1), [((2, 2048, 13 * 384), BF16)], {}),
    "flash_packed_fwd_bwd_split": (
        _fwd_bwd(_flash_packed(25), 1), [((2, 1024, 13 * 384), BF16)],
        {"DS_TPU_FLASH_BWD": "split"}),
    "flash_bert_masked_fwd_bwd": (
        _fwd_bwd(_flash_bert, 3),
        [((8, 16, 512, 64), BF16)] * 3 + [((8, 512), F32)], {}),
    "decode_dense_bf16": (da.flash_decode_attention,
                          _dense_decode_args(1), {}),
    "decode_dense_q8": (da.flash_decode_attention_q8,
                        _dense_decode_args(1, int8=True), {}),
    # generate()'s prefill: the dense kernel with a prompt of query rows.
    "decode_dense_prefill_128_rows": (da.flash_decode_attention,
                                      _dense_decode_args(128), {}),
    "decode_paged_bf16_page128": (da.flash_decode_attention_paged,
                                  _paged_args(128, 1), {}),
    # Pages under the 128-position quantum reach the raw launcher only
    # (the public entry point gathers and takes the dense reference).
    "decode_paged_bf16_page16": (
        lambda *a: da._flash_decode_paged_pallas(*a, 0.125),
        _paged_args(16, 1), {}),
    "decode_paged_q8_page128": (da.flash_decode_attention_paged_q8,
                                _paged_args(128, 1, int8=True), {}),
    "decode_paged_spec_verify_5_rows": (da.flash_decode_attention_paged,
                                        _paged_args(128, 5), {}),
    "decode_paged_prefill_chunk_32_rows": (da.flash_decode_attention_paged,
                                           _paged_args(128, 32), {}),
    # The arena whole and the layer in the index map: what the serving
    # step runs (decode scan, speculative verify, the prefill lane).
    "decode_paged_whole_arena_1_row": (_paged_whole,
                                       _whole_arena_args(1, SLOTS), {}),
    "decode_paged_whole_arena_verify_5_rows": (
        _paged_whole, _whole_arena_args(5, SLOTS), {}),
    "decode_paged_whole_arena_lane_128_rows": (
        _paged_whole, _whole_arena_args(128, 1), {}),
    "decode_paged_whole_arena_q8": (_paged_whole_q8,
                                    _whole_arena_args(1, SLOTS, int8=True),
                                    {}),
    "kv_append_1_row": (_append, _append_args(1, SLOTS), {}),
    "kv_append_verify_5_rows": (_append, _append_args(5, SLOTS), {}),
    "kv_append_lane_128_rows": (_append, _append_args(128, 1), {}),
    "kv_append_q8_1_row": (_append, _append_args(1, SLOTS, int8=True), {}),
    "kv_append_q8_verify_5_rows": (_append,
                                   _append_args(5, SLOTS, int8=True), {}),
    "kv_append_q8_lane_128_rows": (_append,
                                   _append_args(128, 1, int8=True), {}),
    # The arena as the pool stores GPT-2's: two heads of 64 a lane tile.
    # The decode scan's one row, speculation's verify (2 x 5 rows in one
    # sublane tile), the prefill lane (2 x 128 rows), and the int8 tier.
    "packed_paged_decode_1_row_d64": (
        _paged_whole, _whole_arena_args(1, SLOTS, packed=True), {}),
    "packed_paged_verify_5_rows_d64": (
        _paged_whole, _whole_arena_args(5, SLOTS, packed=True), {}),
    "packed_paged_lane_128_rows_d64": (
        _paged_whole, _whole_arena_args(128, 1, packed=True), {}),
    "packed_paged_q8_1_row_d64": (
        _paged_whole_q8, _whole_arena_args(1, SLOTS, int8=True, packed=True),
        {}),
    "packed_paged_q8_verify_5_rows_d64": (
        _paged_whole_q8, _whole_arena_args(5, SLOTS, int8=True, packed=True),
        {}),
    "packed_kv_append_1_row_d64": (
        _append, _append_args(1, SLOTS, packed=True), {}),
    "packed_kv_append_verify_5_rows_d64": (
        _append, _append_args(5, SLOTS, packed=True), {}),
    "packed_kv_append_lane_128_rows_d64": (
        _append, _append_args(128, 1, packed=True), {}),
    "packed_kv_append_q8_1_row_d64": (
        _append, _append_args(1, SLOTS, int8=True, packed=True), {}),
    "olmoe_paged_decode_32_rows_d128": (_olmoe_decode(),
                                        _olmoe_decode_args(1, O_SLOTS), {}),
    "olmoe_prefill_attn_lane_128_rows_d128": (
        _olmoe_decode("prefill_attn"), _olmoe_decode_args(128, 1), {}),
    # Speculation's verify (k + 1 = 5 query rows a slot) at head dim 128.
    "olmoe_paged_verify_5_rows_d128": (_olmoe_decode(),
                                       _olmoe_decode_args(5, O_SLOTS), {}),
    "olmoe_kv_append_32_rows_d128": (_olmoe_append,
                                     _olmoe_append_args(1, O_SLOTS), {}),
    "olmoe_kv_append_lane_128_rows_d128": (_olmoe_append,
                                           _olmoe_append_args(128, 1), {}),
    # The decode scan's call (all 128 heads of a row one unit) and the lane's
    # (S = 128: 8 heads a group) of the latent cache's kernel.
    "latent_decode_128_slots_1_row": (_latent(), _latent_args(1, D_SLOTS),
                                      {}),
    "latent_prefill_attn_lane_128_rows": (
        _latent("prefill_attn"), _latent_args(128, 1), {}),
    # The decode scan's one row a slot at the two largest batches a cell
    # runs: all 128 rows of the latent cache's one arena and all 64 of the
    # grouped-query pool's 8 stored heads in ONE unit of the walk.
    "latent_kv_append_128_slots_1_row": (
        _latent_append, _latent_append_args(1, D_SLOTS), {}),
    "granite_kv_append_64_rows_gqa_d128": (
        _granite_append, _granite_append_args(1, G_SLOTS), {}),
    # Grouped-query rows over lane-packed heads, g = 2 x rep = 4 (LFM2's 32
    # over 8 of 64): the scan's call, the lane's, and both appends.
    "lfm2_paged_decode_128_slots_g2_rep4": (
        _lfm2_decode(), _lfm2_decode_args(1, L_SLOTS), {}),
    "lfm2_prefill_attn_lane_128_rows_g2_rep4": (
        _lfm2_decode("prefill_attn"), _lfm2_decode_args(128, 1), {}),
    "lfm2_kv_append_128_slots_g2": (
        _lfm2_append, _lfm2_append_args(1, L_SLOTS), {}),
    "lfm2_kv_append_lane_128_rows_g2": (
        _lfm2_append, _lfm2_append_args(128, 1), {}),
    # Multi-query rows, rep = 20 over ONE stored head of 128 (Jamba2-3B's):
    # the scan's call at eight pages a unit, the lane's at two, both appends.
    "jamba_paged_decode_128_slots_rep20": (
        _jamba_decode(), _jamba_decode_args(1, J_SLOTS), {}),
    "jamba_prefill_attn_lane_128_rows_rep20": (
        _jamba_decode("prefill_attn"), _jamba_decode_args(128, 1), {}),
    "jamba_kv_append_128_slots_1_head": (
        _jamba_append, _jamba_append_args(1, J_SLOTS), {}),
    "jamba_kv_append_lane_128_rows_1_head": (
        _jamba_append, _jamba_append_args(128, 1), {}),
    # The one-token KDA update at the Kimi cell's pool (all 32 heads of a
    # row one unit: 4 x 2 MiB of blocks) and at the batch of 1 the
    # benchmark's state probe calls it with.
    "kda_update_128_slots_32_heads": (kda_update.kda_update,
                                      _kda_update_args(128), {}),
    "kda_update_1_row_32_heads": (kda_update.kda_update,
                                  _kda_update_args(1), {}),
    "fused_layer_norm_fwd_bwd": (
        _fwd_bwd(lambda x, g, b: layer_norm.fused_layer_norm(x, g, b), 3),
        [LN_X, VEC, VEC], {}),
    "fused_bias_residual_layer_norm": (
        lambda x, r, g, b, bias: layer_norm.fused_bias_residual_layer_norm(
            x, r, g, b, bias=bias), [LN_X, LN_X, VEC, VEC, VEC], {}),
    "fused_bias_gelu_fwd_bwd": (
        _fwd_bwd(gelu.fused_bias_gelu, 2),
        [((8, 512, 4096), BF16), ((4096,), F32)], {}),
    "dropout_tpu_prng_fwd_bwd": (
        _fwd_bwd(lambda x, b, r: fused_bias_dropout_residual(
            x, b, r, 0.1, 7), 3), [LN_X, VEC, LN_X], {}),
    "dropout_attention_context": (
        lambda x: ds_dropout(x, 0.1, 7),
        [((8, 16, 512, 64), BF16)], {}),
    "attn_softmax_fwd_bwd": (
        _fwd_bwd(lambda s, m: softmax.attn_softmax(s, m, 0.125, False), 1),
        [((8, 16, 512, 512), BF16), ((8, 512), F32)], {}),
    "block_sparse_fwd_bwd_auto": (_fwd_bwd(_sparse, 3),
                                  [SPARSE_QKV] * 3, {}),
    "block_sparse_fwd_bwd_split": (_fwd_bwd(_sparse, 3), [SPARSE_QKV] * 3,
                                   {"DS_TPU_FLASH_BWD": "split"}),
}


KERNEL_NAME = re.compile(
    r"^(flash_fwd|flash_bwd_fused|flash_bwd_dq|flash_bwd_dkv|decode_attn|"
    r"decode_attn_q8|paged_decode|paged_decode_q8|prefill_attn|kv_append|"
    r"latent_decode|kda_update|"
    r"sparse_attn_fwd|sparse_attn_bwd_fused|sparse_attn_bwd_dq|"
    r"sparse_attn_bwd_dkv|dropout_fwd|dropout_mask|bias_gelu|"
    r"layer_norm_fwd|attn_softmax)(\.\d+)?$")


def _kernel_calls(text):
    return re.findall(
        r'%([\w.-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)


def test_kernel_names_survive_scan_cond_and_the_lanes_own_name(
        chip, monkeypatch):
    """The decode kernel inside a scan and the prefill lane's call of the
    same body inside a cond keep their own names (a trace of the parent
    printed ``closed_call`` and ``branch_1_fun`` there)."""
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)

    def step(q, k, v, tbl, pos):
        def lane(q):
            return da.flash_decode_attention_paged(q, k, v, tbl, pos,
                                                   name="prefill_attn")

        def body(q, _):
            return da.flash_decode_attention_paged(q, k, v, tbl, pos), None

        q = jax.lax.cond(pos[0] > 0, lane, lambda q: q, q)
        return jax.lax.scan(body, q, None, length=2)[0]

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in _paged_args(128, 1)]
    calls = _kernel_calls(jax.jit(step).lower(*args).compile().as_text())
    assert sorted(c.split(".")[0] for c in calls) == \
        ["paged_decode", "prefill_attn"]


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, chip, monkeypatch):
    fn, shapes, env = CASES[name]
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    # Every kernel's custom call is named after the kernel (what a trace
    # of the chip prints), never after where it sits.
    assert _kernel_calls(text) and all(
        KERNEL_NAME.match(c) for c in _kernel_calls(text)), \
        _kernel_calls(text)


@pytest.mark.parametrize("name", ["flash_train_gpt2m_1chip_fwd_bwd",
                                  "flash_train_gpt2xl_dp4_fwd_bwd"])
def test_training_cells_flash_calls_keep_their_names_and_take_strips(
        name, chip, monkeypatch):
    """The training cells' attention is ONE ``flash_fwd`` and ONE
    ``flash_bwd_fused`` custom call (the names the three rooflines read),
    the launcher's rule gave them strips (S divides the block, fewer tiles
    than the square are computed) and two heads a lane tile, and the
    program the chip's compiler makes of the call holds NO ``copy`` and no
    ``transpose`` under the attention region: the kernels read c_attn's
    output and write c_proj's input where they lie."""
    fn, shapes, _ = CASES[name]
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = _kernel_calls(text)
    assert sorted(c.split(".")[0] for c in calls) == \
        ["flash_bwd_fused", "flash_fwd"]
    walk = attention.last_walk()
    n = 1024 // walk["subtile"]
    assert n > 1 and walk["tiles_visited_share"] == (n + 1) / (2 * n)
    assert walk["lane_pack"] == 2
    under = [line.strip()[:160] for line in text.splitlines()
             if re.search(r'op_name="[^"]*attn[/)]', line)]
    assert under and not [line for line in under
                          if re.search(r" (copy|transpose)\(", line)], under


@pytest.mark.parametrize("name, pages", [
    ("latent_decode_128_slots_1_row", 4),
    ("latent_prefill_attn_lane_128_rows", 1),
    ("packed_paged_decode_1_row_d64", 1),
    ("packed_paged_lane_128_rows_d64", 1),
    ("olmoe_paged_decode_32_rows_d128", 1),
    ("olmoe_prefill_attn_lane_128_rows_d128", 1),
    ("jamba_paged_decode_128_slots_rep20", 8),
    ("jamba_prefill_attn_lane_128_rows_rep20", 2)])
def test_pages_a_unit_in_the_compiled_kernel(name, pages, chip, monkeypatch):
    """K, the pages one unit of a paged kernel joins, read off the compiled
    call: each arena is an operand once a page of the unit. The latent
    cache's decode call joins four 164 KB pages (inside its VMEM reckoning:
    all 128 heads still one unit, and Mosaic's scoped limit, or the compile
    fails) and its lane's call one; GPT-2's packed and OLMoE's calls are at
    512 KB and 1 MB a page and stay at one, the program they always were; a
    multi-query pair (Jamba2-3B's ONE stored head of 128, 64 KB a page for k
    and v) joins eight in the scan's call, K > 1 on a k/v pair, and two in
    the lane's, whose 2,560 query rows fill VMEM."""
    fn, shapes, _ = CASES[name]
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    call, = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    arena = "bf16[{}]{{".format(",".join(str(n) for n in shapes[1][0]))
    operands = call.split("operand_layout_constraints={")[1]
    assert operands.count(arena) == pages * shapes.count(shapes[1]), call
    if name.startswith("latent"):
        s_len = shapes[0][0][2]
        assert da._latent_heads_per_unit(
            D_HEADS, s_len, PAGE, D_W, D_RANK, BF16, pages) == \
            da._latent_heads_per_unit(D_HEADS, s_len, PAGE, D_W, D_RANK, BF16)


# ------------------------------------------------- the serving step itself

# No byte moves in these: a parameter, a tuple, an element of one, a bitcast,
# and control flow (its computations are read in their own right).
_PLUMBING = ("parameter", "get-tuple-element", "tuple", "bitcast",
             "conditional", "while")


def _computations(text):
    """Optimised HLO text -> {computation name: [instruction lines]}."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and " = " in line:
            cur.append(line.strip())
    return comps


def _reachable(comps, root):
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition|true_computation|"
                r"false_computation)=%([\w.-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += re.findall(r"%([\w.-]+)", group)
    return seen


def _arena_shaped(lines, shapes):
    """Instructions with a result of one of ``shapes`` that are neither a
    Pallas kernel nor plumbing."""
    found = []
    for line in lines:
        m = re.match(r"(?:ROOT )?%([\w.-]+) = (.*?) ([\w-]+)\(", line)
        if not m or m.group(3) in _PLUMBING or \
                'custom_call_target="tpu_custom_call"' in line:
            continue
        if any(shape in m.group(2) for shape in shapes):
            found.append((m.group(1), m.group(3)))
    return found


# The vocabulary of the whole-step compiles below, GPT-2's apart (its step is
# the cell's, whole). What they hold is in the layers: where each arena and
# each state is written. Their seconds were in the vocabulary: the sampler
# over ``[slots, vocabulary]`` took 28 of the 33 s OLMoE's step compiles for,
# a layer 0.4 s (PR 52: 1 or 2 layers, 8 or 64 experts, 8 or 32 slots all
# 33-35 s at 50,304 ids; 16.0 s at 1,024, 4.8 s at 128).
STEP_VOCAB = 512


def _mixed_step_text(chip, adapter, params, pool, chunk, lane):
    """Optimised HLO of the engine's mixed step, compiled for the described
    chip from shapes alone: ONE program, whose outputs beside the donated
    pool include the harvest's snapshot (``kv_pool.snapshot_of``: the engine
    reads it after the next step has taken the pool)."""
    from deepspeed_tpu.inference import engine as engine_mod

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    def scalar(dtype):
        return jax.ShapeDtypeStruct((), dtype, sharding=chip)

    def mixed_step(*args):
        return engine_mod._mixed_step_program(*args)

    lowered = jax.jit(
        mixed_step, static_argnums=(1, 2, 3), donate_argnums=(4,),
        compiler_options=engine_mod.step_compiler_options("tpu")).lower(
        on_chip(params), adapter, chunk, None, on_chip(pool),
        jax.ShapeDtypeStruct((1, lane), I32, sharding=chip),
        scalar(I32), scalar(I32), scalar(I32), scalar(jnp.bool_),
        scalar(jnp.bool_), scalar(I32), scalar(I32), scalar(F32),
        scalar(I32), scalar(jnp.uint32))
    new_pool, _, _, _, snap = lowered.out_info
    slots = pool["pos"].shape
    assert set(snap) == {"pos", "active", "last_tok"} | {
        name for name in pool if name.startswith("aux_")}
    assert all(snap[name].shape == new_pool[name].shape for name in snap)
    assert snap["pos"].shape == snap["active"].shape == slots
    text = lowered.compile().as_text()
    assert len(re.findall(r"^ENTRY ", text, re.M)) == 1
    return text


def _while_bodies(comps, within=""):
    """The body computations' names of the ``while``s whose line holds
    ``within``."""
    return [m for lines in comps.values() for line in lines
            if within in line and " while(" in line
            for m in re.findall(r"body=%([\w.-]+)", line)]


def _scan_lines(comps):
    """The instruction lines of everything the decode scan's ``while`` body
    reaches, and the names of those computations."""
    bodies = _while_bodies(comps, "decode_scan/while")
    assert len(bodies) == 1, bodies
    scan = _reachable(comps, bodies[0])
    return scan, [line for name in scan for line in comps[name]]


def test_mixed_step_forms_no_layer_of_the_arena_in_the_decode_scan(
        chip, monkeypatch):
    """The engine's mixed step (355M widths and all its 24 layers, the
    cells' paged pool: 16 slots, page 128, chunk 16, prefill chunk 128;
    a minute to compile) for the described chip, with the compiler options
    the engine jits it with: inside the decode scan's ``while`` body nothing
    but the kernels has a result shaped like the arena or one layer of it
    — no slice, scatter, ``dynamic-update-slice`` or layout ``copy``. (A
    5-D XLA scatter in place of ``kv_append`` passes every parity test
    and fails here: XLA gives the arena the scatter's layout and converts
    all of it around every kernel call.) Nor outside the scan: the pool
    stores the arena as the kernels read it, two heads of 64 a lane tile
    (``[L, P, 8, 128, 128]``), so the step no longer converts k and v
    where it enters and leaves (six whole-arena ``copy`` until PR 30).
    All 24 layers, because depth decides it: from some 18 layers on XLA's
    copy elision runs out of its default allowance and leaves four
    whole-arena copies around the lane's last ``kv_append``
    (``engine.step_compiler_options``); at 4 layers this test saw none."""
    from deepspeed_tpu.inference import kv_pool
    from deepspeed_tpu.inference.adapters.gpt2 import GPT2Adapter
    from deepspeed_tpu.inference.config import InferenceConfig
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    n_layer, chunk, lane = LAYERS, 16, 128
    model = GPT2LMHeadModel(GPT2Config(
        n_embd=HEADS * HD, n_layer=n_layer, n_head=HEADS, n_positions=T_KV,
        vocab_size=50257, dtype=BF16, dropout=0.0))
    config = InferenceConfig.from_dict(dict(
        max_slots=SLOTS, max_len=T_KV, chunk_size=chunk, paged_kv=True,
        kv_page_len=PAGE, prefill_chunk=lane, use_flash_decode=True))
    adapter = GPT2Adapter.from_model(model, use_flash_decode=True).bind(
        config, None)
    # flax's float32 tree as the engine holds it: the matrices in bf16
    params = jax.eval_shape(lambda: adapter.serving_params(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), I32))["params"]))
    assert params["h_0"]["mlp"]["c_fc"]["kernel"].dtype == BF16
    assert params["wte"].dtype == params["ln_f"]["scale"].dtype == F32
    pool = jax.eval_shape(lambda: kv_pool.init_pool(
        adapter.cache_spec(), SLOTS, T_KV, slack=lane, page_len=PAGE,
        num_pages=PAGES - 1))
    assert pool["k"].shape == (n_layer,) + PACKED[0][1:] == \
        (n_layer, PAGES, HEADS // 2, PAGE, 128)

    text = _mixed_step_text(chip, adapter, params, pool, chunk, lane)
    # No matrix is converted inside the step, hoisted out of the scan or not
    # (a float32 tree paid 3.7 ms of a 35 ms step for it until PR 56): no
    # ``convert`` gives a bf16 array of a matrix's shape. (The TABLE still has
    # one, the compiler's own: the head's float32 matmul runs as one bf16
    # pass, and its operand's rounding is hoisted to ENTRY, once a step.)
    matrices = {"{},{}".format(*leaf.shape)
                for leaf in jax.tree_util.tree_leaves(params)
                if leaf.ndim == 2 and leaf.dtype == BF16}
    assert len(matrices) == 4       # wpe's shape is c_proj's
    assert re.findall(r"= bf16\[({})\]\S* convert\(".format(
        "|".join(sorted(matrices))), text) == []

    comps = _computations(text)
    scan, in_scan = _scan_lines(comps)
    names = sorted(c.split(".")[0] for c in _kernel_calls(
        "\n".join(in_scan)))
    assert names == ["kv_append"] * n_layer + ["paged_decode"] * n_layer
    shapes = ["[{},{},{},{},{}]".format(n_layer, *PACKED[0][1:]),
              "[{},{},{},{}]".format(*PACKED[0][1:]),
              "[{},{},{},{},{}]".format(n_layer, *ARENA[0][1:]),
              "[{},{},{},{}]".format(*ARENA[0][1:])]
    assert _arena_shaped(in_scan, shapes) == []
    # Outside it: the lane's cond and the scan's carry add nothing of their
    # own, and nothing converts the arena where the step enters or leaves.
    outside = _arena_shaped(
        [line for name, lines in comps.items() if name not in scan
         for line in lines], shapes)
    assert outside == [], outside


def test_decoder_mixed_step_forms_no_layer_of_the_arena_in_its_decode_scan(
        chip, monkeypatch):
    """The same for the config-driven decoder block at OLMoE-1B-7B's widths
    (2 of its layers; the cell's pool: 32 slots of 2048, page 128, chunk 16,
    lane 128): in the decode scan the arenas meet ``kv_append`` and the
    layer-indexed ``paged_decode`` and nothing else, and no layer of the
    STACKED expert weights is copied out to feed a matmul (805 MB a layer:
    the grouped matmul that lost the chip measurement wanted exactly that,
    PERF.md section 6, PR 27)."""
    from deepspeed_tpu.inference import kv_pool
    from deepspeed_tpu.inference.adapters import DecoderAdapter
    from deepspeed_tpu.inference.config import InferenceConfig
    from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    n_layer, chunk, lane, experts, width = 2, 16, 128, 64, 1024
    model = DecoderLM(DecoderConfig(
        vocab_size=STEP_VOCAB, n_layer=n_layer, n_head=HEADS, head_dim=O_HD,
        hidden_size=2048, n_positions=4096, n_experts=experts,
        experts_per_token=8, expert_width=width, dtype=BF16))
    config = InferenceConfig.from_dict(dict(
        max_slots=O_SLOTS, max_len=2048, chunk_size=chunk, paged_kv=True,
        kv_page_len=PAGE, prefill_chunk=lane, use_flash_decode=True))
    adapter = DecoderAdapter.from_model(model, use_flash_decode=True).bind(
        config, None)
    assert adapter.gcfg.kv_page_len == PAGE and adapter.gcfg.use_flash_decode
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))["params"])
    pool = jax.eval_shape(lambda: dict(kv_pool.init_pool(
        adapter.cache_spec(), O_SLOTS, 2048, slack=lane, page_len=PAGE,
        num_pages=O_PAGES - 1), **adapter.aux_state()))
    assert pool["k"].shape == (n_layer,) + O_ARENA[0][1:]

    comps = _computations(_mixed_step_text(chip, adapter, params, pool,
                                           chunk, lane))
    scan, in_scan = _scan_lines(comps)
    names = sorted(c.split(".")[0] for c in _kernel_calls(
        "\n".join(in_scan)))
    assert names == ["kv_append"] * n_layer + ["paged_decode"] * n_layer
    arena = ["[{},{},{},{},{}]".format(n_layer, O_PAGES, HEADS, PAGE, O_HD),
             "[{},{},{},{}]".format(O_PAGES, HEADS, PAGE, O_HD)]
    assert _arena_shaped(in_scan, arena) == []
    # one layer of the stacked experts as a value of its own, anywhere (a
    # slice INSIDE a matmul's fusion is an operand read in place, not a copy)
    everywhere = [line for name, lines in comps.items()
                  if not name.startswith("fused_computation")
                  for line in lines]
    one_layer = ["[{},2048,{}]".format(experts, 2 * width),
                 "[{},{},2048]".format(experts, width)]
    assert _arena_shaped(everywhere, one_layer) == []


def test_hybrid_mixed_step_updates_each_layers_state_where_it_lies(
        chip, monkeypatch):
    """The hybrid stack at Granite 4.0-H Small's widths, FOUR layers (Mamba
    on both sides of the one attention layer, two of them in a row; the
    cell's period has ten) and a vocabulary of ``STEP_VOCAB`` (the cell's
    pool: 64 slots of 2304, page 128, chunk 16, lane 128; 36 of 72 experts
    held; a quarter of a minute to compile): in the decode scan each Mamba
    layer's float32 state ``slot_ssm<j>`` [64, 128, 8192] is the result of
    ONE fusion an iteration (the update, in place in the scan's carry) and of
    nothing else: no ``copy``, no slice, no ``dynamic-update-slice``; and the
    one attention layer's arena (8 STORED heads under 32 query heads) meets
    ``kv_append`` and the grouped-query ``paged_decode`` only. (The state
    written by an XLA scatter over its rows fails this at four layers as at
    ten, and the scatter in place of ``kv_append`` fails the arena's half:
    ``CHANGES.md``, PR 52.)"""
    from deepspeed_tpu.inference import kv_pool
    from deepspeed_tpu.inference.adapters import DecoderAdapter
    from deepspeed_tpu.inference.config import InferenceConfig
    from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    slots, chunk, lane = 64, 16, 128
    kinds = ("mamba", "mamba", "attention", "mamba")
    n_state = kinds.count("mamba")
    model = DecoderLM(DecoderConfig(
        vocab_size=STEP_VOCAB, n_layer=len(kinds), n_head=32, head_dim=128,
        hidden_size=4096, n_positions=131072, n_experts=72,
        experts_per_token=10, expert_width=768, qk_norm=False,
        norm_topk_prob=True, tie_word_embeddings=True, dtype=BF16,
        n_kv_head=8, rope=False, attn_scale=1 / 128.0,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=16.0, shared_width=1536, experts_held=(0, 36),
        layer_types=kinds, mamba_heads=128, mamba_head_dim=64,
        mamba_state=128))
    config = InferenceConfig.from_dict(dict(
        max_slots=slots, max_len=2304, chunk_size=chunk, paged_kv=True,
        kv_page_len=PAGE, prefill_chunk=lane, use_flash_decode=True))
    adapter = DecoderAdapter.from_model(model, use_flash_decode=True).bind(
        config, None)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))["params"])
    pool = jax.eval_shape(lambda: dict(kv_pool.init_pool(
        adapter.cache_spec(), slots, 2304, slack=lane, page_len=PAGE),
        **adapter.aux_state()))
    assert pool["k"].shape == (1, 64 * 19 + 1, 8, PAGE, 128)
    assert all(pool["slot_ssm{}".format(j)].shape == (slots, 128, 8192)
               for j in range(n_state))

    text = _mixed_step_text(chip, adapter, params, pool, chunk, lane)
    dump = os.environ.get("DS_TPU_HLO_DUMP")
    if dump:
        with open(dump, "w") as f:
            f.write(text)
    comps = _computations(text)
    scan, in_scan = _scan_lines(comps)
    names = sorted(c.split(".")[0] for c in _kernel_calls(
        "\n".join(in_scan)))
    assert names == ["kv_append", "paged_decode"]
    # Whole instructions only: what a fusion computes inside itself never
    # reaches memory.
    def whole(names):
        return [line for name in names
                if not name.startswith("fused_computation")
                for line in comps[name]]

    state = ["f32[64,128,8192]"]
    touched = _arena_shaped(whole(scan), state)
    assert [op for _, op in touched] == ["fusion"] * n_state, touched
    arena = ["[1,1217,8,128,128]", "[1217,8,128,128]"]
    assert _arena_shaped([line for lines in comps.values()
                          for line in lines], arena) == []
    # Outside the scan the lane slices ONE slot's state out of each layer's
    # and writes it back where it lies (a fused dynamic-update-slice); no
    # copy of a layer's state anywhere in the step.
    outside = _arena_shaped(whole(set(comps) - set(scan)), state)
    assert [op for _, op in outside] == ["fusion"] * n_state, outside


def test_latent_mixed_step_appends_and_attends_the_one_arena_in_place(
        chip, monkeypatch):
    """DeepSeek-V3's block at its published widths and the cell's 6 layers
    (1 dense + 5 with 8 of 256 experts held; the cell's pool: 128 slots of
    2944, page 128, chunk 16, lane 128; a minute or two to compile): the
    latent cache is ONE arena ``[6, 3073, 1, 128, 640]`` (576 stored as 640:
    at 576 the compiler adds two whole-arena copies and 3 GB of temp around
    the kernels, which is how the width was decided). In the decode scan
    it meets ``kv_append`` and ``latent_decode``, once a layer each and under
    those names, and nothing else; no whole-arena value is formed anywhere
    in the step; the lane's call of the same body is ``prefill_attn``; and
    the step fits the chip beside its weights and pool."""
    from deepspeed_tpu.inference import kv_pool
    from deepspeed_tpu.inference.adapters import DecoderAdapter
    from deepspeed_tpu.inference.config import InferenceConfig
    from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    slots, chunk, lane, n_layer = 128, 16, 128, 6
    model = DecoderLM(DecoderConfig(
        vocab_size=STEP_VOCAB, n_layer=n_layer, n_head=128, head_dim=192,
        hidden_size=7168, n_positions=163840, n_experts=256,
        experts_per_token=8, expert_width=2048, rms_norm_eps=1e-6,
        qk_norm=False, norm_topk_prob=True, dtype=BF16, shared_width=2048,
        experts_held=(0, 8), kv_lora_rank=512, q_lora_rank=1536,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        rope_yarn=(40.0, 4096, 32.0, 1.0, 1.0, 1.0), dense_layers=1,
        dense_width=18432, router_scoring="sigmoid", n_group=8, topk_group=4,
        routed_scaling=2.5))
    config = InferenceConfig.from_dict(dict(
        max_slots=slots, max_len=2944, chunk_size=chunk, paged_kv=True,
        kv_page_len=PAGE, prefill_chunk=lane, use_flash_decode=True))
    adapter = DecoderAdapter.from_model(model, use_flash_decode=True).bind(
        config, None)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))["params"])
    pool = jax.eval_shape(lambda: dict(kv_pool.init_pool(
        adapter.cache_spec(), slots, 2944, slack=lane, page_len=PAGE),
        **adapter.aux_state()))
    assert "v" not in pool and pool["k"].shape == (6, 128 * 24 + 1, 1, PAGE,
                                                   640)

    text = _mixed_step_text(chip, adapter, params, pool, chunk, lane)
    dump = os.environ.get("DS_TPU_HLO_DUMP")
    if dump:
        with open(dump, "w") as f:
            f.write(text)
    comps = _computations(text)
    scan, in_scan = _scan_lines(comps)
    names = sorted(c.split(".")[0] for c in _kernel_calls(
        "\n".join(in_scan)))
    assert names == ["kv_append"] * n_layer + ["latent_decode"] * n_layer
    everywhere = sorted(c.split(".")[0] for c in _kernel_calls(text))
    assert everywhere == ["kv_append"] * 2 * n_layer \
        + ["latent_decode"] * n_layer + ["prefill_attn"] * n_layer
    arena = ["[6,3073,1,128,640]", "[3073,1,128,640]"]
    assert _arena_shaped([line for lines in comps.values()
                          for line in lines], arena) == []


def test_diffusion_step_attends_a_block_at_32_rows_a_stored_head(
        chip, monkeypatch):
    """The mixed step of a model that generates by diffusion over blocks of 4
    (SDAR-30B-A3B-Chat's attention at its published widths: 32 query heads
    over 4 stored heads of 128; ONE layer and 8 experts, the depth and width
    at which the property first shows; the cell's pool: 64 slots of 2304,
    page 128, chunk 16, lane 128; seconds to compile): Mosaic takes
    ``paged_decode`` with the block visibility rule in its straddle mask at
    ``rep x block`` = 32 query rows a stored head and ``kv_append`` at 4 rows
    a slot; the scan is the diffusion scan (a region ``unmask``), its arenas
    meet those two kernels and nothing else, and the lane's kernel is
    ``prefill_attn`` under the same rule."""
    from deepspeed_tpu.inference import engine as engine_mod, kv_pool
    from deepspeed_tpu.inference.adapters import DecoderAdapter
    from deepspeed_tpu.inference.config import InferenceConfig
    from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    slots, chunk, lane, block = 64, 16, 128, 4
    model = DecoderLM(DecoderConfig(
        vocab_size=151936, n_layer=1, n_head=32, head_dim=128,
        hidden_size=2048, n_positions=32768, n_experts=8,
        experts_per_token=2, expert_width=768, rms_norm_eps=1e-6,
        rope_theta=1e6, qk_norm="head", norm_topk_prob=True, dtype=BF16,
        n_kv_head=4, block_length=block, mask_token_id=151669))
    config = InferenceConfig.from_dict(dict(
        max_slots=slots, max_len=2304, chunk_size=chunk, paged_kv=True,
        kv_page_len=PAGE, prefill_chunk=lane, use_flash_decode=True,
        denoising_steps=2))
    adapter = DecoderAdapter.from_model(model, use_flash_decode=True).bind(
        config, None)
    assert adapter.block_length == block
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))["params"])
    pool = jax.eval_shape(lambda: dict(
        kv_pool.init_pool(adapter.cache_spec(), slots, 2304, slack=lane,
                          page_len=PAGE),
        **engine_mod.block_state(slots, block), **adapter.aux_state()))
    assert pool["k"].shape == (1, 64 * 19 + 1, 4, PAGE, 128)

    text = _mixed_step_text(chip, adapter, params, pool, chunk, lane)
    comps = _computations(text)
    scan, in_scan = _scan_lines(comps)
    assert any("/unmask/" in line for line in in_scan)
    names = sorted(c.split(".")[0] for c in _kernel_calls(
        "\n".join(in_scan)))
    assert names == ["kv_append", "paged_decode"]
    everywhere = sorted(c.split(".")[0] for c in _kernel_calls(text))
    assert everywhere == ["kv_append"] * 2 + ["paged_decode", "prefill_attn"]
    arena = ["[1,1217,4,128,128]", "[1217,4,128,128]"]
    assert _arena_shaped(in_scan, arena) == []


def test_kimi_mixed_step_holds_three_caches_each_where_it_lies(
        chip, monkeypatch):
    """Kimi Linear's block at its published widths, ONE period of its four
    layers (3 KDA, 1 MLA; 1 dense + 3 with 16 of 256 experts held; the cell
    has three periods) and a vocabulary of ``STEP_VOCAB`` (the cell's pool:
    128 slots of 2944, page 128, chunk 16, lane 128; a quarter of a minute to
    compile): the latent plane is ONE arena ``[1, 3073, 1, 128, 640]``, as
    deep as the MLA layers only (DeepSeek-V3's step above holds one six
    deep), that meets ``kv_append`` and ``latent_decode`` once an MLA layer
    in the decode scan and is formed whole nowhere; each KDA layer's float32
    state ``slot_kda<j>``
    [128, 32, 128, 128] is in the scan the result of its ``kda_update`` call
    (aliased: updated where it lies in the scan's carry) and of NOTHING
    else: no ``copy``, no slice, no ``dynamic-update-slice``, no fusion that
    writes a whole state, and no fusion that reads one (the frontier-0
    select and both sums are the kernel's). (A state handed to its kernel
    through an XLA scatter over its rows fails this at one period as at
    three, and the scatter in place of ``kv_append`` fails the arena's half:
    ``CHANGES.md``, PR 52.)"""
    from deepspeed_tpu.inference import kv_pool
    from deepspeed_tpu.inference.adapters import DecoderAdapter
    from deepspeed_tpu.inference.config import InferenceConfig
    from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    slots, chunk, lane = 128, 16, 128
    kinds = ("kda", "kda", "kda", "attention")
    n_kda, n_mla = kinds.count("kda"), kinds.count("attention")
    model = DecoderLM(DecoderConfig(
        vocab_size=STEP_VOCAB, n_layer=len(kinds), n_head=32, head_dim=192,
        hidden_size=2304, n_positions=1048576, n_experts=256,
        experts_per_token=8, expert_width=1024, qk_norm=False,
        norm_topk_prob=True, dtype=BF16, rope=False, shared_width=1024,
        experts_held=(0, 16), layer_types=kinds, kv_lora_rank=512,
        q_lora_rank=0, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        dense_layers=1, dense_width=9216, router_scoring="sigmoid",
        routed_scaling=2.446, kda_heads=32, kda_head_dim=128))
    config = InferenceConfig.from_dict(dict(
        max_slots=slots, max_len=2944, chunk_size=chunk, paged_kv=True,
        kv_page_len=PAGE, prefill_chunk=lane, use_flash_decode=True))
    adapter = DecoderAdapter.from_model(model, use_flash_decode=True).bind(
        config, None)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))["params"])
    pool = jax.eval_shape(lambda: dict(kv_pool.init_pool(
        adapter.cache_spec(), slots, 2944, slack=lane, page_len=PAGE),
        **adapter.aux_state()))
    assert "v" not in pool and pool["k"].shape == (n_mla, 128 * 24 + 1, 1,
                                                   PAGE, 640)
    assert all(pool["slot_kda{}".format(j)].shape == (slots, 32, 128, 128)
               and pool["slot_kdaconv{}".format(j)].shape
               == (slots, 3, 12288) for j in range(n_kda))

    text = _mixed_step_text(chip, adapter, params, pool, chunk, lane)
    dump = os.environ.get("DS_TPU_HLO_DUMP")
    if dump:
        with open(dump, "w") as f:
            f.write(text)
    comps = _computations(text)
    scan, in_scan = _scan_lines(comps)
    names = sorted(c.split(".")[0] for c in _kernel_calls(
        "\n".join(in_scan)))
    assert names == ["kda_update"] * n_kda + ["kv_append"] * n_mla \
        + ["latent_decode"] * n_mla
    everywhere = sorted(c.split(".")[0] for c in _kernel_calls(text))
    assert everywhere == ["kda_update"] * n_kda + ["kv_append"] * 2 * n_mla \
        + ["latent_decode"] * n_mla + ["prefill_attn"] * n_mla
    arena = ["[1,3073,1,128,640]", "[3073,1,128,640]"]
    assert _arena_shaped([line for lines in comps.values()
                          for line in lines], arena) == []

    # Whole instructions only: what a fusion computes inside itself never
    # reaches memory.
    def whole(names):
        return [line for name in names
                if not name.startswith("fused_computation")
                for line in comps[name]]

    state = "f32[128,32,128,128]"
    in_scan = whole(scan)
    # nothing in the scan but the kernel has a whole state for its result
    assert _arena_shaped(in_scan, [state]) == []
    updates = [line for line in in_scan
               if 'custom_call_target="tpu_custom_call"' in line
               and state in line]
    assert len(updates) == n_kda and all("kda_update" in line
                                     for line in updates)
    # each is aliased to its state operand (operand 5: three scalar
    # prefetches, the unit's rows of decay | k | q, v, the state; output 1)
    assert all(re.search(r"output_to_operand_aliasing=\{[^=]*\{1\}: \(5, "
                         r"\{\}\)", line) for line in updates), updates
    # and nothing else in the scan READS a whole state (the frontier-0
    # select and both sums are the kernel's): a state's name is an operand
    # of its kernel and of plumbing alone
    held = {m.group(1) for m in (re.match(
        r"(?:ROOT )?%([\w.-]+) = " + re.escape(state), line)
        for line in in_scan) if m}
    assert len(held) >= 2 * n_kda, held
    readers = []
    for line in in_scan:
        m = re.match(r"(?:ROOT )?%[\w.-]+ = .*? ([\w-]+)\((.*?)\)", line)
        if m and m.group(1) not in _PLUMBING and \
                'custom_call_target="tpu_custom_call"' not in line and \
                held & set(re.findall(r"%([\w.-]+)", m.group(2))):
            readers.append(line[:200])
    assert readers == [], readers
    outside = _arena_shaped(whole(set(comps) - set(scan)), [state])
    assert {op for _, op in outside} <= {"fusion"}, outside


def test_lfm2_mixed_step_attends_packed_grouped_query_keys_in_place(
        chip, monkeypatch):
    """LFM2-8B-A1B's block at its published widths and the cell's 12 layers
    (9 gated short convolutions, 3 attention; 1 dense + 11 with all 32
    experts held; the cell's pool: 128 slots of 2944, page 128, chunk 16,
    lane 128; half a minute to compile): the keys and values are two arenas
    ``[3, 3073, 4, 128, 128]``, as deep as the attention layers only, 8
    stored heads of 64 packed two a lane tile, that meet ``kv_append`` and
    ``paged_decode`` (32 query heads, four a stored head) once an attention
    layer in the decode scan and are formed whole nowhere; the lane's calls
    are ``prefill_attn``; each conv layer's tail is its own
    ``[128, 2, 2048]``; and the step's scratch is a small part of what the
    chip has left beside 8.2 GB of weights (8.47 with the published
    vocabulary; ``STEP_VOCAB`` here) and 2.4 GB of pool."""
    from deepspeed_tpu.inference import kv_pool
    from deepspeed_tpu.inference.adapters import DecoderAdapter
    from deepspeed_tpu.inference.config import InferenceConfig
    from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    slots, chunk, lane = 128, 16, 128
    kinds = ("shortconv", "shortconv", "attention", "shortconv") * 3
    model = DecoderLM(DecoderConfig(
        vocab_size=STEP_VOCAB, n_layer=len(kinds), n_head=L_HEADS,
        head_dim=L_HD,
        hidden_size=2048, n_positions=128000, n_experts=32,
        experts_per_token=4, expert_width=1792, rope_theta=1e6,
        qk_norm="head", norm_topk_prob=True, tie_word_embeddings=True,
        dtype=BF16, n_kv_head=L_KV, layer_types=kinds, dense_layers=1,
        dense_width=7168, router_scoring="sigmoid"))
    config = InferenceConfig.from_dict(dict(
        max_slots=slots, max_len=2944, chunk_size=chunk, paged_kv=True,
        kv_page_len=PAGE, prefill_chunk=lane, use_flash_decode=True))
    adapter = DecoderAdapter.from_model(model, use_flash_decode=True).bind(
        config, None)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))["params"])
    pool = jax.eval_shape(lambda: dict(kv_pool.init_pool(
        adapter.cache_spec(), slots, 2944, slack=lane, page_len=PAGE),
        **adapter.aux_state()))
    assert pool["k"].shape == pool["v"].shape == L_ARENA[0]
    assert all(pool["slot_shortconv{}".format(j)].shape == (slots, 2, 2048)
               for j in range(9))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves((params, pool)))
    assert 10.5e9 < held < 10.8e9

    text = _mixed_step_text(chip, adapter, params, pool, chunk, lane)
    comps = _computations(text)
    scan, in_scan = _scan_lines(comps)
    names = sorted(c.split(".")[0] for c in _kernel_calls(
        "\n".join(in_scan)))
    assert names == ["kv_append"] * 3 + ["paged_decode"] * 3
    everywhere = sorted(c.split(".")[0] for c in _kernel_calls(text))
    assert everywhere == ["kv_append"] * 6 + ["paged_decode"] * 3 \
        + ["prefill_attn"] * 3
    arena = ["[3,3073,4,128,128]", "[3073,4,128,128]"]
    assert _arena_shaped([line for lines in comps.values()
                          for line in lines], arena) == []


# ------------------------------------------------- the ZeRO-2 training step

def test_zero2_step_keeps_the_partition_out_of_the_model(host, monkeypatch):
    """The engine's ZeRO-2 fused step for the four described chips (a
    1-layer, 256-wide GPT-2 with an odd vocabulary of 1001, 8 sequences of
    256; 5 s): the optimizer's partition stays out of the model. The tied
    table's gradient can only split by FEATURES, and GSPMD carried that
    split into the LM head: ``f32[2048,1001] all-reduce`` of the logits a
    chunk, every chip over every chip's rows, ``all-to-all``s around it
    (149.5 ms of exposed collectives a step at GPT-2 XL on the chip, PR
    46). Under the engine's data-parallel region the model holds no
    collective, every matrix leaf that splits by rows leaves through a
    reduce-scatter, and the flash kernels are in the program. Since PR 54
    the float32 master is sharded as its moments are and the region
    gathers its bf16 cast at its head (``zero_gather``): the step holds no
    float32 all-gather and nothing after the update. The engine
    is built on described devices, which hold no array: ``device_put`` is
    the identity while it places its state, and the step is lowered from
    shapes."""
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from tests.unit.test_engine import (
        SEQ, gradient_scatters, hlo_collectives, optimizer_collectives,
        parameter_gathers, partition_leaks, tiny_gpt2, tiny_gpt2_params)

    ids = jnp.zeros((8, SEQ), I32)
    mesh = mesh_lib.build_mesh(devices=host)
    with monkeypatch.context() as m:
        m.setattr(jax, "device_put", lambda x, *a, **k: x)
        engine, _, _, _ = deepspeed.initialize(
            model=tiny_gpt2(BF16), model_parameters=tiny_gpt2_params(BF16),
            mesh=mesh,
            config_params={"train_batch_size": 8,
                           "optimizer": {"type": "AdamW",
                                         "params": {"lr": 1e-4}},
                           "bf16": {"enabled": True},
                           "zero_optimization": {"stage": 2}})
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)

    def described(tree, shardings):
        return jax.tree_util.tree_map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            tree, shardings)

    whole = mesh_lib.replicated(mesh)
    rows = jax.ShapeDtypeStruct(ids.shape, I32,
                                sharding=mesh_lib.batch_sharding(mesh))
    scalar = jax.ShapeDtypeStruct((), F32, sharding=whole)
    text = engine._build_fused_step().lower(
        described(engine.params, engine.param_sharding),
        described(engine.opt_state, engine.opt_state_sharding),
        (rows, rows), jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=whole),
        scalar, scalar, scalar).compile().as_text()

    assert partition_leaks(text) == []
    # (The table itself, split along its MINOR dim, is reduced whole in
    # bf16 and sliced: the compiler scatters along a major dim only.)
    matrices = [p for p in jax.tree_util.tree_leaves(engine.params)
                if p.ndim == 2 and p.shape[0] % len(host) == 0]
    assert len(matrices) == 5
    assert len(gradient_scatters(text)) >= len(matrices)
    assert sorted(c.split(".")[0] for c in _kernel_calls(text)) == \
        ["flash_bwd_fused", "flash_fwd"]
    # The master is sharded and its bf16 CAST is what crosses the wire,
    # under the region's zero_gather (the TPU's compiler merges the small
    # leaves' gathers, so they number at most a leaf each): no float32
    # all-gather anywhere (the parent's tail: the updated master whole),
    # and no collective of the optimizer's, whose update is local.
    n_leaves = len(jax.tree_util.tree_leaves(engine.params))
    assert engine._zero_leaves == (n_leaves, 0, n_leaves)
    gathers = [result for kind, result in hlo_collectives(text)
               if kind == "all-gather"]
    assert gathers and all(
        set(re.findall(r"\b([a-z]+\d+)\[", result)) == {"bf16"}
        for result in gathers)
    matrices_gathered = parameter_gathers(text, engine.params)
    assert matrices_gathered and all(
        "zero_gather" in name for _, _, _, name in matrices_gathered)
    assert optimizer_collectives(text) == []
    # ... and in the schedule every gather stands before the backward.
    entry = text[text.index("ENTRY "):].splitlines()
    backward = next(i for i, line in enumerate(entry)
                    if re.match(r"\s*%flash_bwd_fused", line))
    assert [line for line in entry[:backward] if " all-gather" in line]
    assert not [line for line in entry[backward:] if " all-gather" in line]


# ------------------------------------------------------- the LM head's loop

@pytest.mark.parametrize("sequences, width", [(16, 1024), (4, 1600)])
def test_eager_head_keeps_one_logits_sized_array_a_chunk(sequences, width,
                                                         chip):
    """``value_and_grad`` of the eager head alone at a chip's share of the
    two training cells (16 x 1,023 tokens at GPT-2 355M's width, 4 x 1,023
    at GPT-2 XL's; chunks of 2,048 against 50,257 ids; 10 s each). The chunk
    loop writes ONE array the size of a chunk's logits, the float32 logits
    themselves: the softmax gradient is formed inside both gradient matmuls'
    fusions. The parent's scatter-add of the label's -1 left a float32 ``dl``
    (``multiply_bitcast_fusion``), two ``reshape``s to a flat array and back
    and a ``convert_element_type`` of that size in the loop (33 ms of the
    one-chip cell's 303 ms step, PR 50), and 1,028 / 1,005 MB of temporaries
    where a chunk's logits and the bf16 table are 515 / 573."""
    from deepspeed_tpu.models.heads import chunked_tied_softmax_xent

    vocab, chunk = 50257, 2048

    def loss(x, table, labels):
        return chunked_tied_softmax_xent(x, table, labels, BF16,
                                         chunk=chunk, impl="eager")

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in (((sequences, 1023, width), BF16),
                                 ((vocab, width), F32),
                                 ((sequences, 1023), I32))]
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        *args).compile()
    comps = _computations(compiled.as_text())
    body, = _while_bodies(comps)
    either_way = ["[2048,50257]", "[50257,2048]", "[102926336]"]
    logits_sized = _arena_shaped(comps[body], either_way)
    assert [op for _, op in logits_sized] == ["fusion"], logits_sized
    assert _arena_shaped(comps[body], ["f32[2048,50257]"]) == logits_sized
    logits_and_table = chunk * vocab * 4 + vocab * width * 2
    assert compiled.memory_analysis().temp_size_in_bytes < \
        logits_and_table + (32 << 20)
