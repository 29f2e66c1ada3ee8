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
    layer_norm, softmax)
from deepspeed_tpu.ops.transformer.kernels import (  # noqa: E402
    dropout as ds_dropout)


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, with the compilation cache off around the
    module (a described-device executable is written to the cache but
    cannot be read back without a chip)."""
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


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


def _paged_args(page_len, rows, int8=False):
    n_lp = T_KV // page_len
    arena = ((SLOTS * n_lp + 1, HEADS, page_len, HD), I8 if int8 else BF16)
    scale = ((SLOTS * n_lp + 1, HEADS, page_len), F32)
    return ([((SLOTS, HEADS, rows, HD), BF16), arena, arena]
            + ([scale, scale] if int8 else [])
            + [((SLOTS, n_lp), I32), ((SLOTS,), I32)])


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


SPARSE_QKV = ((2, 16, 4096, 64), BF16)   # bench.py's BERT-large sparse shape
LN_X = ((8, 512, 1024), BF16)
VEC = ((1024,), F32)

# name -> (function, [(shape, dtype), ...], environment)
CASES = {
    "flash_fwd": (_flash_fwd, [QKV] * 3, {}),
    "flash_fwd_bwd_auto": (_fwd_bwd(_flash_causal, 3), [QKV] * 3, {}),
    "flash_fwd_bwd_split": (_fwd_bwd(_flash_causal, 3), [QKV] * 3,
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
    r"decode_attn_q8|paged_decode|paged_decode_q8|prefill_attn|"
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
