"""Fused scale+mask+softmax over attention scores — TPU-native equivalent of
reference csrc/transformer/softmax_kernels.cu (attn_softmax :9/:139,
launch_attn_softmax :290, softmax_backward_kernel_v2 :498).

Standalone op for the un-fused attention path and for tests; the flash
attention kernel (attention.py) subsumes it in the fused fast path. Backward
uses the classic dS = P * (dP - rowsum(dP * P)) with the saved probabilities,
matching the reference's backward_v2 contraction.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.ops import pallas_mode
from deepspeed_tpu.ops.transformer.kernels.attention import _mask_operand

NEG_INF = -1e30


def _softmax_kernel(s_ref, o_ref, *, scale, causal, mask_ref=None):
    s = s_ref[...].astype(jnp.float32) * scale            # [1, 1, bq, T]
    if mask_ref is not None:
        s = s + mask_ref[...].astype(jnp.float32)[:, None, None, :]
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        iq = pl.program_id(2)
        q_pos = iq * t_q + jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 1)
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


def _softmax_fwd(scores, mask, scale, causal):
    b, h, t_q, t_k = scores.shape
    block_q = t_q
    # Keep the [bq, T] tile within ~2 MB fp32 VMEM.
    while block_q > 8 and block_q * t_k * 4 > 2 * 1024 * 1024:
        block_q //= 2
    while t_q % block_q:
        block_q //= 2
    block_q = max(block_q, 1)
    grid = (b, h, t_q // block_q)
    spec = pl.BlockSpec((1, 1, block_q, t_k), lambda b_, h_, i: (b_, h_, i, 0))
    args = [scores]
    in_specs = [spec]
    if mask is not None:
        # [B, 1, T] with the batch dim squeezed, so the kernel sees (1, T).
        in_specs.append(pl.BlockSpec((None, 1, t_k),
                                     lambda b_, h_, i: (b_, 0, 0)))
        args.append(_mask_operand(mask))

        def kernel(s_ref, m_ref, o_ref):
            _softmax_kernel(s_ref, o_ref, scale=scale, causal=causal,
                            mask_ref=m_ref)
    else:
        kernel = functools.partial(_softmax_kernel, scale=scale, causal=causal)

    return pallas_mode.kernel_call(
        "attn_softmax", kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(scores.shape, scores.dtype),
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def attn_softmax(scores, mask, scale=1.0, causal=False):
    """softmax(scores * scale + mask [+ causal]) over the last axis.

    scores: [B, H, T_q, T_k]; mask: additive [B, T_k] or None.
    """
    return _softmax_fwd(scores, mask, scale, causal)


def _attn_softmax_fwd(scores, mask, scale, causal):
    p = _softmax_fwd(scores, mask, scale, causal)
    return p, p


def _attn_softmax_bwd(scale, causal, p, g):
    pf = p.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    ds = pf * (gf - jnp.sum(gf * pf, axis=-1, keepdims=True)) * scale
    return ds.astype(p.dtype), None


attn_softmax.defvjp(_attn_softmax_fwd, _attn_softmax_bwd)


def attn_softmax_reference(scores, mask=None, scale=1.0, causal=False):
    s = scores.astype(jnp.float32) * scale
    if mask is not None:
        s = s + mask[:, None, None, :].astype(jnp.float32)
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((t_q, t_k), dtype=bool))
        s = jnp.where(cm[None, None], s, NEG_INF)
    return jax.nn.softmax(s, axis=-1).astype(scores.dtype)
