"""Fused bias+GELU — TPU-native equivalent of reference
csrc/transformer/gelu_kernels.cu (gelu_kernel :38, fused_bias_gelu :98,
d_gelu backward :182, launchers :277-335).

One Pallas kernel computes gelu(x + bias) in a single HBM pass; the backward
regenerates the activation derivative from the saved pre-activation (the
reference does the same — it stores the *input* and recomputes tanh in
d_gelu_func). Uses the tanh approximation exactly as the reference does.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.ops import pallas_mode

_SQRT_2_OVER_PI = 0.7978845608028654


def _gelu_f32(z):
    return 0.5 * z * (1.0 + jnp.tanh(_SQRT_2_OVER_PI * (z + 0.044715 * z ** 3)))


def _d_gelu_f32(z):
    t = jnp.tanh(_SQRT_2_OVER_PI * (z + 0.044715 * z ** 3))
    dt = (1.0 - t * t) * _SQRT_2_OVER_PI * (1.0 + 3 * 0.044715 * z * z)
    return 0.5 * (1.0 + t) + 0.5 * z * dt


def _bias_gelu_kernel(x_ref, b_ref, o_ref):
    z = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = _gelu_f32(z).astype(o_ref.dtype)


def _bias_gelu_fwd(x, bias):
    hidden = x.shape[-1]
    x2 = x.reshape(-1, hidden)
    n = x2.shape[0]
    rows = max(8, min(n, (2 * 1024 * 1024) // max(1, hidden * 4)))
    while n % rows:
        rows //= 2
    o = pallas_mode.kernel_call(
        "bias_gelu", _bias_gelu_kernel,
        grid=(n // max(rows, 1),),
        in_specs=[pl.BlockSpec((max(rows, 1), hidden), lambda i: (i, 0)),
                  pl.BlockSpec((hidden,), lambda i: (0,))],
        out_specs=pl.BlockSpec((max(rows, 1), hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, hidden), x.dtype),
    )(x2, bias)
    return o.reshape(x.shape)


@jax.custom_vjp
def fused_bias_gelu(x, bias):
    """gelu(x + bias), tanh approximation (reference gelu_kernels.cu:38)."""
    return _bias_gelu_fwd(x, bias)


def _fused_bias_gelu_fwd(x, bias):
    return _bias_gelu_fwd(x, bias), (x, bias)


def _fused_bias_gelu_bwd(res, g):
    x, bias = res
    z = x.astype(jnp.float32) + bias.astype(jnp.float32)
    dz = g.astype(jnp.float32) * _d_gelu_f32(z)
    dx = dz.astype(x.dtype)
    reduce_axes = tuple(range(x.ndim - 1))
    dbias = jnp.sum(dz, axis=reduce_axes).astype(bias.dtype)
    return dx, dbias


fused_bias_gelu.defvjp(_fused_bias_gelu_fwd, _fused_bias_gelu_bwd)


def bias_gelu_reference(x, bias):
    z = x.astype(jnp.float32) + bias.astype(jnp.float32)
    return _gelu_f32(z).astype(x.dtype)
