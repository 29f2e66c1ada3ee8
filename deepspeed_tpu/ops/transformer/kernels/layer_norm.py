"""Fused (bias + residual +) LayerNorm — TPU-native equivalent of the
reference's LN kernels (csrc/transformer/normalize_kernels.cu:
fused_bias_residual_layer_norm fwd at :16/:226, LayerNormBackward1/2 at
:607-1715 including the _fused_add residual variants).

Forward is one Pallas kernel: a single HBM read of x (+bias/+residual),
mean/var in fp32 on the VPU, one HBM write — the bandwidth profile the CUDA
kernels were written for. Backward uses the saved (mu, rstd): dx is a small
closed-form elementwise+row-reduction expression that XLA fuses into two
passes; dgamma/dbeta are column reductions (the reference's
LayerNormBackward1) which XLA maps to efficient tree reductions, so a
hand-written Pallas backward buys nothing on TPU.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.ops import pallas_mode


def _pick_block_rows(n_rows, hidden):
    # Budget ~2 MB of VMEM for the x block in fp32.
    rows = max(8, min(n_rows, (2 * 1024 * 1024) // max(1, hidden * 4)))
    while n_rows % rows:
        rows //= 2
    return max(rows, 1)


def _ln_fwd_kernel(x_ref, g_ref, b_ref, o_ref, mu_ref, rstd_ref, *, eps,
                   bias_ref=None, res_ref=None):
    x = x_ref[...].astype(jnp.float32)
    if bias_ref is not None:
        x = x + bias_ref[...].astype(jnp.float32)
    if res_ref is not None:
        x = x + res_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)
    mu_ref[...] = mu
    rstd_ref[...] = rstd


def _ln_fwd(x, gamma, beta, bias, residual, eps):
    orig_shape = x.shape
    hidden = orig_shape[-1]
    x2 = x.reshape(-1, hidden)
    n = x2.shape[0]
    rows = _pick_block_rows(n, hidden)
    grid = (n // rows,)

    row_spec = pl.BlockSpec((rows, hidden), lambda i: (i, 0))
    gb_spec = pl.BlockSpec((hidden,), lambda i: (0,))
    stat_spec = pl.BlockSpec((rows, 1), lambda i: (i, 0))

    args = [x2, gamma, beta]
    in_specs = [row_spec, gb_spec, gb_spec]
    kwargs = {"eps": eps}
    kernel = _ln_fwd_kernel
    if bias is not None and residual is not None:
        def kernel(x_ref, g_ref, b_ref, bias_r, res_r, o_ref, mu_ref, rstd_ref):
            _ln_fwd_kernel(x_ref, g_ref, b_ref, o_ref, mu_ref, rstd_ref,
                           eps=eps, bias_ref=bias_r, res_ref=res_r)
        args += [bias, residual.reshape(-1, hidden)]
        in_specs += [gb_spec, row_spec]
    elif bias is not None or residual is not None:
        extra = bias if bias is not None else residual.reshape(-1, hidden)
        is_bias = bias is not None

        def kernel(x_ref, g_ref, b_ref, e_ref, o_ref, mu_ref, rstd_ref):
            _ln_fwd_kernel(x_ref, g_ref, b_ref, o_ref, mu_ref, rstd_ref,
                           eps=eps,
                           bias_ref=e_ref if is_bias else None,
                           res_ref=None if is_bias else e_ref)
        args.append(extra)
        in_specs.append(gb_spec if is_bias else row_spec)
    else:
        kernel = functools.partial(_ln_fwd_kernel, eps=eps)

    o, mu, rstd = pallas_mode.kernel_call(
        "layer_norm_fwd", kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, hidden), x.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
    )(*args)
    return o.reshape(orig_shape), mu, rstd


def _ln_input(x, bias, residual):
    z = x.astype(jnp.float32)
    if bias is not None:
        z = z + bias.astype(jnp.float32)
    if residual is not None:
        z = z + residual.astype(jnp.float32)
    return z


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused_ln(x, gamma, beta, bias, residual, eps):
    o, _, _ = _ln_fwd(x, gamma, beta, bias, residual, eps)
    return o


def _fused_ln_vjp_fwd(x, gamma, beta, bias, residual, eps):
    o, mu, rstd = _ln_fwd(x, gamma, beta, bias, residual, eps)
    return o, (x, gamma, bias, residual, mu, rstd)


def _fused_ln_vjp_bwd(eps, res, g):
    x, gamma, bias, residual, mu, rstd = res
    hidden = x.shape[-1]
    g2 = g.reshape(-1, hidden).astype(jnp.float32)
    z = _ln_input(x, bias, residual).reshape(-1, hidden)
    xhat = (z - mu) * rstd
    gg = g2 * gamma.astype(jnp.float32)
    # dx = rstd * (gg - mean(gg) - xhat * mean(gg * xhat))
    m1 = jnp.mean(gg, axis=-1, keepdims=True)
    m2 = jnp.mean(gg * xhat, axis=-1, keepdims=True)
    dz = (rstd * (gg - m1 - xhat * m2))
    dgamma = jnp.sum(g2 * xhat, axis=0).astype(gamma.dtype)
    dbeta = jnp.sum(g2, axis=0).astype(gamma.dtype)
    dx = dz.reshape(x.shape).astype(x.dtype)
    dbias = None if bias is None else jnp.sum(dz, axis=0).astype(bias.dtype)
    dres = None if residual is None else dx.astype(residual.dtype)
    return dx, dgamma, dbeta, dbias, dres


_fused_ln.defvjp(_fused_ln_vjp_fwd, _fused_ln_vjp_bwd)


def fused_layer_norm(x, gamma, beta, eps=1e-12):
    """LayerNorm over the last axis (reference launch_bias_residual_layer_norm
    with null residual)."""
    return _fused_ln(x, gamma, beta, None, None, float(eps))


def fused_bias_residual_layer_norm(x, residual, gamma, beta, bias=None,
                                   eps=1e-12):
    """LN(x + bias + residual) in one kernel — the reference's
    `fused_bias_residual_layer_norm` (normalize_kernels.cu:226), the
    post-attention/post-FFN LN of the fused transformer layer."""
    return _fused_ln(x, gamma, beta, bias, residual, float(eps))


def layer_norm_reference(x, gamma, beta, eps=1e-12):
    z = x.astype(jnp.float32)
    mu = jnp.mean(z, axis=-1, keepdims=True)
    var = jnp.var(z, axis=-1, keepdims=True)
    y = (z - mu) * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32) \
        + beta.astype(jnp.float32)
    return y.astype(x.dtype)
