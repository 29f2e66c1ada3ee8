"""Dropout with counter-based RNG — TPU-native equivalent of reference
csrc/transformer/dropout_kernels.cu (dropout_kernel :5, launch_dropout :257).

The CUDA kernels store a byte mask per element so backward can replay it.
On TPU the RNG is counter-based (threefry / pltpu PRNG), so the mask is a
pure function of (seed, offset): backward regenerates it instead of storing
it — zero mask memory, same semantics. The fused bias(+residual) variants
mirror the reference's `dropout_kernel` overloads that add bias/residual in
the same pass.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import pallas_mode


def _mask_from_bits(bits, rate):
    # bits: uint32. Keep when uniform(0,1) >= rate  <=>  bits >= rate * 2^32.
    threshold = jnp.uint32(min(int(rate * 4294967296.0), 4294967295))
    return (bits >= threshold).astype(jnp.float32)


def _dropout_kernel(x_ref, seed_ref, o_ref, *, rate, n_cols, bias_ref=None,
                    res_ref=None):
    i = pl.program_id(0)
    # Per-block seed: fold the block index into the scalar seed so every
    # block draws an independent, reproducible stream.
    pltpu.prng_seed(seed_ref[0] + i)
    x = x_ref[...].astype(jnp.float32)
    if bias_ref is not None:
        x = x + bias_ref[...].astype(jnp.float32)
    bits = pltpu.prng_random_bits(x.shape)
    keep = _mask_from_bits(pltpu.bitcast(bits, jnp.uint32), rate)
    y = x * keep * (1.0 / (1.0 - rate))
    if res_ref is not None:
        y = y + res_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _dropout_mask_jnp(shape, seed, rate):
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
    return (jax.random.uniform(key, shape) >= rate).astype(jnp.float32)


def _dropout_fwd(x, seed, rate, bias, residual):
    hidden = x.shape[-1]
    x2 = x.reshape(-1, hidden)
    n = x2.shape[0]
    if pallas_mode.interpret():
        # Off-TPU: identical semantics via threefry (pltpu PRNG only lowers
        # on real TPUs; interpret mode has no prng_seed primitive).
        z = x2.astype(jnp.float32)
        if bias is not None:
            z = z + bias.astype(jnp.float32)
        keep = _dropout_mask_jnp((n, hidden), seed, rate)
        y = z * keep * (1.0 / (1.0 - rate))
        if residual is not None:
            y = y + residual.reshape(-1, hidden).astype(jnp.float32)
        return y.astype(x.dtype).reshape(x.shape)

    rows = max(8, min(n, (2 * 1024 * 1024) // max(1, hidden * 4)))
    while n % rows:
        rows //= 2
    rows = max(rows, 1)
    row_spec = pl.BlockSpec((rows, hidden), lambda i: (i, 0))
    args = [x2, jnp.asarray([seed], jnp.int32)]
    in_specs = [row_spec, pl.BlockSpec(memory_space=pltpu.SMEM)]
    if bias is not None and residual is not None:
        def kernel(x_ref, s_ref, b_ref, r_ref, o_ref):
            _dropout_kernel(x_ref, s_ref, o_ref, rate=rate, n_cols=hidden,
                            bias_ref=b_ref, res_ref=r_ref)
        args += [bias, residual.reshape(-1, hidden)]
        in_specs += [pl.BlockSpec((hidden,), lambda i: (0,)), row_spec]
    elif bias is not None:
        def kernel(x_ref, s_ref, b_ref, o_ref):
            _dropout_kernel(x_ref, s_ref, o_ref, rate=rate, n_cols=hidden,
                            bias_ref=b_ref)
        args.append(bias)
        in_specs.append(pl.BlockSpec((hidden,), lambda i: (0,)))
    elif residual is not None:
        def kernel(x_ref, s_ref, r_ref, o_ref):
            _dropout_kernel(x_ref, s_ref, o_ref, rate=rate, n_cols=hidden,
                            res_ref=r_ref)
        args.append(residual.reshape(-1, hidden))
        in_specs.append(row_spec)
    else:
        kernel = functools.partial(_dropout_kernel, rate=rate, n_cols=hidden)

    o = pallas_mode.kernel_call(
        "dropout_fwd", kernel,
        grid=(n // rows,),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((n, hidden), x.dtype),
    )(*args)
    return o.reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dropout(x, seed, rate, bias, residual):
    # seed is a (traced or concrete) int32 scalar — per-step seeds from the
    # flax dropout RNG flow through without retracing.
    return _dropout_fwd(x, seed, rate, bias, residual)


def _dropout_vjp_fwd(x, seed, rate, bias, residual):
    return _dropout_fwd(x, seed, rate, bias, residual), (x, seed, bias,
                                                         residual)


def _dropout_vjp_bwd(rate, res, g):
    x, seed, bias, residual = res
    hidden = x.shape[-1]
    n = x.size // hidden
    # Regenerate the identical mask from (seed, offset); matches what the
    # fwd kernel drew because both use the same counter stream.
    if pallas_mode.interpret():
        keep = _dropout_mask_jnp((n, hidden), seed, rate)
    else:
        keep = _regen_mask_tpu((n, hidden), seed, rate)
    dz = (g.reshape(-1, hidden).astype(jnp.float32) * keep
          * (1.0 / (1.0 - rate)))
    dx = dz.reshape(x.shape).astype(x.dtype)
    dbias = None if bias is None else jnp.sum(dz, axis=0).astype(bias.dtype)
    dres = None if residual is None else g.astype(residual.dtype)
    import numpy as _np
    dseed = _np.zeros((), dtype=jax.dtypes.float0)  # int arg: float0 cotangent
    return dx, dseed, dbias, dres


_dropout.defvjp(_dropout_vjp_fwd, _dropout_vjp_bwd)


def _mask_kernel(seed_ref, o_ref, *, rate):
    i = pl.program_id(0)
    pltpu.prng_seed(seed_ref[0] + i)
    bits = pltpu.prng_random_bits(o_ref.shape)
    o_ref[...] = _mask_from_bits(pltpu.bitcast(bits, jnp.uint32), rate)


def _regen_mask_tpu(shape, seed, rate):
    n, hidden = shape
    rows = max(8, min(n, (2 * 1024 * 1024) // max(1, hidden * 4)))
    while n % rows:
        rows //= 2
    rows = max(rows, 1)
    return pallas_mode.kernel_call(
        "dropout_mask", functools.partial(_mask_kernel, rate=rate),
        grid=(n // rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((rows, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, hidden), jnp.float32),
    )(jnp.asarray([seed], jnp.int32))


def dropout(x, rate, seed, deterministic=False):
    """Inverted dropout; mask reproducible from (seed)."""
    if deterministic or rate <= 0.0:
        return x
    return _dropout(x, jnp.asarray(seed, jnp.int32), float(rate), None, None)


def fused_bias_dropout_residual(x, bias, residual, rate, seed,
                                deterministic=False):
    """dropout(x + bias) + residual in one pass (reference
    dropout_kernels.cu bias/residual overloads) — the transformer layer's
    post-GEMM epilogue."""
    if deterministic or rate <= 0.0:
        y = x.astype(jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        if residual is not None:
            y = y + residual.astype(jnp.float32)
        return y.astype(x.dtype)
    return _dropout(x, jnp.asarray(seed, jnp.int32), float(rate), bias, residual)
