"""One token of Kimi Delta Attention's recurrence (kernel ``kda_update``):
the decode scan's delta-rule update of a layer's matrix state, which moves
that state ONCE.

``models/kda.py`` ``step`` in plain ``jax.numpy`` is, a head,

    S' = exp(g) * S             s_k = S'^T k        s_q = S'^T q
    u  = beta (v - s_k)         o = s_q + (k . q) u         S = S' + k u^T

and XLA compiles it to a fusion that reads the state for the two sums and a
second that reads it again for the write: the sums down the key channels
must end before the rank-one write can start, and no 268 MB stays between
two fusions (three passes at 77% of the bandwidth: 51% of the region's
roofline, PERF.md, PR 42). Here a grid step is one row's unit of ``Hb``
heads: the unit's state ``[Hb, d_k, d_v]`` float32 comes into VMEM by block
spec, both sums run down the sublanes of the same tile, and the new state
goes back to where it came from (the state operand is aliased to the
output, so a scan's carry is updated where it lies). Everything in float32
on the VPU, the algebra as written above and nothing re-associated: a row
with ``g = 0`` and ``beta = 0`` leaves its state bit for bit (``S * 1 +
k * 0``).

The pool's layout stays (key channels on the sublanes, value channels on
the lanes), so ``exp(g)``, ``k`` and ``q`` are COLUMN vectors. They reach
the kernel as they lie, rows of one operand ``[B, H / Hb, 3 Hb, d_k]``
(kilobytes a row), and the unit's tile is transposed IN the kernel, once a
grid step: a head's column is then a static lane of it. (Transposed by XLA
outside, ``[B, H / Hb, d_k, 3 Hb]``, the kernel ran as fast alone, but the
layout it asked for walked back up the layer: the chip's compiler turned
the convolutions' tails and q, k, v batch-minor, and the ``kda/conv``
region doubled, 0.103 -> 0.190 s of a traced window: PERF.md, PR 43.)
``v`` and ``o`` are rows of ``[B, H / Hb, Hb, d_v]``; ``beta`` and
``k . q`` are scalars a head and ``fresh`` (the row starts from zeros
whatever its slot holds: a SELECT, the slot may hold NaN) a flag a row, on
scalar prefetch.

``Hb`` comes from shapes alone (``unit_heads``): the largest divisor of the
heads whose state blocks, in and out and double-buffered, fit the VMEM
budget the paged kernels use. Which shapes take the kernel at all
(``supported``): a float32 state whose ``d_v`` is whole lane tiles and
``d_k`` whole sublane tiles.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.analysis.annotations import hot_path
from deepspeed_tpu.ops import pallas_mode
from deepspeed_tpu.ops.transformer.kernels.attention import (
    kernel_sharding, on_shards)
from deepspeed_tpu.ops.transformer.kernels.decode_attention import (
    LANES, _PAGED_VMEM_BUDGET, _sublane)


def supported(shape, dtype):
    """Whether a state ``[.., H, d_k, d_v]`` of ``dtype`` takes the kernel:
    float32, ``d_v`` whole lane tiles, ``d_k`` whole sublane tiles."""
    return jnp.dtype(dtype) == jnp.float32 and len(shape) >= 3 \
        and shape[-1] % LANES == 0 and shape[-2] % _sublane(dtype) == 0


def unit_heads(shape, dtype):
    """``Hb``, the heads of a row that one unit (grid step) holds, for a
    state ``[.., H, d_k, d_v]`` (the engine reports a pool's as
    ``kda_update_unit_heads``): the largest divisor of ``H`` whose state
    blocks, in and out and each double-buffered, fit
    ``_PAGED_VMEM_BUDGET``; 0 for a state the plain form runs. From the
    shape and the dtype alone."""
    if not supported(shape, dtype):
        return 0
    h, d_k, d_v = shape[-3:]
    fit = max(1, _PAGED_VMEM_BUDGET // (4 * d_k * d_v * 4))
    return max(n for n in range(1, h + 1) if h % n == 0 and n <= fit)


def _kernel(fresh_ref, beta_ref, kq_ref, rows_ref, v_ref, state_ref, o_ref,
            out_ref, *, hb):
    b, first = pl.program_id(0), pl.program_id(1) * hb
    fresh = fresh_ref[b] != 0
    cols = rows_ref[0, 0].T                       # [3 Hb, d_k] -> [d_k, 3 Hb]
    for h in range(hb):
        decay, k, q = (cols[:, i * hb + h:i * hb + h + 1] for i in range(3))
        decayed = jnp.where(fresh, 0.0, state_ref[0, h]) * decay
        s_k = jnp.sum(decayed * k, axis=0, keepdims=True)
        s_q = jnp.sum(decayed * q, axis=0, keepdims=True)
        u = beta_ref[b, first + h] * (v_ref[0, 0, h:h + 1, :] - s_k)
        o_ref[0, 0, h:h + 1, :] = s_q + kq_ref[b, first + h] * u
        out_ref[0, h] = decayed + k * u


def _launch(fresh, beta, kq, rows, v, state, *, hb):
    from jax.experimental.pallas import tpu as pltpu

    b, h, d_k, d_v = state.shape

    def unit(*block):
        # row b's unit u of an operand [B, H / Hb, ..] or [B, H, ..]
        return pl.BlockSpec((1,) * (4 - len(block)) + block,
                            lambda b_, u, *_: (b_, u, 0, 0))

    o, state = pallas_mode.kernel_call(
        "kda_update", functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, h // hb),
            in_specs=[unit(3 * hb, d_k), unit(hb, d_v), unit(hb, d_k, d_v)],
            out_specs=[unit(hb, d_v), unit(hb, d_k, d_v)]),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # Operands count the three scalar-prefetch arguments.
        input_output_aliases={5: 1},
        cost_estimate=pl.CostEstimate(
            flops=8 * state.size, transcendentals=0,
            bytes_accessed=2 * state.size * state.dtype.itemsize),
    )(fresh, beta, kq, rows, v, state)
    return o, state


@hot_path
def kda_update(q, k, v, g, beta, state, fresh=None):
    """``kda.step`` of a ``supported`` state, IN PLACE in ``state`` (donate
    it, or carry it through a scan: it comes back as the same buffer).
    q, k, v, g ``[B, H, d]``, beta ``[B, H]``, state ``[B, H, d_k, d_v]``,
    all float32; ``fresh`` ``[B]`` bool, a row that starts from zeros
    whatever ``state`` holds of it (None: no row). Returns (o
    ``[B, H, d_v]``, the state after)."""
    b, h, d_k, d_v = state.shape
    assert supported(state.shape, state.dtype), (state.shape, state.dtype)
    fresh = jnp.zeros((b,), jnp.int32) if fresh is None \
        else fresh.astype(jnp.int32)

    def launch(fresh, beta, kq, decay, k, q, v, state):
        hb = unit_heads(state.shape, state.dtype)
        # a unit's rows: its heads' decays, then their k, then their q
        rows = jnp.concatenate([x.reshape(b, h // hb, hb, d_k)
                                for x in (decay, k, q)], axis=2)
        o, state = _launch(fresh, beta, kq, rows,
                           v.reshape(b, h // hb, hb, d_v), state, hb=hb)
        return o.reshape(b, h, d_v), state

    # Every operand WHOLE on every shard, rows and heads: the pool keeps a
    # slot's state replicated (``kv_pool.pool_shardings``), and a split here
    # would gather 268 MB a layer back.
    return on_shards(launch, kernel_sharding(b, h),
                     ("-",) + ("--",) * 7, ("--", "--"))(
        fresh, beta, jnp.sum(k * q, axis=-1), jnp.exp(g), k, q, v, state)
