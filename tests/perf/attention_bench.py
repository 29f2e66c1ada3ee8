"""Flash-attention kernel microbenchmark — per-layer fwd+bwd time at GPT-2
shapes, vs the dense-XLA path and the MXU-ideal bound.

Feeds the component table in docs/PERF.md (the TPU analogue of the
reference's csrc/transformer timer sweep). Timing scans REPS steps inside
one jit and fetches a scalar, so dispatch is amortized and the fetch waits
for all of them.

Usage: python tests/perf/attention_bench.py [--seq 1024] [--batch 8]
       [--dense] [--blocks 1024,1024]
"""

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import _platform

_platform.setup()

from deepspeed_tpu.ops.transformer.kernels.attention import (
    flash_attention, mha_reference)

REPS = 20


def time_fn(fn, *args):
    """Median of 3 timed runs of a jitted REPS-step scan over fn."""
    eps = jnp.asarray(1e-7, args[0].dtype)

    def fwd_bwd(q, k, v):
        def once(carry, _):
            q_, k_, v_ = carry
            g = jax.grad(
                lambda a, b, c: fn(a, b, c).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q_, k_, v_)
            return (q_ + g[0] * eps, k_ + g[1] * eps, v_ + g[2] * eps), None

        (q, k, v), _ = jax.lax.scan(once, (q, k, v), None, length=REPS)
        return q.astype(jnp.float32).sum()

    jitted = jax.jit(fwd_bwd)
    float(jitted(*args))  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.time()
        float(jitted(*args))
        times.append(time.time() - t0)
    return float(np.median(times)) / REPS


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--dense", action="store_true",
                    help="also time the dense XLA reference path")
    ap.add_argument("--blocks", default=None,
                    help="block_q,block_k (default: autotuner)")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--bwd", default=None, choices=["auto", "fused", "split"],
                    help="flash backward path (sets DS_TPU_FLASH_BWD)")
    args = ap.parse_args()
    if args.bwd:
        os.environ["DS_TPU_FLASH_BWD"] = args.bwd

    b, h, t, d = args.batch, args.heads, args.seq, args.dim
    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, t, d), dtype)
    k = jnp.asarray(rng.randn(b, h, t, d), dtype)
    v = jnp.asarray(rng.randn(b, h, t, d), dtype)

    bq = bk = None
    if args.blocks:
        bq, bk = (int(x) for x in args.blocks.split(","))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)

    sec = time_fn(flash, q, k, v)
    # Ideal: 4 score-sized matmuls (s, pv fwd; dp, {ds k / ds q / p dv} ~ 5
    # total bwd+fwd counted as in PERF.md) — use the same accounting as the
    # component table: causal fwd+bwd attention matmul FLOPs / peak.
    flops = 3 * (2 * 2 * t * t * d) / 2 * b * h  # fwd + 2x bwd, causal half
    peak = 197e12 if jax.default_backend() == "tpu" else 1e12
    print("flash  b{} h{} t{} d{} {}: {:.3f} ms/iter  ({:.3f} ms/layer-eq, "
          "ideal {:.3f} ms, {:.1f}% of MXU-ideal)".format(
              b, h, t, d, dtype.name, sec * 1e3, sec * 1e3,
              flops / peak * 1e3, flops / peak / sec * 100))

    if args.dense:
        def dense(q, k, v):
            return mha_reference(q, k, v, causal=True)
        sec_d = time_fn(dense, q, k, v)
        print("dense  same shapes: {:.3f} ms/iter  (flash speedup {:.2f}x)"
              .format(sec_d * 1e3, sec_d / sec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
