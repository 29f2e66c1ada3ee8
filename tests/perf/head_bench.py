"""Chunked tied-decoder XE head microbenchmark — fwd+bwd time at GPT-2
shapes, vs the GEMM-bound ideal, across head implementations and chunk
sizes.

Feeds the component table in docs/PERF.md. The round-3 head computes
dx/dW eagerly in the forward chunk loop (3 logit-sized GEMMs per chunk,
models/heads.py); DS_TPU_XE_HEAD=remat selects the 4-GEMM autodiff
baseline. This bench times both on the same shapes (and a chunk-size
sweep for the eager path) so a headline regression can be attributed.
Timing uses the same scan-in-jit + scalar-fetch pattern as
attention_bench.py.

Usage: python tests/perf/head_bench.py [--tokens 8192] [--embd 1024]
       [--vocab 50257] [--chunks 2048,4096,8192]
"""

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import _platform

_platform.setup()

from deepspeed_tpu.models.heads import chunked_tied_softmax_xent

REPS = 10


def time_fn(fn, x, wte):
    eps = jnp.asarray(1e-7, x.dtype)

    def fwd_bwd(x, wte):
        def once(carry, _):
            x_, w_ = carry
            gx, gw = jax.grad(lambda a, b: fn(a, b).astype(jnp.float32),
                              argnums=(0, 1))(x_, w_)
            return (x_ + gx * eps, w_ + gw * eps), None

        (x, wte), _ = jax.lax.scan(once, (x, wte), None, length=REPS)
        return x.astype(jnp.float32).sum() + wte.astype(jnp.float32).sum()

    jitted = jax.jit(fwd_bwd)
    float(jitted(x, wte))  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.time()
        float(jitted(x, wte))
        times.append(time.time() - t0)
    return float(np.median(times)) / REPS


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--embd", type=int, default=1024)
    ap.add_argument("--vocab", type=int, default=50257)
    ap.add_argument("--chunks", default="2048,4096,8192")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    n, c, v = args.tokens, args.embd, args.vocab
    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(0)
    # The bench reshapes to the [B, T, C] form the real head takes.
    x = jnp.asarray(rng.randn(1, n, c) * 0.02, dtype)
    wte = jnp.asarray(rng.randn(v, c) * 0.02, dtype)
    labels = jnp.asarray(rng.randint(0, v, size=(1, n)), jnp.int32)

    peak = 197e12 if jax.default_backend() == "tpu" else 1e12
    gemm = 2 * n * c * v  # one logit-sized GEMM

    def run(impl, chunk):
        return time_fn(
            lambda x_, w_: chunked_tied_softmax_xent(
                x_, w_, labels, dtype, chunk=chunk, impl=impl),
            x, wte)

    chunks = [int(s) for s in args.chunks.split(",") if s.strip()]
    base = None
    for chunk in chunks:
        sec = run("eager", chunk)
        if base is None:
            base = sec
        ideal = 3 * gemm / peak
        print("head3  n{} c{} v{} chunk{} {}: {:.3f} ms  (3-GEMM ideal "
              "{:.3f} ms, {:.1f}% of ideal)".format(
                  n, c, v, chunk, dtype.name, sec * 1e3, ideal * 1e3,
                  ideal / sec * 100), flush=True)

    sec4 = run("remat", chunks[0])
    ideal4 = 4 * gemm / peak
    print("head4  remat chunk{}: {:.3f} ms  (4-GEMM ideal {:.3f} ms, "
          "{:.1f}% of ideal; eager/chunk{} speedup {:.2f}x)".format(
              chunks[0], sec4 * 1e3, ideal4 * 1e3, ideal4 / sec4 * 100,
              chunks[0], sec4 / base), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
