"""Flash-attention kernel microbenchmark — per-layer fwd+bwd time at GPT-2
shapes, vs the dense-XLA path and the MXU-ideal bound.

The TPU analogue of the reference's csrc/transformer timer sweep; PR 45's
readings of it are in the root PERF.md, section 6. Timing scans REPS steps inside
one jit and fetches a scalar, so dispatch is amortized and the fetch waits
for all of them.

Usage: python tests/perf/attention_bench.py [--seq 1024] [--batch 8]
       [--dense] [--blocks 1024,1024]
       python tests/perf/attention_bench.py --subtile 0,128,256,512
           (the kernels ALONE, by the device trace, at the training cells'
           shapes: one line a shape and sub-tile side; 0 = the block is its
           own tile, the kernels before the walk)
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import _platform

_platform.setup()

from deepspeed_tpu.ops.transformer.kernels import attention
from deepspeed_tpu.ops.transformer.kernels.attention import (
    flash_attention, mha_reference)

REPS = 20
# The training cells' calls (`train-gpt2m-1chip`, a chip of
# `train-gpt2xl-zero-dp4`) and BERT-large's: (b, h, t, d), causal.
CELL_SHAPES = (((16, 16, 1024, 64), True), ((4, 25, 1024, 64), True),
               ((8, 16, 512, 64), False))
KERNELS = ("flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv")


def kernel_us(fn, args, reps=5):
    """{kernel name: (mean device microseconds a call, calls)} of the flash
    kernels in ``reps`` runs of jitted ``fn``, read from the profiler's
    trace of the first TPU's ``XLA Ops`` line: the kernel alone, no host,
    no neighbouring XLA op."""
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*args))
    out_dir = tempfile.mkdtemp(prefix="attention_bench_")
    with jax.profiler.trace(out_dir):
        for _ in range(reps):
            out = jitted(*args)
        jax.block_until_ready(out)
    times = {}
    for path in glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                          recursive=True):
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    head = ev.name.split("=")[0]
                    for k in KERNELS:
                        if k in head:
                            times.setdefault(k, []).append(ev.duration_ns)
    return {k: (float(np.mean(v)) / 1e3, len(v)) for k, v in times.items()}


def subtile_table(sides, dtype):
    """Time the forward and the fused backward alone for each sub-tile
    side at each of CELL_SHAPES, head-major and (where the shape packs)
    in the projection's own layout, the fused tile-arranged operand
    ``CausalSelfAttention`` hands the kernels; one JSON line each on
    stdout."""
    rng = np.random.RandomState(0)
    for (b, h, t, d), causal in CELL_SHAPES:
        q, k, v = (jnp.asarray(rng.randn(b, h, t, d), dtype)
                   for _ in range(3))
        mask = None if causal else jnp.zeros((b, t), jnp.float32)
        qkv = attention.tile_qkv(jnp.concatenate(
            [x.transpose(0, 2, 1, 3).reshape(b, t, h * d)
             for x in (q, k, v)], axis=-1), h, d)

        for side in sides if causal else sides[:1]:
            # 0: a side no block reaches, so every block is its own tile.
            attention._SUBTILE_SIDE = side or 1 << 30

            def fwd_bwd(*ops):  # a function a side: jit caches by it
                out, vjp = jax.vjp(lambda *ops: flash_attention(
                    *ops, mask=mask, causal=causal, heads=h, head_dim=d),
                    *ops)
                return out, vjp(out)

            for layout, ops in (("head_major", (q, k, v)),
                                ("packed", (qkv,))):
                us = kernel_us(fwd_bwd, ops)
                walk = attention.last_walk()
                print(json.dumps({
                    "shape": [b, h, t, d], "causal": causal,
                    "layout": layout, "lane_pack": walk["lane_pack"],
                    "dtype": jnp.dtype(dtype).name,
                    "subtile": walk["subtile"],
                    "tiles_visited_share": walk["tiles_visited_share"],
                    "us_a_head": {k: round(us[k][0] / (b * h), 3)
                                  for k in sorted(us)},
                    "us_a_call": {k: round(us[k][0], 1) for k in sorted(us)},
                    "device": jax.devices()[0].device_kind}), flush=True)


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
    ap.add_argument("--subtile", default=None,
                    help="comma list of sub-tile sides to time the kernels "
                         "alone at (0: the block is its own tile)")
    args = ap.parse_args()
    if args.subtile:
        subtile_table([int(x) for x in args.subtile.split(",")],
                      jnp.dtype(args.dtype))
        return 0
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
