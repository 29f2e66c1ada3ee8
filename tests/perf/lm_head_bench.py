"""The LM head alone on the chip: ``value_and_grad`` of
``chunked_tied_softmax_xent`` (the eager head) at the two training cells'
shapes, one JSON line a (heads.py, shape): milliseconds a call by the host's
clock and the device time of the chunk loop's operations by the profiler's
names, a chunk.

    chiprun -- python tests/perf/lm_head_bench.py [--vocab V] [heads.py ...]

Every ``heads.py`` named (the tree's own by default) is loaded beside the
others and timed in the same process, so a parent's copy and a variant are
compared on one chip. PR 50's choice between the two forms of the softmax
gradient rests on it (PERF.md section 6).
"""

import argparse
import importlib.util
import json
import os
import re
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import _platform

_platform.setup()

from benchmark import trace_reduce  # noqa: E402

OWN = os.path.join(_platform.REPO, "deepspeed_tpu", "models", "heads.py")
V, T = 50257, 1023
# (sequences a chip, width): train-gpt2m-1chip; a chip of train-gpt2xl-zero-dp4.
SHAPES = ((16, 1024), (4, 1600))


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "heads_" + re.sub(r"\W", "_", os.path.abspath(path)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _device_ops(trace_dir):
    """The 14 largest operations by device SELF seconds, under the names of
    the ledger's ``breakdown``, and the seconds the chip was busy, by the
    benchmark's own reducer (which finds nothing in a CPU's trace)."""
    reduced = trace_reduce.reduce_trace(
        trace_reduce.load(trace_reduce.find_xplane(trace_dir)), top=14)
    if reduced is None:
        return [], 0.0
    return reduced["device_ops"], reduced["busy_s"]


def measure(path, b, c, vocab=V, calls=5):
    heads = _load(path)
    rng = np.random.RandomState(b * c)
    x = jnp.asarray(rng.randn(b, T, c), jnp.bfloat16)
    w = jnp.asarray(rng.randn(vocab, c) * 0.02, jnp.float32)
    labels = jnp.asarray(rng.randint(0, vocab, size=(b, T)), jnp.int32)

    @jax.jit
    def step(x, w):
        return jax.value_and_grad(
            lambda x, w: heads.chunked_tied_softmax_xent(
                x, w, labels, jnp.bfloat16, impl="eager"),
            argnums=(0, 1))(x, w)

    loss, _ = jax.block_until_ready(step(x, w))
    jax.block_until_ready(step(x, w))
    start = time.perf_counter()
    for _ in range(calls):
        out = step(x, w)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - start) / calls * 1e3
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                out = step(x, w)
            jax.block_until_ready(out)
        ops, busy = _device_ops(trace_dir)
    chunks = calls * -(-b * T // 2048)
    return {
        "heads": os.path.relpath(path), "sequences": b, "width": c,
        "device": jax.devices()[0].device_kind, "loss": float(loss),
        "ms_a_call": round(ms, 3),
        "device_ms_a_call": round(busy / calls * 1e3, 3),
        "ms_a_chunk": {n: round(s / chunks * 1e3, 4) for n, s in ops},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("heads", nargs="*", default=[OWN])
    ap.add_argument("--vocab", type=int, default=V,
                    help="a small one rehearses the script on the CPU")
    args = ap.parse_args()
    for b, c in SHAPES:
        for path in args.heads:
            print(json.dumps(measure(path, b, c, args.vocab)), flush=True)


if __name__ == "__main__":
    main()
