"""The Mamba-1 selective scan alone (``deepspeed_tpu/models/mamba1.py``): the
prompt forms at the lane's shape, ``[1, 128]`` tokens of a ``[16, 5120]``
state, and the one-token update at the cell's 128 slots; the baseline a
kernel for either is read against (PR 48's readings on a v5e: root PERF.md,
section 6).

Usage: python tests/perf/selective_scan_bench.py     (one JSON line)

- ``sequential``: ``mamba1.step`` a token at a time in a ``lax.scan``, what
  ``mamba1.scan`` is; also unrolled 4 and 16 times.
- ``associative``: ``lax.associative_scan`` over ``(exp(dt A), dt B x)``
  pairs, ``[128, 16, 5120]`` float32 of each.
- PR 48 also read blocks of 8 / 16 / 32 / 64 tokens side by side, their
  starts carried over and added by one parallel pass (426 / 408 / 404 / 407
  us against 395 sequential: the pass takes a second ``exp`` an element);
  that form was not kept and is not here.
- ``one_token``: 16 iterations of ``mamba1.step`` on a donated
  ``[128, 16, 5120]`` state in one program, a call; beside it the time its
  bytes take at 819 GB/s.
"""

import json
import time

import jax
import jax.numpy as jnp

import _platform

_platform.setup()

from deepspeed_tpu.models import mamba1

S, N, W, SLOTS, ITERATIONS = 128, 16, 5120, 128, 16


def inputs(key, lead):
    ks = jax.random.split(key, 5)
    step = jnp.exp(jax.random.uniform(ks[1], lead + (W,), jnp.float32,
                                      jnp.log(0.001), jnp.log(0.1)))
    return (jax.random.normal(ks[0], lead + (W,)), step,
            jax.random.normal(ks[2], lead + (N,)),
            jax.random.normal(ks[3], lead + (N,)))


def sequential(a, state, x, dt, bmat, cmat, unroll=1):
    def token(state, inputs):
        y, state = mamba1.step(inputs[0], inputs[1], a, inputs[2], inputs[3],
                               state)
        return state, y

    state, y = jax.lax.scan(token, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bmat, cmat)), unroll=unroll)
    return jnp.moveaxis(y, 0, 1), state


def associative(a, state, x, dt, bmat, cmat):
    decay = jnp.exp(dt[:, :, None, :] * a)                   # [B, S, N, W]
    fed = bmat[..., None] * (dt * x)[:, :, None, :]
    decay, fed = jax.lax.associative_scan(
        lambda l, r: (l[0] * r[0], r[0] * l[1] + r[1]), (decay, fed), axis=1)
    states = decay * state[:, None] + fed
    return jnp.sum(states * cmat[..., None], axis=2), states[:, -1]


def timed(f, *args, n=30):
    f = jax.jit(f)
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e6


def main():
    key = jax.random.PRNGKey(0)
    a = -jnp.arange(1, N + 1, dtype=jnp.float32)[:, None] \
        * jax.random.uniform(key, (N, W), jnp.float32, 0.5, 1.5)
    lane = (a, jax.random.normal(key, (1, N, W))) + inputs(key, (1, S))
    out = {"device": str(jax.devices()[0].device_kind),
           "sequential_us": timed(sequential, *lane),
           "associative_us": timed(associative, *lane)}
    for unroll in (4, 16):
        out["sequential_unroll{}_us".format(unroll)] = timed(
            lambda *v: sequential(*v, unroll=unroll), *lane)

    def decode(state, x, dt, bmat, cmat):
        def iteration(state, inputs):
            y, state = mamba1.step(inputs[0], inputs[1], a, inputs[2],
                                   inputs[3], state)
            return state, y
        return jax.lax.scan(iteration, state, (x, dt, bmat, cmat))

    step = jax.jit(decode, donate_argnums=(0,))
    tokens = inputs(key, (ITERATIONS, SLOTS))
    state, _ = step(jax.random.normal(key, (SLOTS, N, W)), *tokens)
    jax.block_until_ready(state)
    t = time.perf_counter()
    for _ in range(20):
        state, _ = step(state, *tokens)
    jax.block_until_ready(state)
    out["one_token_us"] = (time.perf_counter() - t) / 20 / ITERATIONS * 1e6
    out["one_token_bytes_us"] = (2 * SLOTS * N * W * 4
                                 + SLOTS * (3 * W + 2 * N) * 4) / 819e9 * 1e6
    print(json.dumps(out))


if __name__ == "__main__":
    main()
