"""GPT-2 as published, in plain ``jax.numpy`` and float32.

Radford et al. 2019 / the ``openai-community/gpt2*`` ``config.json``: learned
token and position tables, pre-LayerNorm blocks (LayerNorm eps 1e-5, fused
QKV projection, causal softmax attention scaled by 1/sqrt(head size), output
projection; LayerNorm, 4x MLP with the tanh GELU ``gelu_new``), a final
LayerNorm and the token table reused as the output head. No kernels, no
cache, no batching tricks, no dropout. Independent of
``deepspeed_tpu/models/gpt2.py``: it shares only the names of the parameter
tree it is handed (``wte``, ``wpe``, ``h_<i>/{ln_1,attn/{c_attn,c_proj},
ln_2,mlp/{c_fc,c_proj}}``, ``ln_f``; dense kernels are ``[in, out]``).

On a TPU a float32 matmul runs in lower precision unless asked otherwise, so
every matmul is traced under ``jax.default_matmul_precision("highest")``.
"""

import functools
import math

import jax
import jax.numpy as jnp

LAYER_NORM_EPS = 1e-5


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LAYER_NORM_EPS) * _f32(p["scale"]) \
        + _f32(p["bias"])


def _dense(x, p):
    return x @ _f32(p["kernel"]) + _f32(p["bias"])


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, p, n_head):
    b, t, c = x.shape
    d = c // n_head
    q, k, v = jnp.split(_dense(x, p["c_attn"]), 3, axis=-1)

    def heads(a):
        return a.reshape(b, t, n_head, d).transpose(0, 2, 1, 3)

    scores = heads(q) @ heads(k).transpose(0, 1, 3, 2) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ heads(v)
    return _dense(out.transpose(0, 2, 1, 3).reshape(b, t, c), p["c_proj"])


# Three small programs run in a Python loop, not one program of every layer:
# the mathematics is the same, and a compiled 24- or 48-layer float32 program
# is a 30-40 MB entry in a compile cache that the chip tool caps at 192 MiB
# (it evicted the step programs the cells are there to measure).

@jax.jit
def _embed(wte, wpe, input_ids):
    return _f32(wte)[input_ids] + _f32(wpe)[:input_ids.shape[1]][None]


@functools.partial(jax.jit, static_argnames=("n_head",))
def _block(x, p, n_head):
    with jax.default_matmul_precision("highest"):
        x = x + _attention(_layer_norm(x, p["ln_1"]), p["attn"], n_head)
        h = _dense(_layer_norm(x, p["ln_2"]), p["mlp"]["c_fc"])
        return x + _dense(_gelu_new(h), p["mlp"]["c_proj"])


@jax.jit
def _head(x, ln_f, wte):
    with jax.default_matmul_precision("highest"):
        return _layer_norm(x, ln_f) @ _f32(wte).T


@jax.jit
def _sequence_loss_sum(x, ln_f, wte, ids):
    """Summed next-token cross-entropy of one sequence ``x`` = ``[T, C]``."""
    lg = _head(x[:-1], ln_f, wte)
    gold = jnp.take_along_axis(lg, ids[1:, None], axis=1)[:, 0]
    return jnp.sum(jax.scipy.special.logsumexp(lg, axis=-1) - gold)


def _blocks(params, input_ids, n_head):
    """The residual stream ``[B, T, C]`` after the last block, in float32."""
    x = _embed(params["wte"], params["wpe"], input_ids)
    n_layer = sum(1 for name in params if name.startswith("h_"))
    for i in range(n_layer):
        x = _block(x, params["h_{}".format(i)], n_head)
    return x


def logits(params, input_ids, n_head):
    """Next-token logits ``[B, T, V]`` in float32."""
    return _head(_blocks(params, input_ids, n_head), params["ln_f"],
                 params["wte"])


def loss(params, input_ids, n_head):
    """Mean next-token cross-entropy of ``input_ids`` against itself
    shifted by one, one sequence at a time so that the ``[T, V]`` logits and
    the ``[heads, T, T]`` scores of a single sequence are the most that is
    ever held."""
    total = 0.0
    for row in range(input_ids.shape[0]):
        ids = input_ids[row]
        x = _blocks(params, ids[None], n_head)[0]
        total = total + _sequence_loss_sum(x, params["ln_f"], params["wte"],
                                           ids)
    return total / (input_ids.shape[0] * (input_ids.shape[1] - 1))
