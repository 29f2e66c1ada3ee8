"""Jamba (``model_type`` ``jamba``; AI21-Jamba2-3B) in plain ``jax.numpy`` and
float32, from the published ``config.json`` of ``ai21labs/AI21-Jamba2-3B``,
the Mamba paper (Gu & Dao 2023: the selective scan) and the family's one
addition to it, three RMSNorms inside the mixer.

A token table (tied to the output head); ``num_hidden_layers`` pre-norm layers
whose mixer is attention where ``i % attn_layer_period == attn_layer_offset``
and a Mamba mixer elsewhere; after every mixer a dense gated feed-forward
(``num_experts`` 1: the family builds a plain MLP wherever a layer's expert
count is 1); a final RMSNorm. No bias but the convolution's and the step's.
For a residual stream ``x`` [T, C]::

    x = embed[ids]
    each layer:
      x = x + mixer(rms(x) * input_layernorm)
      x = x + down(silu(gate h) * up h),  h = rms(x) * pre_ff_layernorm
    logits = (rms(x) * final_layernorm) @ embed.T

    attention: q (H heads), k, v (Hkv heads) of D from three projections, NO
      positions of any kind (no rotary, no bias), no QK norm;
      scores = q . k / sqrt(D), causal softmax; query head j reads key/value
      head j // (H / Hkv) (Hkv = 1: every head reads the one); o_proj.
    mamba: x_ | z = split(h @ in_proj, 2)                       W = expand * C
      x_ = silu(causal depthwise conv1d(x_, width K) + conv_bias)   only x_
      d | B | C = split(x_ @ x_proj, [R, R + N])
      d, B, C = rms(d) * dt_layernorm, rms(B) * b_layernorm, rms(C) * c_layernorm
      dt = softplus(d @ dt_proj + dt_bias)        [T, W]: a step a channel
      A = -exp(A_log)                             [W, N]
      S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
      y_t[c] = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]
      out = (y * silu(z)) @ out_proj              no norm after the gate

No kernels, no cache, no chunking: the recurrence is a token-by-token
``lax.scan`` from a zero state, attention a full masked softmax. Independent
of ``deepspeed_tpu``: it is handed a tree under the names above (dense
kernels ``[in, out]``, the convolution ``[K, W]`` with tap ``K - 1`` on the
current token, ``A_log`` ``[W, N]``), ``layers`` an iterable that may be a
generator (at the published widths a layer is cast to float32 one at a time).

DEPARTURE 1 (the only one of arithmetic, and it is the configuration's
``assumed``, not a change): the order of the layer kinds is the family's rule
above, which the published keys state but the catalog does not spell out.

DEPARTURE 2, of memory and not of arithmetic: attention is computed a group of
query heads at a time and the logits a block of the table's rows at a time,
a sequence at a time (the same products and sums; whole, the float32 scores
of 20 heads over 2,944 positions are 0.7 GB and a sequence's logits 0.77 GB,
beside an engine that holds half the chip).

WHAT IT SHOWS BESIDE THE LOGITS. ``logits(.., watch=f)`` hands ``f`` what a
comparison on IDENTICAL inputs needs, a layer and a sequence at a time
(``f(layer, sequence, seen)``): the normed input of the mixer and what the
mixer adds to the stream (``mix_in``, ``mix_out`` [T, C]), the stream the
feed-forward is handed and what it adds (``ff_in``, ``ff_out`` [T, C]: the
stream ITSELF, not its norm); for a Mamba layer
also the stream before the convolution (``x_in`` [T, W]: its last ``K - 1``
rows are what a cache keeps), the recurrence's inputs (``x``, ``dt`` [T, W],
``B``, ``C`` [T, N], ``A`` [W, N]) and the state after the last token
(``state`` [W, N]).

On a TPU a float32 matmul runs in lower precision unless asked otherwise, so
everything is traced under ``jax.default_matmul_precision("highest")``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(weight)


_HEAD_GROUPS = 4


def _attention(h, p, n_head, n_kv):
    t = h.shape[0]
    q, k, v = (h @ _f32(p[name]) for name in ("q_proj", "k_proj", "v_proj"))
    d = q.shape[-1] // n_head
    rep = n_head // n_kv
    causal = jnp.tril(jnp.ones((t, t), bool))
    # query head j beside the stored head it reads, j // rep
    q = q.reshape(t, n_head, d).transpose(1, 0, 2)              # [H, T, D]
    k = jnp.repeat(k.reshape(t, n_kv, d).transpose(1, 0, 2), rep, axis=0)
    v = jnp.repeat(v.reshape(t, n_kv, d).transpose(1, 0, 2), rep, axis=0)

    def group(qkv):
        # a group of query heads at a time (DEPARTURE 2)
        q_g, k_g, v_g = qkv
        scores = jnp.einsum("htd,hsd->hts", q_g, k_g) / np.sqrt(d)
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("hts,hsd->htd", jax.nn.softmax(scores, axis=-1),
                          v_g)

    groups = _HEAD_GROUPS if n_head % _HEAD_GROUPS == 0 else 1
    out = jax.lax.map(group, tuple(
        a.reshape(groups, n_head // groups, t, d) for a in (q, k, v)))
    return out.reshape(n_head, t, d).transpose(1, 0, 2).reshape(
        t, n_head * d) @ _f32(p["o_proj"])


def mamba(h, p, d_state, dt_rank, eps, seen=None):
    """The Mamba mixer on one sequence ``h`` [T, C] from a zero state; a
    dict ``seen`` is given the recurrence's inputs and the state after the
    last token (module docstring)."""
    t = h.shape[0]
    k = p["conv_w"].shape[0]
    x_in, z = jnp.split(h @ _f32(p["in_proj"]), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, x_in.shape[1])), x_in])
    x = jax.nn.silu(_f32(p["conv_b"]) + sum(
        _f32(p["conv_w"])[j] * padded[j:j + t] for j in range(k)))
    d, bmat, cmat = jnp.split(x @ _f32(p["x_proj"]),
                              [dt_rank, dt_rank + d_state], axis=-1)
    d = _rms(d, p["dt_layernorm"], eps)
    bmat = _rms(bmat, p["b_layernorm"], eps)
    cmat = _rms(cmat, p["c_layernorm"], eps)
    dt = jax.nn.softplus(d @ _f32(p["dt_proj"]) + _f32(p["dt_bias"]))
    a = -jnp.exp(_f32(p["A_log"]))                              # [W, N]

    def token(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, state @ c_t                               # [W]

    state, y = jax.lax.scan(token, jnp.zeros_like(a), (x, bmat, cmat, dt))
    if seen is not None:
        seen.update(x_in=x_in, x=x, dt=dt, B=bmat, C=cmat, A=a, state=state)
    return ((y + _f32(p["D"]) * x) * jax.nn.silu(z)) @ _f32(p["out_proj"])


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


@functools.partial(jax.jit, static_argnames=(
    "kind", "n_head", "n_kv", "d_state", "dt_rank", "eps"))
def block(x, p, kind, n_head, n_kv, d_state, dt_rank, eps):
    """One layer on one sequence: x [T, C] float32 -> (x, what ``watch`` is
    shown)."""
    with jax.default_matmul_precision("highest"):
        seen = {}
        h = _rms(x, p["input_layernorm"], eps)
        mix = mamba(h, p, d_state, dt_rank, eps, seen=seen) \
            if kind == "mamba" else _attention(h, p, n_head, n_kv)
        seen.update(mix_in=h, mix_out=mix)
        x = x + mix
        h = _rms(x, p["pre_ff_layernorm"], eps)
        ff = _gated(h, p["gate_proj"], p["up_proj"], p["down_proj"])
        seen.update(ff_in=x, ff_out=ff)
        return x + ff, seen


_HEAD_BLOCKS = 8


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, embed, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, norm, eps)
        blocks = _HEAD_BLOCKS if embed.shape[0] % _HEAD_BLOCKS == 0 else 1
        # a block of the table's rows at a time (DEPARTURE 2)
        out = jax.lax.map(lambda rows: h @ _f32(rows).T,
                          embed.reshape(blocks, -1, embed.shape[1]))
        return out.transpose(1, 0, 2).reshape(h.shape[0], embed.shape[0])


def layer_kinds(n_layer, period, offset):
    """The family's rule for the order of the layer kinds."""
    return tuple("attention" if i % period == offset else "mamba"
                 for i in range(n_layer))


def logits(params, input_ids, hyper, watch=None):
    """Next-token logits ``[B, T, V]`` float32, as a HOST array (at the
    cell's sizes 0.77 GB a sequence), a layer at a time and in it a sequence
    at a time. ``hyper``: ``layer_types`` (``layer_kinds``'s), ``n_head``,
    ``n_kv``, ``d_state``, ``dt_rank``, ``eps``. ``watch``: module
    docstring."""
    ids = np.asarray(input_ids)
    embed = jnp.asarray(params["embed_tokens"])
    xs = [_f32(embed[row]) for row in ids]
    for i, (kind, layer) in enumerate(zip(hyper["layer_types"],
                                          params["layers"])):
        for b, x in enumerate(xs):
            xs[b], seen = block(x, layer, kind, hyper["n_head"],
                                hyper["n_kv"], hyper["d_state"],
                                hyper["dt_rank"], hyper["eps"])
            if watch is not None:
                watch(i, b, seen)
            del seen
    return np.stack([np.asarray(_head(x, params["final_layernorm"], embed,
                                      hyper["eps"])) for x in xs])
