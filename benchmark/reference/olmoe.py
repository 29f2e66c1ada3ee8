"""OLMoE as published, in plain ``jax.numpy`` and float32.

Muennighoff et al. 2024, "OLMoE: Open Mixture-of-Experts Language Models", and
the ``allenai/OLMoE-1B-7B-0125-Instruct`` ``config.json`` (``model_type``
``olmoe``). A token table; ``num_hidden_layers`` identical pre-norm blocks;
a final RMSNorm; an output head that is its own matrix. No bias anywhere, no
``clip_qkv``. One block, for a residual stream ``x`` [T, C]::

    n1 = rms(x) * input_layernorm
    q  = rms(n1 @ q_proj) * q_norm        over the WHOLE projected width,
    k  = rms(n1 @ k_proj) * k_norm        before the split into heads
    v  = n1 @ v_proj
    q, k = rope(q), rope(k)               each head: x*cos + rotate_half(x)*sin,
                                          angle = position * theta**(-2i/d)
    h  = x + (causal softmax(q k^T / sqrt(d)) v) @ o_proj
    n2 = rms(h) * post_attention_layernorm
    w  = softmax(n2 @ gate) in float32 over ALL experts; the
         num_experts_per_tok largest are kept and (norm_topk_prob false)
         NOT renormalised
    out = h + sum over the kept experts e of
              w_e * (silu(n2 @ gate_proj_e) * (n2 @ up_proj_e)) @ down_proj_e

No kernels, no cache, no capacity, nothing dropped. Independent of
``deepspeed_tpu``: it is handed a tree under the PUBLISHED names (dense
kernels ``[in, out]``)::

    embed_tokens [V, C]    norm [C]    lm_head [C, V]
    layers: an iterable of {input_layernorm, q_proj, k_proj, v_proj, o_proj,
        q_norm, k_norm, post_attention_layernorm, gate [C, E],
        gate_proj [E, C, F], up_proj [E, C, F], down_proj [E, F, C]}

and the builder (``model_builders/olmoe.py``) maps the program's names onto
these, one layer at a time: ``layers`` may be a generator, and at the published
widths it has to be (a layer is 0.84 GB in bf16, the float32 copy of all eight
would be 14 GB beside an engine that holds 12).

ONE DEPARTURE from "for each token, loop over its experts", in ``_moe``: the
loop runs over the EXPERTS, every token computes every expert, and the sum
keeps an expert's term only for the tokens whose router kept it. It is the
same sum term by term; a per-token gather of expert matrices would read 25 MB
a (token, expert) pair, 13 TB for the driver's 8,192 tokens. ``moe_per_token``
is the literal form, and the tests hold the two together at a small size.

On a TPU a float32 matmul runs in lower precision unless asked otherwise, so
every matmul is traced under ``jax.default_matmul_precision("highest")``.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(weight)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope(x, theta):
    """x [T, H, D] at positions 0..T-1."""
    t, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # [T, 1, D]
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def _attention(n1, p, n_head, eps, theta):
    t, c = n1.shape
    q = _rms(n1 @ _f32(p["q_proj"]), p["q_norm"], eps)
    k = _rms(n1 @ _f32(p["k_proj"]), p["k_norm"], eps)
    v = n1 @ _f32(p["v_proj"])
    d = q.shape[-1] // n_head
    q = _rope(q.reshape(t, n_head, d), theta).transpose(1, 0, 2)
    k = _rope(k.reshape(t, n_head, d), theta).transpose(1, 0, 2)
    v = v.reshape(t, n_head, d).transpose(1, 0, 2)
    scores = q @ k.transpose(0, 2, 1) / math.sqrt(d)            # [H, T, T]
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v
    return out.transpose(1, 0, 2).reshape(t, n_head * d) @ _f32(p["o_proj"])


def _router(n2, p, top_k, norm_topk_prob):
    """(weights [T, E] with 0 for an expert that was not kept, the gap
    between the last weight kept and the first one cut [T])."""
    probs = jax.nn.softmax(n2 @ _f32(p["gate"]), axis=-1)
    top, idx = jax.lax.top_k(probs, top_k + 1)
    kept = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], idx[:, :top_k]].set(
            top[:, :top_k])
    if norm_topk_prob:
        kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return kept, top[:, top_k - 1] - top[:, top_k]


def _expert(x, gate_proj, up_proj, down_proj):
    return (jax.nn.silu(x @ _f32(gate_proj)) * (x @ _f32(up_proj))) \
        @ _f32(down_proj)


def _moe(n2, p, kept):
    def one_expert(total, e):
        term = _expert(n2, p["gate_proj"][e], p["up_proj"][e],
                       p["down_proj"][e])
        return total + kept[:, e][:, None] * term, None

    return jax.lax.scan(one_expert, jnp.zeros_like(n2),
                        jnp.arange(kept.shape[1]))[0]


def moe_per_token(n2, p, top_k, norm_topk_prob=False):
    """The literal form: for each token, a loop over the experts its router
    kept. For small sizes (the tests)."""
    with jax.default_matmul_precision("highest"):
        kept, _ = _router(_f32(n2), p, top_k, norm_topk_prob)
        rows = []
        for t in range(n2.shape[0]):
            total = jnp.zeros((n2.shape[1],), jnp.float32)
            for e in np.flatnonzero(np.asarray(kept[t])):
                total = total + kept[t, e] * _expert(
                    _f32(n2[t]), p["gate_proj"][e], p["up_proj"][e],
                    p["down_proj"][e])
            rows.append(total)
        return jnp.stack(rows)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "top_k", "eps", "theta", "norm_topk_prob"))
def block(x, p, n_head, top_k, eps, theta, norm_topk_prob=False):
    """One layer on one sequence: x [T, C] float32 -> (x, router gap [T])."""
    with jax.default_matmul_precision("highest"):
        h = x + _attention(_rms(x, p["input_layernorm"], eps), p, n_head,
                           eps, theta)
        n2 = _rms(h, p["post_attention_layernorm"], eps)
        kept, gap = _router(n2, p, top_k, norm_topk_prob)
        return h + _moe(n2, p, kept), gap


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ _f32(lm_head)


def logits(params, input_ids, n_head, top_k, eps, theta,
           norm_topk_prob=False, with_gaps=False):
    """Next-token logits ``[B, T, V]`` float32, as a HOST array (at the
    published widths 0.4 GB a sequence), one sequence at a time through one
    layer at a time. ``with_gaps`` also returns ``[B, T]``: the smallest gap,
    over the layers, between the last router weight a token kept and the
    first it cut (where that is under a program's rounding, the program may
    keep the other expert and both are right)."""
    ids = np.asarray(input_ids)
    xs = [_f32(jnp.asarray(params["embed_tokens"])[row]) for row in ids]
    gaps = [jnp.full((ids.shape[1],), jnp.inf) for _ in xs]
    for layer in params["layers"]:
        for b, x in enumerate(xs):
            xs[b], gap = block(x, layer, n_head, top_k, eps, theta,
                               norm_topk_prob)
            gaps[b] = jnp.minimum(gaps[b], gap)
    out = np.stack([np.asarray(_head(x, params["norm"], params["lm_head"],
                                     eps)) for x in xs])
    return (out, np.stack([np.asarray(g) for g in gaps])) if with_gaps \
        else out
