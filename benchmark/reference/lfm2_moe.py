"""LFM2 with experts (``model_type`` ``lfm2_moe``: LFM2-8B-A1B) in plain
``jax.numpy`` and float32, from the published ``config.json`` of
``LiquidAI/LFM2-8B-A1B`` and the equations of the LFM2 family (Liquid AI,
2025: a hybrid of gated short convolutions and grouped-query attention).

A token table; ``num_hidden_layers`` pre-norm layers whose operator is, by
``layer_types``, a GATED SHORT CONVOLUTION (``conv``) or grouped-query
attention (``full_attention``); a feed-forward that is dense in the first
``num_dense_layers`` layers and after them a mixture of gated experts with no
shared one; a final RMSNorm and a head TIED to the token table. No bias but
the router's selection bias. For a residual stream ``x`` [T, C], all norms
RMSNorm (eps ``norm_eps``, a weight a channel)::

    x = embed[ids]
    each layer:
      x = x + op(rms(x) * operator_norm)
      h = rms(x) * ffn_norm
      x = x + (dense(h)  |  routed(h))
    logits = (rms(x) * norm) @ embed^T

    conv(u), width K = conv_L_cache (3), no bias, NO activation:
      [B | C | z] = u @ in_proj                [C, 3C], in that order
      v_t = B_t * z_t
      c_t = sum_{j < K} conv[j] * v_{t - (K-1) + j}      depthwise, causal,
                                       v = 0 before the sequence starts
      out = (C_t * c_t) @ out_proj
    attention(u), H query heads over Hkv stored heads of d = C / H:
      q = u @ q_proj -> [T, H, d];  k, v = u @ k_proj, u @ v_proj -> [T, Hkv, d]
      q = rms_head(q) * q_layernorm;  k = rms_head(k) * k_layernorm
                         over a head's d lanes, ONE weight [d] for all query
                         heads and one for all key heads, BEFORE the rotation
      rotary over the whole head, halves convention:
        f_i = theta^(-2i/d), a = pos * f_i, [x1 | x2] -> [x1 cos a - x2 sin a
        | x2 cos a + x1 sin a]; no scaling
      query head j reads stored head j // (H / Hkv);
      s(t, u) = d^-1/2 q(t) . k(u), causal softmax, sum_u p v(u)
      out = concat_heads @ out_proj
    routed(h): s = sigmoid(h @ gate) [T, E]; the num_experts_per_tok
      largest of s + expert_bias are chosen (the bias chooses and reaches
      nothing else); weights = s of the chosen, divided by their sum + 1e-6
      (``norm_topk_prob``), times routed_scaling_factor;
      expert e: w2_e(silu(w1_e h) * w3_e h)
    dense(h): the same gated form at ``intermediate_size``, every token.

No kernels, no cache, no chunking: the convolution runs over the whole
sequence from zeros, keys and values are materialised a head, attention is a
full masked softmax, the experts a loop. Independent of ``deepspeed_tpu``: it
is handed a tree under the names above (dense kernels ``[in, out]``, the
convolution ``[K, C]`` with tap ``K - 1`` on the current token), ``layers`` an
iterable that may be a generator.

DEPARTURE 1, as for OLMoE: the loop runs over the EXPERTS, every token
computes every expert, and the sum keeps an expert's term only for the
tokens whose router chose it: the same sum term by term.

DEPARTURE 2, of memory and not of arithmetic: attention is computed a STORED
head at a time (its ``H / Hkv`` query heads with it; whole, the float32 scores
of 32 heads over 2,944 positions are 1.1 GB beside an engine that holds two
thirds of the chip), the dense feed-forward a block of its width at a time,
the logits a block of the vocabulary at a time, and the experts' loop asks
for ONE expert's three matrices at a time, for every sequence before the
next.

WHAT IT SHOWS BESIDE THE LOGITS. ``logits(.., watch=f)`` hands ``f`` what a
comparison on IDENTICAL inputs needs, a layer and a sequence at a time, once
the layer is done (``f(layer, sequence, seen)``): the normed input of the
operator (``mix_in`` [T, C]) and what the operator adds to the stream
(``mix_out`` [T, C], before the residual); for a conv layer the last ``K - 1``
rows of ``v`` (``tail`` [K - 1, C]); the normed input of the feed-forward and
what it adds to the stream (``ffn_in``, ``ffn_out`` [T, C]), and for an expert
layer the router's logits and the weights it kept (``router_logits``,
``kept`` [T, E], 0 for an expert that was not chosen).

WHOSE CHOICE OF EXPERTS. The reference's own, unless ``logits(.., follow=f)``
names another: ``f(layer, sequence, router_logits)`` returns None or the
experts to keep ``[T, k]``, which are then weighed by the reference's OWN
scores. Where two scores stand closer than rounding moves them, either
choice is the model's, and a caller that compares a served stream says which
one that stream made; when it may do so is the caller's to justify.

A ROUNDING MODEL, for the size of that rounding and nothing else:
``hyper["round"]`` names a type (``"bfloat16"``) that every value a layer
hands on is rounded to (the stream, an operator's and a feed-forward's input,
intermediate products and output; accumulation stays float32, and so do the
norms and the router, which reads the float32 norm of the rounded stream
unrounded, as the configuration states). Left out: float32 throughout.

On a TPU a float32 matmul runs in lower precision unless asked otherwise, so
everything is traced under ``jax.default_matmul_precision("highest")``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rounder(hyper):
    """``hyper["round"]``'s rounding (module docstring, A ROUNDING MODEL):
    the identity where it names no type."""
    name = hyper.get("round")
    if not name:
        return lambda x: x
    return lambda x: x.astype(jnp.dtype(name)).astype(jnp.float32)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(weight)


def short_conv(u, p, hyper, seen=None):
    """The gated short convolution on one sequence ``u`` [T, C] (normed)."""
    t, r = u.shape[0], _rounder(hyper)
    b, c, z = jnp.split(r(u @ _f32(p["in_proj"])), 3, axis=-1)
    v = r(b * z)
    k = p["conv"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, v.shape[1])), v])
    conv = sum(_f32(p["conv"][j]) * padded[j:j + t] for j in range(k))
    out = r(c * conv) @ _f32(p["out_proj"])
    if seen is not None:
        seen.update(mix_in=u, mix_out=out, tail=padded[t:])
    return out


def rotate(x, theta):
    """Rotary positions 0 .. T - 1 over the whole head, halves convention:
    x [T, heads, d]."""
    t, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(u, p, hyper, seen=None):
    """Grouped-query attention on one sequence ``u`` [T, C] (normed), a
    stored head at a time (DEPARTURE 2)."""
    t, r = u.shape[0], _rounder(hyper)
    nh, nkv, eps = hyper["n_head"], hyper["n_kv"], hyper["eps"]
    d = p["q_proj"].shape[1] // nh
    rep = nh // nkv
    q = _rms(r(u @ _f32(p["q_proj"])).reshape(t, nh, d), p["q_layernorm"],
             eps)
    k = _rms(r(u @ _f32(p["k_proj"])).reshape(t, nkv, d), p["k_layernorm"],
             eps)
    v = r(u @ _f32(p["v_proj"])).reshape(t, nkv, d)
    q, k = r(rotate(q, hyper["theta"])), r(rotate(k, hyper["theta"]))
    causal = jnp.tril(jnp.ones((t, t), bool))

    def stored_head(x):
        q_g, k_h, v_h = x                      # [T, rep, d], [T, d], [T, d]
        scores = jnp.einsum("tgd,ud->gtu", q_g, k_h) * d ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("gtu,ud->tgd", jax.nn.softmax(scores, axis=-1),
                          v_h)

    heads = jax.lax.map(stored_head, (
        jnp.moveaxis(q.reshape(t, nkv, rep, d), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))   # [Hkv, T, rep, d]
    out = r(jnp.moveaxis(heads, 0, 1).reshape(t, nh * d)) \
        @ _f32(p["out_proj"])
    if seen is not None:
        seen.update(mix_in=u, mix_out=out)
    return out


def _static(hyper):
    """``hyper`` as a hashable static argument."""
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in hyper.items()))


@functools.partial(jax.jit, static_argnames=("static",))
def keep(logits, bias, static, chosen=None):
    """The weights [T, E] the router keeps, 0 for an expert that was not
    chosen: of the ``top_k`` largest of ``sigmoid(logits) + bias``, or of
    ``chosen`` [T, k] where a caller names the experts (module docstring,
    WHOSE CHOICE); the weights are the reference's own scores either way."""
    hyper = dict(static)
    scores = jax.nn.sigmoid(logits)
    if chosen is None:
        chosen = jax.lax.top_k(scores + _f32(bias)[None], hyper["top_k"])[1]
    weight = jnp.take_along_axis(scores, chosen, axis=1)
    if hyper["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    weight = weight * hyper["routed_scaling_factor"]
    return jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], chosen].set(weight)


@functools.partial(jax.jit, static_argnames=("static", "kind", "routed"))
def mixed(x, p, static, kind, routed):
    """The first half of a layer on one sequence, and the router's logits
    where the layer has one: x [T, C] -> (x after the operator, the input
    of the feed-forward, what ``watch`` is shown). ``p`` holds the layer's
    matrices but the feed-forward's."""
    hyper = dict(static)
    r = _rounder(hyper)
    with jax.default_matmul_precision("highest"):
        seen = {}
        x = r(x + r((short_conv if kind == "conv" else attention)(
            r(_rms(x, p["operator_norm"], hyper["eps"])), p, hyper,
            seen=seen)))
        h = _rms(x, p["ffn_norm"], hyper["eps"])
        seen["ffn_in"] = h
        if routed:
            seen["router_logits"] = h @ _f32(p["gate"])
        return x, r(h), seen


@functools.partial(jax.jit, static_argnames=("static",))
def gated_term(total, h, weight, w1, w3, w2, static):
    """``total`` with one gated term: ONE expert's (every token computes it
    and keeps it by the router's weight for that expert [T], 0 where it was
    not chosen) or a block of the dense feed-forward's width (weight 1)."""
    r = _rounder(dict(static))
    with jax.default_matmul_precision("highest"):
        gate, up = r(h @ _f32(w1)), r(h @ _f32(w3))
        return total + weight[:, None] * (
            r(jax.nn.silu(gate) * up) @ _f32(w2))


_DENSE_BLOCKS = 4


def feed_forward(hs, kepts, layer, static):
    """The feed-forward's output for every sequence of one layer (``hs`` a
    list of [T, C]); one matrix triple at a time, for every sequence before
    the next (DEPARTURE 2)."""
    totals = [jnp.zeros_like(h) for h in hs]

    def add(weights, w1, w3, w2):
        for b, h in enumerate(hs):
            totals[b] = gated_term(totals[b], h, weights[b], w1, w3, w2,
                                   static)

    if "gate" not in layer:                          # a leading dense layer
        ones = jnp.ones((hs[0].shape[0],), jnp.float32)
        width = layer["w1"].shape[1]
        blocks = _DENSE_BLOCKS if width % _DENSE_BLOCKS == 0 else 1
        step = width // blocks
        for lo in range(0, width, step):
            add([ones] * len(hs), layer["w1"][:, lo:lo + step],
                layer["w3"][:, lo:lo + step], layer["w2"][lo:lo + step])
        return totals
    for e in range(layer["gate"].shape[1]):
        add([k[:, e] for k in kepts], layer["w1"][e], layer["w3"][e],
            layer["w2"][e])
    return totals


_FFN = ("w1", "w3", "w2")
_VOCAB_BLOCKS = 8


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, table, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, norm, eps)
        v = table.shape[0]
        blocks = _VOCAB_BLOCKS if v % _VOCAB_BLOCKS == 0 else 1
        # a block of the vocabulary at a time (DEPARTURE 2)
        out = jax.lax.map(lambda rows: h @ _f32(rows).T,
                          table.reshape(blocks, v // blocks, -1))
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], v)


def logits(params, input_ids, hyper, watch=None, follow=None):
    """Next-token logits ``[B, T, V]`` float32, as a HOST array, a layer at
    a time and in it a sequence and an expert at a time. ``hyper``:
    ``layer_types`` (``"conv"`` | ``"full_attention"`` a layer), ``n_head``,
    ``n_kv``, ``theta``, ``eps``, ``top_k``, ``norm_topk_prob``,
    ``routed_scaling_factor`` and, for the rounding model alone, ``round``.
    ``watch``, ``follow``: module docstring."""
    ids = np.asarray(input_ids)
    static = _static(hyper)
    r = _rounder(hyper)
    xs = [r(_f32(jnp.asarray(params["embed_tokens"])[row])) for row in ids]
    for i, layer in enumerate(params["layers"]):
        small = {k: v for k, v in layer.items() if k not in _FFN}
        routed = "gate" in layer
        hs, kepts, seens = [], [], []
        for b, x in enumerate(xs):
            xs[b], h, seen = mixed(x, small, static,
                                   hyper["layer_types"][i], routed)
            if routed:
                chosen = follow(i, b, seen["router_logits"]) \
                    if follow is not None else None
                seen["kept"] = keep(
                    seen["router_logits"], layer["expert_bias"], static,
                    None if chosen is None else jnp.asarray(chosen))
            hs.append(h)
            kepts.append(seen.get("kept"))
            seens.append(seen if watch is not None else None)
        for b, total in enumerate(feed_forward(hs, kepts, layer, static)):
            total = r(total)
            xs[b] = r(xs[b] + total)
            if watch is not None:
                watch(i, b, dict(seens[b], ffn_out=total))
                seens[b] = None
    return np.stack([np.asarray(_head(x, params["norm"],
                                      params["embed_tokens"], hyper["eps"]))
                     for x in xs])
