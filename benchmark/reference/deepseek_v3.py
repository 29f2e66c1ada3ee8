"""DeepSeek-V3 (``model_type`` ``deepseek_v3``; R1 and V3.1 share the block) in
plain ``jax.numpy`` and float32, from the published ``config.json`` of
``deepseek-ai/DeepSeek-V3``, its ``modeling_deepseek.py`` and the technical
report (arXiv 2412.19437).

A token table; ``num_hidden_layers`` pre-norm layers of multi-head LATENT
attention (MLA) and a feed-forward that is dense in the first
``first_k_dense_replace`` layers and, after them, a mixture of gated experts
beside one shared gated expert; a final RMSNorm and an untied head. No bias
but the router's selection bias. For a residual stream ``x`` [T, C], all
norms RMSNorm (eps 1e-6)::

    x = embed[ids]
    each layer:
      x = x + mla(rms(x) * input_layernorm)
      h = rms(x) * post_attention_layernorm
      x = x + (dense(h)  |  routed(h) + shared(h))
    logits = (rms(x) * norm) @ lm_head

    mla(h): c_q = rms(h @ q_a_proj) * q_a_layernorm                  [T, 1536]
      q = c_q @ q_b_proj -> H heads of [q_nope (128) | q_pe (64)]
      [c_kv (512) | k_pe (64)] = h @ kv_a_proj_with_mqa
      c_kv = rms(c_kv) * kv_a_layernorm;  k_pe is ONE head all heads share
      q_pe, k_pe = rope(de-interleave(q_pe)), rope(de-interleave(k_pe))
      [k_nope_h (128) | v_h (128)] = c_kv @ kv_b_proj, a head        EXPANDED
      s_h(t, u) = scale * (q_nope_h(t) . k_nope_h(u) + q_pe_h(t) . k_pe(u))
      causal softmax; out_h = sum_u p v_h(u); concat_h(out_h) @ o_proj
      scale = 192 ** -0.5 * m * m,  m = 0.1 * mscale_all_dim * ln(factor) + 1
    rope: the checkpoint holds a rotary pair's two lanes side by side; the
      published code moves lane 2j to j and lane 2j + 1 to 32 + j
      (``de-interleave``) and then rotates HALVES:
      ``x * cos + rotate_half(x) * sin``, cos and sin of
      ``position * [inv_freq | inv_freq]``, times mscale / mscale_all_dim.
    YaRN (dim 64, theta 10,000, factor 40, original 4,096, beta 32 and 1):
      f_i = theta ** (-2i / 64);  d(r) = 64 ln(4096 / (2 pi r)) / (2 ln theta)
      low = floor(d(32)) = 10, high = ceil(d(1)) = 23 (clipped to 0..63)
      ramp_i = clip((i - low) / (high - low), 0, 1), i = 0..31
      inv_freq_i = f_i (1 - ramp_i) + (f_i / 40) ramp_i
    routed (``noaux_tc``): s = sigmoid(h @ gate) [T, 256];  s' = s + bias
      8 groups of 32; a group's score = the sum of its 2 largest s'
      the 4 best groups stay, every other s' is set to 0
      the 8 largest s' are chosen; weights = s (NO bias) of the chosen,
      divided by their sum + 1e-20, times routed_scaling_factor (2.5)
      expert e: down_e(silu(gate_e h) * up_e h)
    shared, dense: the same gated form at their own widths, every token.

No kernels, no cache, no absorbed form: keys and values are materialised a
head, attention is a full masked softmax. Independent of ``deepspeed_tpu``:
it is handed a tree under the published names (dense kernels ``[in, out]``,
``kv_b_proj`` [512, H * 256], the rotary columns INTERLEAVED as published),
``layers`` an iterable that may be a generator.

THE CHIP'S SHARE (DEPARTURE 1). ``held = (first, count)``: the layer holds
the experts ``first .. first + count - 1`` of the router's ``E``; the router
runs over all ``E``, only the held experts' terms are summed and what the
absent ones would add is LEFT OUT, as in the program (model-configs guide,
section 4); the shared expert is whole. ``held = (0, E)`` is the uncut layer.

DEPARTURE 2, as for OLMoE: the loop runs over the EXPERTS, every token
computes every held expert, and the sum keeps an expert's term only for the
tokens whose router chose it: the same sum term by term.

DEPARTURE 3, of memory and not of arithmetic: attention is computed a block
of heads at a time (their projections with them), the dense feed-forward a
block of its width at a time, the logits a block of the vocabulary at a
time, and the experts' loop asks for ONE expert's three matrices at a time,
for every sequence before the next (the same products and sums; whole, the
float32 scores of 128 heads over 2,944 positions alone are 4.4 GB beside an
engine that holds 10.5 of the chip's 16).

MULTI-TOKEN PREDICTION is no part of the next-token forward pass (the module
follows the last layer and drafts a further token): not here.

WHAT IT SHOWS BESIDE THE LOGITS. ``logits(.., watch=f)`` hands ``f`` what a
comparison on IDENTICAL inputs needs, a layer and a sequence at a time
(``f(layer, sequence, seen)``): the normed input of attention and the latent
a token would cache from it, ``[c_kv | k_pe]`` after the norm and the
rotation, rotary lanes in halves order (``attn_in`` [T, C], ``latent``
[T, 576]); what attention adds to the stream (``attn_out`` [T, C], before
the residual); the normed input of the feed-forward, and for an expert layer
the router's logits on it (``ffn_in``, ``router_logits``).

On a TPU a float32 matmul runs in lower precision unless asked otherwise, so
everything is traced under ``jax.default_matmul_precision("highest")``.
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


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """The rotary frequencies [dim / 2] (module docstring, YaRN)."""
    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / float(max(high - low, 0.001)), 0.0), 1.0)
        out.append(f * (1.0 - ramp) + f / factor * ramp)
    return np.asarray(out, np.float32)


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(hyper):
    scale = float(hyper["qk_nope"] + hyper["qk_rope"]) ** -0.5
    yarn = hyper.get("yarn")
    if yarn:
        scale *= _mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return scale


def _cos_sin(t, hyper):
    dim, yarn = hyper["qk_rope"], hyper.get("yarn")
    if yarn:
        inv = yarn_inv_freq(dim, hyper["theta"], yarn["factor"],
                            yarn["original_max_position_embeddings"],
                            yarn["beta_fast"], yarn["beta_slow"])
        mult = _mscale(yarn["factor"], yarn["mscale"]) \
            / _mscale(yarn["factor"], yarn["mscale_all_dim"])
    else:
        inv = 1.0 / hyper["theta"] ** (np.arange(0, dim, 2) / float(dim))
        mult = 1.0
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.concatenate(
        [_f32(inv), _f32(inv)])[None]                          # [T, dim]
    return jnp.cos(ang) * mult, jnp.sin(ang) * mult


def _rope(x, cos, sin):
    """x [T, heads, dim] as the checkpoint's projection gives it (pairs
    interleaved): de-interleave, then rotate halves."""
    t, h, d = x.shape
    x = x.reshape(t, h, d // 2, 2).swapaxes(-1, -2).reshape(t, h, d)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos[:, None] + half * sin[:, None]


_HEAD_GROUP = 4


def mla(h, p, hyper, seen=None):
    """Latent attention on one sequence ``h`` [T, C] (normed), EXPANDED:
    every head's keys and values are materialised from the latent. A block
    of heads at a time, their columns of ``q_b_proj`` and ``kv_b_proj`` and
    their rows of ``o_proj`` with them (DEPARTURE 3: the sum over the
    blocks of ``out_block @ o_proj[block]`` is ``concat(out) @ o_proj``)."""
    t = h.shape[0]
    nh, dn, dr, dv = hyper["n_head"], hyper["qk_nope"], hyper["qk_rope"], \
        hyper["v_head"]
    r, eps = hyper["kv_lora_rank"], hyper["eps"]
    cos, sin = _cos_sin(t, hyper)
    c_q = _rms(h @ _f32(p["q_a_proj"]), p["q_a_layernorm"], eps)
    kv = h @ _f32(p["kv_a_proj_with_mqa"])
    c_kv = _rms(kv[:, :r], p["kv_a_layernorm"], eps)
    k_pe = _rope(kv[:, None, r:], cos, sin)[:, 0]              # [T, dr]
    if seen is not None:
        seen.update(attn_in=h, latent=jnp.concatenate([c_kv, k_pe], axis=-1))
    scale = softmax_scale(hyper)
    causal = jnp.tril(jnp.ones((t, t), bool))
    g = _HEAD_GROUP if nh % _HEAD_GROUP == 0 else 1
    n = nh // g

    def heads(total, w):
        q_w, kv_w, o_w = w
        q = (c_q @ _f32(q_w)).reshape(t, g, dn + dr)
        q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], cos, sin)
        kv_h = (c_kv @ _f32(kv_w)).reshape(t, g, dn + dv)
        k_nope, v = kv_h[..., :dn], kv_h[..., dn:]
        scores = (jnp.einsum("tgd,ugd->gtu", q_nope, k_nope)
                  + jnp.einsum("tgd,ud->gtu", q_pe, k_pe)) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        out = jnp.einsum("gtu,ugd->tgd", jax.nn.softmax(scores, axis=-1), v)
        return total + out.reshape(t, g * dv) @ _f32(o_w), None

    def blocks(w, width):
        # [in, heads x width] -> [n, in, g x width]
        return jnp.moveaxis(w.reshape(w.shape[0], n, g * width), 1, 0)

    o_proj = p["o_proj"]
    total, _ = jax.lax.scan(
        heads, jnp.zeros((t, o_proj.shape[1]), jnp.float32),
        (blocks(p["q_b_proj"], dn + dr), blocks(p["kv_b_proj"], dn + dv),
         o_proj.reshape(n, g * dv, o_proj.shape[1])))
    if seen is not None:
        seen["attn_out"] = total
    return total


def router(h, p, hyper):
    """(weights [T, E], 0 for an expert that was not chosen; the logits
    [T, E]) of the group-limited sigmoid router (module docstring)."""
    t = h.shape[0]
    logits = h @ _f32(p["gate"])
    e = logits.shape[1]
    n_group, topk_group, top_k = hyper["n_group"], hyper["topk_group"], \
        hyper["top_k"]
    scores = jax.nn.sigmoid(logits)
    choice = scores + _f32(p["e_score_correction_bias"])[None]
    grouped = choice.reshape(t, n_group, e // n_group)
    group_scores = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    best = jax.lax.top_k(group_scores, topk_group)[1]
    group_mask = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    masked = jnp.where(group_mask[:, :, None], grouped, 0.0).reshape(t, e)
    idx = jax.lax.top_k(masked, top_k)[1]
    weight = jnp.take_along_axis(scores, idx, axis=1)
    if hyper["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = weight * hyper["routed_scaling_factor"]
    kept = jnp.zeros_like(logits).at[jnp.arange(t)[:, None], idx].set(weight)
    return kept, logits


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def _static(hyper):
    """``hyper`` as a hashable static argument."""
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in hyper.items()))


def _hyper(static):
    return {k: dict(v) if k == "yarn" and v else v for k, v in static}


@functools.partial(jax.jit, static_argnames=("static", "routed"))
def mixed(x, p, static, routed):
    """The first half of a layer on one sequence, and the router where the
    layer has one: x [T, C] -> (x after attention, the normed input of the
    feed-forward, the router's weights [T, E] or None, what ``watch`` is
    shown). ``p`` holds the layer's matrices but the feed-forward's."""
    hyper = _hyper(static)
    with jax.default_matmul_precision("highest"):
        seen = {}
        x = x + mla(_rms(x, p["input_layernorm"], hyper["eps"]), p, hyper,
                    seen=seen)
        h = _rms(x, p["post_attention_layernorm"], hyper["eps"])
        seen["ffn_in"] = h
        kept = None
        if routed:
            kept, seen["router_logits"] = router(h, p, hyper)
        return x, h, kept, seen


@jax.jit
def gated_term(total, h, weight, gate, up, down):
    """``total`` with one gated term: ONE expert's (every token computes it
    and keeps it by the router's weight for that expert [T], 0 where it was
    not chosen), the shared expert's (weight 1), or a block of the dense
    feed-forward's width (weight 1)."""
    with jax.default_matmul_precision("highest"):
        return total + weight[:, None] * _gated(h, gate, up, down)


_DENSE_BLOCKS = 4


def feed_forward(hs, kepts, layer, hyper):
    """The feed-forward's output for every sequence of one layer (``hs`` a
    list of [T, C]); one matrix triple at a time, for every sequence before
    the next (DEPARTURE 3)."""
    ones = jnp.ones((hs[0].shape[0],), jnp.float32)
    totals = [jnp.zeros_like(h) for h in hs]

    def add(weights, gate, up, down):
        for b, h in enumerate(hs):
            totals[b] = gated_term(totals[b], h, weights[b], gate, up, down)

    if "gate" not in layer:                          # a leading dense layer
        width = layer["gate_proj"].shape[1]
        blocks = _DENSE_BLOCKS if width % _DENSE_BLOCKS == 0 else 1
        step = width // blocks
        for lo in range(0, width, step):
            add([ones] * len(hs), layer["gate_proj"][:, lo:lo + step],
                layer["up_proj"][:, lo:lo + step],
                layer["down_proj"][lo:lo + step])
        return totals
    first, count = hyper["held"]
    for e in range(count):
        add([k[:, first + e] for k in kepts], layer["gate_proj"][e],
            layer["up_proj"][e], layer["down_proj"][e])
    add([ones] * len(hs), layer["shared_gate"], layer["shared_up"],
        layer["shared_down"])
    return totals


_SMALL = ("gate_proj", "up_proj", "down_proj", "shared_gate", "shared_up",
          "shared_down")
_VOCAB_BLOCKS = 8


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, norm, eps)
        v = lm_head.shape[1]
        blocks = _VOCAB_BLOCKS if v % _VOCAB_BLOCKS == 0 else 1
        # a block of the vocabulary at a time (DEPARTURE 3)
        out = jax.lax.map(
            lambda cols: h @ _f32(cols),
            jnp.moveaxis(lm_head.reshape(lm_head.shape[0], blocks, -1), 1, 0))
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], v)


def logits(params, input_ids, hyper, watch=None):
    """Next-token logits ``[B, T, V]`` float32, as a HOST array, a layer at
    a time and in it a sequence and an expert at a time. ``hyper``:
    ``n_head``, ``qk_nope``, ``qk_rope``, ``v_head``, ``kv_lora_rank``,
    ``theta``, ``yarn`` (the published ``rope_scaling`` or None), ``eps``,
    ``top_k``, ``n_group``, ``topk_group``, ``norm_topk_prob``,
    ``routed_scaling_factor``, ``held``. ``watch``: module docstring."""
    ids = np.asarray(input_ids)
    static = _static(hyper)
    xs = [_f32(jnp.asarray(params["embed_tokens"])[row]) for row in ids]
    for i, layer in enumerate(params["layers"]):
        small = {k: v for k, v in layer.items() if k not in _SMALL}
        hs, kepts = [], []
        for b, x in enumerate(xs):
            xs[b], h, kept, seen = mixed(x, small, static, "gate" in layer)
            hs.append(h)
            kepts.append(kept)
            if watch is not None:
                watch(i, b, seen)
            del seen
        for b, total in enumerate(feed_forward(hs, kepts, layer, hyper)):
            xs[b] = xs[b] + total
    return np.stack([np.asarray(_head(x, params["norm"], params["lm_head"],
                                      hyper["eps"])) for x in xs])
