"""Kimi Linear (``model_type`` ``kimi_linear``: Kimi-Linear-48B-A3B) in plain
``jax.numpy`` and float32, from the published ``config.json`` of
``moonshotai/Kimi-Linear-48B-A3B-Instruct`` and the Kimi Linear technical
report (Moonshot AI, 2025: Kimi Delta Attention, "KDA").

A token table; ``num_hidden_layers`` pre-norm layers whose mixer is, by
``linear_attn_config`` (1-indexed ``kda_layers`` / ``full_attn_layers``, three
to one), Kimi Delta Attention or multi-head LATENT attention (MLA) WITHOUT
positions (``mla_use_nope``: no rotary anywhere in the model); a feed-forward
that is dense in the first ``first_k_dense_replace`` layers and after them a
mixture of gated experts beside one shared gated expert; a final RMSNorm and
an untied head. No bias but the router's selection bias. For a residual
stream ``x`` [T, C], all norms RMSNorm (eps 1e-5)::

    x = embed[ids]
    each layer:
      x = x + mixer(rms(x) * input_layernorm)
      h = rms(x) * post_attention_layernorm
      x = x + (dense(h)  |  routed(h) + shared(h))
    logits = (rms(x) * norm) @ lm_head

    kda(h), H heads of d channels for keys and values, conv width 4:
      q~, k~, v = silu(conv4(h @ q_proj)), silu(conv4(h @ k_proj)),
                  silu(conv4(h @ v_proj))        causal, depthwise, no bias
      q = q~ / sqrt(sum_head(q~^2) + 1e-6) * d^-1/2
      k = k~ / sqrt(sum_head(k~^2) + 1e-6)
      g = -exp(A_log[head]) * softplus((h @ f_a_proj) @ f_b_proj + dt_bias)
                                           [T, H, d]: a log-decay a CHANNEL
      beta = sigmoid(h @ b_proj)                                    [T, H]
      a head, from S_0 = 0 [d_k, d_v], token by token:
        S'  = exp(g_t)[:, None] * S_{t-1}
        S_t = S' + beta_t * outer(k_t, v_t - S'^T k_t)
        o_t = S_t^T q_t
      y = rms_head(o_t) * o_norm * sigmoid((h @ g_a_proj) @ g_b_proj)
                                         the norm BEFORE the gate, a head
      out = concat_heads(y) @ o_proj
    mla(h), q_lora_rank null, NoPE:
      q = h @ q_proj -> H heads of [q_nope (128) | q_pe (64)]
      [c_kv (512) | k_pe (64)] = h @ kv_a_proj_with_mqa
      c_kv = rms(c_kv) * kv_a_layernorm;  k_pe is ONE head all heads share,
      carried as it is (unrotated)
      [k_nope_h (128) | v_h (128)] = c_kv @ kv_b_proj, a head       EXPANDED
      s_h(t, u) = 192^-1/2 (q_nope_h(t) . k_nope_h(u) + q_pe_h(t) . k_pe(u))
      causal softmax; out_h = sum_u p v_h(u); concat_h(out_h) @ o_proj
    routed: s = sigmoid(h @ gate) [T, 256]; the num_experts_per_token
      largest of s + e_score_correction_bias are chosen (``num_expert_group``
      1, ``topk_group`` 1: one group, nothing to limit); weights = s (NO
      bias) of the chosen, divided by their sum + 1e-20
      (``moe_renormalize``), times routed_scaling_factor (2.446);
      expert e: down_e(silu(gate_e h) * up_e h)
    shared, dense: the same gated form at their own widths, every token.

No kernels, no cache, no chunking, no absorbed form: the recurrence is a
token-by-token ``lax.scan`` from a zero state, keys and values are
materialised a head, attention is a full masked softmax, the experts a loop.
Independent of ``deepspeed_tpu``: it is handed a tree under the published
names (dense kernels ``[in, out]``, the convolutions ``[K, H d]`` with tap
``K - 1`` on the current token), ``layers`` an iterable that may be a
generator.

THE CHIP'S SHARE (DEPARTURE 1). ``held = (first, count)``: the layer holds
the experts ``first .. first + count - 1`` of the router's ``E``; the router
runs over all ``E``, only the held experts' terms are summed and what the
absent ones would add is LEFT OUT, as in the program (model-configs guide,
section 4); the shared expert is whole. ``held = (0, E)`` is the uncut layer.

DEPARTURE 2, as for OLMoE: the loop runs over the EXPERTS, every token
computes every held expert, and the sum keeps an expert's term only for the
tokens whose router chose it: the same sum term by term.

DEPARTURE 3, of memory and not of arithmetic: attention is computed a block
of heads at a time (their projections with them), the recurrence a block of
heads at a time (heads do not meet before ``o_proj``), the dense
feed-forward a block of its width at a time, the logits a block of the
vocabulary at a time, and the experts' loop asks for ONE expert's three
matrices at a time, for every sequence before the next (the same products
and sums; whole, the float32 scores of 32 heads over 2,944 positions alone
are 1.1 GB beside an engine that holds half of the chip's 16).

WHAT IT SHOWS BESIDE THE LOGITS. ``logits(.., watch=f)`` hands ``f`` what a
comparison on IDENTICAL inputs needs, a layer and a sequence at a time
(``f(layer, sequence, seen)``): the normed input of the mixer (``mix_in``
[T, C]) and what the mixer adds to the stream (``mix_out`` [T, C], before
the residual); for an MLA layer the latent a token would cache,
``[c_kv | k_pe]`` after the norm (``latent`` [T, 576]); for a KDA layer the
recurrence's inputs (``q``, ``k``, ``v``, ``g`` [T, H, d], ``beta``
[T, H]), the state after the last token (``state`` [H, d_k, d_v]) and the
last three rows before the convolutions (``tail`` [3, 3 H d]: q | k | v);
the normed input of the feed-forward, and for an expert layer the router's
logits on it (``ffn_in``, ``router_logits``).

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


def _conv(x, weight):
    """Causal depthwise convolution: x [T, W], weight [K, W] with tap
    ``K - 1`` on the current token; zeros before the first."""
    k = weight.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1])), x])
    return sum(_f32(weight[j]) * padded[j:j + x.shape[0]] for j in range(k))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


_KDA_HEAD_GROUP = 8


def delta_rule(q, k, v, g, beta):
    """The recurrence of the module docstring over one sequence, token by
    token from a zero state: q, k, v, g [T, H, d], beta [T, H] ->
    (o [T, H, d], the state after the last token [H, d_k, d_v]). A block of
    heads at a time (DEPARTURE 3)."""
    t, h, d = q.shape
    grp = _KDA_HEAD_GROUP if h % _KDA_HEAD_GROUP == 0 else h

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x              # [grp, d]; b_t [grp]
        decayed = jnp.exp(g_t)[:, :, None] * state
        seen = jnp.einsum("hkv,hk->hv", decayed, k_t)
        state = decayed + b_t[:, None, None] * (
            k_t[:, :, None] * (v_t - seen)[:, None, :])
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    def heads(xs):
        return jax.lax.scan(token, jnp.zeros((grp, d, d), jnp.float32), xs)

    def split(x):
        # [T, H, ..] -> [H / grp, T, grp, ..]
        return jnp.moveaxis(x.reshape((t, h // grp, grp) + x.shape[2:]), 1, 0)

    state, o = jax.lax.map(heads, tuple(split(x)
                                        for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(t, h, d), state.reshape(h, d, d)


def kda(h, p, hyper, seen=None):
    """Kimi Delta Attention on one sequence ``h`` [T, C] (normed)."""
    t = h.shape[0]
    nh, d = hyper["kda_heads"], hyper["kda_head_dim"]
    before = [h @ _f32(p[name]) for name in ("q_proj", "k_proj", "v_proj")]
    q, k, v = (jax.nn.silu(_conv(x, p[name])).reshape(t, nh, d)
               for x, name in zip(before, ("q_conv", "k_conv", "v_conv")))
    q, k = _l2(q) * d ** -0.5, _l2(k)
    g = -jnp.exp(_f32(p["A_log"]))[None, :, None] * jax.nn.softplus(
        (h @ _f32(p["f_a_proj"])) @ _f32(p["f_b_proj"])
        + _f32(p["dt_bias"])).reshape(t, nh, d)
    beta = jax.nn.sigmoid(h @ _f32(p["b_proj"]))
    o, state = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid((h @ _f32(p["g_a_proj"])) @ _f32(p["g_b_proj"]))
    y = _rms(o, p["o_norm"], hyper["eps"]) * gate.reshape(t, nh, d)
    out = y.reshape(t, nh * d) @ _f32(p["o_proj"])
    if seen is not None:
        keep = p["q_conv"].shape[0] - 1
        seen.update(mix_in=h, mix_out=out, q=q, k=k, v=v, g=g, beta=beta,
                    state=state,
                    tail=jnp.concatenate(before, axis=-1)[t - keep:])
    return out


_HEAD_GROUP = 4


def mla(h, p, hyper, seen=None):
    """Latent attention without positions on one sequence ``h`` [T, C]
    (normed), EXPANDED: every head's keys and values are materialised from
    the latent. A block of heads at a time, their columns of ``q_proj`` and
    ``kv_b_proj`` and their rows of ``o_proj`` with them (DEPARTURE 3)."""
    t = h.shape[0]
    nh, dn, dr, dv = hyper["n_head"], hyper["qk_nope"], hyper["qk_rope"], \
        hyper["v_head"]
    r, eps = hyper["kv_lora_rank"], hyper["eps"]
    kv = h @ _f32(p["kv_a_proj_with_mqa"])
    c_kv = _rms(kv[:, :r], p["kv_a_layernorm"], eps)
    k_pe = kv[:, r:]                                           # [T, dr]
    scale = float(dn + dr) ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    g = _HEAD_GROUP if nh % _HEAD_GROUP == 0 else 1
    n = nh // g

    def heads(total, w):
        q_w, kv_w, o_w = w
        q = (h @ _f32(q_w)).reshape(t, g, dn + dr)
        kv_h = (c_kv @ _f32(kv_w)).reshape(t, g, dn + dv)
        k_nope, v = kv_h[..., :dn], kv_h[..., dn:]
        scores = (jnp.einsum("tgd,ugd->gtu", q[..., :dn], k_nope)
                  + jnp.einsum("tgd,ud->gtu", q[..., dn:], k_pe)) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        out = jnp.einsum("gtu,ugd->tgd", jax.nn.softmax(scores, axis=-1), v)
        return total + out.reshape(t, g * dv) @ _f32(o_w), None

    def blocks(w, width):
        # [in, heads x width] -> [n, in, g x width]
        return jnp.moveaxis(w.reshape(w.shape[0], n, g * width), 1, 0)

    o_proj = p["o_proj"]
    total, _ = jax.lax.scan(
        heads, jnp.zeros((t, o_proj.shape[1]), jnp.float32),
        (blocks(p["q_proj"], dn + dr), blocks(p["kv_b_proj"], dn + dv),
         o_proj.reshape(n, g * dv, o_proj.shape[1])))
    if seen is not None:
        seen.update(mix_in=h, mix_out=total,
                    latent=jnp.concatenate([c_kv, k_pe], axis=-1))
    return total


def router(h, p, hyper):
    """(weights [T, E], 0 for an expert that was not chosen; the logits
    [T, E]) of the sigmoid router (module docstring)."""
    t = h.shape[0]
    logits = h @ _f32(p["gate"])
    scores = jax.nn.sigmoid(logits)
    idx = jax.lax.top_k(
        scores + _f32(p["e_score_correction_bias"])[None], hyper["top_k"])[1]
    weight = jnp.take_along_axis(scores, idx, axis=1)
    if hyper["renormalize"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = weight * hyper["routed_scaling_factor"]
    kept = jnp.zeros_like(logits).at[jnp.arange(t)[:, None], idx].set(weight)
    return kept, logits


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def _static(hyper):
    """``hyper`` as a hashable static argument."""
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in hyper.items()))


@functools.partial(jax.jit, static_argnames=("static", "kind", "routed"))
def mixed(x, p, static, kind, routed):
    """The first half of a layer on one sequence, and the router where the
    layer has one: x [T, C] -> (x after the mixer, the normed input of the
    feed-forward, the router's weights [T, E] or None, what ``watch`` is
    shown). ``p`` holds the layer's matrices but the feed-forward's."""
    hyper = dict(static)
    with jax.default_matmul_precision("highest"):
        seen = {}
        x = x + (kda if kind == "kda" else mla)(
            _rms(x, p["input_layernorm"], hyper["eps"]), p, hyper, seen=seen)
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


_FFN = ("gate_proj", "up_proj", "down_proj", "shared_gate", "shared_up",
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
    ``layer_types`` (``"kda"`` | ``"attention"`` a layer), ``kda_heads``,
    ``kda_head_dim``, ``n_head``, ``qk_nope``, ``qk_rope``, ``v_head``,
    ``kv_lora_rank``, ``eps``, ``top_k``, ``renormalize``,
    ``routed_scaling_factor``, ``held``. ``watch``: module docstring."""
    ids = np.asarray(input_ids)
    static = _static(hyper)
    xs = [_f32(jnp.asarray(params["embed_tokens"])[row]) for row in ids]
    for i, layer in enumerate(params["layers"]):
        small = {k: v for k, v in layer.items() if k not in _FFN}
        hs, kepts = [], []
        for b, x in enumerate(xs):
            xs[b], h, kept, seen = mixed(
                x, small, static, hyper["layer_types"][i], "gate" in layer)
            hs.append(h)
            kepts.append(kept)
            if watch is not None:
                watch(i, b, seen)
            del seen
        for b, total in enumerate(feed_forward(hs, kepts, layer, hyper)):
            xs[b] = xs[b] + total
    return np.stack([np.asarray(_head(x, params["norm"], params["lm_head"],
                                      hyper["eps"])) for x in xs])
