"""Granite 4.0-H (``model_type`` ``granitemoehybrid``) in plain ``jax.numpy``
and float32, from the published ``config.json`` of
``ibm-granite/granite-4.0-h-small`` and the Mamba-2 paper (Dao & Gu 2024).

A token table (tied to the output head); ``num_hidden_layers`` pre-norm layers
whose mixer is, by ``layer_types``, a Mamba-2 state-space mixer or grouped-query
attention WITHOUT positions (``position_embedding_type`` ``nope``); after every
mixer a mixture of gated experts beside one shared gated expert; a final
RMSNorm. No bias but the convolution's. For a residual stream ``x`` [T, C]::

    x = embed[ids] * embedding_multiplier
    each layer:
      x = x + residual_multiplier * mixer(rms(x) * input_layernorm)
      h = rms(x) * post_attention_layernorm
      x = x + residual_multiplier * (routed(h) + shared(h))
    logits = (rms(x) * norm) @ embed.T / logits_scaling

    attention: q (H heads), k, v (Hkv heads) of D from three projections;
      scores = q . k * attention_multiplier, causal softmax; query head j
      reads key/value head j // (H / Hkv); output projection.
    mamba: z | xBC | dt = split(h @ in_proj, [W, W + 2N, Hm])     W = Hm * P
      xBC = silu(causal depthwise conv1d(xBC, width K) + conv_bias)
      x_ | B | C = split(xBC, [W, N, N])
      dt = softplus(dt + dt_bias);  A = -exp(A_log) a head
      S_t = exp(dt_t A) S_{t-1} + dt_t outer(x_t, B_t)    a head: [P, N]
      y_t = S_t C_t + D x_t
      out = (rms(y * silu(z)) * norm) @ out_proj      (gate BEFORE the norm)
    routed: logits = h @ router [T, E]; the num_experts_per_tok largest kept;
      weights = softmax over the KEPT logits; expert e:
      down_e(silu(gate_e h) * up_e h)
    shared: the same gated form at its own width, every token, weight 1

No kernels, no cache, no chunking: the recurrence is a token-by-token
``lax.scan`` from a zero state, attention a full masked softmax, the experts a
loop. Independent of ``deepspeed_tpu``: it is handed a tree under the names
above (dense kernels ``[in, out]``, the convolution ``[K, width]`` with tap
``K - 1`` on the current token), ``layers`` an iterable that may be a
generator (at the published widths a layer is cast to float32 one at a time).

THE CHIP'S SHARE (DEPARTURE 1). ``held = (first, count)``: the layer holds the
experts ``first .. first + count - 1`` of the router's ``E`` (their matrices
are the ``count`` it is given). The router runs over all ``E``; only the held
experts' terms are summed, and what the absent ones would add is LEFT OUT, as
in the program (model-configs guide, section 4); the shared expert is whole.
``held = (0, E)`` is the uncut layer.

DEPARTURE 2, as for OLMoE: the loop runs over the EXPERTS, every token
computes every held expert, and the sum keeps an expert's term only for the
tokens whose router kept it: the same sum term by term.

DEPARTURE 3, of memory and not of arithmetic: attention is computed a stored
head's group of query heads at a time, the logits a block of the table's
rows at a time, and the experts' loop is a Python loop that asks for ONE
expert's three matrices at a time (``gate_proj[e]``: the builder slices them
out of the program's stack when asked), for every sequence before the next
expert (the same products and sums; whole, the float32 scores of 32 heads
over 2,304 positions, a float32 copy of the table and a layer's experts are
3 GB beside an engine that holds 12.6 of the chip's 16).

WHAT IT SHOWS BESIDE THE LOGITS. ``logits(.., watch=f)`` hands ``f`` what a
comparison on IDENTICAL inputs needs, a layer and a sequence at a time
(``f(layer, sequence, seen)``): the normed input of the feed-forward and the
router's logits on it (``ffn_in``, ``router_logits``), and for a Mamba layer
the recurrence's inputs and the state after the last token (``x`` [T, Hm, P],
``dt`` [T, Hm], ``B``, ``C`` [T, N], ``A`` [Hm], ``state`` [Hm, P, N]).

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


def _attention(h, p, n_head, n_kv, scale):
    t = h.shape[0]
    q, k, v = (h @ _f32(p[name]) for name in ("q_proj", "k_proj", "v_proj"))
    d = q.shape[-1] // n_head
    rep = n_head // n_kv
    q = q.reshape(t, n_kv, rep, d).transpose(1, 2, 0, 3)      # [Hkv, rep, T, D]
    k = k.reshape(t, n_kv, d).transpose(1, 0, 2)
    v = v.reshape(t, n_kv, d).transpose(1, 0, 2)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def group(qkv):
        # the ``rep`` query heads that read one stored head (DEPARTURE 3)
        q_g, k_g, v_g = qkv
        scores = q_g @ k_g.T * scale                            # [rep, T, T]
        scores = jnp.where(causal, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_g

    out = jax.lax.map(group, (q, k, v))                         # [Hkv, rep, T, D]
    return out.transpose(2, 0, 1, 3).reshape(t, n_head * d) @ _f32(p["o_proj"])


def mamba(h, p, n_heads, d_state, eps, with_state=False, seen=None):
    """The Mamba-2 mixer on one sequence ``h`` [T, C] from a zero state.
    ``with_state`` also returns the state after the last token,
    ``[heads, P, N]``; a dict ``seen`` is given the recurrence's inputs and
    that state (module docstring)."""
    t = h.shape[0]
    k = p["conv_w"].shape[0]
    w = p["out_proj"].shape[0]
    hp = w // n_heads
    z, xbc, dt = jnp.split(h @ _f32(p["in_proj"]),
                           [w, 2 * w + 2 * d_state], axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(_f32(p["conv_b"]) + sum(
        _f32(p["conv_w"])[j] * padded[j:j + t] for j in range(k)))
    x, bmat, cmat = jnp.split(xbc, [w, w + d_state], axis=-1)
    x = x.reshape(t, n_heads, hp)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))               # [T, Hm]
    a = -jnp.exp(_f32(p["A_log"]))

    def token(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = jnp.exp(dt_t * a)[:, None, None] * state + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, state @ c_t                               # [Hm, P]

    state, y = jax.lax.scan(token, jnp.zeros((n_heads, hp, d_state)),
                            (x, bmat, cmat, dt))
    if seen is not None:
        seen.update(x=x, dt=dt, B=bmat, C=cmat, A=a, state=state)
    y = (y + _f32(p["D"])[:, None] * x).reshape(t, w)
    out = _rms(y * jax.nn.silu(z), p["norm"], eps) @ _f32(p["out_proj"])
    return (out, state) if with_state else out


def _router(h, p, top_k):
    """(weights [T, E], 0 for an expert that was not kept: the softmax over
    the kept logits; the gap between the last logit kept and the first cut
    [T]; the logits [T, E])."""
    logits = h @ _f32(p["router"])
    top, idx = jax.lax.top_k(logits, top_k + 1)
    weights = jax.nn.softmax(top[:, :top_k], axis=-1)
    kept = jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], idx[:, :top_k]].set(weights)
    return kept, top[:, top_k - 1] - top[:, top_k], logits


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


@functools.partial(jax.jit, static_argnames=(
    "kind", "n_head", "n_kv", "scale", "mamba_heads", "d_state", "top_k",
    "eps", "residual"))
def mixed(x, p, kind, n_head, n_kv, scale, mamba_heads, d_state, top_k, eps,
          residual):
    """The first half of a layer on one sequence, and the router: x [T, C]
    -> (x after the mixer, the normed input of the feed-forward, the
    router's weights [T, E], its gap [T], what ``watch`` is shown). ``p``
    holds the layer's matrices but the routed experts'."""
    with jax.default_matmul_precision("highest"):
        seen = {}
        h = _rms(x, p["input_layernorm"], eps)
        branch = mamba(h, p, mamba_heads, d_state, eps, seen=seen) \
            if kind == "mamba" else _attention(h, p, n_head, n_kv, scale)
        x = x + residual * branch
        h = _rms(x, p["post_attention_layernorm"], eps)
        kept, gap, logits = _router(h, p, top_k)
        seen.update(ffn_in=h, router_logits=logits)
        return x, h, kept, gap, seen


@jax.jit
def expert_term(total, h, weight, gate, up, down):
    """``total`` with ONE expert's term: every token computes it, and keeps
    it by the router's weight for that expert [T] (0 where it was cut)."""
    with jax.default_matmul_precision("highest"):
        return total + weight[:, None] * _gated(h, gate, up, down)


@jax.jit
def shared(h, p):
    with jax.default_matmul_precision("highest"):
        return _gated(h, p["shared_gate"], p["shared_up"], p["shared_down"])


def routed(h, kept, p, held):
    """The held experts' part of the routed sum (module docstring,
    DEPARTURES 1 to 3)."""
    first, count = held
    total = jnp.zeros_like(h)
    for e in range(count):
        total = expert_term(total, h, kept[:, first + e], p["gate_proj"][e],
                            p["up_proj"][e], p["down_proj"][e])
    return total


def block(x, p, kind, n_head, n_kv, scale, mamba_heads, d_state, top_k, held,
          eps, residual, parts=False):
    """One layer on one sequence: x [T, C] float32 -> (x, router gap [T]).
    ``parts`` returns instead (x after the mixer, the normed input of the
    feed-forward, the routed part, the shared part): what the test of the
    shares adds up."""
    small = {k: v for k, v in p.items()
             if k not in ("gate_proj", "up_proj", "down_proj")}
    x, h, kept, gap, _ = mixed(x, small, kind, n_head, n_kv, scale,
                               mamba_heads, d_state, top_k, eps, residual)
    part = routed(h, kept, p, held)
    if parts:
        return x, h, part, shared(h, small)
    return x + residual * (part + shared(h, small)), gap


_HEAD_BLOCKS = 8


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _head(x, norm, embed, eps, scaling):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, norm, eps)
        blocks = _HEAD_BLOCKS if embed.shape[0] % _HEAD_BLOCKS == 0 else 1
        # a block of the table's rows at a time (DEPARTURE 3)
        out = jax.lax.map(lambda rows: h @ _f32(rows).T / scaling,
                          embed.reshape(blocks, -1, embed.shape[1]))
        return out.transpose(1, 0, 2).reshape(h.shape[0], embed.shape[0])


def logits(params, input_ids, hyper, with_gaps=False, watch=None):
    """Next-token logits ``[B, T, V]`` float32, as a HOST array (at the
    cell's sizes 0.46 GB a sequence), a layer at a time and in it a sequence
    and an expert at a time. ``hyper``: ``layer_types``, ``n_head``,
    ``n_kv``, ``attention_multiplier``, ``mamba_heads``, ``d_state``,
    ``top_k``, ``held``, ``eps``, ``embedding_multiplier``,
    ``residual_multiplier``, ``logits_scaling``. ``with_gaps`` also returns
    ``[B, T]``: the smallest gap, over the layers, between the last router
    logit a token kept and the first it cut. ``watch``: module docstring."""
    ids = np.asarray(input_ids)
    embed = jnp.asarray(params["embed_tokens"])
    xs = [_f32(embed[row]) * hyper["embedding_multiplier"] for row in ids]
    gaps = [jnp.full((ids.shape[1],), jnp.inf) for _ in xs]
    first, count = hyper["held"]
    residual = hyper["residual_multiplier"]
    for i, (kind, layer) in enumerate(zip(hyper["layer_types"],
                                          params["layers"])):
        small = {k: v for k, v in layer.items()
                 if k not in ("gate_proj", "up_proj", "down_proj")}
        hs, kepts = [], []
        for b, x in enumerate(xs):
            xs[b], h, kept, gap, seen = mixed(
                x, small, kind, hyper["n_head"], hyper["n_kv"],
                hyper["attention_multiplier"], hyper["mamba_heads"],
                hyper["d_state"], hyper["top_k"], hyper["eps"], residual)
            gaps[b] = jnp.minimum(gaps[b], gap)
            hs.append(h)
            kepts.append(kept)
            if watch is not None:
                watch(i, b, seen)
            del seen
        totals = [jnp.zeros_like(h) for h in hs]
        for e in range(count):
            # one expert's matrices at a time, for every sequence
            gate, up, down = (layer[k][e] for k in
                              ("gate_proj", "up_proj", "down_proj"))
            for b, h in enumerate(hs):
                totals[b] = expert_term(totals[b], h, kepts[b][:, first + e],
                                        gate, up, down)
        for b, h in enumerate(hs):
            xs[b] = xs[b] + residual * (totals[b] + shared(h, small))
    out = np.stack([np.asarray(_head(x, params["norm"], embed, hyper["eps"],
                                     hyper["logits_scaling"])) for x in xs])
    return (out, np.stack([np.asarray(g) for g in gaps])) if with_gaps \
        else out
