"""Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``; the "SambaY"
decoder-hybrid-decoder of arXiv:2507.06607) in plain ``jax.numpy`` and float32,
from the published ``config.json`` of ``microsoft/Phi-4-mini-flash-reasoning``,
the Mamba paper (Gu & Dao 2023: the selective scan), YOCO's cross-decoder (one
layer's keys and values read by every later attention layer) and the paper's
gated memory unit.

A token table (tied to the output head); ``num_hidden_layers`` pre-LayerNorm
layers (weight AND bias, eps ``layer_norm_eps``) whose mixer is one of five
kinds, each followed by a dense gated feed-forward; a last LayerNorm. For a
residual stream ``x`` [T, C], 0-based layer ``i`` of ``L`` (32)::

    x = embed[ids]
    each layer:
      x = x + Mix_i(LN1_i(x))
      x = x + (silu(g) * u) W_2,   [g | u] = LN2_i(x) W_1     W_1 [C, 2F]
    logits = LN(x) @ embed.T

    i even, i <= L/2      mamba: [xs | z] = h W_in;  xc = silu(conv(xs) + b)
                           [d | B | C] = xc W_x;  D_t = softplus(d W_dt + b_dt)
                           S_t = exp(D_t A) * S_{t-1} + (D_t xc_t) B_t^T
                           y_t = S_t C_t + D * xc_t;  out = (y * silu(z)) W_out
                           The LAST of them (layer L/2) also hands m = y.
    i odd,  i <  L/2      window attention: [q | k | v] = h W_qkv + b, scale
                           1/sqrt(D), the query at p sees p - W < j <= p
    i = L/2 + 1           the same attention, full causal (j <= p): its keys
                           and values are THE shared plane
    i even, i >  L/2      gated memory unit: out = (m * silu(h W_1g)) W_2g
    i odd,  i >  L/2 + 1  cross attention: q = h W_q + b against layer
                           L/2 + 1's keys and values, j <= p; out = o W_o + b

ASSUMED, where the catalog's keys are silent (each also under ``assumed`` in
``benchmark/configs/phi-4-mini-flash-reasoning.json``; marked ASSUMED at its
line below): which layer is which (``layer_kinds``: the family's rule from
``mb_per_layer`` 2 and ``num_hidden_layers`` / 2); LayerNorm with a bias;
a bias on the attention projections and none on the feed-forward or the
Mamba projections but ``dt_proj``'s and the convolution's; the window counts
the query's own position; no positions of any kind; ``m`` is the scan output
with the ``D`` skip and before the gate; Mamba's ``d_state`` 16, ``d_conv``
4, ``expand`` 2, ``dt_rank`` ceil(C / 16) and NO inner norms.

DEPARTURE (the one of arithmetic): the paper says the released checkpoint's
attention is DIFFERENTIAL attention (two softmax maps subtracted, a norm a
head). The catalog's ``config`` has no key of it, so which heads pair cannot
be written down; the attention here is softmax grouped-query attention as
the keys give it (marked DEPARTURE below).

DEPARTURE of memory and not of arithmetic: attention is computed a group of
query heads at a time and the logits a block of the table's rows at a time,
each block moved to the host as it is made (whole, a sequence's float32
logits over 200,064 ids are 2.4 GB beside an engine that holds most of the
chip). The masks are explicit ``[T, T]`` arrays, window and causal.

No kernels, no cache, no ring: the recurrence is a token-by-token
``lax.scan`` from a zero state, attention a full masked softmax over the
whole sequence. Independent of ``deepspeed_tpu``: it is handed a tree under
the names used below (dense kernels ``[in, out]``, the convolution ``[K, W]``
with tap ``K - 1`` on the current token, ``A_log`` ``[W, N]``), ``layers`` an
iterable that may be a generator.

WHAT IT SHOWS BESIDE THE LOGITS. ``logits(.., watch=f)`` hands ``f`` what a
comparison on IDENTICAL inputs needs, a layer and a sequence at a time
(``f(layer, sequence, seen)``): the normed input of the mixer and what the
mixer adds (``mix_in``, ``mix_out`` [T, C]), the stream the feed-forward is
handed and what it adds (``ff_in``, ``ff_out``); for a Mamba layer the
recurrence's inputs (``x``, ``dt`` [T, W], ``B``, ``C`` [T, N], ``A`` [W, N]),
the state after the last token (``state`` [W, N]) and its output ``y``; for a
gated memory unit the ``memory`` it read.

On a TPU a float32 matmul runs in lower precision unless asked otherwise, so
everything is traced under ``jax.default_matmul_precision("highest")``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _ln(x, weight, bias, eps):
    # ASSUMED: LayerNorm with a bias (the key is layer_norm_eps)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(weight) + _f32(bias)


def layer_kinds(n_layer, mb_per_layer=2):
    """ASSUMED: the family's rule for the order of the layer kinds (module
    docstring): the self-decoder ``0 .. n_layer / 2 + 1`` alternates Mamba and
    window attention (one attention in ``mb_per_layer`` layers) and ends on
    the ONE full-attention layer; the cross-decoder after it alternates gated
    memory units and cross attention."""
    half = n_layer // 2
    kinds = []
    for i in range(n_layer):
        mixes = i % mb_per_layer == 0
        if i <= half:
            kinds.append("mamba" if mixes else "window")
        elif i == half + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if mixes else "cross")
    return tuple(kinds)


_HEAD_GROUPS = 4


def _attend(q, k, v, mask, n_head, n_kv):
    """Masked softmax attention of queries ``q`` [T, H D] over keys and
    values ``k``, ``v`` [T, Hkv D] under ``mask`` [T, T] (True: seen); query
    head j reads stored head j // (H / Hkv). No positions (ASSUMED). DEPARTURE:
    plain softmax attention, not the checkpoint's differential attention."""
    t = q.shape[0]
    d = q.shape[-1] // n_head
    rep = n_head // n_kv
    q = q.reshape(t, n_head, d).transpose(1, 0, 2)              # [H, T, D]
    k = jnp.repeat(k.reshape(t, n_kv, d).transpose(1, 0, 2), rep, axis=0)
    v = jnp.repeat(v.reshape(t, n_kv, d).transpose(1, 0, 2), rep, axis=0)

    def group(qkv):
        # a group of query heads at a time (DEPARTURE of memory)
        q_g, k_g, v_g = qkv
        scores = jnp.einsum("htd,hsd->hts", q_g, k_g) / np.sqrt(d)
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("hts,hsd->htd", jax.nn.softmax(scores, axis=-1),
                          v_g)

    groups = _HEAD_GROUPS if n_head % _HEAD_GROUPS == 0 else 1
    out = jax.lax.map(group, tuple(
        a.reshape(groups, n_head // groups, t, d) for a in (q, k, v)))
    return out.reshape(n_head, t, d).transpose(1, 0, 2).reshape(
        t, n_head * d)


def masks(t, window):
    """(causal, window) ``[T, T]``: key ``j`` is seen by the query at ``p``
    iff ``j <= p``, and under the window iff also ``p - window < j``
    (ASSUMED: the window counts the query's own position)."""
    p, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    causal = j <= p
    return causal, causal & (j > p - window)


def mamba(h, p, d_state, dt_rank, seen=None):
    """The Mamba-1 mixer on one sequence ``h`` [T, C] from a zero state,
    WITHOUT inner norms (ASSUMED: Mamba as published): (out [T, C], y [T, W]
    the scan's output with the ``D`` skip and before the gate)."""
    t = h.shape[0]
    k = p["conv_w"].shape[0]
    x_in, z = jnp.split(h @ _f32(p["in_proj"]), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, x_in.shape[1])), x_in])
    x = jax.nn.silu(_f32(p["conv_b"]) + sum(
        _f32(p["conv_w"])[j] * padded[j:j + t] for j in range(k)))
    d, bmat, cmat = jnp.split(x @ _f32(p["x_proj"]),
                              [dt_rank, dt_rank + d_state], axis=-1)
    dt = jax.nn.softplus(d @ _f32(p["dt_proj"]) + _f32(p["dt_bias"]))
    a = -jnp.exp(_f32(p["A_log"]))                              # [W, N]

    def token(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, state @ c_t                               # [W]

    state, y = jax.lax.scan(token, jnp.zeros_like(a), (x, bmat, cmat, dt))
    y = y + _f32(p["D"]) * x
    if seen is not None:
        seen.update(x_in=x_in, x=x, dt=dt, B=bmat, C=cmat, A=a, state=state,
                    y=y)
    return (y * jax.nn.silu(z)) @ _f32(p["out_proj"]), y


@functools.partial(jax.jit, static_argnames=(
    "kind", "n_head", "n_kv", "d_state", "dt_rank", "window", "eps"))
def block(x, p, carried, kind, n_head, n_kv, d_state, dt_rank, window, eps):
    """One layer on one sequence: x [T, C] float32 and what the stack
    carries down beside it (``carried``: ``memory`` [T, W] once the last
    Mamba layer ran, ``k`` / ``v`` [T, Hkv D] once the full layer did) ->
    (x, carried, what ``watch`` is shown)."""
    with jax.default_matmul_precision("highest"):
        seen = {}
        carried = dict(carried)
        causal, windowed = masks(x.shape[0], window)
        h = _ln(x, p["ln1_w"], p["ln1_b"], eps)
        if kind == "mamba":
            # every Mamba layer overwrites it: the LAST one's stays
            mix, carried["memory"] = mamba(h, p, d_state, dt_rank, seen)
        elif kind in ("window", "full"):
            q_w = p["o_proj"].shape[0]
            kv_w = (p["qkv_proj"].shape[1] - q_w) // 2
            # ASSUMED: a bias on the attention projections
            q, k, v = jnp.split(h @ _f32(p["qkv_proj"]) + _f32(p["qkv_b"]),
                                [q_w, q_w + kv_w], axis=-1)
            mix = _attend(q, k, v, windowed if kind == "window" else causal,
                          n_head, n_kv) @ _f32(p["o_proj"]) + _f32(p["o_b"])
            if kind == "full":
                carried.update(k=k, v=v)
        elif kind == "gmu":
            # ASSUMED: the memory is y before the gate, of the same token
            seen["memory"] = carried["memory"]
            mix = (carried["memory"] * jax.nn.silu(h @ _f32(p["gmu_in"]))) \
                @ _f32(p["gmu_out"])
        else:
            q = h @ _f32(p["q_proj"]) + _f32(p["q_b"])
            mix = _attend(q, carried["k"], carried["v"], causal, n_head,
                          n_kv) @ _f32(p["o_proj"]) + _f32(p["o_b"])
        seen.update(mix_in=h, mix_out=mix)
        x = x + mix
        h = _ln(x, p["ln2_w"], p["ln2_b"], eps)
        f = p["down_proj"].shape[0]
        gu = h @ _f32(p["gate_up_proj"])
        ff = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ _f32(p["down_proj"])
        seen.update(ff_in=x, ff_out=ff)
        return x + ff, carried, seen


_HEAD_BLOCKS = 16


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, weight, bias, eps):
    return _ln(x, weight, bias, eps)


@jax.jit
def _head_block(h, rows):
    with jax.default_matmul_precision("highest"):
        return h @ _f32(rows).T


def _head(x, params, embed, eps):
    """A sequence's logits [T, V] as a HOST array, a block of the table's
    rows at a time (DEPARTURE of memory)."""
    h = _normed(x, params["final_ln_w"], params["final_ln_b"], eps)
    v = embed.shape[0]
    blocks = _HEAD_BLOCKS if v % _HEAD_BLOCKS == 0 else 1
    out = np.empty((x.shape[0], v), np.float32)
    for b in range(blocks):
        lo, hi = b * (v // blocks), (b + 1) * (v // blocks)
        out[:, lo:hi] = np.asarray(_head_block(h, embed[lo:hi]))
    return out


def logits(params, input_ids, hyper, watch=None):
    """Next-token logits ``[B, T, V]`` float32, as a HOST array, a layer at a
    time and in it a sequence at a time. ``hyper``: ``layer_types``
    (``layer_kinds``'s), ``n_head``, ``n_kv``, ``d_state``, ``dt_rank``,
    ``window``, ``eps``. ``watch``: module docstring."""
    ids = np.asarray(input_ids)
    embed = jnp.asarray(params["embed_tokens"])
    xs = [_f32(embed[row]) for row in ids]
    carried = [{} for _ in xs]
    for i, (kind, layer) in enumerate(zip(hyper["layer_types"],
                                          params["layers"])):
        for b, x in enumerate(xs):
            xs[b], carried[b], seen = block(
                x, layer, carried[b], kind, hyper["n_head"], hyper["n_kv"],
                hyper["d_state"], hyper["dt_rank"], hyper["window"],
                hyper["eps"])
            if watch is not None:
                watch(i, b, seen)
            del seen
    return np.stack([_head(x, params, embed, hyper["eps"]) for x in xs])
