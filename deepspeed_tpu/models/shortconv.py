"""The gated short convolution (LFM2, Liquid AI 2025: the ``conv`` operator of
``model_type`` ``lfm2`` / ``lfm2_moe``), a token mixer, for serving.

One layer, for a normed residual stream ``h`` [B, S, C], with ONE depthwise
causal convolution of width ``K`` (``conv_L_cache``, 3) over all ``C``
channels, no bias anywhere and no activation::

    B | C | z = split(h @ in_proj, 3)             in_proj [C, 3C], that order
    v_t = B_t * z_t                               the input gate
    c_t = sum_{j < K} conv_w[j] * v_{t - (K-1) + j}   v is 0 before the start
    out = (C_t * c_t) @ out_proj                  the output gate; [C, C]

WHAT A ROW CARRIES BETWEEN CALLS, and nothing else, for conv layer ``j``: the
last ``K - 1`` rows of ``v`` (``slot_shortconv<j>`` ``[B, K - 1, C]`` in the
compute type: two rows of 2,048, 8 KB a slot a layer at LFM2-8B-A1B's
widths). It is the kind's ONLY state: a recurrent kind carries as many
arrays a layer as its ``state_keys`` names (Mamba-2 and KDA two, this one),
and ``decoder.forward`` hands the mixer what the keys name and takes as many
back. ``v`` is rounded to the tail's type BEFORE the convolution reads it, in
the slice as in the tail, so that what a token's output is made of does not
depend on whether its neighbours came in the same call: a prompt's tails and
logits are the same however it was chunked, a chunk shorter than the kernel
included (``tests/unit/test_shortconv.py``). The three rules of
``mamba2.py``'s state hold, by the same means:

- a pad column (``s >= n_valid[b]``) never enters the tail, and a row that
  is not decoding (``n_valid[b] == 0``) keeps its tail EXACTLY as it lies;
- a row whose frontier is 0 starts from zeros whatever its slot holds;
- nothing is rolled back by not advancing ``pos``: speculation and prefix
  sharing are refused for a model that has it (``adapters/decoder.py``),
  though a snapshot of two rows would be cheap (ROADMAP.md).

ONE convolution, two ways to cut the tail. The slice with the row's tail in
front of it, ``[tail | v]``, is convolved as it stands (one token: three
multiply-adds a channel). The tail AFTER the call is rows ``n_valid ..
n_valid + K - 2`` of that: for one token a SELECT between the tail moved up
a row and the tail as it was, for a slice a select-and-sum over the slice's
rows. Neither is a gather a row, which the chip's compiler runs as a loop
over the rows, a layer, an iteration (``kda.mixer``, PR 42). Plain
``jax.numpy``: the update moves 8 KB a slot a layer, so there is no kernel.

Regions of a trace (``jax.named_scope``): ``shortconv`` holding ``in_proj``
(and the input gate), ``conv`` (the convolution, the output gate and the
tail) and ``out_proj``.
"""

import jax
import jax.numpy as jnp


def state_key(j):
    return "slot_shortconv{}".format(j)


def state_keys(j):
    return (state_key(j),)


def state_shapes(cfg):
    """A row's recurrent state, as ``cache_spec().slot_state`` names it:
    ``((key, shape a row, dtype), ...)``, empty for a model with no such
    layer."""
    return tuple((state_key(j), (cfg.shortconv_kernel - 1, cfg.hidden_size),
                  cfg.dtype) for j in range(len(cfg.shortconv_layers)))


def init_layer(key, cfg):
    """One layer's parameters: the projections normal at
    ``initializer_range``, the convolution as PyTorch's ``Conv1d`` default
    (uniform at ``1 / sqrt(K)``), ``conv_w[K - 1]`` the current token's
    tap."""
    c, k, dt = cfg.hidden_size, cfg.shortconv_kernel, cfg.dtype
    ks = jax.random.split(key, 3)
    bound = 1.0 / k ** 0.5
    return {
        "in_proj": cfg.initializer_range * jax.random.normal(
            ks[0], (c, 3 * c), dt),
        "conv_w": jax.random.uniform(ks[1], (k, c), jnp.float32,
                                     -bound, bound).astype(dt),
        "out_proj": cfg.initializer_range * jax.random.normal(
            ks[2], (c, c), dt),
    }


def convolve(v, tail, weight):
    """``v`` [B, S, C] with ``tail`` [B, K - 1, C] before it, both in one
    type: (the causal depthwise convolution [B, S, C] float32, ``[tail | v]``
    [B, K - 1 + S, C] as it was convolved)."""
    s = v.shape[1]
    full = jnp.concatenate([tail, v], axis=1)
    full32, w32 = full.astype(jnp.float32), weight.astype(jnp.float32)
    return sum(w32[j] * full32[:, j:j + s]
               for j in range(weight.shape[0])), full


def tail_after(full, tail, n_valid):
    """The tail after ``n_valid[b]`` of the slice's rows: rows ``n_valid ..
    n_valid + K - 2`` of ``full`` = ``[tail | v]`` (module docstring), by a
    select. A row with ``n_valid`` 0 keeps ``tail``, whatever ``full``
    starts with."""
    keep = tail.shape[1]
    live = (n_valid > 0)[:, None, None]
    if full.shape[1] == keep + 1:           # one token: up a row, or not
        return jnp.where(live, full[:, 1:], tail)
    at = jnp.arange(full.shape[1])[None, :, None]
    rows = [jnp.sum(jnp.where(at == (n_valid + r)[:, None, None], full, 0),
                    axis=1) for r in range(keep)]
    return jnp.where(live, jnp.stack(rows, axis=1).astype(tail.dtype), tail)


def mixer(p, cfg, hid, tail, pos, n_valid):
    """The mixer of one gated short convolution layer.

    ``p`` the layer's parameters, ``hid`` [B, S, C] the normed stream,
    ``tail`` [B, K - 1, C] the rows' tail of this layer (module docstring),
    ``pos`` [B] the frontiers before this call, ``n_valid`` [B] how many
    leading columns of each row are real (0: the row does not move).
    Returns (out [B, S, C] in the compute type, tail)."""
    dt_ = cfg.dtype
    with jax.named_scope("in_proj"):
        b_in, c_out, z = jnp.split(hid @ p["in_proj"].astype(dt_), 3, axis=-1)
        v = (b_in.astype(jnp.float32) * z.astype(jnp.float32)).astype(
            tail.dtype)
    with jax.named_scope("conv"):
        start = jnp.where((pos == 0)[:, None, None], jnp.zeros_like(tail),
                          tail)
        conv, full = convolve(v, start, p["conv_w"])
        y = (c_out.astype(jnp.float32) * conv).astype(dt_)
        tail = tail_after(full, tail, n_valid)
    with jax.named_scope("out_proj"):
        return y @ p["out_proj"].astype(dt_), tail
