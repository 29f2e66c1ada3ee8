"""The Mamba-2 mixer (Dao & Gu 2024, "state-space duality") for serving.

One layer, for a normed residual stream ``h`` [B, S, C], with ``H`` heads of
``P`` channels (``W = H * P``, the configuration's heads times head size: not
always ``expand * C``), a state of ``N`` a channel and ``G`` groups
(``cfg.mamba_groups``) of ``H / G`` neighbouring heads that share a ``B`` and
a ``C`` (head ``j`` reads group ``j // (H / G)``)::

    z | xBC | dt = split(h @ in_proj, [W, W + 2GN, H])     (``dt`` from a
                         matrix of its own where ``cfg.mamba_dt_apart``)
    xBC          = silu(causal depthwise conv1d(xBC, width K) + conv_b)
    x | B | C    = split(xBC, [W, GN, GN])          B, C: [G, N]
    dt           = softplus(dt + dt_bias)           A = -exp(A_log), a head
    S_t          = exp(dt_t A) S_{t-1} + dt_t outer(B_g(j),t, x_t)  [N, W]
    y_t          = C_g(j),t . S_t + D x_t
    out          = (RMSNorm(y * silu(z)) * norm) @ out_proj   (gate BEFORE
                              the norm, over each group's W / G channels)

ONE GROUP (Granite 4.0-H) is the form this file had before there were more:
``B`` and ``C`` are ``[N]`` a token, the norm runs over all ``W``, and every
branch on ``G`` below is static, so that stack lowers as it did. With MORE
(Nemotron-H: 64 heads of 64, 8 to a group) a row's state is carried A GROUP
APART, ``[B, G, N, W / G]``: a group's ``B`` and ``C`` are then column vectors
over its own lanes alone (512, four whole lane tiles), the same plain vector
work as one group's. Kept ``[B, N, W]``, the update needs ``B`` and ``C``
spread to ``[B, N, W]`` first, which the TPU compiler materialises (a
broadcast to ``[B, N, G, 512]`` and a reshape that is no bitcast): twice the
state's bytes written and read again a layer, found by compiling the step for
a described v5e.

WHAT A ROW CARRIES BETWEEN CALLS, and nothing else, for Mamba layer ``j``:
its ``S`` (``slot_ssm<j>`` ``[B, N, W]`` float32, ``[B, G, N, W / G]`` with
more groups than one: the state dim on the
sublanes, the heads' channels side by side on the lanes, so that a head's
decay and its input are row vectors, ``B`` and ``C`` column vectors and ``y``
a sum down the sublanes: plain vector work) and the last ``K - 1`` rows of
``xBC`` BEFORE the convolution (``slot_conv<j>`` ``[B, K - 1, W + 2GN]``, in
the compute type). An array a layer of each, because a layer rewrites all of
its own every token: a layer of a stacked array is a value of its own, which
XLA copies out and back (the stacked state did not fit the chip, the stacked
tails were re-laid layer-major and copied whole around every layer's update),
while an array of its own in the scan's carry is updated where it lies.
Unlike keys and values neither has a position axis: there is no "past the
frontier" to hide garbage in, so

- a pad column (``s >= n_valid[b]``) and a row that is not decoding
  (``n_valid[b] == 0``) must leave both EXACTLY as they were: their ``dt`` is
  zeroed (decay 1, input 0) and the convolution's tail is taken at
  ``n_valid``, not at the end of the slice (a row at frontier 0 has nothing
  to keep: it reads as zeros);
- a row whose frontier is 0 starts from zeros whatever its slot holds, so a
  slot is reused without a reset from the host;
- nothing can be rolled back by not advancing ``pos``: speculation and
  prefix sharing need a snapshot of the state and are refused for a model
  that has it (``adapters/decoder.py``).

ONE recurrence, three uses. ``ssd`` is the chunked ("SSD") form: inside a
chunk the outputs are matmuls against a decay-masked ``C B^T``, between
chunks the state is carried; the prefill lane and the cache-free ``apply``
run it. ``step`` is the same recurrence for one token: the decode scan runs
it, one fused read and write of the layer's state (at Granite 4.0-H Small's
sizes 4.19 MB a slot and layer, 2.4 GB over 64 slots and 9 layers, every
iteration: as much traffic as the attention of a dense model over a long
cache). The chunked form in float32 at ``highest`` precision agrees with the
token-by-token one to rounding, so a prompt's state does not depend on how
it was chunked.

Regions of a trace (``jax.named_scope``): ``mamba`` holding ``in_proj``,
``conv``, ``ssm``, ``gate_norm``, ``out_proj``.
"""

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def state_shapes(cfg):
    """A row's recurrent state, as ``cache_spec().slot_state`` names it:
    ``((key, shape a row, dtype), ...)``, empty for a model with no Mamba
    layer."""
    n_mamba = len(cfg.mamba_layers)
    if not n_mamba:
        return ()
    w, g, n = cfg.mamba_heads * cfg.mamba_head_dim, cfg.mamba_groups, \
        cfg.mamba_state
    tail = (cfg.mamba_conv - 1, w + 2 * g * n)
    ssm = (n, w) if g == 1 else (g, n, w // g)     # a group apart
    return tuple((ssm_key(j), ssm, jnp.float32) for j in range(n_mamba)) \
        + tuple((conv_key(j), tail, cfg.dtype) for j in range(n_mamba))


def ssm_key(j):
    return "slot_ssm{}".format(j)


def conv_key(j):
    return "slot_conv{}".format(j)


def state_keys(j):
    return ssm_key(j), conv_key(j)


def init_layer(key, cfg):
    """One Mamba layer's parameters, by Mamba-2's conventional
    initialisation: ``A`` uniform in 1..16, the step ``dt`` log-uniform in
    0.001..0.1 (so a head remembers between one and a thousand tokens),
    ``D`` and the norm at 1, the convolution as PyTorch's ``Conv1d`` default
    (uniform at ``1 / sqrt(K)``), the projections normal at
    ``initializer_range``."""
    h, p, n, k = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state, \
        cfg.mamba_conv
    w, c, dt = h * p, cfg.hidden_size, cfg.dtype
    gn = cfg.mamba_groups * n           # the lanes of B, and of C
    ks = jax.random.split(key, 6)
    step = jnp.exp(jax.random.uniform(ks[2], (h,), jnp.float32,
                                      jnp.log(0.001), jnp.log(0.1)))
    bound = 1.0 / k ** 0.5
    in_proj = cfg.initializer_range * jax.random.normal(
        ks[0], (c, 2 * w + 2 * gn + h), dt)
    # ``mamba_dt_apart``: the same draw, its last ``h`` columns apart
    apart = {"in_proj": in_proj[:, :-h], "dt_proj": in_proj[:, -h:]} \
        if cfg.mamba_dt_apart else {"in_proj": in_proj}
    return dict(apart, **{
        "conv_w": jax.random.uniform(ks[1], (k, w + 2 * gn), jnp.float32,
                                     -bound, bound).astype(dt),
        "conv_b": jax.random.uniform(ks[5], (w + 2 * gn,), jnp.float32,
                                     -bound, bound).astype(dt),
        # the inverse of softplus at the drawn step
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(ks[3], (h,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((h,), jnp.float32),
        "norm": jnp.ones((w,), dt),
        "out_proj": cfg.initializer_range * jax.random.normal(
            ks[4], (w, c), dt),
    })


def causal_conv(xbc, tail, weight, bias, n_valid):
    """``xbc`` [B, S, Cd] before the convolution, ``tail`` [B, K - 1, Cd]
    the rows before it. Returns (``silu(conv + bias)`` [B, S, Cd] float32,
    the tail after ``n_valid[b]`` of the S rows: pad rows never enter it)."""
    k, s = weight.shape[0], xbc.shape[1]
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    full32, w32 = full.astype(jnp.float32), weight.astype(jnp.float32)
    out = bias.astype(jnp.float32) + sum(
        w32[j] * full32[:, j:j + s] for j in range(k))
    new_tail = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
        f, n, k - 1, axis=0))(full, n_valid)
    return jax.nn.silu(out), new_tail.astype(tail.dtype)


def ssd(x, dt, a, bmat, cmat, state, chunk):
    """The recurrence over ``S`` tokens in chunks of ``chunk``.

    x ``[B, S, H, P]``, dt ``[B, S, H]`` (after softplus, 0 where a column
    must not move the state), a ``[H]`` (negative), bmat and cmat
    ``[B, S, N]`` and state ``[B, N, H * P]`` (one group), or ``[B, S, G,
    N]`` and ``[B, G, N, H * P / G]`` (head ``j`` reads group ``j // (H /
    G)``: the heads are then carried ``[G, H / G]``, so that a group's ``C
    B^T`` is formed once); all float32. Returns (y ``[B, S, H, P]`` without
    the ``D`` term, the state after)."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    # the subscripts of a group, of the heads and of the state, and the
    # heads' shape
    g_, h_, s_, heads = ("", "h", "bnhp", (h,)) if bmat.ndim == 3 else (
        "g", "gr", "bgnrp", (bmat.shape[2], h // bmat.shape[2]))
    x, dt, a = x.reshape((b, s) + heads + (p,)), dt.reshape(
        (b, s) + heads), a.reshape(heads)
    carried = state.shape
    state = state.reshape(state.shape[:-1] + heads[-1:] + (p,))
    ys = []
    for lo in range(0, s, chunk):
        sl = slice(lo, min(lo + chunk, s))
        xc, dtc, bc, cc = x[:, sl], dt[:, sl], bmat[:, sl], cmat[:, sl]
        ln = xc.shape[1]
        cum = jnp.cumsum(dtc * a, axis=1)                   # [B, L, H] <= 0
        # decay from after token s to token t, for s <= t
        seg = cum[:, :, None] - cum[:, None, :]             # [B, t, s, H]
        causal = jnp.tril(jnp.ones((ln, ln), bool))[
            (None, slice(None), slice(None)) + (None,) * len(heads)]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        g = jnp.einsum("bt{0}n,bs{0}n->bts{0}".format(g_), cc, bc,
                       precision=_HIGHEST)
        m = g[..., None] * decay * dtc[:, None]
        y = jnp.einsum("bts{0},bs{0}p->bt{0}p".format(h_), m, xc,
                       precision=_HIGHEST)
        y = y + jnp.einsum("bt{0}n,{1}->bt{2}p".format(g_, s_, h_), cc, state,
                           precision=_HIGHEST) * jnp.exp(cum)[..., None]
        rest = jnp.exp(cum[:, -1:] - cum) * dtc              # [B, s, H]
        last = jnp.exp(cum[:, -1])          # [B, H], over the state's N
        last = last[:, None, :, None] if bmat.ndim == 3 \
            else last[:, :, None, :, None]
        state = last * state + jnp.einsum(
            "bs{0}n,bs{1}p->{2}".format(g_, h_, s_), bc, rest[..., None] * xc,
            precision=_HIGHEST)
        ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
    return y.reshape(b, s, h, p), state.reshape(carried)


def step(x, dt, a, bvec, cvec, state):
    """One token of the recurrence. x ``[B, H, P]``, dt ``[B, H]`` (0 for a
    row that must not move: decay 1, input 0, the state exactly as it was),
    bvec and cvec ``[B, N]`` and state ``[B, N, H * P]`` (one group), or
    ``[B, G, N]`` and ``[B, G, N, H * P / G]`` (a group apart: module
    docstring). Returns (y ``[B, H, P]``, the state after)."""
    b, h, p = x.shape
    decay = jnp.repeat(jnp.exp(dt * a), p, axis=1)          # [B, W]
    dtx = (dt[..., None] * x).reshape(b, h * p)
    if bvec.ndim == 3:      # a group's lanes apart, [B, G, 1, W / G]
        decay, dtx = (v.reshape(b, bvec.shape[1], -1) for v in (decay, dtx))
    state = state * decay[..., None, :] + bvec[..., None] * dtx[..., None, :]
    y = jnp.sum(state * cvec[..., None], axis=-2)
    return y.reshape(b, h, p), state


def mixer(p, cfg, hid, ssm, tail, pos, n_valid):
    """The mixer of one Mamba layer.

    ``p`` the layer's parameters, ``hid`` [B, S, C] the normed stream,
    ``ssm`` and ``tail`` the rows' state and convolution tail of this layer
    (``state_shapes``; module docstring), ``pos`` [B] the frontiers before
    this call,
    ``n_valid`` [B] how many leading columns of each row are real (0: the
    row does not move). Returns (out [B, S, C] in the compute type, ssm,
    tail)."""
    b, s, _ = hid.shape
    h, hp, n = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state
    w, dt_, g = h * hp, cfg.dtype, cfg.mamba_groups
    with jax.named_scope("in_proj"):
        proj = hid @ p["in_proj"].astype(dt_)
        if "dt_proj" in p:      # ``cfg.mamba_dt_apart``
            z, xbc = jnp.split(proj, [w], axis=-1)
            dt = hid @ p["dt_proj"].astype(dt_)
        else:
            z, xbc, dt = jnp.split(proj, [w, 2 * w + 2 * g * n], axis=-1)
    fresh = (pos == 0)[:, None, None]
    with jax.named_scope("conv"):
        tail = jnp.where(fresh, jnp.zeros_like(tail), tail)
        xbc, tail = causal_conv(xbc, tail, p["conv_w"], p["conv_b"], n_valid)
        x, bmat, cmat = jnp.split(xbc, [w, w + g * n], axis=-1)
        if g > 1:
            bmat, cmat = bmat.reshape(b, s, g, n), cmat.reshape(b, s, g, n)
    with jax.named_scope("ssm"):
        valid = jnp.arange(s)[None, :] < n_valid[:, None]
        dt = jnp.where(valid[..., None], jax.nn.softplus(
            dt.astype(jnp.float32) + p["dt_bias"]), 0.0)
        a = -jnp.exp(p["A_log"])
        x = x.reshape(b, s, h, hp)
        state = jnp.where(fresh if g == 1 else fresh[..., None], 0.0,
                          ssm.astype(jnp.float32))
        if s == 1:
            y, state = step(x[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0],
                            state)
            y = y[:, None]
        else:
            y, state = ssd(x, dt, a, bmat, cmat, state, cfg.mamba_chunk)
        ssm = state.astype(ssm.dtype)
        y = (y + p["D"][:, None] * x).reshape(b, s, w)
    with jax.named_scope("gate_norm"):
        y = y * jax.nn.silu(z.astype(jnp.float32))
        if g > 1:       # the norm over each group's channels apart
            y = y.reshape(b, s, g, w // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        y = (y.reshape(b, s, w) * p["norm"].astype(jnp.float32)).astype(dt_)
    with jax.named_scope("out_proj"):
        return y @ p["out_proj"].astype(dt_), ssm, tail
