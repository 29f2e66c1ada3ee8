"""Kimi Delta Attention ("KDA": Kimi Linear, Moonshot AI 2025), a linear-
attention mixer, for serving.

One layer, for a normed residual stream ``h`` [B, S, C], with ``H`` heads of
``d`` channels for keys and values alike (``W = H * d``), three short causal
depthwise convolutions (width ``K``, no bias), a delta-rule recurrence whose
decay is a CHANNEL's and not a head's, a low-rank forget gate and a low-rank
output gate (both of rank ``d``)::

    q~ | k~ | v = silu(conv_K(h @ wqkv))                 one conv a third
    q = l2norm_head(q~) * d ** -0.5         k = l2norm_head(k~)
    f | o | b   = split(h @ w_low, [d, 2d])              the three low ranks
    g    = -exp(A_log[head]) * softplus(f @ w_fb + dt_bias)   [S, H, d] <= 0
    beta = sigmoid(b)                                          [S, H]
    S'_t = diag(exp(g_t)) S_{t-1}                        [d_k, d_v] a head
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = S_t^T q_t
    out  = (RMSNorm_d(o_t) * norm * sigmoid(o @ w_gb)) @ wo   (norm BEFORE
                                                    the gate, a head)

WHAT A ROW CARRIES BETWEEN CALLS, and nothing else, for KDA layer ``j``: its
``S`` (``slot_kda<j>`` ``[B, H, d_k, d_v]`` float32, 64 KB a head: the key
channels on the sublanes, the value channels on the lanes, so that the decay
and ``k`` are column vectors, ``v``, ``S^T k`` and ``S^T q`` row vectors and
both products sums down the sublanes) and the last ``K - 1`` rows of
``h @ wqkv`` BEFORE the convolutions (``slot_kdaconv<j>`` ``[B, K - 1, 3 W]``,
in the compute type). An array a layer of each, for the reason
``mamba2.py`` gives: a layer rewrites all of its own every token, and a
layer of a stacked array is a value XLA copies out and back. The three rules
of ``mamba2.py``'s state hold, by the same means:

- a pad column (``s >= n_valid[b]``) and a row that is not decoding
  (``n_valid[b] == 0``) leave state and tails EXACTLY as they were: their
  ``beta`` and ``g`` are zeroed (decay 1, nothing written) and the tail is
  taken at ``n_valid``;
- a row whose frontier is 0 starts from zeros whatever its slot holds;
- nothing is rolled back by not advancing ``pos``: speculation and prefix
  sharing are refused for a model that has it (``adapters/decoder.py``).

ONE recurrence, two forms. ``step`` is one token, for the decode scan. With
``u = beta (v - S'^T k)``, ``o = S^T q = S'^T q + (k . q) u``, so ``S'^T k``
and ``S'^T q`` are two sums over the same ``S'`` and ``S = S' + k u^T`` is
the one write; computed as published, ``S^T q`` waits for the write and
reads the new state again. It moves a layer's state ONCE, one read AND one
write, where the Pallas kernel ``kda_update`` runs it
(``ops/transformer/kernels/kda_update.py``: a row's unit of heads stays in
VMEM between the sums and the write, and goes back to where it came from):
a float32 state whose ``d_v`` is whole lane tiles and ``d_k`` whole sublane
tiles, for any number of rows and heads, the frontier-0 select included (a
flag a row, ``fresh``). Any other shape (the tiny configurations of
``tests/``) runs the same algebra in plain ``jax.numpy`` (``step_plain``),
which XLA compiles to a fusion that reads the state for the two sums and a
second that reads it again for the write: the sums must end before the write
can start, and XLA keeps no state between two fusions. One algorithm with a
shape rule, reported as ``kda_update_unit_heads``; no option chooses.
``chunked`` is the lane's form over a slice of tokens, in sub-chunks of
``CHUNK``: with ``G_t`` the running sum of ``g`` inside a sub-chunk and
``U`` the rows ``u_t``,

    (I + diag(beta) tril(A, -1)) U = diag(beta) (V - (K exp(G)) S_0)
    A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])
    O   = (Q exp(G)) S_0 + tril(B) U,   B[t, s] likewise with q_t
    S_L = diag(exp(G_L)) S_0 + (K exp(G_L - G))^T U

a unit triangular solve a head and the state carried between sub-chunks,
float32 at ``highest``. The decay is folded into the products PAIRWISE
(``exp(G_t - G_s)``, s <= t, never above 1) and not into q and k apart:
``exp(-G_s)`` alone overflows float32 at a channel that forgets faster than
e^-88 a sub-chunk, which nothing published forbids. The two forms agree
token by token to rounding, so a prompt's state does not depend on how it
was chunked (``tests/unit/test_kda.py``).

Regions of a trace (``jax.named_scope``): ``kda`` holding ``qkv_proj``,
``conv``, ``gate`` (the two low-rank gates and beta), ``update`` (the
recurrence), ``gate_norm``, ``o_proj``.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import mamba2
from deepspeed_tpu.ops.transformer.kernels import kda_update

_HIGHEST = jax.lax.Precision.HIGHEST
# tokens a sub-chunk of the lane's form solves at once
CHUNK = 16
L2_EPS = 1e-6


def state_key(j):
    return "slot_kda{}".format(j)


def conv_key(j):
    return "slot_kdaconv{}".format(j)


def state_keys(j):
    return state_key(j), conv_key(j)


def state_shapes(cfg):
    """A row's recurrent state, as ``cache_spec().slot_state`` names it:
    ``((key, shape a row, dtype), ...)``, empty for a model with no KDA
    layer."""
    n = len(cfg.kda_layers)
    h, d = cfg.kda_heads, cfg.kda_head_dim
    return tuple((state_key(j), (h, d, d), jnp.float32) for j in range(n)) \
        + tuple((conv_key(j), (cfg.kda_conv - 1, 3 * h * d), cfg.dtype)
                for j in range(n))


def init_layer(key, cfg):
    """One KDA layer's parameters: ``A`` uniform in 1..16 a head and the
    step ``dt`` log-uniform in 0.001..0.1 a channel (the family's
    convention, as ``mamba2.init_layer``: a channel remembers between one
    and a thousand tokens), the norm at 1, the convolutions as PyTorch's
    ``Conv1d`` default, the projections normal at ``initializer_range``."""
    h, d, k = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
    w, c, dt = h * d, cfg.hidden_size, cfg.dtype
    ks = jax.random.split(key, 8)

    def normal(key, shape):
        return cfg.initializer_range * jax.random.normal(key, shape, dt)

    step = jnp.exp(jax.random.uniform(ks[5], (w,), jnp.float32,
                                      jnp.log(0.001), jnp.log(0.1)))
    bound = 1.0 / k ** 0.5
    return {
        "wqkv": normal(ks[0], (c, 3 * w)),
        "conv_w": jax.random.uniform(ks[1], (k, 3 * w), jnp.float32,
                                     -bound, bound).astype(dt),
        # [f_a | g_a | b]: the forget gate's and the output gate's first
        # halves and beta's projection, one matmul
        "w_low": normal(ks[2], (c, 2 * d + h)),
        "w_fb": normal(ks[3], (d, w)),
        "w_gb": normal(ks[4], (d, w)),
        # the inverse of softplus at the drawn step
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(ks[6], (h,), jnp.float32,
                                            1.0, 16.0)),
        "norm": jnp.ones((d,), dt),
        "wo": normal(ks[7], (w, c)),
    }


def l2norm(x):
    """``x / sqrt(sum(x^2) + eps)`` over a head's channels, float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def step(q, k, v, g, beta, state, fresh=None):
    """One token of the recurrence (module docstring: ONE read and ONE write
    of the state where the kernel runs). q, k, v, g ``[B, H, d]``, beta
    ``[B, H]`` (``g`` and ``beta`` 0 for a row that must not move: the state
    exactly as it was), state ``[B, H, d_k, d_v]``; all float32. ``fresh``
    ``[B]`` bool: a row that starts from zeros whatever ``state`` holds of
    it (None: no row). Returns (o ``[B, H, d_v]``, the state after).

    ONE algorithm with a shape rule (``kda_update.supported``): a float32
    state whose ``d_v`` is whole lane tiles and ``d_k`` whole sublane tiles
    takes the Pallas kernel ``kda_update``, in place, for any number of
    rows and heads; any other shape runs ``step_plain``."""
    if kda_update.supported(state.shape, state.dtype):
        return kda_update.kda_update(q, k, v, g, beta, state, fresh)
    return step_plain(q, k, v, g, beta, state, fresh)


def step_plain(q, k, v, g, beta, state, fresh=None):
    """``step`` in ``jax.numpy`` (what a shape the kernel does not take
    runs, and the kernel's reference in the tests)."""
    if fresh is not None:
        state = jnp.where(fresh[:, None, None, None], 0.0, state)
    decayed = state * jnp.exp(g)[..., None]
    s_k = jnp.sum(decayed * k[..., None], axis=-2)
    s_q = jnp.sum(decayed * q[..., None], axis=-2)
    u = beta[..., None] * (v - s_k)
    o = s_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, decayed + k[..., None] * u[..., None, :]


def chunked(q, k, v, g, beta, state):
    """The recurrence over ``S`` tokens in sub-chunks of ``CHUNK`` (module
    docstring). q, k, v, g ``[B, S, H, d]``, beta ``[B, S, H]`` (``g`` and
    ``beta`` 0 where a column must not move the state), state
    ``[B, H, d_k, d_v]``; all float32. Returns (o ``[B, S, H, d_v]``, the
    state after)."""
    b, s, h, d = q.shape
    n = -(-s // CHUNK)
    pad = n * CHUNK - s

    def blocks(x):
        # [B, S, H, ..] -> [n, B, H, CHUNK, ..]; a pad column moves nothing
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, CHUNK) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    lower = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    strict = jnp.tril(jnp.ones((CHUNK, CHUNK), bool), -1)
    eye = jnp.eye(CHUNK, dtype=jnp.float32)

    def block(state, xs):
        qc, kc, vc, gc, bc = xs          # [B, H, L, d]; bc [B, H, L]
        cum = jnp.cumsum(gc, axis=2)                        # G_t <= 0
        # decay from after token s to token t, s <= t: never above 1
        seg = jnp.exp(jnp.where(
            lower[:, :, None], cum[:, :, :, None] - cum[:, :, None], -jnp.inf))
        a = jnp.einsum("bhtc,bhsc,bhtsc->bhts", kc, kc, seg,
                       precision=_HIGHEST)
        qk = jnp.einsum("bhtc,bhsc,bhtsc->bhts", qc, kc, seg,
                        precision=_HIGHEST)
        into = jnp.exp(cum)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bhtc,bhcv->bhtv", kc * into, state, precision=_HIGHEST))
        u = jax.lax.linalg.triangular_solve(
            eye + bc[..., None] * jnp.where(strict, a, 0.0), rhs,
            left_side=True, lower=True, unit_diagonal=True)
        o = jnp.einsum("bhtc,bhcv->bhtv", qc * into, state,
                       precision=_HIGHEST) + jnp.einsum(
            "bhts,bhsv->bhtv", jnp.where(lower, qk, 0.0), u,
            precision=_HIGHEST)
        rest = jnp.exp(cum[:, :, -1:] - cum)                # token s to L
        state = into[:, :, -1, :, None] * state + jnp.einsum(
            "bhsc,bhsv->bhcv", kc * rest, u, precision=_HIGHEST)
        return state, o

    state, o = jax.lax.scan(block, state, tuple(
        blocks(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)           # [B, n, L, H, d]
    return o.reshape(b, n * CHUNK, h, d)[:, :s], state


def gates(p, cfg, hid, n_valid):
    """(g ``[B, S, H, d]`` the log-decay a channel, beta ``[B, S, H]``, both
    float32 and 0 at a column that must not move the state; the output
    gate's low-rank half ``[B, S, d]``) of the normed stream ``hid``."""
    b, s, _ = hid.shape
    h, d, dt_ = cfg.kda_heads, cfg.kda_head_dim, cfg.dtype
    f_a, g_a, beta = jnp.split(hid @ p["w_low"].astype(dt_), [d, 2 * d],
                               axis=-1)
    valid = jnp.arange(s)[None, :] < n_valid[:, None]
    step_ = jax.nn.softplus((f_a @ p["w_fb"].astype(dt_)).astype(jnp.float32)
                            + p["dt_bias"]).reshape(b, s, h, d)
    g = -jnp.exp(p["A_log"])[:, None] * step_
    return jnp.where(valid[..., None, None], g, 0.0), \
        jnp.where(valid[..., None], jax.nn.sigmoid(beta.astype(jnp.float32)),
                  0.0), g_a


def mixer(p, cfg, hid, state, tail, pos, n_valid):
    """The mixer of one KDA layer.

    ``p`` the layer's parameters, ``hid`` [B, S, C] the normed stream,
    ``state`` [B, H, d, d] and ``tail`` the rows' state and convolution tail
    of this layer (module docstring), ``pos`` [B] the frontiers before this
    call, ``n_valid`` [B] how many leading columns of each row are real (0:
    the row does not move). Returns (out [B, S, C] in the compute type,
    state, tail)."""
    b, s, _ = hid.shape
    h, d, dt_ = cfg.kda_heads, cfg.kda_head_dim, cfg.dtype
    with jax.named_scope("qkv_proj"):
        rows = hid @ p["wqkv"].astype(dt_)
    fresh = pos == 0
    with jax.named_scope("conv"):
        tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail), tail)
        qkv, moved = mamba2.causal_conv(rows, tail, p["conv_w"],
                                        jnp.zeros((), jnp.float32), n_valid)
        if s == 1:
            # One token: a live row's tail moves up a row, a SELECT. The
            # slice at ``n_valid`` a row is a gather, which the chip's
            # compiler runs as a loop over the rows, a layer, an iteration
            # (compiled for a described v5e: 212 us a call for 19 MB).
            moved = jnp.where((n_valid > 0)[:, None, None], jnp.concatenate(
                [tail[:, 1:], rows.astype(tail.dtype)], axis=1), tail)
        tail = moved
        q, k, v = (x.reshape(b, s, h, d) for x in jnp.split(qkv, 3, axis=-1))
        q, k = l2norm(q) * d ** -0.5, l2norm(k)
    with jax.named_scope("gate"):
        g, beta, g_a = gates(p, cfg, hid, n_valid)
    with jax.named_scope("update"):
        s32 = state.astype(jnp.float32)
        if s == 1:
            # the frontier-0 select rides in ``step``: outside it is a pass
            # over the state of its own
            o, s32 = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s32,
                          fresh)
            o = o[:, None]
        else:
            o, s32 = chunked(q, k, v, g, beta, jnp.where(
                fresh[:, None, None, None], 0.0, s32))
        state = s32.astype(state.dtype)
    with jax.named_scope("gate_norm"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        gate = jax.nn.sigmoid((g_a @ p["w_gb"].astype(dt_)).astype(
            jnp.float32)).reshape(b, s, h, d)
        y = (o * p["norm"].astype(jnp.float32) * gate).astype(dt_)
    with jax.named_scope("o_proj"):
        return y.reshape(b, s, h * d) @ p["wo"].astype(dt_), state, tail
