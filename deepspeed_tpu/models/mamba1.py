"""The Mamba-1 mixer (Gu & Dao 2023, the SELECTIVE SCAN) as the Jamba family
runs it (AI21: ``model_type`` ``jamba``), for serving.

One layer, for a normed residual stream ``h`` [B, S, C], with ``W =
mamba_expand * C`` channels, a state of ``N`` a channel, a step through a
bottleneck of rank ``R`` and NO heads::

    x | z     = split(h @ in_proj, 2)              in_proj [C, 2W], that order
    x         = silu(causal depthwise conv1d(x, width K) + conv_b)   ONLY x
    d | B | C = split(x @ x_proj, [R, R + N])      x_proj [W, R + 2N]
    d, B, C   = RMSNorm(d), RMSNorm(B), RMSNorm(C) the family's own addition
    dt        = softplus(d @ dt_proj + dt_bias)    [W]: a step a CHANNEL
    A         = -exp(A_log)                        [N, W]
    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] S_t[n, c] + D[c] x_t[c]
    out       = (y * silu(z)) @ out_proj           no norm after the gate

What sets it apart from Mamba-2 (``mamba2.py``): the decay is a CHANNEL AND A
STATE INDEX (``A`` is a matrix, so every element of the state takes an
``exp`` of its own a token, where Mamba-2 has one a head), ``B`` and ``C``
come from a projection of the CONVOLVED stream and not through the
convolution, and the prompt has no matmul form: SSD's duality needs a scalar
decay a head.

WHAT A ROW CARRIES BETWEEN CALLS, for layer ``j`` of the kind: its ``S``
(``slot_sel<j>`` ``[B, N, W]`` float32: the state index on the sublanes, 16 =
two float32 tiles, the channels on the lanes, as Mamba-2's lies; laid ``[W,
N]`` the chip would pad a minor dim of 16 to 128 lanes and move eight times
the bytes. ``dt`` and ``x`` are then row vectors, ``B`` and ``C`` column
vectors, ``A`` is HELD ``[N, W]`` and ``y`` is a sum down the sublanes) and
the last ``K - 1`` rows of ``x`` BEFORE the convolution (``slot_selconv<j>``
``[B, K - 1, W]`` in the compute type). The three rules of ``mamba2.py``'s
state hold, by the same means: a pad column and a row that is not decoding
leave both EXACTLY as they were (``dt`` zeroed: decay 1, input 0; the tail
cut at ``n_valid`` by ``shortconv.tail_after``'s select, not a gather); a row
whose frontier is 0 starts from zeros whatever its slot holds; nothing is
rolled back by not advancing ``pos`` (``adapters/decoder.py`` refuses
speculation and the prefix cache by the kind's name).

ONE recurrence, two forms, plain ``jax.numpy`` both (no kernel: what one
would be worth is what the benchmark's ``selective_scan_roofline`` reads).
``step`` is one token: the decode scan runs it, written so that XLA fuses the
read of the state, the ``exp``, the update, the sum with ``C`` and the write
into one pass. ``scan`` is a prompt (the lane's ``prefill_chunk`` tokens of
one row, and the cache-free ``apply``): EXACT, ``step`` a token at a time in
a ``lax.scan``, because that is what measured fastest on the chip at the
lane's shape, 128 tokens of one row of ``[16, 5120]`` (PERF.md, PR 48): 395
us a layer, 3.1 us a trip of which the state's 328 KB are 0.8. The two forms
that trade trips for work lost to it or tied: BLOCKS of 8 to 64 tokens run
side by side from zero states, their true starts carried over the blocks and
added to each token's output by one parallel pass, 404-426 us (fewer, larger
trips, but the pass takes a second ``exp`` an element of ``[S, N, W]``, and
the ``exp`` is what the vector units are short of); ``lax.associative_scan``
over ``(exp(dt A), dt B x)`` pairs 1,964 us (84 MB of pairs swept a dozen
times). Nothing is ever divided by a cumulative decay. The state after a
prompt does not depend on how the prompt was chunked AT ALL: every chunking
runs the same token steps in the same order. What would beat a trip's 3 us is
a kernel that keeps the state in VMEM over the slice (ROADMAP.md).

TWO THINGS A FAMILY MAY TAKE AWAY OR ASK FOR, both off for Jamba:
``cfg.mamba_inner_norms`` False is Mamba-1 as published, WITHOUT the three
inner RMSNorms (the tree then has no ``dt_norm`` / ``b_norm`` / ``c_norm``);
``mixer(.., hand_y=True)`` also returns ``y`` [B, S, W] float32, the scan's
output with the ``D`` skip and BEFORE the gate: the MEMORY a decoder-hybrid-
decoder stack's gated memory units read (``models/decoder.py`` ``gmu``), a
value of one pass and no state.

Regions of a trace (``jax.named_scope``): ``mamba1`` holding ``in_proj``,
``conv``, ``x_proj`` (with the three norms), ``dt_proj``, ``ssm`` (Mamba-2's
word for the recurrence), ``gate`` and ``out_proj``.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import shortconv

_HIGHEST = jax.lax.Precision.HIGHEST


def ssm_key(j):
    return "slot_sel{}".format(j)


def conv_key(j):
    return "slot_selconv{}".format(j)


def state_keys(j):
    return ssm_key(j), conv_key(j)


def width(cfg):
    return cfg.mamba_expand * cfg.hidden_size


def state_shapes(cfg):
    """A row's recurrent state, as ``cache_spec().slot_state`` names it:
    ``((key, shape a row, dtype), ...)``, empty for a model with no such
    layer."""
    n = len(cfg.mamba1_layers)
    w = width(cfg)
    return tuple((ssm_key(j), (cfg.mamba_state, w), jnp.float32)
                 for j in range(n)) \
        + tuple((conv_key(j), (cfg.mamba_conv - 1, w), cfg.dtype)
                for j in range(n))


def init_layer(key, cfg):
    """One layer's parameters, by Mamba's conventional initialisation: ``A``
    1..16 over the state index in every channel (S4D-real), the step ``dt``
    log-uniform in 0.001..0.1 a channel, ``D`` and the three inner norms at
    1, the convolution as PyTorch's ``Conv1d`` default (uniform at ``1 /
    sqrt(K)``), the projections normal at ``initializer_range``. ``A_log``,
    ``D`` and ``dt_bias`` are float32 whatever the compute type."""
    n, k, r = cfg.mamba_state, cfg.mamba_conv, cfg.mamba_dt_rank
    w, c, dt = width(cfg), cfg.hidden_size, cfg.dtype
    ks = jax.random.split(key, 7)

    def normal(key, shape):
        return cfg.initializer_range * jax.random.normal(key, shape, dt)

    step = jnp.exp(jax.random.uniform(ks[2], (w,), jnp.float32,
                                      jnp.log(0.001), jnp.log(0.1)))
    bound = 1.0 / k ** 0.5
    out = {
        "in_proj": normal(ks[0], (c, 2 * w)),
        "conv_w": jax.random.uniform(ks[1], (k, w), jnp.float32,
                                     -bound, bound).astype(dt),
        "conv_b": jax.random.uniform(ks[5], (w,), jnp.float32,
                                     -bound, bound).astype(dt),
        "x_proj": normal(ks[3], (w, r + 2 * n)),
        "dt_norm": jnp.ones((r,), dt),
        "b_norm": jnp.ones((n,), dt),
        "c_norm": jnp.ones((n,), dt),
        "dt_proj": normal(ks[6], (r, w)),
        # the inverse of softplus at the drawn step
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, n + 1, dtype=jnp.float32))[:, None], (n, w)),
        "D": jnp.ones((w,), jnp.float32),
        "out_proj": normal(ks[4], (w, c)),
    }
    if not getattr(cfg, "mamba_inner_norms", True):
        for name in ("dt_norm", "b_norm", "c_norm"):
            del out[name]
    return out


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(jnp.float32)


def selection(p, cfg, x):
    """What a token's step and its two vectors are, from the convolved
    stream ``x`` [.., W] (float32): (dt [.., W] after the softplus, B, C
    [.., N]), float32 from ``x_proj``'s output on."""
    n, r = cfg.mamba_state, cfg.mamba_dt_rank
    eps = cfg.rms_norm_eps
    with jax.named_scope("x_proj"):
        dbc = jnp.dot(x.astype(cfg.dtype), p["x_proj"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)
        if getattr(cfg, "mamba_inner_norms", True):
            d = _rms(dbc[..., :r], p["dt_norm"], eps)
            bmat = _rms(dbc[..., r:r + n], p["b_norm"], eps)
            cmat = _rms(dbc[..., r + n:], p["c_norm"], eps)
        else:
            d, bmat, cmat = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
    with jax.named_scope("dt_proj"):
        dt = jax.nn.softplus(jnp.dot(
            d, p["dt_proj"].astype(jnp.float32), precision=_HIGHEST)
            + p["dt_bias"])
    return dt, bmat, cmat


def step(x, dt, a, bvec, cvec, state):
    """One token of the recurrence. x, dt ``[B, W]`` (dt 0 for a row that
    must not move: decay 1, input 0, the state exactly as it was), a
    ``[N, W]`` (negative), bvec and cvec ``[B, N]``, state ``[B, N, W]``; all
    float32. Returns (y ``[B, W]`` without the ``D`` term, the state
    after)."""
    state = state * jnp.exp(dt[:, None, :] * a) \
        + bvec[:, :, None] * (dt * x)[:, None, :]
    return jnp.sum(state * cvec[:, :, None], axis=1), state


def scan(x, dt, a, bmat, cmat, state):
    """The recurrence over ``S`` tokens, ``step`` a token at a time (module
    docstring). x, dt ``[B, S, W]`` (dt 0 where a column must not move the
    state), a ``[N, W]``, bmat and cmat ``[B, S, N]``, state ``[B, N, W]``;
    all float32. Returns (y ``[B, S, W]`` without the ``D`` term, the state
    after)."""
    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        y, state = step(x_t, dt_t, a, b_t, c_t, state)
        return state, y

    state, y = jax.lax.scan(token, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bmat, cmat)))
    return jnp.moveaxis(y, 0, 1), state


def mixer(p, cfg, hid, ssm, tail, pos, n_valid, hand_y=False):
    """The mixer of one Mamba-1 layer.

    ``p`` the layer's parameters, ``hid`` [B, S, C] the normed stream,
    ``ssm`` [B, N, W] and ``tail`` [B, K - 1, W] the rows' state and
    convolution tail of this layer (module docstring), ``pos`` [B] the
    frontiers before this call, ``n_valid`` [B] how many leading columns of
    each row are real (0: the row does not move). Returns (out [B, S, C]
    float32, ``out_proj``'s sums as the MXU forms them: the caller casts
    them to its stream's type as it adds them; ssm, tail), and with
    ``hand_y`` the scan's output ``y`` [B, S, W] float32 after them (module
    docstring)."""
    s = hid.shape[1]
    dt_ = cfg.dtype
    with jax.named_scope("in_proj"):
        # float32 out: ``z`` reaches the gate unrounded, ``x`` is rounded
        # once, to the tail's type
        x, z = jnp.split(jnp.matmul(hid, p["in_proj"].astype(dt_),
                                    preferred_element_type=jnp.float32),
                         2, axis=-1)
    fresh = (pos == 0)[:, None, None]
    with jax.named_scope("conv"):
        start = jnp.where(fresh, jnp.zeros_like(tail), tail)
        conv, full = shortconv.convolve(x.astype(tail.dtype), start,
                                        p["conv_w"])
        x = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
        tail = shortconv.tail_after(full, tail, n_valid)
    dt, bmat, cmat = selection(p, cfg, x)
    with jax.named_scope("ssm"):
        valid = jnp.arange(s)[None, :] < n_valid[:, None]
        dt = jnp.where(valid[..., None], dt, 0.0)
        a = -jnp.exp(p["A_log"])
        state = jnp.where(fresh, 0.0, ssm.astype(jnp.float32))
        if s == 1:
            y, state = step(x[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0],
                            state)
            y = y[:, None]
        else:
            y, state = scan(x, dt, a, bmat, cmat, state)
        ssm = state.astype(ssm.dtype)
        y = y + p["D"] * x
    with jax.named_scope("gate"):
        gated = (y * jax.nn.silu(z)).astype(dt_)
    with jax.named_scope("out_proj"):
        out = jnp.matmul(gated, p["out_proj"].astype(dt_),
                         preferred_element_type=jnp.float32)
    return (out, ssm, tail, y) if hand_y else (out, ssm, tail)
