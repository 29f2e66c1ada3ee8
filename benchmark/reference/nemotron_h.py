"""Nemotron-H (``model_type`` ``nemotron_h``) in plain ``jax.numpy`` and
float32, from the published ``config.json`` of
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``, the family's published
modelling code (``NemotronHBlock``, ``NemotronHMamba2Mixer``,
``MambaRMSNormGated``, ``NemotronHMOE``, ``NemotronHAttention``) and the
Mamba-2 paper (Dao & Gu 2024).

A token table, ``num_hidden_layers`` layers of ONE branch each, a final
RMSNorm (``norm_f``) and an output head of its own (``lm_head``). The branch
of layer ``i`` is the ``i``-th letter of ``hybrid_override_pattern``: ``M`` a
Mamba-2 mixer, ``E`` a mixture of experts, ``*`` attention. No bias but the
convolution's. For a residual stream ``x`` [T, C]::

    x = embeddings[ids]
    each layer:  x = x + branch_i(rms(x) * norm_i)
    logits = (rms(x) * norm_f) @ lm_head

    M: z | xBC | dt = split(h @ in_proj, [W, W + 2 G N, Hm])   W = Hm * P
       xBC = silu(causal depthwise conv1d(xBC, width K) + conv_bias)
       x_ | B | C = split(xBC, [W, G N, G N]);  B, C: [G, N], head j reads
         group j // (Hm / G)
       dt = softplus(dt + dt_bias);  A = -exp(A_log) a head
       S_t = exp(dt_t A) S_{t-1} + dt_t outer(x_t, B_g(j),t)   a head: [P, N]
       y_t = S_t C_g(j),t + D x_t
       out = (group_rms(y * silu(z)) * gate_norm) @ out_proj: the gate BEFORE
         the norm, the norm over each group's W / G channels apart (the
         mixer's own ``norm``; ``norm`` here is the layer's, of the stream)
    E: logits = h @ gate [T, E] in float32; s = sigmoid(logits); the
       num_experts_per_tok largest of s + e_score_correction_bias are kept
       (n_group = topk_group = 1: no group limit); weights = s of the kept /
       (their sum + 1e-20) * routed_scaling_factor; expert e is NOT gated:
       down_e(relu(up_e h) ** 2); one shared expert of the same form at its
       own width, every token, weight 1; out = routed + shared
    *: q (H heads), k, v (Hkv heads) of D from three projections; scores =
       q . k / sqrt(D), causal softmax, NO positions (the family's attention
       applies no rotary embedding); query head j reads stored head
       j // (H / Hkv); output projection.

No kernels, no cache, no chunking: the recurrence is a token-by-token
``lax.scan`` from a zero state, attention a full masked softmax, the experts a
loop. Independent of ``deepspeed_tpu``: it is handed a tree under the names
above (dense kernels ``[in, out]``, the convolution ``[K, width]`` with tap
``K - 1`` on the current token), ``layers`` an iterable that may be a
generator (at the published widths a layer is cast to float32 one at a time).

THE CHIP'S SHARE (DEPARTURE 1), as arguments. ``held = (first, count)``: the
layer holds the experts ``first .. first + count - 1`` of the router's ``E``
(``up_proj[e]`` / ``down_proj[e]`` for ``e < count`` are theirs). The router
runs over all ``E``; only the held experts' terms are summed and what the
absent ones would add is LEFT OUT, as in the program (model-configs guide,
section 4); the shared expert is whole. ``held = (0, E)`` is the uncut layer,
and the routed parts of the shares ``(0, E/8) .. (7E/8, E/8)`` add up to it.
``vocab = (first, count)``: ids and logits run over the table's rows (the
head's columns) ``first .. first + count - 1``; a table that has exactly
``count`` rows IS the slice.

DEPARTURE 2, as for OLMoE: the loop runs over the EXPERTS, every token
computes every held expert, and the sum keeps an expert's term only for the
tokens whose router kept it: the same sum term by term as a token's own six.

DEPARTURE 3, of memory and not of arithmetic: attention is computed a stored
head's group of query heads at a time, the logits a block of the head's
columns at a time, and the experts' loop asks for ONE expert's two matrices
at a time, for every sequence before the next expert.

WHAT IT SHOWS BESIDE THE LOGITS. ``logits(.., watch=f)`` hands ``f`` what a
comparison on IDENTICAL inputs needs, a layer and a sequence at a time
(``f(layer, sequence, seen)``): the stream before the branch and what the
branch adds (``stream``, ``branch``); for ``M`` the recurrence's inputs and the
state after the last token (``x`` [T, Hm, P], ``dt`` [T, Hm], ``B``, ``C``
[T, G, N], ``A`` [Hm], ``state`` [Hm, P, N]); for ``E`` the normed stream, the
router's logits and the weights kept (``ffn_in``, ``router_logits``, ``kept``
[T, E], 0 for an expert that was cut). ``logits(.., follow=g)``: WHOSE
EXPERTS ARE KEPT. ``g(layer, sequence, router_logits)`` may return the experts
to keep for every token, [T, k] (None: the reference's own); they are weighed
by the reference's own scores. The builder uses it where the reference's own
choice is a near-tie that bf16 rounding of the stream decides either way.

On a TPU a float32 matmul runs in lower precision unless asked otherwise, so
everything is traced under ``jax.default_matmul_precision("highest")``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def layer_kinds(pattern):
    """``hybrid_override_pattern`` -> a kind a layer."""
    return tuple(KINDS[letter] for letter in pattern)


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(weight)


def attention(h, p, n_head, n_kv):
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
        scores = q_g @ k_g.T * d ** -0.5                        # [rep, T, T]
        scores = jnp.where(causal, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_g

    out = jax.lax.map(group, (q, k, v))                         # [Hkv, rep, T, D]
    return out.transpose(2, 0, 1, 3).reshape(t, n_head * d) @ _f32(p["o_proj"])


def mamba(h, p, n_heads, n_groups, d_state, eps, seen=None):
    """The Mamba-2 mixer on one sequence ``h`` [T, C] from a zero state. A
    dict ``seen`` is given the recurrence's inputs and the state after the
    last token (module docstring)."""
    t = h.shape[0]
    k = p["conv_w"].shape[0]
    w = p["out_proj"].shape[0]
    hp, gn = w // n_heads, n_groups * d_state
    z, xbc, dt = jnp.split(h @ _f32(p["in_proj"]), [w, 2 * w + 2 * gn],
                           axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(_f32(p["conv_b"]) + sum(
        _f32(p["conv_w"])[j] * padded[j:j + t] for j in range(k)))
    x, bmat, cmat = jnp.split(xbc, [w, w + gn], axis=-1)
    x = x.reshape(t, n_heads, hp)
    bmat = bmat.reshape(t, n_groups, d_state)
    cmat = cmat.reshape(t, n_groups, d_state)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))               # [T, Hm]
    a = -jnp.exp(_f32(p["A_log"]))
    rep = n_heads // n_groups

    def token(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        b_h, c_h = jnp.repeat(b_t, rep, axis=0), jnp.repeat(c_t, rep, axis=0)
        state = jnp.exp(dt_t * a)[:, None, None] * state + \
            (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_h)

    state, y = jax.lax.scan(token, jnp.zeros((n_heads, hp, d_state)),
                            (x, bmat, cmat, dt))
    if seen is not None:
        seen.update(x=x, dt=dt, B=bmat, C=cmat, A=a, state=state)
    y = (y + _f32(p["D"])[:, None] * x).reshape(t, w)
    # the family's MambaRMSNormGated(group_size = W / n_groups): the gate
    # first, then the norm over each group's channels apart
    y = (y * jax.nn.silu(z)).reshape(t, n_groups, w // n_groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + eps)
    return (y.reshape(t, w) * _f32(p["gate_norm"])) @ _f32(p["out_proj"])


def keep(logits, bias, top_k, scale, normalise=True, experts=None):
    """The router's weights [T, E] from its logits (0 for an expert that is
    not kept): the ``top_k`` largest ``sigmoid(logits) + bias`` kept (or
    ``experts`` [T, k]: module docstring, WHOSE EXPERTS), each weighed by its
    ``sigmoid(logit)`` alone over the kept ones' sum, times ``scale``."""
    scores = jax.nn.sigmoid(logits)
    if experts is None:
        _, experts = jax.lax.top_k(scores + _f32(bias), top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if normalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    rows = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, experts].set(weights * scale)


def _relu2(x, up, down):
    return jnp.square(jax.nn.relu(x @ _f32(up))) @ _f32(down)


@functools.partial(jax.jit, static_argnames=(
    "kind", "n_head", "n_kv", "mamba_heads", "n_groups", "d_state", "eps"))
def mixed(x, p, kind, n_head, n_kv, mamba_heads, n_groups, d_state, eps):
    """A mixer layer on one sequence: x [T, C] -> (x with the branch added,
    what ``watch`` is shown)."""
    with jax.default_matmul_precision("highest"):
        seen = {}
        h = _rms(x, p["norm"], eps)
        branch = mamba(h, p, mamba_heads, n_groups, d_state, eps, seen) \
            if kind == "mamba" else attention(h, p, n_head, n_kv)
        seen.update(stream=x, branch=branch)
        return x + branch, seen


@functools.partial(jax.jit, static_argnames=("eps",))
def routed_in(x, p, eps):
    """The head of an expert layer on one sequence: (the normed stream, the
    router's logits [T, E]). ``p`` holds ``norm`` and ``gate``."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, p["norm"], eps)
        return h, h @ _f32(p["gate"])


@jax.jit
def expert_term(total, h, weight, up, down):
    """``total`` with ONE expert's term: every token computes it, and keeps
    it by the router's weight for that expert [T] (0 where it was cut)."""
    with jax.default_matmul_precision("highest"):
        return total + weight[:, None] * _relu2(h, up, down)


@jax.jit
def shared(h, up, down):
    with jax.default_matmul_precision("highest"):
        return _relu2(h, up, down)


def routed(h, kept, p, held):
    """The held experts' part of the routed sum for one sequence (module
    docstring, DEPARTURES 1 and 2)."""
    first, count = held
    total = jnp.zeros_like(h)
    for e in range(count):
        total = expert_term(total, h, kept[:, first + e], p["up_proj"][e],
                            p["down_proj"][e])
    return total


def expert_parts(x, p, hyper):
    """One expert layer on one sequence, in its parts: (the held experts'
    routed part, the shared expert's) of what the layer adds to ``x``
    [T, C]: what the test of the shares adds up."""
    h, logits = routed_in(_f32(x), {"norm": p["norm"], "gate": p["gate"]},
                          hyper["eps"])
    kept = keep(logits, p["e_score_correction_bias"], hyper["top_k"],
                hyper["routed_scaling_factor"], hyper["norm_topk_prob"])
    return routed(h, kept, p, hyper["held"]), \
        shared(h, p["shared_up"], p["shared_down"])


_HEAD_BLOCKS = 8


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, norm, eps)
        blocks = _HEAD_BLOCKS if head.shape[1] % _HEAD_BLOCKS == 0 else 1
        # a block of the head's columns at a time (DEPARTURE 3)
        out = jax.lax.map(
            lambda cols: h @ _f32(cols),
            head.reshape(head.shape[0], blocks, -1).transpose(1, 0, 2))
        return out.transpose(1, 0, 2).reshape(h.shape[0], head.shape[1])


def logits(params, input_ids, hyper, watch=None, follow=None):
    """Next-token logits ``[B, T, V]`` float32, as a HOST array, a layer at a
    time and in it a sequence and an expert at a time. ``hyper``:
    ``pattern``, ``n_head``, ``n_kv``, ``mamba_heads``, ``n_groups``,
    ``d_state``, ``top_k``, ``norm_topk_prob``, ``routed_scaling_factor``,
    ``held``, ``vocab``, ``eps``. ``watch``, ``follow``: module docstring."""
    ids = np.asarray(input_ids)
    first_id, n_ids = hyper["vocab"]
    table, head = jnp.asarray(params["embeddings"]), \
        jnp.asarray(params["lm_head"])
    if table.shape[0] != n_ids:        # the whole table: take the slice
        table = table[first_id:first_id + n_ids]
        head = head[:, first_id:first_id + n_ids]
    xs = [_f32(table[row]) for row in ids]
    eps = hyper["eps"]
    for i, (kind, layer) in enumerate(zip(layer_kinds(hyper["pattern"]),
                                          params["layers"])):
        if kind != "moe":
            for b, x in enumerate(xs):
                xs[b], seen = mixed(
                    x, layer, kind, hyper["n_head"], hyper["n_kv"],
                    hyper["mamba_heads"], hyper["n_groups"],
                    hyper["d_state"], eps)
                if watch is not None:
                    watch(i, b, seen)
                del seen
            continue
        small = {"norm": layer["norm"], "gate": layer["gate"]}
        hs, kepts, logit = [], [], []
        for b, x in enumerate(xs):
            h, lg = routed_in(x, small, eps)
            experts = None if follow is None else follow(i, b, lg)
            hs.append(h)
            logit.append(lg)
            kepts.append(keep(
                lg, layer["e_score_correction_bias"], hyper["top_k"],
                hyper["routed_scaling_factor"], hyper["norm_topk_prob"],
                None if experts is None else jnp.asarray(experts)))
        first, count = hyper["held"]
        totals = [jnp.zeros_like(h) for h in hs]
        for e in range(count):
            # one expert's matrices at a time, for every sequence
            up, down = layer["up_proj"][e], layer["down_proj"][e]
            for b, h in enumerate(hs):
                totals[b] = expert_term(totals[b], h, kepts[b][:, first + e],
                                        up, down)
        for b, h in enumerate(hs):
            branch = totals[b] + shared(h, layer["shared_up"],
                                        layer["shared_down"])
            if watch is not None:
                watch(i, b, {"stream": xs[b], "branch": branch, "ffn_in": h,
                             "router_logits": logit[b], "kept": kepts[b]})
            xs[b] = xs[b] + branch
    return np.stack([np.asarray(_head(x, params["norm_f"], head, eps))
                     for x in xs])
