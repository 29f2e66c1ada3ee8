"""SDAR-MoE (``model_type`` ``sdar_moe``: SDAR-30B-A3B-Chat) in plain
``jax.numpy`` and float32: the block, the visibility rule of a model that
generates by DIFFUSION OVER BLOCKS, the family's noisy forward and the
generation loop itself.

Written from the ``JetLM/SDAR-30B-A3B-Chat`` ``config.json`` and the family's
description (a Qwen3-MoE block trained to denoise blocks), from nothing under
``deepspeed_tpu``. A token table; ``num_hidden_layers`` identical pre-norm
blocks; a final RMSNorm; an output head that is its own matrix. No bias. One
block, for a residual stream ``x`` [T, C] at absolute positions 0..T-1::

    n1 = rms(x) * input_layernorm
    q  = rms_head(n1 @ q_proj) * q_norm   an RMSNorm over EACH head's lanes,
    k  = rms_head(n1 @ k_proj) * k_norm   one weight [head_dim] for all heads
    v  = n1 @ v_proj                      (q: H heads, k and v: Hkv stored)
    q, k = rope(q), rope(k)               halves, theta ** (-2i / d)
    h  = x + (softmax(q k^T / sqrt(d), over the keys a query SEES) v) @ o_proj
         query head j reads stored head j // (H / Hkv)
    n2 = rms(h) * post_attention_layernorm
    w  = softmax(n2 @ gate) in float32 over ALL experts, the
         num_experts_per_tok largest kept and renormalised to sum 1
    out = h + sum over the kept experts e of
              w_e * (silu(n2 @ gate_proj_e) * (n2 @ up_proj_e)) @ down_proj_e

VISIBILITY. A key at absolute position ``j`` is seen by a query at ``i`` iff
``j <= (i // L + 1) * L - 1``, ``L`` the block length: causal across blocks,
both ways inside one (``visible``).

THE NOISY FORWARD (the family's training forward, ``noisy_hidden``). A NOISY
copy of the sequence (some positions masked: they carry the mask id's
embedding) is run beside the CLEAN one. A noisy query in block ``b`` reads the
CLEAN stream's keys of all earlier blocks and the NOISY stream's keys of its
own block: what a denoising pass over block ``b`` computes, for every block of
the sequence in one forward.

GENERATION (``generate``). Blocks are absolute, ``[bL, (b + 1) L)``. A prompt
of ``p`` tokens gives its ``(p // L) * L`` leading tokens as finished blocks;
the ``p % L`` left open the first generated block beside masked positions. A
DENOISING pass runs the block against everything before it and itself, reads
at every masked position the logits AT that position (no shift), takes ``t_i =
argmax`` and the confidence ``c_i = softmax(logits_i)[t_i]`` and unmasks the
``L / S`` most confident masked positions (all that are left if fewer; ties to
the lower position): ``low_confidence_static``, ``S`` the denoising steps a
block. Once nothing is masked a COMMIT pass runs the block once more (here,
with no cache, it computes nothing new and is only counted), and generation
moves on by ``L``.

DEPARTURES from the published code, each noted where it is made:
- masked-ness is a boolean carried BESIDE the ids (``masked``), not an id in
  the sequence, and the mask id is left out of the argmax: an id in a prompt
  or a sample that equals the mask id is then just a token;
- a last block that the budget cuts short is denoised WHOLE and the positions
  past the budget are dropped (the published loop generates whole blocks and
  cuts the answer, which is the same tokens);
- ``_moe`` loops over the EXPERTS (every token computes every expert, the sum
  keeps an expert's term for the tokens whose router kept it): the same sum
  term by term as "for each token, its experts", as ``reference/olmoe.py``.

It is handed a tree under the PUBLISHED names (dense kernels ``[in, out]``)::

    embed_tokens [V, C]    norm [C]    lm_head [C, V]
    layers: a CALLABLE that returns an iterable of {input_layernorm,
        q_proj [C, H d], k_proj, v_proj [C, Hkv d], o_proj [H d, C],
        q_norm [d], k_norm [d],
        post_attention_layernorm, gate [C, E], gate_proj [E, C, F],
        up_proj [E, C, F], down_proj [E, F, C]}

``layers()`` may return a generator (one layer's slices at a time; a callable
because the generation loop runs the stack once a pass). On a TPU a
float32 matmul runs in lower precision unless asked otherwise, so every matmul
is traced under ``jax.default_matmul_precision("highest")``.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Query positions one attention call scores at once: [H, QUERY_CHUNK, T]
# float32 scores, so that a sequence of a few thousand positions fits beside
# an engine (32 heads x 512 x 2,304 x 4 B = 151 MB).
QUERY_CHUNK = 512


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(weight)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope(x, positions, theta):
    """x [T, H, D] at absolute ``positions`` [T]."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # [T, 1, D]
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def visible(q_pos, k_pos, block_length):
    """[Tq, Tk] bool: does the query at ``q_pos[i]`` see the key at
    ``k_pos[j]``: ``j <= (i // L + 1) * L - 1``."""
    return k_pos[None, :] <= ((q_pos // block_length + 1)
                              * block_length - 1)[:, None]


def _qkv(n1, p, positions, n_head, n_kv_head, eps, theta):
    """q [H, T, D], k and v [Hkv, T, D] of the normed stream ``n1`` [T, C]:
    the RMSNorm a head on q and k, then the rotation."""
    t = n1.shape[0]
    d = p["q_proj"].shape[1] // n_head
    q = (n1 @ _f32(p["q_proj"])).reshape(t, n_head, d)
    k = (n1 @ _f32(p["k_proj"])).reshape(t, n_kv_head, d)
    v = (n1 @ _f32(p["v_proj"])).reshape(t, n_kv_head, d)
    q = _rope(_rms(q, p["q_norm"], eps), positions, theta)
    k = _rope(_rms(k, p["k_norm"], eps), positions, theta)
    return (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
            v.transpose(1, 0, 2))


def _attend(q, sources, o_proj):
    """q [H, Tq, D] over ``sources``, a list of (k [Hkv, Tk, D], v, seen
    [Tq, Tk] bool): ONE softmax over all the sources' keys a query sees.
    Query head j reads stored head ``j // (H / Hkv)``. -> [Tq, C]."""
    h, tq, d = q.shape
    rep = h // sources[0][0].shape[0]
    keys = [jnp.repeat(k, rep, axis=0).transpose(0, 2, 1)
            for k, _, _ in sources]
    values = jnp.concatenate(
        [jnp.repeat(v, rep, axis=0) for _, v, _ in sources], axis=1)
    out = []
    for a in range(0, tq, QUERY_CHUNK):
        qc = q[:, a:a + QUERY_CHUNK]
        scores = jnp.concatenate([
            jnp.where(seen[a:a + QUERY_CHUNK][None],
                      qc @ k / math.sqrt(d), -jnp.inf)
            for k, (_, _, seen) in zip(keys, sources)], axis=-1)
        out.append(jax.nn.softmax(scores, axis=-1) @ values)
    y = jnp.concatenate(out, axis=1)                          # [H, Tq, D]
    return y.transpose(1, 0, 2).reshape(tq, h * d) @ _f32(o_proj)


def _router(n2, p, top_k, follow=None, follow_gap=0.0):
    """(weights [T, E], 0 for an expert that was not kept, the kept ones
    renormalised to sum 1 (``norm_topk_prob`` true); what a comparison with
    a program needs to know of the choice, a dict of [T] arrays).

    ``follow`` [T, top_k]: the experts a PROGRAM kept for these tokens. Two
    roundings of one stream may keep another expert where the reference's
    own scores leave the choice a near-tie, and both are right; so where the
    program's set is not the reference's and every expert that changed
    sides stands within ``follow_gap`` router logits of the edge of the
    choice (half way between the last logit kept and the first cut) BY THE
    REFERENCE'S OWN LOGITS, the program's experts are kept, weighed by the
    reference's own probabilities. A change from further away is not
    followed. ``gap``: last logit kept less first cut; ``differ`` /
    ``followed``: the program's set was another / was taken; ``far``: how
    far from the edge the furthest expert that changed sides stood."""
    logits = n2 @ _f32(p["gate"])
    probs = jax.nn.softmax(logits, axis=-1)
    rows = jnp.arange(probs.shape[0])[:, None]
    top, idx = jax.lax.top_k(logits, top_k + 1)
    keep = jnp.zeros(probs.shape, bool).at[rows, idx[:, :top_k]].set(True)
    seen = {"gap": top[:, top_k - 1] - top[:, top_k]}
    if follow is not None:
        theirs = jnp.zeros(probs.shape, bool).at[rows, follow].set(True)
        edge = 0.5 * (top[:, top_k - 1] + top[:, top_k])[:, None]
        far = jnp.max(jnp.where(keep != theirs, jnp.abs(logits - edge), 0.0),
                      axis=-1)
        differ = jnp.any(keep != theirs, axis=-1)
        followed = differ & (far <= follow_gap)
        keep = jnp.where(followed[:, None], theirs, keep)
        seen.update(differ=differ, followed=followed, far=far)
    kept = jnp.where(keep, probs, 0.0)
    return kept / jnp.sum(kept, axis=-1, keepdims=True), seen


def _expert(x, gate_proj, up_proj, down_proj):
    return (jax.nn.silu(x @ _f32(gate_proj)) * (x @ _f32(up_proj))) \
        @ _f32(down_proj)


def _moe(n2, p, kept):
    # DEPARTURE (module docstring): the loop runs over the experts.
    def one_expert(total, e):
        term = _expert(n2, p["gate_proj"][e], p["up_proj"][e],
                       p["down_proj"][e])
        return total + kept[:, e][:, None] * term, None

    return jax.lax.scan(one_expert, jnp.zeros_like(n2),
                        jnp.arange(kept.shape[1]))[0]


def moe_per_token(n2, p, top_k):
    """The literal form: for each token, a loop over the experts its router
    kept. For small sizes (the tests)."""
    with jax.default_matmul_precision("highest"):
        kept, _ = _router(_f32(n2), p, top_k)
        rows = []
        for t in range(n2.shape[0]):
            total = jnp.zeros((n2.shape[1],), jnp.float32)
            for e in np.flatnonzero(np.asarray(kept[t])):
                total = total + kept[t, e] * _expert(
                    _f32(n2[t]), p["gate_proj"][e], p["up_proj"][e],
                    p["down_proj"][e])
            rows.append(total)
        return jnp.stack(rows)


def _feed_forward(h, p, top_k, eps, follow, follow_gap):
    n2 = _rms(h, p["post_attention_layernorm"], eps)
    kept, seen = _router(n2, p, top_k, follow, follow_gap)
    return h + _moe(n2, p, kept), dict(seen, ffn_in=n2)


_STATIC = ("n_head", "n_kv_head", "top_k", "eps", "theta", "block_length",
           "follow_gap")


@functools.partial(jax.jit, static_argnames=_STATIC)
def clean_block(x, p, n_head, n_kv_head, top_k, eps, theta, block_length,
                follow=None, follow_gap=0.0):
    """One layer on the CLEAN stream x [T, C] at positions 0..T-1 under the
    block visibility rule -> (x, the layer's rotated keys and its values
    [Hkv, T, D], which the noisy streams read, what ``_router`` saw and the
    normed stream it was handed, ``ffn_in``)."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(x.shape[0])
        q, k, v = _qkv(_rms(x, p["input_layernorm"], eps), p, pos, n_head,
                       n_kv_head, eps, theta)
        h = x + _attend(q, [(k, v, visible(pos, pos, block_length))],
                        p["o_proj"])
        x, seen = _feed_forward(h, p, top_k, eps, follow, follow_gap)
        return x, k, v, seen


@functools.partial(jax.jit, static_argnames=_STATIC)
def noisy_block(xn, k_clean, v_clean, p, n_head, n_kv_head, top_k, eps, theta,
                block_length, follow=None, follow_gap=0.0):
    """One layer on ONE noisy stream xn [T, C], a copy of the whole sequence:
    a query in block ``b`` reads the clean keys before ``b L`` and the noisy
    keys of its own block. -> (xn, what ``_router`` saw)."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(xn.shape[0])
        q, k, v = _qkv(_rms(xn, p["input_layernorm"], eps), p, pos, n_head,
                       n_kv_head, eps, theta)
        block_of = pos // block_length
        earlier = jnp.arange(k_clean.shape[1])[None, :] \
            < (block_of * block_length)[:, None]
        own = block_of[None, :] == block_of[:, None]
        h = xn + _attend(q, [(k_clean, v_clean, earlier), (k, v, own)],
                         p["o_proj"])
        return _feed_forward(h, p, top_k, eps, follow, follow_gap)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, norm, lm_head, eps):
    """Float32 logits [.., V] of residual rows ``x`` [.., C]."""
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ _f32(lm_head)


def _embed(params, ids, masked, mask_id):
    # DEPARTURE (module docstring): masked-ness beside the ids.
    ids = jnp.asarray(ids)
    if masked is not None:
        ids = jnp.where(jnp.asarray(masked), mask_id, ids)
    return _f32(jnp.asarray(params["embed_tokens"])[ids])


def noisy_hidden(params, clean_ids, noisy, mask_id, follow=None, watch=None,
                 **sizes):
    """(2b) The residual rows, before the last norm, of the clean sequence
    ``clean_ids`` [T] and of each noisy copy of it in ``noisy``, a list of
    (ids [T], masked [T] bool). -> (clean [T, C], [noisy [T, C], ...]).

    ``follow``: the experts a program kept, [1 + len(noisy), layers, T,
    top_k] (the clean stream first), followed as ``_router`` says (with
    ``sizes['follow_gap']``). ``watch(layer index, stream index, seen,
    layer's tree)`` is called with what each stream's router saw."""
    x = _embed(params, clean_ids, None, mask_id)
    xs = [_embed(params, ids, masked, mask_id) for ids, masked in noisy]
    for i, layer in enumerate(params["layers"]()):
        chosen = [None] * (1 + len(xs)) if follow is None \
            else [jnp.asarray(f[i]) for f in follow]
        x, k, v, seen = clean_block(x, layer, follow=chosen[0], **sizes)
        if watch is not None:
            watch(i, 0, seen, layer)
        for j, xn in enumerate(xs):
            xs[j], seen = noisy_block(xn, k, v, layer, follow=chosen[j + 1],
                                      **sizes)
            if watch is not None:
                watch(i, j + 1, seen, layer)
    return x, xs


def logits(params, ids, mask_id, masked=None, **sizes):
    """(2a) Float32 logits [T, V] of ONE sequence ``ids`` [T] under the block
    visibility rule, read AT each position; ``masked`` [T] bool marks
    positions that carry the mask id's embedding."""
    x = _embed(params, ids, masked, mask_id)
    for layer in params["layers"]():
        x = clean_block(x, layer, **sizes)[0]
    return head(x, params["norm"], params["lm_head"], sizes["eps"])


def noisy_logits(params, clean_ids, noisy_ids, noisy_masked, mask_id,
                 **sizes):
    """(2b) for small sizes: the logits [T, V] of one noisy copy against the
    clean tokens before each of its blocks."""
    _, (xn,) = noisy_hidden(params, clean_ids, [(noisy_ids, noisy_masked)],
                            mask_id, **sizes)
    return head(xn, params["norm"], params["lm_head"], sizes["eps"])


def unmask(block_logits, masked, a_pass, mask_id):
    """The unmasking rule on ONE block: ``block_logits`` [L, V], ``masked``
    [L] bool -> (tokens [L] the argmax at every position, confidence [L],
    chosen [L] bool: the ``a_pass`` most confident masked positions, ties to
    the lower one, all that are left if fewer)."""
    lg = np.array(block_logits, np.float32)
    lg[:, mask_id] = -np.inf        # DEPARTURE: the mask id is never chosen
    tokens = lg.argmax(axis=-1)
    top = lg.max(axis=-1)
    conf = (1.0 / np.exp(lg - top[:, None]).sum(axis=-1)).astype(np.float32)
    order = sorted(np.flatnonzero(masked), key=lambda i: (-conf[i], i))
    chosen = np.zeros(len(masked), bool)
    chosen[order[:a_pass]] = True
    return tokens, conf, chosen


def generate(params, prompt, max_new, steps, mask_id, **sizes):
    """(2c) The generation loop, for small sizes (no cache: every pass runs
    the whole sequence). -> (tokens [max_new], passes [max_new]: the pass of
    its block in which each token was unmasked, counts: a dict of the passes
    made, the commit passes among them and the tokens unmasked before the
    budget's end)."""
    length = sizes["block_length"]
    prompt = np.asarray(prompt, np.int64)
    p, end = len(prompt), len(prompt) + max_new
    done = prompt[:p // length * length].tolist()       # finished blocks
    block = prompt[len(done):].tolist()
    known = len(block)
    out, out_pass = {}, {}
    counts = {"passes": 0, "commit_passes": 0, "tokens_unmasked": 0}
    while len(done) < end:
        first = len(done)
        ids = np.array(block + [0] * (length - len(block)), np.int64)
        masked = np.arange(length) >= known
        n_pass = 0
        while masked.any():
            seq = np.concatenate([np.asarray(done, np.int64), ids])
            flags = np.concatenate([np.zeros(first, bool), masked])
            lg = np.asarray(logits(params, seq, mask_id, flags,
                                   **sizes))[first:]
            tokens, _, chosen = unmask(lg, masked, length // steps, mask_id)
            for i in np.flatnonzero(chosen):
                ids[i] = tokens[i]
                if first + i < end:     # DEPARTURE: past the budget, dropped
                    out[first + i], out_pass[first + i] = tokens[i], n_pass
                    counts["tokens_unmasked"] += 1
            masked = masked & ~chosen
            n_pass += 1
            counts["passes"] += 1
        counts["passes"] += 1           # the commit pass: nothing to compute
        counts["commit_passes"] += 1
        done += ids.tolist()
        block, known = [], 0
    where = range(p, end)
    return (np.array([out[i] for i in where], np.int64),
            np.array([out_pass[i] for i in where], np.int64), counts)
