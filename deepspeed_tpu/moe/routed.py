"""Exact top-k routed experts for serving: no capacity, nothing dropped.

``sharded_moe.py`` is the GShard recipe for TRAINING (a ``[tokens, experts,
capacity]`` one-hot, top-1 and top-2, tokens over capacity dropped). A served
model's router is part of its mathematics: every token is computed by exactly
the ``k`` experts its router weights are largest for, whatever its
neighbours in the batch chose. That is what the adapter protocol's per-row
independence asks too (a replayed request lands beside other rows and must
emit the same stream).

``route`` is the published router: softmax in float32 over ALL experts, the
``k`` largest kept, renormalised only where the configuration says so
(renormalised, it is the softmax over the kept logits alone: Granite's
router). ``route_grouped`` is the DeepSeek-V3 router (``noaux_tc``): sigmoid
scores, a selection bias, the choice limited to the best groups of experts.
``dispatch`` turns the choice into a gate over the experts THIS
chip holds and counts their load; ``expert_ffn`` is the feed-forward of the
chosen experts, ``sum_j w[t, j] * f_e(x_t)`` with ``e = experts[t, j]``, in
one of TWO FORMS the configuration names (``act``): ``"swiglu"``, the gated
``f_e(x) = down_e(silu(gate_e(x)) * up_e(x))`` over ``w_gate_up [E, C, 2F]``
(three matrices an expert), or ``"relu2"``, the ungated ``f_e(x) =
down_e(relu(up_e(x)) ** 2)`` over ``w_up [E, C, F]`` (two: Nemotron-H's).

THE EXPERTS HELD. A layer is told which of the router's experts it holds: a
contiguous range ``first .. first + held - 1`` (``dispatch``'s arguments),
all of them for a model one chip holds whole (OLMoE here), a chip's share
of an expert-parallel layer otherwise (Granite 4.0-H Small: 36 of 72). The
router runs over ALL experts either way; the layer computes the terms of
the experts it holds and leaves out what the absent ones would add (another
chip's part of the sum, which an all-to-all would bring: nothing here
stands in for it). ``shared_ffn`` is the dense feed-forward of the same form
every token takes beside its routed experts.
"""

import jax
import jax.numpy as jnp


def route(logits, k, renormalise=False):
    """``[T, E]`` float32 router logits -> (weights ``[T, k]`` float32,
    experts ``[T, k]`` int32). The softmax runs over all ``E`` experts
    before the cut, so the kept weights sum to less than 1 unless
    ``renormalise`` (``norm_topk_prob``) divides them by their sum."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


def route_grouped(logits, bias, k, n_group, topk_group, scale=1.0,
                  renormalise=True):
    """The group-limited sigmoid router (DeepSeek-V3, ``topk_method``
    ``noaux_tc``): ``[T, E]`` float32 logits and the selection bias ``[E]``
    -> (weights ``[T, k]`` float32, experts ``[T, k]`` int32).

    ``s = sigmoid(logits)`` scores every expert; ``s + bias`` CHOOSES and is
    used for nothing else (the bias balances load without an auxiliary loss,
    and must not reach the output). The ``E`` experts lie in ``n_group``
    groups of ``E / n_group`` neighbours (a node's experts in the published
    deployment); a group's score is the sum of its 2 largest ``s + bias``,
    only the ``topk_group`` best groups stay eligible (the others' scores
    are set to 0, as the published code does, so an eligible expert whose
    biased score is negative can lose to a cut one: kept as published),
    and of them the ``k`` largest are chosen. The weights are the chosen
    experts' ``s`` WITHOUT the bias, divided by their sum where
    ``renormalise`` (``norm_topk_prob``), times ``scale``
    (``routed_scaling_factor``)."""
    t, e = logits.shape
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    choose = scores + bias.astype(jnp.float32)
    grouped = choose.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, topk_group)        # [T, topk_group]
    eligible = jnp.any(best[:, :, None] == jnp.arange(n_group), axis=1)
    choose = jnp.where(eligible[:, :, None], grouped, 0.0).reshape(t, e)
    _, experts = jax.lax.top_k(choose, k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * scale, experts.astype(jnp.int32)


def dispatch(weights, experts, held, first=0):
    """``route``'s choice as a dense gate over the ``held`` experts
    ``first .. first + held - 1``: (``[T, held]`` float32, an expert's
    weight for the tokens that chose it and 0 elsewhere; tokens routed to
    each held expert ``[held]`` float32, the load gauges). A choice that
    fell on an expert held elsewhere is in neither: the caller counts
    ``T * k - sum(load)`` of them. A compare against the expert index and a
    sum over ``k``: one small fusion, where a scatter costs a decode
    iteration 16 us a layer."""
    with jax.named_scope("dispatch"):
        chosen = experts[..., None] == jnp.arange(first, first + held)
        gate = jnp.sum(jnp.where(chosen, weights[..., None], 0.0), axis=1)
        return gate, jnp.sum(chosen, axis=(0, 1)).astype(jnp.float32)


def first_matrix(act):
    """(the name of an expert's FIRST matrix after ``w_`` / ``shared_``, how
    many hidden widths its columns hold) in the form ``act``: gate and up
    side by side, or up alone."""
    return ("up", 1) if act == "relu2" else ("gate_up", 2)


def _activate(h, act):
    """An expert's hidden value from its first matmul's output ``h``
    [.., 2F] (``"swiglu"``: gate then up) or [.., F] (``"relu2"``)."""
    if act == "relu2":
        return jnp.square(jax.nn.relu(h))
    f = h.shape[-1] // 2
    return jax.nn.silu(h[..., :f]) * h[..., f:]


def expert_ffn(x, gate, w_in, w_down, act="swiglu"):
    """The chosen experts' feed-forward, summed with the router's weights.
    x ``[T, C]``; gate ``[T, E]`` (``dispatch``; ``E`` the experts held);
    w_in ``[E, C, 2F]`` (``"swiglu"``: gate then up) or ``[E, C, F]``
    (``"relu2"``: up alone); w_down ``[E, F, C]``. Returns ``[T, C]`` in x's
    type.

    Every held expert computes every token, and the gate (0 for an expert a
    token did not choose) picks the sum: plain matmuls (``combine``
    folds the gate in BEFORE the down projection, so no ``[T, E, C]`` value
    is formed). A decode batch touches nearly every expert anyway (32 rows
    of top-8 of 64 leave 1.4% untouched), so the step is bound by streaming
    the expert weights, and this streams them at 717 to 742 GB/s of a v5e's
    819. Rows that follow the routed tokens (sorted by expert,
    ``jax.lax.ragged_dot``) measured 3.6x to 4.7x slower at 32 and at 128
    tokens: the grouped matmul wants its layer of the stacked weights copied
    out first (PERF.md section 6, PR 27). A chip's share of a sharded layer
    is touched as fully (64 rows of top-10 of 72 leave 0.007% of 36 held
    experts untouched; 64 of top-6 of 128 leave 4.6% of 16), so the same
    holds there; a deployment that prefills
    thousands of tokens a call is where grouping pays, and is not served
    here yet."""
    with jax.named_scope("experts"):
        h = _activate(jnp.einsum("tc,ecf->tef", x, w_in.astype(x.dtype)),
                      act)
    with jax.named_scope("combine"):
        h = (h.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    with jax.named_scope("experts"):
        return jnp.einsum("tef,efc->tc", h, w_down.astype(x.dtype))


def shared_ffn(x, w_in, w_down, act="swiglu"):
    """The shared expert: the same form, every token, weight 1.
    x ``[T, C]``; w_in ``[C, 2F]`` (gate then up) or ``[C, F]`` (``act``
    ``"relu2"``); w_down ``[F, C]``."""
    with jax.named_scope("shared"):
        return _activate(x @ w_in.astype(x.dtype), act) \
            @ w_down.astype(x.dtype)
