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
router). ``dispatch`` turns the choice into a gate over the experts THIS
chip holds and counts their load; ``expert_ffn`` is the gated feed-forward
of the chosen experts, ``sum_j w[t, j] * down_e(silu(gate_e(x_t)) *
up_e(x_t))`` with ``e = experts[t, j]``.

THE EXPERTS HELD. A layer is told which of the router's experts it holds: a
contiguous range ``first .. first + held - 1`` (``dispatch``'s arguments),
all of them for a model one chip holds whole (OLMoE here), a chip's share
of an expert-parallel layer otherwise (Granite 4.0-H Small: 36 of 72). The
router runs over ALL experts either way; the layer computes the terms of
the experts it holds and leaves out what the absent ones would add (another
chip's part of the sum, which an all-to-all would bring: nothing here
stands in for it). ``shared_ffn`` is the dense gated feed-forward every
token takes beside its routed experts.
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


def expert_ffn(x, gate, w_gate_up, w_down):
    """The chosen experts' gated feed-forward, summed with the router's
    weights. x ``[T, C]``; gate ``[T, E]`` (``dispatch``; ``E`` the experts
    held); w_gate_up ``[E, C, 2F]`` (gate then up); w_down ``[E, F, C]``.
    Returns ``[T, C]`` in x's type.

    Every held expert computes every token, and the gate (0 for an expert a
    token did not choose) picks the sum: three plain matmuls (``combine``
    folds the gate in BEFORE the down projection, so no ``[T, E, C]`` value
    is formed). A decode batch touches nearly every expert anyway (32 rows
    of top-8 of 64 leave 1.4% untouched), so the step is bound by streaming
    the expert weights, and this streams them at 717 to 742 GB/s of a v5e's
    819. Rows that follow the routed tokens (sorted by expert,
    ``jax.lax.ragged_dot``) measured 3.6x to 4.7x slower at 32 and at 128
    tokens: the grouped matmul wants its layer of the stacked weights copied
    out first (PERF.md section 6, PR 27). A chip's share of a sharded layer
    is touched as fully (64 rows of top-10 of 72 leave 0.007% of 36 held
    experts untouched), so the same holds there; a deployment that prefills
    thousands of tokens a call is where grouping pays, and is not served
    here yet."""
    f = w_gate_up.shape[-1] // 2
    with jax.named_scope("experts"):
        gu = jnp.einsum("tc,ecf->tef", x, w_gate_up.astype(x.dtype))
        h = jax.nn.silu(gu[..., :f]) * gu[..., f:]
    with jax.named_scope("combine"):
        h = (h.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    with jax.named_scope("experts"):
        return jnp.einsum("tef,efc->tc", h, w_down.astype(x.dtype))


def shared_ffn(x, w_gate_up, w_down):
    """The shared expert: the same gated form, every token, weight 1.
    x ``[T, C]``; w_gate_up ``[C, 2F]`` (gate then up); w_down ``[F, C]``."""
    f = w_gate_up.shape[-1] // 2
    with jax.named_scope("shared"):
        gu = x @ w_gate_up.astype(x.dtype)
        return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) \
            @ w_down.astype(x.dtype)
