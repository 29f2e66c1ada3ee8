"""Exact top-k routed experts for serving: no capacity, nothing dropped.

``sharded_moe.py`` is the GShard recipe for TRAINING (a ``[tokens, experts,
capacity]`` one-hot, top-1 and top-2, tokens over capacity dropped). A served
model's router is part of its mathematics: every token is computed by exactly
the ``k`` experts its router weights are largest for, whatever its
neighbours in the batch chose. That is what the adapter protocol's per-row
independence asks too (a replayed request lands beside other rows and must
emit the same stream).

``route`` is the published router: softmax in float32 over ALL experts, the
``k`` largest kept, renormalised only where the configuration says so.
``dispatch`` turns the choice into a ``[T, E]`` gate and counts the load;
``expert_ffn`` is the gated feed-forward of the chosen experts,
``sum_j w[t, j] * down_e(silu(gate_e(x_t)) * up_e(x_t))`` with ``e =
experts[t, j]``.
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


def dispatch(weights, experts, n_experts):
    """``route``'s choice as a dense gate: (``[T, E]`` float32, an expert's
    weight for the tokens that chose it and 0 elsewhere; tokens routed to
    each expert ``[E]`` float32, the load gauges). A compare against the
    expert index and a sum over ``k``: one small fusion, where a scatter
    costs a decode iteration 16 us a layer."""
    with jax.named_scope("dispatch"):
        chosen = experts[..., None] == jnp.arange(n_experts)    # [T, k, E]
        gate = jnp.sum(jnp.where(chosen, weights[..., None], 0.0), axis=1)
        return gate, jnp.sum(chosen, axis=(0, 1)).astype(jnp.float32)


def expert_ffn(x, gate, w_gate_up, w_down):
    """The chosen experts' gated feed-forward, summed with the router's
    weights. x ``[T, C]``; gate ``[T, E]`` (``dispatch``); w_gate_up
    ``[E, C, 2F]`` (gate then up); w_down ``[E, F, C]``. Returns ``[T, C]``
    in x's type.

    Every expert computes every token, and the gate (0 for an expert a
    token did not choose) picks the sum: three plain matmuls (``combine``
    folds the gate in BEFORE the down projection, so no ``[T, E, C]`` value
    is formed). A decode batch touches nearly every expert anyway (32 rows
    of top-8 of 64 leave 1.4% untouched), so the step is bound by streaming
    the expert weights, and this streams them at 717 to 742 GB/s of a v5e's
    819. Rows that follow the routed tokens (sorted by expert,
    ``jax.lax.ragged_dot``) measured 3.6x to 4.7x slower at 32 and at 128
    tokens: the grouped matmul wants its layer of the stacked weights copied
    out first (PERF.md section 6, PR 27). A deployment that shards experts
    over chips, or prefills thousands of tokens a call, is where grouping
    pays; neither is served here yet."""
    f = w_gate_up.shape[-1] // 2
    with jax.named_scope("experts"):
        gu = jnp.einsum("tc,ecf->tef", x, w_gate_up.astype(x.dtype))
        h = jax.nn.silu(gu[..., :f]) * gu[..., f:]
    with jax.named_scope("combine"):
        h = (h.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    with jax.named_scope("experts"):
        return jnp.einsum("tef,efc->tc", h, w_down.astype(x.dtype))
