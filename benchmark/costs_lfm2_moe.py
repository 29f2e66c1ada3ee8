"""Operations and bytes of the LFM2 family's layers as its cell runs them,
from shapes alone (``costs.py`` holds the ones every family shares; this file
is the family's, so that no later PR that claims a gain can move its
denominators). Both are other families' layers at other numbers: experts
held WHOLE are ``costs_granitemoehybrid``'s ``expert_held_cost`` with every
expert of the router held, grouped-query decode attention ``costs``'s
``decode_attention_cost`` with the bytes a token STORES.
"""

from benchmark import costs, costs_granitemoehybrid


def expert_stream_cost(rows, experts, top_k, hidden, width, dtype_bytes=2):
    """FLOPs and HBM bytes of ONE call of the routed gated feed-forward with
    ALL ``experts`` held (one layer, ``rows`` tokens): under uniform,
    independent routing the call touches ``experts x (1 - (1 - top_k /
    experts)^rows)`` of them in expectation and must read the three matrices
    of each once (at 128 rows of top-4 of 32: all 32 but 1e-6 of one), the
    tokens once, and write the result once; every token's ``top_k`` choices
    are computed here (a gate, an up and a down matmul each, 2 FLOPs a
    multiply-add). LFM2-8B-A1B: 704.6 MB and 11.5 GFLOP a layer, 16 FLOP a
    byte against the v5e's 240: bound by the weight stream."""
    return costs_granitemoehybrid.expert_held_cost(
        rows, experts, experts, top_k, hidden, width, dtype_bytes)


def paged_decode_cost(context_lens, heads, head_dim, kv_bytes_per_token):
    """FLOPs and HBM bytes of ONE call of grouped-query decode attention
    over a paged cache (one layer, one new token for each slot): every slot
    reads the keys and values its context STORES once,
    ``kv_bytes_per_token`` a token (8 stored heads of 64, k and v, bf16:
    2,048 B, whatever the 32 query heads that share them), and each of the
    ``heads`` query heads scores and sums them: 4 x 32 x 64 = 8,192 FLOP for
    2,048 B a cached token, 4 FLOP a byte: bound by memory."""
    return costs.decode_attention_cost(
        context_lens, heads, head_dim, kv_bytes_per_token=kv_bytes_per_token)
