"""Operations and bytes of the OLMoE family's own layers, from shapes alone
(``costs.py`` holds the ones every family shares; this file is the family's,
so that no later PR that claims a gain can move its denominators).
"""


def experts_touched(rows, n_experts, top_k):
    """How many of ``n_experts`` a call with ``rows`` tokens routes at least
    one token to, IN EXPECTATION under uniform, independent routing: a token
    misses a given expert with probability ``1 - top_k / n_experts``. The
    benchmark's tokens are uniform and its weights random, so its routing
    is; a skewed mix touches fewer and needs its own count."""
    return min(float(n_experts),
               n_experts * (1.0 - (1.0 - float(top_k) / n_experts) ** rows))


def expert_ffn_cost(rows, n_experts, top_k, hidden, width, dtype_bytes=2):
    """FLOPs and HBM bytes of ONE call of the routed gated feed-forward (one
    layer, ``rows`` tokens): each token is computed by ``top_k`` experts (a
    gate, an up and a down matmul, 2 FLOPs a multiply-add), and the call
    must read the three matrices of every expert it touches once, the
    tokens once, and write the result once. Experts no token chose need not
    be read, so an implementation that reads them anyway scores under 100."""
    touched = experts_touched(rows, n_experts, top_k)
    return {
        "flops": rows * top_k * 3 * 2 * hidden * width,
        "bytes": (touched * 3 * hidden * width + 2 * rows * hidden)
        * dtype_bytes,
        "experts_touched": touched,
    }
