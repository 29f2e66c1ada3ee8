"""Operations and bytes of the Granite 4.0-H family's own layers, from shapes
alone (``costs.py`` holds the ones every family shares; this file is the
family's, so that no later PR that claims a gain can move its denominators).
"""


def ssm_update_bytes(rows, heads, head_dim, d_state, state_bytes=4):
    """HBM bytes ONE call of the one-token state-space update must move (one
    Mamba layer, ``rows`` slots): every row's state ``[heads, head_dim,
    d_state]`` is read once and written once, whatever the context; beside
    it the token's decay and input a channel, its ``B`` and ``C`` and the
    output ``y`` (float32, kilobytes). The convolution's tail is NOT
    counted: it is moved under the region ``mamba/conv``, not under the
    region this is divided by. The update is two multiply-adds an element,
    0.5 FLOP a byte: bound by memory on any chip."""
    channels = heads * head_dim
    state = rows * channels * d_state * state_bytes
    vectors = rows * (3 * channels + 2 * d_state) * 4
    return 2 * state + vectors


def experts_touched(rows, held, published, top_k):
    """How many of the ``held`` experts (of a router ``published`` wide) a
    call with ``rows`` tokens routes at least one token to, IN EXPECTATION
    under uniform, independent routing: a token misses a given expert with
    probability ``1 - top_k / published``, held or not."""
    return min(float(held),
               held * (1.0 - (1.0 - float(top_k) / published) ** rows))


def expert_held_cost(rows, held, published, top_k, hidden, width,
                     dtype_bytes=2):
    """FLOPs and HBM bytes of ONE call of a chip's share of the routed gated
    feed-forward (one layer, ``rows`` tokens, ``held`` of ``published``
    experts here): of a token's ``top_k`` choices ``held / published`` fall
    on this chip in expectation (a gate, an up and a down matmul each,
    2 FLOPs a multiply-add), and the call must read the three matrices of
    every held expert it touches once, the tokens once, and write its part
    of the result once."""
    touched = experts_touched(rows, held, published, top_k)
    return {
        "flops": rows * top_k * (float(held) / published)
        * 3 * 2 * hidden * width,
        "bytes": (touched * 3 * hidden * width + 2 * rows * hidden)
        * dtype_bytes,
        "experts_touched": touched,
    }
