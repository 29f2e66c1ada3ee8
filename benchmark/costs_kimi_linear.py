"""Operations and bytes of the Kimi Linear family's own layer, from shapes
alone (``costs.py`` holds the ones every family shares; this file is the
family's, so that no later PR that claims a gain can move its denominators).
Its latent attention is ``costs_deepseek_v3``'s ``latent_decode_cost`` and a
chip's share of its routed experts ``costs_granitemoehybrid``'s
``expert_held_cost``: the same layers at other widths.
"""


def kda_update_bytes(rows, heads, d_k, d_v, state_bytes=4):
    """HBM bytes ONE call of the one-token Kimi Delta Attention update must
    move (one KDA layer, ``rows`` slots): every row's state ``[heads, d_k,
    d_v]`` is read ONCE and written ONCE, whatever the context (the delta
    rule's two products, ``S^T k`` and ``S^T q``, can share one read:
    ``models/kda.py``); beside it the token's q, k and log-decay a key
    channel, its v and the output a value channel and beta a head (float32,
    kilobytes). The convolutions' tails are NOT counted: they are moved
    under the region ``kda/conv``, not under the region this is divided by.
    The update is four multiply-adds an element of the state (the decay,
    two sums down the key channels, the rank-one write), 0.5 FLOP a byte
    moved: bound by memory on any chip."""
    state = rows * heads * d_k * d_v * state_bytes
    vectors = rows * heads * (3 * d_k + 2 * d_v + 1) * 4
    return 2 * state + vectors
