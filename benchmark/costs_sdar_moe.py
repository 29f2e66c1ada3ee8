"""Operations and bytes of what generation by diffusion over blocks adds to
the decoder block as its cell runs it, from shapes alone (``costs.py`` holds
the ones every family shares; this file is the family's, so that no later PR
that claims a gain can move its denominators).
"""

from benchmark import costs


def block_attention_cost(context_lens, block_length, heads, head_dim,
                         kv_bytes_per_token):
    """FLOPs and HBM bytes of ONE call of the diffusion scan's paged
    attention (one layer, one PASS for each slot): ``block_length`` query
    rows a slot at the block's first position ``context_lens[s]``, every one
    of them seeing the keys and values of the earlier blocks and of its own
    block, ``context + block_length`` tokens. The keys of a slot are read
    ONCE for all the rows that share them (``rep x block_length`` a stored
    head: 32 here), ``kv_bytes_per_token`` a token as the builder states
    them; each of the ``heads`` query heads of each of the ``block_length``
    rows scores and sums them: 4 x 32 x 128 x 4 = 65,536 FLOP for 2,048 B a
    cached token, 32 FLOP a byte against the v5e's 240: bound by memory."""
    tokens = [n + block_length for n in context_lens]
    one_row = costs.decode_attention_cost(
        tokens, heads, head_dim, kv_bytes_per_token=kv_bytes_per_token)
    return {"flops": block_length * one_row["flops"],
            "bytes": one_row["bytes"]}
