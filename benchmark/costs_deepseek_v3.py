"""Operations and bytes of the DeepSeek-V3 family's own kernel, from shapes
alone (``costs.py`` holds the ones every family shares; this file is the
family's, so that no later PR that claims a gain can move its denominators).
A chip's share of the routed experts is ``costs_granitemoehybrid``'s
``expert_held_cost``: the same layer at other widths.
"""


def latent_decode_cost(context_lens, heads, rank, rope, dtype_bytes=2):
    """FLOPs and HBM bytes of ONE call of absorbed latent attention in the
    decode scan (one layer, one new token for each slot): every slot reads
    its context's cached latents once, ``rank + rope`` values a token,
    whatever pad the pool stores beside them; each of the ``heads`` query
    heads scores its ``rank + rope`` lanes against every cached token and
    sums the token's first ``rank`` lanes as values, 2 FLOPs a
    multiply-add; the queries carried into the latent are read and the
    latent results written once a slot (a fifth of the bytes at a context
    of 1,200: 128 heads of 1,088 values against 1,200 tokens of 576).
    DeepSeek-V3: 2 x 128 x (576 + 512) = 278,528 FLOP for 1,152 bytes a
    cached token, 242 FLOP a byte against the v5e's 240: on the ridge."""
    total = float(sum(context_lens))
    rows = len(context_lens)
    return {"flops": 2.0 * heads * (2 * rank + rope) * total,
            "bytes": ((rank + rope) * total
                      + rows * heads * (2 * rank + rope)) * dtype_bytes}
