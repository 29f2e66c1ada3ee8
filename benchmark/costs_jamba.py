"""Operations and bytes of the Jamba family's own layer, from shapes alone
(``costs.py`` holds the ones every family shares; this file is the family's,
so that no later PR that claims a gain can move its denominators).
"""


def selective_scan_bytes(rows, channels, d_state, state_bytes=4):
    """HBM bytes ONE call of the one-token Mamba-1 selective-scan update
    must move (one layer, ``rows`` slots): every row's state ``[d_state,
    channels]`` is read once and written once, whatever the context; beside
    it the token's step ``dt`` and input ``x`` a channel, its ``B`` and ``C``
    and the output ``y`` (float32, kilobytes a row). The convolution's tail
    is NOT counted: it is moved under the region ``mamba1/conv``, not under
    the region this is divided by. NOR ARE THE ``exp``: the update takes one
    a state element (``rows x d_state x channels`` a call, 10.5M at 128 slots
    of [16, 5120]) beside two multiply-adds, on the transcendental unit and
    not on the memory system; the bound is the memory's alone, so a reading
    well under 100 may be the ``exp``'s and not a waste of bandwidth."""
    state = rows * channels * d_state * state_bytes
    vectors = rows * (3 * channels + 2 * d_state) * 4
    return 2 * state + vectors
