"""Operations and bytes of the Nemotron-H family's own layers, from shapes
alone (``costs.py`` holds the ones every family shares; this file is the
family's, so that no later PR that claims a gain can move its denominators).
``experts_touched`` (how many held experts a call routes a token to, in
expectation: 16 x (1 - (1 - 6 / 128) ** 64) = 15.26 at the cell's sizes) is
Granite's function, the same share of an expert-parallel layer.
"""

from benchmark.costs_granitemoehybrid import experts_touched


def ssm_grouped_update_bytes(rows, heads, head_dim, d_state, groups,
                             state_bytes=4):
    """HBM bytes ONE call of the one-token state-space update must move (one
    Mamba-2 layer with ``groups`` groups of ``B`` and ``C``, ``rows`` slots):
    every row's state ``[d_state, heads x head_dim]`` is read once and
    written once, whatever the context and however many groups; beside it
    the token's decay and input a channel, the output ``y`` a channel and a
    ``B`` and a ``C`` a group (float32, kilobytes). The convolution's tail is
    NOT counted: it is moved under the region ``mamba/conv``. Two
    multiply-adds an element, 0.5 FLOP a byte: bound by memory on any
    chip."""
    channels = heads * head_dim
    state = rows * channels * d_state * state_bytes
    vectors = rows * (3 * channels + 2 * groups * d_state) * 4
    return 2 * state + vectors


def expert_relu2_cost(rows, held, published, top_k, hidden, width,
                      dtype_bytes=2):
    """FLOPs and HBM bytes of ONE call of a chip's share of the routed
    UNGATED feed-forward ``down(relu(up(x)) ** 2)`` (one layer, ``rows``
    tokens, ``held`` of ``published`` experts here): of a token's ``top_k``
    choices ``held / published`` fall on this chip in expectation (an up and
    a down matmul each, 2 FLOPs a multiply-add), and the call must read the
    TWO matrices of every held expert it touches once, the tokens once, and
    write its part of the result once."""
    touched = experts_touched(rows, held, published, top_k)
    return {
        "flops": rows * top_k * (float(held) / published)
        * 2 * 2 * hidden * width,
        "bytes": (touched * 2 * hidden * width + 2 * rows * hidden)
        * dtype_bytes,
        "experts_touched": touched,
    }
