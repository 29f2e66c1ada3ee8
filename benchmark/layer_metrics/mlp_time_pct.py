"""Device self time under the region ``mlp`` (a dense gated feed-forward:
its norm, the gate and up matmul, the silu and the product, the down matmul
and the residual) over device busy time. None for a program without the
region."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "mlp")
