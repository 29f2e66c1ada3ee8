"""Device self time under the region ``gmu`` (a gated memory unit's mixer:
its LayerNorm, the gate's matmul, the product with the memory the last Mamba
layer's scan handed down, the output matmul and the residual) over device
busy time. None for a program without the region."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "gmu")
