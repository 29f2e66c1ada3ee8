"""Device self time under the region ``mamba`` (a state-space layer's norm,
its projections, the convolution, the recurrence and the gated norm) over
device busy time. None for a program without the region."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "mamba")
