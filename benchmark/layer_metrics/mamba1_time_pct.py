"""Device self time under the region ``mamba1`` (a Mamba-1 selective-scan
layer's norm, its projections, the convolution, the three inner norms, the
step, the recurrence, the gate and the output projection) over device busy
time. None for a program without the region."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "mamba1")
