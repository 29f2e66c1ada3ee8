"""Device self time under the region ``shortconv`` (a gated short convolution
layer's norm, its one input projection and gate, the convolution, the output
gate, the tail a slot carries and the output projection) over device busy
time. None for a program without the region."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "shortconv")
