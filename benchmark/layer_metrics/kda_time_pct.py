"""Device self time under the region ``kda`` (a Kimi Delta Attention layer's
norm, its projections, the convolutions, the gates, the recurrence, the
gated norm and the output projection) over device busy time. None for a
program without the region."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "kda")
