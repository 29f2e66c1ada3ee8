"""Device self time under the region ``lm_head`` (final LayerNorm, head and
loss, forward and backward) over device busy time."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "lm_head")
