"""Device self time under the region ``unmask`` of the diffusion scan (the
confidence over the vocabulary at ``slots x block`` rows, the top ``block /
steps`` and the scatter into the block) over device busy time. None for a
program that has no such region."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "unmask")
