"""Device self time under the region ``rehearsal_gate`` over device busy
time: a reader of a word that ``names/rehearsal.json`` brings, as a family
PR's reader of its router or its expert matmuls will be."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "rehearsal_gate")
