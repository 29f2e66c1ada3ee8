"""Device self time under the region ``prefill_lane`` (the lane's model
pass, its kernel and its write-back into the arena) over device busy time;
0 when no prompt was prefilled in the traced tail."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "prefill_lane")
