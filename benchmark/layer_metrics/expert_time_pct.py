"""Device self time under the region ``moe`` (the feed-forward norm, the
router, the routed experts and their sum) over device busy time."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "moe")
