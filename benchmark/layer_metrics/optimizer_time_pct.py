"""Device self time under the region ``optimizer`` (gradient cast, clip,
update) over device busy time. Collectives traced there (ZeRO's gathers of
the updated shards) are left to the sharding layer's metrics."""

import re

from benchmark import scope_reduce, trace_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    if "optimizer" not in reduced["regions"]:
        return None  # a program without the region
    collective = [re.compile(p)
                  for p in trace_reduce.kernel_names()["collective"]]
    seconds = sum(
        s for region, ops in reduced["scope_ops"].items()
        if "optimizer" in region.split("/")
        for op, s in ops.items()
        if not any(p.search(op) for p in collective))
    return 100.0 * seconds / run["trace"]["busy_s"]
