"""Device self time under ``moe/shared`` (the dense gated feed-forward every
token takes beside its routed experts) over device busy time. None for a
program without the region."""

from benchmark import scope_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    if "shared" not in reduced["regions"]:
        return None
    shared = sum(s for region, s in reduced["scope_s"].items()
                 if {"moe", "shared"} <= set(region.split("/")))
    return 100.0 * shared / run["trace"]["busy_s"]
