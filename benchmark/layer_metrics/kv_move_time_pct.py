"""Device self time of pure data movement (copy, slice, dynamic-slice,
dynamic-update-slice and fusions of nothing else) inside the decode scan,
over device busy time: work that no token needs."""

from benchmark import scope_reduce


def read(run):
    return 100.0 * scope_reduce.of_run(run)["move_scan_s"] \
        / run["trace"]["busy_s"]
