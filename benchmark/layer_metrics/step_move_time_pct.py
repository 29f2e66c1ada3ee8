"""Device self time of pure data movement (copy, slice, dynamic-slice,
dynamic-update-slice and fusions of nothing else) in the traced serving
steps OUTSIDE the decode scan, over device busy time: the whole-arena layout
copies where a step enters and leaves (PERF.md section 7), which
``kv_move_time_pct`` does not read."""

from benchmark import scope_reduce


def read(run):
    return 100.0 * scope_reduce.of_run(run)["move_outside_s"] \
        / run["trace"]["busy_s"]
