"""Device time inside collectives (all-reduce, all-gather, reduce-scatter,
all-to-all, collective-permute) per training step, worst chip."""


def read(run):
    steps = run["counters"].get("trace_steps")
    if not steps or run["counters"].get("chips", 1) < 2:
        return None
    return 1e3 * run["trace"]["collective_s"] / steps
