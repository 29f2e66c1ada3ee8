"""The part of the collectives' time during which no other operation runs
on that chip, as a share of the traced window (worst chip)."""


def read(run):
    if run["counters"].get("chips", 1) < 2:
        return None
    trace = run["trace"]
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
