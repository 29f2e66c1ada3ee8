"""A per-layer metric that exists only in the tests: added as this one file
and one entry of the manifest."""


def read(run):
    return run["counters"].get("steps")
