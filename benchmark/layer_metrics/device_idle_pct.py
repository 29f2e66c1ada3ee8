"""1 - union of the device's operation intervals over the traced window,
worst chip."""


def read(run):
    return run["trace"]["idle_pct"]
