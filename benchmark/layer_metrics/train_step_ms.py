"""Median time from one step's loss being ready to the next one's, on the
host's clock, over the window (steady state: steps are dispatched ahead)."""


def read(run):
    return run["values"].get("train_step_ms")
