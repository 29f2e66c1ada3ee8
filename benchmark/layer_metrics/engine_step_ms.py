"""Median wall time of engine.step(), the benchmark's own span, over the window."""


def read(run):
    return run["values"].get("engine_step_ms")
