"""Largest (actual submit - due time) over the window's requests."""


def read(run):
    return run["values"].get("gen_late_max_ms")
