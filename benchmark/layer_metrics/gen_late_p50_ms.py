"""Median of (actual submit - due time) over the window's requests: a starved generator is not a fast server."""


def read(run):
    return run["values"].get("gen_late_p50_ms")
