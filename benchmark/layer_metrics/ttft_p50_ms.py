"""Median, over the window's requests due up to drain_s before its end, of first token at the host minus the time the request was DUE."""


def read(run):
    return run["values"].get("serve_ttft_p50_ms")
