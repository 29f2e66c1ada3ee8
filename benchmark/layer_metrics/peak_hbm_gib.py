"""Allocator peak (memory_stats peak_bytes_in_use) on the fullest chip."""


def read(run):
    peak = run["device"].get("memory_peak_bytes")
    return None if peak is None else peak / 2.0 ** 30
