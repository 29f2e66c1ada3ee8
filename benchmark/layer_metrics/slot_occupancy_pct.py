"""Share of (decode iteration x slot) places that emitted a token, from the engine's own harvest counters over the window."""


def read(run):
    return run["values"].get("slot_occupancy_pct")
