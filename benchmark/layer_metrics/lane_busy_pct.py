"""Share of the traced tail's dispatched steps whose prefill lane carried a
slice: ``request/slice`` instants (at most one a step) over
``inference/mixed_step`` spans; 0 where the lane stood idle through the
tail. None for a program that does not name the lane (no request of the
tail has a wait for it, seen or carried)."""

from benchmark import harness, scope_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    instants = reduced["instants"]
    steps = reduced["host"].get("inference/mixed_step", {}).get("count", 0)
    if not steps or not scope_reduce.request_gaps_ms(
            instants, "request/admitted", "request/slice", "lane_wait_ms"):
        return None
    slices = len(instants.get("request/slice", []))
    harness.note(event="lane_load", slices=slices, steps=steps)
    return 100.0 * slices / steps
