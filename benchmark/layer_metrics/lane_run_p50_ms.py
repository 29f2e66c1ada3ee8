"""Median time a prompt's slices took the lane, of the requests the traced
tail saw with their last slice dispatched: ``request/last_slice`` minus the
request's FIRST ``request/slice`` (``slices`` 1) by ``rid``; 0 for a prompt
of one slice (for a first slice before the tail, the ``lane_run_ms`` the
request's later instants carry). The second part of ``prefill_p50_ms``."""

import statistics

from benchmark import harness, scope_reduce

FIRST, LAST = "request/slice", "request/last_slice"


def read(run):
    instants = dict(scope_reduce.of_run(run)["instants"])
    # A tail that opens inside a prompt holds its later slices only.
    instants[FIRST] = [row for row in instants.get(FIRST, [])
                       if int(row[2].get("slices", 0)) == 1]
    gaps = scope_reduce.request_gaps_ms(instants, FIRST, LAST, "lane_run_ms")
    harness.note(event="request_gaps", earlier=FIRST, later=LAST,
                 samples=len(gaps), ms=gaps)
    return statistics.median(gaps) if gaps else None
