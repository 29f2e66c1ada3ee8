"""Median wait for the one prefill lane of the requests the traced tail saw
with a slice dispatched: a request's first ``request/slice`` minus its
``request/admitted`` by ``rid`` (for a transition before the tail, the
``lane_wait_ms`` the request's later instants carry). The first of the three
parts of ``prefill_p50_ms``: other prompts' slices ahead of it in the lane."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.request_gap_p50_ms(
        run, "request/admitted", "request/slice", "lane_wait_ms")
