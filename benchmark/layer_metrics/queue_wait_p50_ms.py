"""Median wait in the engine's queue of the requests the traced tail saw
admitted or in a slot: ``request/admitted`` minus ``request/submitted`` by
``rid`` (for one admitted before the tail, the ``queue_ms`` its later
instants carry)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.request_gap_p50_ms(
        run, "request/submitted", "request/admitted", "queue_ms")
