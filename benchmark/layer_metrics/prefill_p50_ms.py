"""Median time from admission to the first token of the requests the
traced tail saw with a first token: ``request/first_token`` minus
``request/admitted`` by ``rid`` (for a transition before the tail, the
``prefill_ms`` the request's later instants carry): the wait for the one
prefill lane and the steps the prompt's chunks take."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.request_gap_p50_ms(
        run, "request/admitted", "request/first_token", "prefill_ms")
