"""Median time from the dispatch of a prompt's last slice to its first token
at the host, of the requests the traced tail saw with a first token:
``request/first_token`` minus ``request/last_slice`` by ``rid`` (for a
transition before the tail, the ``first_lag_ms`` the request's later
instants carry). The third part of ``prefill_p50_ms``: the step in flight
ahead of the slice's own step, that step, and its harvest."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.request_gap_p50_ms(
        run, "request/last_slice", "request/first_token", "first_lag_ms")
