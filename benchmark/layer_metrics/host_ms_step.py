"""The host half of ``engine.step()`` a step: self time of
``inference/schedule`` (expiry, admission, pages, the step's inputs),
``inference/mixed_step`` (the dispatch), ``inference/deliver`` (tokens to
handles, eviction) and what ``inference/step`` spends outside its children.
``inference/harvest`` is the device's half: it waits for the step."""

from benchmark import scope_reduce

SPANS = ("inference/step", "inference/schedule", "inference/mixed_step",
         "inference/deliver")


def read(run):
    return scope_reduce.host_ms_a_step(scope_reduce.of_run(run)["host"],
                                       SPANS, "inference/step")
