"""Union of the ``compile/trace`` spans before the window: Python traced into
jaxprs. What the stack's depth at the first dispatch, unrolled layers and
Pallas bodies re-traced at every call site move.
``benchmark/setup_reduce.py`` has the cut."""

from benchmark import setup_reduce


def read(run):
    return setup_reduce.reading(run, "trace_s")
