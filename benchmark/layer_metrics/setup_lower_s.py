"""Union of the ``compile/lower`` spans before the window: jaxprs lowered to
MLIR modules.
``benchmark/setup_reduce.py`` has the cut."""

from benchmark import setup_reduce


def read(run):
    return setup_reduce.reading(run, "lower_s")
