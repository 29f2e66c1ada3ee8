"""Union of the ``compile/backend`` spans before the window: the backend's
compiles and the persistent cache's reads.
``benchmark/setup_reduce.py`` has the cut."""

from benchmark import setup_reduce


def read(run):
    return setup_reduce.reading(run, "compile_s")
