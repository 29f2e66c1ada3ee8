"""SELF time of ``setup/engine_init``: the engine's constructor less the
``compile/*`` spans inside it (the pool's and the optimizer state's
allocation, the weights' placing).
``benchmark/setup_reduce.py`` has the cut."""

from benchmark import setup_reduce


def read(run):
    return setup_reduce.reading(run, "engine_init_s")
