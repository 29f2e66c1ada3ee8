"""Of ``setup_programs``, those compiled for real (the cache was asked and
missed). 0 in a warm run: says whether THIS reading of the seven other
``setup_*`` metrics was warm or cold.
``benchmark/setup_reduce.py`` has the cut."""

from benchmark import setup_reduce


def read(run):
    return setup_reduce.reading(run, "cache_misses")
