"""``compile/backend`` spans before the window: the programs the set-up
compiled or read from the cache (1,041 and more at 128 slots).
``benchmark/setup_reduce.py`` has the cut."""

from benchmark import setup_reduce


def read(run):
    return setup_reduce.reading(run, "programs")
