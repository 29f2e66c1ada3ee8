"""Process start to the first ``compile/*`` or ``setup/engine_init``: the
interpreter, the imports, the chip's start-up and the cache's placement. The
machine's part of ``setup_s``: a regression here is not the PR's.
``benchmark/setup_reduce.py`` has the cut."""

from benchmark import setup_reduce


def read(run):
    return setup_reduce.reading(run, "boot_s")
