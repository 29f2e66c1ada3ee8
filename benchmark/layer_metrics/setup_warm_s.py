"""``setup/ready`` to the window's opening, less the ``compile/*`` spans inside:
the driver's warm-up on a program that is ready (serving: the steps that
fill the slots, one admission a step).
``benchmark/setup_reduce.py`` has the cut."""

from benchmark import setup_reduce


def read(run):
    return setup_reduce.reading(run, "warm_s")
