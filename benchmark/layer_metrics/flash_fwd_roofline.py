"""Flash attention forward: the least time the chip could take for the
``flash_fwd`` calls the trace holds (benchmark/costs.py x calls OBSERVED: a
forward that remat runs again is a call) over their device time."""

from benchmark import scope_reduce


def read(run):
    measured, calls = scope_reduce.kernel_total(
        scope_reduce.of_run(run)["kernels"], "flash_fwd")
    return scope_reduce.flash_roofline_pct(run, "fwd", calls, measured)
