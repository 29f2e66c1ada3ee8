"""Flash attention backward: the least time for the backward passes the
trace holds over the device time of every ``flash_bwd*`` kernel. A pass is
one ``flash_bwd_fused`` call, or one ``flash_bwd_dq`` call with its
``flash_bwd_dkv`` (the split form's two kernels make one pass)."""

from benchmark import scope_reduce


def read(run):
    kernels = scope_reduce.of_run(run)["kernels"]
    measured, _ = scope_reduce.kernel_total(kernels, "flash_bwd")
    passes = sum(scope_reduce.kernel_total(kernels, name)[1]
                 for name in ("flash_bwd_fused", "flash_bwd_dq"))
    return scope_reduce.flash_roofline_pct(run, "bwd", passes, measured)
