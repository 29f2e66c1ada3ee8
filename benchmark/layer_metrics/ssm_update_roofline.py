"""The decode scan's one-token state-space update against its roofline.

Least time: the traced tail's decode iterations (``trace_steps`` x
``chunk_size``) each run the update once a Mamba layer with ``slots`` rows; a
call must read and write every row's state once
(``costs_granitemoehybrid.ssm_update_bytes``), which bounds it by memory.
Measured: device self time of the region ``decode_scan/mamba/ssm``, BY REGION
and not by a kernel's name, so that it reads the same work whatever
implements it (plain XLA on an array a layer today; an in-place Pallas kernel
on a stacked state measured no faster, PERF.md PR 33). None where
the program has no such region (a parent commit, another family)."""

from benchmark import costs, costs_granitemoehybrid, scope_reduce


def read(run):
    c, config = run["counters"], run["cell"].config
    reduced = scope_reduce.of_run(run)
    measured = sum(
        s for region, s in reduced["scope_s"].items()
        if {"decode_scan", "mamba", "ssm"} <= set(region.split("/")))
    if "mamba_n_heads" not in config or not measured \
            or not c.get("trace_steps"):
        return None
    nbytes = costs_granitemoehybrid.ssm_update_bytes(
        c["slots"], config["mamba_n_heads"], config["mamba_d_head"],
        config["mamba_d_state"])
    calls = c["trace_steps"] * c["chunk_size"] \
        * config["layer_types"].count("mamba")
    least = costs.least_seconds(
        0.5 * nbytes, nbytes, costs.device_peaks(run["device"]["kind"]))[0]
    return 100.0 * calls * least / measured
