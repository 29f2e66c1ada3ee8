"""The decode scan's one-token Mamba-1 selective-scan update against its
roofline, for the decode iterations the trace HOLDS.

Least time of one call: every slot's ``[mamba_d_state, mamba_expand x
hidden_size]`` float32 state read once and written once, beside the token's
vectors (``costs_jamba.selective_scan_bytes``), over the chip's memory
bandwidth: bound by memory. The ``exp`` an element of the state is NOT in the
bound (``costs_jamba``'s docstring). Calls: one a Mamba layer an iteration;
the iterations are counted from the trace as the ``paged_decode`` kernel's
calls over ITS calls an iteration (one an attention layer), not
``trace_steps`` x ``chunk_size`` (PERF.md section 7: a traced tail of K calls
holds K - 1 to K device steps). Measured: device self time of the region
``decode_scan/mamba1/ssm``, BY REGION and not by a kernel's name, so that a
later kernel is read against the same work. None where the configuration
lacks a key read here, the program has no such region or the trace no such
kernel (a parent commit, another family)."""

from benchmark import costs, costs_jamba, scope_reduce

NEEDS = ("mamba_dt_rank", "mamba_d_state", "mamba_expand", "hidden_size",
         "num_hidden_layers", "attn_layer_period", "attn_layer_offset")


def read(run):
    c, config = run["counters"], run["cell"].config
    reduced = scope_reduce.of_run(run)
    measured = sum(
        s for region, s in reduced["scope_s"].items()
        if {"decode_scan", "mamba1", "ssm"} <= set(region.split("/")))
    _, kernel_calls = scope_reduce.kernel_total(reduced["kernels"],
                                                "paged_decode")
    if any(key not in config for key in NEEDS) or not measured \
            or not kernel_calls:
        return None
    attention = sum(
        1 for i in range(config["num_hidden_layers"])
        if i % config["attn_layer_period"] == config["attn_layer_offset"])
    iterations = kernel_calls / float(attention)
    nbytes = costs_jamba.selective_scan_bytes(
        c["slots"], config["mamba_expand"] * config["hidden_size"],
        config["mamba_d_state"])
    calls = iterations * (config["num_hidden_layers"] - attention)
    least = costs.least_seconds(
        0.0, nbytes, costs.device_peaks(run["device"]["kind"]))[0]
    return 100.0 * calls * least / measured
