"""The decode scan's one-token Kimi Delta Attention update against its
roofline, for the decode iterations the trace HOLDS.

Least time of one call: a KDA layer's update with ``slots`` rows must read
and write every row's float32 matrix state once
(``costs_kimi_linear.kda_update_bytes``), which bounds it by memory. Calls:
one a KDA layer an iteration; the iterations are counted from the trace as
the ``latent_decode`` kernel's calls over ITS calls an iteration (one an MLA
layer), not ``trace_steps`` x ``chunk_size`` (PERF.md section 7: a traced
tail of K calls holds K - 1 to K device steps), as ``expert_share_roofline``
counts them. Measured: device self time of the region
``decode_scan/kda/update``, BY REGION and not by a kernel's name, so that it
reads the same work whatever implements it (plain XLA on an array a layer
today, which reads the state twice: at most 67%). None where the
configuration has no KDA layer, the program no such region or the trace no
such kernel (a parent commit, another family)."""

from benchmark import costs, costs_kimi_linear, scope_reduce


def read(run):
    c, config = run["counters"], run["cell"].config
    linear = config.get("linear_attn_config")
    reduced = scope_reduce.of_run(run)
    measured = sum(
        s for region, s in reduced["scope_s"].items()
        if {"decode_scan", "kda", "update"} <= set(region.split("/")))
    _, kernel_calls = scope_reduce.kernel_total(reduced["kernels"],
                                                "latent_decode")
    if not linear or not measured or not kernel_calls:
        return None
    iterations = kernel_calls / float(len(linear["full_attn_layers"]))
    nbytes = costs_kimi_linear.kda_update_bytes(
        c["slots"], linear["num_heads"], linear["head_dim"],
        linear["head_dim"])
    least = costs.least_seconds(
        0.5 * nbytes, nbytes, costs.device_peaks(run["device"]["kind"]))[0]
    return 100.0 * iterations * len(linear["kda_layers"]) * least / measured
