"""The routed expert feed-forward with every expert held whole, in a scan
whose iteration is ``slots x block_length`` ROWS (a pass over a block a slot),
against its roofline, for the iterations the trace HOLDS.

Least time of one call: ``costs_lfm2_moe.expert_stream_cost`` (imported, not
copied) at ``slots x block_length`` rows: the experts touched in expectation
under the cell's uniform tokens, their three matrices read once, and the
rows. Calls: one a layer an iteration (every layer routes); the iterations
are counted from the trace as the ``paged_decode`` kernel's calls over its
calls an iteration (one a layer), as ``expert_stream_roofline`` counts them,
not ``trace_steps`` x ``chunk_size``. Measured: device self time of the
region ``decode_scan/moe/experts``, BY REGION, whatever implements it. None
where the driver counted no block (another kind of traffic), the
configuration lacks a key read here, the program has no such region or the
trace no such kernel (a CPU, a parent commit)."""

from benchmark import costs, costs_lfm2_moe, scope_reduce

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
NEEDS = ("num_experts", "num_experts_per_tok", "hidden_size",
         "moe_intermediate_size", "num_hidden_layers")


def read(run):
    c, config = run["counters"], run["cell"].config
    reduced = scope_reduce.of_run(run)
    measured = sum(
        s for region, s in reduced["scope_s"].items()
        if {"decode_scan", "moe", "experts"} <= set(region.split("/")))
    _, kernel_calls = scope_reduce.kernel_total(reduced["kernels"],
                                                "paged_decode")
    if "block_length" not in c or any(key not in config for key in NEEDS) \
            or not measured or not kernel_calls:
        return None
    cost = costs_lfm2_moe.expert_stream_cost(
        c["slots"] * c["block_length"], config["num_experts"],
        config["num_experts_per_tok"], config["hidden_size"],
        config["moe_intermediate_size"],
        DTYPE_BYTES[config["deployment"]["compute_dtype"]])
    least = costs.least_seconds(
        cost["flops"], cost["bytes"],
        costs.device_peaks(run["device"]["kind"]))[0]
    return 100.0 * kernel_calls * least / measured
