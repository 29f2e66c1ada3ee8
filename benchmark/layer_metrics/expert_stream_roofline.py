"""The routed expert feed-forward with EVERY expert held whole, in the decode
scan, against its roofline, for the decode iterations the trace HOLDS.

Least time of one call: ``costs_lfm2_moe.expert_stream_cost`` with ``slots``
rows (``E x (1 - (1 - k / E)^rows)`` experts touched in expectation under the
cell's uniform tokens, their three matrices of ``moe_intermediate_size`` read
once, and the rows). Calls: one an expert layer (``num_hidden_layers -
num_dense_layers``) an iteration; the iterations are counted from the trace
as the ``paged_decode`` kernel's calls over ITS calls an iteration (one a
``full_attention`` layer), not ``trace_steps`` x ``chunk_size`` (PERF.md
section 7: a traced tail of K calls holds K - 1 to K device steps), as
``expert_share_roofline`` counts them by its family's kernel. Measured:
device self time of the region ``decode_scan/moe/experts``, BY REGION,
whatever implements it. Told by WHAT THE CONFIGURATION HOLDS, not by its
family's name: None where it holds a share of its experts (``experts_held``:
``expert_held_roofline`` and ``expert_share_roofline`` read those) or lacks
a key read here, the program has no such region or the trace no such kernel
(a parent commit, a family with another kernel)."""

from benchmark import costs, costs_lfm2_moe, scope_reduce

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
NEEDS = ("layer_types", "num_experts", "num_experts_per_tok", "hidden_size",
         "moe_intermediate_size", "num_hidden_layers", "num_dense_layers")


def read(run):
    c, config = run["counters"], run["cell"].config
    reduced = scope_reduce.of_run(run)
    measured = sum(
        s for region, s in reduced["scope_s"].items()
        if {"decode_scan", "moe", "experts"} <= set(region.split("/")))
    _, kernel_calls = scope_reduce.kernel_total(reduced["kernels"],
                                                "paged_decode")
    if "experts_held" in config or any(key not in config for key in NEEDS) \
            or not measured or not kernel_calls:
        return None
    iterations = kernel_calls / float(
        config["layer_types"].count("full_attention"))
    cost = costs_lfm2_moe.expert_stream_cost(
        c["slots"], config["num_experts"], config["num_experts_per_tok"],
        config["hidden_size"], config["moe_intermediate_size"],
        DTYPE_BYTES[config["deployment"]["compute_dtype"]])
    calls = iterations * (config["num_hidden_layers"]
                          - config["num_dense_layers"])
    least = costs.least_seconds(
        cost["flops"], cost["bytes"],
        costs.device_peaks(run["device"]["kind"]))[0]
    return 100.0 * calls * least / measured
