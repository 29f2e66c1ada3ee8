"""A chip's share of the routed expert feed-forward, in the decode scan,
against its roofline, for a model whose expert width and expert layers are
not its ``intermediate_size`` and ``num_hidden_layers`` (DeepSeek-V3: leading
dense layers, ``moe_intermediate_size``), and for the decode iterations the
trace HOLDS.

Least time of one call: ``costs_granitemoehybrid.expert_held_cost`` with
``slots`` rows (``held x (1 - (1 - k / E_published)^rows)`` experts touched
in expectation under the cell's uniform tokens, their three matrices read
once; the group limit makes a token's choices dependent, but over uniform
tokens and random weights every expert is still chosen with probability
``k / E_published``). Calls: one an expert layer an iteration; the
iterations are counted from the trace as the ``latent_decode`` kernel's
calls over ITS calls an iteration (one a layer), not ``trace_steps`` x
``chunk_size`` (PERF.md section 7: a traced tail of K calls holds K - 1 to K
device steps).
Measured: device self time of the region ``decode_scan/moe/experts``. None
where the configuration states no share, the program has no such region or
the trace no such kernel."""

from benchmark import costs, costs_granitemoehybrid, scope_reduce

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    c, config = run["counters"], run["cell"].config
    reduced = scope_reduce.of_run(run)
    measured = sum(
        s for region, s in reduced["scope_s"].items()
        if {"decode_scan", "moe", "experts"} <= set(region.split("/")))
    _, kernel_calls = scope_reduce.kernel_total(reduced["kernels"],
                                                "latent_decode")
    if "moe_intermediate_size" not in config or "experts_held" not in config \
            or not measured or not kernel_calls:
        return None
    layers = config["num_hidden_layers"]
    iterations = kernel_calls / float(layers)
    cost = costs_granitemoehybrid.expert_held_cost(
        c["slots"], config["experts_held"][1], config["router_outputs"],
        config["num_experts_per_tok"], config["hidden_size"],
        config["moe_intermediate_size"],
        DTYPE_BYTES[config["deployment"]["compute_dtype"]])
    calls = iterations * (layers - config["first_k_dense_replace"])
    least = costs.least_seconds(
        cost["flops"], cost["bytes"],
        costs.device_peaks(run["device"]["kind"]))[0]
    return 100.0 * calls * least / measured
