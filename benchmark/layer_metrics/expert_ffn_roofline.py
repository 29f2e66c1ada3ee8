"""The routed expert feed-forward of the decode scan against its roofline.

Least time: the traced tail's decode iterations (``trace_steps`` x
``chunk_size``) each call the feed-forward once a layer with ``slots`` rows;
a call must read the weights of the experts it touches
(``costs_olmoe.expert_ffn_cost``: ``E x (1 - (1 - k/E)^rows)`` of them in
expectation under the cell's uniform tokens, the assumption written there)
and its rows, which at these shapes bounds it by memory (``least_seconds``
says which). Measured: device self time of the region
``decode_scan/moe/experts``, the experts' matmuls and their activation, or,
should a family keep a Pallas kernel for them, of class ``grouped_matmul``
inside the scan. None where the program has no such region (a parent commit,
another family)."""

from benchmark import costs, costs_olmoe, scope_reduce

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    c, config = run["counters"], run["cell"].config
    reduced = scope_reduce.of_run(run)
    measured = sum(
        s for region, s in reduced["scope_s"].items()
        if {"decode_scan", "moe", "experts"} <= set(region.split("/")))
    if "num_experts" not in config or not measured \
            or not c.get("trace_steps"):
        return None
    cost = costs_olmoe.expert_ffn_cost(
        c["slots"], config["num_experts"], config["num_experts_per_tok"],
        config["hidden_size"], config["intermediate_size"],
        DTYPE_BYTES[config["deployment"]["compute_dtype"]])
    calls = c["trace_steps"] * c["chunk_size"] * c["n_layer"]
    least = costs.least_seconds(
        cost["flops"], cost["bytes"],
        costs.device_peaks(run["device"]["kind"]))[0]
    return 100.0 * calls * least / measured
