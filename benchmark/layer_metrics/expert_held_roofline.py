"""A chip's share of the routed expert feed-forward, in the decode scan,
against its roofline: as ``expert_ffn_roofline`` with the experts HELD.

Least time: the traced tail's decode iterations (``trace_steps`` x
``chunk_size``) each call the feed-forward once a layer with ``slots`` rows; a
call must read the weights of the held experts it touches
(``costs_granitemoehybrid.expert_held_cost``: ``held x (1 - (1 -
k / E_published)^rows)`` in expectation under the cell's uniform tokens) and
its rows. Measured: device self time of the region
``decode_scan/moe/experts``. None where the configuration states no share
(``experts_held`` / ``router_outputs``) or the program has no such region."""

from benchmark import costs, costs_granitemoehybrid, scope_reduce

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    c, config = run["counters"], run["cell"].config
    reduced = scope_reduce.of_run(run)
    measured = sum(
        s for region, s in reduced["scope_s"].items()
        if {"decode_scan", "moe", "experts"} <= set(region.split("/")))
    if "experts_held" not in config or not measured \
            or not c.get("trace_steps"):
        return None
    cost = costs_granitemoehybrid.expert_held_cost(
        c["slots"], config["experts_held"][1], config["router_outputs"],
        config["num_experts_per_tok"], config["hidden_size"],
        config["intermediate_size"],
        DTYPE_BYTES[config["deployment"]["compute_dtype"]])
    calls = c["trace_steps"] * c["chunk_size"] * c["n_layer"]
    least = costs.least_seconds(
        cost["flops"], cost["bytes"],
        costs.device_peaks(run["device"]["kind"]))[0]
    return 100.0 * calls * least / measured
