"""A chip's share of the routed UNGATED expert feed-forward
(``down(relu(up(x)) ** 2)``, two matrices an expert; Nemotron-H's), in the
decode scan, against its roofline.

Least time: the traced tail's decode iterations (``trace_steps`` x
``chunk_size``) each call the feed-forward once an EXPERT layer (the ``E`` of
``hybrid_override_pattern``: 23 of 52, not every layer) with ``slots`` rows; a
call must read the TWO matrices of the held experts it touches
(``costs_nemotron_h.expert_relu2_cost``: ``held x (1 - (1 - k /
E_published)^rows)`` in expectation under the cell's uniform tokens) and its
rows. Measured: device self time of the region ``decode_scan/moe/experts``, by
region. None where the configuration is not this family's or the program has
no such region (a parent commit)."""

from benchmark import costs, costs_nemotron_h, scope_reduce

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def read(run):
    c, config = run["counters"], run["cell"].config
    if "hybrid_override_pattern" not in config or not c.get("trace_steps"):
        return None
    measured = sum(
        s for region, s in scope_reduce.of_run(run)["scope_s"].items()
        if {"decode_scan", "moe", "experts"} <= set(region.split("/")))
    if not measured:
        return None
    cost = costs_nemotron_h.expert_relu2_cost(
        c["slots"], config["experts_held"][1], config["router_outputs"],
        config["num_experts_per_tok"], config["hidden_size"],
        config["moe_intermediate_size"],
        DTYPE_BYTES[config["deployment"]["compute_dtype"]])
    calls = c["trace_steps"] * c["chunk_size"] \
        * config["hybrid_override_pattern"].count("E")
    least = costs.least_seconds(
        cost["flops"], cost["bytes"],
        costs.device_peaks(run["device"]["kind"]))[0]
    return 100.0 * calls * least / measured
