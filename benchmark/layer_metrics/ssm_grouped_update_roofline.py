"""The decode scan's one-token GROUPED state-space update (Mamba-2 with
``n_groups`` groups of ``B`` and ``C``; Nemotron-H's) against its roofline.

Least time: the traced tail's decode iterations (``trace_steps`` x
``chunk_size``) each run the update once a Mamba layer (the ``M`` of
``hybrid_override_pattern``) with ``slots`` rows; a call must read and write
every row's float32 state once (``costs_nemotron_h.ssm_grouped_update_bytes``),
which bounds it by memory. Measured: device self time of the region
``decode_scan/mamba/ssm``, BY REGION and not by a kernel's name, so that it
reads the same work whatever implements it. None where the configuration is
not this family's or the program has no such region (a parent commit)."""

from benchmark import costs, costs_nemotron_h, scope_reduce


def read(run):
    c, config = run["counters"], run["cell"].config
    if "hybrid_override_pattern" not in config or not c.get("trace_steps"):
        return None
    measured = sum(
        s for region, s in scope_reduce.of_run(run)["scope_s"].items()
        if {"decode_scan", "mamba", "ssm"} <= set(region.split("/")))
    if not measured:
        return None
    nbytes = costs_nemotron_h.ssm_grouped_update_bytes(
        c["slots"], config["mamba_num_heads"], config["mamba_head_dim"],
        config["ssm_state_size"], config["n_groups"])
    calls = c["trace_steps"] * c["chunk_size"] \
        * config["hybrid_override_pattern"].count("M")
    least = costs.least_seconds(
        0.5 * nbytes, nbytes, costs.device_peaks(run["device"]["kind"]))[0]
    return 100.0 * calls * least / measured
