"""Flash attention forward + backward: the least time the chip could take
for one step's calls at the cell's shapes (benchmark/costs.py; compute-bound
at T1024 and head size 64: 300 FLOP a byte against the chip's 240) over
their measured device time."""

from benchmark import costs


def read(run):
    c, trace = run["counters"], run["trace"]
    measured = trace["classes"].get("flash", 0.0)
    if not measured or not c.get("trace_steps"):
        return None
    cost = costs.flash_attention_cost(
        c["global_batch"] // c["chips"], c["n_head"], c["seq_len"],
        c["head_dim"])
    peaks = costs.device_peaks(run["device"]["kind"])
    least = sum(costs.least_seconds(cost[k + "_flops"], cost[k + "_bytes"],
                                    peaks)[0] for k in ("fwd", "bwd"))
    return 100.0 * least * c["n_layer"] * c["trace_steps"] / measured
