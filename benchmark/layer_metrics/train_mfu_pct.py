"""Model FLOP/s utilization: tokens/s/chip times the FLOPs a token requires
(benchmark/costs.py; recomputation not counted) over the chip's peak."""

from benchmark import costs


def read(run):
    rate = run["values"].get("train_tok_s_chip")
    if rate is None:
        return None
    peaks = costs.device_peaks(run["device"]["kind"])
    return 100.0 * rate * run["counters"]["flops_per_token"] \
        / peaks["flops_per_s"]
