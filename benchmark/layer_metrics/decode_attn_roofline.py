"""Paged decode attention: the bytes of keys and values each call must read
(from the context lengths the benchmark counted, benchmark/costs.py; memory
bound: 1 FLOP a byte) over the chip's bandwidth, over measured device time."""

from benchmark import costs


def read(run):
    c, trace = run["counters"], run["trace"]
    measured = trace["classes"].get("decode_attn", 0.0)
    if not measured or not c.get("trace_context"):
        return None
    peaks = costs.device_peaks(run["device"]["kind"])
    least = 0.0
    for lens in c["trace_context"]:
        for i in range(c["chunk_size"]):
            cost = costs.decode_attention_cost(
                [n + i for n in lens], c["n_head"], c["head_dim"])
            least += c["n_layer"] * costs.least_seconds(
                cost["flops"], cost["bytes"], peaks)[0]
    return 100.0 * least / measured
