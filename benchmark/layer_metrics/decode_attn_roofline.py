"""Decode attention: the bytes of cache each call must read (the context
lengths the benchmark counted x the bytes a token holds in one layer, which
the model's builder states; benchmark/costs.py; memory bound: 1 FLOP a byte
at GPT-2's heads) over the chip's bandwidth, over the measured device time of
the decode kernels alone (class ``decode_attn``: found by name)."""

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
                [n + i for n in lens], c["n_head"], c["head_dim"],
                kv_bytes_per_token=c["kv_bytes_token_layer"])
            least += c["n_layer"] * costs.least_seconds(
                cost["flops"], cost["bytes"], peaks)[0]
    return 100.0 * least / measured
