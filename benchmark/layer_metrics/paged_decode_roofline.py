"""The decode scan's paged attention kernel under GROUPED-QUERY rows against
its roofline, for the calls the trace HOLDS.

Least time of one call: every decoding slot's stored keys and values read
once, the bytes a token stores in one layer as the builder states them
(``kv_bytes_token_layer``: 8 stored heads of 64, k and v, bf16 = 2,048 B),
and the query heads' two products (``costs_lfm2_moe.paged_decode_cost``: 4
FLOP a byte, bound by memory). Calls: the ``paged_decode`` kernel's own, by
name, as the trace holds them (the lane's calls are ``prefill_attn`` and are
not counted). The context a call reads: the MEAN over the traced tail's
iterations of the decoding slots' context lengths the benchmark counted,
taken ONE STEP BACK (``n + i - chunk_size``): the device runs the step
dispatched before the one the host is counting (PERF.md section 7: a traced
tail of K calls holds K - 1 to K device steps), so the contexts the trace
holds are never longer than these, and the share is not counted too high;
``decode_attn_roofline`` multiplies the host's steps and is not copied here.
Measured: the kernel's device self time by name. Everything it reads is
the trace's or the driver's count, so any cell whose scan calls
``paged_decode`` may list itself; None where the trace holds no such kernel
(a family with another one, a parent commit) or the benchmark counted no
context."""

from benchmark import costs, costs_lfm2_moe, scope_reduce


def read(run):
    c = run["counters"]
    reduced = scope_reduce.of_run(run)
    measured, calls = scope_reduce.kernel_total(reduced["kernels"],
                                                "paged_decode")
    lens = [step for step in c.get("trace_context") or () if step]
    if not measured or not calls or not lens:
        return None
    peaks = costs.device_peaks(run["device"]["kind"])
    chunk = c["chunk_size"]
    least = []
    for step in lens:
        for i in range(chunk):
            cost = costs_lfm2_moe.paged_decode_cost(
                [max(n + i - chunk, 0) for n in step], c["n_head"],
                c["head_dim"], c["kv_bytes_token_layer"])
            least.append(costs.least_seconds(cost["flops"], cost["bytes"],
                                             peaks)[0])
    return 100.0 * calls * (sum(least) / len(least)) / measured
