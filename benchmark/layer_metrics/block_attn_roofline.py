"""The diffusion scan's paged attention at ``block_length`` query rows a slot
against its roofline, for the calls the trace HOLDS.

Least time of one call: ``costs_sdar_moe.block_attention_cost`` (the keys and
values of a slot's earlier blocks and of its open block read ONCE for all
``rep x block_length`` rows that share them, the bytes a token stores in one
layer as the builder states them; bound by memory). Calls: the
``paged_decode`` kernel's own, by name, as the trace holds them (the lane's
are ``prefill_attn`` and are not counted). The context a call reads: the MEAN
over the traced tail's steps of the slots' open blocks' first positions as
the driver's own bookkeeping has them (``trace_context``: a slot's context
grows a block every ``steps + 1`` iterations, not a position an iteration),
taken ONE STEP BACK, ``chunk_size x block / (steps + 1)`` positions: the
device runs the step dispatched before the one the host is counting (PERF.md
section 7), so the contexts the trace holds are never longer than these, and
the share is not counted too high. Measured: the kernel's device self time by
name. None where the trace holds no such kernel (a CPU, a parent commit) or
the driver counted no context or no block."""

from benchmark import costs, costs_sdar_moe, scope_reduce


def read(run):
    c = run["counters"]
    reduced = scope_reduce.of_run(run)
    measured, calls = scope_reduce.kernel_total(reduced["kernels"],
                                                "paged_decode")
    lens = [step for step in c.get("trace_context") or () if step]
    if not measured or not calls or not lens or "block_length" not in c:
        return None
    peaks = costs.device_peaks(run["device"]["kind"])
    length = c["block_length"]
    back = c["chunk_size"] * length // (c["denoising_steps"] + 1)
    least = []
    for step in lens:
        cost = costs_sdar_moe.block_attention_cost(
            [max(n - back, 0) for n in step], length, c["n_head"],
            c["head_dim"], c["kv_bytes_token_layer"])
        least.append(costs.least_seconds(cost["flops"], cost["bytes"],
                                         peaks)[0])
    return 100.0 * calls * (sum(least) / len(least)) / measured
