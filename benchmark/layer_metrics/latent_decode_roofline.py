"""The ``latent_decode`` kernel against its roofline, for the decode
iterations the trace HOLDS.

Least time of one decode iteration: a call a layer, each the larger of its
bytes over the chip's bandwidth and its operations over the chip's peak
(``costs_deepseek_v3.latent_decode_cost``: the cached latents of every
decoding slot's context, 576 values a token whatever the pool pads them to,
and 2 x heads x (576 + 512) FLOP a token: within a hundredth of the v5e's
ridge, so either bound may set it). The contexts are the ones the benchmark
counted at each traced step (``trace_context``), advanced a token an
iteration. The ITERATIONS are counted from the trace, the kernel's calls
over its calls an iteration (one a layer), not ``trace_steps`` x
``chunk_size``: with a step in flight a traced tail of K calls holds K - 1
to K device steps (PERF.md section 7), and 8 steps' work over 7 steps' time
would read 8/7 too high. Each held iteration is charged the MEAN least time
of the counted steps' iterations. Measured: the self time of the kernel's
calls, by name. None where the trace holds no such kernel (a parent commit,
another family)."""

from benchmark import costs, costs_deepseek_v3, scope_reduce


def read(run):
    c, config = run["counters"], run["cell"].config
    measured, calls = scope_reduce.kernel_total(
        scope_reduce.of_run(run)["kernels"], "latent_decode")
    steps = [lens for lens in c.get("trace_context") or [] if lens]
    if not config.get("kv_lora_rank") or not measured or not steps:
        return None
    peaks = costs.device_peaks(run["device"]["kind"])
    least = 0.0
    for lens in steps:
        for i in range(c["chunk_size"]):
            cost = costs_deepseek_v3.latent_decode_cost(
                [n + i for n in lens], config["num_attention_heads"],
                config["kv_lora_rank"], config["qk_rope_head_dim"])
            least += costs.least_seconds(cost["flops"], cost["bytes"],
                                         peaks)[0]
    a_call = least / (len(steps) * c["chunk_size"])
    return 100.0 * calls * a_call / measured
