"""The decode scan's WINDOW attention kernel against its roofline, for the
calls the trace HOLDS.

Least time of one call: every decoding slot's last ``min(context,
sliding_window)`` keys and values read once, the bytes a token stores in one
layer as the builder states them (20 stored heads of 64, k and v, bf16 =
5,120 B), the queries and the result once a row, and the query heads' two
products (``costs_phi4flash.window_decode_cost``: bound by memory). Calls: the
``window_decode`` kernel's own, by name, as the trace holds them (the lane's
calls are ``window_prefill`` and are not counted). The contexts a call reads:
``costs_phi4flash.least_call_seconds``' (the driver's count, one step back).
Measured: the kernel's device self time by name. None where the trace holds
no such kernel (another family, a parent commit), the configuration has no
window or the benchmark counted no context."""

from benchmark import costs_phi4flash, scope_reduce


def read(run):
    c, window = run["counters"], run["cell"].config.get("sliding_window")
    measured, calls = scope_reduce.kernel_total(
        scope_reduce.of_run(run)["kernels"], "window_decode")
    if not measured or not calls or not window:
        return None
    least = costs_phi4flash.least_call_seconds(
        c, run["device"]["kind"],
        lambda lens: costs_phi4flash.window_decode_cost(
            lens, window, c["n_head"], c["head_dim"],
            c["kv_bytes_token_layer"]))
    return None if least is None else 100.0 * calls * least / measured
