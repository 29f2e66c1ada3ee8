"""The decode scan's ``paged_decode`` over the SHARED plane (the full layer's
call and the seven cross layers': eight an iteration, each reading every
decoding slot's whole context with its own queries) against its roofline,
for the calls the trace HOLDS.

Least time of one call: every decoding slot's stored keys and values read
once at the bytes a token stores in one layer as the builder states them
(5,120 B), the queries and the result once a row, and the query heads' two
products (``costs_phi4flash.shared_decode_cost``: bound by memory); contexts
``costs_phi4flash.least_call_seconds``' (the driver's count, one step back).
Calls: the ``paged_decode`` kernel's own, by name (the lane's are
``prefill_attn``; the window group's ``window_decode`` does not begin with
the name). Measured: the kernel's device self time by name. The family's own
reader because ``paged_decode_roofline``'s list of cells is pinned by a test
of another family. None where the program has no cross layer's region, the
trace no such kernel or the benchmark counted no context."""

from benchmark import costs_phi4flash, scope_reduce


def read(run):
    c = run["counters"]
    reduced = scope_reduce.of_run(run)
    measured, calls = scope_reduce.kernel_total(reduced["kernels"],
                                                "paged_decode")
    if "xattn" not in reduced["regions"] or not measured or not calls:
        return None
    least = costs_phi4flash.least_call_seconds(
        c, run["device"]["kind"],
        lambda lens: costs_phi4flash.shared_decode_cost(
            lens, c["n_head"], c["head_dim"], c["kv_bytes_token_layer"]))
    return None if least is None else 100.0 * calls * least / measured
