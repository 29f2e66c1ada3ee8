"""Operations and bytes of the decoder-hybrid-decoder family's own calls, from
shapes alone (``costs.py`` holds the ones every family shares; this file is the
family's, so that no later PR that claims a gain can move its denominators).
"""

from benchmark import costs


def _attention(tokens, rows, heads, head_dim, kv_bytes_per_token, dtype_bytes):
    """One call that reads ``tokens`` cached tokens in all for ``rows``
    decoding slots: the stored keys and values once, the queries and the
    result once a row; q.K^T and p.V at 2 FLOP a multiply-add for each of the
    ``heads`` query heads (4 FLOP a byte at 40 heads of 64 over 5,120 B a
    token: bound by memory)."""
    return {"flops": 4.0 * heads * head_dim * tokens,
            "bytes": float(kv_bytes_per_token) * tokens
            + 2.0 * rows * heads * head_dim * dtype_bytes}


def window_decode_cost(context_lens, window, heads, head_dim,
                       kv_bytes_per_token, dtype_bytes=2):
    """FLOPs and HBM bytes of ONE call of ``window_decode`` (one window
    layer, one new token for each decoding slot): a slot reads the keys and
    values of the last ``min(context, window)`` positions ONLY, whatever
    its context, ``kv_bytes_per_token`` a token. What the kernel brings in
    beyond them (the rest of the window's first and last page: up to two
    pages less two tokens a slot) is NOT in the bound: a reading well under
    100 at a window of four pages is partly those."""
    lens = [n for n in context_lens if n > 0]
    return _attention(float(sum(min(n, window) for n in lens)), len(lens),
                      heads, head_dim, kv_bytes_per_token, dtype_bytes)


def shared_decode_cost(context_lens, heads, head_dim, kv_bytes_per_token,
                       dtype_bytes=2):
    """FLOPs and HBM bytes of ONE call of ``paged_decode`` over the SHARED
    plane (the full layer's own call or a cross layer's: each reads every
    decoding slot's WHOLE context once with its own queries)."""
    lens = [n for n in context_lens if n > 0]
    return _attention(float(sum(lens)), len(lens), heads, head_dim,
                      kv_bytes_per_token, dtype_bytes)


def least_call_seconds(counters, device_kind, cost_of):
    """The mean, over the traced tail's decode iterations, of the least time
    of ONE call whose cost ``cost_of(context lengths)`` gives. The contexts
    are the decoding slots' as the driver counted them a step
    (``trace_context``), taken ONE STEP BACK (``n + i - chunk_size``), as
    ``paged_decode_roofline`` takes them: the device runs the step dispatched
    before the one the host is counting, so the contexts the trace holds are
    never longer than these and a share is not counted too high. None where
    the driver counted no context."""
    lens = [step for step in counters.get("trace_context") or () if step]
    if not lens:
        return None
    peaks = costs.device_peaks(device_kind)
    chunk = counters["chunk_size"]
    least = []
    for step in lens:
        for i in range(chunk):
            cost = cost_of([max(n + i - chunk, 0) for n in step])
            least.append(costs.least_seconds(cost["flops"], cost["bytes"],
                                             peaks)[0])
    return sum(least) / len(least)
