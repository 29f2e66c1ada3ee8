"""Operations and bytes an algorithm needs, computed from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
denominator of a utilization or a roofline share. Every function counts
what the mathematics requires; recomputation that an implementation adds
(remat of a block) is never counted, the recomputation that IS the flash
backward algorithm (scores rebuilt from q and k) is.
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def device_peaks(device_kind):
    """The peaks row of ``benchmark/peaks.json`` for ``device_kind``; an
    unknown kind is an error, never another chip's row."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError("no peaks row for device kind {!r} in benchmark/"
                       "peaks.json (known: {})".format(
                           device_kind, sorted(table)))
    return table[device_kind]


def gpt2_num_params(n_layer, n_embd, vocab_size, n_positions):
    """Parameters of GPT-2 with a tied head: token and position tables,
    per block 12*C*C weights + 13*C biases and LayerNorm vectors, and the
    final LayerNorm."""
    c = n_embd
    return (vocab_size * c + n_positions * c
            + n_layer * (12 * c * c + 13 * c) + 2 * c)


def gpt2_train_flops_per_token(n_layer, n_embd, vocab_size, n_positions,
                               seq_len):
    """Forward + backward FLOPs per trained token: 6*N for the dense
    matmuls (the tied head counted once, as its table is), plus causal
    attention's two matmuls per layer: 2 matmuls x 2 FLOPs x T x C = 4TC
    forward, halved by causality, tripled for forward + backward = 6*L*T*C.
    Recomputation is not counted."""
    n = gpt2_num_params(n_layer, n_embd, vocab_size, n_positions)
    return 6 * n + 6 * n_layer * seq_len * n_embd


def flash_attention_cost(batch, heads, seq_len, head_dim, causal=True,
                         dtype_bytes=2):
    """FLOPs and HBM bytes of one flash-attention forward call and one
    backward call on ``[batch, heads, seq_len, head_dim]``.

    Forward: S = QK^T and O = PV, 2 matmuls of 2*T*T*d FLOPs a head.
    Backward: S rebuilt, dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q:
    5 matmuls. A causal mask halves both. Bytes are the tensors each call
    must read and write once: forward q, k, v in and o out (+ fp32
    log-sum-exp); backward q, k, v, o, do in and dq, dk, dv out (+ lse)."""
    bh = batch * heads
    mm = 2 * seq_len * seq_len * head_dim * (0.5 if causal else 1.0)
    tensor = bh * seq_len * head_dim * dtype_bytes
    lse = bh * seq_len * 4
    return {
        "fwd_flops": 2 * mm * bh, "bwd_flops": 5 * mm * bh,
        "fwd_bytes": 4 * tensor + lse, "bwd_bytes": 8 * tensor + lse,
    }


def decode_attention_cost(context_lens, heads, head_dim, dtype_bytes=2,
                          kv_bytes_per_token=None):
    """FLOPs and HBM bytes of one decode-attention call (one layer, one
    new token for each slot): every slot reads the keys and the values of
    its own context once (q and the output are negligible and left out),
    and does q.K^T and p.V, 2 FLOPs a multiply-add each for each of the
    ``heads`` query heads. ``kv_bytes_per_token`` is what one token's cache
    holds in one layer, as the model's builder states it (grouped-query or
    latent heads hold less than a key and a value for every query head,
    which is what is counted without it)."""
    total = float(sum(context_lens))
    if kv_bytes_per_token is None:
        kv_bytes_per_token = 2 * heads * head_dim * dtype_bytes
    return {"flops": 4 * heads * head_dim * total,
            "bytes": kv_bytes_per_token * total}


def least_seconds(flops, nbytes, peaks):
    """The roofline: the least time the chip could take, and which of the
    two bounds sets it."""
    t_compute = flops / peaks["flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
