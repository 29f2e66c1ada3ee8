"""KV-cache decode throughput (GPT-2 355M greedy generation).

Beyond the reference's training-era scope, but the framework ships a
cached decode path (models/generation.py: prefill + lax.scan single-token
steps) and an inference number belongs next to the training headline:
decode is HBM-bandwidth-bound (every step streams the full weights), so
tokens/s/chip ≈ HBM_BW / bytes(params) is the roofline to compare against.

Prints one JSON line. Shapes: 355M bf16, batch 8, 1024-token prompt,
128 new tokens on TPU; tiny model off-TPU.
"""

import json
import os
import sys
import time

import _platform

_platform.setup()

import jax
import numpy as np

from deepspeed_tpu.models.generation import generate
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel


def main():
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        # Like bench.py's guard: without a TPU the run fails unless the
        # tiny CPU smoke was asked for explicitly.
        print("decode_bench: needs a TPU, got backend {!r} (set "
              "JAX_PLATFORMS=cpu for the tiny CPU smoke)".format(
                  jax.default_backend()), file=sys.stderr)
        return 3
    if on_tpu:
        cfg = GPT2Config.gpt2_medium(dropout=0.0, n_positions=2048)
        batch, prompt_len, new_tokens, reps = 8, 1024, 128, 3
    else:
        cfg = GPT2Config.tiny(dropout=0.0)
        batch, prompt_len, new_tokens, reps = 4, 32, 16, 2

    model = GPT2LMHeadModel(cfg)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, prompt_len))
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        ids[:, :8])
    params = variables["params"]

    def timed(n, reps_):
        out = generate(model, params, ids, n, temperature=0.0)
        np.asarray(out)  # compile; concrete fetch is the reliable barrier
        t0 = time.perf_counter()
        for _ in range(reps_):
            out = generate(model, params, ids, n, temperature=0.0)
        np.asarray(out)
        return (time.perf_counter() - t0) / reps_

    # The prefill (batch x prompt_len dense forward) would otherwise
    # dominate the window and halve the reported decode rate vs the
    # roofline: subtract a (prefill + 1 step) run so only the cached
    # single-token steps are counted.
    dt_full = timed(new_tokens, reps)
    dt_prefill = timed(1, reps)
    decode_s = max(dt_full - dt_prefill, 1e-9)
    tok_s = batch * (new_tokens - 1) / decode_s

    n_params = int(sum(int(np.prod(l.shape)) for l in
                       jax.tree_util.tree_leaves(params)))
    # bf16 decode roofline: one full weight read per token step.
    hbm_bw = 819e9 if on_tpu else None  # v5e ~819 GB/s
    roofline = (hbm_bw / (2 * n_params) * batch) if hbm_bw else None
    print(json.dumps({
        "metric": "gpt2_{}_decode_tokens_per_sec_per_chip".format(
            "355m" if on_tpu else "tiny"),
        "value": round(tok_s, 1),
        "unit": "tokens/s/chip",
        "extra": {
            "platform": jax.default_backend(),
            "batch": batch,
            "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "params": n_params,
            "decode_seconds_per_rep": round(decode_s, 3),
            "prefill_seconds_per_rep": round(dt_prefill, 3),
            "bw_roofline_tokens_per_sec": (round(roofline, 1)
                                           if roofline else None),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
