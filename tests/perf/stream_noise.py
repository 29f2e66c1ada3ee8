"""How far a served token of the Jamba cell stands from the float32
reference's choice, by the type of the residual stream: the cache-free
forward pass of ``serve-jamba2-decode-closed``'s model at its widths and all
28 layers against ``benchmark/reference/jamba.py`` on seeded weights and
uniform tokens; the logit error's rms and, a sequence, the largest margin
(the reference's best logit less its logit for the program's choice: what
the serve driver holds to 0.1). PR 48's readings on a v5e: root PERF.md,
section 6. Full size: run it on the chip.

Usage: python tests/perf/stream_noise.py [seed] [tokens a sequence]

- ``bf16_stream``: ``residual_fp32`` False.
- ``stream_only``: the stream float32, a dense feed-forward's matmuls and a
  Mamba-1 mixer's ``out_proj`` emitting bf16 as under a bf16 stream.
- ``float32_stream``: ``residual_fp32`` True, what the cell runs.
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

import _platform

_platform.setup()

from benchmark import harness
from deepspeed_tpu.models import decoder, mamba1

CELL = "serve-jamba2-decode-closed"


def main(seed=4800051, tokens=640, sequences=4):
    cell = harness.Cell(harness.load_json(harness.MANIFEST), CELL)
    builder = harness.load_by_name("model_builders", "jamba")
    model = builder.Model(cell.config)
    params = model.init_params(seed)
    ids = np.random.RandomState(seed % 2 ** 31).randint(
        0, model.vocab_size, (sequences, tokens))
    want = builder.reference_logits(params, ids, model.cfg)
    out = {"seed": seed, "tokens": [sequences, tokens],
           "device": str(jax.devices()[0].device_kind),
           "logit_spread": float(want.std(axis=-1).mean())}

    def read(tag, cfg):
        apply = jax.jit(decoder.DecoderLM(cfg).apply)
        got = np.concatenate([np.asarray(apply(
            {"params": params}, jnp.asarray(row[None]))) for row in ids])
        chosen = np.take_along_axis(want, got.argmax(-1)[..., None], -1)
        margin = want.max(-1) - chosen[..., 0]
        out[tag] = {"logit_err_rms": float((got - want).std()),
                    "margin_a_sequence": margin.max(1).tolist()}

    wide = model.cfg._replace(residual_fp32=True)
    read("bf16_stream", wide._replace(residual_fp32=False))
    read("float32_stream", wide)
    dense_mix, mixer = decoder.dense_mix, mamba1.mixer
    decoder.dense_mix = lambda layer, cfg, h: dense_mix(
        layer, cfg._replace(residual_fp32=False), h)

    def rounded(p, cfg, *state):
        y, *state = mixer(p, cfg, *state)
        return (y.astype(cfg.dtype), *state)

    mamba1.mixer = rounded
    try:
        read("stream_only", wide)
    finally:
        decoder.dense_mix, mamba1.mixer = dense_mix, mixer
    print(json.dumps(out))


if __name__ == "__main__":
    main(*(int(v) for v in sys.argv[1:3]))
