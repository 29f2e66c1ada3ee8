"""What the limits of ``model_builders/phi4flash.py`` rest on, read at the
cell's widths on the device it is run on, through the builder's own
comparison::

    python3 benchmark/probe_phi4flash.py [--seed N] [--sequences 1]
        [--tokens 1536] [--cell serve-phi4flash-decode-closed]

on seeded weights and uniform tokens, the last line of standard output one
JSON object (also ``chiprun_out/phi4flash_probe_<seed>.json``), in
``probe_jamba.py``'s form and through its machinery (``probe``, ``planted``):

- ``sound``: the five readings of ``Precision`` on the reference's inputs
  (every layer of every sequence), which have to be ``ok()``.
- ``below``: for each quantity the configuration states, the precision below
  PLANTED IN THE PROGRAM (``CONTROLS``: a window layer's keys and values
  rounded to fp8 as they go to the ring, the full layer's as they go to the
  shared plane, the memory handed to the gated memory units in fp8 (its
  product with the gate goes to the matmul in bf16, so a bf16 memory is
  inside that step's own rounding: the builder's comment), the Mamba state
  carried in bf16, the residual stream carried in bf16) and the
  same ``Precision.watch`` run again: ``ok()`` has to be False, by that
  quantity's limit and by no other.

It exits non-zero where the sound program is not ``ok()`` or a planted
precision is. ``tests/benchmark/test_phi4flash.py`` runs the same controls at
the stand-in's size."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402

from benchmark import harness, probe_jamba  # noqa: E402
from benchmark.probe_jamba import BF16, FP8, _carried, _low  # noqa: E402


def _lowered():
    """name -> (the limit that has to catch it, [(the module or class to
    patch, the attribute, what to put there given the real one)])."""
    from deepspeed_tpu.models import decoder, generation, mamba1

    def rounded(real):
        # keys and values in fp8 as they are written (a read-only call
        # writes none)
        def call(self, i, q, k, v, planes, *rest, **kw):
            if k is not None:
                k, v = _low(k, FP8), _low(v, FP8)
            return real(self, i, q, k, v, planes, *rest, **kw)
        return call

    return {
        "ring_fp8": ("window_rel_err", [(
            generation.CacheAttention, "windowed", rounded)]),
        "plane_fp8": ("shared_rel_err", [(
            generation.CacheAttention, "__call__", rounded)]),
        "memory_fp8": ("gmu_rel_err", [(
            decoder, "gmu_mix", lambda real: lambda layer, cfg, h, memory:
            real(layer, cfg, h, _low(memory, FP8)))]),
        "state_bf16": ("state_rel_err", [(
            mamba1, "state_shapes", lambda real: _carried(
                real, lambda key: "conv" not in key, BF16))]),
        "stream_bf16": ("stream_rel_err", [(
            decoder.DecoderConfig, "stream_dtype",
            lambda real: property(lambda self: jnp.dtype(BF16)))]),
    }


CONTROLS = ("ring_fp8", "plane_fp8", "memory_fp8", "state_bf16",
            "stream_bf16")


def probe(builder, model, seed, n_seq, t):
    """``probe_jamba.probe`` with this family's controls."""
    was = probe_jamba._lowered, probe_jamba.CONTROLS
    probe_jamba._lowered, probe_jamba.CONTROLS = _lowered, CONTROLS
    try:
        return probe_jamba.probe(builder, model, seed, n_seq, t)
    finally:
        probe_jamba._lowered, probe_jamba.CONTROLS = was


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=5500100)
    ap.add_argument("--sequences", type=int, default=1)
    ap.add_argument("--tokens", type=int, default=1536)
    ap.add_argument("--cell", default="serve-phi4flash-decode-closed")
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_json(harness.MANIFEST), args.cell)
    builder = harness.load_by_name("model_builders",
                                   cell.config["model_type"])
    out = probe(builder, builder.Model(cell.config), args.seed,
                args.sequences, args.tokens)
    os.makedirs(os.path.join(harness.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(harness.ROOT, "chiprun_out",
                           "phi4flash_probe_{}.json".format(args.seed)),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 1 if out["faults"] else 0


if __name__ == "__main__":
    sys.exit(main())
