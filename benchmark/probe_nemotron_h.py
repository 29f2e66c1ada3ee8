"""What the limits of ``model_builders/nemotron_h.py`` rest on, read at the
cell's widths on the device it is run on, through the builder's own
comparison::

    python3 benchmark/probe_nemotron_h.py [--seed N] [--sequences 2]
        [--tokens 1024] [--cell serve-nemotron3nano-decode-closed]

on seeded weights and uniform tokens, the last line of standard output one
JSON object (also ``chiprun_out/nemotron_probe_<seed>.json``):

- ``sound``: the three readings of ``Precision`` on the reference's inputs
  (every layer of every sequence), which have to be ``ok()``.
- ``below``: for each quantity the configuration states, the precision below
  PLANTED IN THE PROGRAM (the state carried in bf16, the router's matmul in
  bf16, the residual stream carried in bf16) and the same
  ``Precision.watch`` run again: ``ok()`` has to be False, by that quantity's
  limit and by no other.

It exits non-zero where the sound program is not ``ok()`` or a planted
precision is. ``tests/benchmark/test_nemotron_h.py`` runs the same controls
at the stand-in's size; the walk is ``probe_jamba.py``'s."""

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.probe_jamba import readings  # noqa: E402


def _lowered(builder):
    """name -> (the limit that has to catch it, (the module to patch, the
    attribute, what to put there given the real one))."""
    from deepspeed_tpu.models import decoder, mamba2

    return {
        "state_bf16": ("state_rel_err", (
            mamba2, "state_shapes", lambda real: lambda cfg: tuple(
                (k, s, jnp.bfloat16 if k.startswith("slot_ssm") else d)
                for k, s, d in real(cfg)))),
        "router_bf16": ("router_logit_err", (
            decoder, "router_logits", lambda real: lambda n32, router:
            jnp.dot(n32.astype(jnp.bfloat16), router.astype(
                jnp.bfloat16)).astype(jnp.float32))),
        "stream_bf16": ("stream_rel_err", (
            builder, "stream_error", lambda real: lambda stack, cfg, seen:
            real(stack, cfg, seen, jnp.bfloat16))),
    }


@contextlib.contextmanager
def planted(builder, name):
    """The program with one quantity computed in the precision below; yields
    the name of the limit that has to catch it."""
    limit, (owner, attr, lower) = _lowered(builder)[name]
    real = getattr(owner, attr)
    setattr(owner, attr, lower(real))
    builder.retrace()
    try:
        yield limit
    finally:
        setattr(owner, attr, real)
        builder.retrace()


def probe(builder, model, seed, n_seq, t):
    cfg = model.cfg
    params = model.init_params(seed)
    ids = np.random.RandomState(seed % 2 ** 31).randint(
        0, model.vocab_size, (n_seq, t))
    shown = []
    logits = builder.reference_logits(
        params, ids, cfg, watch=lambda layer, sequence, seen: shown.append(
            (layer, sequence, jax.device_get(seen))))
    out = {"seed": seed, "tokens": [n_seq, t],
           "device": str(jax.devices()[0].device_kind),
           "compute_dtype": str(cfg.dtype),
           "logit_spread": float(logits.std(axis=-1).mean()),
           "limits": dict(builder.Precision.LIMITS), "below": {}}
    del logits
    ok, out["sound"] = readings(builder, params, cfg, shown)
    faults = [] if ok else ["the sound program is not ok()"]
    for name in sorted(_lowered(builder)):
        with planted(builder, name) as limit:
            ok, read = readings(builder, params, cfg, shown)
        over = sorted(k for k, v in read.items()
                      if v is not None and v > out["limits"][k])
        out["below"][name] = dict(read, ok=ok, over=over)
        if ok or over != [limit]:
            faults.append("{}: ok() {}, over {}".format(name, ok, over))
    out["faults"] = faults
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=5800100)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("--cell", default="serve-nemotron3nano-decode-closed")
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_json(harness.MANIFEST), args.cell)
    builder = harness.load_by_name("model_builders",
                                   cell.config["model_type"])
    out = probe(builder, builder.Model(cell.config), args.seed,
                args.sequences, args.tokens)
    os.makedirs(os.path.join(harness.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(harness.ROOT, "chiprun_out",
                           "nemotron_probe_{}.json".format(args.seed)),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 1 if out["faults"] else 0


if __name__ == "__main__":
    sys.exit(main())
