"""What the limits of ``model_builders/jamba.py`` rest on, read at the cell's
widths on the device it is run on, through the builder's own comparison::

    python3 benchmark/probe_jamba.py [--seed N] [--sequences 2]
        [--tokens 1024] [--cell serve-jamba2-decode-closed]

on seeded weights and uniform tokens, the last line of standard output one
JSON object (also ``chiprun_out/jamba_probe_<seed>.json``):

- ``sound``: the five readings of ``Precision`` on the reference's inputs
  (every layer of every sequence), which have to be ``ok()``.
- ``below``: for each quantity the configuration states, the precision below
  PLANTED IN THE PROGRAM (``CONTROLS``: the state carried in bf16, the tail in
  fp8, ``x_proj``'s output, the three inner norms' and ``dt_proj``'s rounded
  to bf16, keys and values rounded to fp8 as they are written, the residual
  stream carried in bf16) and the same
  ``Precision.watch`` run again: ``ok()`` has to be False, by that
  quantity's limit and by no other.

It exits non-zero where the sound program is not ``ok()`` or a planted
precision is. ``tests/benchmark/test_jamba.py`` runs the same controls at the
stand-in's size."""

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

FP8, BF16 = jnp.float8_e4m3fn, jnp.bfloat16


def _low(x, dtype):
    """``x`` rounded to ``dtype`` and back. The barrier makes the rounded
    array real: left to itself the compiler may keep the excess precision of
    a rounding it can fuse away (``probe_lfm2_moe.py``)."""
    return jax.lax.optimization_barrier(x.astype(dtype)).astype(x.dtype)


def _carried(real, which, dtype):
    """``mamba1.state_shapes`` with the arrays ``which`` names (the state's
    keys or the tail's) carried in ``dtype``."""
    def shapes(cfg):
        return tuple((key, shape, dtype if which(key) else was)
                     for key, shape, was in real(cfg))
    return shapes


def _lowered():
    """name -> (the limit that has to catch it, [(the module or class to
    patch, the attribute, what to put there given the real one)])."""
    from deepspeed_tpu.models import decoder, generation, mamba1

    return {
        "state_bf16": ("state_rel_err", [(
            mamba1, "state_shapes", lambda real: _carried(
                real, lambda key: "conv" not in key, BF16))]),
        "tail_fp8": ("tail_rel_err", [(
            mamba1, "state_shapes", lambda real: _carried(
                real, lambda key: "conv" in key, FP8))]),
        "dt_bf16": ("dt_rel_err", [
            (mamba1, "_rms", lambda real: lambda x, weight, eps: _low(
                real(_low(x, BF16), weight, eps), BF16)),
            (jax.nn, "softplus", lambda real: lambda v: real(
                _low(v, BF16)))]),
        "keys_fp8": ("attention_rel_err", [(
            generation.CacheAttention, "__call__",
            lambda real: lambda self, i, q, k, v, planes: real(
                self, i, q, _low(k, FP8), _low(v, FP8), planes))]),
        "stream_bf16": ("stream_rel_err", [(
            decoder.DecoderConfig, "stream_dtype",
            lambda real: property(lambda self: jnp.dtype(BF16)))]),
    }


CONTROLS = ("state_bf16", "tail_fp8", "dt_bf16", "keys_fp8", "stream_bf16")


@contextlib.contextmanager
def planted(builder, name):
    """The program with one quantity computed in the precision below
    (``_lowered``); yields the name of the limit that has to catch it. The
    builder's compiled probes are traced again on the way in and out."""
    limit, patches = _lowered()[name]
    real = [getattr(owner, attr) for owner, attr, _ in patches]
    for (owner, attr, lower), was in zip(patches, real):
        setattr(owner, attr, lower(was))
    builder.retrace()
    try:
        yield limit
    finally:
        for (owner, attr, _), was in zip(patches, real):
            setattr(owner, attr, was)
        builder.retrace()


def readings(builder, params, cfg, shown):
    """``Precision`` fed what the reference showed (``shown``: (layer,
    sequence, seen) in order, ``seen`` on the host): (ok, its readings)."""
    held = builder.Precision(params, cfg)
    for layer, sequence, seen in shown:
        held.watch(layer, sequence, {k: jnp.asarray(v)
                                     for k, v in seen.items()})
    return held.ok(), held.readings()


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
    for name in CONTROLS:
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
    ap.add_argument("--seed", type=int, default=4800100)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("--cell", default="serve-jamba2-decode-closed")
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_json(harness.MANIFEST), args.cell)
    builder = harness.load_by_name("model_builders",
                                   cell.config["model_type"])
    out = probe(builder, builder.Model(cell.config), args.seed,
                args.sequences, args.tokens)
    os.makedirs(os.path.join(harness.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(harness.ROOT, "chiprun_out",
                           "jamba_probe_{}.json".format(args.seed)),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 1 if out["faults"] else 0


if __name__ == "__main__":
    sys.exit(main())
