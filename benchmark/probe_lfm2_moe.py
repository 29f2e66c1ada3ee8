"""What the constants of ``model_builders/lfm2_moe.py`` rest on, read at the
cell's widths on the device it is run on, through the builder's own
comparison::

    python3 benchmark/probe_lfm2_moe.py [--seed N] [--sequences 4]
        [--tokens 1024] [--cell serve-lfm2moe-decode-closed]

on seeded weights and uniform tokens, the last line of standard output one
JSON object (also ``chiprun_out/lfm2_probe_<seed>.json``):

- ``logit_noise``: the builder's ``LOGIT_NOISE`` from THE REFERENCE'S ROUNDING
  MODEL, the rms of a router logit's difference, an expert layer, between
  the float32 reference and the same reference with every value a layer
  hands on rounded to bf16 (``reference/lfm2_moe.py``, A ROUNDING MODEL),
  the rounded run keeping the float32 run's experts. No program in it.
- ``sound``: the six readings of ``Precision`` on the reference's inputs
  (every layer of the first sequence), which have to be ``ok()``.
- ``below``: for each quantity the configuration states, the precision below
  PLANTED IN THE PROGRAM (``CONTROLS``: the tail carried in fp8, the router's
  matmul in bf16, the logits its scores are made of in bf16, keys and values
  rounded to fp8 as they are written, the experts' and the dense layer's
  matrices rounded to fp8) and the same
  ``Precision.watch`` run again: ``ok()`` has to be False, by that
  quantity's limit and by no other.
- ``followed``: the program's decode replay of the sequences beside the
  reference at the noise just measured, every changed choice followed: how
  many (layer, position) pairs keep other experts, how far from the edge the
  furthest expert that changed sides stood and how many stood beyond each
  mark (``FOLLOW_SIGMAS`` has to cover them all, with room), with the share
  of positions the band exempts.

It exits non-zero where the sound program is not ``ok()`` or a planted
precision is. ``tests/benchmark/test_lfm2_moe.py`` runs the same controls at
the stand-in's size."""

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import harness  # noqa: E402

FP8 = jnp.float8_e4m3fn


def _low(x, dtype):
    """``x`` rounded to ``dtype`` and back. The barrier makes the rounded
    array real: left to itself the compiler may keep the excess precision of
    a rounding it can fuse away (on the chip it did, for the dense layer's
    matrices and the router's scores)."""
    import jax

    return jax.lax.optimization_barrier(x.astype(dtype)).astype(x.dtype)


def _fp8(x):
    return _low(x, FP8)


def _lowered():
    """name -> (the limit that has to catch it, the module or class to
    patch, the attribute, what to put there given the real one)."""
    from deepspeed_tpu.models import decoder, generation, shortconv
    from deepspeed_tpu.moe import routed

    return {
        "tail_fp8": ("tail_rel_err", shortconv, "state_shapes",
                     lambda real: lambda cfg: tuple(
                         (key, shape, FP8) for key, shape, _ in real(cfg))),
        "router_bf16": ("router_logit_err", decoder, "router_logits",
                        lambda real: lambda n32, router: (
                            n32.astype(jnp.bfloat16)
                            @ router.astype(jnp.bfloat16)).astype(
                                jnp.float32)),
        "weights_bf16": ("router_weight_err", routed, "route_grouped",
                         lambda real: lambda logits, bias, *a: real(
                             _low(logits, jnp.bfloat16), bias, *a)),
        "keys_fp8": ("attention_rel_err", generation.CacheAttention,
                     "__call__",
                     lambda real: lambda self, i, q, k, v, planes: real(
                         self, i, q, _fp8(k), _fp8(v), planes)),
        "experts_fp8": ("expert_rel_err", routed, "expert_ffn",
                        lambda real: lambda x, gate, w_gate_up, w_down: real(
                            x, gate, _fp8(w_gate_up), _fp8(w_down))),
        "dense_fp8": ("dense_rel_err", decoder, "dense_mix",
                      lambda real: lambda layer, cfg, h: real(
                          {k: _fp8(v) for k, v in layer.items()}, cfg, h)),
    }


CONTROLS = ("tail_fp8", "router_bf16", "weights_bf16", "keys_fp8",
            "experts_fp8", "dense_fp8")


@contextlib.contextmanager
def planted(builder, name):
    """The program with one quantity computed in the precision below
    (``_lowered``); yields the name of the limit that has to catch it. The
    builder's compiled probes are traced again on the way in and out."""
    limit, owner, attr, lower = _lowered()[name]
    real = getattr(owner, attr)
    setattr(owner, attr, lower(real))
    builder.retrace()
    try:
        yield limit
    finally:
        setattr(owner, attr, real)
        builder.retrace()


def readings(builder, params, cfg, shown):
    """``Precision`` fed what the reference showed (``shown``: (layer,
    sequence, seen) in order): (ok, its readings)."""
    held = builder.Precision(params, cfg)
    for layer, sequence, seen in shown:
        held.watch(layer, sequence, seen)
    return held.ok(), held.readings()


def rounding_noise(builder, params, cfg, ids):
    """(``logit_noise`` an expert layer; the float32 run's ``watch`` calls of
    the first sequence; the spread of a router logit an expert layer)."""
    first = cfg.dense_layers
    plain, rounded, shown = {}, {}, []

    def own(layer, sequence, logits):
        plain[layer, sequence] = np.asarray(logits)

    def watch(layer, sequence, seen):
        if sequence == 0:
            shown.append((layer, sequence, seen))

    builder.reference_logits(params, ids, cfg, watch=watch, follow=own)

    def same(layer, sequence, logits):
        rounded[layer, sequence] = np.asarray(logits)
        inside, _ = builder.sides(
            plain[layer, sequence],
            np.asarray(params["moe"]["router_bias"][layer - first]),
            cfg.experts_per_token, 1.0)
        return np.argsort(~inside, axis=-1, kind="stable")[
            :, :cfg.experts_per_token]

    builder.reference_logits(params, ids, cfg, follow=same, round="bfloat16")
    layers = sorted({layer for layer, _ in plain})
    noise = [float(np.sqrt(np.mean([
        (rounded[layer, b] - plain[layer, b]) ** 2
        for b in range(ids.shape[0])]))) for layer in layers]
    spread = [float(np.std([plain[layer, b] for b in range(ids.shape[0])]))
              for layer in layers]
    return noise, shown, spread


def followed(builder, params, cfg, ids):
    """The program's replay beside the reference (module docstring), with
    EVERY expert that changed sides followed, so that no choice left
    unfollowed parts the streams and feeds the layers after it: how far
    the furthest stood is then rounding's own reach."""
    rule, marks = builder.FOLLOW_SIGMAS, builder.MARKS
    builder.FOLLOW_SIGMAS = float("inf")
    builder.MARKS = tuple(sorted(set(marks) | {rule, 1.5 * rule, 2 * rule}))
    try:
        held = builder.Precision(params, cfg,
                                 builder.replay(params, cfg, ids))
        builder.reference_logits(params, ids, cfg, follow=held.follow)
    finally:
        builder.FOLLOW_SIGMAS, builder.MARKS = rule, marks
    ties = held.ties(ids.shape)
    return dict(held.routing(), positions=int(ties.size),
                exempt_positions=int(ties.sum()), follow_sigmas=rule,
                band_sigmas=builder.BAND_SIGMAS)


def probe(builder, model, seed, n_seq, t):
    import jax

    cfg = model.cfg
    params = model.init_params(seed)
    ids = np.random.RandomState(seed % 2 ** 31).randint(
        0, model.vocab_size, (n_seq, t))
    noise, shown, spread = rounding_noise(builder, params, cfg, ids)
    out = {"seed": seed, "tokens": [n_seq, t],
           "device": str(jax.devices()[0].device_kind),
           "compute_dtype": str(cfg.dtype), "logit_noise": noise,
           "router_logit_spread": spread,
           "limits": dict(builder.Precision.LIMITS), "below": {}}
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
    builder.LOGIT_NOISE = tuple(noise)
    out["followed"] = followed(builder, params, cfg, ids)
    out["faults"] = faults
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=4400100)
    ap.add_argument("--sequences", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("--cell", default="serve-lfm2moe-decode-closed")
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_json(harness.MANIFEST), args.cell)
    builder = harness.load_by_name("model_builders",
                                   cell.config["model_type"])
    out = probe(builder, builder.Model(cell.config), args.seed,
                args.sequences, args.tokens)
    os.makedirs(os.path.join(harness.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(harness.ROOT, "chiprun_out",
                           "lfm2_probe_{}.json".format(args.seed)), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 1 if out["faults"] else 0


if __name__ == "__main__":
    sys.exit(main())
