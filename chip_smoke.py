"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the full
width of GPT-2 355M (24 layers, 1024 wide, 16 heads, sequence 1024; random
weights from a seed), and checks what comes out against the repo's own
references:

    python chip_smoke.py            # one chip: train, layer, serve
    python chip_smoke.py --chips 4  # four chips: zero2-dp4, fleet4 (only)

One process, which imports JAX once and starts no child: the chip belongs to
one process at a time. The platform is checked first, so a run without a TPU
fails in seconds. Every phase prints one JSON line; any failure makes the last
line say ``"ok": false`` and the process exit non-zero. The last line of
standard output is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Times and rates on the earlier lines are SMOKE READINGS (one cold run, no
repeats, compilation next to them) — never benchmark numbers.
"""

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

SEED = 0
REPO = os.path.dirname(os.path.abspath(__file__))

# Stated tolerances (bf16 compute, fp32 loss):
# - a loss near ln(50257) = 10.8 carries about one bf16 ulp (0.06) of slack
#   between two attention implementations;
LOSS_TOL = 0.05
# - fused layer against its jnp reference: the repo's own bf16 tolerance
#   (tests/unit/test_transformer_layer.py), |a - b| <= atol + rtol * |b|;
LAYER_RTOL, LAYER_ATOL = 5e-2, 2e-2
# - a served token is right when the reference model prefers no other token
#   by more than this many logits (a bf16 near-tie; a wrong token is off by
#   the logits' spread, ~0.6 at random init).
TOKEN_MARGIN_TOL = 0.05


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One size of every phase. FULL is what the chip runs; TINY is the same
    code at a size the CPU tests can afford (tests/unit/test_chip_smoke.py)."""
    gpt2: str                 # GPT2Config constructor name
    batch: int
    seq: int
    train_steps: int
    layer_hidden: int
    layer_heads: int
    layer_seq: int
    layer_batch: int
    serve: dict               # the "inference" config block
    prompt_lens: tuple
    n_requests: int
    new_tokens: int
    compared: tuple           # indices of the requests checked token by token


FULL = Sizes(
    gpt2="gpt2_medium", batch=8, seq=1024, train_steps=4,
    layer_hidden=1024, layer_heads=16, layer_seq=512, layer_batch=8,
    # The GPT-2 serving cells' shape: 16 slots x 1024, paged KV on, flash
    # decode left to default_flash_decode().
    serve={"max_slots": 16, "max_len": 1024, "chunk_size": 16,
           "max_queue": 64, "paged_kv": True},
    prompt_lens=(64, 128, 192, 256), n_requests=12, new_tokens=32,
    compared=(1, 5))

TINY = Sizes(
    gpt2="tiny", batch=8, seq=64, train_steps=3,
    layer_hidden=64, layer_heads=4, layer_seq=32, layer_batch=2,
    serve={"max_slots": 4, "max_len": 64, "chunk_size": 4, "max_queue": 64,
           "paged_kv": True, "kv_page_len": 16},
    prompt_lens=(6, 12), n_requests=6, new_tokens=8, compared=(1, 3))


def emit(obj):
    print(json.dumps(obj), flush=True)


def check(cond, message):
    if not cond:
        raise AssertionError(message)


def _gpt2_config(size, **kw):
    from deepspeed_tpu.models.gpt2 import GPT2Config

    return getattr(GPT2Config, size.gpt2)(dropout=0.0, **kw)


def _init_params(model, ids):
    """Random weights from SEED, made in one jitted program."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda: model.init(
        jax.random.PRNGKey(SEED), jnp.asarray(ids))["params"])()


def _peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else int(stats["peak_bytes_in_use"])


def _program(xray, prefix):
    """The active record of the program whose label starts with ``prefix`` in
    a ``perf_xray()`` section."""
    rows = [p for p in xray["programs"]
            if p["program"].startswith(prefix) and not p["superseded"]]
    check(len(rows) == 1, "expected one active {!r} program, got {}".format(
        prefix, [p["program"] for p in xray["programs"]]))
    check(rows[0]["error"] is None, "{} did not compile for analysis: {}"
          .format(prefix, rows[0]["error"]))
    return rows[0]


def _train_config(size, stage=0):
    return {"train_batch_size": size.batch,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": stage}}


# --------------------------------------------------------------------- train

def train_phase(size, mesh, expect_kernels):
    """deepspeed.initialize -> train_batch steps and the three-call contract
    on a repeated batch; first loss against the XLA-attention model."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

    cfg = _gpt2_config(size, use_flash_attention=True)
    model = GPT2LMHeadModel(cfg)
    ids = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, size=(size.batch, size.seq))
    params = _init_params(model, ids[:1])

    # The reference: the same weights through the model built with XLA
    # attention, cast the way the engine casts them (bf16 compute).
    ref_model = GPT2LMHeadModel(_gpt2_config(size, use_flash_attention=False))
    ref_loss = float(jax.jit(lambda p, x: ref_model.apply(
        {"params": jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)},
        x, x))(params, jnp.asarray(ids)))

    engine, _, _, _ = deepspeed.initialize(
        model=model, model_parameters=params, mesh=mesh,
        config_params=_train_config(size))
    del params  # the engine owns (and donates) them from here on

    t0 = time.perf_counter()
    losses = [float(engine.train_batch(batch=(ids, ids)))]
    cold_s = time.perf_counter() - t0
    step_s = []
    for _ in range(size.train_steps):
        t0 = time.perf_counter()
        loss = engine.train_batch(batch=(ids, ids))
        jax.block_until_ready(loss)
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    n_fused = len(losses)
    # The DeepSpeed three-call contract on the same engine and batch.
    for _ in range(2):
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))

    check(all(np.isfinite(losses)), "non-finite loss: {}".format(losses))
    check(losses[-1] < losses[0] and losses[n_fused - 1] < losses[0],
          "loss on a repeated batch did not fall: {}".format(losses))
    check(abs(losses[0] - ref_loss) <= LOSS_TOL,
          "first loss {} vs XLA-attention reference {} (tol {})".format(
              losses[0], ref_loss, LOSS_TOL))
    step = _program(engine.perf_xray(), "fused_train_step")
    if expect_kernels:
        # Flash forward + backward per layer; a kernel that gave way to a
        # reference leaves no custom call and fails here.
        check(step["kernel_calls"] >= 2 * cfg.n_layer,
              "flash kernels missing from the train step: {} custom calls"
              .format(step["kernel_calls"]))
    return {
        "model": {"n_layer": cfg.n_layer, "n_embd": cfg.n_embd,
                  "n_head": cfg.n_head, "params": cfg.num_params()},
        "batch": size.batch, "seq": size.seq,
        "losses": losses, "reference_first_loss": ref_loss,
        "kernel_calls": step["kernel_calls"],
        "program_temp_bytes": step["temp_bytes"],
        "program_peak_hbm_bytes": step["peak_hbm_bytes"],
        "smoke_readings": {
            "cold_first_step_seconds": cold_s,
            "warm_step_seconds": step_s,
            "peak_bytes_in_use": _peak_bytes(engine.mesh.devices.flat[0]),
        },
    }


# --------------------------------------------------------------------- layer

def layer_phase(size, expect_kernels):
    """One DeepSpeedTransformerLayer at BERT-large widths, forward and
    backward: against transformer_layer_reference without dropout; mask
    statistics and mask regeneration with it. The attention softmax kernel,
    which the flash path subsumes inside the layer, runs once beside it."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerConfig,
                                               DeepSpeedTransformerLayer)
    from deepspeed_tpu.ops.transformer.kernels import (
        attn_softmax, attn_softmax_reference, dropout)
    from deepspeed_tpu.ops.transformer.transformer import (
        transformer_layer_reference)

    b, t, h = size.layer_batch, size.layer_seq, size.layer_hidden

    def config(rate):
        return DeepSpeedTransformerConfig(
            batch_size=b, max_seq_length=t, hidden_size=h,
            intermediate_size=4 * h, heads=size.layer_heads,
            attn_dropout_ratio=rate, hidden_dropout_ratio=rate,
            num_hidden_layers=24, initializer_range=0.02, seed=SEED + 1,
            dtype=jnp.bfloat16)

    key = jax.random.PRNGKey(SEED)
    x = jax.random.normal(key, (b, t, h), jnp.float32)
    layer = DeepSpeedTransformerLayer(config(0.0))
    params = jax.jit(lambda: layer.init(key, x)["params"])()

    def fused(p, x):
        return layer.apply({"params": p}, x, deterministic=False)

    def reference(p, x):
        return transformer_layer_reference(p, x, None, config(0.0))

    def out_and_dx(fn):
        def run(p, x):
            out, vjp = jax.vjp(lambda x_: fn(p, x_).astype(jnp.float32), x)
            return out, vjp(jnp.ones_like(out))[0]
        return jax.jit(run)

    compiled = out_and_dx(fused).lower(params, x).compile()
    kernel_calls = compiled.as_text().count("tpu_custom_call")
    out, dx = compiled(params, x)
    ref_out, ref_dx = out_and_dx(reference)(params, x)
    err_out = float(jnp.max(jnp.abs(out - ref_out)))
    err_dx = float(jnp.max(jnp.abs(dx - ref_dx)))
    check(np.allclose(out, ref_out, rtol=LAYER_RTOL, atol=LAYER_ATOL)
          and np.allclose(dx, ref_dx, rtol=LAYER_RTOL, atol=LAYER_ATOL),
          "fused layer vs reference: max abs err out {} dx {} (rtol {}, "
          "atol {})".format(err_out, err_dx, LAYER_RTOL, LAYER_ATOL))
    if expect_kernels:
        # Flash forward, the fused flash backward, two LayerNorms and the
        # bias-GELU forward; the LayerNorm and GELU backwards are jnp by
        # design, and dropout is off in this program.
        check(kernel_calls >= 5, "fused layer kernels missing: {} custom "
              "calls".format(kernel_calls))

    # Dropout on: finite, and the layer still differentiates.
    drop_layer = DeepSpeedTransformerLayer(config(0.1))
    d_out, d_dx = out_and_dx(lambda p, x: drop_layer.apply(
        {"params": p}, x, deterministic=False))(params, x)
    check(bool(jnp.all(jnp.isfinite(d_out)) & jnp.all(jnp.isfinite(d_dx))),
          "dropout layer produced non-finite values")

    # One dropout mask: kept share, and the backward regenerates it.
    ones = jnp.ones((b * t, h), jnp.bfloat16)
    y, vjp = jax.vjp(lambda a: dropout(a, 0.1, SEED + 7), ones)
    (g,) = vjp(jnp.ones_like(y))
    kept = float(jnp.mean((y != 0).astype(jnp.float32)))
    check(abs(kept - 0.9) <= 0.01, "kept share {} is not 0.9".format(kept))
    check(bool(jnp.all((g == 0) == (y == 0))),
          "backward did not regenerate the forward's dropout mask")

    scores = jax.random.normal(key, (2, size.layer_heads, t, t), jnp.bfloat16)
    sm_err = float(jnp.max(jnp.abs(
        attn_softmax(scores, None, 0.125, False).astype(jnp.float32)
        - attn_softmax_reference(scores, None, 0.125, False)
        .astype(jnp.float32))))
    check(sm_err <= 0.01, "attn_softmax vs reference: {}".format(sm_err))
    return {"hidden": h, "heads": size.layer_heads, "seq": t, "batch": b,
            "max_abs_err_out": err_out, "max_abs_err_dx": err_dx,
            "kernel_calls": kernel_calls, "dropout_kept_share": kept,
            "softmax_max_abs_err": sm_err}


# --------------------------------------------------------------------- serve

def _prompts(size, vocab):
    rng = np.random.RandomState(SEED + 1)
    return [rng.randint(0, vocab, size=(size.prompt_lens[
        i % len(size.prompt_lens)],)).astype(np.int32)
        for i in range(size.n_requests)]


def _token_margins(forward, params, prompt, tokens):
    """Teacher forcing through the plain model (``forward``: its jitted
    logits): for each served token, by how many logits the reference prefers
    its own argmax over it (0 = agrees)."""
    import jax.numpy as jnp

    seq = np.concatenate([prompt, np.asarray(tokens, np.int32)])[None, :-1]
    logits = forward(params, jnp.asarray(seq))[0, len(prompt) - 1:]
    picked = jnp.take_along_axis(
        logits, jnp.asarray(tokens, jnp.int32)[:, None], axis=1)[:, 0]
    return np.asarray(jnp.max(logits, axis=1) - picked)


def serve_phase(size, expect_kernels):
    """deepspeed.init_inference -> submit / run, greedy, paged KV; two
    streams against models.generation.generate and the plain model; a short
    int8-KV pass."""
    import jax

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models.generation import generate
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

    cfg = _gpt2_config(size, use_flash_attention=True)
    model = GPT2LMHeadModel(cfg)
    params = _init_params(model, np.zeros((1, 16), np.int32))
    prompts = _prompts(size, cfg.vocab_size)
    a, b = size.compared
    check(len(prompts[a]) == len(prompts[b]), "compared prompts must share "
          "one length (one generate program)")

    engine = deepspeed.init_inference(
        model=model, params=params, config={"inference": dict(size.serve)})
    t0 = time.perf_counter()
    first = engine.submit(prompts[0], max_new_tokens=size.new_tokens)
    engine.run()
    first_s = time.perf_counter() - t0
    compiles = engine.compile_count
    t0 = time.perf_counter()
    reqs = [first] + [engine.submit(p, max_new_tokens=size.new_tokens)
                      for p in prompts[1:]]
    engine.run()
    wall = time.perf_counter() - t0
    check(all(r.done and len(r.tokens) == size.new_tokens for r in reqs),
          "unfinished requests: {}".format(
              [(r.rid, r.phase, len(r.tokens)) for r in reqs]))
    check(engine.compile_count == compiles,
          "compile_count grew after the first request: {} -> {}".format(
              compiles, engine.compile_count))

    want = np.asarray(generate(
        model, params, np.stack([prompts[a], prompts[b]]), size.new_tokens,
        temperature=0.0))
    ref_model = GPT2LMHeadModel(_gpt2_config(size, use_flash_attention=False))
    ref_logits = jax.jit(lambda p, x: ref_model.apply({"params": p}, x))
    equal, margins = [], []
    for i, row in zip((a, b), want):
        equal.append(list(reqs[i].tokens) == row.tolist())
        margins.append(float(np.max(_token_margins(
            ref_logits, params, prompts[i], reqs[i].tokens))))
    # Right means: the plain model's greedy choice at every position, up to a
    # bf16 near-tie. Equality with generate() follows unless a near-tie fell
    # the other way there; both are printed.
    check(max(margins) <= TOKEN_MARGIN_TOL,
          "served tokens disagree with the reference model: margins {} (tol "
          "{}), equal to generate: {}".format(margins, TOKEN_MARGIN_TOL,
                                             equal))

    mixed = _program(engine.perf_xray(), "mixed_step")
    if expect_kernels:
        check(mixed["kernel_calls"] >= cfg.n_layer,
              "paged decode kernel missing from the mixed step: {} custom "
              "calls".format(mixed["kernel_calls"]))
    ttft = [r.first_token_time - r.submit_time for r in reqs[1:]]
    out = {
        "requests": len(reqs), "new_tokens": size.new_tokens,
        "compile_count": compiles,
        "equal_to_generate": equal, "max_token_margin": margins,
        "mixed_step_kernel_calls": mixed["kernel_calls"],
        "smoke_readings": {
            "first_request_seconds_with_compile": first_s,
            "ttft_seconds_median": float(np.median(ttft)),
            "tokens_per_second": (len(reqs) - 1) * size.new_tokens / wall,
        },
    }
    engine.close()
    del engine, reqs

    # int8 KV: the q8 paged kernel, once.
    q8 = deepspeed.init_inference(
        model=model, params=params,
        config={"inference": dict(size.serve, int8_kv=True)})
    streams = q8.generate(prompts[:3], max_new_tokens=size.new_tokens // 2)
    check(all(len(s) == size.new_tokens // 2 for s in streams),
          "int8-KV pass left unfinished requests")
    # Quantized KV moves logits by more than a near-tie, so its streams are
    # held to the looser "no token the reference finds implausible".
    q8_margin = float(np.max(_token_margins(
        ref_logits, params, prompts[0], streams[0])))
    check(q8_margin <= 10 * TOKEN_MARGIN_TOL,
          "int8-KV stream off the reference by {} logits".format(q8_margin))
    q8_mixed = _program(q8.perf_xray(), "mixed_step")
    if expect_kernels:
        check(q8_mixed["kernel_calls"] >= cfg.n_layer,
              "q8 paged kernel missing: {} custom calls".format(
                  q8_mixed["kernel_calls"]))
    q8.close()
    out["int8_kv"] = {"max_token_margin": q8_margin,
                      "mixed_step_kernel_calls": q8_mixed["kernel_calls"]}
    return out


# ---------------------------------------------------------------- four chips

def zero2_phase(size, mesh, expect_kernels):
    """ZeRO-2 over four devices against stage 0 on one of them: same model,
    seed and global batch."""
    import jax

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.parallel.mesh import build_mesh

    cfg = _gpt2_config(size, use_flash_attention=True)
    model = GPT2LMHeadModel(cfg)
    ids = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, size=(size.batch, size.seq))

    def run(mesh, stage):
        engine, _, _, _ = deepspeed.initialize(
            model=model, model_parameters=_init_params(model, ids[:1]),
            mesh=mesh, config_params=_train_config(size, stage))
        losses = [float(engine.train_batch(batch=(ids, ids)))
                  for _ in range(3)]
        return engine, losses

    engine, losses = run(mesh, 2)
    devices = list(engine.mesh.devices.flat)
    check(len(devices) == 4, "zero2 mesh has {} devices".format(len(devices)))

    sharded = replicated = replicated_bytes = 0
    for name in ("exp_avg", "exp_avg_sq"):
        for leaf in jax.tree.leaves(engine.opt_state[name]):
            shard = leaf.addressable_shards[0].data
            if len(leaf.sharding.device_set) == 4 and \
                    shard.size * 4 == leaf.size:
                sharded += 1
            else:
                replicated += 1
                replicated_bytes += leaf.nbytes
                check(leaf.size < 4096 or leaf.shape[0] % 4,
                      "large moment {} {} left unsharded".format(
                          name, leaf.shape))
    check(sharded > 0, "no optimizer moment is sharded four ways")

    stats = [d.memory_stats() for d in devices]
    in_use = None
    if all(s is not None for s in stats):
        in_use = [int(s["bytes_in_use"]) for s in stats]
        check(max(in_use) <= 2 * np.mean(in_use),
              "device memory is lopsided: {}".format(in_use))
    step = _program(engine.perf_xray(), "fused_train_step")
    check(step["collectives"].get("reduce-scatter", 0)
          + step["collectives"].get("all-reduce", 0) > 0,
          "no gradient collective in the ZeRO-2 step: {}".format(
              step["collectives"]))
    if expect_kernels:
        check(step["kernel_calls"] >= 2 * cfg.n_layer,
              "flash kernels missing from the ZeRO-2 step: {}".format(
                  step["kernel_calls"]))
    del engine

    _, ref_losses = run(build_mesh(devices=devices[:1]), 0)
    check(np.allclose(losses, ref_losses, atol=LOSS_TOL, rtol=0),
          "ZeRO-2 losses {} vs one-device {} (tol {})".format(
              losses, ref_losses, LOSS_TOL))
    return {"losses": losses, "one_device_losses": ref_losses,
            "moments_sharded_4way": sharded,
            "moments_replicated": replicated,
            "moments_replicated_bytes": replicated_bytes,
            "bytes_in_use_per_device": in_use,
            "kernel_calls": step["kernel_calls"],
            "collectives": step["collectives"]}


def fleet_phase(size):
    """Four replicas placed by parallel.mesh.replica_devices against one
    engine: each replica's parameters on its own device, equal streams."""
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.inference import ServingFleet
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

    cfg = _gpt2_config(size, use_flash_attention=True)
    model = GPT2LMHeadModel(cfg)
    params = _init_params(model, np.zeros((1, 16), np.int32))
    prompts = _prompts(size, cfg.vocab_size)

    fleet = ServingFleet(model, params, n_replicas=4,
                         config=dict(size.serve))
    try:
        homes = fleet.param_devices
        check(len(set(homes)) == 4, "replicas share devices: {}".format(homes))
        reqs = [fleet.submit(p, max_new_tokens=size.new_tokens)
                for p in prompts]
        check(fleet.wait_idle(timeout_s=600.0), "fleet did not settle")
        streams = [list(r.tokens) for r in reqs]
        served_by = sorted({r.replica_id for r in reqs})
    finally:
        fleet.close()
    del fleet

    single = deepspeed.init_inference(
        model=model, params=params, config={"inference": dict(size.serve)})
    want = single.generate(prompts, max_new_tokens=size.new_tokens)
    single.close()
    check(streams == [list(w) for w in want],
          "fleet streams differ from a single engine's")
    return {"replica_devices": [str(d) for d in homes],
            "replicas_that_served": served_by, "requests": len(reqs)}


# ---------------------------------------------------------------------- main

def _run_phases(phases):
    ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            result = fn()
        except Exception:  # the boundary that reports, then fails the run
            traceback.print_exc()
            emit({"phase": name, "ok": False,
                  "error": traceback.format_exc(limit=1).splitlines()[-1]})
            ok = False
            continue
        emit(dict({"phase": name, "ok": True,
                   "phase_seconds": time.perf_counter() - t0}, **result))
    return ok


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the four-chip phases")
    args = parser.parse_args(argv)

    # The user autotune table lives outside the checkout; point it inside so
    # nothing this run reads is a file git would not commit.
    os.environ["XDG_CACHE_HOME"] = os.path.join(REPO, ".jax_cache", "xdg")

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or device["count"] < args.chips:
        print("chip_smoke: needs {} TPU chip(s), JAX reports {}".format(
            args.chips, device), file=sys.stderr)
        emit({"ok": False, "device": device})
        return 1

    import jax.monitoring

    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def count(event, **_):
        name = event.rsplit("/", 1)[-1]
        if name in cache_events:
            cache_events[name] += 1

    jax.monitoring.register_event_listener(count)

    if args.chips == 4:
        # The default mesh over all four devices, as a user gets it.
        phases = [("zero2-dp4", lambda: zero2_phase(FULL, None, True)),
                  ("fleet4", lambda: fleet_phase(FULL))]
    else:
        one = build_mesh(devices=devices[:1])
        phases = [("train", lambda: train_phase(FULL, one, True)),
                  ("layer", lambda: layer_phase(FULL, True)),
                  ("serve", lambda: serve_phase(FULL, True))]
    ok = _run_phases(phases)
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    emit(dict({"phase": "compile_cache", "dir": cache_dir,
               "entries": entries}, **cache_events))
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
