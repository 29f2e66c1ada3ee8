"""Pipeline-executor on-chip sanity bench.

Single-chip comparison of the SAME transformer-block stack driven two
ways: the plain engine's one fused jitted program vs the PipelineEngine's
interpreted instruction stream at pp=1 (and pp=1 with micro-batching).
The ratio prices the executor machinery — per-instruction dispatch,
per-stage jit boundaries, recompute backward — on real hardware; the
multi-stage overlap itself is CPU-mesh-validated (pipe_dispatch_profile).

Prints one JSON line per scenario. Shapes follow GPT-2 355M blocks on
TPU (24 x d1024 blocks at T=1024) and shrink off-TPU.
"""

import json
import time

import _platform

_platform.setup()

import jax
import flax.linen as nn
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as deepspeed
from deepspeed_tpu.models.gpt2 import Block, GPT2Config
from deepspeed_tpu.pipe import LayerSpec, PipelineModule


def sq_loss(out, labels):
    # Parameter-less pipeline loss: keeps the comparison about the
    # executor, not LM-head machinery (the headline bench owns that).
    return jnp.mean(jnp.square(out.astype(jnp.float32)))


class BlockStack(nn.Module):
    """The same blocks as the pipeline layers, one monolithic module."""
    config: GPT2Config
    n_layers: int

    @nn.compact
    def __call__(self, x, labels=None):
        for i in range(self.n_layers):
            x = Block(self.config, name="h{}".format(i))(x)
        return sq_loss(x, labels)


def measure(fn, steps, tokens_per_step, warmup=2):
    out = None
    for _ in range(warmup):
        out = fn()
    # Barrier: keep warmup and first-call compile out of the timed window.
    float(np.asarray(jax.device_get(out)).ravel()[0])
    t0 = time.perf_counter()
    last = None
    for _ in range(steps):
        last = fn()
    # the scalar fetch waits for every step queued before it
    float(np.asarray(jax.device_get(last)).ravel()[0])
    dt = (time.perf_counter() - t0) / steps
    return tokens_per_step / dt, dt


def main():
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        batch, seq, d, n_layers, steps = 8, 1024, 1024, 24, 8
    else:
        # batch must cover gas=4 x the CPU test mesh's dp=8 in the batch
        # triangle (micro_batch_per_gpu >= 1).
        batch, seq, d, n_layers, steps = 32, 128, 64, 4, 3
    cfg = GPT2Config(vocab_size=256, n_positions=seq, n_embd=d,
                     n_layer=n_layers, n_head=max(d // 64, 1), dropout=0.0,
                     use_flash_attention=on_tpu)
    rng = np.random.RandomState(0)
    x = rng.randn(batch, seq, d).astype(np.float32)
    y = np.zeros((batch,), np.int64)
    tokens = batch * seq

    def opt():
        return {"type": "Adam", "params": {"lr": 1e-4}}

    # (a) plain engine, fused train_batch — the reference point.
    plain, _, _, _ = deepspeed.initialize(
        model=BlockStack(cfg, n_layers),
        config_params={"train_batch_size": batch, "optimizer": opt(),
                       "bf16": {"enabled": True}})
    plain_tps, plain_dt = measure(
        lambda: plain.train_batch(batch=(x, y)), steps, tokens)

    results = {"plain_fused": {"tokens_per_s": round(plain_tps, 1),
                               "step_s": round(plain_dt, 4)}}

    # (b) pipeline executor at pp=1 (pure machinery overhead), and
    # (c) pp=1 with gas=4 micro-batching (the 1F1B dispatch pattern).
    for gas in (1, 4):
        model = PipelineModule(
            layers=[LayerSpec(Block, cfg) for _ in range(n_layers)],
            num_stages=1, loss_fn=sq_loss, seed_layers=True, base_seed=42)
        pipe, _, _, _ = deepspeed.initialize(
            model=model,
            config_params={"train_batch_size": batch,
                           "gradient_accumulation_steps": gas,
                           "optimizer": opt(),
                           "bf16": {"enabled": True}})
        mb = batch // gas
        micro = [(x[i * mb:(i + 1) * mb], y[i * mb:(i + 1) * mb])
                 for i in range(gas)]
        tps, dt = measure(
            lambda: pipe.train_batch(data_iter=iter(list(micro))),
            steps, tokens)
        results["pipe_pp1_gas{}".format(gas)] = {
            "tokens_per_s": round(tps, 1), "step_s": round(dt, 4)}

    # (d) COMPILED pipeline (runtime/pipe/compiled.py): the whole schedule
    # as one XLA program, pp=1 single-chip (multi-stage is a mesh story).
    # Same cfg as the interpreter rows — flash included (the shard_map
    # worker launches raw pallas kernels).
    for gas in (1, 4):
        model = PipelineModule(
            layers=[LayerSpec(Block, cfg) for _ in range(n_layers)],
            num_stages=1, loss_fn=sq_loss, seed_layers=True, base_seed=42,
            compiled=True)
        cpipe, _, _, _ = deepspeed.initialize(
            model=model,
            config_params={"train_batch_size": batch,
                           "gradient_accumulation_steps": gas,
                           "optimizer": opt(),
                           "bf16": {"enabled": True}})
        mb = batch // gas
        micro = [(x[i * mb:(i + 1) * mb], y[i * mb:(i + 1) * mb])
                 for i in range(gas)]
        tps, dt = measure(
            lambda: cpipe.train_batch(data_iter=iter(list(micro))),
            steps, tokens)
        results["compiled_pp1_gas{}".format(gas)] = {
            "tokens_per_s": round(tps, 1), "step_s": round(dt, 4)}

    eff = results["pipe_pp1_gas1"]["tokens_per_s"] / plain_tps
    print(json.dumps({
        "metric": "pipe_executor_efficiency_vs_fused",
        "value": round(eff, 4),
        "unit": "ratio",
        "extra": dict(results, platform=jax.default_backend(),
                      batch=batch, seq=seq, d=d, n_layers=n_layers,
                      compiled_efficiency=round(
                          results["compiled_pp1_gas4"]["tokens_per_s"] /
                          plain_tps, 4),
                      note="pp=1 pipeline vs one fused program, same "
                           "blocks; gas=4 row adds 1F1B micro-batch "
                           "dispatch; recompute backward means the "
                           "pipeline rows pay ~4/3 the FLOPs; compiled_* "
                           "rows run the one-program engine "
                           "(runtime/pipe/compiled.py), same kernels"),
    }), flush=True)


if __name__ == "__main__":
    main()
