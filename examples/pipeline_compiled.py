"""Compiled pipeline parallelism: the whole 1F1B-style schedule as ONE
XLA program (`PipelineModule(..., compiled=True)`).

Where the default PipelineEngine interprets the reference's instruction
streams (runtime/pipe/engine.py), the compiled engine traces the entire
schedule — micro-batch wavefront, inter-stage collective-permute
transfers, remat, backward, optimizer — into a single jitted global-mesh
program (runtime/pipe/compiled.py). Zero per-instruction host work, and
it runs unchanged under multi-controller `jax.distributed` (multi-host
pods), which a host-driven interpreter cannot.

Run (virtual 8-device CPU mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/pipeline_compiled.py
"""

import os

import jax

# The example wants a mesh of several devices: it runs on the virtual CPU
# mesh unless JAX_PLATFORMS names another platform.
if os.environ.get("JAX_PLATFORMS", "") in ("", "cpu"):
    jax.config.update("jax_platforms", "cpu")

import numpy as np

import deepspeed_tpu as deepspeed
from deepspeed_tpu.models.gpt2 import GPT2Config, gpt2_pipeline


def main():
    cfg = GPT2Config(vocab_size=512, n_positions=128, n_embd=128,
                     n_layer=4, n_head=4, dropout=0.0)
    # Untied head: the compiled engine keeps per-stage params on disjoint
    # 'pipe' slices, so cross-stage weight tying is excluded by design.
    model = gpt2_pipeline(cfg, num_stages=2, compiled=True)
    engine, _, _, _ = deepspeed.initialize(
        model=model,
        config_params={
            "train_batch_size": 16,
            "gradient_accumulation_steps": 4,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            # ZeRO x PP: fp32 moments shard over each stage's data
            # replicas inside the same program.
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 1},
        })

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(16, 64))
    micro = [(ids[i * 4:(i + 1) * 4], ids[i * 4:(i + 1) * 4])
             for i in range(4)]
    for step in range(5):
        loss = engine.train_batch(data_iter=iter(list(micro)))
        print("step {} loss {:.4f}".format(step + 1, loss))


if __name__ == "__main__":
    main()
