"""Train a small GPT-2 with ZeRO-2 + bf16 on synthetic data.

The minimal end-to-end flow from docs/tutorials/getting-started.md. Runs
anywhere: real TPU chips, or a virtual CPU mesh —

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python examples/train_gpt2.py --steps 10
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

import deepspeed_tpu as deepspeed
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--zero", type=int, default=2)
    args = ap.parse_args()

    cfg = GPT2Config.tiny(dropout=0.0)
    engine, _, _, scheduler = deepspeed.initialize(
        model=GPT2LMHeadModel(cfg),
        config_params={
            "train_batch_size": args.batch * jax.device_count(),
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "scheduler": {"type": "WarmupLR",
                          "params": {"warmup_num_steps": 5,
                                     "warmup_max_lr": 1e-3}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": args.zero},
            "gradient_clipping": 1.0,
        })

    rng = np.random.RandomState(0)
    for step in range(args.steps):
        ids = rng.randint(0, cfg.vocab_size,
                          size=(args.batch * jax.device_count(), args.seq))
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        if step % 5 == 0 or step == args.steps - 1:
            print("step {:3d}  loss {:.4f}  lr {:.2e}".format(
                step, float(loss), scheduler.get_last_lr()[0]))

    engine.save_checkpoint("/tmp/gpt2_example_ckpt")
    print("checkpoint tag:", open("/tmp/gpt2_example_ckpt/latest").read())


if __name__ == "__main__":
    main()
