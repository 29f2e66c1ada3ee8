"""Configurations of ``"model_type": "gpt2"``: the program's model built from
a configuration file, its weights, its plain reference and its costs. A
configuration of another family brings a file of its own beside this one,
found by its ``model_type``; what the drivers and the readers ask of it is
listed in ``benchmark/README.md`` ("What a builder owes") and held by
``tests/benchmark/test_builders.py``.
"""

import contextlib

from benchmark import costs
from benchmark.reference import gpt2 as reference


class Model(object):
    def __init__(self, config):
        import jax.numpy as jnp

        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

        deployment = config["deployment"]
        self.cfg = GPT2Config(
            vocab_size=config["vocab_size"],
            n_positions=config["n_positions"], n_embd=config["n_embd"],
            n_layer=config["n_layer"], n_head=config["n_head"],
            layer_norm_epsilon=config["layer_norm_epsilon"],
            dropout=config["resid_pdrop"],
            dtype=jnp.dtype(deployment["compute_dtype"]),
            remat=bool(deployment.get("train", {}).get("remat", False)),
            use_flash_attention=True)
        self.module = GPT2LMHeadModel(self.cfg)
        self.n_layer, self.n_head = self.cfg.n_layer, self.cfg.n_head
        self.head_dim = self.cfg.n_embd // self.cfg.n_head
        self.vocab_size = self.cfg.vocab_size

    def sizes(self):
        c = self.cfg
        return {"n_layer": c.n_layer, "n_embd": c.n_embd, "n_head": c.n_head,
                "vocab_size": c.vocab_size, "n_positions": c.n_positions,
                "remat": c.remat, "params": costs.gpt2_num_params(
                    c.n_layer, c.n_embd, c.vocab_size, c.n_positions)}

    def init_params(self, seed, on_host=False):
        """Random weights from the seed, made in one jitted program on the
        default device, or on the host. The seed is an argument of that
        program, so that one cached program serves every seed."""
        import jax
        import jax.numpy as jnp

        place = jax.default_device(jax.devices("cpu")[0]) if on_host \
            else contextlib.nullcontext()
        with place:
            return jax.jit(lambda key: self.module.init(
                key, jnp.zeros((1, 16), jnp.int32))["params"])(
                    jax.random.PRNGKey(seed))

    def kv_bytes_per_token_layer(self):
        """Bytes of cache one token holds in one layer: a key and a value
        for every head, in the type the engine computes and stores in."""
        return 2 * self.n_head * self.head_dim * self.cfg.dtype.itemsize

    def train_flops_per_token(self, seq_len):
        c = self.cfg
        return costs.gpt2_train_flops_per_token(
            c.n_layer, c.n_embd, c.vocab_size, c.n_positions, seq_len)

    def reference_loss(self, params, ids):
        return reference.loss(params, ids, self.n_head)

    def reference_logits(self, params, ids):
        return reference.logits(params, ids, self.n_head)
