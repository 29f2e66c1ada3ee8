"""Configurations of ``"model_type": "rehearsal"``: the builder of a second
family, as the first PR that adds one will write it (``test_rehearsal.py``):
its own keys, its own reference, its own account of the cache. The program
has no second family through ``init_inference`` yet, so the model it builds
is the GPT-2 module; the family PR's builds its own. Serving only: it owes
what the ``serve`` driver asks and nothing of training
(benchmark/README.md, "What a builder owes").
"""

from tests.benchmark.reference import rehearsal as reference


class Model(object):
    def __init__(self, config):
        import jax.numpy as jnp

        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

        self.cfg = GPT2Config(
            vocab_size=config["vocab_size"],
            n_positions=config["max_position_embeddings"],
            n_embd=config["hidden_size"],
            n_layer=config["num_hidden_layers"],
            n_head=config["num_attention_heads"],
            layer_norm_epsilon=config["norm_eps"], dropout=0.0,
            dtype=jnp.dtype(config["deployment"]["compute_dtype"]),
            use_flash_attention=True)
        self.module = GPT2LMHeadModel(self.cfg)
        self.n_layer, self.n_head = self.cfg.n_layer, self.cfg.n_head
        self.kv_heads = config["num_key_value_heads"]
        self.head_dim = self.cfg.n_embd // self.cfg.n_head
        self.vocab_size = self.cfg.vocab_size

    def sizes(self):
        return {"num_hidden_layers": self.n_layer,
                "hidden_size": self.cfg.n_embd, "heads": self.n_head,
                "kv_heads": self.kv_heads, "vocab_size": self.vocab_size}

    def init_params(self, seed, on_host=False):
        import jax
        import jax.numpy as jnp

        return jax.jit(lambda key: self.module.init(
            key, jnp.zeros((1, 16), jnp.int32))["params"])(
                jax.random.PRNGKey(seed))

    def kv_bytes_per_token_layer(self):
        """What the family's cache WOULD hold: a key and a value for each
        of its key/value heads, fewer than its query heads."""
        return 2 * self.kv_heads * self.head_dim * self.cfg.dtype.itemsize

    def reference_logits(self, params, ids):
        return reference.logits(params, ids, self.n_head)
