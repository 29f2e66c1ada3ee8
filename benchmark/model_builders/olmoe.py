"""Configurations of ``"model_type": "olmoe"``: the program's config-driven
decoder block (``deepspeed_tpu/models/decoder.py``) built from the published
keys, its weights from the seed, its plain reference and its account of the
cache. Serving only: it owes what the ``serve`` driver asks and nothing of
training (benchmark/README.md, "What a builder owes").
"""

from benchmark.reference import olmoe as reference


class Model(object):
    def __init__(self, config):
        import jax.numpy as jnp

        from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

        for key, published in (("attention_bias", False), ("clip_qkv", None),
                               ("rope_scaling", None), ("hidden_act", "silu")):
            if config[key] != published:
                raise ValueError("model_builders/olmoe.py builds {}={!r} "
                                 "only".format(key, published))
        if config["num_key_value_heads"] != config["num_attention_heads"]:
            raise ValueError("the decoder block holds a key and a value for "
                             "every query head")
        n_head = config["num_attention_heads"]
        self.cfg = DecoderConfig(
            vocab_size=config["vocab_size"],
            n_layer=config["num_hidden_layers"], n_head=n_head,
            head_dim=config["hidden_size"] // n_head,
            hidden_size=config["hidden_size"],
            n_positions=config["max_position_embeddings"],
            n_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["intermediate_size"],
            rms_norm_eps=config["rms_norm_eps"],
            rope_theta=float(config["rope_theta"]), qk_norm=True,
            norm_topk_prob=config["norm_topk_prob"],
            tie_word_embeddings=config["tie_word_embeddings"],
            dtype=jnp.dtype(config["deployment"]["compute_dtype"]),
            initializer_range=config["initializer_range"])
        self.module = DecoderLM(self.cfg)
        self.n_layer, self.n_head = self.cfg.n_layer, n_head
        self.head_dim = self.cfg.head_dim
        self.vocab_size = self.cfg.vocab_size

    def sizes(self):
        c = self.cfg
        layer = (c.hidden_size * 3 * c.n_embd + c.n_embd * c.hidden_size
                 + 2 * c.hidden_size + 2 * c.n_embd
                 + c.hidden_size * c.n_experts
                 + c.n_experts * 3 * c.hidden_size * c.expert_width)
        tables = c.vocab_size * c.hidden_size * (
            1 if c.tie_word_embeddings else 2)
        return {"num_hidden_layers": c.n_layer, "hidden_size": c.hidden_size,
                "heads": c.n_head, "head_dim": c.head_dim,
                "num_experts": c.n_experts,
                "num_experts_per_tok": c.experts_per_token,
                "intermediate_size": c.expert_width,
                "vocab_size": c.vocab_size,
                "params": c.n_layer * layer + tables + c.hidden_size}

    def init_params(self, seed, on_host=False):
        """Random weights from the seed in the type they are served in, made
        in one jitted program on the default device. The seed is an argument
        of that program, so that one cached program serves every seed."""
        import jax

        return jax.jit(lambda key: self.module.init(key)["params"])(
            jax.random.PRNGKey(seed))

    def kv_bytes_per_token_layer(self):
        """A key and a value for every head, in the type the engine stores."""
        return 2 * self.n_head * self.head_dim * self.cfg.dtype.itemsize

    def reference_logits(self, params, ids):
        return reference_logits(params, ids, self.cfg)


def published_names(params, cfg):
    """The program's tree under the reference's (the published) names.
    ``layers`` is a generator: one layer's slices exist at a time."""
    w, f = cfg.n_embd, cfg.expert_width

    def layers():
        for i in range(cfg.n_layer):
            p = {k: v[i] for k, v in params["layers"].items()}
            yield {"input_layernorm": p["attn_norm"],
                   "q_proj": p["wqkv"][:, :w],
                   "k_proj": p["wqkv"][:, w:2 * w],
                   "v_proj": p["wqkv"][:, 2 * w:],
                   "q_norm": p["q_norm"], "k_norm": p["k_norm"],
                   "o_proj": p["wo"],
                   "post_attention_layernorm": p["ffn_norm"],
                   "gate": p["router"],
                   "gate_proj": p["w_gate_up"][..., :f],
                   "up_proj": p["w_gate_up"][..., f:],
                   "down_proj": p["w_down"]}

    return {"embed_tokens": params["embed"], "layers": layers(),
            "norm": params["final_norm"],
            "lm_head": params["embed"].T if cfg.tie_word_embeddings
            else params["lm_head"]}


def reference_logits(params, ids, cfg, with_gaps=False):
    """The plain reference on the program's parameter tree, for a
    ``DecoderConfig`` ``cfg`` (the tests call it at a tiny size)."""
    return reference.logits(
        published_names(params, cfg), ids, cfg.n_head, cfg.experts_per_token,
        cfg.rms_norm_eps, cfg.rope_theta, cfg.norm_topk_prob,
        with_gaps=with_gaps)
