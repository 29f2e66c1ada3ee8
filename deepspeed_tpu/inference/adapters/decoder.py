"""DecoderAdapter — ``models/decoder.py`` behind the adapter protocol.

The config-driven decoder block (RMSNorm, rotary positions, QK-norm, top-k
routed SwiGLU experts; OLMoE-1B-7B is one) served through everything GPT-2
is served through: the paged pool, ``kv_append`` and the layer-indexed paged
decode kernel on the chip, the gather + einsum path off it, int8 planes and
aliased prefixes (``generation.CacheAttention`` owns all of them). A frozen
dataclass over the hashable ``DecoderConfig``, so it is a valid jit static
argument.

Subclasses GPT2Adapter for what is model-agnostic there: the drafting pair
(``ngram_draft`` / ``accept_counts`` never touch weights) and ``bind``, which
stamps ``use_flash_decode`` and ``kv_page_len`` into the static config.

Routing is exact top-k with no capacity, so nothing is ever dropped and a
row's logits depend on that row alone (the protocol's replay invariant).
Per-expert routed counts ride the pool's ``aux_`` channel and ``observe``
publishes ``moe_expert_load{expert=i}`` and ``moe_tokens_routed``. They
count every row the program computes (idle slots decode garbage by design),
so they read as the program's load, not as requests' tokens.
"""

import dataclasses
from typing import ClassVar

import jax.numpy as jnp

from deepspeed_tpu.analysis.annotations import hot_path
from deepspeed_tpu.inference.adapters.gpt2 import GPT2Adapter
from deepspeed_tpu.models import decoder


@dataclasses.dataclass(frozen=True)
class DecoderAdapter(GPT2Adapter):
    gcfg: decoder.DecoderConfig
    name: ClassVar[str] = "decoder"

    @classmethod
    def from_model(cls, model, use_flash_decode=None):
        """Adapter from a ``DecoderLM`` or its ``DecoderConfig``."""
        return cls(decoder.served_config(getattr(model, "config", model),
                                         use_flash_decode))

    def init_cache(self, batch, max_len, dtype=None):
        return dict(super().init_cache(batch, max_len, dtype),
                    **self.aux_state())

    def aux_state(self):
        return {"aux_moe_load": jnp.zeros((self.gcfg.n_experts,),
                                          jnp.float32),
                "aux_moe_routed": jnp.zeros((), jnp.float32)}

    @hot_path
    def prefill_append(self, params, ids, cache, n_valid=None):
        pos0 = cache["pos"]
        logits, cache = decoder.forward(params, self.gcfg, ids, cache,
                                        attn_name="prefill_attn")
        if n_valid is not None:
            cache = dict(cache, pos=pos0 + n_valid)
        return logits, cache

    @hot_path
    def decode_step(self, params, tok, cache):
        logits, cache = decoder.forward(params, self.gcfg, tok[:, None],
                                        cache)
        return logits[:, 0], cache

    @hot_path
    def verify_forward(self, params, ids, cache):
        pos0 = cache["pos"]
        logits, cache = decoder.forward(params, self.gcfg, ids, cache)
        return logits, dict(cache, pos=pos0)

    def observe(self, snap, registry):
        load = snap.get("aux_moe_load")
        if load is None:
            return
        for i, v in enumerate(load):
            registry.gauge("moe_expert_load", expert=str(i)).set(float(v))
        registry.gauge("moe_tokens_routed").set(
            float(snap.get("aux_moe_routed", 0.0)))
