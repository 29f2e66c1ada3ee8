"""GPT2Adapter — the generation.py primitives behind the adapter protocol.

THE one sanctioned ``models.generation`` import inside ``inference/``
(graftlint ADAPTER rule): every other inference module reaches the model
only through a ModelAdapter. The adapter is a frozen dataclass over the
hashable ``_GenCfg`` so it is a valid jit static argument — equal
adapters (same spec) hit the same compiled program, and rebuilding the
pool (crash recovery, preemption) never recompiles.

Bit-identity contract: the engine calling these delegating methods
lowers to exactly the jaxprs the pre-adapter engine built by calling
``generation.*`` directly — same primitives, same argument order — so
greedy AND sampled streams, spec on or off, are bit-identical to the
pre-refactor engine (pinned by tests/unit/test_inference.py golden
streams and the conformance kit).
"""

import dataclasses
from typing import ClassVar

from deepspeed_tpu.analysis.annotations import hot_path
from deepspeed_tpu.inference.adapters.protocol import ModelAdapter
from deepspeed_tpu.models import generation


@dataclasses.dataclass(frozen=True)
class GPT2Adapter(ModelAdapter):
    """Dense GPT-2 decode: delegates to models/generation.py."""

    gcfg: generation._GenCfg
    name: ClassVar[str] = "gpt2"

    @classmethod
    def from_model(cls, model, use_flash_decode=None):
        """Adapter from a GPT2LMHeadModel / GPT2Config / _GenCfg.
        ``use_flash_decode=None`` defers to the config, then the platform
        default (generation.default_flash_decode)."""
        return cls(generation.as_gencfg(getattr(model, "config", model),
                                        use_flash_decode=use_flash_decode))

    def cache_spec(self):
        return self.gcfg

    def bind(self, config, mesh=None):
        if config is None:
            return self
        gcfg = self.gcfg
        flag = getattr(config, "use_flash_decode", None)
        if flag is not None and bool(flag) != gcfg.use_flash_decode:
            gcfg = gcfg._replace(use_flash_decode=bool(flag))
        # Paged cache-spec variant (``inference.paged_kv``): stamp the
        # page quantum into the static cfg so the jit cache key names
        # the layout — generation._forward itself dispatches on the
        # cache's ``block_tbl`` key, but two engines serving dense and
        # paged pools must never share a traced program.
        page_len = (int(getattr(config, "kv_page_len", 0))
                    if getattr(config, "paged_kv", False) else 0)
        if page_len != gcfg.kv_page_len:
            gcfg = gcfg._replace(kv_page_len=page_len)
        if gcfg is self.gcfg:
            return self
        return dataclasses.replace(self, gcfg=gcfg)

    def serving_params(self, params):
        return generation.serving_params(params, self.gcfg)

    def init_cache(self, batch, max_len, dtype=None):
        return generation.init_cache(self.gcfg, batch, max_len, dtype)

    @hot_path
    def prefill_append(self, params, ids, cache, n_valid=None):
        return generation.append_forward(params, self.gcfg, ids, cache,
                                         n_valid=n_valid)

    @hot_path
    def decode_step(self, params, tok, cache):
        return generation.decode_step(params, self.gcfg, tok, cache)

    @hot_path
    def verify_forward(self, params, ids, cache):
        return generation.verify_forward(params, self.gcfg, ids, cache)

    @hot_path
    def ngram_draft(self, toks, pos, n, k):
        return generation.ngram_draft(toks, pos, n, k)

    @hot_path
    def accept_counts(self, draft, choices, ok=None):
        return generation.accept_counts(draft, choices, ok=ok)
