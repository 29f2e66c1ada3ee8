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
publishes ``moe_experts_held``, ``moe_expert_layers`` (how many layers of the
stack route: every one past the dense leading layers, or a ONE-BRANCH stack's
``"moe"`` layers alone, 23 of Nemotron-H's 52, so that a reader and a person
can tell how many calls a step holds), ``moe_expert_load{expert=i}`` over the
held experts, ``moe_tokens_routed`` and, for a chip's share of an
expert-parallel layer, ``moe_tokens_absent`` (choices that fell on experts
held elsewhere), all summed over the layers that route and no others.
They count every row the program computes (idle slots decode garbage by
design), so they read as the program's load, not as requests' tokens. A
stack that names a kind a layer (``layer_types``) also says how many layers
of each kind it holds, ``stack_layers{kind=k}`` (23 / 23 / 6). A
STACK WITHOUT EXPERTS (``expert_layers`` 0: Jamba2-3B, every feed-forward
dense) gets no ``aux_moe_*`` channel in its pool and publishes NO ``moe_*``
gauge: a gauge that read 0 experts held would be a wrong reading, not an
absent one.

A HYBRID stack (``layer_types`` with ``mamba``, ``kda``, ``shortconv`` or
``mamba1`` layers) carries a recurrent state a slot:
``cache_spec().slot_state`` names
it, the pool allocates and threads it (``kv_pool.py``, A RECURRENT STATE A
SLOT) and ``observe`` publishes its size as ``ssm_state_bytes`` (ONE gauge
for any ``slot_state``: a Mamba-2 layer's state-space state or a KDA layer's
matrix state, each with its convolution tail, 19 to 38 MB a slot; a Mamba-1
layer's ``[16, 5120]`` float32 state and its three-row tail, 9.3 MB a slot
over Jamba2-3B's 26; a gated
short convolution's tail, the ONE array a layer of a kind with one state, 72
KB a slot at LFM2-8B-A1B's nine layers). A small state is refused what a
large one is: the refusals go by mechanism, not by size, and a two-row tail
cheap enough to snapshot every step is the first candidate to lift the one on
speculation (ROADMAP.md Reach A1). What the stale-cache rule gave
the engine for free does not exist for it, so ``bind`` REFUSES, by the name
of the mechanism, what would need a snapshot of the state: speculative
decoding (a rejected draft has already moved the state), the prefix cache
(a shared prefix needs the state at its end) and int8 planes (the state has
no int8 form, and a pool quantised in part is not built). Host offload,
preemption and handoff ship the state with the slot and resume it bit for
bit.

LATENT ATTENTION (``kv_lora_rank``; DeepSeek-V3's block) gives a cache that
is not a k/v pair: ``cache_spec().latent`` says a token stores one head in
one plane and no value plane (``kv_pool.py``, A LATENT CACHE), and
``kv_latent_bytes_token`` publishes what a token holds over all layers. The
stale-cache rule holds for it, so speculative decoding, preemption, host
offload and handoff work as for any keys (tests/unit/test_mla.py); what
``bind`` refuses by name is what has no one-plane form yet: int8 planes (a
latent's 512 values and its rotary key want scales of their own) and the
prefix cache (its records name a ``pk`` / ``pv`` pair).

GENERATION BY DIFFUSION OVER BLOCKS (``block_length`` > 1;
SDAR-30B-A3B-Chat's, at 4): the adapter says the block length, the mask id and
what a pass is (``block_pass``: the block's positions at the frontier, keys
written, ``pos`` not moved), and the engine picks its decode scan from the
block length alone. ``bind`` refuses by name what cannot compose yet:
speculation, the prefix tiers, int8 planes, and with them host offload and the
prefill / decode roles (a slot's open block has no snapshot form); it holds
``kv_page_len`` and ``prefill_chunk`` to whole blocks and ``denoising_steps``
to a divisor of the block.

WINDOW LAYERS BESIDE FULL ONES (``layer_types`` with ``swa``; the
decoder-hybrid-decoder stack of ``models/decoder.py``, whose ``xattn`` layers
read the ONE full plane and whose ``gmu`` layers read a value of the pass):
``cache_spec()`` names the window group (``window``, ``window_layers``), the
pool gives every slot a fixed ring of pages a window layer (``kv_pool.py``, A
WINDOW GROUP) and ``cache_gauges`` says what that costs: ``kv_window_tokens``,
``kv_window_pages_slot``, ``kv_window_bytes``, and of the stack
``kv_shared_readers`` (the layers that attend the most-read full plane: its
writer and the cross layers after it) and ``gmu_layers``. ``bind`` refuses by
the kind's name what has no ring form: speculation (a verify's rejected keys
would have evicted pages a rollback needs), the prefix cache (a prefix is
shared as pages of the full group's table, and a ring is a slot's own), int8
planes, and host offload and the prefill / decode roles (the hierarchy's
records walk the full group's pages and ship no ring).

BOTH AT ONCE (Kimi Linear: a latent plane as deep as its MLA layers only,
beside a KDA state a slot) gets both sets of refusals, each by its own
mechanism's name: the latent plane's first (int8, prefix cache), then the
state's (speculation; a configuration that asks for nothing the plane
refuses still may not speculate).
"""

import dataclasses
import functools
from typing import ClassVar

import jax.numpy as jnp

from deepspeed_tpu.analysis.annotations import hot_path
from deepspeed_tpu.inference.adapters.gpt2 import GPT2Adapter
from deepspeed_tpu.inference.kv_pool import slot_state_nbytes
from deepspeed_tpu.models import decoder


@functools.lru_cache(maxsize=None)
def _kind_counts(kinds):
    """((kind, how many layers of it), ..) of a stack's ``kinds``: counted
    once a configuration, ``observe`` runs every step."""
    return tuple((kind, kinds.count(kind)) for kind in sorted(set(kinds)))


@dataclasses.dataclass(frozen=True)
class DecoderAdapter(GPT2Adapter):
    gcfg: decoder.DecoderConfig
    name: ClassVar[str] = "decoder"

    @classmethod
    def from_model(cls, model, use_flash_decode=None):
        """Adapter from a ``DecoderLM`` or its ``DecoderConfig``."""
        return cls(decoder.served_config(getattr(model, "config", model),
                                         use_flash_decode))

    def cache_spec(self):
        return decoder.cache_spec(self.gcfg)

    @property
    def recurrent(self):
        """Does a row carry a state that has no position axis?"""
        return bool(self.cache_spec().slot_state)

    @property
    def latent(self):
        """Does a token cache one latent plane in place of keys and values?"""
        return bool(self.gcfg.kv_lora_rank)

    @property
    def windowed(self):
        """Do some layers keep a window of keys on a ring of pages a slot?"""
        return bool(self.gcfg.window_layers)

    @property
    def block_length(self):
        """Positions a block of the model's generation holds (1: it makes
        its tokens one a pass)."""
        return self.gcfg.block_length

    @property
    def mask_token_id(self):
        return self.gcfg.mask_token_id

    def _refuse_for_blocks(self, config):
        """What cannot compose yet with generation by diffusion over blocks,
        each by the name of its mechanism."""
        c = self.gcfg
        if c.mask_token_id is None or not 0 <= c.mask_token_id < c.vocab_size:
            raise ValueError(
                "block_length {} needs mask_token_id inside the vocabulary, "
                "got {!r}".format(c.block_length, c.mask_token_id))
        if self.recurrent or self.latent:
            raise ValueError(
                "generation by diffusion over blocks (block_length {}) is "
                "built for keys and values a head: a pass over a block "
                "rewrites its positions, which a recurrent state cannot "
                "take back and the latent kernel masks causally".format(
                    c.block_length))
        if getattr(config, "paged_kv", False) \
                and config.kv_page_len % c.block_length:
            raise ValueError(
                "kv_page_len {} must hold whole blocks of {} positions: a "
                "block never straddles a page".format(
                    config.kv_page_len, c.block_length))
        if config.prefill_chunk % c.block_length:
            raise ValueError(
                "prefill_chunk {} must hold whole blocks of {} positions: a "
                "prompt is prefilled block by block".format(
                    config.prefill_chunk, c.block_length))
        steps = config.denoising_steps
        if steps is not None and (steps < 1 or c.block_length % steps):
            raise ValueError(
                "denoising_steps {} must divide block_length {}".format(
                    steps, c.block_length))
        refused = (
            ("speculative decoding (spec_decode)",
             config.resolved_spec_decode(),
             "a verify scores drafted NEXT tokens under the causal rule, "
             "and a block's positions are filled in no order a draft could "
             "run ahead of"),
            ("the prefix cache (prefix_cache)", config.prefix_cache,
             "a shared prefix ends where a request's prompt does, inside a "
             "block whose other positions the next request fills "
             "differently, and the tiers keep no block boundary"),
            ("int8 planes (int8_kv)", config.int8_kv,
             "a block's keys are rewritten every pass until its commit, and "
             "the int8 kernels mask causally"),
            ("host offload (host_offload)", config.host_offload,
             "a slot swapped out inside a block would leave the block's "
             "tokens and which of them are masked behind: the tiers "
             "snapshot keys, not a block"),
            ("the prefill and decode roles (role)", config.role != "mixed",
             "a handoff captures a slot after its prompt, and the block "
             "its prompt's tail opened is not in the record"))
        for what, asked, why in refused:
            if asked:
                raise ValueError(
                    "{} cannot serve a model that generates by diffusion "
                    "over blocks (block_length {}): {}".format(
                        what, c.block_length, why))

    def bind(self, config, mesh=None):
        if config is not None and self.block_length > 1:
            self._refuse_for_blocks(config)
        if config is not None and self.latent:
            refused = (
                ("int8 planes (int8_kv)", config.int8_kv,
                 "the int8 tier quantises a k plane and a v plane a head, "
                 "and a latent token is one plane whose compressed values "
                 "and rotary key want scales of their own"),
                ("the prefix cache (prefix_cache)", config.prefix_cache,
                 "a shared prefix is stored and adopted as a pk / pv pair, "
                 "and a latent pool has no value plane"))
            for what, asked, why in refused:
                if asked:
                    raise ValueError(
                        "{} cannot serve a latent-attention cache "
                        "(kv_lora_rank {}, one plane of {} values a "
                        "token): {}".format(what, self.gcfg.kv_lora_rank,
                                            self.gcfg.latent_width, why))
        if config is not None and self.windowed:
            refused = (
                ("speculative decoding (spec_decode)",
                 config.resolved_spec_decode(),
                 "a verify appends drafted keys to the ring, and the ones it "
                 "rejects have already evicted pages a rollback would read"),
                ("the prefix cache (prefix_cache)", config.prefix_cache,
                 "a prefix is shared as pages of the full group's table, and "
                 "a ring of pages is a slot's own"),
                ("int8 planes (int8_kv)", config.int8_kv,
                 "the ring has no scale planes and a pool quantised in part "
                 "is not built"),
                ("host offload (host_offload)", config.host_offload,
                 "the tiers' records walk the full group's pages and ship no "
                 "ring"),
                ("the prefill and decode roles (role)",
                 config.role != "mixed",
                 "a handoff's record ships the full group's pages and no "
                 "ring"))
            for what, asked, why in refused:
                if asked:
                    raise ValueError(
                        "{} cannot serve a model with window layers ({} swa "
                        "layers, a ring of pages a slot for the last {} "
                        "positions): {}".format(
                            what, len(self.gcfg.window_layers),
                            self.gcfg.sliding_window, why))
        if config is not None and self.recurrent:
            refused = (
                ("speculative decoding (spec_decode)",
                 config.resolved_spec_decode(),
                 "a verify moves the recurrent state past a draft it may "
                 "reject, and rollback needs a snapshot of the state a "
                 "draft"),
                ("the prefix cache (prefix_cache)", config.prefix_cache,
                 "a shared prefix needs the recurrent state at its end, "
                 "which no tier stores"),
                ("int8 planes (int8_kv)", config.int8_kv,
                 "the recurrent state has no int8 form and a pool "
                 "quantised in part is not built"))
            for what, asked, why in refused:
                if asked:
                    raise ValueError(
                        "{} cannot serve a model with a recurrent state a "
                        "slot ({}): {}".format(what, ", ".join(
                            "{} {} layers".format(self.gcfg.kinds.count(k), k)
                            for k in decoder.RECURRENT
                            if k in self.gcfg.kinds), why))
        return super().bind(config, mesh)

    def serving_params(self, params):
        # The protocol's default, NOT GPT-2's cast: these families' weights
        # are made in ``cfg.dtype``, and a float32 leaf (Mamba's ``A_log``
        # and ``dt`` path, a float32 state's parameters, a norm computed in
        # float32) is float32 on purpose.
        return params

    def init_cache(self, batch, max_len, dtype=None):
        return dict(decoder.init_cache(self.gcfg, batch, max_len),
                    **self.aux_state())

    def aux_state(self):
        if not self.gcfg.expert_layers:     # nothing routes: no channel
            return {}
        aux = {"aux_moe_load": jnp.zeros((self.gcfg.held[1],), jnp.float32),
               "aux_moe_routed": jnp.zeros((), jnp.float32)}
        if self.gcfg.experts_held is not None:
            aux["aux_moe_absent"] = jnp.zeros((), jnp.float32)
        return aux

    @hot_path
    def prefill_append(self, params, ids, cache, n_valid=None):
        pos0 = cache["pos"]
        if n_valid is not None:
            # pad columns must not move a recurrent state (keys hide them
            # past the frontier; a state has no frontier)
            cache = dict(cache, n_valid=n_valid)
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
    def block_pass(self, params, ids, cache):
        pos0 = cache["pos"]
        logits, cache = decoder.forward(params, self.gcfg, ids, cache)
        return logits, dict(cache, pos=pos0)

    @hot_path
    def verify_forward(self, params, ids, cache):
        if self.recurrent:
            raise NotImplementedError(
                "verify_forward: scoring a draft moves the recurrent state, "
                "and not advancing pos does not move it back")
        pos0 = cache["pos"]
        logits, cache = decoder.forward(params, self.gcfg, ids, cache)
        return logits, dict(cache, pos=pos0)

    def cache_gauges(self, pool):
        """What the pool holds BESIDE the full group's pages, as gauges the
        engine sets once a pool and repeats in ``metrics()`` (module
        docstring, WINDOW LAYERS BESIDE FULL ONES); empty for a model with
        neither window layers nor gated memory units."""
        from deepspeed_tpu.inference.kv_pool import window_pages_slot

        c, out = self.gcfg, {}
        if self.windowed:
            out.update(
                kv_window_tokens=c.sliding_window,
                kv_window_pages_slot=window_pages_slot(pool),
                kv_window_bytes=int(pool["wk"].nbytes + pool["wv"].nbytes))
        if "xattn" in c.kinds:
            planes = [c.kv_plane(i) for i, k in enumerate(c.kinds)
                      if k in ("attention", "xattn")]
            out["kv_shared_readers"] = max(planes.count(p)
                                           for p in set(planes))
        if "gmu" in c.kinds:
            out["gmu_layers"] = c.kinds.count("gmu")
        return out

    def observe(self, snap, registry):
        load = snap.get("aux_moe_load")
        if load is not None:
            first, held = self.gcfg.held
            registry.gauge("moe_experts_held").set(held)
            registry.gauge("moe_expert_layers").set(self.gcfg.expert_layers)
            for i, v in enumerate(load):
                registry.gauge("moe_expert_load",
                               expert=str(first + i)).set(float(v))
            registry.gauge("moe_tokens_routed").set(
                float(snap.get("aux_moe_routed", 0.0)))
            if "aux_moe_absent" in snap:
                registry.gauge("moe_tokens_absent").set(
                    float(snap["aux_moe_absent"]))
        if self.gcfg.layer_types is not None:
            for kind, layers in _kind_counts(self.gcfg.kinds):
                registry.gauge("stack_layers", kind=kind).set(layers)
        if self.recurrent:
            registry.gauge("ssm_state_bytes").set(
                len(snap["pos"]) * slot_state_nbytes(self.cache_spec()))
