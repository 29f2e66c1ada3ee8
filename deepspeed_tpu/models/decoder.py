"""A config-driven decoder block of the present-day kind, for serving.

Pre-RMSNorm blocks with rotary positions, RMSNorm on the projected queries
and keys, multi-head attention over the serving cache, and a mixture of gated
(SwiGLU) experts with exact top-k routing; a final RMSNorm and an output head
that is its own matrix unless the configuration ties it. No bias anywhere.
OLMoE-1B-7B (``model_type`` ``olmoe``) is this block at 16 layers, hidden
2048, 16 heads of 128, 64 experts of width 1024, 8 a token.

The same block with its optional pieces, each a field of the configuration
that leaves a model without it lowering as before, is a HYBRID stack
(Granite 4.0-H Small, ``model_type`` ``granitemoehybrid``): a static kind a
layer (``layer_types``: ``attention`` or ``mamba``, the Mamba-2 mixer of
``models/mamba2.py`` with a recurrent state a row in place of keys), fewer
stored key/value heads than query heads (``n_kv_head``: query head ``j``
reads stored head ``j // (n_head / n_kv_head)``), no rotary (``rope``
False), a softmax scale of the configuration's own (``attn_scale``), the
chip's share of the routed experts (``experts_held``: the router stays
``n_experts`` wide, ``moe/routed.py``), a shared expert (``shared_width``)
and Granite's multipliers (on the embedding, on every residual branch, under
the logits). One path, not two: every piece is one static branch inside
``forward``, so what OLMoE runs is what it ran.

The same block again is DeepSeek-V3's (``model_type`` ``deepseek_v3``; R1
and V3.1 share it), by four more fields that are off by default:
``kv_lora_rank`` makes the mixer LATENT ATTENTION (``mla``): queries through
a low-rank bottleneck, keys and values through ONE compressed latent a token
plus one rotary key all heads share, so a token caches ``kv_lora_rank +
qk_rope_dim`` values (576) in ONE plane and no value plane; ``rope_yarn``
gives the rotary frequencies YaRN's ramp; ``dense_layers`` leading layers
take a dense gated feed-forward of ``dense_width`` in place of experts; and
``router_scoring`` ``"sigmoid"`` routes by ``routed.route_grouped``.

The same block once more is Kimi Linear's (``model_type`` ``kimi_linear``),
by a third kind in ``layer_types`` and two values the fields above did not
take: ``"kda"`` layers run Kimi Delta Attention (``models/kda.py``: a
delta-rule recurrence with a decay a channel and a ``[d_k, d_v]`` float32
state a head a row), three to one latent-attention layer WITHOUT positions
(``rope`` False with ``kv_lora_rank`` set: the shared key's lanes and the
queries' are carried unrotated) whose queries come straight from the stream
(``q_lora_rank`` 0: no ``wq_a``, no ``q_a_norm``). Its cache is BOTH a latent
plane, as deep as the MLA layers only (``kv_layers``), and a recurrent state
a row (``cache_spec`` composes the two); its dense leading layer's mixer is
a KDA layer. The kinds that carry a state a row are ``RECURRENT``: one
branch of ``forward`` for Mamba-2 and KDA alike.

And once more LFM2's (``model_type`` ``lfm2_moe``: LFM2-8B-A1B), by a fourth
kind and one more value: ``"shortconv"`` layers run the GATED SHORT
CONVOLUTION (``models/shortconv.py``: two gates around a depthwise causal
convolution of width 3), whose ONLY state a row is its tail, two rows of the
stream's width, so a recurrent kind carries as many arrays a layer as its
``state_keys`` names and ``forward`` threads whatever they are; and
``qk_norm`` ``"head"`` norms the queries and keys A HEAD (one weight
``[head_dim]`` for all query heads, one for all key heads, over each head's
lanes, before the rotation) where ``True`` is OLMoE's norm over the whole
projected width. Its three attention layers in twelve are the first to be
rotary, grouped-query and paged at once, at a head of 64: the pool packs two
stored heads a lane tile while four query heads share each
(``decode_attention.py``, ``lane_pack`` x grouped-query rows).

And a fifth time Jamba's (``model_type`` ``jamba``: AI21-Jamba2-3B), by a
fifth kind and two values of fields that exist: ``"mamba1"`` layers run the
Mamba-1 SELECTIVE SCAN (``models/mamba1.py``: a decay a channel AND a state
index, a step a channel through a bottleneck of rank ``mamba_dt_rank``, ``B``
and ``C`` from a projection of the convolved stream, three inner RMSNorms, no
heads, no gated norm and no matmul form for a prompt; ``mamba_state`` and
``mamba_conv`` are the fields Mamba-2 reads, ``mamba_expand`` and
``mamba_dt_rank`` its own); ``dense_layers == n_layer`` is A STACK WITHOUT
EXPERTS (``expert_layers`` 0: every layer's feed-forward is ``dense_ffn``,
the tree has no ``moe`` and no router, and the adapter hands its pool no
``aux_moe_*`` for ``forward`` to count into; ``n_experts``, ``experts_per_token`` and ``expert_width``
are then read by nothing); ``residual_fp32`` keeps the residual stream in
float32 (``stream_dtype``: the type a branch is added in, a stack 28 layers
deep);
and ``n_kv_head`` 1 is MULTI-QUERY attention, all
``n_head`` query heads over ONE stored head, with ``rope`` False (20 over one
of 128: the paged kernels put the 20 beside ``S`` on the sublane axis, and a
page of one head is small enough that a unit of the decode scan's call joins
eight, ``decode_attention.py`` ``_pages_per_unit``).

And a sixth time SDAR's (``model_type`` ``sdar_moe``: SDAR-30B-A3B-Chat), by
NO new piece of the block (32 query heads over 4 stored heads of 128,
``qk_norm`` ``"head"``, 128 experts of 768, 8 a token, renormalised) and two
fields that say how its tokens are made: ``block_length`` and
``mask_token_id``. It generates by DIFFUSION OVER BLOCKS: a block of
``block_length`` absolute positions is passed over repeatedly, its still-masked
positions carrying the mask id's embedding, every position of the block seeing
every other and all earlier blocks (the one visibility rule,
``decode_attention.visible_upto``); the logits are read AT a masked position
(no shift). ``forward`` knows none of this beyond the rule: a pass is ``ids``
[B, block_length] at a block's start, written at ``[pos, pos + block_length)``
with ``pos`` left where it was (the adapter's ``block_pass``), and which
positions are unmasked when is the serving engine's scan
(``inference/engine.py`` ``_diffusion_chunk_program``).

And a seventh time Phi-4-mini-flash's (``model_type`` ``phi4flash``: the
"SambaY" DECODER-HYBRID-DECODER of arXiv:2507.06607), by four more kinds in
``layer_types`` and four fields that are off by default. ``"swa"`` layers are
WINDOW ATTENTION: a query at ``p`` sees keys ``p - sliding_window < j <= p``
(``decode_attention.visible``, the one expression of both bounds), and their
keys live in a SECOND GROUP of planes (``cache_spec``'s ``window_layers``: a
fixed ring of pages a slot in a paged pool, ``kv_pool.py`` A WINDOW GROUP;
``CacheAttention.windowed``) beside the full group, which here is ONE plane
deep: the one ``"attention"`` layer writes it and every ``"xattn"`` layer (a
CROSS layer: it projects queries only, ``wq`` / ``wo``, and appends nothing)
READS it (``kv_plane``: the plane a layer reads is the last one written
before it; YOCO's cross-decoder). ``"gmu"`` layers are GATED MEMORY UNITS,
``out = (m * silu(h W_in)) W_out`` with ``m`` the same token's MEMORY: the
scan output ``y`` of the last Mamba-1 layer before them (``memory_layer``;
``mamba1.mixer(.., hand_y=True)``), a value that lives for one ``forward`` and
is threaded down the stack beside the stream: no state, no cache, no pool
array. The fields: ``sliding_window``; ``layer_norm`` (LayerNorm with a
weight AND a bias, ``<name>_b`` beside every norm's weight, in place of
RMSNorm); ``attn_bias`` (a bias on the attention projections, ``bqkv`` / ``bq``
and ``bo``); ``mamba_inner_norms`` False (Mamba-1 without Jamba's three inner
norms). The region of a trace takes the kind's word: ``swa``, ``attn``,
``xattn``, ``gmu``.

And an eighth time Nemotron-H's (``model_type`` ``nemotron_h``:
NVIDIA-Nemotron-3-Nano-30B-A3B), by a kind that is NO MIXER and three values.
Every stack above gives a layer TWO branches, a mixer then a feed-forward;
here a layer is ONE branch, ``x = x + f_i(norm_i(x))``, and ``layer_types``
says which: a mixer kind (``"mamba"``, ``"attention"``) that takes NO
feed-forward after it, or ``"moe"``, the expert feed-forward ALONE
(``one_branch``: a stack is one as soon as it names a ``"moe"`` layer). Such a
stack holds ONE norm a layer, and its expert stacks are as deep as its
``"moe"`` layers only (``moe_layers``; ``expert_layers`` counts them: 23 of
52, so the 29 layers without experts hold none). ``mamba_groups`` 8 gives the
Mamba-2 mixer eight groups of ``B`` and ``C`` and a gated norm a group
(``models/mamba2.py``); ``expert_act`` ``"relu2"`` makes an expert, and the
shared one, the UNGATED ``down(relu(up(x)) ** 2)`` over ``w_up [E, C, F]``
(``moe/routed.py``, TWO FORMS); its router is DeepSeek-V3's with one group, and
its six attention layers are grouped-query without positions (32 over 2).

THE ABSORBED FORM IS THE ONE PATH of latent attention, for the lane and the
scan alike. Per head ``[k_nope_h | v_h] = c_kv W_kvb,h``, so
``q_nope_h . k_nope_h(u) = (q_nope_h W_uk,h) . c_kv(u)`` and
``sum_u p(u) v_h(u) = (sum_u p(u) c_kv(u)) W_uv,h``: each query head is
carried INTO the latent (``w_uk`` [H, rank, nope]), scored against what the
cache holds, ``[c_kv | k_r]``, as one stored head of width 576 whose first
512 lanes are also its values, and carried back out (``w_uv`` [H, v, rank]).
Nothing per head is ever cached or re-expanded. The expanded form
(materialised ``k_nope`` and ``v``) is what the plain reference computes;
``tests/unit/test_mla.py`` holds the two equal. The parameters hold
``kv_b_proj`` as the two stacks ``w_uk`` / ``w_uv`` (the same numbers), and
the rotary columns of ``wq_rope`` and ``wkv_a`` in HALVES order: the published
checkpoint interleaves a pair's two lanes and its code de-interleaves every
activation before rotating halves, which a loader does once, to the columns
(a common permutation of q's and k's lanes changes no score).

Like ``models/generation.py`` for GPT-2 this is a pure-functional program over
a parameter tree, one ``forward`` for prefill, chunked prefill, decode and
verify: rows sit at their own frontiers ``cache['pos']``, rotary angles come
from ``pos[b] + s``, and keys are rotated BEFORE they are written, so what the
cache holds never depends on how a prompt was chunked. The cache side
(layouts, paging, int8, the kernels) is ``generation.CacheAttention``, shared
with GPT-2's block.

Parameters (``DecoderLM.init(key, ids)["params"]``), layers stacked on a
leading axis, dense kernels ``[in, out]``::

    embed [V, C]                 final_norm [C]       lm_head [C, V] (untied)
    layers/attn_norm [L, C]      layers/wqkv [L, C, 3*H*D]  (q | k | v)
    layers/q_norm, k_norm [L, H*D] ([L, D]: a head)   layers/wo [L, H*D, C]
    layers/ffn_norm [L, C]       layers/router [L, C, E]
    layers/w_gate_up [L, E, C, 2F]  (gate | up)       layers/w_down [L, E, F, C]

A hybrid stack (``layer_types`` given) keeps under ``layers`` what EVERY
layer has (the two norms, the router, the held experts ``[L, E_held, ..]``,
``shared_gate_up [L, C, 2Fs]`` / ``shared_down [L, Fs, C]``) and stacks each
kind of mixer over the layers of that kind: ``attn/wqkv [La, C, (H + 2 Hkv) D]``
(+ the QK norms), ``attn/wo``; ``mamba/...`` [Lm, ..] (``mamba2.init_layer``);
``kda/...`` [Lk, ..] (``kda.init_layer``); ``shortconv/...`` [Lc, ..]
(``shortconv.init_layer``); ``mamba1/...`` [Ls, ..] (``mamba1.init_layer``).
A latent-attention stack (``kv_lora_rank`` given) keeps the two norms under
``layers`` and stacks the rest by kind (``L`` the layers of that kind):
``mla/wq_a [L, C, Rq]``,
``q_a_norm [L, Rq]``, ``wq_nope [L, H nope, Rq]``, ``wq_rope [L, H rope, Rq]``
(``q_b_proj``'s nope and rotary columns of every head, ``[out, in]``; from
``C`` and without the first two where ``q_lora_rank`` is 0),
``wkv_a [L, C, R + rope]``, ``kv_a_norm [L, R]``, ``w_uk [L, H, R, nope]``, ``w_uv [L, H, v, R]``,
``wo [L, H v, C]`` (the four stacks the absorbed form contracts a head at
a time lie ``[out, in]``, contraction minor, as the step's matmuls read
them: laid ``[in, out]`` the compiler transposes each whole stack every
step, 0.9 GB of temporaries at DeepSeek-V3's widths; found by compiling for
a described v5e); ``dense/w_gate_up [Ld, C, 2 Fd]``, ``w_down [Ld, Fd, C]``
for the leading dense layers; ``moe/...`` [L - Ld, ..] what ``layers`` holds
of an expert layer elsewhere, and ``router_bias [L - Ld, E]`` float32 (no
``moe`` at all where ``Ld == L``). A ONE-BRANCH stack keeps under ``layers``
the one norm a layer, ``norm [L, C]``, and nothing else; its mixers by kind as
above (``mamba/...`` [Lm, ..], ``attn/...`` [La, ..]) and ``moe/...`` [Le, ..]
over its ``"moe"`` layers: ``router [Le, C, E]``, ``router_bias [Le, E]``,
``w_up [Le, E_held, C, F]`` / ``w_down [Le, E_held, F, C]`` and ``shared_up
[Le, C, Fs]`` / ``shared_down [Le, Fs, C]`` where ``expert_act`` is
``"relu2"`` (``w_gate_up`` / ``shared_gate_up`` as above where it is gated).

The regions of a trace (``jax.named_scope``, under the caller's
``prefill_lane`` / ``decode_scan``): ``embed``; per layer ``attn`` (norm, qkv,
``rope``, ``qk_norm``, attention, projection; latent attention: ``q_proj``,
``kv_proj``, ``rope``, ``absorb`` (the two per-head products with
``W_kvb``), ``o_proj``), ``kv_write``, ``kv_view``,
or ``mamba`` (``mamba2.mixer``'s words), ``kda`` (``kda.mixer``'s),
``shortconv`` (``shortconv.mixer``'s) or ``mamba1`` (``mamba1.mixer``'s);
``moe`` holding ``router``,
``dispatch``, ``experts``, ``combine`` and ``shared``, or ``mlp`` for a dense
layer; then ``lm_head``. A one-branch layer is ONE of these regions
(``mamba``, ``attn`` or ``moe``), under the same words.

The layers are unrolled (a static ``layer=`` in the cache kernels' index
maps), not scanned: eight of them compile in well under GPT-2's 24, and a
traced layer index would take a scalar-prefetch operand the kernels do not
have (PERF.md section 6, PR 27).
"""

import math
import typing

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.analysis.annotations import hot_path
from deepspeed_tpu.models import generation, kda, mamba1, mamba2, shortconv
from deepspeed_tpu.moe import routed


class DecoderConfig(typing.NamedTuple):
    """Hashable: the static argument of every jitted serving program. The
    cache's shape is ``cache_spec(cfg)`` (``ModelAdapter.cache_spec``);
    ``use_flash_decode`` and ``kv_page_len`` are stamped by the adapter's
    ``bind``. The fields from ``n_kv_head`` on are the optional pieces of
    the module docstring, each off by default."""

    vocab_size: int
    n_layer: int
    n_head: int
    head_dim: int
    hidden_size: int
    n_positions: int
    n_experts: int
    experts_per_token: int
    expert_width: int
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # True: over the whole projected width; "head": over each head's lanes
    qk_norm: typing.Union[bool, str] = True
    norm_topk_prob: bool = False
    tie_word_embeddings: bool = False
    dtype: typing.Any = jnp.bfloat16
    initializer_range: float = 0.02
    use_flash_decode: typing.Optional[bool] = None
    kv_page_len: int = 0
    n_kv_head: typing.Optional[int] = None     # None: one a query head
    rope: bool = True
    attn_scale: typing.Optional[float] = None  # None: 1 / sqrt(head_dim)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    shared_width: int = 0                      # 0: no shared expert
    # (first, count) of the router's experts this chip holds; None: all
    experts_held: typing.Optional[typing.Tuple[int, int]] = None
    # "attention" | "mamba" | "kda" | "shortconv" | "mamba1" a layer; None:
    # attention everywhere. "moe": the expert feed-forward ALONE, in a stack
    # whose layers are ONE branch each (``one_branch``)
    layer_types: typing.Optional[typing.Tuple[str, ...]] = None
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_state: int = 0
    mamba_conv: int = 4
    mamba_chunk: int = 256
    # latent attention (module docstring): 0 is attention by heads
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # (factor, original positions, beta_fast, beta_slow, mscale,
    # mscale_all_dim) of YaRN's frequencies; None: theta ** (-2i / d)
    rope_yarn: typing.Optional[typing.Tuple[float, ...]] = None
    dense_layers: int = 0                      # leading layers, no experts
    dense_width: int = 0
    router_scoring: str = "softmax"            # | "sigmoid", group-limited
    n_group: int = 1
    topk_group: int = 1
    routed_scaling: float = 1.0
    # Kimi Delta Attention (``models/kda.py``), where ``layer_types`` has it
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    # The gated short convolution's width (``models/shortconv.py``). A field
    # of its own: a kind's ``state_shapes`` reads its own width, and a stack
    # may hold two kinds (Kimi's KDA at 4, LFM2's at 3).
    shortconv_kernel: int = 3
    # The Mamba-1 selective scan (``models/mamba1.py``), where ``layer_types``
    # has it: ``mamba_expand * hidden_size`` channels and the rank of the
    # step's bottleneck. ``mamba_state`` and ``mamba_conv`` are Mamba-2's
    # fields: no stack holds both kinds.
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # The residual stream in float32 (``stream_dtype``; Mamba's reference
    # code calls it ``residual_in_fp32``): matrices and matmul inputs stay
    # in ``dtype``. A stack tens of layers deep rounds a ``dtype`` stream
    # twice a layer. False: the stream is in ``dtype``.
    residual_fp32: bool = False
    # What the model IS, not how it is served: it generates by DIFFUSION OVER
    # BLOCKS of ``block_length`` positions (module docstring). A key at
    # absolute position ``j`` is seen by a query at ``i`` iff ``j <= (i //
    # block_length + 1) * block_length - 1``: causal across blocks, both ways
    # inside one (``decode_attention.visible_upto``, the one expression
    # wherever a mask is formed). 1 is next-token generation, whose rule that
    # then IS the causal one. ``mask_token_id``: the id whose embedding a
    # still-masked position carries.
    block_length: int = 1
    mask_token_id: typing.Optional[int] = None
    # The decoder-hybrid-decoder stack (module docstring), where
    # ``layer_types`` has "swa" | "xattn" | "gmu": the positions a window
    # layer sees, its own counted (0: the model has no window layer).
    sliding_window: int = 0
    # LayerNorm (weight and bias) in place of RMSNorm, everywhere a norm of
    # the stream stands; a bias on the attention projections; Mamba-1 with
    # (Jamba) or without (as published) the three inner RMSNorms.
    layer_norm: bool = False
    attn_bias: bool = False
    mamba_inner_norms: bool = True
    # Groups of Mamba-2 heads that share a ``B`` and a ``C``, and whose
    # channels the gated norm runs over apart (``models/mamba2.py``).
    mamba_groups: int = 1
    # The step's ``H`` columns of a Mamba-2 layer a matrix of their own
    # (``mamba/dt_proj`` [C, H]) beside ``in_proj`` [C, 2W + 2GN]: for a
    # stack whose ``2W + 2GN + H`` columns are not whole 128-lane tiles
    # (Nemotron-H: 10,304 = 80.5 x 128; as one matrix the TPU compiler
    # copies the WHOLE stacked ``in_proj``, 1.19 GB, before every layer's
    # matmul of the prefill lane: found by compiling the step for a
    # described v5e). False: one matrix (Granite's 16,768 are whole tiles).
    mamba_dt_apart: bool = False
    # The form of an expert and of the shared one (``moe/routed.py``, TWO
    # FORMS): "swiglu" (gated, ``w_gate_up``) | "relu2" (ungated, ``w_up``).
    expert_act: str = "swiglu"

    @property
    def stream_dtype(self):
        """The type of the residual stream, and so of the sums a dense
        feed-forward forms for it: its two matmuls emit in this type
        (``dense_mix``), and every branch is cast to it as it is added
        (``_residual``)."""
        return jnp.dtype(jnp.float32 if self.residual_fp32 else self.dtype)

    @property
    def n_embd(self):
        """Width of one token's QUERIES; ``n_embd // n_head`` is the head
        size wherever a cache is read."""
        return self.n_head * self.head_dim

    @property
    def n_kv(self):
        """Key/value heads a token stores in an attention layer."""
        return self.n_kv_head or self.n_head

    @property
    def kinds(self):
        return self.layer_types or ("attention",) * self.n_layer

    @property
    def kv_layers(self):
        """The layers that hold keys (or a latent), in order: layer
        ``kv_layers[a]`` is layer ``a`` of the cache's planes."""
        return tuple(i for i, k in enumerate(self.kinds) if k == "attention")

    @property
    def window_layers(self):
        """The layers that hold a WINDOW of keys, in order: layer
        ``window_layers[a]`` is layer ``a`` of the window group's planes."""
        return tuple(i for i, k in enumerate(self.kinds) if k == "swa")

    def kv_plane(self, i):
        """The plane of the full group that layer ``i`` (``"attention"``,
        which writes it, or ``"xattn"``, which only reads) attends: the last
        one written at or before it."""
        return sum(1 for k in self.kinds[:i + 1] if k == "attention") - 1

    @property
    def memory_layer(self):
        """The Mamba-1 layer whose scan output is the gated memory units'
        memory: the last one before the first ``"gmu"``; None without one."""
        if "gmu" not in self.kinds:
            return None
        return max(i for i in self.mamba1_layers
                   if i < self.kinds.index("gmu"))

    @property
    def mamba_layers(self):
        return tuple(i for i, k in enumerate(self.kinds) if k == "mamba")

    @property
    def kda_layers(self):
        return tuple(i for i, k in enumerate(self.kinds) if k == "kda")

    @property
    def shortconv_layers(self):
        return tuple(i for i, k in enumerate(self.kinds) if k == "shortconv")

    @property
    def mamba1_layers(self):
        return tuple(i for i, k in enumerate(self.kinds) if k == "mamba1")

    @property
    def one_branch(self):
        """Is a layer ONE branch (a mixer OR a feed-forward, one norm) and
        not a mixer then a feed-forward? So as soon as ``layer_types`` names
        a feed-forward as a layer of its own."""
        return "moe" in self.kinds

    @property
    def moe_layers(self):
        """The layers of a one-branch stack that ARE an expert feed-forward,
        in order: layer ``moe_layers[e]`` is layer ``e`` of the expert
        stacks."""
        return tuple(i for i, k in enumerate(self.kinds) if k == "moe")

    @property
    def expert_layers(self):
        """How many layers route to experts: every one past the leading
        ``dense_layers``, or a one-branch stack's ``moe_layers``. 0
        (``dense_layers == n_layer``) is a stack WITHOUT
        experts: no ``moe`` tree, no router, no ``aux_moe_*``."""
        if self.one_branch:
            return len(self.moe_layers)
        return self.n_layer - self.dense_layers

    @property
    def held(self):
        """(first, count) of the routed experts held here."""
        return self.experts_held or (0, self.n_experts)

    @property
    def softmax_scale(self):
        """The softmax scale of latent attention: ``(nope + rope) ** -1/2``,
        times YaRN's temperature squared (``mscale_all_dim``) where the
        frequencies are YaRN's; ``attn_scale`` overrides."""
        if self.attn_scale is not None:
            return self.attn_scale
        scale = float(self.qk_nope_dim + self.qk_rope_dim) ** -0.5
        if self.rope_yarn is not None:
            scale *= yarn_mscale(self.rope_yarn[0], self.rope_yarn[5]) ** 2
        return scale

    @property
    def latent_width(self):
        """Values a token STORES in a latent-attention layer, ``[c_kv | k_r]``
        and zeros up to a whole number of 128-lane tiles (576 -> 640): the
        chip stores and moves a minor dim in whole tiles whatever the shape
        says (``kv_pool.py``, THE PAGED ARENA), so the pad costs no byte that
        576 would save and the kernels get aligned blocks."""
        return -(-(self.kv_lora_rank + self.qk_rope_dim) // 128) * 128

    @property
    def layer_norm_epsilon(self):
        return self.rms_norm_eps


# The kinds of layer whose mixer carries a recurrent state a row in place of
# keys (``layer_types``; the region of a trace and the parameters' tree take
# the kind's name), each a module with ``state_shapes(cfg)``, ``state_keys(j)``
# (the arrays layer ``j`` of the kind carries a row, ANY number of them: two
# for Mamba-2, KDA and Mamba-1, a state and a tail; one for the short
# convolution), ``init_layer(key, cfg)`` and ``mixer(p, cfg, h, *states, pos,
# n_valid)`` -> ``(out, *states)``.
RECURRENT = {"mamba": mamba2, "kda": kda, "shortconv": shortconv,
             "mamba1": mamba1}


class CacheSpec(typing.NamedTuple):
    """What a pool of this model holds (``ModelAdapter.cache_spec``): the k
    and v planes (``latent``: the ONE plane) are as deep as the layers that
    hold keys and as wide as the heads a token STORES, and ``slot_state``
    names the recurrent state a row carries beside them
    (the ``state_shapes`` of every ``RECURRENT`` kind; empty without it)."""

    n_layer: int
    n_head: int
    n_embd: int
    n_positions: int
    dtype: typing.Any
    layer_norm_epsilon: float
    use_flash_decode: typing.Optional[bool]
    kv_page_len: int
    slot_state: tuple = ()
    latent: int = 0
    # the window group (``kv_pool.py``, A WINDOW GROUP): positions a window
    # layer sees and how many such layers hold keys, as wide as the full group
    window: int = 0
    window_layers: int = 0


def cache_spec(cfg):
    heads, width = (1, cfg.latent_width) if cfg.kv_lora_rank \
        else (cfg.n_kv, cfg.n_kv * cfg.head_dim)
    return CacheSpec(len(cfg.kv_layers), heads, width, cfg.n_positions,
                     cfg.dtype, cfg.rms_norm_eps, cfg.use_flash_decode,
                     cfg.kv_page_len,
                     tuple(state for kind in RECURRENT.values()
                           for state in kind.state_shapes(cfg)),
                     cfg.kv_lora_rank, cfg.sliding_window,
                     len(cfg.window_layers))


def served_config(cfg, use_flash_decode=None):
    """``cfg`` with ``use_flash_decode`` decided: the argument, else the
    configuration's own, else the platform's (the kernels on a TPU)."""
    flag = use_flash_decode
    if flag is None:
        flag = cfg.use_flash_decode
    if flag is None:
        flag = generation.default_flash_decode()
    return cfg._replace(use_flash_decode=bool(flag))


def init_params(key, cfg):
    """Weights normal at ``initializer_range``, norms at 1, in ``cfg.dtype``;
    a Mamba layer's as ``mamba2.init_layer``. A layer at a time (``lax.map``
    over the layers' keys), so that the largest value the generator holds is
    one layer's, not the stack's."""
    c, e, f = cfg.hidden_size, cfg.held[1], cfg.expert_width
    q_w, kv_w = cfg.n_embd, cfg.n_kv * cfg.head_dim
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape):
        return cfg.initializer_range * jax.random.normal(k, shape, cfg.dtype)

    def attention(k_qkv, k_out, cross=False):
        # a cross layer projects queries only (``wq``: it reads another
        # layer's keys); biases where the configuration has them
        if cross:
            out = {"wq": normal(k_qkv, (c, q_w)),
                   "wo": normal(k_out, (q_w, c))}
        else:
            out = {"wqkv": normal(k_qkv, (c, q_w + 2 * kv_w)),
                   "wo": normal(k_out, (q_w, c))}
        if cfg.attn_bias:
            out["bq" if cross else "bqkv"] = jnp.zeros(
                (q_w if cross else q_w + 2 * kv_w,), cfg.dtype)
            out["bo"] = jnp.zeros((c,), cfg.dtype)
        if cfg.qk_norm:
            a_head = cfg.qk_norm == "head"
            out["q_norm"] = jnp.ones((cfg.head_dim if a_head else q_w,),
                                     cfg.dtype)
            out["k_norm"] = jnp.ones((cfg.head_dim if a_head else kv_w,),
                                     cfg.dtype)
        return out

    def experts(k):
        # the feed-forward half of a layer that has experts, in the form
        # ``cfg.expert_act`` names: gate and up side by side, or up alone
        ks = jax.random.split(k, 5)
        first, wide = routed.first_matrix(cfg.expert_act)
        out = {"router": normal(ks[2], (c, cfg.n_experts)),
               "w_" + first: normal(ks[3], (e, c, wide * f)),
               "w_down": normal(ks[4], (e, f, c))}
        if cfg.shared_width:
            k1, k2 = jax.random.split(jax.random.fold_in(k, 5))
            out["shared_" + first] = normal(
                k1, (c, wide * cfg.shared_width))
            out["shared_down"] = normal(k2, (cfg.shared_width, c))
        if cfg.router_scoring == "sigmoid":
            out["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
        return out

    def layer(k):
        if cfg.one_branch:      # one norm, and its branch by kind below
            return {"norm": jnp.ones((c,), cfg.dtype)}
        ks = jax.random.split(k, 5)
        out = {"attn_norm": jnp.ones((c,), cfg.dtype),
               "ffn_norm": jnp.ones((c,), cfg.dtype)}
        if cfg.layer_norm:
            out.update(attn_norm_b=jnp.zeros((c,), cfg.dtype),
                       ffn_norm_b=jnp.zeros((c,), cfg.dtype))
        if not cfg.dense_layers:
            out.update(experts(k))
        if cfg.layer_types is None and not cfg.kv_lora_rank:
            out.update(attention(ks[0], ks[1]))
        return out

    def latent(k):
        ks = jax.random.split(k, 6)
        nh, r, rq = cfg.n_head, cfg.kv_lora_rank, cfg.q_lora_rank
        dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        out = {"wq_nope": normal(ks[1], (nh * dn, rq or c)),
               "wq_rope": normal(jax.random.fold_in(ks[1], 1),
                                 (nh * dr, rq or c)),
               "wkv_a": normal(ks[2], (c, r + dr)),
               "kv_a_norm": jnp.ones((r,), cfg.dtype),
               "w_uk": normal(ks[3], (nh, r, dn)),
               "w_uv": normal(ks[4], (nh, dv, r)),
               "wo": normal(ks[5], (nh * dv, c))}
        if rq:      # 0: the queries come straight from the stream
            out.update(wq_a=normal(ks[0], (c, rq)),
                       q_a_norm=jnp.ones((rq,), cfg.dtype))
        return out

    def dense(k):
        k1, k2 = jax.random.split(k)
        return {"w_gate_up": normal(k1, (c, 2 * cfg.dense_width)),
                "w_down": normal(k2, (cfg.dense_width, c))}

    def memory_unit(k):
        k1, k2 = jax.random.split(k)
        w = mamba1.width(cfg)
        return {"w_in": normal(k1, (c, w)), "w_out": normal(k2, (w, c))}

    def stacked(make, salt, n):
        return jax.lax.map(make, jax.random.split(
            jax.random.fold_in(key, salt), n))

    params = {"embed": normal(k_embed, (cfg.vocab_size, c)),
              "layers": jax.lax.map(layer,
                                    jax.random.split(k_layers, cfg.n_layer)),
              "final_norm": jnp.ones((c,), cfg.dtype)}
    if cfg.layer_types is not None and not cfg.kv_lora_rank:
        params["attn"] = jax.lax.map(
            lambda k: attention(*jax.random.split(k)), jax.random.split(
                jax.random.fold_in(key, 3), len(cfg.kv_layers)))
    for kind, salt, cross in (("swa", 12, False), ("xattn", 13, True)):
        if kind in cfg.kinds:
            params[kind] = stacked(
                lambda k, cross=cross: attention(*jax.random.split(k),
                                                 cross=cross),
                salt, cfg.kinds.count(kind))
    if "gmu" in cfg.kinds:
        params["gmu"] = stacked(memory_unit, 14, cfg.kinds.count("gmu"))
    if cfg.layer_norm:
        params["final_norm_b"] = jnp.zeros((c,), cfg.dtype)
    for kind, salt in (("mamba", 4), ("kda", 9), ("shortconv", 10),
                       ("mamba1", 11)):
        if kind in cfg.kinds:
            params[kind] = stacked(
                lambda k, kind=kind: RECURRENT[kind].init_layer(k, cfg),
                salt, cfg.kinds.count(kind))
    if cfg.kv_lora_rank:
        params["mla"] = stacked(latent, 6, len(cfg.kv_layers))
    if cfg.dense_layers:
        params["dense"] = stacked(dense, 7, cfg.dense_layers)
    if (cfg.dense_layers or cfg.one_branch) and cfg.expert_layers:
        params["moe"] = stacked(experts, 8, cfg.expert_layers)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = normal(k_head, (c, cfg.vocab_size))
    return params


def _rms32(x, scale, eps):
    """RMSNorm in float32; the caller casts."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _norm32(x, tree, name, cfg):
    """The stack's norm of the stream in float32, the caller casts: RMSNorm
    by ``tree[name]``, or where ``cfg.layer_norm`` LayerNorm by the weight
    ``tree[name]`` and the bias ``tree[name + "_b"]``."""
    if not cfg.layer_norm:
        return _rms32(x, tree[name], cfg.rms_norm_eps)
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + cfg.rms_norm_eps)
    return y * tree[name].astype(jnp.float32) \
        + tree[name + "_b"].astype(jnp.float32)


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """YaRN's frequencies [dim / 2] (numpy float64 -> float32, static): pair
    ``i`` turns at ``f_i = theta ** (-2i / dim)`` where it makes more than
    ``beta_fast`` turns over the ``original`` positions, at ``f_i / factor``
    where it makes fewer than ``beta_slow``, and on a linear ramp between
    the two pair indices ``low = floor(d(beta_fast))`` and ``high =
    ceil(d(beta_slow))``, ``d(r) = dim ln(original / (2 pi r)) /
    (2 ln theta)``."""
    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    f = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return (f * (1.0 - ramp) + f / factor * ramp).astype(np.float32)


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature ``0.1 mscale ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_angles(positions, head_dim, theta, yarn=None):
    """cos, sin ``[B, S, 1, head_dim / 2]`` float32 of ``positions`` [B, S]:
    frequency ``theta ** (-2i / head_dim)`` for pair i, or YaRN's
    (``yarn``: ``DecoderConfig.rope_yarn``), whose cos and sin also carry
    ``mscale / mscale_all_dim`` (1 as published for DeepSeek-V3)."""
    if yarn is None:
        inv_freq = 1.0 / theta ** (
            jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        mult = 1.0
    else:
        factor, original, fast, slow, mscale, mscale_all = yarn
        inv_freq = jnp.asarray(yarn_inv_freq(head_dim, theta, factor,
                                             original, fast, slow))
        mult = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    return (cos, sin) if mult == 1.0 else (cos * mult, sin * mult)


def _rope(x, cos, sin):
    """Rotate the two HALVES of each head (``x*cos + rotate_half(x)*sin``).
    x ``[B, S, H, D]``, float32 in and out. (Swapping the halves by a
    reverse over a ``[2, D/2]`` view rids the decode scan of the
    concatenate's two small copies a layer and costs three times the device
    time in reshapes: measured, PERF.md section 6, PR 27.)"""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _residual(cfg, x, branch):
    if cfg.residual_multiplier != 1.0:
        branch = branch * cfg.residual_multiplier
    return x + branch.astype(x.dtype)


# The region word of each kind of attention layer (module docstring).
ATTENTION_SCOPES = {"attention": "attn", "swa": "swa", "xattn": "xattn"}


def _cross_mix(layer, cfg, h, i, attend, planes):
    """What a CROSS layer adds to the stream: its own queries against plane
    ``i`` of the full group as another layer of this pass wrote it; nothing
    is appended. No rotary and no QK norm: the one family that has such a
    layer has neither."""
    b, s, c = h.shape
    nh, hd, dt = cfg.n_head, cfg.head_dim, cfg.dtype
    assert not (cfg.rope or cfg.qk_norm), "a cross layer's queries are plain"
    with jax.named_scope("xattn"):
        q = h @ layer["wq"].astype(dt)
        if cfg.attn_bias:
            q = q + layer["bq"].astype(dt)
        q = q.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    y, planes = attend(i, q, None, None, planes, write=False, scope="xattn")
    with jax.named_scope("xattn"):
        y = y.transpose(0, 2, 1, 3).reshape(b, s, nh * hd) \
            @ layer["wo"].astype(dt)
        return (y + layer["bo"].astype(dt) if cfg.attn_bias else y), planes


def attention_mix(layer, cfg, h, i, rope, attend, planes, kind="attention"):
    """What an attention layer ADDS to the stream, from the normed stream
    ``h`` [B, S, C] in ``cfg.dtype``: (y [B, S, C], the cache planes with
    layer ``i`` OF THE CACHE written). ``rope`` None: no rotary. ``kind``:
    ``"swa"`` writes and reads layer ``i`` of the WINDOW group (``planes`` are
    then that group's), ``"xattn"`` reads plane ``i`` and writes nothing."""
    if kind == "xattn":
        return _cross_mix(layer, cfg, h, i, attend, planes)
    b, s, c = h.shape
    nh, nkv, hd, eps, dt = cfg.n_head, cfg.n_kv, cfg.head_dim, \
        cfg.rms_norm_eps, cfg.dtype
    scope = ATTENTION_SCOPES[kind]
    with jax.named_scope(scope):
        qkv = h @ layer["wqkv"].astype(dt)
        if cfg.attn_bias:
            qkv = qkv + layer["bqkv"].astype(dt)
        q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
        if cfg.qk_norm == "head":
            # over each head's lanes, one weight for all the heads; else
            # over the whole projected width, before the heads split
            q, k = q.reshape(b, s, nh, hd), k.reshape(b, s, nkv, hd)
        if cfg.qk_norm:
            with jax.named_scope("qk_norm"):
                q = _rms32(q, layer["q_norm"], eps)
                k = _rms32(k, layer["k_norm"], eps)
        q, k = q.reshape(b, s, nh, hd), k.reshape(b, s, nkv, hd)
        if rope is not None:
            with jax.named_scope("rope"):
                q = _rope(q.astype(jnp.float32), *rope)
                k = _rope(k.astype(jnp.float32), *rope)
        q = q.astype(dt).transpose(0, 2, 1, 3)
        k = k.astype(dt).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, nkv, hd).transpose(0, 2, 1, 3)
    if kind == "swa":
        y, planes = attend.windowed(i, q, k, v, planes, scope)
    else:
        y, planes = attend(i, q, k, v, planes)
    with jax.named_scope(scope):
        y = y.transpose(0, 2, 1, 3).reshape(b, s, nh * hd) \
            @ layer["wo"].astype(dt)
        return (y + layer["bo"].astype(dt) if cfg.attn_bias else y), planes


def attention(layer, cfg, x, i, rope, attend, planes, kind="attention"):
    """An attention layer's mixer: ``x`` [B, S, C] -> (x with
    ``attention_mix`` of its norm added, the cache planes)."""
    scope = ATTENTION_SCOPES[kind]
    with jax.named_scope(scope):
        h = _norm32(x, layer, "attn_norm", cfg).astype(cfg.dtype)
    y, planes = attention_mix(layer, cfg, h, i, rope, attend, planes, kind)
    with jax.named_scope(scope):
        return _residual(cfg, x, y), planes


def gmu_mix(layer, cfg, h, memory):
    """What a GATED MEMORY UNIT adds to the stream (module docstring), from
    the normed stream ``h`` [B, S, C] in ``cfg.dtype`` and ``memory``
    [B, S, W] float32, the same tokens' scan output of ``cfg.memory_layer``:
    ``(memory * silu(h W_in)) W_out`` [B, S, C] float32. Both matmuls take
    ``cfg.dtype`` and emit float32, as a Mamba mixer's gate and ``out_proj``
    do: the gate meets the memory unrounded."""
    dt = cfg.dtype
    gate = jnp.matmul(h, layer["w_in"].astype(dt),
                      preferred_element_type=jnp.float32)
    return jnp.matmul((memory * jax.nn.silu(gate)).astype(dt),
                      layer["w_out"].astype(dt),
                      preferred_element_type=jnp.float32)


def gmu(layer, cfg, x, memory):
    """A gated memory unit's mixer: ``x`` [B, S, C] with ``gmu_mix`` of its
    norm added (region ``gmu``)."""
    with jax.named_scope("gmu"):
        h = _norm32(x, layer, "attn_norm", cfg).astype(cfg.dtype)
        return _residual(cfg, x, gmu_mix(layer, cfg, h, memory))


def latent_token(layer, cfg, h, rope):
    """What a token CACHES in a latent-attention layer, from the normed
    stream ``h`` [B, S, C]: ``[c_kv | k_r | 0]`` [B, 1, S, W] in
    ``cfg.dtype``, the compressed latent after its norm and the one rotary
    key all heads share after its rotation."""
    r, dt = cfg.kv_lora_rank, cfg.dtype
    pad = cfg.latent_width - r - cfg.qk_rope_dim
    with jax.named_scope("kv_proj"):
        kv = h @ layer["wkv_a"].astype(dt)                    # [B, S, R + dr]
        c_kv = _rms32(kv[..., :r], layer["kv_a_norm"], cfg.rms_norm_eps)
    k_r = kv[..., r:].astype(jnp.float32)
    if rope is not None:
        with jax.named_scope("rope"):
            k_r = _rope(k_r[:, :, None], *rope)[:, :, 0]
    return jnp.pad(jnp.concatenate([c_kv, k_r], axis=-1),
                   ((0, 0), (0, 0), (0, pad))).astype(dt)[:, None]


def latent_mix(layer, cfg, h, i, rope, attend, planes):
    """What a latent-attention layer ADDS to the stream, from the normed
    stream ``h`` [B, S, C] in ``cfg.dtype``, in the ABSORBED form (module
    docstring): (y [B, S, C], the cache's one plane with layer ``i``
    written). What ``attend`` is handed: the queries carried into the
    latent, ``[q_nope W_uk | q_rope | 0]`` [B, H, S, W], and
    ``latent_token``'s stored head; it scales the scores by
    ``cfg.softmax_scale`` and returns ``sum_u p(u) c_kv(u)``
    [B, H, S, rank]."""
    b, s, c = h.shape
    nh, r, eps, dt = cfg.n_head, cfg.kv_lora_rank, cfg.rms_norm_eps, cfg.dtype
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    with jax.named_scope("attn"):
        with jax.named_scope("q_proj"):
            c_q = _rms32(h @ layer["wq_a"].astype(dt), layer["q_a_norm"],
                         eps).astype(dt) if cfg.q_lora_rank else h
            q_n = jnp.einsum("bsr,nr->bsn", c_q, layer["wq_nope"].astype(
                dt)).reshape(b, s, nh, dn)
            q_r = jnp.einsum("bsr,nr->bsn", c_q, layer["wq_rope"].astype(
                dt)).reshape(b, s, nh, dr)
        k = latent_token(layer, cfg, h, rope)
        if rope is not None:
            with jax.named_scope("rope"):
                q_r = _rope(q_r.astype(jnp.float32), *rope).astype(dt)
        with jax.named_scope("absorb"):
            q_lat = jnp.einsum("bshd,hrd->bhsr", q_n,
                               layer["w_uk"].astype(dt))
        q = jnp.pad(jnp.concatenate([q_lat, q_r.transpose(0, 2, 1, 3)], -1),
                    ((0, 0),) * 3 + ((0, cfg.latent_width - r - dr),))
    y, planes = attend(i, q, k, None, planes)
    with jax.named_scope("attn"):
        with jax.named_scope("absorb"):
            y = jnp.einsum("bhsr,hdr->bshd", y, layer["w_uv"].astype(dt))
        with jax.named_scope("o_proj"):
            y = y.reshape(b, s, nh * dv) @ layer["wo"].astype(dt)
    return y, planes


def mla(layer, cfg, x, i, rope, attend, planes):
    """A latent-attention layer's mixer: ``x`` [B, S, C] -> (x with
    ``latent_mix`` of its norm added, the cache's plane)."""
    with jax.named_scope("attn"):
        h = _rms32(x, layer["attn_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
    y, planes = latent_mix(layer, cfg, h, i, rope, attend, planes)
    with jax.named_scope("attn"), jax.named_scope("o_proj"):
        return _residual(cfg, x, y), planes


def router_logits(n32, router):
    """The router's logits [T, E] of the float32 normed stream ``n32``
    [T, C]. The published router's softmax is float32; its matmul is too,
    from the float32 norm: where a token's last kept and first cut weights
    are close, bf16 logits would pick another expert than the model does."""
    return jnp.dot(n32, router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def moe(layer, cfg, x, chosen=None):
    """The feed-forward half of a layer: ``x`` [B, S, C] -> (x, tokens
    routed to each HELD expert [E_held], choices that fell on experts held
    elsewhere (a scalar; 0 for a model held whole)). ``chosen``: a list that
    is given the experts each token keeps, [B, S, k] (``forward``'s
    ``aux_moe_choice``)."""
    b, s, c = x.shape
    dt = cfg.dtype
    first, held = cfg.held
    with jax.named_scope("moe"):
        n32 = _rms32(x, layer["ffn_norm"], cfg.rms_norm_eps).reshape(b * s, c)
        with jax.named_scope("router"):
            logits = router_logits(n32, layer["router"])
            if cfg.router_scoring == "sigmoid":
                weights, experts = routed.route_grouped(
                    logits, layer["router_bias"], cfg.experts_per_token,
                    cfg.n_group, cfg.topk_group, cfg.routed_scaling,
                    cfg.norm_topk_prob)
            else:
                weights, experts = routed.route(
                    logits, cfg.experts_per_token, cfg.norm_topk_prob)
        if chosen is not None:
            chosen.append(experts.reshape(b, s, -1))
        gate, counts = routed.dispatch(weights, experts, held, first)
        first, _ = routed.first_matrix(cfg.expert_act)
        out = routed.expert_ffn(n32.astype(dt), gate, layer["w_" + first],
                                layer["w_down"], cfg.expert_act)
        if cfg.shared_width:
            out = out + routed.shared_ffn(
                n32.astype(dt), layer["shared_" + first],
                layer["shared_down"], cfg.expert_act)
        x = _residual(cfg, x, out.reshape(b, s, c))
    absent = b * s * cfg.experts_per_token - jnp.sum(counts)
    return x, counts, absent


def dense_mix(layer, cfg, h):
    """What a dense layer's feed-forward ADDS to the stream, from the normed
    stream ``h`` [.., C] in ``cfg.dtype``: the gated form at ``dense_width``,
    every token. Both matmuls take ``cfg.dtype`` and emit the stream's type
    (``cfg.stream_dtype``), so the gated product is formed in it."""
    f = cfg.dense_width
    gu = jnp.matmul(h, layer["w_gate_up"].astype(cfg.dtype),
                    preferred_element_type=cfg.stream_dtype)
    return jnp.matmul(
        (jax.nn.silu(gu[..., :f]) * gu[..., f:]).astype(cfg.dtype),
        layer["w_down"].astype(cfg.dtype),
        preferred_element_type=cfg.stream_dtype)


def dense_ffn(layer, cfg, x):
    """A leading dense layer's feed-forward: ``x`` with ``dense_mix`` of
    its norm added (region ``mlp``)."""
    with jax.named_scope("mlp"):
        h = _norm32(x, layer, "ffn_norm", cfg).astype(cfg.dtype)
        return _residual(cfg, x, dense_mix(layer, cfg, h))


@hot_path
def forward(params, cfg, ids, cache, attn_name=None):
    """ids [B, S], row b starting at ``cache['pos'][b]``; returns (float32
    logits [B, S, V], the advanced cache). Same contract as
    ``generation._forward``. A cache that carries ``aux_moe_load`` /
    ``aux_moe_routed`` (the adapter's pool does) gets the routed counts
    added: every row the program computes counts, a pad column or an idle
    slot too, so the gauges read the program's load.

    A model with ``RECURRENT`` layers reads and returns the rows' recurrent
    state (``slot_ssm<j>`` / ``slot_conv<j>``, ``slot_kda<j>`` /
    ``slot_kdaconv<j>``, ``slot_shortconv<j>``, ``slot_sel<j>`` /
    ``slot_selconv<j>``: what the kind's
    ``state_keys`` names) and ``cache['n_valid']`` [B]: how many leading
    columns of each row are real, 0 for a row that must not move (default:
    all ``S``). The key is consumed here. A cache that carries
    ``aux_moe_choice`` (no pool does: a replay that asks which experts the
    program keeps) gets it back as this call's choices, [expert layers, B,
    S, k]."""
    s = ids.shape[1]
    dt = cfg.dtype
    cache = dict(cache)
    n_valid = cache.pop("n_valid", None)
    attend = generation.CacheAttention(cfg, cache, s, attn_name)
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[ids]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        x = x.astype(cfg.stream_dtype)
    planes, wplanes = attend.planes, attend.wplanes
    rope = rope_angles(attend.q_pos, cfg.qk_rope_dim or cfg.head_dim,
                       cfg.rope_theta, cfg.rope_yarn) if cfg.rope else None
    state = {}
    if n_valid is None:
        n_valid = jnp.full(ids.shape[:1], s, jnp.int32)
    load = jnp.zeros((cfg.held[1],), jnp.float32)
    absent = jnp.zeros((), jnp.float32)
    chosen = [] if "aux_moe_choice" in cache else None
    n_attn = n_moe = 0
    n_recurrent = {kind: 0 for kind in RECURRENT}
    n_own = {kind: 0 for kind in ("swa", "xattn", "gmu")}
    memory = None       # the gated memory units': this pass's, no state
    for i, kind in enumerate(cfg.kinds):
        layer = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        if cfg.one_branch:      # the one norm, under the name its branch reads
            layer = {"attn_norm": layer["norm"], "ffn_norm": layer["norm"]}
        if kind == "moe":
            x, counts, away = moe(dict(layer, **jax.tree_util.tree_map(
                lambda a: a[n_moe], params["moe"])), cfg, x, chosen)
            load, absent = load + counts, absent + away
            n_moe += 1
            continue
        if kind in RECURRENT:
            j = n_recurrent[kind]
            mix = jax.tree_util.tree_map(lambda a: a[j], params[kind])
            with jax.named_scope(kind):
                h = _norm32(x, layer, "attn_norm", cfg).astype(dt)
                keys = RECURRENT[kind].state_keys(j)
                hands = {"hand_y": True} if i == cfg.memory_layer else {}
                h, *after = RECURRENT[kind].mixer(
                    mix, cfg, h, *(cache[k] for k in keys), attend.pos,
                    n_valid, **hands)
                if hands:
                    memory = after.pop()
                state.update(zip(keys, after))
                x = _residual(cfg, x, h)
            n_recurrent[kind] += 1
        elif kind in n_own:
            j = n_own[kind]
            layer = dict(layer, **jax.tree_util.tree_map(
                lambda a: a[j], params[kind]))
            if kind == "gmu":
                x = gmu(layer, cfg, x, memory)
            elif kind == "swa":
                x, wplanes = attention(layer, cfg, x, j, rope, attend,
                                       wplanes, kind)
            else:
                x, planes = attention(layer, cfg, x, cfg.kv_plane(i), rope,
                                      attend, planes, kind)
            n_own[kind] += 1
        else:
            tree = "mla" if cfg.kv_lora_rank else "attn"
            if tree in params:
                layer = dict(layer, **jax.tree_util.tree_map(
                    lambda a: a[n_attn], params[tree]))
            x, planes = (mla if cfg.kv_lora_rank else attention)(
                layer, cfg, x, n_attn, rope, attend, planes)
            n_attn += 1
        if cfg.one_branch:      # a mixer is the whole layer
            continue
        if i < cfg.dense_layers:
            x = dense_ffn(dict(layer, **jax.tree_util.tree_map(
                lambda a: a[i], params["dense"])), cfg, x)
            continue
        if cfg.dense_layers:
            layer = dict(layer, **jax.tree_util.tree_map(
                lambda a: a[i - cfg.dense_layers], params["moe"]))
        x, counts, away = moe(layer, cfg, x, chosen)
        load, absent = load + counts, absent + away
    with jax.named_scope("lm_head"):
        x = _norm32(x, params, "final_norm", cfg).astype(dt)
        head = params["embed"].T if cfg.tie_word_embeddings \
            else params["lm_head"]
        logits = jnp.dot(x, head.astype(dt),
                         preferred_element_type=jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
    cache = attend.advanced(planes, wplanes)
    cache.update(state)
    if "aux_moe_load" in cache:
        cache["aux_moe_load"] = cache["aux_moe_load"] + load
        cache["aux_moe_routed"] = cache["aux_moe_routed"] + jnp.sum(load)
    if "aux_moe_absent" in cache:
        cache["aux_moe_absent"] = cache["aux_moe_absent"] + absent
    if chosen is not None:
        cache["aux_moe_choice"] = jnp.stack(chosen)
    return logits, cache


def init_cache(cfg, batch, max_len):
    """A zeroed dense cache for ``batch`` rows: the planes of the layers
    that hold keys, ``pos``, and the rows' recurrent state."""
    spec = cache_spec(cfg)
    cache = generation.init_cache(spec, batch, max_len)
    for name, shape, dtype in spec.slot_state:
        cache[name] = jnp.zeros((batch,) + tuple(shape), dtype)
    return cache


class DecoderLM(object):
    """The model as ``deepspeed.init_inference(model=)`` takes it: its
    ``config``, ``init(key, ids)["params"]`` and a cache-free ``apply``."""

    def __init__(self, config):
        self.config = config

    def init(self, key, ids=None):
        return {"params": init_params(key, self.config)}

    def apply(self, variables, ids):
        """Float32 logits [B, T, V] of whole sequences ``ids`` [B, T]."""
        cfg = self.config._replace(use_flash_decode=False)
        cache = init_cache(cfg, ids.shape[0], ids.shape[1])
        return forward(variables["params"], cfg, ids, cache)[0]
