"""A config-driven decoder block of the present-day kind, for serving.

Pre-RMSNorm blocks with rotary positions, RMSNorm on the projected queries
and keys, multi-head attention over the serving cache, and a mixture of gated
(SwiGLU) experts with exact top-k routing; a final RMSNorm and an output head
that is its own matrix unless the configuration ties it. No bias anywhere.
OLMoE-1B-7B (``model_type`` ``olmoe``) is this block at 16 layers, hidden
2048, 16 heads of 128, 64 experts of width 1024, 8 a token.

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
    layers/q_norm, k_norm [L, H*D]                    layers/wo [L, H*D, C]
    layers/ffn_norm [L, C]       layers/router [L, C, E]
    layers/w_gate_up [L, E, C, 2F]  (gate | up)       layers/w_down [L, E, F, C]

The regions of a trace (``jax.named_scope``, under the caller's
``prefill_lane`` / ``decode_scan``): ``embed``; per layer ``attn`` (norm, qkv,
``rope``, ``qk_norm``, attention, projection), ``kv_write``, ``kv_view``,
``moe`` holding ``router``, ``dispatch``, ``experts``, ``combine``; then
``lm_head``.

The layers are unrolled (a static ``layer=`` in the cache kernels' index
maps), not scanned: eight of them compile in well under GPT-2's 24, and a
traced layer index would take a scalar-prefetch operand the kernels do not
have (PERF.md section 6, PR 27).
"""

import typing

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis.annotations import hot_path
from deepspeed_tpu.models import generation
from deepspeed_tpu.moe import routed


class DecoderConfig(typing.NamedTuple):
    """Hashable: the static argument of every jitted serving program. The
    cache's shape is read from ``n_layer / n_head / n_embd / n_positions /
    dtype`` (``ModelAdapter.cache_spec``); ``use_flash_decode`` and
    ``kv_page_len`` are stamped by the adapter's ``bind``."""

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
    qk_norm: bool = True
    norm_topk_prob: bool = False
    tie_word_embeddings: bool = False
    dtype: typing.Any = jnp.bfloat16
    initializer_range: float = 0.02
    use_flash_decode: typing.Optional[bool] = None
    kv_page_len: int = 0

    @property
    def n_embd(self):
        """Width of one token's keys (and values) in the cache."""
        return self.n_head * self.head_dim

    @property
    def layer_norm_epsilon(self):
        return self.rms_norm_eps


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
    """Weights normal at ``initializer_range``, norms at 1, in ``cfg.dtype``.
    A layer at a time (``lax.map`` over the layers' keys), so that the
    largest value the generator holds is one layer's, not the stack's."""
    c, qkv, e, f = cfg.hidden_size, cfg.n_embd, cfg.n_experts, \
        cfg.expert_width
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape):
        return cfg.initializer_range * jax.random.normal(k, shape, cfg.dtype)

    def layer(k):
        ks = jax.random.split(k, 5)
        return {"attn_norm": jnp.ones((c,), cfg.dtype),
                "wqkv": normal(ks[0], (c, 3 * qkv)),
                "q_norm": jnp.ones((qkv,), cfg.dtype),
                "k_norm": jnp.ones((qkv,), cfg.dtype),
                "wo": normal(ks[1], (qkv, c)),
                "ffn_norm": jnp.ones((c,), cfg.dtype),
                "router": normal(ks[2], (c, e)),
                "w_gate_up": normal(ks[3], (e, c, 2 * f)),
                "w_down": normal(ks[4], (e, f, c))}

    params = {"embed": normal(k_embed, (cfg.vocab_size, c)),
              "layers": jax.lax.map(layer,
                                    jax.random.split(k_layers, cfg.n_layer)),
              "final_norm": jnp.ones((c,), cfg.dtype)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = normal(k_head, (c, cfg.vocab_size))
    return params


def _rms32(x, scale, eps):
    """RMSNorm in float32; the caller casts."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def rope_angles(positions, head_dim, theta):
    """cos, sin ``[B, S, 1, head_dim / 2]`` float32 of ``positions`` [B, S]:
    frequency ``theta ** (-2i / head_dim)`` for pair i."""
    inv_freq = 1.0 / theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]


def _rope(x, cos, sin):
    """Rotate the two HALVES of each head (``x*cos + rotate_half(x)*sin``).
    x ``[B, S, H, D]``, float32 in and out. (Swapping the halves by a
    reverse over a ``[2, D/2]`` view rids the decode scan of the
    concatenate's two small copies a layer and costs three times the device
    time in reshapes: measured, PERF.md section 6, PR 27.)"""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(layer, cfg, x, i, rope, attend, planes):
    """One layer: ``x`` [B, S, C] -> (x, the cache planes with layer ``i``
    written, tokens routed to each expert [E]). ``layer`` holds ONE layer's
    parameters."""
    b, s, c = x.shape
    nh, hd, eps, dt = cfg.n_head, cfg.head_dim, cfg.rms_norm_eps, cfg.dtype
    with jax.named_scope("attn"):
        h = _rms32(x, layer["attn_norm"], eps).astype(dt)
        q, k, v = jnp.split(h @ layer["wqkv"].astype(dt), 3, axis=-1)
        if cfg.qk_norm:
            with jax.named_scope("qk_norm"):
                # over the whole projected width, before the heads split
                q = _rms32(q, layer["q_norm"], eps)
                k = _rms32(k, layer["k_norm"], eps)
        with jax.named_scope("rope"):
            q = _rope(q.astype(jnp.float32).reshape(b, s, nh, hd), *rope)
            k = _rope(k.astype(jnp.float32).reshape(b, s, nh, hd), *rope)
        q = q.astype(dt).transpose(0, 2, 1, 3)
        k = k.astype(dt).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    y, planes = attend(i, q, k, v, planes)
    with jax.named_scope("attn"):
        y = y.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
        x = x + y @ layer["wo"].astype(dt)
    with jax.named_scope("moe"):
        n32 = _rms32(x, layer["ffn_norm"], eps).reshape(b * s, c)
        with jax.named_scope("router"):
            # The published router's softmax is float32; its matmul is too,
            # from the float32 norm: where a token's 8th and 9th weights
            # are close, bf16's rounding would choose for it.
            logits = jnp.dot(n32, layer["router"].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            weights, experts = routed.route(logits, cfg.experts_per_token,
                                            cfg.norm_topk_prob)
        gate, counts = routed.dispatch(weights, experts, cfg.n_experts)
        out = routed.expert_ffn(n32.astype(dt), gate, layer["w_gate_up"],
                                layer["w_down"])
        x = x + out.reshape(b, s, c)
    return x, planes, counts


@hot_path
def forward(params, cfg, ids, cache, attn_name=None):
    """ids [B, S], row b starting at ``cache['pos'][b]``; returns (float32
    logits [B, S, V], the advanced cache). Same contract as
    ``generation._forward``. A cache that carries ``aux_moe_load`` /
    ``aux_moe_routed`` (the adapter's pool does) gets the routed counts
    added: every row the program computes counts, a pad column or an idle
    slot too, so the gauges read the program's load."""
    s = ids.shape[1]
    dt = cfg.dtype
    attend = generation.CacheAttention(cfg, cache, s, attn_name)
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[ids]
    planes = attend.planes
    rope = rope_angles(attend.q_pos, cfg.head_dim, cfg.rope_theta)
    load = jnp.zeros((cfg.n_experts,), jnp.float32)
    for i in range(cfg.n_layer):
        layer = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        x, planes, counts = block(layer, cfg, x, i, rope, attend, planes)
        load = load + counts
    with jax.named_scope("lm_head"):
        x = _rms32(x, params["final_norm"], cfg.rms_norm_eps).astype(dt)
        head = params["embed"].T if cfg.tie_word_embeddings \
            else params["lm_head"]
        logits = jnp.dot(x, head.astype(dt),
                         preferred_element_type=jnp.float32)
    cache = attend.advanced(planes)
    if "aux_moe_load" in cache:
        cache["aux_moe_load"] = cache["aux_moe_load"] + load
        cache["aux_moe_routed"] = cache["aux_moe_routed"] + jnp.sum(load)
    return logits, cache


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
        cache = generation.init_cache(cfg, ids.shape[0], ids.shape[1])
        return forward(variables["params"], cfg, ids, cache)[0]
