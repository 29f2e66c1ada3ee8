"""GPT-2 family in flax — the flagship model for the TPU framework.

The reference ships no models in-tree (users bring Megatron/HF models and the
fused ``DeepSpeedTransformerLayer``); our TPU framework provides a first-class
GPT-2 implementation sized per the perf-baseline configs
(/root/reference/tests/model/Megatron_GPT2/run_perf_baseline.py:18-60:
1.5B/4B/8B configs) so benchmarks and parity tests are self-contained.

TPU-first design notes:
- compute dtype bf16 by default, fp32 params (master weights live with the
  optimizer; see engine precision handling);
- weights laid out so QKV/MLP matmuls hit the MXU as single large GEMMs;
- causal mask folded into the softmax via additive bias (no dynamic shapes);
- optional ``jax.checkpoint`` (remat) per block — the activation-checkpointing
  equivalent (reference activation_checkpointing/checkpointing.py:314).
"""

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.1
    # GPT-2's LayerNorm epsilon (HF layer_norm_epsilon; flax's default of
    # 1e-6 costs ~1e-3 logits parity against reference checkpoints).
    layer_norm_epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # Attention implementation: the Pallas flash kernel gives O(T) memory
    # and beats XLA's dense attention on v5e (355M shapes: 4.5 vs 9.5
    # ms/layer fwd+bwd at T=1024, 9.7 vs 29.3 at T=2048) — on by default.
    use_flash_attention: bool = True
    # Decode-time (KV-cache) attention kernel for models/generation.py and
    # the serving engine: True forces the Pallas flash-decode kernel,
    # False forces the dense einsum path, None defers to
    # generation.default_flash_decode() (on-TPU by default; the
    # DS_TPU_FLASH_DECODE env overrides).
    use_flash_decode: Optional[bool] = None
    # Sequence (context) parallelism: name of the mesh axis the sequence
    # dim is sharded over. When set AND the model runs inside shard_map
    # with that axis bound (the engine's sequence_parallel config does
    # this), positions are offset per shard, attention mixes tokens
    # across shards (ops/transformer/ring_attention.py), and the loss is
    # globally averaged via psum. Outside shard_map the model behaves
    # normally, so init/eval on the full sequence work unchanged.
    sequence_parallel_axis: Any = None
    # "ring" (k/v rotation, O(T/N) memory, any shard count) or "ulysses"
    # (two all_to_alls swapping token<->head sharding; needs
    # n_head % shards == 0; cheaper collectives for small shard counts).
    sequence_parallel_mode: str = "ring"

    @classmethod
    def gpt2_small(cls, **kw):
        return cls(n_embd=768, n_layer=12, n_head=12, **kw)

    @classmethod
    def gpt2_medium(cls, **kw):
        return cls(n_embd=1024, n_layer=24, n_head=16, **kw)

    @classmethod
    def gpt2_large(cls, **kw):
        return cls(n_embd=1280, n_layer=36, n_head=20, **kw)

    @classmethod
    def gpt2_xl(cls, **kw):
        # 1.5B — the BASELINE.md north-star config.
        return cls(n_embd=1600, n_layer=48, n_head=25, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 1024)
        kw.setdefault("n_positions", 128)
        kw.setdefault("dropout", 0.0)
        return cls(n_embd=64, n_layer=2, n_head=4, **kw)

    def num_params(self):
        wpe = self.n_positions * self.n_embd
        wte = self.vocab_size * self.n_embd
        per_block = 12 * self.n_embd * self.n_embd + 13 * self.n_embd
        return wte + wpe + self.n_layer * per_block + 2 * self.n_embd


def _sp_axis(cfg):
    """The sequence-parallel axis name IF the model is being traced inside
    a shard_map that binds it; None otherwise (init / serial eval)."""
    from deepspeed_tpu.parallel.mesh import active_sp_axis
    return active_sp_axis(getattr(cfg, "sequence_parallel_axis", None))


@jax.custom_vjp
def _row_major_grad(w):
    """``w``, its gradient held to ``w``'s own row-major layout. The
    compiler is free to hand a weight's gradient over column-major where
    that makes a rearrangement of its columns free, and ZeRO's
    ``psum_scatter`` of a leaf by ROWS then lowers to an all-reduce and a
    slice (twice the bytes over the wires: the c_attn leaves of GPT-2 XL
    on four chips, PR 49)."""
    return w


def _row_major_grad_fwd(w):
    return w, None


def _row_major_grad_bwd(_, g):
    from jax.experimental.layout import Layout, with_layout_constraint
    return (with_layout_constraint(
        g, Layout(major_to_minor=tuple(range(g.ndim)))),)


_row_major_grad.defvjp(_row_major_grad_fwd, _row_major_grad_bwd)


class _TiledQKV(nn.Dense):
    """``c_attn`` for the flash kernels' packed operand: ``nn.Dense``'s
    parameters under ``nn.Dense``'s names (the ``[C, 3C]`` kernel and the
    bias, q | k | v a head after a head: the tree every checkpoint and the
    serving engine hold) and its arithmetic, with the OUTPUT's columns
    arranged a lane tile at a time (``attention.tile_qkv``). What is
    arranged is the weight, 6 MB a layer at GPT-2 355M, at trace time; the
    32 MB activations around attention are then never transposed."""
    heads: int = 1

    @nn.compact
    def __call__(self, x):
        from deepspeed_tpu.ops.transformer.kernels.attention import tile_qkv
        kernel = self.param("kernel", self.kernel_init,
                            (x.shape[-1], self.features), self.param_dtype)
        bias = self.param("bias", self.bias_init, (self.features,),
                          self.param_dtype)
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=self.dtype)
        d = self.features // (3 * self.heads)
        kernel = _row_major_grad(kernel)
        kernel, bias = (tile_qkv(w, self.heads, d) for w in (kernel, bias))
        return jax.lax.dot_general(
            x, kernel, (((x.ndim - 1,), (0,)), ((), ()))) + bias


class CausalSelfAttention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        B, T, C = x.shape
        nh, hd = cfg.n_head, C // cfg.n_head
        sp = _sp_axis(cfg)

        if cfg.use_flash_attention and sp is None:
            from deepspeed_tpu.ops.transformer.kernels.attention import (
                flash_attention, packed_heads)
            if packed_heads(nh, hd):
                # The Pallas flash kernels on c_attn's output AS IT COMES,
                # ``packed_heads`` heads a 128-lane tile (two at head dim
                # 64), and their output straight into c_proj: no head split,
                # no transpose, forward or backward
                # (ops/transformer/kernels/attention.py, "Two operand
                # layouts"). Where the heads do not fill the last tile
                # (GPT-2 XL's 25) its dead lanes are dropped here.
                qkv = _TiledQKV(3 * C, dtype=cfg.dtype, heads=nh,
                                name="c_attn")(x)
                y = flash_attention(qkv, heads=nh, head_dim=hd, causal=True)
                y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
                y = nn.Dense(C, dtype=cfg.dtype, name="c_proj")(y[..., :C])
                return nn.Dropout(cfg.dropout)(y,
                                               deterministic=deterministic)

        # One fused QKV GEMM (MXU-friendly: [B*T, C] x [C, 3C]).
        qkv = nn.Dense(3 * C, dtype=cfg.dtype, name="c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, nh, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, T, nh, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, T, nh, hd).transpose(0, 2, 1, 3)

        if sp is not None:
            # Sequence-parallel: q/k/v hold this shard's tokens; attend
            # globally via the k/v ring (causality handled at block level)
            # or Ulysses all-to-all head swaps, per config.
            from deepspeed_tpu.ops.transformer.ring_attention import (
                get_sp_attention)
            sp_attn = get_sp_attention(cfg.sequence_parallel_mode)
            y = sp_attn(q, k, v, axis_name=sp, causal=True)
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        elif cfg.use_flash_attention:
            # The head-major entry of the same kernels: a head dim the
            # packed layout cannot hold, or a 'model' axis that would cut a
            # lane tile. Attention-prob dropout moves to the context output
            # (flash never materializes probs).
            y = flash_attention(q, k, v, causal=True)
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        else:
            att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(hd).astype(cfg.dtype)
            causal_mask = jnp.tril(jnp.ones((T, T), dtype=bool))
            att = jnp.where(causal_mask[None, None, :, :], att, jnp.finfo(cfg.dtype).min)
            att = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(cfg.dtype)
            att = nn.Dropout(cfg.dropout)(att, deterministic=deterministic)
            y = jnp.einsum("bhqk,bhkd->bhqd", att, v)
        y = y.transpose(0, 2, 1, 3).reshape(B, T, C)
        y = nn.Dense(C, dtype=cfg.dtype, name="c_proj")(y)
        y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        h = nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype, name="c_fc")(x)
        h = nn.gelu(h, approximate=True)
        h = nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="c_proj")(h)
        h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        # Pre-LN transformer block (GPT-2 style). The regions of a trace:
        # block/ln, block/attn, block/mlp (jax.named_scope).
        ln = functools.partial(nn.LayerNorm, epsilon=cfg.layer_norm_epsilon,
                               dtype=cfg.dtype)
        with jax.named_scope("block"):
            with jax.named_scope("ln"):
                h = ln(name="ln_1")(x)
            with jax.named_scope("attn"):
                x = x + CausalSelfAttention(cfg, name="attn")(h, deterministic)
            with jax.named_scope("ln"):
                h = ln(name="ln_2")(x)
            with jax.named_scope("mlp"):
                x = x + MLP(cfg, name="mlp")(h, deterministic)
        return x


class GPT2LMHeadModel(nn.Module):
    """GPT-2 causal LM. Returns loss when labels given (DeepSpeed convention:
    the model's forward output is the loss; see reference tests
    simple_model.py:9-25 where models return CE loss directly)."""

    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, labels=None, deterministic=True):
        cfg = self.config
        B, T = input_ids.shape
        assert T <= cfg.n_positions

        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.n_embd), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.n_positions, cfg.n_embd), jnp.float32)

        sp = _sp_axis(cfg)
        if sp is not None:
            # This shard holds tokens [idx*T, (idx+1)*T) of the global
            # sequence: offset the position table slice. The GLOBAL length
            # must fit the table — dynamic_slice would silently clamp an
            # out-of-range start to reuse early positions.
            assert jax.lax.axis_size(sp) * T <= cfg.n_positions, (
                "global sequence {} ({} shards x {} local) exceeds "
                "n_positions={}".format(jax.lax.axis_size(sp) * T,
                                        jax.lax.axis_size(sp), T,
                                        cfg.n_positions))
            pos0 = jax.lax.axis_index(sp) * T
            pe = jax.lax.dynamic_slice(wpe, (pos0, 0), (T, cfg.n_embd))
        else:
            pe = wpe[:T]
        with jax.named_scope("embed"):
            x = wte.astype(cfg.dtype)[input_ids] + pe.astype(cfg.dtype)[None]
            x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)

        block_cls = Block
        if cfg.remat:
            block_cls = nn.remat(Block, static_argnums=(2,))
        for i in range(cfg.n_layer):
            x = block_cls(cfg, name="h_{}".format(i))(x, deterministic)

        with jax.named_scope("lm_head"):
            x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                             name="ln_f")(x)

        if labels is None:
            # Tied LM head: logits in fp32 for a stable softmax-xent.
            with jax.named_scope("lm_head"):
                return jnp.einsum("btc,vc->btv", x.astype(jnp.float32),
                                  wte.astype(jnp.float32))

        if sp is not None:
            return _sequence_parallel_xent(x, wte, labels, cfg, sp)

        # Next-token prediction: shift inside the loss. The [B,T,V] logits
        # are never materialized — the head GEMM + softmax-xent run in token
        # chunks (bf16 GEMM, fp32 accumulation) with per-chunk remat, cutting
        # peak HBM by ~2*B*T*V*4 bytes and keeping the GEMM on the MXU.
        return _chunked_softmax_xent(x[:, :-1], wte, labels[:, 1:],
                                     cfg.dtype)


def _chunked_softmax_xent(x, wte, labels, dtype, chunk=2048):
    """Causal-LM form of the shared chunked tied-decoder loss (every token
    supervised; see models/heads.py)."""
    from deepspeed_tpu.models.heads import chunked_tied_softmax_xent
    return chunked_tied_softmax_xent(x, wte, labels, dtype, chunk=chunk)


def _sequence_parallel_xent(x, wte, labels, cfg, axis):
    """Next-token loss under sequence parallelism.

    The label shift crosses shard boundaries: position t predicts label
    t+1, so each shard needs the FIRST label of the next shard for its
    last position. One ppermute of a [B, 1] slice provides it; the global
    last token (next shard is the wrap-around) is excluded via the ignore
    mask. The mean is globally weighted: (psum of per-shard sums) /
    (psum of counts) — shards would otherwise be weighted unevenly.
    """
    from deepspeed_tpu.models.heads import chunked_tied_softmax_xent

    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    # Shard i receives shard (i+1)'s first label (source j sends to j-1).
    perm = [(i, (i - 1) % n) for i in range(n)]
    nxt = jax.lax.ppermute(labels[:, :1], axis, perm)
    # Wrap-around delivery to the last shard is meaningless: mask it.
    nxt = jnp.where(idx == n - 1, -1, nxt.astype(jnp.int32))
    shifted = jnp.concatenate(
        [labels[:, 1:].astype(jnp.int32), nxt], axis=1)
    total, count = chunked_tied_softmax_xent(
        x, wte, shifted, cfg.dtype, ignore_index=-1,
        reduction="sum_count")
    total = jax.lax.psum(total, axis)
    count = jax.lax.psum(count, axis)
    return total / jnp.maximum(count, 1.0)


def create_model(config=None, **kw):
    config = config or GPT2Config(**kw)
    return GPT2LMHeadModel(config)


# ------------------------------------------------------- pipeline variant

class GPT2PipeEmbed(nn.Module):
    """Pipeline stage 0: token + position embedding (the reference's
    EmbeddingPipe, megatron-style first stage). Exposes ``wte`` so a
    TiedLayerSpec can reuse it as the LM head."""
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.n_embd), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.n_positions, cfg.n_embd), jnp.float32)
        T = input_ids.shape[1]
        x = wte.astype(cfg.dtype)[input_ids] + \
            wpe.astype(cfg.dtype)[:T][None]
        # train/eval is signaled by dropout-rng PRESENCE: the pipeline
        # engines pass a dropout rng only on training forwards.
        return nn.Dropout(cfg.dropout)(
            x, deterministic=not self.has_rng("dropout"))


class GPT2PipeBlock(nn.Module):
    """One transformer block as a pipeline layer (the uniform run the
    compiled engine stacks over its 'pipe' axis)."""
    config: GPT2Config

    @nn.compact
    def __call__(self, x):
        return Block(self.config)(x, not self.has_rng("dropout"))


class GPT2PipeFinal(nn.Module):
    """Final LayerNorm + UNTIED LM head producing fp32 logits. Untied so
    the compiled engine (which rejects cross-stage tied params) can run
    it; the tied variant reuses GPT2PipeEmbed via TiedLayerSpec."""
    config: GPT2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                         name="ln_f")(x)
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.vocab_size, cfg.n_embd), jnp.float32)
        # (hidden, head) tuple — the loss_fn runs the CHUNKED tied-decoder
        # softmax-xent so [B,T,V] logits are never materialized (same
        # reason GPT2LMHeadModel routes through chunked_tied_softmax_xent).
        return x, head


def _gpt2_tied_head(layer, params, x):
    """TiedLayerSpec.forward_fn: final norm lives in the PREVIOUS layer;
    this reuse hands the embedding stage's wte to the chunked loss."""
    return x, params["wte"]


class GPT2PipeLN(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x):
        return nn.LayerNorm(epsilon=self.config.layer_norm_epsilon,
                            dtype=self.config.dtype, name="ln_f")(x)


def gpt2_lm_loss(out, labels):
    """Shifted softmax-xent for the pipeline head (the loss_fn slot of
    PipelineModule; reference pipeline models pass CrossEntropy the same
    way). Takes the final stage's (hidden, head) tuple and runs the
    CHUNKED tied-decoder loss so full logits never hit HBM; a plain
    logits array is also accepted."""
    if isinstance(out, (tuple, list)):
        x, head = out
        return _chunked_softmax_xent(x[:, :-1], head, labels[:, 1:],
                                     x.dtype)
    v = out.shape[-1]
    lg = out[:, :-1].reshape(-1, v)
    lb = labels[:, 1:].reshape(-1)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, lb[:, None], axis=1)[:, 0]
    return jnp.mean(lse - gold)


def gpt2_pipeline(config=None, num_stages=2, tied=None, compiled=False,
                  partition_method="uniform", **kw):
    """GPT-2 as a PipelineModule: embed prologue, n_layer uniform blocks,
    final-LN+head epilogue (the reference's GPT2ModelPipe shape:
    Megatron_GPT2 pipeline examples).

    tied=True (default for the interpreter engine) shares the embedding
    with the LM head via TiedLayerSpec; compiled=True forces the untied
    head (the one-program engine keeps per-stage params on disjoint pipe
    slices, so cross-stage sharing is structurally excluded).
    """
    from deepspeed_tpu.pipe import (LayerSpec, PipelineModule,
                                    TiedLayerSpec)
    cfg = config or GPT2Config(**kw)
    if tied is None:
        tied = not compiled
    if compiled and tied:
        raise ValueError("compiled GPT-2 pipeline requires tied=False")
    # (Flash attention works in compiled pipelines: the engine's
    # shard_map worker runs blocks shard-locally and flash entry points
    # launch raw pallas kernels inside a shard_map region.)
    blocks = [LayerSpec(GPT2PipeBlock, cfg) for _ in range(cfg.n_layer)]
    if tied:
        layers = ([TiedLayerSpec("embed", GPT2PipeEmbed, cfg)] + blocks +
                  [LayerSpec(GPT2PipeLN, cfg),
                   TiedLayerSpec("embed", GPT2PipeEmbed, cfg,
                                 forward_fn=_gpt2_tied_head)])
    else:
        layers = ([LayerSpec(GPT2PipeEmbed, cfg)] + blocks +
                  [LayerSpec(GPT2PipeFinal, cfg)])
    return PipelineModule(layers=layers, num_stages=num_stages,
                          loss_fn=gpt2_lm_loss, seed_layers=True,
                          partition_method=partition_method,
                          compiled=compiled)
