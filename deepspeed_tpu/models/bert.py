"""BERT family — encoder stack on the fused DeepSpeedTransformerLayer.

The reference ships no models in-tree but its headline benchmark is
BERT-large pretraining with the fused transformer kernel (BASELINE.md: 66
TFLOPS/GPU, docs/_posts/2020-05-19-bert-record.md:14), and its kernel tests
vendor a full BERT implementation (tests/unit/modeling.py:1578). This module
is the TPU framework's first-class equivalent: a flax BERT whose encoder
layers are the fused Pallas DeepSpeedTransformerLayer (opt-out to a plain
stack), with the MLM+NSP pretraining heads, sized per bert_base/bert_large.
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerConfig,
                                           DeepSpeedTransformerLayer)


@dataclasses.dataclass
class BertConfig:
    """HF-compatible config surface (duck-typed where the reference expects
    bert_config, e.g. module_inject/replace_module.py:6)."""
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12
    pad_token_id: int = 0
    dtype: Any = jnp.bfloat16
    pre_layer_norm: bool = False
    use_fused_layer: bool = True
    # Sequence (context) parallelism: mesh axis the token dim shards over
    # (the engine's "sequence_parallel" config runs the model inside
    # shard_map with this axis bound). Requires use_fused_layer=False —
    # the plain encoder path carries the ring attention. See
    # GPT2Config.sequence_parallel_axis for the mechanism.
    sequence_parallel_axis: Any = None
    # "ring" or "ulysses" (see GPT2Config.sequence_parallel_mode).
    sequence_parallel_mode: str = "ring"
    # A SparsityConfig (ops/sparse_attention/sparsity_config.py) routes the
    # plain encoder's attention through the block-sparse Pallas kernel —
    # the model-level form of the reference's
    # replace_model_self_attention_with_sparse_self_attention swap
    # (sparse_attention_utils.py:85-121). Requires use_fused_layer=False.
    sparse_attention_config: Any = None

    @classmethod
    def bert_base(cls, **kw):
        return cls(**kw)

    @classmethod
    def bert_large(cls, **kw):
        kw.setdefault("hidden_size", 1024)
        kw.setdefault("num_hidden_layers", 24)
        kw.setdefault("num_attention_heads", 16)
        kw.setdefault("intermediate_size", 4096)
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 1024)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("max_position_embeddings", 128)
        return cls(**kw)

    def num_params(self):
        h, inter = self.hidden_size, self.intermediate_size
        emb = (self.vocab_size + self.max_position_embeddings +
               self.type_vocab_size) * h + 2 * h
        per_layer = 4 * h * h + 2 * h * inter + 9 * h + inter
        pooler = h * h + h
        return emb + self.num_hidden_layers * per_layer + pooler

    def _ds_layer_config(self, training):
        return DeepSpeedTransformerConfig(
            batch_size=-1,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            heads=self.num_attention_heads,
            attn_dropout_ratio=self.attention_probs_dropout_prob,
            hidden_dropout_ratio=self.hidden_dropout_prob,
            num_hidden_layers=self.num_hidden_layers,
            initializer_range=self.initializer_range,
            layer_norm_eps=self.layer_norm_eps,
            pre_layer_norm=self.pre_layer_norm,
            training=training,
            dtype=self.dtype,
        )


def _sp_axis(cfg):
    """The sequence-parallel axis IF bound in the current trace (see
    parallel/mesh.py:active_sp_axis)."""
    from deepspeed_tpu.parallel.mesh import active_sp_axis
    return active_sp_axis(getattr(cfg, "sequence_parallel_axis", None))


class BertEmbeddings(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, position_ids=None,
                 deterministic=True):
        cfg = self.config
        b, t = input_ids.shape
        ini = nn.initializers.normal(cfg.initializer_range)
        wte = self.param("word_embeddings", ini,
                         (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        wpe = self.param("position_embeddings", ini,
                         (cfg.max_position_embeddings, cfg.hidden_size),
                         jnp.float32)
        wtt = self.param("token_type_embeddings", ini,
                         (cfg.type_vocab_size, cfg.hidden_size), jnp.float32)
        if position_ids is None:
            sp = _sp_axis(cfg)
            if sp is not None:
                # Token-sharded: this shard holds global positions
                # [idx*t, (idx+1)*t).
                n = jax.lax.axis_size(sp)
                assert n * t <= cfg.max_position_embeddings, (
                    "global sequence {} exceeds max_position_embeddings={}"
                    .format(n * t, cfg.max_position_embeddings))
                position_ids = (jax.lax.axis_index(sp) * t
                                + jnp.arange(t))[None, :]
            else:
                position_ids = jnp.arange(t)[None, :]
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = (wte[input_ids] + wpe[position_ids] + wtt[token_type_ids])
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="LayerNorm")(x)
        x = nn.Dropout(cfg.hidden_dropout_prob)(x, deterministic=deterministic)
        # The table rides along for weight tying in the MLM decoder.
        return x.astype(cfg.dtype), wte


class PlainBertLayer(nn.Module):
    """Stock post-LN BERT encoder layer (unfused XLA path) — the opt-out when
    use_fused_layer=False, and the module_inject swap target."""

    config: BertConfig

    @nn.compact
    def __call__(self, x, add_mask=None, deterministic=True):
        cfg = self.config
        b, t, h = x.shape
        nh, hd = cfg.num_attention_heads, h // cfg.num_attention_heads

        def heads(z):
            return z.reshape(b, t, nh, hd).transpose(0, 2, 1, 3)

        q = heads(nn.Dense(h, dtype=cfg.dtype, name="query")(x))
        k = heads(nn.Dense(h, dtype=cfg.dtype, name="key")(x))
        v = heads(nn.Dense(h, dtype=cfg.dtype, name="value")(x))
        sp = _sp_axis(cfg)
        if cfg.sparse_attention_config is not None:
            # Block-sparse Pallas attention (the reference's sparse-BERT
            # long-sequence path); probs never materialize, so the
            # attention dropout rides the context output.
            from deepspeed_tpu.ops.sparse_attention import (
                SparseSelfAttention)
            ctx = SparseSelfAttention(
                sparsity_config=cfg.sparse_attention_config,
                name="sparse_attn")(q, k, v, key_padding_mask=add_mask)
            ctx = nn.Dropout(cfg.attention_probs_dropout_prob)(
                ctx, deterministic=deterministic)
        elif sp is not None:
            # Token-sharded: attend globally via the k/v ring (local
            # key-padding mask rotates with its block) or Ulysses
            # all-to-all head swaps. Attention-prob dropout moves to the
            # context output (the ring/flash path never materializes
            # probs — same policy as GPT-2's flash).
            from deepspeed_tpu.ops.transformer.ring_attention import (
                get_sp_attention)
            sp_attn = get_sp_attention(cfg.sequence_parallel_mode)
            ctx = sp_attn(q, k, v, axis_name=sp, mask=add_mask)
            ctx = nn.Dropout(cfg.attention_probs_dropout_prob)(
                ctx, deterministic=deterministic)
        else:
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / \
                jnp.sqrt(hd).astype(cfg.dtype)
            if add_mask is not None:
                s = s + add_mask[:, None, None, :].astype(s.dtype)
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(cfg.dtype)
            p = nn.Dropout(cfg.attention_probs_dropout_prob)(
                p, deterministic=deterministic)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", p, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, h)
        a = nn.Dense(h, dtype=cfg.dtype, name="attn_out")(ctx)
        a = nn.Dropout(cfg.hidden_dropout_prob)(a, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="attn_LayerNorm")(
            (x + a).astype(jnp.float32)).astype(cfg.dtype)

        f = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                     name="intermediate")(x)
        f = nn.gelu(f, approximate=False)
        f = nn.Dense(h, dtype=cfg.dtype, name="output")(f)
        f = nn.Dropout(cfg.hidden_dropout_prob)(f, deterministic=deterministic)
        return nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="out_LayerNorm")(
            (x + f).astype(jnp.float32)).astype(cfg.dtype)


class BertModel(nn.Module):
    """Embeddings → fused encoder stack → pooled [CLS]."""

    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 deterministic=True):
        cfg = self.config
        x, wte = BertEmbeddings(cfg, name="embeddings")(
            input_ids, token_type_ids, deterministic=deterministic)

        add_mask = None
        if attention_mask is not None:
            # HF 1/0 mask → the additive convention the kernels use
            # (0 keep / large-negative drop, [B, T]).
            add_mask = (1.0 - attention_mask.astype(jnp.float32)) * -1e9

        sp = _sp_axis(cfg)
        if sp is not None and cfg.use_fused_layer:
            raise ValueError(
                "sequence_parallel BERT requires use_fused_layer=False "
                "(the plain encoder path carries the ring attention)")
        if cfg.sparse_attention_config is not None and cfg.use_fused_layer:
            raise ValueError(
                "sparse_attention_config requires use_fused_layer=False "
                "(the plain encoder path carries the block-sparse kernel)")
        if cfg.sparse_attention_config is not None and sp is not None:
            raise ValueError(
                "sparse attention x sequence parallelism is not supported "
                "(the block-sparse layout is over the full sequence)")

        layer_cfg = cfg._ds_layer_config(training=not deterministic)
        for i in range(cfg.num_hidden_layers):
            if cfg.use_fused_layer:
                x = DeepSpeedTransformerLayer(
                    config=layer_cfg, name="layer_{}".format(i))(
                        x, attention_mask=add_mask,
                        deterministic=deterministic)
            else:
                x = PlainBertLayer(cfg, name="layer_{}".format(i))(
                    x, add_mask, deterministic=deterministic)

        if sp is not None:
            # [CLS] (global token 0) lives on shard 0 only; every shard
            # needs the pooled vector (replicated) for the NSP head.
            cls = jnp.where(jax.lax.axis_index(sp) == 0,
                            x[:, 0].astype(jnp.float32), 0.0)
            cls = jax.lax.psum(cls, sp).astype(cfg.dtype)
        else:
            cls = x[:, 0]
        pooled = nn.tanh(nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                                  name="pooler")(cls))
        return x, pooled, wte


def _chunked_mlm_xent(h, wte, bias, labels, dtype, chunk=2048):
    """Masked-LM form of the shared chunked tied-decoder loss: -1 labels
    ignored (the BERT convention, reference tests/unit/modeling.py MLM
    loss), decoder bias added, mean over masked positions."""
    from deepspeed_tpu.models.heads import chunked_tied_softmax_xent
    return chunked_tied_softmax_xent(h, wte, labels, dtype, chunk=chunk,
                                     bias=bias, ignore_index=-1)


class BertForPreTraining(nn.Module):
    """MLM + NSP pretraining heads. Returns the summed loss when labels are
    given (DeepSpeed convention: model output IS the loss), else
    (prediction_logits, seq_relationship_logits)."""

    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 masked_lm_labels=None, next_sentence_label=None,
                 deterministic=True):
        cfg = self.config
        seq_out, pooled, wte = BertModel(cfg, name="bert")(
            input_ids, attention_mask, token_type_ids,
            deterministic=deterministic)

        # MLM head: transform + LN + decoder tied to word embeddings.
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     name="transform")(seq_out)
        h = nn.gelu(h, approximate=False)
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps,
                         name="transform_LayerNorm")(h.astype(jnp.float32))
        mlm_bias = self.param("mlm_bias", nn.initializers.zeros,
                              (cfg.vocab_size,), jnp.float32)

        seq_relationship = nn.Dense(2, dtype=jnp.float32,
                                    name="seq_relationship")(
                                        pooled.astype(jnp.float32))

        if masked_lm_labels is None and next_sentence_label is None:
            prediction_logits = h @ wte.T.astype(jnp.float32) + mlm_bias
            return prediction_logits, seq_relationship

        sp = _sp_axis(cfg)
        total = 0.0
        if masked_lm_labels is not None:
            # Chunked masked-LM loss: the [B, T, V] fp32 logits never
            # materialize (the GPT-2 head's chunking, gpt2.py:178, with
            # BERT's -1-ignore labels and decoder bias).
            if sp is not None:
                # Token-sharded: globally count-weighted mean (shards hold
                # different numbers of masked positions).
                from deepspeed_tpu.models.heads import (
                    chunked_tied_softmax_xent)
                mlm_sum, mlm_count = chunked_tied_softmax_xent(
                    h, wte, masked_lm_labels, cfg.dtype, bias=mlm_bias,
                    ignore_index=-1, reduction="sum_count")
                total = total + jax.lax.psum(mlm_sum, sp) / jnp.maximum(
                    jax.lax.psum(mlm_count, sp), 1.0)
            else:
                total = total + _chunked_mlm_xent(h, wte, mlm_bias,
                                                  masked_lm_labels,
                                                  cfg.dtype)
        if next_sentence_label is not None:
            logp = jax.nn.log_softmax(seq_relationship, axis=-1)
            nll = -jnp.take_along_axis(
                logp, next_sentence_label[..., None], axis=-1)[..., 0]
            nsp = jnp.mean(nll)
            if sp is not None:
                # Keep the value an explicit cross-shard reduction (every
                # shard computes the identical scalar through the
                # replicated pooled vector): psum(nsp / n) == nsp. Under
                # shard_map's collective-aware autodiff the gradient is
                # the same with or without this — the engine pmean's
                # grads over 'seq' — but the psum makes the replication
                # visible to vma checks and readers.
                n = jax.lax.axis_size(sp)
                nsp = jax.lax.psum(nsp / n, sp)
            total = total + nsp
        return total
