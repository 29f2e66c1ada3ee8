"""BERT family tests: fused-encoder forward shapes, MLM+NSP pretraining loss
under the engine, attention-mask semantics, p2p/pt-compat/tensorboard
surfaces added alongside."""

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as deepspeed
from deepspeed_tpu.models.bert import (BertConfig, BertForPreTraining,
                                       BertModel)


def _ids(b=2, t=32, vocab=1024, seed=0):
    return np.random.RandomState(seed).randint(0, vocab, size=(b, t))


def test_bert_model_shapes():
    cfg = BertConfig.tiny()
    model = BertModel(cfg)
    ids = jnp.asarray(_ids())
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    seq, pooled, wte = jax.jit(model.apply)(params, ids)
    assert seq.shape == (2, 32, cfg.hidden_size)
    assert pooled.shape == (2, cfg.hidden_size)
    assert wte.shape == (cfg.vocab_size, cfg.hidden_size)


def test_bert_config_sizes():
    base = BertConfig.bert_base()
    large = BertConfig.bert_large()
    assert abs(base.num_params() - 110e6) / 110e6 < 0.05
    assert abs(large.num_params() - 335e6) / 335e6 < 0.05


def test_bert_attention_mask_zeroes_padding_influence():
    cfg = BertConfig.tiny(hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0)
    model = BertModel(cfg)
    ids = jnp.asarray(_ids())
    mask = jnp.asarray(np.concatenate(
        [np.ones((2, 24)), np.zeros((2, 8))], axis=1))
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids, mask)
    apply = jax.jit(model.apply)
    seq1, _, _ = apply(params, ids, mask)
    # changing the masked-out tokens must not change unmasked outputs
    ids2 = jnp.asarray(np.concatenate(
        [np.asarray(ids)[:, :24], _ids(2, 8, seed=9)[:, :8]], axis=1))
    seq2, _, _ = apply(params, ids2, mask)
    np.testing.assert_allclose(np.asarray(seq1[:, :24], np.float32),
                               np.asarray(seq2[:, :24], np.float32),
                               rtol=2e-2, atol=2e-2)


def test_chunked_mlm_loss_matches_dense():
    """The chunked masked-LM loss (logits never materialized) must equal
    the naive dense log_softmax loss, value AND gradient, including the
    -1-ignore convention and a chunk-padding tail."""
    from deepspeed_tpu.models.bert import _chunked_mlm_xent

    rng = np.random.RandomState(0)
    b, t, c, v = 2, 9, 8, 32  # t chosen so b*t is NOT a multiple of 128
    h = jnp.asarray(rng.randn(b, t, c).astype(np.float32))
    wte = jnp.asarray(rng.randn(v, c).astype(np.float32))
    bias = jnp.asarray(rng.randn(v).astype(np.float32))
    labels = rng.randint(0, v, size=(b, t))
    labels[rng.rand(b, t) > 0.4] = -1  # most positions unmasked
    labels = jnp.asarray(labels)

    def dense(h, wte, bias):
        logits = h.astype(jnp.float32) @ wte.T + bias
        valid = (labels >= 0).astype(jnp.float32)
        li = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, li[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)

    def chunked(h, wte, bias):
        return _chunked_mlm_xent(h, wte, bias, labels, jnp.float32, chunk=4)

    np.testing.assert_allclose(float(chunked(h, wte, bias)),
                               float(dense(h, wte, bias)), rtol=1e-5)
    g_c = jax.grad(chunked, argnums=(0, 1, 2))(h, wte, bias)
    g_d = jax.grad(dense, argnums=(0, 1, 2))(h, wte, bias)
    for a, b_ in zip(g_c, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-6)


def test_bert_pretraining_trains_under_engine():
    cfg = BertConfig.tiny(hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0)
    engine, _, _, _ = deepspeed.initialize(
        model=BertForPreTraining(cfg),
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        })
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(8, 32))
    mlm_labels = np.full((8, 32), -1)
    mlm_labels[:, ::5] = rng.randint(0, cfg.vocab_size, size=(8, 7))
    nsp = rng.randint(0, 2, size=(8,))
    losses = []
    for _ in range(6):
        loss = engine(ids, None, None, jnp.asarray(mlm_labels),
                      jnp.asarray(nsp))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_bert_sparse_attention_mask_zeroes_padding_influence():
    """The additive key-padding mask must survive the hand-off into the
    block-sparse kernel: varying the CONTENT of padded positions cannot
    change the encoder output at kept positions."""
    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
    sc = FixedSparsityConfig(num_heads=4, block=16,
                             attention="bidirectional")
    cfg = BertConfig.tiny(use_fused_layer=False, hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0,
                          sparse_attention_config=sc)
    model = BertModel(cfg)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(2, 32))
    mask = np.ones((2, 32), np.int32)
    mask[:, 24:] = 0  # last 8 positions are padding
    params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.asarray(ids),
                        jnp.asarray(mask))
    apply = jax.jit(model.apply)
    seq1, _, _ = apply(params, jnp.asarray(ids), jnp.asarray(mask))
    ids2 = ids.copy()
    ids2[:, 24:] = rng.randint(0, cfg.vocab_size, size=(2, 8))
    seq2, _, _ = apply(params, jnp.asarray(ids2), jnp.asarray(mask))
    np.testing.assert_allclose(
        np.asarray(seq1[:, :24], np.float32),
        np.asarray(seq2[:, :24], np.float32), atol=1e-5,
        err_msg="padded-token content leaked through the sparse kernel")


def test_bert_sparse_attention_model_path():
    """BertConfig.sparse_attention_config routes the plain encoder through
    the block-sparse kernel (model-level form of the reference's
    sparse-attention swap); the model still trains."""
    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
    sc = FixedSparsityConfig(num_heads=4, block=16,
                             attention="bidirectional")
    cfg = BertConfig.tiny(use_fused_layer=False, hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0,
                          sparse_attention_config=sc)
    engine, _, _, _ = deepspeed.initialize(
        model=BertForPreTraining(cfg),
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        })
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(8, 32))
    mlm_labels = np.full((8, 32), -1)
    mlm_labels[:, ::5] = rng.randint(0, cfg.vocab_size, size=(8, 7))
    nsp = rng.randint(0, 2, size=(8,))
    losses = []
    for _ in range(6):
        loss = engine(ids, np.ones_like(ids), None,
                      jnp.asarray(mlm_labels), jnp.asarray(nsp))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_bert_sparse_requires_plain_layer():
    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
    import pytest
    cfg = BertConfig.tiny(sparse_attention_config=FixedSparsityConfig(
        num_heads=4, block=16, attention="bidirectional"))
    model = BertModel(cfg)
    with pytest.raises(ValueError, match="use_fused_layer"):
        model.init(jax.random.PRNGKey(0), jnp.asarray(_ids()))


def test_pt_backwards_compat_aliases():
    import importlib
    mod = importlib.import_module("deepspeed_tpu.pt.deepspeed_utils")
    assert hasattr(mod, "partition_balanced")
    cfgmod = importlib.import_module("deepspeed_tpu.pt.deepspeed_config")
    assert hasattr(cfgmod, "DeepSpeedConfig")
    ls = importlib.import_module("deepspeed_tpu.pt.loss_scaler")
    assert hasattr(ls, "DynamicLossScaler")


def test_pipe_p2p_roundtrip():
    from deepspeed_tpu.runtime.pipe import p2p

    class Grid:
        pipe_parallel_size = 2
        stage_id = 0

        def get_stage_id(self):
            return self.stage_id

    grid = Grid()
    p2p.init_process_groups(grid)
    x = jnp.arange(8.0)
    p2p.send(x, dest_stage=1)
    grid.stage_id = 1
    out = p2p.recv(jnp.zeros(8), src_stage=0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    p2p.barrier(0)


def test_tensorboard_events(tmp_path, monkeypatch):
    """The engine's scalars reach an event file through
    ``TensorBoardScalarWriter``. The writer it asks for by name,
    ``torch.utils.tensorboard.SummaryWriter``, takes 20 s to import here
    (``torch``, and TensorFlow's stubs behind ``tensorboard``): the case hands
    it ``tensorboardX``'s, the same surface writing the same files, under
    that name, and falls back on the real one where that is missing."""
    import importlib.util
    import sys
    import types

    from deepspeed_tpu.models.simple import SimpleModel
    if importlib.util.find_spec("tensorboardX") is not None:
        import tensorboardX
        monkeypatch.setitem(
            sys.modules, "torch.utils.tensorboard", types.SimpleNamespace(
                SummaryWriter=tensorboardX.SummaryWriter))
    engine, _, _, _ = deepspeed.initialize(
        model=SimpleModel(hidden_dim=8),
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "tensorboard": {"enabled": True,
                            "output_path": str(tmp_path),
                            "job_name": "job"},
        })
    rng = np.random.RandomState(0)
    x = rng.randn(8, 8).astype(np.float32)
    y = rng.randint(0, 8, size=(8,))
    for _ in range(2):
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    event_files = list((tmp_path / "job").glob("events.out.tfevents.*"))
    assert event_files, "no tensorboard event files written"


def test_plain_bert_layer_path():
    cfg = BertConfig.tiny(use_fused_layer=False, hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0)
    model = BertModel(cfg)
    ids = jnp.asarray(_ids())
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    seq, pooled, _ = jax.jit(model.apply)(params, ids)
    assert seq.shape == (2, 32, cfg.hidden_size)
    assert np.all(np.isfinite(np.asarray(seq, np.float32)))


def test_engine_enables_dropout_in_training():
    """The engine passes deterministic=False when training, so dropout is
    live (two forwards with different RNG steps differ)."""
    cfg = BertConfig.tiny(hidden_dropout_prob=0.5)
    engine, _, _, _ = deepspeed.initialize(
        model=BertForPreTraining(cfg),
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        })
    ids = _ids(8, 16)
    mlm = np.full((8, 16), -1)
    mlm[:, ::4] = 1
    l1 = float(engine(ids, None, None, jnp.asarray(mlm)))
    l2 = float(engine(ids, None, None, jnp.asarray(mlm)))
    assert l1 != l2, "dropout inactive: identical losses across RNG draws"
    engine.eval()
    l3 = float(engine(ids, None, None, jnp.asarray(mlm)))
    l4 = float(engine(ids, None, None, jnp.asarray(mlm)))
    assert l3 == l4, "eval mode should be deterministic"
