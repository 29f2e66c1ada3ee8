"""CSR sparse-gradient tests (mirror reference tests/unit/test_csr.py plus
the sparse allgather collective on the 8-device mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.runtime.csr_tensor import (CSRTensor, csr_allreduce,
                                              pad_csr)


def test_csr_roundtrip():
    dense = jnp.zeros((10, 4)).at[2].set(1.0).at[7].set(-2.0)
    csr = CSRTensor(dense)
    assert csr.indices.shape[0] == 2
    np.testing.assert_array_equal(np.asarray(csr.to_dense()),
                                  np.asarray(dense))


def test_csr_sparse_size_and_add():
    dense = jnp.zeros((10, 4)).at[1].set(3.0)
    a = CSRTensor(dense)
    b = CSRTensor(dense)
    a.add(b)
    np.testing.assert_array_equal(np.asarray(a.to_dense()),
                                  np.asarray(dense) * 2)
    sparse, full = a.sparse_size()
    assert full == 40 and sparse == 2 + 2 * 4


def test_pad_csr():
    idx = jnp.asarray([3, 5])
    val = jnp.ones((2, 4))
    pi, pv = pad_csr(idx, val, 5)
    assert pi.shape == (5,) and pv.shape == (5, 4)
    assert int(pi[2]) == 0 and float(pv[2].sum()) == 0.0


def test_sparse_grad_exchange_matches_psum():
    """sparse_grad_exchange == dense pmean for row-sparse grads (8 devices)."""
    from deepspeed_tpu.runtime.csr_tensor import sparse_grad_exchange

    devices = jax.devices()
    if len(devices) < 8:
        import pytest
        pytest.skip("needs 8 virtual devices")
    mesh = Mesh(np.asarray(devices).reshape(8), ("data",))
    rng = np.random.RandomState(0)
    vocab, dim, k = 64, 8, 4
    grads = np.zeros((8, vocab, dim), np.float32)
    for d in range(8):
        rows = rng.choice(vocab, size=k, replace=False)
        grads[d, rows] = rng.randn(k, dim)

    def sparse_fn(g):
        return sparse_grad_exchange(g[0], "data", k, average=True)[None]

    def dense_fn(g):
        return jax.lax.pmean(g[0], "data")[None]

    kw = dict(mesh=mesh, in_specs=P("data"), out_specs=P("data"),
              check_vma=False)
    sparse = np.asarray(shard_map(sparse_fn, **kw)(jnp.asarray(grads)))
    dense = np.asarray(shard_map(dense_fn, **kw)(jnp.asarray(grads)))
    np.testing.assert_allclose(sparse, dense, rtol=1e-6, atol=1e-7)


def test_split_half_float_double_csr():
    """Dtype bucketing with CSR tensors separated (reference
    engine.py:54-66)."""
    from deepspeed_tpu.runtime.engine import split_half_float_double_csr

    csr = CSRTensor(jnp.zeros((4, 2)).at[1].set(1.0))
    tensors = [jnp.zeros((2,), jnp.bfloat16), jnp.zeros((2,), jnp.float32),
               csr, jnp.ones((3,), jnp.float32)]
    buckets = dict(split_half_float_double_csr(tensors))
    assert len(buckets["bfloat16"]) == 1
    assert len(buckets["float32"]) == 2
    assert buckets[CSRTensor.type()] == [csr]


def test_engine_sparse_embedding_grad_parity():
    """Engine-integrated sparse embedding-grad DP (reference
    engine.py:180-185,1186-1242): training with sparse_gradients=true must
    match dense-gradient training step for step on the 8-device mesh."""
    import flax.linen as nn
    import pytest

    import deepspeed_tpu as deepspeed

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")

    class EmbedModel(nn.Module):
        vocab: int = 64
        dim: int = 16

        @nn.compact
        def __call__(self, ids, y):
            h = nn.Embed(self.vocab, self.dim, name="embed")(ids)
            h = h.mean(axis=1)
            logits = nn.Dense(self.vocab)(h)
            logp = nn.log_softmax(logits)
            return -jnp.mean(
                jnp.take_along_axis(logp, y[..., None], axis=-1))

    def run(sparse):
        engine, _, _, _ = deepspeed.initialize(
            model=EmbedModel(),
            config_params={
                "train_batch_size": 8,
                "sparse_gradients": sparse,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            })
        losses = []
        for i in range(5):
            rng = np.random.RandomState(i % 2)
            ids = rng.randint(0, 64, size=(8, 4))
            y = rng.randint(0, 64, size=(8,))
            loss = engine(ids, y)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        return losses

    sparse_losses = run(True)
    dense_losses = run(False)
    np.testing.assert_allclose(sparse_losses, dense_losses,
                               rtol=1e-5, atol=1e-6)
    assert sparse_losses[-1] < sparse_losses[0]


def test_engine_sparse_grads_tied_softmax_falls_back_dense():
    """When the embedding doubles as the tied output head, softmax XE makes
    EVERY vocab row's grad nonzero — the k-row sparse exchange must detect
    the overflow at runtime and fall back to a dense reduction instead of
    silently dropping gradient."""
    import flax.linen as nn
    import pytest

    import deepspeed_tpu as deepspeed

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")

    class TiedLM(nn.Module):
        vocab: int = 32
        dim: int = 16

        @nn.compact
        def __call__(self, ids, y):
            emb = self.param("embedding", nn.initializers.normal(0.1),
                             (self.vocab, self.dim))
            h = emb[ids].mean(axis=1)
            logits = h @ emb.T  # tied softmax head: dense embedding grad
            logp = nn.log_softmax(logits)
            return -jnp.mean(
                jnp.take_along_axis(logp, y[..., None], axis=-1))

    def run(sparse):
        engine, _, _, _ = deepspeed.initialize(
            model=TiedLM(),
            config_params={
                "train_batch_size": 8,
                "sparse_gradients": sparse,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            })
        losses = []
        for i in range(4):
            rng = np.random.RandomState(i % 2)
            ids = rng.randint(0, 32, size=(8, 4))
            y = rng.randint(0, 32, size=(8,))
            loss = engine(ids, y)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5, atol=1e-6)


def test_csr_allreduce_matches_dense_mean(eight_devices):
    """Sparse index/value allgather == dense psum average."""
    w, rows, dim = 8, 16, 4
    rng = np.random.RandomState(0)
    dense = np.zeros((w, rows, dim), np.float32)
    for r in range(w):
        touched = rng.choice(rows, 3, replace=False)
        dense[r, touched] = rng.randn(3, dim)

    # per-worker CSR (padded to 3 rows each)
    idxs = np.zeros((w, 3), np.int32)
    vals = np.zeros((w, 3, dim), np.float32)
    for r in range(w):
        nz = np.nonzero(dense[r].any(-1))[0]
        i, v = pad_csr(jnp.asarray(nz, jnp.int32), jnp.asarray(dense[r, nz]), 3)
        idxs[r], vals[r] = np.asarray(i), np.asarray(v)

    mesh = Mesh(np.array(eight_devices), ("data",))

    def f(i, v):
        gi, gv = csr_allreduce(i[0], v[0], "data")
        return gi[None], gv[None]

    gi, gv = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P("data", None), P("data", None, None)),
        out_specs=(P("data", None), P("data", None, None))))(
            jnp.asarray(idxs), jnp.asarray(vals))

    merged = CSRTensor(indices=np.asarray(gi)[0],
                       values=jnp.asarray(np.asarray(gv)[0]),
                       dense_size=(rows, dim))
    np.testing.assert_allclose(np.asarray(merged.to_dense()),
                               dense.mean(0), rtol=1e-5, atol=1e-6)


def test_engine_csr_api():
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models.simple import SimpleModel
    engine, _, _, _ = deepspeed.initialize(
        model=SimpleModel(hidden_dim=8),
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "sparse_gradients": True,
        })
    assert engine.sparse_gradients_enabled()
    csr = CSRTensor(jnp.zeros((6, 2)).at[1].set(1.0))
    out = engine.csr_allreduce_no_retain([csr])
    assert len(out) == 1
    np.testing.assert_array_equal(np.asarray(out[0].to_dense()),
                                  np.asarray(csr.to_dense()))
