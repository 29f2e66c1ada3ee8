"""Ring attention (sequence-parallel flash) tests on the 8-device mesh.

The reference has no sequence parallelism (SURVEY §0: v0.3.10's
long-context lever is block-sparse attention only) — parity here is
against the dense jnp attention on the full sequence, the same ground
truth the flash kernel tests use.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.transformer.kernels.attention import (
    flash_attention_with_lse, mha_reference)
from deepspeed_tpu.ops.transformer.ring_attention import (
    ring_flash_attention, sequence_parallel_attention)


def make_qkv(b=2, h=4, t=256, d=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, t, d), dtype) for k in ks)


def seq_mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("seq",))


def test_with_lse_matches_reference():
    q, k, v = make_qkv()
    o, lse = flash_attention_with_lse(q, k, v, causal=True,
                                      block_q=64, block_k=64)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # lse against a direct computation
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    cm = jnp.tril(jnp.ones((q.shape[2], k.shape[2]), dtype=bool))
    s = jnp.where(cm[None, None], s, -1e30)
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=1e-5, atol=1e-5)


def test_lse_cotangent():
    """Gradients flow through the lse output (the ring merge needs this)."""
    q, k, v = make_qkv(t=128)

    def loss_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True,
                                          block_q=64, block_k=64)
        return (o.sum() + 0.5 * lse.sum()).astype(jnp.float32)

    def loss_ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        cm = jnp.tril(jnp.ones((q.shape[2], k.shape[2]), dtype=bool))
        s = jnp.where(cm[None, None], s, -1e30)
        lse = jax.scipy.special.logsumexp(s, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse), v)
        return o.sum() + 0.5 * lse.sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(causal):
    q, k, v = make_qkv(t=256)
    mesh = seq_mesh()
    out = sequence_parallel_attention(mesh, q, k, v, axis_name="seq",
                                      causal=causal, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # outputs keep the sequence sharding
    assert out.sharding.spec == P(None, None, "seq", None)


def test_ring_gradients_match_dense():
    q, k, v = make_qkv(t=128, h=2)
    mesh = seq_mesh()

    def ring_loss(q, k, v):
        out = sequence_parallel_attention(mesh, q, k, v, axis_name="seq",
                                          causal=True, block_q=16,
                                          block_k=16)
        return out.astype(jnp.float32).sum()

    def dense_loss(q, k, v):
        return mha_reference(q, k, v, causal=True).astype(jnp.float32).sum()

    gr = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_with_padding_mask(causal):
    """The key padding mask rotates with k/v around the ring (the
    encoder/BERT-style attention convention)."""
    q, k, v = make_qkv(t=256)
    b, t = q.shape[0], q.shape[2]
    rng = np.random.RandomState(4)
    mask = jnp.where(jnp.asarray(rng.rand(b, t)) > 0.2, 0.0,
                     -1e9).astype(jnp.float32)
    mesh = seq_mesh()
    out = sequence_parallel_attention(mesh, q, k, v, axis_name="seq",
                                      causal=causal, mask=mask,
                                      block_q=32, block_k=32)
    ref = mha_reference(q, k, v, mask=mask, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_masked_gradients():
    q, k, v = make_qkv(t=128, h=2)
    b, t = q.shape[0], q.shape[2]
    mask = jnp.where(jnp.arange(t)[None, :] < t - 32, 0.0,
                     -1e9) * jnp.ones((b, 1))
    mask = mask.astype(jnp.float32)
    mesh = seq_mesh()

    def ring_loss(q, k, v):
        out = sequence_parallel_attention(mesh, q, k, v, axis_name="seq",
                                          mask=mask, block_q=16, block_k=16)
        return out.astype(jnp.float32).sum()

    def dense_loss(q, k, v):
        return mha_reference(q, k, v, mask=mask).astype(jnp.float32).sum()

    gr = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_ragged_blocks_dense_fallback(causal):
    """Shard lengths not divisible by the requested tiles route to the
    dense per-block path (fwd AND the custom backward) with identical
    semantics."""
    q, k, v = make_qkv(t=192, h=2)  # t_local = 24, blocks 16 -> ragged
    mesh = seq_mesh()

    def ring_loss(q, k, v):
        out = sequence_parallel_attention(mesh, q, k, v, axis_name="seq",
                                          causal=causal, block_q=16,
                                          block_k=16)
        return out.astype(jnp.float32).sum()

    out = sequence_parallel_attention(mesh, q, k, v, axis_name="seq",
                                      causal=causal, block_q=16,
                                      block_k=16)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    gr = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(lambda q, k, v: mha_reference(
        q, k, v, causal=causal).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_ring_ragged_blocks_with_mask():
    """Dense fallback + padding mask, fwd and bwd (the BERT-style path
    for shard lengths the tiles cannot take), including one fully-masked
    row — grads must stay finite (the exp(s - lse) clamp)."""
    q, k, v = make_qkv(t=192, h=2)
    b, t = q.shape[0], q.shape[2]
    rng = np.random.RandomState(9)
    mask = np.where(rng.rand(b, t) > 0.2, 0.0, -1e9).astype(np.float32)
    mask[0, :] = -1e9  # one sequence fully padded
    mask = jnp.asarray(mask)
    mesh = seq_mesh()

    def ring_loss(q, k, v):
        out = sequence_parallel_attention(mesh, q, k, v, axis_name="seq",
                                          mask=mask, block_q=16,
                                          block_k=16)
        return out.astype(jnp.float32).sum()

    out = sequence_parallel_attention(mesh, q, k, v, axis_name="seq",
                                      mask=mask, block_q=16, block_k=16)
    ref = mha_reference(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out[1:]), np.asarray(ref[1:]),
                               rtol=2e-5, atol=2e-5)
    gr = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for g in gr:
        assert np.isfinite(np.asarray(g)).all()
    gd = jax.grad(lambda q, k, v: mha_reference(
        q, k, v, mask=mask).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    # Valid sequences' grads match the dense reference.
    for a, b_ in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a)[1:], np.asarray(b_)[1:],
                                   rtol=5e-4, atol=5e-4)


def _shard_map_ulysses(mesh, q, k, v, mask=None, causal=False, **kw):
    from jax import shard_map

    from deepspeed_tpu.ops.transformer.ring_attention import (
        ulysses_attention)

    spec = P(None, None, "seq", None)
    if mask is None:
        fn = shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis_name="seq",
                                              causal=causal, **kw),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return fn(q, k, v)
    fn = shard_map(
        lambda q, k, v, m: ulysses_attention(q, k, v, axis_name="seq",
                                             causal=causal, mask=m, **kw),
        mesh=mesh, in_specs=(spec, spec, spec, P(None, "seq")),
        out_specs=spec, check_vma=False)
    return fn(q, k, v, mask)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    """All-to-all sequence parallelism: 8 shards x 8 heads, parity vs
    dense full-sequence attention."""
    q, k, v = make_qkv(t=256, h=8)
    mesh = seq_mesh()
    out = _shard_map_ulysses(mesh, q, k, v, causal=causal,
                             block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_masked_and_gradients():
    q, k, v = make_qkv(t=128, h=8)
    b, t = q.shape[0], q.shape[2]
    rng = np.random.RandomState(11)
    mask = jnp.asarray(np.where(rng.rand(b, t) > 0.2, 0.0,
                                -1e9).astype(np.float32))
    mesh = seq_mesh()

    def uly_out(q, k, v):
        return _shard_map_ulysses(mesh, q, k, v, mask=mask, block_q=16,
                                  block_k=16)

    def loss_and_out(q, k, v):
        out = uly_out(q, k, v)
        return out.astype(jnp.float32).sum(), out

    # One sharded execution serves both the output-parity check (aux)
    # and the gradients.
    (_, out), gr = jax.value_and_grad(
        loss_and_out, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    ref = mha_reference(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    gd = jax.grad(lambda q, k, v: mha_reference(
        q, k, v, mask=mask).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


def test_ulysses_rejects_indivisible_heads():
    q, k, v = make_qkv(t=256, h=4)  # 4 heads, 8 shards
    mesh = seq_mesh()
    with pytest.raises(ValueError, match="divisible"):
        _shard_map_ulysses(mesh, q, k, v)


def test_ring_inside_user_shard_map():
    """ring_flash_attention composes inside a caller's shard_map with a
    batch x seq mesh (dp on batch, ring on sequence)."""
    from jax import shard_map

    q, k, v = make_qkv(b=4, t=128, h=2)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "seq"))
    spec = P("data", None, "seq", None)

    fn = shard_map(
        lambda q, k, v: ring_flash_attention(q, k, v, axis_name="seq",
                                             causal=True, block_q=16,
                                             block_k=16),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = fn(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
