"""Parity tests for the Pallas kernel tier vs pure-jnp references — the TPU
equivalent of reference tests/unit/test_cuda_forward.py /
test_cuda_backward.py (fused CUDA layer vs vendored BertLayer across
batch/seq/hidden/heads grids, fwd and bwd)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.kernels import attention
from deepspeed_tpu.ops.transformer.kernels.attention import (
    flash_attention, flash_attention_with_lse, mha_reference)
from deepspeed_tpu.ops.transformer.kernels.dropout import (
    dropout, fused_bias_dropout_residual)
from deepspeed_tpu.ops.transformer.kernels.gelu import (
    bias_gelu_reference, fused_bias_gelu)
from deepspeed_tpu.ops.transformer.kernels.layer_norm import (
    fused_bias_residual_layer_norm, fused_layer_norm, layer_norm_reference)
from deepspeed_tpu.ops.transformer.kernels.softmax import (
    attn_softmax, attn_softmax_reference)

RTOL, ATOL = 1e-5, 1e-5


def rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape), jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_mask", [False, True])
@pytest.mark.parametrize("b,h,t,d", [(1, 2, 64, 32), (2, 3, 128, 16)])
def test_flash_attention_forward(b, h, t, d, use_mask, causal):
    rng = np.random.RandomState(7)
    q, k, v = rand(rng, b, h, t, d), rand(rng, b, h, t, d), rand(rng, b, h, t, d)
    mask = None
    if use_mask:
        mask = jnp.where(jnp.asarray(rng.rand(b, t)) > 0.25, 0.0, -1e9)
        mask = mask.astype(jnp.float32)
    o = flash_attention(q, k, v, mask=mask, causal=causal,
                        block_q=32, block_k=32)
    ref = mha_reference(q, k, v, mask=mask, causal=causal)
    np.testing.assert_allclose(o, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_backward(causal):
    rng = np.random.RandomState(3)
    b, h, t, d = 2, 2, 64, 32
    q, k, v = rand(rng, b, h, t, d), rand(rng, b, h, t, d), rand(rng, b, h, t, d)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=32, block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(a, b_, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("mode", ["fused", "split"])
@pytest.mark.parametrize("causal,use_mask", [(False, False), (True, False),
                                             (True, True)])
def test_flash_attention_backward_modes_agree(monkeypatch, mode, causal,
                                              use_mask):
    """The fused one-pass backward and the split dq/dkv kernels must both
    match the dense oracle — DS_TPU_FLASH_BWD selects the path (the auto
    heuristic picks fused whenever k/v + accumulators fit VMEM)."""
    monkeypatch.setenv("DS_TPU_FLASH_BWD", mode)
    rng = np.random.RandomState(11)
    b, h, t, d = 2, 2, 96, 32
    q, k, v = rand(rng, b, h, t, d), rand(rng, b, h, t, d), rand(rng, b, h, t, d)
    mask = None
    if use_mask:
        mask = jnp.where(jnp.asarray(rng.rand(b, t)) > 0.25, 0.0,
                         -1e9).astype(jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask=mask, causal=causal,
                                       block_q=32, block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, mask=mask,
                                     causal=causal) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(a, b_, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("mode", ["fused", "split"])
@pytest.mark.parametrize("t_q,t_kv,blk", [(16, 32, 16), (32, 16, 16),
                                          (16, 64, 16)])
def test_flash_attention_backward_cross_lengths(monkeypatch, t_q, t_kv, blk,
                                                mode):
    """Causal grads with t_q != t_kv — regression for the single-q-block
    dkv path, where kv blocks entirely past the query extent must receive
    zero gradient (they got unmasked garbage before the fix). Parametrized
    over both backward paths: auto would route these tiny shapes to the
    fused kernel and leave the split kernels' cross-length handling
    untested."""
    monkeypatch.setenv("DS_TPU_FLASH_BWD", mode)
    rng = np.random.RandomState(5)
    b, h, d = 2, 2, 16
    q = rand(rng, b, h, t_q, d)
    k, v = rand(rng, b, h, t_kv, d), rand(rng, b, h, t_kv, d)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=blk, block_k=blk) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(a, b_, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("multi_block", [False, True])
def test_flash_attention_bf16_lowp_path(causal, multi_block):
    """bf16 models take the low-precision kernel branch (model-dtype exp,
    MXU-fused row-sum and delta subtraction) — parity vs the fp32 dense
    reference at bf16-appropriate tolerances, fwd and bwd."""
    rng = np.random.RandomState(11)
    b, h, t, d = 2, 2, 128, 32
    blk = 64 if multi_block else 128
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)

    o = flash_attention(q, k, v, causal=causal, block_q=blk, block_k=blk)
    ref = mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=causal)
    np.testing.assert_allclose(np.asarray(o, np.float32), ref,
                               rtol=5e-2, atol=2e-2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=blk, block_k=blk)
                       .astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32))
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32), b_,
                                   rtol=1e-1, atol=5e-2)


def test_flash_attention_fp16_loss_scaled_grads_finite():
    """Under dynamic loss scaling, delta = rowsum(dO * O) can exceed fp16
    max even when every dO element fits in fp16 — the kernel must keep the
    delta subtraction in fp32 for fp16 models (a fused fp16 delta column
    would go inf and NaN the MXU accumulation)."""
    rng = np.random.RandomState(2)
    b, h, t, d = 1, 1, 64, 64
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float16)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float16)
    v = jnp.asarray(50.0 + rng.rand(b, h, t, d), jnp.float16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        # Scaled loss: dO ~ 50 elementwise; delta ~ 50*50*64 >> 65504.
        return jnp.sum(o.astype(jnp.float32) * 50.0)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a in g:
        assert np.isfinite(np.asarray(a, np.float32)).all()


# dtype -> (forward rtol, atol, gradient rtol, atol) against the float32
# dense reference.
_WALK_TOL = {jnp.float32: (1e-4, 1e-4, 1e-3, 2e-4),
             jnp.bfloat16: (5e-2, 2e-2, 1e-1, 5e-2),
             jnp.float16: (1e-2, 5e-3, 5e-2, 2e-2)}


@pytest.mark.parametrize("t_q,t_kv,blk,side,dtype,use_mask,with_lse", [
    (512, 512, None, None, jnp.float32, False, False),  # 10 tiles of 16
    (384, 384, None, None, jnp.float32, True, False),   # a mask skips none
    (512, 512, None, 256, jnp.bfloat16, False, False),  # another side
    (256, 256, None, None, jnp.float16, False, False),  # unfused dp - delta
    (512, 512, 256, None, jnp.float32, False, False),   # 2 x 2 grid blocks
    (256, 512, 256, None, jnp.float32, False, False),   # keys past the rows
    (512, 256, 256, None, jnp.float32, False, False),   # rows past the keys
    (256, 256, None, None, jnp.float32, False, True),   # a nonzero dlse
])
def test_flash_attention_takes_a_diagonal_block_in_strips(
        monkeypatch, t_q, t_kv, blk, side, dtype, use_mask, with_lse):
    """Several sub-tiles a grid block (the block is the whole sequence, as
    in the training cells, or a square part of it): forward and gradients
    against the dense reference. The tiles above the diagonal are never
    formed, so what they would have held must be exactly nothing: keys past
    the last query row get zero gradient, rows past the last key see every
    key."""
    if side:
        monkeypatch.setattr(attention, "_SUBTILE_SIDE", side)
    rng = np.random.RandomState(17)
    b, h, d = 1, 2, 16
    q = jnp.asarray(rng.randn(b, h, t_q, d), dtype)
    k = jnp.asarray(rng.randn(b, h, t_kv, d), dtype)
    v = jnp.asarray(rng.randn(b, h, t_kv, d), dtype)
    mask = None
    if use_mask:
        mask = jnp.where(jnp.asarray(rng.rand(b, t_kv)) > 0.25, 0.0,
                         -1e9).astype(jnp.float32)
    w = jnp.asarray(rng.randn(b, h, t_q, 1), jnp.float32)

    def flash(q, k, v):
        return flash_attention_with_lse(q, k, v, mask=mask, causal=True,
                                        block_q=blk, block_k=blk)

    def dense(q, k, v):
        return mha_reference(q, k, v, mask=mask, causal=True,
                             return_lse=True)

    def outputs_and_grads(fn, *qkv):
        """(o, lse) and the loss's gradients from ONE program a side."""
        def loss(q, k, v):
            o, lse = fn(q, k, v)
            out = jnp.sum(o.astype(jnp.float32) ** 2)
            return (out + jnp.sum(lse * w) if with_lse else out), (o, lse)
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(*qkv)
        return out, grads

    rtol, atol, g_rtol, g_atol = _WALK_TOL[dtype]
    (o, lse), g = outputs_and_grads(flash, q, k, v)
    (o_ref, lse_ref), gr = outputs_and_grads(
        dense, *(x.astype(jnp.float32) for x in (q, k, v)))
    walk = attention.last_walk()
    assert walk["subtile"] == (side or attention._SUBTILE_SIDE)
    n = t_q // walk["subtile"], t_kv // walk["subtile"]
    assert walk["tiles_visited_share"] == \
        sum(min(r + 1, n[1]) for r in range(n[0])) / (n[0] * n[1])
    np.testing.assert_allclose(np.asarray(o, np.float32), o_ref,
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(lse, lse_ref, rtol=rtol, atol=atol)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32), b_,
                                   rtol=g_rtol, atol=g_atol)
    if t_kv > t_q:
        assert not np.asarray(g[1][:, :, t_q:], np.float32).any()
        assert not np.asarray(g[2][:, :, t_q:], np.float32).any()


def _packed_cases():
    """(heads, d, t, dtype, causal, use_mask): GPT-2 355M's heads (two a
    lane tile), GPT-2 XL's (12.5 tiles: the last half dead), head dim 128
    (a head a tile); T 1024 takes the block in eight strips, T 256 causal
    in two, not causal whole."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    cases = []
    for heads, d in ((16, 64), (25, 64), (4, 128)):
        cases += [(heads, d, 256, f32, True, False),
                  (heads, d, 256, f32, False, True),
                  (heads, d, 256, f32, True, True),
                  (heads, d, 256, bf16, True, False),
                  (heads, d, 256, bf16, False, True),
                  (heads, d, 256, bf16, False, False),
                  (heads, d, 1024, bf16, True, False)]
    return cases + [(25, 64, 1024, f32, True, True)]


def _flat(x):
    """[B, H, T, d] as a projection emits it, [B, T, H x d]."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _fused_operand(q, k, v, plant=jnp.nan):
    """Head-major q, k, v as the ONE packed operand ``CausalSelfAttention``
    hands the kernels, ``plant`` written where a half-empty tile's dead
    lanes lie (nothing may be read from there)."""
    h, d = q.shape[1], q.shape[3]
    g = attention.lane_pack(d, h)
    x = attention.tile_qkv(
        jnp.concatenate([_flat(q), _flat(k), _flat(v)], axis=-1), h, d)
    lane = jnp.arange(x.shape[-1])
    dead = (lane // (3 * g * d) == -(-h // g) - 1) & \
        (lane % (g * d) >= (g - (-h % g)) * d)
    return jnp.where(dead, plant, x)


@pytest.mark.parametrize("heads,d,t,dtype,causal,use_mask", _packed_cases())
def test_flash_attention_packed_layout(heads, d, t, dtype, causal, use_mask):
    """The kernels on the projection's own ``[B, T, lanes]`` layout,
    ``lane_pack`` heads a 128-lane tile, q, k and v one fused tile-arranged
    operand: output, log-sum-exp and the three gradients (which come back
    through ``tile_qkv``'s own transpose: ONE ``dqkv`` from the kernel)
    against the dense reference on head-major copies of the same values,
    with NaN where GPT-2 XL's thirteenth tile is dead."""
    rng = np.random.RandomState(49)
    b = 1
    q, k, v = (jnp.asarray(rng.randn(b, heads, t, d), dtype)
               for _ in range(3))
    mask = None
    if use_mask:
        mask = jnp.where(jnp.asarray(rng.rand(b, t)) > 0.25, 0.0,
                         -1e9).astype(jnp.float32)
    w = jnp.asarray(rng.randn(b, heads, t, 1), jnp.float32)

    def packed(q, k, v):
        o, lse = flash_attention_with_lse(
            _fused_operand(q, k, v), mask=mask, causal=causal, heads=heads,
            head_dim=d)
        o = o[..., :heads * d].reshape(b, t, heads, d)
        return o.transpose(0, 2, 1, 3), lse[:, :heads]

    def dense(q, k, v):
        return mha_reference(q, k, v, mask=mask, causal=causal,
                             return_lse=True)

    def outputs_and_grads(fn, *qkv):
        def loss(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse * w), \
                (o, lse)
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(*qkv)
        return out, grads

    rtol, atol, g_rtol, g_atol = _WALK_TOL[dtype]
    (o, lse), g = outputs_and_grads(packed, q, k, v)
    walk = attention.last_walk()
    assert walk["lane_pack"] == 128 // d
    assert walk["subtile"] == (128 if causal else 0)
    (o_ref, lse_ref), gr = outputs_and_grads(
        dense, *(x.astype(jnp.float32) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(o, np.float32), o_ref,
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(lse, lse_ref, rtol=rtol, atol=atol)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32), b_,
                                   rtol=g_rtol, atol=g_atol)


@pytest.mark.parametrize("fused", [False, True])
def test_flash_attention_packed_is_head_major_to_the_bit_at_one_head_a_tile(
        fused):
    """At head dim 128 a tile holds ONE head, nothing is selected, and the
    packed launches run the head-major body on other block specs: the
    output and the gradients (the kernels' own delta among them) are the
    head-major entry's bit for bit, from three packed arrays and from the
    fused one."""
    rng = np.random.RandomState(7)
    b, h, t, d = 1, 3, 256, 128
    q, k, v = (jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
               for _ in range(3))

    def unflat(x):
        return x.reshape(b, t, h, d).transpose(0, 2, 1, 3)

    def head_major(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def packed(q, k, v):
        ops = (_fused_operand(q, k, v),) if fused else \
            (_flat(q), _flat(k), _flat(v))
        return unflat(flash_attention(*ops, causal=True, heads=h,
                                      head_dim=d))

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(jnp.ones_like(out))

    for a, b_ in zip(run(head_major), run(packed)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b_, np.float32))
    assert attention.last_walk()["lane_pack"] == 1


def test_flash_attention_packed_shards_whole_tiles_over_a_mesh():
    """Under ``kernels_on_mesh`` the packed entry splits the batch over
    'data' and its LANE dim over 'model', whole tiles a shard (the row
    statistics by heads beside them), and ``packed_heads`` sends a caller
    whose heads a 'model' axis would cut through a tile to the head-major
    entry: decided from the mesh at trace time."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    rng = np.random.RandomState(3)
    b, h, t, d = 2, 4, 256, 64
    qkv = attention.tile_qkv(rand(rng, b, t, 3 * h * d), h, d)

    def loss(x):
        return jnp.sum(flash_attention(x, heads=h, head_dim=d,
                                       causal=True) ** 2)

    def on_mesh(x):
        with attention.kernels_on_mesh(mesh):
            assert attention.packed_heads(h, d) == 2      # 2 tiles, 2 shards
            assert attention.packed_heads(2, d) == 0      # 1 tile, 2 shards
            assert attention.packed_heads(h, 80) == 0     # no such tile
            return loss(x)

    assert attention.packed_heads(2, d) == 2              # no mesh: raw
    want = jax.value_and_grad(loss)(qkv)
    step = jax.jit(jax.value_and_grad(on_mesh))
    got = step(qkv)
    assert "manual_computation" in step.lower(qkv).as_text()  # shard_map
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)


def test_flash_subtile_rule_for_the_training_cells():
    """The rule itself, from shapes alone: the side S for the two training
    cells' calls ([16, 16, 1024, 64] and [4, 25, 1024, 64]: one block of
    1024 x 1024 a head), the tiles it visits, n (n + 1) / 2 of n x n, and
    the fused backward's footprint with a STRIP's scores live, not the
    block's: the whole 1024 rows fit one grid step under the budget."""
    s = attention.flash_subtile(1024, 1024, True)
    assert s == attention._SUBTILE_SIDE == 128
    n = 1024 // s
    assert attention.tiles_visited(1024, 1024, s, s, True) == \
        (n * (n + 1) // 2, n * n)
    assert attention.tiles_visited(1024, 1024, 1024, 1024, False) == (1, 1)
    # Nothing to skip without a causal mask; a block no side divides, one
    # no larger than the smallest side, or one that is not square, is its
    # own tile.
    assert attention.flash_subtile(512, 512, False) == 0
    assert attention.flash_subtile(128, 128, True) == 0
    assert attention.flash_subtile(96, 96, True) == 0
    assert attention.flash_subtile(512, 1024, True) == 0
    assert attention.flash_subtile(256, 256, True) == 128
    assert attention.flash_subtile(192, 192, True) == 0
    # The fused backward: a strip's scores, not the block's 12 B x 1M.
    strip = attention._fused_bwd_vmem_bytes(1024, 64, jnp.bfloat16, 1024,
                                            1024, s, True)
    block = attention._fused_bwd_vmem_bytes(1024, 64, jnp.bfloat16, 1024,
                                            1024, 0, True)
    assert strip < attention._FUSED_BWD_VMEM_BUDGET < block
    assert attention._fit_fused_bwd_tiles(
        1024, 64, jnp.bfloat16, 1024, 1024, s, True) == (1024, 1024, s)
    # A block that is its own tile still halves to fit, the larger side
    # first (BERT-shaped calls at T 1024; T 512 fits whole).
    assert attention._fit_fused_bwd_tiles(
        1024, 64, jnp.bfloat16, 1024, 1024, 0, False) == (512, 1024, 0)
    assert attention._fit_fused_bwd_tiles(
        512, 64, jnp.bfloat16, 512, 512, 0, False) == (512, 512, 0)


def test_flash_attention_ragged_fallback():
    # Non-divisible seq lengths take the jnp path; result must still match.
    rng = np.random.RandomState(5)
    b, h, t, d = 1, 2, 100, 16
    q, k, v = rand(rng, b, h, t, d), rand(rng, b, h, t, d), rand(rng, b, h, t, d)
    o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(o, ref, rtol=1e-4, atol=1e-4)


def test_flash_attention_packed_ragged_fallback():
    """A packed operand whose length the block does not divide takes the
    jnp path too (on head-major copies), and answers in its own layout:
    the output's dead lanes zero, a row of statistics a stored head."""
    rng = np.random.RandomState(5)
    b, h, t, d = 1, 5, 96, 64
    q, k, v = (rand(rng, b, h, t, d) for _ in range(3))
    qkv = _fused_operand(q, k, v, plant=0.0)
    o, lse = flash_attention_with_lse(qkv, heads=h, head_dim=d, causal=True,
                                      block_q=64, block_k=64)
    ref, lse_ref = mha_reference(q, k, v, causal=True, return_lse=True)
    assert o.shape == (b, t, 3 * 128) and lse.shape == (b, 6, t, 1)
    np.testing.assert_allclose(o[..., :h * d], _flat(ref), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(lse[:, :h], lse_ref, rtol=1e-4, atol=1e-4)
    assert not np.asarray(o[..., h * d:]).any()
    grad = jax.grad(lambda x: jnp.sum(flash_attention(
        x, heads=h, head_dim=d, causal=True, block_q=64, block_k=64) ** 2))
    assert np.isfinite(np.asarray(grad(qkv))).all()


@pytest.mark.parametrize("shape", [(64, 256), (2, 32, 128)])
def test_fused_layer_norm(shape):
    rng = np.random.RandomState(11)
    x = rand(rng, *shape)
    gamma = rand(rng, shape[-1])
    beta = rand(rng, shape[-1])
    y = fused_layer_norm(x, gamma, beta)
    ref = layer_norm_reference(x, gamma, beta)
    np.testing.assert_allclose(y, ref, rtol=RTOL, atol=ATOL)


def test_fused_layer_norm_grad():
    rng = np.random.RandomState(13)
    x, gamma, beta = rand(rng, 32, 128), rand(rng, 128), rand(rng, 128)

    def f(x, g, b):
        return jnp.sum(fused_layer_norm(x, g, b) ** 2)

    def fr(x, g, b):
        return jnp.sum(layer_norm_reference(x, g, b) ** 2)

    grads = jax.grad(f, argnums=(0, 1, 2))(x, gamma, beta)
    grads_r = jax.grad(fr, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b_ in zip(grads, grads_r):
        np.testing.assert_allclose(a, b_, rtol=1e-3, atol=1e-4)


def test_fused_bias_residual_layer_norm():
    rng = np.random.RandomState(17)
    x, res = rand(rng, 4, 16, 128), rand(rng, 4, 16, 128)
    gamma, beta, bias = rand(rng, 128), rand(rng, 128), rand(rng, 128)
    y = fused_bias_residual_layer_norm(x, res, gamma, beta, bias=bias)
    ref = layer_norm_reference(x + bias + res, gamma, beta)
    np.testing.assert_allclose(y, ref, rtol=RTOL, atol=ATOL)


def test_fused_bias_gelu():
    rng = np.random.RandomState(19)
    x, bias = rand(rng, 16, 512), rand(rng, 512)
    np.testing.assert_allclose(fused_bias_gelu(x, bias),
                               bias_gelu_reference(x, bias),
                               rtol=RTOL, atol=ATOL)
    g = jax.grad(lambda x, b: jnp.sum(fused_bias_gelu(x, b) ** 2),
                 argnums=(0, 1))(x, bias)
    gr = jax.grad(lambda x, b: jnp.sum(bias_gelu_reference(x, b) ** 2),
                  argnums=(0, 1))(x, bias)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(a, b_, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_mask", [False, True])
def test_attn_softmax(use_mask, causal):
    rng = np.random.RandomState(23)
    b, h, t = 2, 3, 64
    s = rand(rng, b, h, t, t)
    mask = None
    if use_mask:
        mask = jnp.where(jnp.asarray(rng.rand(b, t)) > 0.25, 0.0, -1e9)
        mask = mask.astype(jnp.float32)
    p = attn_softmax(s, mask, 0.125, causal)
    ref = attn_softmax_reference(s, mask, 0.125, causal)
    np.testing.assert_allclose(p, ref, rtol=1e-4, atol=1e-5)
    # backward
    g = jax.grad(lambda s: jnp.sum(attn_softmax(s, mask, 0.125, causal) ** 2))(s)
    gr = jax.grad(lambda s: jnp.sum(
        attn_softmax_reference(s, mask, 0.125, causal) ** 2))(s)
    np.testing.assert_allclose(g, gr, rtol=1e-3, atol=1e-4)


def test_dropout_deterministic_replay():
    rng = np.random.RandomState(29)
    x = rand(rng, 64, 128)
    y1 = dropout(x, 0.5, seed=123)
    y2 = dropout(x, 0.5, seed=123)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    # Different seed -> different mask.
    y3 = dropout(x, 0.5, seed=124)
    assert not np.array_equal(np.asarray(y1), np.asarray(y3))
    # Mean preserved (inverted dropout).
    assert abs(float(jnp.mean(y1)) - float(jnp.mean(x))) < 0.05
    # Zeros exactly where dropped.
    zeros = np.asarray(y1) == 0
    assert 0.4 < zeros.mean() < 0.6


def test_dropout_backward_uses_same_mask():
    rng = np.random.RandomState(31)
    x = rand(rng, 32, 64)
    y, vjp = jax.vjp(lambda x: dropout(x, 0.5, seed=7), x)
    (dx,) = vjp(jnp.ones_like(y))
    # Gradient must be 2x where kept, 0 where dropped — the same mask.
    kept = np.asarray(y) != 0
    np.testing.assert_allclose(np.asarray(dx)[kept], 2.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dx)[~kept], 0.0)


def test_fused_bias_dropout_residual_eval():
    rng = np.random.RandomState(37)
    x, res = rand(rng, 8, 64), rand(rng, 8, 64)
    bias = rand(rng, 64)
    y = fused_bias_dropout_residual(x, bias, res, 0.1, 5, deterministic=True)
    np.testing.assert_allclose(y, x + bias + res, rtol=1e-6, atol=1e-6)
