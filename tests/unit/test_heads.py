"""Direct parity tests for the 3-GEMM chunked tied-decoder XE
(models/heads.py) — the custom_vjp that replaces autodiff on the LM-head
loss. Model-tier tests cover it end-to-end; these pin the contract
against a naive dense reference at every seam: multi-chunk, padding,
ignore_index, bias, sum_count reduction, and both GEMM dtypes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.heads import chunked_tied_softmax_xent


def dense_reference(x, wte, labels, bias=None, ignore_index=None,
                    reduction="mean"):
    """Naive full-logits XE in fp64-ish fp32 — the semantic spec."""
    b, t, c = x.shape
    xf = x.reshape(b * t, c).astype(jnp.float32)
    lf = labels.reshape(b * t)
    logits = xf @ wte.astype(jnp.float32).T
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(lf, 0)[:, None],
                               axis=1)[:, 0]
    valid = jnp.ones_like(lf, jnp.float32)
    if ignore_index is not None:
        valid = (lf != ignore_index).astype(jnp.float32)
    total = jnp.sum((lse - gold) * valid)
    count = jnp.sum(valid)
    if reduction == "sum_count":
        return total, count
    return total / jnp.maximum(count, 1.0)


def make_inputs(n_tokens=96, c=32, v=128, seed=0, ignore_frac=0.0):
    """Random inputs; the stream's first two and last two labels are id 0
    and id v - 1 (the ends of the head's iota compare), wherever the chunks'
    boundaries fall."""
    rng = np.random.RandomState(seed)
    b, t = 4, n_tokens // 4
    x = jnp.asarray(rng.randn(b, t, c), jnp.float32) * 0.3
    wte = jnp.asarray(rng.randn(v, c), jnp.float32) * 0.3
    labels = rng.randint(0, v, size=(b, t))
    if ignore_frac:
        mask = rng.rand(b, t) < ignore_frac
        labels = np.where(mask, -1, labels)
    labels[0, :2] = 0, v - 1
    labels[-1, -2:] = 0, v - 1
    return x, wte, jnp.asarray(labels)


@pytest.mark.parametrize("impl", ["eager", "remat"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("chunk", [2048, 32, 40])  # single / multi / padded
@pytest.mark.parametrize("vocab", [128, 131])  # whole lane tiles / a ragged one
def test_loss_and_grads_match_dense(dtype, tol, chunk, impl, vocab):
    """Both head implementations (eager 3-GEMM custom_vjp, remat 4-GEMM
    autodiff) must match the dense spec in loss AND grads, in both the
    fp32 and bf16 regimes (the remat path's model-dtype dW accumulation
    differs most from the eager fp32 accumulator in bf16)."""
    x, wte, labels = make_inputs(v=vocab)

    def ours(x, w):
        return chunked_tied_softmax_xent(x, w, labels, dtype, chunk=chunk,
                                         impl=impl)

    def ref(x, w):
        return dense_reference(x, w, labels)

    (lo, go), (lr, gr) = [jax.value_and_grad(f, argnums=(0, 1))(x, wte)
                          for f in (ours, ref)]
    assert abs(float(lo) - float(lr)) < tol * max(1.0, abs(float(lr)))
    for a, b in zip(go, gr):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a.astype(jnp.float32) - b).max()) / scale < tol


def test_head_impl_env_and_validation(monkeypatch):
    """DS_TPU_XE_HEAD drives the default; explicit impl wins; junk
    rejected."""
    x, wte, labels = make_inputs(n_tokens=32)
    monkeypatch.setenv("DS_TPU_XE_HEAD", "remat")
    a = chunked_tied_softmax_xent(x, wte, labels, jnp.float32, chunk=32)
    b = chunked_tied_softmax_xent(x, wte, labels, jnp.float32, chunk=32,
                                  impl="eager")
    assert abs(float(a) - float(b)) < 1e-5
    with pytest.raises(ValueError):
        chunked_tied_softmax_xent(x, wte, labels, jnp.float32, impl="nope")


# name: (vocab, chunk, ignore_index, GEMM dtype). The eager head takes the
# label's -1 by a compare against EVERY row's label id, so the rows that are
# not supervised are the cases: an ignored row's id is clamped to 0 (or, with
# a non-negative ``ignore_index``, IS a column), a padded row's is 0 and its
# logits are the bias alone.
BIAS_CASES = {
    "ignored_rows": (128, 32, -1, jnp.float32),
    "padded_and_ignored_rows": (128, 40, -1, jnp.float32),
    "ignored_id_is_a_column": (128, 40, 5, jnp.float32),
    "ragged_vocab": (131, 40, -1, jnp.float32),
    "bf16": (128, 40, -1, jnp.bfloat16),
    "bf16_ragged_vocab_one_chunk": (131, 2048, 7, jnp.bfloat16),
}
BIAS_TOL = {jnp.float32: (1e-5, 2e-5), jnp.bfloat16: (4e-2, 4e-2)}  # loss, grads


@pytest.mark.parametrize("case", sorted(BIAS_CASES))
def test_ignore_index_and_bias_match_dense(case):
    """dx, dW and db of the eager head against plain autodiff of the dense
    loss, with the decoder bias and rows that carry no loss."""
    vocab, chunk, ignore, dtype = BIAS_CASES[case]
    loss_tol, tol = BIAS_TOL[dtype]
    x, wte, labels = make_inputs(v=vocab, ignore_frac=0.3)
    if ignore != -1:
        labels = jnp.where(labels == -1, ignore, labels)
    assert int(jnp.sum(labels == ignore)) > 10
    bias = jnp.asarray(np.random.RandomState(7).randn(vocab), jnp.float32)

    def ours(x, w, b_):
        return chunked_tied_softmax_xent(x, w, labels, dtype, chunk=chunk,
                                         bias=b_, ignore_index=ignore,
                                         impl="eager")

    def ref(x, w, b_):
        return dense_reference(x, w, labels, bias=b_, ignore_index=ignore)

    (lo, go) = jax.value_and_grad(ours, argnums=(0, 1, 2))(x, wte, bias)
    (lr, gr) = jax.value_and_grad(ref, argnums=(0, 1, 2))(x, wte, bias)
    assert abs(float(lo) - float(lr)) < loss_tol
    for a, b in zip(go, gr):
        assert float(jnp.abs(a.astype(jnp.float32) - b).max()) < tol


def test_all_ignored_is_finite_zero():
    x, wte, _ = make_inputs()
    labels = jnp.full((4, 24), -1)
    loss, grads = jax.value_and_grad(
        lambda x_: chunked_tied_softmax_xent(x_, wte, labels, jnp.float32,
                                             chunk=32, ignore_index=-1))(x)
    assert float(loss) == 0.0
    assert bool(jnp.all(jnp.isfinite(grads)))


def test_sum_count_reduction_matches_mean():
    x, wte, labels = make_inputs(ignore_frac=0.25)
    total, count = chunked_tied_softmax_xent(
        x, wte, labels, jnp.float32, chunk=32, ignore_index=-1,
        reduction="sum_count")
    mean = chunked_tied_softmax_xent(
        x, wte, labels, jnp.float32, chunk=32, ignore_index=-1)
    assert count == float(np.sum(np.asarray(labels) != -1))
    assert abs(float(total) / float(count) - float(mean)) < 1e-6


def test_eval_path_no_grad_matches():
    """Undifferentiated call takes the primal (loss-only) path."""
    x, wte, labels = make_inputs()
    lo = chunked_tied_softmax_xent(x, wte, labels, jnp.float32, chunk=32)
    lr = dense_reference(x, wte, labels)
    assert abs(float(lo) - float(lr)) < 1e-5
