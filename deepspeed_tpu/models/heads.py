"""Chunked tied-decoder cross-entropy — the shared LM-head loss.

One helper serves both heads that would otherwise materialize [tokens, V]
fp32 logits: GPT-2's causal LM head (every token supervised) and BERT's
masked-LM head (-1-ignore labels, decoder bias). Logits are computed in
`chunk`-token slices so at most chunk*V live at once — the memory trick
that lets batch 8 x 1024 GPT-2 train without remat (reference analogue:
the fused transformer's gelu/attn checkpoint modes trade memory the same
way, csrc/transformer/ds_transformer_cuda.cpp normalize_invertible family).

GEMM accounting (the head dominates small-model step time). A remat'd
chunked head pays 4 logit-sized GEMMs per chunk — forward, recompute,
dx, dW — a 4/3 overhead over the ideal 3. This implementation pays
exactly 3: because the loss is a SCALAR, the full gradient is known up to
a scalar factor at forward time, so the chunk loop computes dx and dW
eagerly alongside the loss (dW accumulated in fp32 across chunks — tighter
than autodiff's model-dtype accumulation) and the custom_vjp backward is
just a scalar-rescale replay of the stored gradients. Undifferentiated
callers (eval) take the primal path and pay 1 GEMM, nothing eager.

Passes over a logits-sized array (a chunk of 2048 tokens x 50257 ids is
412 MB in fp32, and the three GEMMs are 3.2 ms of a v5e's MXU). The eager
loop WRITES one such array a chunk, the fp32 logits, and reads it three
times: the log-sum-exp, and the prologues of the dW and dx GEMMs, where the
compiler forms ``(softmax - onehot(label)) * valid`` on the fly (the
label's -1 is an iota compare, the result is cast to the GEMM dtype in
registers): 4.1 ms a chunk at C = 1024 on the chip. Taking the -1 by a
scatter-add instead (``dl.at[rows, label].add(-valid)``, cheaper on a CPU)
made the chip write an fp32 dl, relay it flat for the scatter, cast it and
relay it back: five more arrays of that size written a chunk, 8.2 ms.
"""

import functools
import os

import jax
import jax.numpy as jnp


def _chunk_loss(logits, li_, vi):
    """Per-chunk loss pieces: (summed loss, lse[chunk])."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, li_[:, None], axis=1)[:, 0]
    return jnp.sum((lse - gold) * vi), lse


def _logits(xi, w, bias_f, dtype):
    out = jax.lax.dot_general(
        xi.astype(dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [chunk, V] fp32
    if bias_f is not None:
        out = out + bias_f
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunked_xe_total(dtype, xc, w, lc, vc, bias_f):
    """Summed supervised-token XE over chunks; loss-only (eval) path."""
    def one(args):
        xi, li_, vi = args
        loss, _ = _chunk_loss(_logits(xi, w, bias_f, dtype), li_, vi)
        return loss

    return jnp.sum(jax.lax.map(one, (xc, lc, vc)))


def _chunked_xe_total_fwd(dtype, xc, w, lc, vc, bias_f):
    def step(dw_acc, args):
        xi, li_, vi = args
        logits = _logits(xi, w, bias_f, dtype)
        loss, lse = _chunk_loss(logits, li_, vi)
        # dlogits of the summed loss: (softmax - onehot(label)) on
        # supervised rows, 0 elsewhere, as ONE elementwise expression of
        # the logits, the label's -1 an iota compare and not a scatter-add
        # (module docstring): the compiler fuses it into the prologue of
        # both GEMMs below and, with a bias, into db's reduce.
        p = jnp.exp(logits - lse[:, None])
        hit = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) == li_[:, None]
        dl = jnp.where(hit, p - 1.0, p) * vi[:, None]
        dl_cast = dl.astype(dtype)
        dx = jax.lax.dot_general(dl_cast, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dw_acc = dw_acc + jax.lax.dot_general(
            dl_cast, xi.astype(dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [V, C] fp32
        db = jnp.sum(dl, axis=0) if bias_f is not None else 0.0
        return dw_acc, (loss, dx.astype(xc.dtype), db)

    dw, (losses, dx, db) = jax.lax.scan(
        step, jnp.zeros(w.shape, jnp.float32), (xc, lc, vc))
    total = jnp.sum(losses)
    res = (dx, dw, jnp.sum(db, axis=0) if bias_f is not None else None)
    return total, res


@jax.named_scope("lm_head")
def _chunked_xe_total_bwd(dtype, res, g):
    # w entered as model-dtype (the nondiff arg) and bias_f as fp32, so
    # the cotangent dtypes are static; lc (int) and vc (mask) get zeros.
    dx, dw, db = res
    d_xc = (g * dx.astype(jnp.float32)).astype(dx.dtype)
    d_w = (g * dw).astype(dtype)
    d_b = None if db is None else g * db
    return (d_xc, d_w, None, None, d_b)


_chunked_xe_total.defvjp(_chunked_xe_total_fwd, _chunked_xe_total_bwd)


def _chunked_xe_total_remat(dtype, xc, w, lc, vc, bias_f):
    """Remat'd 4-GEMM alternative: plain autodiff through checkpointed
    chunks (forward logits + recomputed logits + dx + dW per chunk). One
    more logit-sized GEMM than the eager path, but no fp32 [V, C] dW
    accumulator carried through the forward scan — selectable via
    DS_TPU_XE_HEAD=remat so the trade can be measured on hardware."""
    @jax.checkpoint
    def one(xi, li_, vi):
        loss, _ = _chunk_loss(_logits(xi, w, bias_f, dtype), li_, vi)
        return loss

    def body(tot, args):
        xi, li_, vi = args
        return tot + one(xi, li_, vi), None

    tot, _ = jax.lax.scan(body, jnp.float32(0.0), (xc, lc, vc))
    return tot


def _xe_head_impl(impl):
    """Resolve the head implementation: the explicit ``impl`` argument
    wins; otherwise DS_TPU_XE_HEAD, defaulting to 'eager'. The env is
    read at trace time — a function jitted before the env changes keeps
    its traced path (pass ``impl=`` explicitly when A/B-ing under jit)."""
    impl = impl or os.environ.get("DS_TPU_XE_HEAD", "eager")
    if impl not in ("eager", "remat"):
        raise ValueError("unknown XE head impl {!r} (eager|remat)".format(
            impl))
    return impl


@jax.named_scope("lm_head")
def chunked_tied_softmax_xent(x, wte, labels, dtype, chunk=2048, bias=None,
                              ignore_index=None, reduction="mean",
                              impl=None):
    """Token cross-entropy against a tied [V, C] embedding decoder. In a
    trace, head and loss, forward and backward, are the region ``lm_head``.

    Args:
      x: [B, T, C] final hidden states.
      wte: [V, C] tied embedding table.
      labels: [B, T] int targets; positions equal to ``ignore_index`` (when
        given) are excluded from both numerator and denominator.
      dtype: GEMM input dtype (fp32 accumulation regardless).
      chunk: tokens per slice; clamped to the padded token count.
      bias: optional [V] decoder bias (BERT's mlm_bias).
      reduction: "mean" returns the scalar mean over supervised tokens;
        "sum_count" returns (sum, count) so a sequence-parallel caller can
        psum both before dividing (a local mean would weight shards with
        different supervised-token counts incorrectly).
      impl: "eager" (3-GEMM custom_vjp, default) or "remat" (4-GEMM
        autodiff); None defers to DS_TPU_XE_HEAD.
    Returns: scalar mean loss, or (loss_sum, token_count) fp32 scalars.
    """
    b, t, c = x.shape
    n = b * t
    xf = x.reshape(n, c)
    lf = labels.reshape(n)
    # Small batches: shrink the chunk (rounded to the 128-lane register
    # width) so padding never multiplies the head-GEMM work.
    chunk = min(chunk, max(128, -(-n // 128) * 128))
    pad = (-n) % chunk
    if pad:
        # jnp.pad, NOT concatenate-with-zeros: GSPMD on the CPU backend
        # miscompiles concat when the rows arrive from a reshape of a
        # sequence-sharded [B, T, C] (values scrambled, loss goes NaN —
        # the sp + train_batch path). Pad lowers to a correct program.
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        lf = jnp.pad(lf, ((0, pad),))
    valid = (jnp.arange(n + pad) < n)
    if ignore_index is not None:
        valid = valid & (lf != ignore_index)
    valid = valid.astype(jnp.float32)
    li = jnp.maximum(lf, 0)
    n_chunks = (n + pad) // chunk
    xc = xf.reshape(n_chunks, chunk, c)
    lc = li.reshape(n_chunks, chunk)
    vc = valid.reshape(n_chunks, chunk)
    w = wte.astype(dtype)
    bias_f = bias.astype(jnp.float32) if bias is not None else None

    if _xe_head_impl(impl) == "remat":
        total = _chunked_xe_total_remat(jnp.dtype(dtype), xc, w, lc, vc,
                                        bias_f)
    else:
        total = _chunked_xe_total(jnp.dtype(dtype), xc, w, lc, vc, bias_f)
    count = jnp.sum(valid)
    if reduction == "sum_count":
        return total, count
    return total / jnp.maximum(count, 1.0)
