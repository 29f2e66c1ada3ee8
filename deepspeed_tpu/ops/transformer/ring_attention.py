"""Ring attention — sequence-parallel flash attention over a mesh axis.

Long-context support beyond the reference: DeepSpeed v0.3.10's only
long-sequence lever is block-sparse attention (verified in SURVEY §0/§5.7 —
no sequence/context parallelism anywhere in that tree). On TPU, sequences
that exceed one chip's HBM shard naturally over the ICI ring: each device
holds a [T/N] slice of q/k/v, computes flash attention against its local
key/value block, then rotates the k/v blocks around the ring with
``jax.lax.ppermute`` — after N-1 rotations every query block has attended
every key block, with O(T/N) activation memory per chip and communication
fully overlappable with the per-block flash kernels.

Design notes:
- The per-block compute is the SAME Pallas flash kernel pair as
  single-chip attention (`kernels/attention.py`: `_flash_fwd_pallas` /
  `_flash_bwd_pallas`); forward partials merge by logsumexp algebra:
      m = max(lse_a, lse_b);  w = exp(lse - m)
      o = (o_a w_a + o_b w_b) / (w_a + w_b);  lse = m + log(w_a + w_b)
  which is exactly the flash online-softmax update at ring granularity.
  Shard lengths the tiled kernels cannot take (ragged vs the tile size)
  use a dense jnp per-block compute instead.
- Causality is decided at BLOCK level from the ring step: source block j
  attends destination block i fully when j < i, causally (diagonal) when
  j == i, and not at all when j > i — the skipped blocks never run a
  kernel (lax.cond on the uniform ring counter) and contribute a NEG_INF
  lse, making the merge a no-op.
- An additive key padding mask ([B, T] over GLOBAL key positions, sharded
  like k/v) rotates around the ring alongside its k/v block.
- The backward is a hand-written custom VJP (`_ring_bwd_scan`): it
  re-rotates k/v and recomputes per-block probabilities from the saved
  GLOBAL logsumexp and delta = rowsum(dO*O) (the flash identity
  ds = p*(dp - delta) holds per block with global statistics); dk/dv
  accumulate in buffers that travel with their block and arrive home
  after the n-th rotation. O(T/N) memory per device in both directions —
  autodiff-through-scan would checkpoint every rotated k/v block.
- Call inside ``shard_map`` with the sequence dim sharded over
  ``axis_name`` (helper ``sequence_parallel_attention`` wraps this for a
  mesh). The batch dim may additionally be sharded over 'data' as usual.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.kernels.attention import (
    NEG_INF, _flash_bwd_pallas, _flash_fwd_pallas, _mxu_precision,
    flash_attention_with_lse, mha_reference, resolve_block_sizes)


def _merge(o_a, lse_a, o_b, lse_b):
    """Combine two partial attention results over the same queries.
    o: [B, H, T, D] fp32; lse: [B, H, T, 1] fp32. Skipped blocks carry
    lse = NEG_INF (-1e30, finite): after subtracting the max their weight
    underflows to exactly 0, so no special-casing is needed — the max side
    always contributes weight exp(0) = 1 and the denominator is >= 1."""
    m = jnp.maximum(lse_a, lse_b)
    w_a = jnp.exp(lse_a - m)
    w_b = jnp.exp(lse_b - m)
    denom = w_a + w_b
    o = (o_a * w_a + o_b * w_b) / denom
    return o, m + jnp.log(denom)


def _dense_block_fwd(q, k, v, mask, scale, causal):
    """Dense jnp per-block (o, lse) for shard lengths the tiled kernels
    cannot take — one shared dense implementation (mha_reference)."""
    return mha_reference(q, k, v, mask=mask, causal=causal, scale=scale,
                         return_lse=True)


def _dense_block_bwd(q, k, v, mask, delta, lse, do, scale, causal):
    """Dense jnp per-block flash backward with GLOBAL row statistics:
    p = exp(s - lse), ds = p * (dp - delta).

    The recomputed s must round the same way the forward (mha_reference)
    did, or p no longer matches the saved lse — so the einsums share the
    forward's dtype-dependent precision rule (fp32 -> HIGHEST on the MXU,
    bf16/fp16 -> DEFAULT, where fwd/bwd rounding cancels)."""
    prec = _mxu_precision(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=prec) * scale
    if mask is not None:
        s = s + mask[:, None, None, :].astype(jnp.float32)
    if causal:
        t_q, t_k = q.shape[2], k.shape[2]
        cm = jnp.tril(jnp.ones((t_q, t_k), dtype=bool))
        s = jnp.where(cm[None, None], s, NEG_INF)
    # s <= lse mathematically; the clamp guards fully-masked rows where
    # fp32 lse (~-1e9, ulp 64) loses the log-sum bits — exp of a spurious
    # +64 would poison the whole step with inf grads.
    p = jnp.exp(jnp.minimum(s - lse, 0.0))
    do32 = do.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, do32, precision=prec)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do32, v.astype(jnp.float32),
                    precision=prec)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32),
                    precision=prec)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32),
                    precision=prec)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _block_fwd(q, k, v, mask, scale, causal, bq, bk, dense):
    if dense:
        return _dense_block_fwd(q, k, v, mask, scale, causal)
    return _flash_fwd_pallas(q, k, v, mask, scale, causal, bq, bk)


def _block_bwd(q, k, v, mask, delta, lse, do, scale, causal, bq, bk,
               dense):
    if dense:
        return _dense_block_bwd(q, k, v, mask, delta, lse, do, scale,
                                causal)
    return _flash_bwd_pallas(q, k, v, mask, delta, lse, do, scale, causal,
                             bq, bk)


def _ring_fwd_scan(q, k, v, mask, axis_name, causal, scale, bq, bk, dense):
    """(o fp32, lse) after the full ring. mask: fp32 [B, T_local] or None."""
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, t_local, d = q.shape
    o0 = jnp.zeros((b, h, t_local, d), jnp.float32)
    lse0 = jnp.full((b, h, t_local, 1), NEG_INF, jnp.float32)
    has_mask = mask is not None
    # The mask occupies a scan-carry slot (rotating with its k/v block)
    # only when present - a dead zeros-mask would cost one extra ppermute
    # per ring step per layer.
    mask_carry = (mask,) if has_mask else ()
    # Ring neighbour: receive from the previous rank, send to the next, so
    # at step s the local device holds k/v block (my - s) mod n.
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        o, lse, k_blk, v_blk = carry[:4]
        cur_mask = carry[4] if has_mask else None
        src = (my - s) % n

        def full_block():
            oc, lc = _block_fwd(q, k_blk, v_blk, cur_mask, scale, False,
                                bq, bk, dense)
            return oc.astype(jnp.float32), lc

        if causal:
            def diag_block():
                od, ld = _block_fwd(q, k_blk, v_blk, cur_mask, scale,
                                    True, bq, bk, dense)
                return od.astype(jnp.float32), ld

            def skipped_block():
                return jnp.zeros_like(o0), jnp.full_like(lse0, NEG_INF)

            # Block-level causality by ring step: src > my contributes
            # nothing (and its kernels never run - cond, not where).
            o_p, lse_p = jax.lax.cond(
                src > my, skipped_block,
                lambda: jax.lax.cond(src == my, diag_block, full_block))
        else:
            o_p, lse_p = full_block()
        o, lse = _merge(o, lse, o_p, lse_p)

        # Rotate k/v (+mask) for the next step. The final step's rotation
        # would be discarded - skip it (the predicate is the scan counter,
        # identical on every device, so the collective stays globally
        # consistent).
        def rotate(kvm):
            return tuple(jax.lax.ppermute(x, axis_name, perm) for x in kvm)

        rolling = (k_blk, v_blk) + ((cur_mask,) if has_mask else ())
        rolling = jax.lax.cond(s < n - 1, rotate, lambda kvm: kvm, rolling)
        return (o, lse) + rolling, None

    (o, lse, *_), _ = jax.lax.scan(step, (o0, lse0, k, v) + mask_carry,
                                   jnp.arange(n))
    return o, lse


def _ring_bwd_scan(q, k, v, mask, o, lse, do, axis_name, causal, scale,
                   bq, bk, dense):
    """Recompute-and-re-rotate ring backward: O(T/N) memory per device.

    The per-block backward is the SAME two-pass flash backward as
    single-chip attention, fed the GLOBAL row statistics (lse and
    delta = rowsum(dO*O)) - the flash identity ds = p*(dp - delta) holds
    per block with global delta. dq accumulates locally; dk/dv accumulate
    in buffers that TRAVEL WITH their k/v block and arrive home after the
    n-th rotation.
    """
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    has_mask = mask is not None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        dq_acc, dk_rot, dv_rot, k_blk, v_blk = carry[:5]
        cur_mask = carry[5] if has_mask else None
        src = (my - s) % n

        def block(causal_mode):
            return _block_bwd(q, k_blk, v_blk, cur_mask, delta, lse, do,
                              scale, causal_mode, bq, bk, dense)

        def full_block():
            return block(False)

        if causal:
            def diag_block():
                return block(True)

            def skipped_block():
                return (jnp.zeros(q.shape, q.dtype),
                        jnp.zeros(k.shape, k.dtype),
                        jnp.zeros(v.shape, v.dtype))

            dq_p, dk_p, dv_p = jax.lax.cond(
                src > my, skipped_block,
                lambda: jax.lax.cond(src == my, diag_block, full_block))
        else:
            dq_p, dk_p, dv_p = full_block()

        dq_acc = dq_acc + dq_p.astype(jnp.float32)
        dk_rot = dk_rot + dk_p.astype(jnp.float32)
        dv_rot = dv_rot + dv_p.astype(jnp.float32)
        # The grad buffers rotate on EVERY step (n rotations total bring
        # block my's gradients home); k/v/mask skip the final dead hop.
        dk_rot = jax.lax.ppermute(dk_rot, axis_name, perm)
        dv_rot = jax.lax.ppermute(dv_rot, axis_name, perm)

        def rotate(kvm):
            return tuple(jax.lax.ppermute(x, axis_name, perm) for x in kvm)

        rolling = (k_blk, v_blk) + ((cur_mask,) if has_mask else ())
        rolling = jax.lax.cond(s < n - 1, rotate, lambda kvm: kvm, rolling)
        return (dq_acc, dk_rot, dv_rot) + rolling, None

    carry0 = (dq0, dk0, dv0, k, v) + ((mask,) if has_mask else ())
    (dq, dk, dv, *_), _ = jax.lax.scan(step, carry0, jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring(q, k, v, mask, axis_name, causal, scale, bq, bk, dense):
    o, _ = _ring_fwd_scan(q, k, v, mask, axis_name, causal, scale, bq, bk,
                          dense)
    return o.astype(q.dtype)


def _ring_fwd(q, k, v, mask, axis_name, causal, scale, bq, bk, dense):
    o, lse = _ring_fwd_scan(q, k, v, mask, axis_name, causal, scale,
                            bq, bk, dense)
    o = o.astype(q.dtype)
    return o, (q, k, v, mask, o, lse)


def _ring_bwd(axis_name, causal, scale, bq, bk, dense, res, do):
    q, k, v, mask, o, lse = res
    dq, dk, dv = _ring_bwd_scan(q, k, v, mask, o, lse, do, axis_name,
                                causal, scale, bq, bk, dense)
    dmask = None if mask is None else jnp.zeros_like(mask)
    return dq, dk, dv, dmask


_ring.defvjp(_ring_fwd, _ring_bwd)


def ring_flash_attention(q, k, v, axis_name, causal=False, mask=None,
                         scale=None, block_q=None, block_k=None):
    """Flash attention over sequence shards on a ring. SPMD-collective:
    must run inside shard_map (or pmap) with ``axis_name`` bound, with
    q/k/v sequence dims sharded over that axis.

    Memory is O(T/N) per device in BOTH directions: the custom backward
    re-rotates k/v and recomputes per-block probabilities from the saved
    global logsumexp (the flash recompute trick at ring granularity) -
    autodiff-through-scan would instead checkpoint every rotated k/v
    block, i.e. the full O(T) key/value set.

    Args:
      q, k, v: [B, H, T_local, D] - the local sequence shard.
      axis_name: mesh axis the sequence is sharded over.
      causal: causal masking in GLOBAL sequence positions (shards are
        assumed laid out in axis-index order).
      mask: optional additive key padding mask shard [B, T_local]
        (0 keep / -1e9 drop), covering this shard's KEY positions; it
        rotates with the k/v blocks (non-differentiable, like the flash
        kernel's mask).
      scale: score scale; default 1/sqrt(D).
      block_q, block_k: Pallas tile sizes for the local kernel. Default
        (None) consults the per-shape autotuner table for the LOCAL
        block shapes. Shard lengths not divisible by the tiles use a
        dense jnp per-block compute (any length works; O(t_local^2)
        score memory per block pair).
    Returns: [B, H, T_local, D] in q.dtype.
    """
    n = jax.lax.axis_size(axis_name)
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)

    if n == 1:
        return flash_attention_with_lse(
            q, k, v, mask=mask, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k)[0]

    # Tile lookup keys the NON-causal autotuner entry: in a causal ring
    # n-1 of the n block kernels are the full (non-causal) variant — the
    # diagonal causal call is the minority. The semantic causal flag is
    # passed to the kernels unchanged.
    bq, bk, dense = resolve_block_sizes(q, k, v, False, block_q, block_k)
    mask_f = None if mask is None else mask.astype(jnp.float32)
    return _ring(q, k, v, mask_f, axis_name, bool(causal), scale, bq, bk,
                 dense)


def sequence_parallel_attention(mesh, q, k, v, axis_name="data",
                                causal=False, mask=None, scale=None,
                                block_q=None, block_k=None):
    """shard_map wrapper: q/k/v are GLOBAL [B, H, T, D] arrays (or host
    numpy); the sequence dim is sharded over ``axis_name`` and attention
    runs as a ring. ``mask`` is the GLOBAL [B, T] additive key padding
    mask. Batch/head dims stay replicated here — compose with
    data-parallel batch sharding by calling ring_flash_attention directly
    inside your own shard_map."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)
    ring = functools.partial(ring_flash_attention, axis_name=axis_name,
                             causal=causal, scale=scale, block_q=block_q,
                             block_k=block_k)
    if mask is None:
        fn = shard_map(ring, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
        return fn(q, k, v)
    fn = shard_map(lambda q, k, v, m: ring(q, k, v, mask=m),
                   mesh=mesh,
                   in_specs=(spec, spec, spec, P(None, axis_name)),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v, mask)


def get_sp_attention(mode):
    """Resolve a sequence_parallel_mode string to its attention
    implementation; unknown modes raise instead of silently running a
    different collective pattern."""
    impls = {"ring": ring_flash_attention, "ulysses": ulysses_attention}
    try:
        return impls[mode]
    except KeyError:
        raise ValueError(
            "unknown sequence_parallel_mode {!r}; expected one of {}"
            .format(mode, sorted(impls))) from None


def ulysses_attention(q, k, v, axis_name, causal=False, mask=None,
                      scale=None, block_q=None, block_k=None):
    """All-to-all (DeepSpeed-Ulysses-style) sequence-parallel attention —
    the other classic context-parallel decomposition, complementing the
    ring: two ``jax.lax.all_to_all`` exchanges swap the TOKEN sharding for
    a HEAD sharding, each device runs ordinary full-sequence flash
    attention for its H/N head subset, and the reverse exchange restores
    token sharding. Versus the ring: 2 all-to-alls instead of N-1
    ppermutes (better for small N / fast ICI), but requires num_heads
    divisible by the axis size and materializes the full sequence per
    device (memory O(T·H/N) instead of O(T/N·H)).

    SPMD-collective: call inside shard_map with ``axis_name`` bound.

    Args:
      q, k, v: [B, H, T_local, D] — the local sequence shard.
      axis_name: mesh axis the sequence is sharded over.
      causal: causal masking (global positions).
      mask: optional additive key padding mask shard [B, T_local]
        (gathered to the full [B, T] for the local attention).
      scale, block_q, block_k: forwarded to flash_attention.
    Returns: [B, H, T_local, D] in q.dtype.
    """
    from deepspeed_tpu.ops.transformer.kernels.attention import (
        flash_attention)

    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               scale=scale, block_q=block_q,
                               block_k=block_k)
    h = q.shape[1]
    if h % n:
        raise ValueError(
            "ulysses_attention requires num_heads ({}) divisible by the "
            "'{}' axis size ({}); use ring attention for more shards "
            "than heads".format(h, axis_name, n))

    def to_tokens(x):    # [B, H/n, T, D] -> [B, H, T/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    full_mask = None
    if mask is not None:
        full_mask = jax.lax.all_gather(mask, axis_name, axis=1, tiled=True)
    # One exchange for all three tensors (q/k/v stacked): the documented
    # "two all_to_alls per layer" — one in, one out.
    qkv = jax.lax.all_to_all(jnp.stack([q, k, v]), axis_name,
                             split_axis=2, concat_axis=3, tiled=True)
    o = flash_attention(qkv[0], qkv[1], qkv[2],
                        mask=full_mask, causal=causal, scale=scale,
                        block_q=block_q, block_k=block_k)
    return to_tokens(o)
