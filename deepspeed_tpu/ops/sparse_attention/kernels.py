"""Block-sparse flash attention — the TPU-native replacement for the
reference's Triton kernel trio (sdd matmul -> sparse softmax -> dsd matmul,
reference deepspeed/ops/sparse_attention/matmul.py:16-60, softmax.py:17-40)
and its OpenMP `sdd_segment` load balancer (csrc/sparse_attention/utils.cpp:119).

Design: the SparsityConfig layout [H, nb, nb] is compile-time metadata. It is
lowered (host-side, numpy) to a per-(head, query-block) lookup table of active
key-block indices, padded to the max row degree. One Pallas kernel then runs a
flash-style online-softmax sweep over *only the active blocks*: scores for a
block pair live in VMEM registers and the [T, T] matrix is never materialized.
This fuses the reference's three kernel launches (plus its block
gather/scatter) into a single MXU-resident kernel, and replaces the sdd_segment
load-balancing machinery entirely — the grid is naturally balanced because
every (head, q-block) program does max_degree iterations with inactive slots
masked (layouts produced by SparsityConfig have near-uniform row degree).

Backward follows the two-pass flash scheme: a dq kernel walks the same LUT; a
dk/dv kernel walks the *transposed* LUT (for each key block, the query blocks
that touch it), both recomputing probabilities from the saved logsumexp.

All kernels run in interpret mode off-TPU so the CPU test mesh exercises the
identical code path.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from deepspeed_tpu.ops import pallas_mode
from deepspeed_tpu.ops.transformer.kernels.attention import (
    _bwd_mode, _mask_operand, _mxu_precision)

NEG_INF = -1e30

_LUT_OP = None  # lazily-loaded C++ lowering op (None until first use)


def _lut_op():
    """The C++ OpenMP LUT lowering (csrc/sparse_attention/lut.cpp — the
    reference's sdd_segment tier, csrc/sparse_attention/utils.cpp:119).
    Returns the bound cdll or False if unavailable."""
    global _LUT_OP
    if _LUT_OP is None:
        from deepspeed_tpu.op_builder import SparseLutBuilder
        builder = SparseLutBuilder()
        try:
            _LUT_OP = builder.load(verbose=False) \
                if builder.is_compatible() else False
        except (RuntimeError, OSError):
            _LUT_OP = False
    return _LUT_OP


def build_luts(layout):
    """Lower a [H, nb, nb] 0/1 layout to forward and transposed LUTs.

    Returns (fwd_lut [H, nb, max_deg], bwd_lut [H, nb, max_deg_t]) int32
    numpy arrays padded with -1. fwd_lut[h, i] lists the active key blocks for
    query block i; bwd_lut[h, j] lists the active query blocks for key block j.

    The lowering runs in the C++ OpenMP op when a toolchain is available
    (one parallel pass per direction); falls back to numpy loops otherwise.
    """
    layout = np.asarray(layout, dtype=bool)
    h, nb, _ = layout.shape

    op = _lut_op()
    if op:
        import ctypes
        lay32 = np.ascontiguousarray(layout, dtype=np.int32)
        ptr = lay32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))

        def lower(transpose):
            deg = int(op.ds_lut_max_degree(h, nb, nb, ptr, transpose))
            lut = np.empty((h, nb, deg), dtype=np.int32)
            op.ds_build_lut(h, nb, nb, ptr, transpose, deg,
                            lut.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
            return lut

        return lower(0), lower(1)

    def rows_to_lut(mat):  # mat: [H, rows, cols] bool
        deg = mat.sum(-1).max() if mat.any() else 1
        deg = max(int(deg), 1)
        lut = np.full((h, mat.shape[1], deg), -1, dtype=np.int32)
        for hi in range(h):
            for r in range(mat.shape[1]):
                cols = np.nonzero(mat[hi, r])[0]
                lut[hi, r, :len(cols)] = cols
        return lut

    return rows_to_lut(layout), rows_to_lut(layout.transpose(0, 2, 1))


def _lut_row(lut_ref, deg):
    """Offset of this program's row in the flattened LUT: the table rides
    in SMEM (scalar prefetch) as one int32 vector [H * rows * deg] — a
    (1, 1, deg) VMEM window on the 3-D table is a shape Mosaic refuses,
    and its entries steer addresses, which is what SMEM is for."""
    return (pl.program_id(1) * pl.num_programs(2) + pl.program_id(2)) * deg


def _apply_masks(s, q_start, c, blk, kpm_blk, bias_blk, valid, causal,
                 kpm_mode, bias_mode):
    """Score post-processing shared by all kernels. s: [bq, blk] fp32."""
    if kpm_blk is not None:
        s = s * kpm_blk if kpm_mode == 'mul' else s + kpm_blk
    if bias_blk is not None:
        s = s * bias_blk if bias_mode == 'mul' else s + bias_blk
    if causal:
        bq = s.shape[0]
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, blk), 0)
        k_pos = c * blk + jax.lax.broadcasted_iota(jnp.int32, (bq, blk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return jnp.where(valid, s, NEG_INF)


def _unpack(refs, n_out, has_kpm, has_bias):
    """Split the flat pallas ref list into (q, k, v, lut, kpm, bias, rest...).
    The LUT is the scalar-prefetch operand, so it leads the list."""
    refs = list(refs)
    lut_ref, q_ref, k_ref, v_ref = refs[:4]
    idx = 4
    kpm_ref = bias_ref = None
    if has_kpm:
        kpm_ref = refs[idx]
        idx += 1
    if has_bias:
        bias_ref = refs[idx]
        idx += 1
    return q_ref, k_ref, v_ref, lut_ref, kpm_ref, bias_ref, refs[idx:]


def _fwd_kernel(*refs, scale, blk, deg, causal, has_kpm, has_bias, kpm_mode,
                bias_mode, precision):
    (q_ref, k_ref, v_ref, lut_ref, kpm_ref, bias_ref,
     (o_ref, lse_ref)) = _unpack(refs, 2, has_kpm, has_bias)

    q = q_ref[0, 0].astype(jnp.float32) * scale            # [bq, d]
    bq, d = q.shape
    iq = pl.program_id(2)
    row = _lut_row(lut_ref, deg)

    def body(j, carry):
        acc, m_prev, l_prev = carry
        col = lut_ref[row + j]
        valid = col >= 0
        c = jnp.maximum(col, 0)
        k_blk = k_ref[0, 0, pl.ds(c * blk, blk)].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(c * blk, blk)].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=precision)
        kpm_blk = (kpm_ref[0, pl.ds(c * blk, blk)][None, :]
                   if kpm_ref is not None else None)
        bias_blk = (bias_ref[0, 0, :, pl.ds(c * blk, blk)]
                    if bias_ref is not None else None)
        s = _apply_masks(s, iq * bq, c, blk, kpm_blk, bias_blk, valid, causal,
                         kpm_mode, bias_mode)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Keep m finite when a whole block is masked (exp(-inf - -inf) traps).
        m_safe = jnp.maximum(m_new, 0.5 * NEG_INF)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        return acc, m_new, l_new

    acc, m, l = jax.lax.fori_loop(
        0, deg, body,
        (jnp.zeros((bq, d), jnp.float32),
         jnp.full((bq, 1), NEG_INF, jnp.float32),
         jnp.zeros((bq, 1), jnp.float32)))

    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.maximum(m, 0.5 * NEG_INF) + jnp.log(l)


def _recompute_p_ds(q, do, lse, delta, k_blk, v_blk, kpm_blk, bias_blk,
                    valid, q_start, c, blk, scale, causal, kpm_mode,
                    bias_mode, precision):
    """Shared backward block recompute for one (row, column) block pair:
    s is rebuilt exactly as the forward built it (same masks, same
    precision), then p = exp(s - lse) and ds = p * (dp - delta) * scale.
    In mul-mask modes the mask scales the pre-softmax score, so it also
    scales the score gradient ds flowing back to q/k. Used by all three
    backward kernels so the split and fused paths cannot diverge."""
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=precision) * scale
    s = _apply_masks(s, q_start, c, blk, kpm_blk, bias_blk, valid, causal,
                     kpm_mode, bias_mode)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=precision)
    ds = p * (dp - delta) * scale
    if kpm_blk is not None and kpm_mode == 'mul':
        ds = ds * kpm_blk
    if bias_blk is not None and bias_mode == 'mul':
        ds = ds * bias_blk
    return p, ds


def _bwd_dq_kernel(*refs, scale, blk, deg, causal, has_kpm, has_bias,
                   kpm_mode, bias_mode, precision):
    (q_ref, k_ref, v_ref, lut_ref, kpm_ref, bias_ref,
     (do_ref, lse_ref, delta_ref, dq_ref)) = _unpack(refs, 1, has_kpm, has_bias)

    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    bq, d = q.shape
    iq = pl.program_id(2)
    row = _lut_row(lut_ref, deg)

    def body(j, dq):
        col = lut_ref[row + j]
        valid = col >= 0
        c = jnp.maximum(col, 0)
        kv = pl.ds(c * blk, blk)
        k_blk = k_ref[0, 0, kv].astype(jnp.float32)
        v_blk = v_ref[0, 0, kv].astype(jnp.float32)
        kpm_blk = kpm_ref[0, kv][None, :] if kpm_ref is not None else None
        bias_blk = bias_ref[0, 0, :, kv] if bias_ref is not None else None
        _, ds = _recompute_p_ds(q, do, lse, delta, k_blk, v_blk, kpm_blk,
                                bias_blk, valid, iq * bq, c, blk, scale,
                                causal, kpm_mode, bias_mode, precision)
        return dq + jax.lax.dot_general(ds, k_blk, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32,
                                        precision=precision)

    dq = jax.lax.fori_loop(0, deg, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_fused_kernel(*refs, scale, blk, deg, causal, has_kpm, has_bias,
                      kpm_mode, bias_mode, precision):
    """One-pass backward: dq, dk, dv from a single LUT-steered sweep.

    The split kernels each recompute s, p and dO.V^T per (row, column)
    block pair; this kernel computes them once, accumulating dk/dv into
    full-length fp32 VMEM scratch indexed by the forward LUT's column
    (a scatter — every listed pair is visited exactly once, so it covers
    exactly what the transposed-LUT gather covered; invalid entries alias
    column 0 but contribute exact zeros since their p and ds are zero).
    Same structure as the dense flash fused backward
    (ops/transformer/kernels/attention.py:_bwd_fused_kernel)."""
    (q_ref, k_ref, v_ref, lut_ref, kpm_ref, bias_ref,
     rest) = _unpack(refs, 3, has_kpm, has_bias)
    do_ref, lse_ref, delta_ref = rest[:3]
    dq_ref, dk_ref, dv_ref = rest[3:6]
    dk_acc, dv_acc = rest[6:8]

    i = pl.program_id(2)
    n_q = pl.num_programs(2)
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    bq, d = q.shape

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    row = _lut_row(lut_ref, deg)

    def body(j, dq):
        col = lut_ref[row + j]
        valid = col >= 0
        c = jnp.maximum(col, 0)
        kv = pl.ds(c * blk, blk)
        k_blk = k_ref[0, 0, kv].astype(jnp.float32)
        v_blk = v_ref[0, 0, kv].astype(jnp.float32)
        kpm_blk = kpm_ref[0, kv][None, :] if kpm_ref is not None else None
        bias_blk = bias_ref[0, 0, :, kv] if bias_ref is not None else None
        p, ds = _recompute_p_ds(q, do, lse, delta, k_blk, v_blk, kpm_blk,
                                bias_blk, valid, i * bq, c, blk, scale,
                                causal, kpm_mode, bias_mode, precision)
        dv_acc[kv] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dk_acc[kv] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        return dq + jax.lax.dot_general(ds, k_blk, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32,
                                        precision=precision)

    dq = jax.lax.fori_loop(0, deg, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)

    @pl.when(i == n_q - 1)
    def _emit():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, blk, bq, deg, causal, has_kpm, has_bias,
                    kpm_mode, bias_mode, precision):
    (q_ref, k_ref, v_ref, tlut_ref, kpm_ref, bias_ref,
     (do_ref, lse_ref, delta_ref, dk_ref, dv_ref)) = _unpack(
         refs, 2, has_kpm, has_bias)

    k_blk = k_ref[0, 0].astype(jnp.float32)                # [blk, d]
    v_blk = v_ref[0, 0].astype(jnp.float32)
    d = k_blk.shape[1]
    jk = pl.program_id(2)
    kpm_blk = kpm_ref[0][None, :] if kpm_ref is not None else None  # [1, blk]
    lut_row = _lut_row(tlut_ref, deg)

    def body(j, carry):
        dk, dv = carry
        row = tlut_ref[lut_row + j]
        valid = row >= 0
        r = jnp.maximum(row, 0)
        q = q_ref[0, 0, pl.ds(r * bq, bq)].astype(jnp.float32)
        do = do_ref[0, 0, pl.ds(r * bq, bq)].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(r * bq, bq)]
        delta = delta_ref[0, 0, pl.ds(r * bq, bq)]
        bias_blk = (bias_ref[0, 0, pl.ds(r * bq, bq), :]
                    if bias_ref is not None else None)
        p, ds = _recompute_p_ds(q, do, lse, delta, k_blk, v_blk, kpm_blk,
                                bias_blk, valid, r * bq, jk, blk, scale,
                                causal, kpm_mode, bias_mode, precision)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32,
                                      precision=precision)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32,
                                      precision=precision)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        0, deg, body,
        (jnp.zeros((blk, d), jnp.float32), jnp.zeros((blk, d), jnp.float32)))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp assembly — one cached closure per (layout, flags) so the LUTs are
# baked into the jaxpr as constants (the layout is per-layer static metadata).
# ---------------------------------------------------------------------------

_FN_CACHE = {}


def _make_fn(fwd_lut, bwd_lut, blk, scale, causal, has_kpm, has_bias,
             kpm_mode, bias_mode, precision=None):
    # LUTs stay numpy in the closure; they are converted per call so that a
    # closure first built under a jit trace never caches tracer constants.
    fwd_lut = np.asarray(fwd_lut)
    bwd_lut = np.asarray(bwd_lut)
    flags = dict(causal=causal, has_kpm=has_kpm, has_bias=has_bias,
                 kpm_mode=kpm_mode, bias_mode=bias_mode, precision=precision)

    def launch(name, kernel, lut, grid, in_specs, out_specs, out_shape,
               args, scratch=()):
        """Kernel ``name`` with the LUT as the scalar-prefetch operand: one
        flattened int32 vector in SMEM, read by the kernel bodies (index
        maps receive it as a trailing argument and ignore it)."""
        from jax.experimental.pallas import tpu as pltpu

        return pallas_mode.kernel_call(
            name, functools.partial(kernel, scale=scale, blk=blk,
                                    deg=lut.shape[2], **flags),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=list(scratch)),
            out_shape=out_shape,
        )(jnp.asarray(lut.reshape(-1)), *args)

    def specs(t, d):
        """(q-block, full-length, row-block, full-row, mask, bias) specs of
        the (batch, head, q-block) grid."""
        return (
            pl.BlockSpec((1, 1, blk, d), lambda b_, h_, i, _: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, t, d), lambda b_, h_, i, _: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, blk, 1), lambda b_, h_, i, _: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, t, 1), lambda b_, h_, i, _: (b_, h_, 0, 0)),
            # [B, 1, T] with the batch dim squeezed: the kernel sees
            # (1, T), and the window's last two dims equal the array's.
            pl.BlockSpec((None, 1, t), lambda b_, h_, i, _: (b_, 0, 0)),
            pl.BlockSpec((1, 1, blk, t), lambda b_, h_, i, _: (b_, h_, i, 0)),
        )

    def fwd(q, k, v, kpm, bias):
        b, h, t, d = q.shape
        q_spec, full, row_blk, _, kpm_spec, bias_spec = specs(t, d)
        in_specs = [q_spec, full, full]
        args = [q, k, v]
        if has_kpm:
            in_specs.append(kpm_spec)
            args.append(_mask_operand(kpm))
        if has_bias:
            in_specs.append(bias_spec)
            args.append(bias.astype(jnp.float32))
        o, lse = launch(
            "sparse_attn_fwd", _fwd_kernel, fwd_lut, (b, h, t // blk), in_specs,
            [q_spec, row_blk],
            [jax.ShapeDtypeStruct(q.shape, q.dtype),
             jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32)], args)
        return o, lse

    @jax.custom_vjp
    def attend(q, k, v, kpm, bias):
        return fwd(q, k, v, kpm, bias)[0]

    def attend_fwd(q, k, v, kpm, bias):
        o, lse = fwd(q, k, v, kpm, bias)
        return o, (q, k, v, kpm, bias, o, lse)

    def attend_bwd(res, g):
        q, k, v, kpm, bias, o, lse = res
        b, h, t, d = q.shape
        grid = (b, h, t // blk)
        do = g
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        q_spec, full, row_blk, row_full, kpm_spec, bias_spec = specs(t, d)
        qkv_shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                      for x in (q, k, v)]

        in_specs = [q_spec, full, full]
        args = [q, k, v]
        if has_kpm:
            in_specs.append(kpm_spec)
            args.append(_mask_operand(kpm))
        if has_bias:
            in_specs.append(bias_spec)
            args.append(bias.astype(jnp.float32))
        in_specs += [q_spec, row_blk, row_blk]
        args += [do, lse, delta]

        if _bwd_mode(t, d, q.dtype) == "fused":
            # One LUT-steered sweep produces dq and scatter-accumulates
            # dk/dv into full-length fp32 scratch (same input layout as
            # the dq kernel, so the spec/arg lists are shared).
            from jax.experimental.pallas import tpu as pltpu

            dq, dk, dv = launch(
                "sparse_attn_bwd_fused", _bwd_fused_kernel, fwd_lut, grid, in_specs,
                [q_spec, full, full], qkv_shapes, args,
                scratch=[pltpu.VMEM((t, d), jnp.float32),
                         pltpu.VMEM((t, d), jnp.float32)])
            return _finish_bwd(q, k, v, kpm, bias, do, lse, delta,
                               dq, dk, dv)

        dq = launch("sparse_attn_bwd_dq", _bwd_dq_kernel, fwd_lut, grid,
                    in_specs, q_spec, qkv_shapes[0], args)

        # dk/dv: the same grid over KEY blocks, walking the transposed LUT.
        kv_spec = q_spec
        in_specs = [full, kv_spec, kv_spec]
        args = [q, k, v]
        if has_kpm:
            in_specs.append(pl.BlockSpec(
                (None, 1, blk), lambda b_, h_, j, _: (b_, 0, j)))
            args.append(_mask_operand(kpm))
        if has_bias:
            in_specs.append(pl.BlockSpec(
                (1, 1, t, blk), lambda b_, h_, j, _: (b_, h_, 0, j)))
            args.append(bias.astype(jnp.float32))
        in_specs += [full, row_full, row_full]
        args += [do, lse, delta]
        dk, dv = launch(
            "sparse_attn_bwd_dkv",
            functools.partial(_bwd_dkv_kernel, bq=blk), bwd_lut, grid,
            in_specs, [kv_spec, kv_spec], qkv_shapes[1:], args)

        return _finish_bwd(q, k, v, kpm, bias, do, lse, delta, dq, dk, dv)

    def _finish_bwd(q, k, v, kpm, bias, do, lse, delta, dq, dk, dv):
        """Shared tail of both backward paths: mask/bias cotangents."""
        b, h, t, d = q.shape
        # The key-padding mask is an input mask, never a learned parameter:
        # its cotangent is defined as zero (documented non-differentiable).
        dkpm = None if kpm is None else jnp.zeros_like(kpm)
        # attn_bias CAN be learned (the reference's rpe receives real grads
        # under torch autograd), so its cotangent must be real: reconstruct
        # p and dS densely — the bias is already a dense [B,H,T,T] tensor,
        # so its gradient is inherently dense-sized and this costs two
        # einsums, comparable to one bwd kernel pass.
        dbias = None
        if bias is not None:
            f32 = jnp.float32
            # layout block mask (from the LUT: listed kv-block columns),
            # then the causal mask — matching _apply_masks exactly.
            nq = t // blk
            valid_blocks = np.zeros((h, nq, nq), bool)
            for h_ in range(h):
                for i_ in range(nq):
                    cols = fwd_lut[h_, i_]
                    valid_blocks[h_, i_, cols[cols >= 0]] = True
            valid_np = np.repeat(np.repeat(valid_blocks, blk, axis=1),
                                 blk, axis=2)
            if causal:
                pos = np.arange(t)
                valid_np = valid_np & (pos[:, None] >= pos[None, :])[None]
            kpm_b = (kpm.astype(f32)[:, None, :]
                     if kpm is not None else None)

            def per_head(args):
                # One head at a time: peak temporaries are [B,T,T], not
                # [B,H,T,T] — the dense reconstruction must not multiply
                # backward memory H-fold in the long-sequence regime this
                # kernel exists for.
                q_h, k_h, v_h, do_h, lse_h, delta_h, bias_h, valid_h = args
                s = jnp.einsum("bqd,bkd->bqk", q_h.astype(f32),
                               k_h.astype(f32), precision=precision,
                               preferred_element_type=f32) * scale
                if kpm_b is not None:
                    s = s * kpm_b if kpm_mode == 'mul' else s + kpm_b
                s_pre_bias = s
                bias_f = bias_h.astype(f32)
                s = s * bias_f if bias_mode == 'mul' else s + bias_f
                s = jnp.where(valid_h[None], s, NEG_INF)
                p = jnp.exp(s - lse_h.astype(f32))
                dp = jnp.einsum("bqd,bkd->bqk", do_h.astype(f32),
                                v_h.astype(f32), precision=precision,
                                preferred_element_type=f32)
                dS = p * (dp - delta_h.astype(f32))
                out = dS if bias_mode != 'mul' else dS * s_pre_bias
                return jnp.where(valid_h[None], out, 0.0).astype(bias.dtype)

            swap = lambda x: jnp.swapaxes(x, 0, 1)  # [B,H,...] -> [H,B,...]
            dbias = jnp.swapaxes(jax.lax.map(per_head, (
                swap(q), swap(k), swap(v), swap(do), swap(lse), swap(delta),
                swap(bias), jnp.asarray(valid_np))), 0, 1)
        return dq, dk, dv, dkpm, dbias

    attend.defvjp(attend_fwd, attend_bwd)
    return attend


def block_sparse_attention(q, k, v, layout, block, scale=None, causal=False,
                           key_padding_mask=None, key_padding_mask_mode='add',
                           attn_bias=None, attn_bias_mode='add'):
    """Block-sparse multi-head attention steered by a SparsityConfig layout.

    Args:
      q, k, v: [B, H, T, D]; T must be a multiple of `block`
        (SparseAttentionUtils.pad_to_block_size pads).
      layout: [H, T//block, T//block] 0/1 numpy array from
        SparsityConfig.make_layout.
      block: layout block size.
      causal: additionally apply an elementwise causal mask (the layouts from
        unidirectional configs are causal only at block granularity; this
        sharpens the diagonal blocks).
      key_padding_mask: [B, T] mask combined per mask mode ('add': added to
        scores; 'mul': multiplies scores — the reference softmax's semantics,
        softmax.py:17-40).
      attn_bias: [B, H, T, T] additive/multiplicative score bias — carries the
        reference's `rpe` and 2D `attn_mask` arguments.
    Returns: [B, H, T, D] in q.dtype.
    """
    b, h, t, d = q.shape
    if t % block != 0:
        raise ValueError('Sequence Length, {}, needs to be dividable by '
                         'Block size {}!'.format(t, block))
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    layout = np.asarray(layout)
    if layout.shape[0] != h:
        raise ValueError('layout heads {} != tensor heads {}'.format(
            layout.shape[0], h))
    # fp32 models contract at HIGHEST: the kernels accumulate in fp32, but
    # at DEFAULT the MXU rounds the fp32 OPERANDS to bf16 — fine when the
    # inputs started as bf16/fp16, silently lossy for fp32 parity.
    precision = _mxu_precision(q.dtype)
    key = (layout.tobytes(), layout.shape, int(block), float(scale),
           bool(causal), key_padding_mask is not None,
           attn_bias is not None, key_padding_mask_mode, attn_bias_mode,
           precision)
    fn = _FN_CACHE.get(key)
    if fn is None:
        fwd_lut, bwd_lut = build_luts(layout)
        fn = _make_fn(fwd_lut, bwd_lut, int(block), float(scale),
                      bool(causal), key_padding_mask is not None,
                      attn_bias is not None, key_padding_mask_mode,
                      attn_bias_mode, precision=precision)
        _FN_CACHE[key] = fn
    return fn(q, k, v, key_padding_mask, attn_bias)


def block_sparse_attention_reference(q, k, v, layout, block, scale=None,
                                     causal=False, key_padding_mask=None,
                                     key_padding_mask_mode='add',
                                     attn_bias=None, attn_bias_mode='add',
                                     precision=None):
    """Dense jnp ground truth: expand the block layout to an elementwise mask
    and run ordinary softmax attention. Used by parity tests.

    precision: forwarded to the einsums. When None, fp32 inputs default to
    'highest' — on TPU, DEFAULT rounds the fp32 operands to bf16 on the
    MXU, which would make the ground truth LESS accurate than the kernel
    under test (the kernel applies the same fp32->HIGHEST rule)."""
    if precision is None:
        precision = _mxu_precision(q.dtype)
    b, h, t, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    layout = np.asarray(layout)
    dense = np.kron(layout, np.ones((block, block)))[:, :t, :t]  # [H, T, T]
    s = jnp.einsum('bhqd,bhkd->bhqk', q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=precision) * scale
    if key_padding_mask is not None:
        kpm = key_padding_mask.astype(jnp.float32)[:, None, None, :]
        s = s * kpm if key_padding_mask_mode == 'mul' else s + kpm
    if attn_bias is not None:
        ab = attn_bias.astype(jnp.float32)
        s = s * ab if attn_bias_mode == 'mul' else s + ab
    if causal:
        cm = jnp.tril(jnp.ones((t, t), dtype=bool))
        s = jnp.where(cm[None, None], s, NEG_INF)
    s = jnp.where(jnp.asarray(dense, dtype=bool)[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # Fully-masked rows (no active blocks) produce zeros, matching the
    # kernel. Causality is already folded into `s` above.
    row_any = jnp.asarray(dense.any(-1), dtype=bool)[None, :, :, None]
    p = jnp.where(row_any, p, 0.0)
    return jnp.einsum('bhqk,bhkd->bhqd', p, v.astype(jnp.float32),
                      precision=precision).astype(q.dtype)
