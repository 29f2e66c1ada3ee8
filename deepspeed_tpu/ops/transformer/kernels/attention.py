"""Fused multi-head attention — the TPU-native answer to the reference's
attention pipeline (reference csrc/transformer/ds_transformer_cuda.cpp:624:
qkv GEMM -> head split -> score GEMM -> launch_attn_softmax -> attn dropout
-> ctx GEMM -> head merge).

On GPU the reference fuses softmax/dropout between separate cuBLAS GEMMs,
materialising the [T, T] score matrix. On TPU the right fusion boundary is
different: one flash-style Pallas kernel keeps each score block in VMEM and
never writes the [T, T] matrix to HBM — O(T) memory instead of O(T^2), and
both GEMMs land on the MXU from the same kernel.

Two operand layouts, told apart by rank (``flash_attention``'s docstring;
"Two operand layouts, one body a direction" below): HEAD-MAJOR
``[B, H, T, d]``, a head a grid step, which ring / Ulysses attention
(``ring_attention.py``), the BERT layer (``transformer.py``) and
``CausalSelfAttention``'s sequence-parallel branch take; and PACKED
``[B, T, tiles x g x d]``, the layout a projection emits, ``g`` heads a
128-lane tile and a grid step (two at head dim 64), which
``CausalSelfAttention``'s flash branch takes: q, k, v, o, dO, dq, dk, dv
all lie as c_attn writes and c_proj reads them, and the eight head-split
transposes a layer (a tenth of a GPT-2 355M training step) do not exist.

Kernel structure (the part that makes it fast). At head dim 64 every
product of the head-major kernels has a contraction or an output 64 wide,
half of the 128-deep MXU, and at that pace the products ARE the time: two
over the whole 1024 x 1024 square of a GPT-2 head are 2.7 us and the
forward took 3.0, five are 6.8 and the fused backward took 8.4 (v5e, PR 45:
not VPU-bound, as this text said until then); a packed tile's products are
128 deep or wide, a head's operand zero in its neighbour's lanes, at the
same count (a head 2.07 / 4.42 us where head-major reads 2.24 / 4.80, PR
49). So the design (a) never forms a
score the causal mask empties and (b) keeps the elementwise passes over
the scores, 16x the elements of the q/o blocks, few enough to hide:
- the GRID block (``block_q`` x ``block_k``) is large, the whole sequence
  at T 1024: a grid step costs 0.3-0.5 us, a tenth of a head's work.
  Where the grid has several blocks a side, fully-masked blocks are
  skipped by @pl.when and their index map clamps to the last useful block
  (no new DMA for a repeated index); at the default tile there is ONE
  block a head and nothing for the grid to skip;
- INSIDE a block that holds the diagonal the kernels take strips of S
  rows, each over the keys up to its own diagonal tile and no further
  (``flash_subtile``; "Strips inside a diagonal grid block" below): 10 of
  the 16 tiles of 256 x 256, 36 of the 64 of 128 x 128;
- q is SCALED by 1/sqrt(d) on its [rows, d] block inside the kernel (never
  a [T, T] pass, and no pass over HBM);
- the causal mask is a CONSTANT additive tril tile passed as an input and
  added only to the tile that straddles the diagonal. Per-block
  iota/compare/select ladders only remain for the uncommon
  block_q != block_k causal shapes;
- when the kv extent is a single block, the online-softmax machinery
  (running max/sum scratch, accumulator rescale) collapses to one direct
  softmax a strip with no scratch at all;
- the softmax ROW-SUM rides the PV matmul: p @ [v | 1] returns the context
  block and the row-sum from one MXU op, deleting a VPU reduce over
  the scores (forward);
- in the backward, the delta subtraction rides the dp matmul the same way:
  [dO | -delta] @ [V | 1]^T produces dp - delta directly (fp32 MXU
  accumulation), deleting another VPU pass; delta = rowsum(dO * O) itself
  is summed in the kernel from the o block (an fp32 ``[B, H, T, 1]`` array
  is stored 128 lanes a value: 134 MB a layer at GPT-2 355M) unless the
  caller brings one of its own (an lse cotangent, a ring's global o);
- in low-precision models the exp runs in the model dtype and dp - delta
  is emitted in the model dtype, so ds = p * dpd is a pure low-precision
  multiply; fp32 models keep fully-fp32 intermediates (parity tests pin
  this);
- matmul inputs stay in the model dtype (bf16) with fp32 MXU accumulation
  (preferred_element_type); softmax statistics and accumulators live in
  fp32 VMEM scratch across grid steps;
- in the backward, the 1/sqrt(d) factor on dq is applied to the [T, d]
  OUTPUT (dk/dv need no factor at all with pre-scaled q), never to the
  [T, T] ds matrix.

Forward: online-softmax accumulation over key/value blocks.
Backward: one fused pass (dq, dk, dv from one sweep, k/v resident in VMEM)
where the resident set fits, else the standard two-pass flash backward (one
kernel produces dq looping over kv blocks; one produces dk/dv looping over
q blocks); both use the saved per-row logsumexp; wired up with
jax.custom_vjp.

Off-TPU the kernels run in Pallas interpret mode, so the CPU test mesh
exercises the same code path (tests mirror reference
tests/unit/test_cuda_forward.py / test_cuda_backward.py grids).
"""

import collections
import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops import pallas_mode
from deepspeed_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

NEG_INF = -1e30
# The lanes of a tile. Also the width of the fp32 softmax-statistic scratch
# rows: Mosaic pads second-minor×minor tiles to (8, 128), so statistics are
# kept broadcast across a full 128-lane row instead of a width-1 column.
LANES = 128
_STATS_LANES = LANES


# ---------------------------------------------------------------------------
# Reference (pure jnp) implementation — ground truth for parity tests and
# fallback for shapes the kernel does not support.
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, mask=None, causal=False, scale=None,
                  return_lse=False, precision=None):
    """q,k,v: [B, H, T, D]; mask: additive [B, T_kv] (broadcast over heads
    and query rows, the BERT padding-mask shape). With return_lse, also
    returns the per-row logsumexp [B, H, T, 1] fp32 (the ragged fallback
    of flash_attention_with_lse shares this single dense implementation).

    precision: forwarded to the two einsums. When None, low-precision
    inputs keep the MXU DEFAULT (a single bf16-input pass — fast, and
    consistent with the recompute in ring attention's dense backward, so
    fwd/bwd rounding cancels) while fp32 inputs contract at HIGHEST: at
    DEFAULT the MXU rounds fp32 operands to bf16, which would make both
    the fp32 production fallback lossy and a parity oracle LESS accurate
    than the kernel under test (the kernel applies the same rule)."""
    if precision is None:
        precision = _mxu_precision(q.dtype)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=precision) * scale
    if mask is not None:
        s = s + mask[:, None, None, :].astype(jnp.float32)
    if causal:
        t_q, t_k = q.shape[2], k.shape[2]
        cm = jnp.tril(jnp.ones((t_q, t_k), dtype=bool))
        s = jnp.where(cm[None, None], s, NEG_INF)
    # Normalize by DIVISION, not exp(s - lse): at mask magnitudes (-1e9)
    # fp32 loses log-sum bits in lse (-1e9 + log2 rounds back to -1e9), so
    # exp(s - lse) silently denormalizes fully-masked rows. Division keeps
    # the row sum exact — the same stability structure as the flash kernel.
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", e / l, v.astype(jnp.float32),
                   precision=precision).astype(q.dtype)
    if return_lse:
        return o, m + jnp.log(l)
    return o


def _mask_operand(mask):
    """The [B, T_kv] additive padding mask as the fp32 [B, 1, T_kv] array
    the kernels take. Mosaic wants the last two dims of a block to tile by
    (8, 128) or equal the array's: a one-row window on the 2-D mask does
    neither (1 is not B), the same window on the 3-D form does."""
    return mask.astype(jnp.float32)[:, None, :]


def _mask_spec(block_k, kv_block):
    """BlockSpec for ``_mask_operand``: row b, key block ``kv_block(*grid
    indices)``. The batch dim is squeezed, so the kernel still sees a
    (1, block_k) ref."""
    return pl.BlockSpec((None, 1, block_k),
                        lambda b_, *idx: (b_, 0, kv_block(b_, *idx)))


def _last_kv_block(iq, block_q, block_k):
    """Index of the last key block a causal query block iq attends to."""
    return ((iq + 1) * block_q - 1) // block_k


def _first_q_block(jk, block_q, block_k):
    """Index of the first query block that attends to causal key block jk."""
    return (jk * block_k) // block_q


def _tril_block(block_q, block_k):
    """Constant additive causal mask for a diagonal block (bq == bk).
    Built from iota primitives (not a materialized array) so functions
    passing it stay const-free; XLA folds it to a constant anyway."""
    r = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(r >= c, jnp.float32(0.0), jnp.float32(NEG_INF))


def _is_lowp(dtype):
    return jnp.dtype(dtype) in (jnp.bfloat16, jnp.float16)


def _mxu_precision(dtype):
    """Dot precision for the kernel's MXU contractions, by model dtype.

    At DEFAULT precision the MXU rounds fp32 operands to bf16 — fine for
    low-precision models (operands already are bf16/fp16), but it silently
    costs fp32 models ~1e-2 parity now that the softmax row-sum and the
    `dp - delta` correction ride the matmuls (the denominator inherits p's
    operand rounding; seen live on v5e: 9e-3 fwd error vs a
    precision-highest oracle). fp32 is the parity/debug path, so it takes
    HIGHEST (multi-pass MXU, ~fp32-exact) and keeps the fusions."""
    return None if _is_lowp(dtype) else jax.lax.Precision.HIGHEST


def _exp_lowp(t, dtype):
    """exp over a [bq, bk] block — the widest VPU pass in the kernel.

    Low-precision models run the exp in the model dtype: half the vector
    elements per VPU op, and the result feeds the next matmul without a
    cast pass. Absolute error is ~p * |t| * 2^-8 <= e^-1 * 2^-8 relative
    to the row total — the same order as the fp32-exp-then-cast-to-bf16 it
    replaces. fp32 models keep the fp32 exp (parity tests pin 1e-4)."""
    if _is_lowp(dtype):
        return jnp.exp(t.astype(dtype))
    return jnp.exp(t)



# ---------------------------------------------------------------------------
# Two operand layouts, one body a direction
#
# HEAD-MAJOR ``[B, H, T, d]``: a grid step works one head; a block is
# ``(block, d)``. What ring / Ulysses attention, the BERT layer and the
# sequence-parallel branches are written over.
#
# PACKED ``[B, T, tiles x g x d]``, the layout a projection EMITS: a grid
# step works the ``g = lane_pack(d, H)`` heads that share one 128-lane tile
# (two of 64: GPT-2), a block is ``(block, g x d)``, lane-dense in HBM and
# in VMEM. Nothing is transposed on the way in or out, forward or backward
# (the head split's eight transposes a layer were a tenth of a GPT-2 355M
# training step, PR 49). The packed operand is either three arrays (q, k,
# v) or ONE, the fused projection's output with its columns arranged a
# tile at a time, ``[B, T, tiles, 3, g x d]`` (``tile_qkv`` arranges a
# ``[.., 3 H d]`` axis so: the model applies it to c_attn's WEIGHT, 6 MB,
# never to an activation); the backward then writes one ``dqkv`` in the
# same arrangement, which is what the projection's own backward consumes.
# An ``H`` that ``g`` does not divide (GPT-2 XL: 25 heads, 12.5 tiles)
# leaves the last tile half dead: its dead lanes may hold anything (they
# are SELECTED away, never multiplied) and its dead head is not computed.
#
# The kernels' bodies see 2-D blocks ``[rows, lanes]`` in either layout
# (the leading block dims are squeezed) and loop over the ``g`` heads of
# the tile. How head ``a`` is parted from its neighbours costs no MXU
# time: an operand with the OTHER heads' lanes selected to zero makes the
# product a 128-deep contraction (the head-major 64 uses half the MXU's
# depth) or a 128-wide output of which ``a``'s lanes are kept, and the
# softmax row-sum and the ``-delta`` that ride the PV and dp products
# (``_pv_rowsum``, ``_dp_minus_delta_of``) ride in another head's lanes,
# set to one, so no product grows past the tile. Masks are ``[rows, 128]``
# passes, never ``[T, T]``. With g = 1 nothing is selected and the body is
# the head-major one to the bit.
# ---------------------------------------------------------------------------

class _Layout(collections.namedtuple(
        "_Layout", "packed g d heads tiles fused")):
    """How a launch's operands hold their heads (static, hashable):
    ``packed`` False is head-major (g 1, ``tiles`` = H); else ``g`` heads
    of ``d`` a tile, ``tiles`` tiles, ``heads`` of them live, ``fused``
    where q, k, v are one tile-arranged array."""

    @property
    def lanes(self):
        return self.g * self.d

    @property
    def ragged(self):
        return self.packed and self.heads % self.g != 0


def lane_pack(d, h):
    """g: of ``h`` heads of width ``d``, how many share one lane tile in the
    packed layout; 0 where the layout cannot hold them (a ``d`` that
    neither divides a tile nor is whole tiles, or fewer heads than a tile
    takes: the block's minor dim must be whole tiles on the chip). The
    serving arena's rule (``decode_attention.lane_pack``) for the shapes
    both can take."""
    if d % LANES == 0:
        return 1
    return LANES // d if LANES % d == 0 and h >= LANES // d else 0


def tile_qkv(x, heads, d):
    """``[..., 3 x heads x d]`` (q | k | v, a head after a head: what
    ``c_attn`` emits and how its kernel's columns and its bias lie) as the
    fused packed operand, ``[..., tiles x 3 x g x d]``: tile p's q, k and v
    lanes side by side, zero heads appended where ``g`` does not divide
    ``heads``. Linear, so autodiff gives its inverse."""
    g = lane_pack(d, heads)
    tiles = -(-heads // g)
    lead = x.shape[:-1]
    x = x.reshape(lead + (3, heads, d))
    if tiles * g != heads:
        x = jnp.pad(x, [(0, 0)] * (len(lead) + 1)
                    + [(0, tiles * g - heads), (0, 0)])
    x = x.reshape(lead + (3, tiles, g * d))
    return jnp.swapaxes(x, -3, -2).reshape(lead + (tiles * 3 * g * d,))


def _resolve_layout(q, k, heads, head_dim):
    """The launch's ``_Layout`` from the operands' rank and shape (and, for
    a packed operand, the heads it holds)."""
    if q.ndim == 4:
        return _Layout(False, 1, q.shape[3], q.shape[1], q.shape[1], False)
    fused = k is None
    lanes = q.shape[2] // (3 if fused else 1)
    d = head_dim or lanes // heads
    g = lane_pack(d, heads)
    tiles = -(-heads // g) if g else 0
    if not g or tiles * g * d != lanes:
        raise ValueError(
            "a packed operand of {} lanes cannot hold {} heads of {}".format(
                lanes, heads, d))
    return _Layout(True, g, d, heads, tiles, fused)


def _mine(shape, a, lay):
    """[rows, lanes] of bools: the lanes of head ``a`` of the tile."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane >= a * lay.d) & (lane < (a + 1) * lay.d)


def _own(x, a, lay, other=0):
    """``x`` ``[rows, lanes]`` with the lanes of every head but ``a``
    selected to ``other``; ``x`` itself where a tile holds one head."""
    if lay.g == 1:
        return x
    return jnp.where(_mine(x.shape, a, lay), x, jnp.asarray(other, x.dtype))


def _spare_lane(a, lay):
    """The first lane of the head after ``a`` in the tile: where what rides
    a product of head ``a`` sits."""
    return ((a + 1) % lay.g) * lay.d


def _put(ref, rows, value, a, lay, whole=False):
    """Head ``a``'s lanes of ``value`` into ``ref[rows]``. ``whole``: the
    other lanes may go too (a block's first writer, where every other
    lane is written again or is dead)."""
    if lay.g == 1 or whole:
        ref[rows] = value
    else:
        ref[rows] = jnp.where(_mine(value.shape, a, lay), value, ref[rows])


def _live_heads(lay):
    """How many heads of this grid step's tile are live: ``g`` but in a
    ragged layout's last tile (None where no tile is ragged). Read at the
    kernel's top: the interpreter resolves ``program_id`` only there."""
    return lay.heads - pl.program_id(1) * lay.g if lay.ragged else None


def _for_live_heads(lay, live, fn):
    """``fn(a)`` for each head of the tile, a dead one skipped."""
    for a in range(lay.g):
        if live is not None and a:
            pl.when(a < live)(functools.partial(fn, a))
        else:
            fn(a)


def _clean_dead_lanes(lay, live, *refs):
    """Zero, IN the blocks the pipeline brought, the lanes of a ragged
    tile's dead heads: a live head's products contract over the whole tile
    against them (its own operand is zero there, and 0 x NaN is NaN)."""
    if live is None:
        return

    @pl.when(live < lay.g)
    def _clean():
        for ref in refs:
            lane = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 1)
            ref[...] = jnp.where(lane < live * lay.d, ref[...],
                                 jnp.zeros_like(ref[...]))


def _scaled(q, scale):
    """q x 1/sqrt(d) on the [rows, lanes] block: never a [T, T] pass."""
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _pv_rowsum(p, v_blk, a=0, lay=None):
    """p @ [v | 1] on the MXU: one matmul returns both the context block
    [bq, d] and the softmax row-sum [bq, 1], deleting a VPU reduce over
    [bq, bk]. The row-sum shares p's rounding with the context numerator,
    so o = pv / l normalizes exactly the values it summed. In a packed
    tile the ones are the other heads' lanes of v: the context comes back
    in head ``a``'s lanes of a [bq, lanes] block, the row-sum in every
    other lane."""
    if lay is not None and lay.g > 1:
        pv_ext = jax.lax.dot_general(
            p.astype(v_blk.dtype), _own(v_blk, a, lay, 1),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=_mxu_precision(v_blk.dtype))
        spare = _spare_lane(a, lay)
        return pv_ext, pv_ext[:, spare:spare + 1]
    d = v_blk.shape[1]
    v_ext = jnp.concatenate(
        [v_blk, jnp.ones((v_blk.shape[0], 1), v_blk.dtype)], axis=1)
    pv_ext = jax.lax.dot_general(p.astype(v_blk.dtype), v_ext,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_mxu_precision(v_blk.dtype))
    return pv_ext[:, :d], pv_ext[:, d:d + 1]


def _dp_minus_delta_of(do, delta, dtype, a=0, lay=None):
    """``v_blk -> dp - delta`` for one block of query rows:
    [dO | -delta] @ [V | 1]^T on the MXU, so that the delta subtraction
    rides the dp matmul (fp32 accumulation inside the MXU) instead of
    costing a VPU pass over the scores. The left operand is built HERE,
    once for the rows; the returned function is called a run of keys.
    Low-precision models split the fp32 delta into
    hi+lo model-dtype COLUMNS (~16 mantissa bits through the MXU): rows
    with concentrated attention have dp ~ delta and p ~ 1, so a single
    bf16 delta column's 2^-8 rounding would surface at full scale in
    ds = p * (dp - delta). The output is emitted in the model dtype — its
    rounding is relative to the (small) difference, not to delta — making
    ds a pure low-precision multiply.

    Only bf16 takes the fused columns: bf16 shares fp32's exponent range,
    so the delta split never overflows. fp16 does NOT — under dynamic loss
    scaling delta = rowsum(dO * O) routinely exceeds fp16 max (65504) even
    when every dO element fits, and an inf hi column would turn the MXU
    accumulation into NaN — so fp16 keeps the classic fp32 subtract. fp32
    models ride an exact fp32 delta column (exact parity).

    In a packed tile (``do`` [rows, lanes], head ``a``) the columns sit in
    the next head's first lanes, the rest of the other lanes zero, against
    v with the other heads' lanes selected to ONE."""
    dims = (((1,), (1,)), ((), ()))
    packed = lay is not None and lay.g > 1
    bf16 = jnp.dtype(dtype) == jnp.bfloat16
    cols = []                     # what rides, a column each
    if bf16:
        d_hi = delta.astype(dtype)
        cols = [-d_hi, -(delta - d_hi.astype(jnp.float32)).astype(dtype)]
    elif not _is_lowp(dtype):
        cols = [(-delta).astype(dtype)]
    prec = None if bf16 else _mxu_precision(dtype)

    if packed:
        do_ext = _own(do.astype(dtype), a, lay)
        lane = jax.lax.broadcasted_iota(jnp.int32, do_ext.shape, 1)
        for n, col in enumerate(cols):
            do_ext = jnp.where(lane == _spare_lane(a, lay) + n, col, do_ext)

        def v_ext(v_blk):
            return _own(v_blk, a, lay, 1 if cols else 0)
    else:
        do_ext = jnp.concatenate([do.astype(dtype)] + cols, axis=1) \
            if cols else do.astype(dtype)

        def v_ext(v_blk):
            if not cols:
                return v_blk
            return jnp.concatenate(
                [v_blk, jnp.ones((v_blk.shape[0], len(cols)), dtype)], axis=1)

    def dp_minus_delta(v_blk):
        # Mosaic requires the MXU accumulator to be 32-bit (a bf16
        # preferred_element_type fails verification), so accumulate in
        # fp32 and cast on emit — same rounding contract: the cast error
        # is relative to the small difference, not to delta.
        dpd = jax.lax.dot_general(do_ext, v_ext(v_blk), dims,
                                  preferred_element_type=jnp.float32,
                                  precision=prec)
        if bf16:
            return dpd.astype(dtype)
        return dpd if cols else dpd - delta  # fp16: overflow-safe subtract
    return dp_minus_delta


def _dp_minus_delta(do, v_blk, delta, a=0, lay=None):
    """dp - delta for one (query block, key block) pair: see
    ``_dp_minus_delta_of``."""
    return _dp_minus_delta_of(do, delta, v_blk.dtype, a, lay)(v_blk)


def _apply_causal(s, iq, j, block_q, block_k, tril_ref):
    """Apply the causal mask to score block (iq, j). With bq == bk only the
    diagonal block straddles the boundary, so the constant tril input is
    added under @pl.when; otherwise fall back to the iota ladder."""
    if tril_ref is not None:
        if isinstance(iq, int) and isinstance(j, int):  # one block each way
            return s + tril_ref[...] if iq == j else s
        return jax.lax.cond(iq == j, lambda: s + tril_ref[...], lambda: s)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)



# ---------------------------------------------------------------------------
# Strips inside a diagonal grid block
#
# A grid block is what ``resolve_block_sizes`` gives: the whole 1024 x 1024
# of a GPT-2 head, ONE grid step (a grid step costs a tenth of a head's
# work at T 1024). The block that holds the causal diagonal is taken in
# STRIPS of S rows (``flash_subtile``): strip r attends the block's keys
# ``[0, (r + 1) S)`` and no further, with one direct softmax over that
# width, the constant ``tril`` added to its last S columns alone. Of the
# block's n x n sub-tiles of side S, n (n + 1) / 2 are computed; the ones
# above the diagonal are never formed. The strips are straight-line code,
# NOT a loop over tiles: a loop iteration is a chain (product, row max,
# exp, product) that cannot overlap the next, and on v5e it cost 0.3 us
# whatever the tile held, a tenth of a head's whole forward (CHANGES.md,
# PR 45: the walk as ``fori_loop``s ran 5.7 / 11.8 us a head, forward /
# backward, at S 256 where the whole square takes 3.0 / 8.6). And the
# strips' three stages (the products, the pointwise pass, the products
# that consume it) are WRITTEN SKEWED, strip r's first products beside
# strip r - 1's pointwise pass and strip r - 2's last products:
# neighbours in program order that need different units and nothing of
# each other, which is as far as the scheduler looks (strip after strip in
# a line ran 3.07 / 5.47 us at S 128; skewed 2.23 / 4.70).
# The forward and the fused backward share the rule; the split backward
# kernels keep the grid's own blocks.
# ---------------------------------------------------------------------------

# The rows of a strip. Timed on v5e at the training cells' shapes, the
# kernels alone (``tests/perf/attention_bench.py --subtile``; CHANGES.md,
# PR 45): a head's forward / fused backward 2.23 / 4.70 us at 128 (36 of
# the 64 tiles), 2.24 / 5.25 at 256 (10 of 16), 2.45 / 6.36 at 512 (3 of
# 4), 3.04 / 8.6 with the block its own tile.
_SUBTILE_SIDE = 128

# What the rule resolved for the last call traced: gauges ``flash_subtile``
# and ``flash_tiles_visited_share`` (runtime/engine.py).
_last_walk = {"subtile": 0, "tiles_visited_share": 0.0, "lane_pack": 0}


def flash_subtile(block_q, block_k, causal):
    """S, the rows of a strip (the side of the sub-tiles) a diagonal grid
    block is taken in; 0 where the block is its own tile (no causal mask:
    nothing to skip; a block that is not square, that S does not divide, or
    that is no larger than S). From the shapes alone: no switch, no table."""
    s = _SUBTILE_SIDE
    return s if causal and block_q == block_k and block_q % s == 0 \
        and block_q > s else 0


def tiles_visited(t_q, t_kv, sub_q, sub_k, causal):
    """(visited, all) tiles of ``sub_q`` x ``sub_k`` in a call's
    [t_q, t_kv] scores."""
    n_r, n_c = t_q // sub_q, t_kv // sub_k
    if not causal:
        return n_r * n_c, n_r * n_c
    return sum(min(_last_kv_block(r, sub_q, sub_k) + 1, n_c)
               for r in range(n_r)), n_r * n_c


def last_walk():
    """{"subtile": S, "tiles_visited_share": visited / all} as the
    launcher resolved them for the last flash call traced."""
    return dict(_last_walk)


def _ds(i, size, width=None):
    """``[i * size, i * size + width)`` of a ref (``width`` = ``size``
    where not given)."""
    start = i * size if isinstance(i, int) else \
        pl.multiple_of(i * size, size)
    return pl.ds(start, width or size)


def _loop(n, body, init):
    """``fori_loop(0, n, body, init)``; a trip count that is the Python
    number 1 is no loop."""
    if isinstance(n, int) and n == 1:
        return body(0, init)
    return jax.lax.fori_loop(0, n, body, init)


def _skewed(n, *stages):
    """``stages[0](t)``, ``stages[1](t)``, ... for t in ``range(n)``, each t
    in stage order, written so that item t's first stage stands beside item
    t - 1's second and item t - 2's third (see above)."""
    for t in range(n + len(stages) - 1):
        for lag, stage in enumerate(stages):
            if 0 <= t - lag < n:
                stage(t - lag)


def _mask_scores(s, kind, tril_ref, iq, j, block_q, block_k):
    """What the causal mask does to the scores ``s`` of grid block
    (iq, j), or of a strip of it. ``kind``: None (every score is live);
    "tril" (a strip that ENDS on the diagonal: the constant is added to its
    last columns); "block" (a whole block, wherever it lies: the constant
    where it is the diagonal one, the ladder where blocks are not square)."""
    if kind == "block":
        return _apply_causal(s, iq, j, block_q, block_k, tril_ref)
    if kind == "tril":
        side = tril_ref.shape[0]
        if s.shape[1] == side:
            return s + tril_ref[...]
        return jnp.concatenate(
            [s[:, :-side], s[:, -side:] + tril_ref[...]], axis=1)
    return s



# ---------------------------------------------------------------------------
# Block specs of the two layouts
# ---------------------------------------------------------------------------

def _rows_spec(lay, rows, row_index, part=0):
    """BlockSpec of ``rows`` rows of one head (head-major ``[B, H, T, d]``)
    or of one lane tile (packed ``[B, T, lanes]``), the leading dims
    squeezed: the kernel sees ``[rows, lanes]``. ``row_index(*grid
    indices after batch and head/tile)`` is the row block; ``part`` is 0, 1
    or 2 for q, k or v of a fused array (whose tile p holds them side by
    side) and is ignored elsewhere."""
    if not lay.packed:
        return pl.BlockSpec((None, None, rows, lay.d),
                            lambda b_, h_, *ij: (b_, h_, row_index(*ij), 0))
    stride, off = (3, part) if lay.fused else (1, 0)
    return pl.BlockSpec(
        (None, rows, lay.lanes),
        lambda b_, p_, *ij: (b_, row_index(*ij), p_ * stride + off))


def _o_spec(lay, rows, row_index):
    """``_rows_spec`` for o / dO, which are never part of a fused array."""
    return _rows_spec(lay._replace(fused=False), rows, row_index)


def _stats_spec(lay, rows, row_index):
    """BlockSpec of the fp32 row statistics (lse, delta), ``[B, tiles x g,
    T, 1]`` in either layout: the kernel sees ``[g, rows, 1]``."""
    return pl.BlockSpec((None, lay.g, rows, 1),
                        lambda b_, p_, *ij: (b_, p_, row_index(*ij), 0))


def _o_shape(lay, b, t):
    """The shape of o (of q, were it no part of a fused array)."""
    return (b, t, lay.tiles * lay.lanes) if lay.packed else \
        (b, lay.tiles, t, lay.d)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, block_q, block_k, sub, lay, has_mask,
                has_tril, single_q, single_kv):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]               # [rows, lanes] each
    idx = 3
    mask_ref = tril_ref = None
    if has_mask:
        mask_ref = refs[idx]
        idx += 1
    if has_tril:
        tril_ref = refs[idx]
        idx += 1
    o_ref, lse_ref = refs[idx:idx + 2]           # [rows, lanes], [g, rows, 1]
    scratch = refs[idx + 2:]

    iq = 0 if single_q else pl.program_id(2)
    j = 0 if single_kv else pl.program_id(3)
    n_kv = pl.num_programs(3)
    prec = _mxu_precision(q_ref.dtype)
    live = _live_heads(lay)
    _clean_dead_lanes(lay, live, k_ref)

    # Head ``a`` of the tile: query rows ``rows`` over keys ``keys`` of the
    # block, in three stages that hand each other values. One kv block: a
    # direct softmax, no scratch, no rescale passes. Else the online-softmax
    # update of the rows' statistics.
    def head(a):
        def scores(rows, keys, kind):
            s = jax.lax.dot_general(
                _own(_scaled(q_ref[rows], scale), a, lay), k_ref[keys],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
            if mask_ref is not None:
                s = s + mask_ref[:, keys]
            return _mask_scores(s, kind, tril_ref, iq, j, block_q, block_k)

        def softmax(rows, s):
            m = jnp.max(s, axis=-1, keepdims=True)
            if not single_kv:
                m = jnp.maximum(scratch[1][a, rows, 0:1], m)    # [rows, 1]
            return m, _exp_lowp(s - m, o_ref.dtype)             # [rows, keys]

        def output(rows, keys, m, p):
            pv, l = _pv_rowsum(p, v_ref[keys], a, lay)
            if single_kv:
                l = jnp.maximum(l, 1e-30)
                _put(o_ref, rows, (pv / l).astype(o_ref.dtype), a, lay,
                     whole=a == 0)
                lse_ref[a, rows] = m + jnp.log(l)
                return
            acc, m_s, l_s = scratch
            alpha = jnp.exp(m_s[a, rows, 0:1] - m)
            l_s[a, rows] = jnp.broadcast_to(alpha * l_s[a, rows, 0:1] + l,
                                            (rows.size, _STATS_LANES))
            m_s[a, rows] = jnp.broadcast_to(m, (rows.size, _STATS_LANES))
            _put(acc, rows, acc[rows] * alpha + pv, a, lay)

        def attend(rows, keys, kind):
            output(rows, keys, *softmax(rows, scores(rows, keys, kind)))

        whole = pl.ds(0, block_q), pl.ds(0, block_k)

        def strips():
            n = block_q // sub
            span = [(pl.ds(r * sub, sub), pl.ds(0, (r + 1) * sub))
                    for r in range(n)]
            held = {}

            def first(r):
                held[r] = scores(*span[r], "tril")

            def second(r):
                held[r] = softmax(span[r][0], held[r])

            def third(r):
                output(*span[r], *held.pop(r))

            _skewed(n, first, second, third)

        if not causal:
            attend(*whole, None)
        elif not sub:
            attend(*whole, "block")
        elif single_q and single_kv:
            strips()
        else:
            pl.when(iq == j)(strips)
            pl.when(j < iq)(lambda: attend(*whole, None))

    def compute():
        _for_live_heads(lay, live, head)

    if single_kv:
        compute()
        return

    acc, m_s, l_s = scratch

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    if causal:
        active = j <= _last_kv_block(iq, block_q, block_k)
    else:
        active = j < n_kv
    pl.when(active)(compute)

    @pl.when(j == n_kv - 1)
    def _finalize():
        def emit(a):
            l = jnp.maximum(l_s[a, :, 0:1], 1e-30)
            _put(o_ref, pl.ds(0, block_q), (acc[...] / l).astype(o_ref.dtype),
                 a, lay, whole=a == 0)
            lse_ref[a] = m_s[a, :, 0:1] + jnp.log(l)

        _for_live_heads(lay, live, emit)


def _walk(lay, t_q, t_kv, block_q, block_k, causal):
    """S for the call, recorded with what else the launcher resolved
    (``last_walk``)."""
    sub = flash_subtile(block_q, block_k, causal)
    visited, tiles = tiles_visited(t_q, t_kv, sub or block_q, sub or block_k,
                                   causal)
    _last_walk.update(subtile=sub, tiles_visited_share=visited / tiles,
                      lane_pack=lay.g if lay.packed else 0)
    return sub


def _flash_fwd_pallas(q, k, v, mask, scale, causal, block_q, block_k,
                      heads=None, head_dim=None):
    """(o, lse) of one launch. q, k, v head-major ``[B, H, T, d]``, or
    packed (``heads`` given): three ``[B, T, lanes]`` arrays, or one fused
    tile-arranged ``[B, T, 3 x lanes]`` with k and v None. o comes in q's
    layout, lse ``[B, tiles x g, T, 1]`` fp32 (a dead head's rows are
    never written)."""
    lay = _resolve_layout(q, k, heads, head_dim)
    t_q = q.shape[-2]
    t_kv = t_q if k is None else k.shape[-2]
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_kv)
    sub = _walk(lay, t_q, t_kv, block_q, block_k, causal)
    if lay.fused:
        k = v = q
    return _flash_fwd_launch(q, k, v, mask, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k, sub=sub,
                             lay=lay)


# The launches are ``pallas_mode.shared_launch``es, so that a model's layers
# share ONE trace and ONE lowering of a kernel: a GPT-2's 24 (48) layers are
# unrolled, and the kernel's body (a strip is two dozen operations, a block
# several strips, a tile two heads) was traced anew at every call site,
# forward and backward, on every run: seconds of a training cell's set-up.
# Everything the trace depends on beside the operands is a static argument;
# nothing inside reads the environment or a module global.
@pallas_mode.shared_launch("scale", "causal", "block_q", "block_k", "sub",
                           "lay")
def _flash_fwd_launch(q, k, v, mask, *, scale, causal, block_q, block_k, sub,
                      lay):
    from jax.experimental.pallas import tpu as pltpu

    b, t_q, t_kv = q.shape[0], q.shape[-2], k.shape[-2]
    n_q = pl.cdiv(t_q, block_q)
    n_kv = pl.cdiv(t_kv, block_k)
    grid = (b, lay.tiles, n_q, n_kv)
    use_tril = causal and block_q == block_k
    single_kv = n_kv == 1

    if causal:
        def kv_block(i, j):
            # Clamp past-diagonal blocks to the last useful one: a repeated
            # block index issues no new DMA, and @pl.when skips the compute.
            return jnp.minimum(j, _last_kv_block(i, block_q, block_k))
    else:
        def kv_block(i, j):
            return j

    def q_block(i, j):
        return i

    in_specs = [
        _rows_spec(lay, block_q, q_block, 0),
        _rows_spec(lay, block_k, kv_block, 1),
        _rows_spec(lay, block_k, kv_block, 2),
    ]
    args = [q, k, v]
    if mask is not None:
        in_specs.append(_mask_spec(
            block_k, lambda b_, h_, i, j: kv_block(i, j)))
        args.append(_mask_operand(mask))
    if use_tril:
        side = sub or block_q
        in_specs.append(
            pl.BlockSpec((side, side), lambda b_, h_, i, j: (0, 0)))
        args.append(_tril_block(side, side))

    o, lse = pallas_mode.kernel_call(
        "flash_fwd",
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, sub=sub, lay=lay,
                          has_mask=mask is not None, has_tril=use_tril,
                          single_q=n_q == 1, single_kv=single_kv),
        grid=grid,
        in_specs=in_specs,
        out_specs=[_o_spec(lay, block_q, q_block),
                   _stats_spec(lay, block_q, q_block)],
        out_shape=[
            jax.ShapeDtypeStruct(_o_shape(lay, b, t_q), q.dtype),
            jax.ShapeDtypeStruct((b, lay.tiles * lay.g, t_q, 1), jnp.float32),
        ],
        scratch_shapes=[] if single_kv else [
            pltpu.VMEM((block_q, lay.lanes), jnp.float32),
            pltpu.VMEM((lay.g, block_q, _STATS_LANES), jnp.float32),
            pltpu.VMEM((lay.g, block_q, _STATS_LANES), jnp.float32),
        ],
    )(*args)
    return o, lse

# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------
# delta_i = rowsum(dO_i * O_i); then with q_s = q/sqrt(d):
#   s = q_s K^T,  P = exp(s - lse),  dP = dO V^T,  dS = P * (dP - delta)
#   dq = (dS K) / sqrt(d),  dk = dS^T q_s,  dv = P^T dO
# P is recomputed blockwise from q_s, k and the saved lse (never stored).
# q is scaled on its [rows, lanes] block as the forward scales it (so the
# recomputed P matches the saved lse); dk needs no correction, dq is
# rescaled on its output.

def _row_delta_of(do, of_ref, rows, a, lay, has_delta):
    """delta = rowsum(dO * O) of head ``a``'s ``rows``, [rows, 1] fp32:
    read where the caller brought it (``of_ref`` the [g, rows, 1]
    statistic: an lse cotangent or a ring's global o are folded in
    there), else summed HERE from the o block over the head's own lanes,
    which costs no pass over HBM and no array of row statistics."""
    if has_delta:
        return of_ref[a, rows]
    prod = do.astype(jnp.float32) * of_ref[rows].astype(jnp.float32)
    return jnp.sum(_own(prod, a, lay), axis=-1, keepdims=True)


def _bwd_unpack(refs, has_mask, has_tril, n_out):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    idx = 3
    mask_ref = tril_ref = None
    if has_mask:
        mask_ref = refs[idx]
        idx += 1
    if has_tril:
        tril_ref = refs[idx]
        idx += 1
    do_ref, lse_ref, delta_ref = refs[idx:idx + 3]
    idx += 3
    outs = refs[idx:idx + n_out]
    scratch = refs[idx + n_out:]
    return (q_ref, k_ref, v_ref, mask_ref, tril_ref, do_ref, lse_ref,
            delta_ref, outs, scratch)


def _bwd_scores(q_a, k_ref, mask_ref, tril_ref, iq, j, causal,
                block_q, block_k):
    s = jax.lax.dot_general(q_a, k_ref[...],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=_mxu_precision(q_a.dtype))
    if mask_ref is not None:
        s = s + mask_ref[0][None, :]
    if causal:
        s = _apply_causal(s, iq, j, block_q, block_k, tril_ref)
    return s


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, lay, has_mask,
                   has_tril, has_delta, single_kv):
    (q_ref, k_ref, v_ref, mask_ref, tril_ref, do_ref, lse_ref, delta_ref,
     (dq_ref,), scratch) = _bwd_unpack(refs, has_mask, has_tril, 1)

    iq = pl.program_id(2)
    j = pl.program_id(3)
    n_kv = pl.num_programs(3)
    live = _live_heads(lay)
    _clean_dead_lanes(lay, live, k_ref)

    def ds_block(a):
        s = _bwd_scores(_own(_scaled(q_ref[...], scale), a, lay), k_ref,
                        mask_ref, tril_ref, iq, j, causal, block_q, block_k)
        # s <= lse mathematically; clamping guards fully-masked rows where
        # fp32 lse (~mask magnitude, ulp 64) loses the log-sum bits and a
        # spurious positive exponent would poison the step with inf grads.
        p = _exp_lowp(jnp.minimum(s - lse_ref[a], 0.0), dq_ref.dtype)
        do = do_ref[...]
        dpd = _dp_minus_delta(
            do, v_ref[...],
            _row_delta_of(do, delta_ref, pl.ds(0, block_q), a, lay,
                          has_delta), a, lay)
        ds = (p * dpd).astype(k_ref.dtype)
        return jax.lax.dot_general(ds, k_ref[...], (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32,
                                   precision=_mxu_precision(k_ref.dtype))

    whole = pl.ds(0, block_q)
    if single_kv:
        _for_live_heads(lay, live, lambda a: _put(
            dq_ref, whole, (ds_block(a) * scale).astype(dq_ref.dtype), a,
            lay, whole=a == 0))
        return

    (dq_acc,) = scratch

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if causal:
        active = j <= _last_kv_block(iq, block_q, block_k)
    else:
        active = j < n_kv

    @pl.when(active)
    def _compute():
        _for_live_heads(lay, live, lambda a: _put(
            dq_acc, whole, dq_acc[...] + ds_block(a), a, lay))

    @pl.when(j == n_kv - 1)
    def _finalize():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, lay, has_mask,
                    has_tril, has_delta, single_q):
    (q_ref, k_ref, v_ref, mask_ref, tril_ref, do_ref, lse_ref, delta_ref,
     (dk_ref, dv_ref), scratch) = _bwd_unpack(refs, has_mask, has_tril, 2)

    jk = pl.program_id(2)
    i = pl.program_id(3)
    n_q = pl.num_programs(3)
    live = _live_heads(lay)
    _clean_dead_lanes(lay, live, k_ref)

    def grads_block(a):
        """Head ``a``'s (dk, dv) of the pair, zero in the other heads'
        lanes (q and dO are selected so)."""
        q_a = _own(_scaled(q_ref[...], scale), a, lay)
        s = _bwd_scores(q_a, k_ref, mask_ref, tril_ref, i, jk, causal,
                        block_q, block_k)
        # s <= lse mathematically; clamping guards fully-masked rows where
        # fp32 lse (~mask magnitude, ulp 64) loses the log-sum bits and a
        # spurious positive exponent would poison the step with inf grads.
        p = _exp_lowp(jnp.minimum(s - lse_ref[a], 0.0), dk_ref.dtype)
        do = do_ref[...]
        dv = jax.lax.dot_general(p.astype(do.dtype), _own(do, a, lay),
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_mxu_precision(do.dtype))
        dpd = _dp_minus_delta(
            do, v_ref[...],
            _row_delta_of(do, delta_ref, pl.ds(0, block_q), a, lay,
                          has_delta), a, lay)
        ds = (p * dpd).astype(q_ref.dtype)
        dk = jax.lax.dot_general(ds, q_a, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_mxu_precision(q_ref.dtype))
        return dk, dv

    whole = pl.ds(0, block_k)

    if single_q:
        def write(a):
            dk, dv = grads_block(a)
            _put(dk_ref, whole, dk.astype(dk_ref.dtype), a, lay,
                 whole=a == 0)
            _put(dv_ref, whole, dv.astype(dv_ref.dtype), a, lay,
                 whole=a == 0)

        if causal:
            # A kv block entirely past the query extent (t_kv > t_q) gets
            # no probability mass — the diagonal tril only covers i == jk,
            # so these blocks must be zeroed explicitly (the multi-block
            # path's `active` guard; verified by the t_q<t_kv grad test).
            active = i >= _first_q_block(jk, block_q, block_k)
            pl.when(active)(lambda: _for_live_heads(lay, live, write))

            @pl.when(jnp.logical_not(active))
            def _zero():
                dk_ref[...] = jnp.zeros_like(dk_ref)
                dv_ref[...] = jnp.zeros_like(dv_ref)
        else:
            _for_live_heads(lay, live, write)
        return

    dk_acc, dv_acc = scratch

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if causal:
        active = i >= _first_q_block(jk, block_q, block_k)
    else:
        active = i < n_q

    @pl.when(active)
    def _compute():
        def add(a):
            dk, dv = grads_block(a)
            dk_acc[...] += dk
            dv_acc[...] += dv

        _for_live_heads(lay, live, add)

    @pl.when(i == n_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(*refs, scale, causal, block_q, block_k, sub, lay,
                      has_mask, has_tril, has_delta, single_q, diag_always):
    """One-pass backward: dq, dk, dv from a single sweep over (i, j) block
    pairs. The split kernels each recompute s, p and dO.V^T per pair —
    7 score-sized matmuls + 2 exp passes per pair total; this kernel does
    5 matmuls + 1 exp (the MXU-ideal count), with k/v resident in VMEM per
    (b, head or tile) and full-length fp32 dk/dv accumulators in scratch.
    It also reads k and v from HBM once per (b, head or tile) instead of
    once per q block. With ``sub`` the query block is taken in strips of
    ``sub`` rows (see "Strips inside a diagonal grid block"): a strip meets
    the key blocks before the diagonal one whole, and of the diagonal one
    the keys up to its own diagonal tile. What is live at once is a
    strip's scores, not the block's. A fused packed operand's gradient is
    ONE output, ``dqkv`` ``[T, 3 x lanes]`` a tile (dq | dk | dv), resident
    over the query blocks."""
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    idx = 3
    mask_ref = tril_ref = None
    if has_mask:
        mask_ref = refs[idx]
        idx += 1
    if has_tril:
        tril_ref = refs[idx]
        idx += 1
    do_ref, lse_ref, delta_ref = refs[idx:idx + 3]
    idx += 3

    i = 0 if single_q else pl.program_id(2)
    n_q = pl.num_programs(2)
    n_kv = k_ref.shape[0] // block_k
    w = lay.lanes
    prec = _mxu_precision(q_ref.dtype)

    if lay.fused:
        dqkv_ref, dk_acc, dv_acc = refs[idx:idx + 3]
        dq_out, out_dtype = dqkv_ref, dqkv_ref.dtype   # dq: its first lanes
        row0 = i * block_q if single_q else pl.multiple_of(i * block_q,
                                                            block_q)

        def dq_rows(rows):
            return (pl.ds(row0 + rows.start, rows.size), pl.ds(0, w))

        def emit_dkv():
            dqkv_ref[:, w:2 * w] = dk_acc[...].astype(out_dtype)
            dqkv_ref[:, 2 * w:] = dv_acc[...].astype(out_dtype)
    else:
        dq_out, dk_ref, dv_ref, dk_acc, dv_acc = refs[idx:idx + 5]
        out_dtype = dq_out.dtype

        def dq_rows(rows):
            return rows

        def emit_dkv():
            dk_ref[...] = dk_acc[...].astype(out_dtype)
            dv_ref[...] = dv_acc[...].astype(out_dtype)

    live = _live_heads(lay)
    _clean_dead_lanes(lay, live, k_ref)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def head(a):
        def stages(rows):
            """The work of the block's query rows ``rows`` against a run
            of keys, as three stages that hand each other values:
            ``products`` (scores and dp - delta, the MXU), ``pointwise``
            (p and ds, the VPU) and ``grads`` (dv and dk into the
            accumulators, dq onto what it is given; the MXU again). q and
            dO are head ``a``'s alone (the others' lanes zero), so dv and
            dk land in its lanes of the accumulators."""
            q_blk = _own(_scaled(q_ref[rows], scale), a, lay)
            do = do_ref[rows]
            do_blk = _own(do, a, lay)
            lse_blk = lse_ref[a, rows]
            dp_minus_delta = _dp_minus_delta_of(
                do, _row_delta_of(do, delta_ref, rows, a, lay, has_delta),
                v_ref.dtype, a, lay)

            def products(keys, kind, j):
                s = jax.lax.dot_general(q_blk, k_ref[keys],
                                        (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32,
                                        precision=prec)
                if mask_ref is not None:
                    s = s + mask_ref[:, keys]
                return (_mask_scores(s, kind, tril_ref, i, j, block_q,
                                     block_k),
                        dp_minus_delta(v_ref[keys]))

            def pointwise(s, dpd):
                # s <= lse mathematically; the clamp guards fully-masked
                # rows (same contract as the split kernels).
                p = _exp_lowp(jnp.minimum(s - lse_blk, 0.0), out_dtype)
                return p.astype(do_blk.dtype), (p * dpd).astype(k_ref.dtype)

            def grads(keys, p, ds, dq):
                dv_acc[keys] += jax.lax.dot_general(
                    p, do_blk, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=prec)
                dk_acc[keys] += jax.lax.dot_general(
                    ds, q_blk, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=prec)
                return dq + jax.lax.dot_general(
                    ds, k_ref[keys], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=prec)

            return products, pointwise, grads

        def over(st, n, keys_of, kind, dq):
            """``dq`` carried through ``n`` key runs ``keys_of(j)``."""
            products, pointwise, grads = st
            return _loop(n, lambda j, dq: grads(
                keys_of(j), *pointwise(*products(keys_of(j), kind, j)), dq),
                dq)

        def write_dq(rows, dq):
            _put(dq_out, dq_rows(rows), (dq * scale).astype(out_dtype), a,
                 lay, whole=a == 0)

        def block_keys(j):
            return _ds(j, block_k)

        whole = pl.ds(0, block_q)
        zeros = jnp.zeros((sub or block_q, w), jnp.float32)
        if not causal:
            write_dq(whole,
                     over(stages(whole), n_kv, block_keys, None, zeros))
        elif not sub:
            n_j = jnp.minimum(_last_kv_block(i, block_q, block_k) + 1, n_kv)
            write_dq(whole,
                     over(stages(whole), n_j, block_keys, "block", zeros))
        else:
            # Square blocks. A strip meets the key blocks before the
            # diagonal one whole and unmasked, then the diagonal block up
            # to its own diagonal tile. ``diag_always`` says from the
            # shapes that every query block has its diagonal block among
            # the keys; the strips' last runs are then written SKEWED, as
            # the forward's.
            n = block_q // sub
            rows = [pl.ds(r * sub, sub) for r in range(n)]
            keys = [_ds(i, block_k, (r + 1) * sub) for r in range(n)]
            st = [stages(r) for r in rows]
            dq = [zeros] * n
            if not single_q:
                n_before = i if diag_always else jnp.minimum(i, n_kv)
                dq = [over(st[r], n_before, block_keys, None, zeros)
                      for r in range(n)]
            if diag_always:
                held = {}

                def first(r):
                    held[r] = st[r][0](keys[r], "tril", i)

                def second(r):
                    held[r] = st[r][1](*held[r])

                def third(r):
                    write_dq(rows[r], st[r][2](keys[r], *held.pop(r), dq[r]))

                _skewed(n, first, second, third)
            else:
                n_diag = (i < n_kv).astype(jnp.int32)
                for r in range(n):
                    write_dq(rows[r], over(st[r], n_diag, lambda _: keys[r],
                                           "tril", dq[r]))

    _for_live_heads(lay, live, head)
    pl.when(i == n_q - 1)(emit_dkv)


# Scoped-VMEM budget for the fused backward's TOTAL estimated footprint
# (resident k/v/dk/dv + fp32 accumulators + one strip's (or the whole
# block's) live scores + double-buffered q-side blocks). The hardware
# limit is ~16 MB/core; 12 MB leaves headroom for Mosaic's own stack
# slop. Measured live (v5e, r5): with the whole 1024x1024 block live the
# kernel stack-OOMed at 20.82 MB vs the 16 MB limit — the old resident-only
# estimate missed the ~16 MB of score-sized intermediates entirely.
# Overridable for experiments.
_FUSED_BWD_VMEM_BUDGET = int(os.environ.get(
    "DS_TPU_FUSED_BWD_MAX_BYTES", 12 * 1024 * 1024))

# Resident-only gate used by _bwd_mode for callers that cannot shrink
# tiles (the block-sparse fused backward keeps full-length k/v/dk/dv
# resident and layouts its own loop blocks): defaults to the pre-r5 6 MB
# so the larger total-footprint default above does not silently admit
# sparse shapes whose resident set alone crowds out the loop
# intermediates — but an EXPLICIT DS_TPU_FUSED_BWD_MAX_BYTES keeps its
# historical power to admit larger resident sets.
_RESIDENT_BWD_VMEM_BUDGET = (
    int(os.environ["DS_TPU_FUSED_BWD_MAX_BYTES"])
    if "DS_TPU_FUSED_BWD_MAX_BYTES" in os.environ else 6 * 1024 * 1024)


def _fused_bwd_vmem_bytes(t_kv, d, dtype, block_q, block_k, sub, causal,
                          g=1):
    """Estimated scoped-VMEM footprint of one fused-backward program
    instance, ``g`` heads of ``d`` a block's lanes (1: head-major). Counts
    what the kernel actually keeps live (see _bwd_fused_kernel): resident
    k/v + dk/dv outputs (model dtype) and two full-length fp32
    accumulators; the scores of ONE strip of ``sub`` rows (of the whole
    ``block_q`` where the block is its own tile) over ``block_k`` keys — s
    and dpd in fp32, p and ds in the model dtype — plus the fp32 tril
    constant when causal blocks are square; and the double-buffered
    streamed q/do/dq blocks with their two fp32 row statistics a head (lse,
    delta: a [block_q, 1] column is padded to whole 128-lane tiles in
    VMEM)."""
    itemsize = jnp.dtype(dtype).itemsize
    lanes = g * d
    resident = t_kv * lanes * (4 * itemsize + 2 * 4)
    side = sub or block_q
    live = side * block_k * (2 * 4 + 2 * itemsize)
    tril = 4 * side * side if causal and block_q == block_k else 0
    streamed = 2 * block_q * (3 * lanes * itemsize
                              + 2 * g * _STATS_LANES * 4)
    return resident + live + tril + streamed


def _fit_fused_bwd_tiles(t_kv, d, dtype, block_q, block_k, sub, causal,
                         g=1):
    """(block_q, block_k, sub) <= the requested ones whose estimated
    footprint fits the budget. A block that is its own tile is halved, the
    larger side first (both sides stay >= 128 and keep dividing the
    sequence since the requested tiles do and only halving happens); a
    block taken in strips has nothing left to give up. None if nothing
    fits."""
    bq, bk = block_q, block_k
    while _fused_bwd_vmem_bytes(t_kv, d, dtype, bq, bk, sub, causal, g) > \
            _FUSED_BWD_VMEM_BUDGET:
        if sub or max(bq, bk) <= 128:
            return None
        if bq >= bk and bq > 128:
            bq //= 2
        else:
            bk //= 2
    return bq, bk, sub


def _bwd_mode(t_kv, d, dtype):
    """'fused' or 'split', decided from the shapes alone: fused when the
    resident k/v/dk/dv set fits its VMEM budget (env DS_TPU_FLASH_BWD
    overrides). Nothing is probed on the device — a fused kernel the
    compiler refuses raises at the call that asked for it. Governs both
    the dense flash backward and the block-sparse one
    (ops/sparse_attention/kernels.py), which share the kernel structure."""
    mode = os.environ.get("DS_TPU_FLASH_BWD", "auto")
    if mode in ("fused", "split"):
        return mode
    itemsize = jnp.dtype(dtype).itemsize
    resident = t_kv * d * (4 * itemsize + 2 * 4)
    return "split" if resident > _RESIDENT_BWD_VMEM_BUDGET else "fused"




@pallas_mode.shared_launch("scale", "causal", "block_q", "block_k", "sub",
                           "lay", "has_delta")
def _flash_bwd_fused_pallas(q, k, v, mask, delta, lse, do, *, scale, causal,
                            block_q, block_k, sub, lay, has_delta):
    from jax.experimental.pallas import tpu as pltpu

    b, t_q, t_kv = q.shape[0], q.shape[-2], k.shape[-2]
    n_q = pl.cdiv(t_q, block_q)
    use_tril = causal and block_q == block_k

    def q_block(i):
        return i

    def whole(i):
        return 0

    q_spec = _rows_spec(lay, block_q, q_block, 0)
    row_spec = _stats_spec(lay, block_q, q_block)

    in_specs = [q_spec, _rows_spec(lay, t_kv, whole, 1),
                _rows_spec(lay, t_kv, whole, 2)]
    args = [q, k, v]
    if mask is not None:
        in_specs.append(_mask_spec(t_kv, lambda b_, h_, i: 0))
        args.append(_mask_operand(mask))
    if use_tril:
        side = sub or block_q
        in_specs.append(
            pl.BlockSpec((side, side), lambda b_, h_, i: (0, 0)))
        args.append(_tril_block(side, side))
    in_specs += [_o_spec(lay, block_q, q_block), row_spec,
                 row_spec if has_delta else _o_spec(lay, block_q, q_block)]
    args += [do, lse, delta]

    if lay.fused:
        # One output, tile p's dq | dk | dv side by side as the operand's
        # q | k | v: the whole tile resident over the query blocks.
        out_specs = [pl.BlockSpec((None, t_q, 3 * lay.lanes),
                                  lambda b_, p_, i: (b_, 0, p_))]
        out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    else:
        kv_full = _rows_spec(lay, t_kv, whole)
        out_specs = [q_spec, kv_full, kv_full]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                     for x in (q, k, v)]

    grads = pallas_mode.kernel_call(
        "flash_bwd_fused",
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, sub=sub, lay=lay,
                          has_mask=mask is not None, has_tril=use_tril,
                          has_delta=has_delta, single_q=n_q == 1,
                          diag_always=t_q <= t_kv),
        grid=(b, lay.tiles, n_q),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((t_kv, lay.lanes), jnp.float32),
                        pltpu.VMEM((t_kv, lay.lanes), jnp.float32)],
    )(*args)
    # Tuple, not pallas_call's list: callers unpack and re-wrap it, and
    # jax's out-tree flattening is container-type strict.
    return tuple(grads)


def _flash_bwd_pallas(q, k, v, mask, delta, lse, g, scale, causal, block_q,
                      block_k, heads=None, head_dim=None, o=None):
    """(dq, dk, dv) in the operands' layout (see ``_flash_fwd_pallas``); a
    fused packed operand gets ``(dqkv, None, None)``. delta:
    [B, tiles x g, T, 1] fp32 = rowsum(dO * O) a head as the caller reckons
    it (minus any lse cotangent — see _flash_attention_lse; from a ring's
    global o), so that delta-shifts need no new kernel variant; None where
    it is just that row-sum, and the kernels then take ``o`` (g's layout)
    and sum it themselves."""
    from jax.experimental.pallas import tpu as pltpu

    lay = _resolve_layout(q, k, heads, head_dim)
    b, t_q = q.shape[0], q.shape[-2]
    t_kv = t_q if k is None else k.shape[-2]
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_kv)
    n_q = pl.cdiv(t_q, block_q)
    n_kv = pl.cdiv(t_kv, block_k)
    do = g
    has_delta = delta is not None
    if not has_delta:
        delta = o
    if lay.fused:
        k = v = q
    if _bwd_mode(t_kv, lay.lanes, q.dtype) == "fused":
        tiles = block_q, block_k, flash_subtile(block_q, block_k, causal)
        if os.environ.get("DS_TPU_FLASH_BWD") != "fused":
            # A block that is its own tile (no causal mask to walk it by)
            # can be too big for the fused backward's live set — shrink
            # just the backward's tiles to the VMEM fit rather than
            # abandoning the one-pass kernel (measured live: a 1024x1024
            # tile stack-OOMed the 16 MB scoped limit). Explicitly forced,
            # the request is honored WITH its exact tiles: an A/B
            # experiment must measure the configured tiling, not a
            # silently substituted one.
            tiles = _fit_fused_bwd_tiles(t_kv, lay.d, q.dtype, *tiles,
                                         causal, lay.g)
        if tiles is not None:
            grads = _flash_bwd_fused_pallas(
                q, k, v, mask, delta, lse, do, scale=scale, causal=causal,
                block_q=tiles[0], block_k=tiles[1], sub=tiles[2], lay=lay,
                has_delta=has_delta)
            return grads + (None, None) if lay.fused else grads
    use_tril = causal and block_q == block_k
    tril = _tril_block(block_q, block_k) if use_tril else None
    # The split kernels give three arrays in either layout.
    out = lay._replace(fused=False)
    grad_shape = jax.ShapeDtypeStruct(_o_shape(lay, b, t_q), q.dtype)

    # dq: grid over (q block, kv block), kv innermost and pipelined.
    if causal:
        def kv_block(i, j):
            return jnp.minimum(j, _last_kv_block(i, block_q, block_k))
    else:
        def kv_block(i, j):
            return j

    def q_block(i, j):
        return i

    q_spec = _rows_spec(lay, block_q, q_block, 0)
    row_spec = _stats_spec(lay, block_q, q_block)
    tril_spec = pl.BlockSpec((block_q, block_k), lambda b_, h_, i, j: (0, 0))

    in_specs = [q_spec, _rows_spec(lay, block_k, kv_block, 1),
                _rows_spec(lay, block_k, kv_block, 2)]
    args = [q, k, v]
    if mask is not None:
        in_specs.append(_mask_spec(
            block_k, lambda b_, h_, i, j: kv_block(i, j)))
        args.append(_mask_operand(mask))
    if use_tril:
        in_specs.append(tril_spec)
        args.append(tril)
    in_specs += [_o_spec(lay, block_q, q_block), row_spec,
                 row_spec if has_delta else _o_spec(lay, block_q, q_block)]
    args += [do, lse, delta]
    dq = pallas_mode.kernel_call(
        "flash_bwd_dq",
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, lay=lay,
                          has_mask=mask is not None, has_tril=use_tril,
                          has_delta=has_delta, single_kv=n_kv == 1),
        grid=(b, lay.tiles, n_q, n_kv),
        in_specs=in_specs,
        out_specs=_rows_spec(out, block_q, q_block),
        out_shape=grad_shape,
        scratch_shapes=[] if n_kv == 1 else
        [pltpu.VMEM((block_q, lay.lanes), jnp.float32)],
    )(*args)

    # dk/dv: grid over (kv block, q block), q innermost and pipelined.
    if causal:
        def q_block2(jk, i):
            # Clamp into the valid block range: fully-inactive kv blocks
            # (first active q block past the end) skip compute, so reading
            # the last block instead issues no stray DMA.
            first = jnp.minimum(_first_q_block(jk, block_q, block_k),
                                n_q - 1)
            return jnp.maximum(i, first)
    else:
        def q_block2(jk, i):
            return i

    def kv_block2(jk, i):
        return jk

    q_spec2 = _rows_spec(lay, block_q, q_block2, 0)
    row_spec2 = _stats_spec(lay, block_q, q_block2)

    in_specs = [q_spec2, _rows_spec(lay, block_k, kv_block2, 1),
                _rows_spec(lay, block_k, kv_block2, 2)]
    args = [q, k, v]
    if mask is not None:
        in_specs.append(_mask_spec(block_k, lambda b_, h_, jk, i: jk))
        args.append(_mask_operand(mask))
    if use_tril:
        in_specs.append(tril_spec)
        args.append(tril)
    in_specs += [_o_spec(lay, block_q, q_block2), row_spec2,
                 row_spec2 if has_delta else _o_spec(lay, block_q, q_block2)]
    args += [do, lse, delta]
    kv_out = _rows_spec(out, block_k, kv_block2)
    kv_shape = jax.ShapeDtypeStruct(_o_shape(lay, b, t_kv), q.dtype)
    dk, dv = pallas_mode.kernel_call(
        "flash_bwd_dkv",
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, lay=lay,
                          has_mask=mask is not None, has_tril=use_tril,
                          has_delta=has_delta, single_q=n_q == 1),
        grid=(b, lay.tiles, n_kv, n_q),
        in_specs=in_specs,
        out_specs=[kv_out, kv_out],
        out_shape=[kv_shape, kv_shape],
        scratch_shapes=[] if n_q == 1 else
        [pltpu.VMEM((block_k, lay.lanes), jnp.float32),
         pltpu.VMEM((block_k, lay.lanes), jnp.float32)],
    )(*args)

    if lay.fused:
        # The two launches' three arrays into the operand's arrangement,
        # tile p's dq | dk | dv side by side (a copy: the split backward is
        # the long sequences' path, which no cell runs).
        tiled = [x.reshape(b, t_q, lay.tiles, 1, lay.lanes)
                 for x in (dq, dk, dv)]
        return jnp.concatenate(tiled, axis=3).reshape(q.shape), None, None
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Kernels on a mesh — batch/head-parallel, through shard_map.
#
# XLA's SPMD partitioner cannot see inside a pallas_call, and for a TPU it
# refuses to partition one at all ("Mosaic kernels cannot be automatically
# partitioned. Please wrap the call in a shard_map"). The kernels are
# embarrassingly parallel over batch and heads, so that is what the wrapper
# declares: batch over 'data', heads over 'model', sequence and head-dim
# whole. Each shard then runs the plain pallas kernel on its local
# [b/dp, h/mp, T, D] block — the TPU analogue of the reference's
# data-parallel engine wrapping its CUDA kernels (engine.py:508-528: kernels
# see local tensors, the framework owns the distribution).
# (jax.experimental.custom_partitioning could read the split off the operands
# instead, but the TPU compiler of this installation refuses it: "Custom
# emitter for CustomSPMDPartitioning not found", on four v5e chips, PR 21.)
#
# The mesh is AMBIENT: whoever jits a model enters kernels_on_mesh(mesh)
# inside the function it jits, so every trace of that function passes
# through it. Inside a shard_map region arrays are already shard-local and
# the kernels launch raw.
# ---------------------------------------------------------------------------

_ambient = threading.local()


@contextlib.contextmanager
def kernels_on_mesh(mesh):
    """Within this context the kernel entry points (flash attention here,
    the decode family in decode_attention.py) split their [B, H, ...]
    operands over ``mesh`` — batch over its 'data' axis, heads over 'model'
    — and launch shard-local. Thread-local and re-entrant; only tracing
    cares, so enter it inside the traced function."""
    prev = getattr(_ambient, "mesh", None)
    _ambient.mesh = mesh
    try:
        yield
    finally:
        _ambient.mesh = prev


def kernel_sharding(batch, heads):
    """How a kernel call with ``batch`` rows and ``heads`` heads is split:
    None (launch raw: no ambient mesh, a one-device mesh, or already inside
    a shard_map region) or the hashable ``(mesh, batch_axis, head_axis)``
    — an axis is None when the mesh does not split that dim evenly, and the
    kernel then sees it whole on every shard."""
    mesh = getattr(_ambient, "mesh", None)
    if mesh is None or mesh.size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return None

    def axis(name, extent):
        size = mesh.shape.get(name, 1)
        return name if size > 1 and extent % size == 0 else None

    return mesh, axis(DATA_AXIS, batch), axis(MODEL_AXIS, heads)


def on_shards(fn, shard, in_dims, out_dims):
    """``fn`` launched shard-local per ``shard`` (a ``kernel_sharding``
    result; None returns ``fn`` itself). ``in_dims`` / ``out_dims`` name,
    per operand and per result, what its leading dims are: ``"bh"`` for
    [B, H, ...], ``"b"`` for [B, ...], ``"-h"`` for [pages, H, ...] (any
    whole leading dim: a batch no shard may split), ``"--h"`` for a whole
    paged arena [layers, pages, H, ...]; the remaining dims stay whole."""
    if shard is None:
        return fn
    mesh, b, h = shard

    def spec(dims):
        return P(*({"b": b, "h": h, "-": None}[c] for c in dims))

    outs = tuple(spec(d) for d in out_dims)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(spec(d) for d in in_dims),
                         out_specs=outs if len(outs) > 1 else outs[0],
                         check_vma=False)



def packed_heads(heads, d):
    """g, the heads a lane tile, where the packed entry can take ``heads``
    heads of ``d`` HERE; 0 where the caller is to split its heads and take
    the head-major entry: a ``d`` the layout cannot hold (``lane_pack``),
    or an ambient mesh whose 'model' axis would cut a lane tile (a shard's
    ``heads / model`` must be whole tiles). Decided from the shapes and the
    mesh at trace time."""
    g = lane_pack(d, heads)
    mesh = getattr(_ambient, "mesh", None)
    if g and mesh is not None and \
            not jax.sharding.get_abstract_mesh().manual_axes:
        if heads % (g * mesh.shape.get(MODEL_AXIS, 1)):
            return 0
    return g


def _dims(x):
    """``on_shards``' name for an operand: head-major or packed."""
    return "bh" if x.ndim == 4 else "b-h"


def _local_heads(shard, heads):
    """The heads one shard's packed operand holds."""
    if shard is None or heads is None or shard[2] is None:
        return heads
    return heads // shard[0].shape[shard[2]]


def _flash_fwd(q, k, v, mask, scale, causal, block_q, block_k, shard=None,
               heads=None, head_dim=None):
    ops = (q,) if k is None else (q, k, v)
    local = _local_heads(shard, heads)

    def f(*args):
        qkv = args[:len(ops)] + (None,) * (3 - len(ops))
        return _flash_fwd_pallas(*qkv, args[len(ops)] if mask is not None
                                 else None, scale, causal, block_q, block_k,
                                 local, head_dim)

    args = ops if mask is None else ops + (mask,)
    in_dims = tuple(_dims(x) for x in ops) + \
        (("b",) if mask is not None else ())
    return on_shards(f, shard, in_dims, (_dims(q), "bh"))(*args)


def _row_delta(g, o, heads, head_dim):
    """delta = rowsum(dO * O) a head, ``[B, tiles x g, T, 1]`` fp32."""
    prod = g.astype(jnp.float32) * o.astype(jnp.float32)
    if o.ndim == 4:
        return jnp.sum(prod, axis=-1, keepdims=True)
    b, t, lanes = o.shape
    d = head_dim or lanes // heads
    delta = jnp.sum(prod.reshape(b, t, lanes // d, d), axis=-1)
    return jnp.swapaxes(delta, 1, 2)[..., None]


def _flash_bwd(res, g, scale, causal, block_q, block_k, dlse=None,
               shard=None, heads=None, head_dim=None):
    q, k, v, mask, o, lse = res
    # delta = rowsum(dO * O): the kernels sum it from o themselves. An lse
    # cotangent folds into the same kernels as a delta shift (dlse_i/ds_ij
    # = p_ij, so ds = p * (dp - (delta - dlse))), and is brought to them
    # as the shifted delta.
    stat = o if dlse is None else _row_delta(g, o, heads, head_dim) - dlse
    ops = (q,) if k is None else (q, k, v)
    local = _local_heads(shard, heads)

    def f(*args):
        qkv = args[:len(ops)] + (None,) * (3 - len(ops))
        stat, lse, g = args[len(ops):len(ops) + 3]
        grads = _flash_bwd_pallas(
            *qkv, args[-1] if mask is not None else None,
            None if dlse is None else stat, lse, g, scale, causal, block_q,
            block_k, local, head_dim, o=stat if dlse is None else None)
        return grads[:len(ops)]

    args = ops + (stat, lse, g) + (() if mask is None else (mask,))
    in_dims = tuple(_dims(x) for x in ops) + \
        (_dims(g) if dlse is None else "bh", "bh", _dims(g)) + \
        (("b",) if mask is not None else ())
    grads = on_shards(f, shard, in_dims,
                      tuple(_dims(x) for x in ops))(*args)
    dmask = None if mask is None else jnp.zeros_like(mask)
    return tuple(grads) + (None,) * (3 - len(ops)) + (dmask,)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------

# ``shard`` (a kernel_sharding result) is a STATIC nondiff arg captured at
# the public entry: the custom_vjp backward is traced lazily at transpose
# time — possibly after the kernels_on_mesh context has exited — so the
# decision must ride the residual-free static args, not the thread-local.
# ``layout`` is (heads, head_dim) of a packed operand, None head-major.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_attention(q, k, v, mask, scale, causal, block_q, block_k, shard,
                     layout):
    o, _ = _flash_fwd(q, k, v, mask, scale, causal, block_q, block_k,
                      shard, *layout)
    return o


def _flash_attention_fwd(q, k, v, mask, scale, causal, block_q, block_k,
                         shard, layout):
    o, lse = _flash_fwd(q, k, v, mask, scale, causal, block_q, block_k,
                        shard, *layout)
    return o, (q, k, v, mask, o, lse)


def _flash_attention_bwd(scale, causal, block_q, block_k, shard, layout,
                         res, g):
    return _flash_bwd(res, g, scale, causal, block_q, block_k, None, shard,
                      *layout)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_attention_lse(q, k, v, mask, scale, causal, block_q, block_k,
                         shard, layout):
    """(o, lse) variant — lse is differentiable too (ring attention merges
    partial results through it)."""
    return _flash_fwd(q, k, v, mask, scale, causal, block_q, block_k,
                      shard, *layout)


def _flash_attention_lse_fwd(q, k, v, mask, scale, causal, block_q,
                             block_k, shard, layout):
    o, lse = _flash_fwd(q, k, v, mask, scale, causal, block_q, block_k,
                        shard, *layout)
    return (o, lse), (q, k, v, mask, o, lse)


def _flash_attention_lse_bwd(scale, causal, block_q, block_k, shard, layout,
                             res, g):
    do, dlse = g
    return _flash_bwd(res, do, scale, causal, block_q, block_k, dlse,
                      shard, *layout)


_flash_attention_lse.defvjp(_flash_attention_lse_fwd,
                            _flash_attention_lse_bwd)


def _head_major(q, k, v, heads, head_dim):
    """A packed operand's q, k, v as ``[B, H, T, d]`` (copies: what only
    the dense fallback of a ragged length pays)."""
    lay = _resolve_layout(q, k, heads, head_dim)
    b, t = q.shape[:2]
    if lay.fused:
        q, k, v = (q.reshape(b, t, lay.tiles, 3, lay.lanes)[:, :, :, n]
                   for n in range(3))
    return tuple(
        x.reshape(b, -1, lay.tiles * lay.g, lay.d)[:, :, :heads]
        .transpose(0, 2, 1, 3) for x in (q, k, v))


def _packed_o(o):
    """``[B, H, T, d]`` as the packed ``[B, T, tiles x g x d]``."""
    b, h, t, d = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, t, h * d)
    return jnp.pad(o, [(0, 0), (0, 0), (0, (-h % lane_pack(d, h)) * d)])


def _entry(q, k, v, mask, causal, scale, block_q, block_k, heads, head_dim,
           with_lse):
    packed = q.ndim == 3
    if packed and not heads:
        raise ValueError("a packed [B, T, lanes] operand needs heads=")
    if not packed and (k is None or v is None):
        raise ValueError("head-major attention takes q, k and v")
    if packed:
        lay = _resolve_layout(q, k, heads, head_dim)
        d, n_heads, layout = lay.d, lay.tiles, (heads, lay.d)
        like = jax.ShapeDtypeStruct((q.shape[0], heads, q.shape[1], d),
                                    q.dtype)
        like = (like, like, like)
    else:
        d, n_heads, layout = q.shape[-1], q.shape[1], (None, None)
        like = (q, k, v)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q, block_k, ragged = resolve_block_sizes(*like, causal,
                                                   block_q, block_k)
    if ragged:
        # Kernel reads fixed-size VMEM slices; ragged tails go to the
        # (differentiable) jnp path. Pad sequences to the block size to stay
        # on the fused kernel (SparseAttentionUtils.pad_to_block_size is the
        # helper, mirroring the reference's %16 padding,
        # ops/transformer/transformer.py:183-193).
        if packed:
            q, k, v = _head_major(q, k, v, heads, d)
        out = mha_reference(q, k, v, mask=mask, causal=causal, scale=scale,
                            return_lse=with_lse)
        if not packed:
            return out
        if not with_lse:
            return _packed_o(out)
        dead = lay.tiles * lay.g - heads
        return _packed_o(out[0]), jnp.pad(
            out[1], [(0, 0), (0, dead), (0, 0), (0, 0)])
    shard = kernel_sharding(q.shape[0], n_heads)
    if packed and shard is not None and lay.ragged:
        shard = shard[:2] + (None,)   # no shard may hold the dead half
    fn = _flash_attention_lse if with_lse else _flash_attention
    return fn(q, k, v, mask, float(scale), bool(causal), block_q, block_k,
              shard, layout)


def flash_attention_with_lse(q, k=None, v=None, mask=None, causal=False,
                             scale=None, block_q=None, block_k=None,
                             heads=None, head_dim=None):
    """flash_attention returning (o, lse[B, H, T, 1] fp32; a packed
    operand's ``[B, tiles x g, T, 1]``, a dead head's rows unwritten); both
    outputs are differentiable. Ragged shapes fall back to the jnp path."""
    return _entry(q, k, v, mask, causal, scale, block_q, block_k, heads,
                  head_dim, True)


def flash_signature(b, h, t_q, t_kv, d, dtype, causal):
    """Autotune-table signature for a flash-attention shape. Exported so
    the sweep/promotion script (tests/perf/autotune_sweep.py) shares the
    exact format and cannot silently drop entries if it changes."""
    return "b{}_h{}_tq{}_tkv{}_d{}_{}_c{}".format(
        b, h, t_q, t_kv, d, jnp.dtype(dtype).name, int(bool(causal)))


def _autotuned_blocks(q, k, v, causal, default_q, default_k):
    """Per-shape tile selection via the autotuner (the reference sweeps
    cublas algos per shape at layer creation, gemm_test.h:27,141).

    Online sweeps need CONCRETE arrays to execute — when q is a tracer
    (flash_attention invoked inside an enclosing jit, the engine's normal
    path), only the bundled/user tables are consulted. Populate the table
    by calling flash_attention eagerly on the target shapes with
    DS_TPU_AUTOTUNE=1 (mirroring the reference, which also sweeps at layer
    creation, not per step)."""
    import jax.core

    from deepspeed_tpu.ops import autotuner

    b, h, t_q, d = q.shape
    t_kv = k.shape[2]
    sig = flash_signature(b, h, t_q, t_kv, d, q.dtype, causal)
    default = [min(default_q, t_q), min(default_k, t_kv)]
    # (a packed call's operands come here as head-major SHAPES)
    traced = any(isinstance(x, jax.core.Tracer)
                 or not isinstance(x, jax.Array) for x in (q, k, v))
    if traced:
        cands = []  # table lookup only; sweeps cannot run during a trace
    else:
        cands = sorted({(min(bq, t_q), min(bk, t_kv))
                        for bq in (256, 512, 1024) for bk in (512, 1024)
                        if t_q % min(bq, t_q) == 0
                        and t_kv % min(bk, t_kv) == 0})
        cands = [list(c) for c in cands]

    def make_run(cand):
        bq, bk = cand
        reps = 10  # amortize dispatch/RTT: kernel time must dominate

        def fwd_bwd(x, y, z):
            eps = jnp.asarray(1e-7, x.dtype)  # nonzero: keeps grads live

            def once(carry, _):
                x_, y_, z_ = carry
                g = jax.grad(lambda a, b_, c: _flash_attention(
                    a, b_, c, None, 1.0 / d ** 0.5, bool(causal), bq, bk,
                    None, (None, None)).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))(x_, y_, z_)
                return (x_ + g[0] * eps, y_ + g[1] * eps,
                        z_ + g[2] * eps), None

            (x, y, z), _ = jax.lax.scan(once, (x, y, z), None, length=reps)
            return x

        jitted = jax.jit(fwd_bwd)

        def run():
            return jitted(q, k, v)
        return run

    choice = autotuner.autotune(
        "flash_attention", sig, cands, make_run, default=default)
    return int(choice[0]), int(choice[1])


def resolve_block_sizes(q, k, v, causal, block_q, block_k,
                        default_q=1024, default_k=1024):
    """(block_q, block_k, ragged) — the ONE block-selection policy shared
    by flash_attention, flash_attention_with_lse and ring attention:
    consult the per-shape autotuner when no explicit tiles were given (on
    TPU), default otherwise, clamp to the sequence extents, and flag
    shapes the tiled kernels cannot take (ragged => dense fallback)."""
    t_q, t_kv = q.shape[2], k.shape[2]
    if block_q is None and block_k is None and not pallas_mode.interpret():
        block_q, block_k = _autotuned_blocks(q, k, v, causal,
                                             default_q, default_k)
    bq = min(int(block_q or default_q), t_q)
    bk = min(int(block_k or default_k), t_kv)
    ragged = bool(t_q % bq or t_kv % bk)
    return bq, bk, ragged



def flash_attention(q, k=None, v=None, mask=None, causal=False, scale=None,
                    block_q=None, block_k=None, heads=None, head_dim=None):
    """Fused (flash) multi-head attention.

    Args:
      q, k, v: the operands, in one of two layouts, told apart by rank.
        HEAD-MAJOR ``[B, H, T, D]`` each: what ring / Ulysses attention,
        the BERT layer (``ops/transformer/transformer.py``) and the
        sequence-parallel and dense branches of ``CausalSelfAttention`` are
        written over. PACKED ``[B, T, tiles x g x D]``, the layout a
        projection emits, ``g = lane_pack(D, heads)`` heads a 128-lane tile
        (``heads``, and ``head_dim`` where ``heads x D`` is not the lane
        count, say what the lanes hold): either three such arrays, or ONE
        (k and v None), the fused projection's ``[B, T, tiles x 3 x g x
        D]`` with tile p's q, k, v lanes side by side (``tile_qkv`` arranges
        a ``[.., 3 H D]`` axis so; ``CausalSelfAttention``'s flash branch
        arranges c_attn's weight and takes this form). Nothing is
        transposed around the kernels then, forward or backward: the
        output and every gradient come in the operand's own layout.
      mask: optional additive padding mask [B, T_kv] (0 keep / -1e9 drop),
        broadcast over heads and query rows — the reference's attention-mask
        convention (csrc/transformer/softmax_kernels.cu attn_softmax).
      causal: apply a causal (autoregressive) mask.
      scale: score scale; default 1/sqrt(D).
      block_q, block_k: the GRID block, the rows and keys of one grid
        step. Default (None) consults the per-shape autotuner table
        (ops/autotuner.py) and falls back to 1024x1024: on v5e a grid step
        costs a tenth of a head's work at T 1024, d=64, so the block is the
        whole sequence there. How a block is taken INSIDE the kernel (strips
        to the causal diagonal, ``flash_subtile``) follows from the block
        and is nobody's setting.
    Returns: ``[B, H, T, D]``, or ``[B, T, tiles x g x D]`` for a packed
      operand (a dead head's lanes, where g does not divide the heads, are
      finite and mean nothing), in q.dtype.
    """
    return _entry(q, k, v, mask, causal, scale, block_q, block_k, heads,
                  head_dim, False)
