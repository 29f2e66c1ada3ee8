"""Flash-decode — length-aware fused cache attention for the slotted KV pool.

Serving reads attention differently than training writes it: the query is
one token (or one short prompt bucket) per row, the keys are a pre-allocated
``[B, H, max_len, D]`` cache plane, and each row has its own sequence
FRONTIER ``pos`` — row b's keys occupy ``0 .. pos[b]+S-1`` and everything
past that is stale garbage a future request will overwrite. The einsum path
in ``models/generation.py`` scores the query against the FULL plane in
fp32, materializes ``[B, H, S, max_len]`` scores and softmaxes over the
whole length, even when the frontier sits at position 30 of a 2048-slot
cache.

This kernel fuses QK-score, online softmax and the value GEMM in one
Pallas program, blocked along the length dimension, with PER-ROW frontier
awareness via scalar prefetch:

- ``pos`` rides a ``PrefetchScalarGridSpec`` scalar operand, so the kv
  BLOCK INDEX MAP can read it: blocks past ``(pos[b]+S-1) // block_k``
  clamp to the last useful block (a repeated index issues no new DMA) and
  ``@pl.when`` skips their compute — the same trick the training kernel
  uses for causal skip, but against a runtime frontier instead of the
  static diagonal;
- scores never leave VMEM: online-softmax statistics live in fp32 scratch
  across the split-KV grid steps, and the row-sum rides the PV matmul
  (``_pv_rowsum``) exactly as in the training kernel;
- the frontier mask only costs a compare/select pass on the one block that
  STRADDLES a row's frontier; fully-visible interior blocks skip it;
- q is pre-scaled by 1/sqrt(d) outside the kernel, and decode's S=1 query
  is padded up to the Mosaic sublane minimum (8 fp32 / 16 bf16) so the
  [S, block_k] score tile is always a legal VMEM shape.

The cache plane length must be a multiple of ``BLOCK_MIN`` (128 lanes);
``inference/kv_pool.py`` pads its pool to that quantum and
``flash_decode_attention`` falls back to the dense reference for
unsupported shapes. Off-TPU the kernel runs in Pallas interpret mode, so
CPU tests exercise the same code path (parity pinned by
``tests/unit/test_decode_attention.py``).

WHAT A UNIT OF WORK IS. The dense kernels above (``decode_attn[_q8]``:
``generate()``'s cache and the dense slot pool) step a grid of
``(row, head, length block)``: one block of one head a step, past-frontier
blocks clamped and skipped but still stepped. The PAGED kernels
(``paged_decode[_q8]``, and the prefill lane's ``prefill_attn``: what the
serving engine runs) step a WORK LIST instead: one unit is one page of ALL
heads of one row (a run of K consecutive pages where a page is small:
``latent_decode``'s four), only a row's live pages (up to its frontier) are
units, a freed row is no unit at all (its output is zeros), and the list's
length is the grid. See the "Paged kernels" section below.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.analysis.annotations import hot_path
from deepspeed_tpu.ops import pallas_mode
from deepspeed_tpu.ops.transformer.kernels.attention import (
    NEG_INF,
    _STATS_LANES,
    _exp_lowp,
    _is_lowp,
    _mxu_precision,
    _pv_rowsum,
    kernel_sharding,
    on_shards,
)

# Length-dimension tile quantum: one 128-lane row of the score tile. The
# kv pool pads max_len to a multiple of this so the kernel always engages.
BLOCK_MIN = 128

_DEFAULT_BLOCK_K = 256


def pad_cache_len(max_len):
    """Smallest multiple of BLOCK_MIN covering ``max_len`` — the cache
    plane length flash-decode requires (padding a plane is inert: the
    frontier never reaches padded positions, so they are always masked)."""
    return -(-int(max_len) // BLOCK_MIN) * BLOCK_MIN


def decode_supported(t_kv):
    """Can the kernel take a cache plane of length ``t_kv``?"""
    return t_kv % BLOCK_MIN == 0


# Lanes of one vector tile: what the minor dim of a stored arena fills.
LANES = 128


def lane_pack(d, h):
    """Of a model's ``h`` heads of width ``d``, how many share one lane
    tile in a PAGED arena: the arena is stored
    ``[L, P, ceil(h / g), page_len, g * d]``, so its minor dim fills the
    tile the chip stores and moves anyway (a minor dim of 64 is padded to
    128 in VMEM, and XLA keeps such an arena page-length minor between
    steps and converts all of it where a step enters and leaves). 1 where
    ``d`` fills a tile or does not divide one; never more than ``h`` (a
    tile wider than all the heads together would store zero heads). The
    launchers read ``g`` back from shapes: ``arena.shape[-1] // d``."""
    return min(LANES // d, h) if d < LANES and LANES % d == 0 else 1


def pad_heads(x, heads, axis):
    """``x`` with zero heads appended along ``axis`` up to ``heads`` (a
    head count ``g`` does not divide; a scale arena's new values)."""
    if x.shape[axis] == heads:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, heads - x.shape[axis])
    return jnp.pad(x, widths)


def pack_heads(x, g):
    """``[..., H, T, D]`` rows as a packed arena holds them,
    ``[..., ceil(H / g), T, g * D]``: head ``g * p + a`` in lanes
    ``a * D .. a * D + D - 1`` of packed head ``p`` (a zero head pads an
    ``H`` that ``g`` does not divide)."""
    if g == 1:
        return x
    lead, (h, t, d) = x.shape[:-3], x.shape[-3:]
    hp = -(-h // g)
    x = pad_heads(x, hp * g, x.ndim - 3).reshape(lead + (hp, g, t, d))
    return jnp.moveaxis(x, -3, -2).reshape(lead + (hp, t, g * d))


def unpack_heads(x, g, h):
    """The inverse of ``pack_heads``: ``[..., Hp, T, g * D]`` back to the
    ``h`` heads ``[..., h, T, D]``."""
    if g == 1:
        return x
    lead, (hp, t, gd) = x.shape[:-3], x.shape[-3:]
    x = jnp.moveaxis(x.reshape(lead + (hp, t, g, gd // g)), -2, -3)
    return x.reshape(lead + (hp * g, t, gd // g))[..., :h, :, :]


def query_group(arena, heads, head_dim):
    """``rep``, the query heads that share one STORED head, read back from a
    k/v arena's shape ``[.., Hkv / g, page_len, g * D]`` for a model of
    ``heads`` query heads of ``head_dim``: 1 where every query head stores a
    key of its own, 4 for 32 heads over 8 stored, whether they lie one a
    lane tile (``g`` 1, a head of 128) or two (``g`` 2, a head of 64). What
    the launchers put beside ``S`` on the sublane axis, and what the engine
    reports as ``kv_query_group``."""
    return max(1, heads // (arena.shape[-3] * (arena.shape[-1] // head_dim)))


def _pack_query(q, g):
    """``[B, H, S, D]`` queries against a packed arena: block-diagonal
    ``[B, ceil(H / g), g * S, g * D]``, head ``a`` of a group in rows
    ``a * S .. a * S + S - 1`` and lanes ``a * D .. a * D + D - 1``, zeros
    elsewhere: a zero lane adds exactly nothing to a row's fp32 scores."""
    if g == 1:
        return q
    b, h, s, d = q.shape
    hp = -(-h // g)
    q = pad_heads(q, hp * g, 1).reshape(b, hp, g, s, 1, d)
    own = jnp.eye(g, dtype=bool).reshape(1, 1, g, 1, g, 1)
    return jnp.where(own, q, 0).reshape(b, hp, g * s, g * d)


def _unpack_output(o, g, h):
    """Each head's own rows and lanes out of the packed kernel's
    ``[B, Hp, g * S, g * D]`` (the other lanes of a row attended another
    head's values): ``[B, h, S, D]``."""
    if g == 1:
        return o
    b, hp, gs, gd = o.shape
    o = o.reshape(b, hp, g, gs // g, g, gd // g)
    o = jnp.stack([o[:, :, a, :, a] for a in range(g)], axis=2)
    return o.reshape(b, hp * g, gs // g, gd // g)[:, :h]


def _sublane(dtype):
    """Mosaic's minimum second-minor tile extent: score tiles narrower than
    this are padded anyway, so the launcher pads the QUERY dim explicitly
    and slices the output (decode's S=1 would otherwise hand Mosaic a
    1-row tile)."""
    return 16 if _is_lowp(dtype) else 8


def decode_signature(b, h, s, t_kv, d, dtype):
    """Autotune-table signature for a decode-attention shape. Exported so
    the sweep/promotion script (tests/perf/autotune_sweep.py) shares the
    exact format and cannot silently drop entries if it changes."""
    return "b{}_h{}_s{}_t{}_d{}_{}".format(
        b, h, s, t_kv, d, jnp.dtype(dtype).name)


# ---------------------------------------------------------------------------
# int8 KV quantization — the storage format of the KV hierarchy's
# compressed tier (inference/kv_hierarchy/). Symmetric per-(head, position)
# scales: each written position gets its own scale, so APPENDING never
# retroactively re-quantizes earlier positions (a running per-head amax
# would corrupt history on every new outlier). The scale planes ride the
# pool as fp32 ``[..., T]`` arrays — 2 bytes/position of overhead against
# the (itemsize-1)*D saved per position.
# ---------------------------------------------------------------------------

# Scale floor: all-zero rows (unwritten cache positions) quantize to zero
# codes with this scale instead of dividing by zero.
_Q8_EPS = 1e-8


@hot_path
def quantize_kv(x):
    """Quantize ``[..., D]`` k/v rows to int8 with per-row symmetric
    scales. Returns ``(codes int8 [..., D], scale fp32 [...])`` where
    ``codes * scale[..., None]`` reconstructs x to within scale/2 per
    element (the parity bound tests pin)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / 127.0, _Q8_EPS)
    codes = jnp.clip(jnp.round(xf / scale[..., None]), -127.0, 127.0)
    return codes.astype(jnp.int8), scale


@hot_path
def dequantize_kv(codes, scale, dtype=jnp.float32):
    """Inverse of ``quantize_kv``: ``codes [..., D]`` int8 with per-row
    ``scale [...]`` back to ``dtype``."""
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# Reference (pure jnp) — ground truth for parity tests and the fallback for
# shapes the kernel does not support. Mirrors models/generation.py's cache
# attention (einsum scores over the full plane, frontier mask, fp32
# softmax) so flag-off and fallback paths are the SAME math.
# ---------------------------------------------------------------------------

def visible_upto(q_pos, block=1):
    """The last key position a query at absolute position ``q_pos`` sees: its
    own where ``block`` is 1 (the causal rule, and then the value itself: a
    next-token model traces what it always has), else the last position of
    its block of ``block`` absolute positions, ``(q_pos // block + 1) * block
    - 1``: causal across blocks, both ways inside one (a model that generates
    by diffusion over blocks, ``models/decoder.py`` ``block_length``). THE ONE
    EXPRESSION wherever a mask is formed: the einsum path
    (``generation.CacheAttention``), the references here and the paged
    kernel's straddle mask (the decode scan's and ``prefill_attn`` alike).
    ``block`` is static; ``q_pos`` is never negative."""
    if block == 1:
        return q_pos
    return (_div(q_pos, block) + 1) * block - 1


def visible_from(q_pos, window=None):
    """The FIRST key position a query at absolute position ``q_pos`` sees
    under a sliding window of ``window`` positions, the query's own counted:
    ``q_pos - window + 1`` (negative near a sequence's start, where every key
    that exists is at or past it). ``window`` None is no window: there is no
    lower bound, ``visible`` forms the mask it always has and a model without
    window layers traces what it always did. The one expression of the LOWER
    bound wherever a mask is formed, as ``visible_upto`` is of the upper:
    the einsum path, the references and BOTH ends of the paged body's
    straddle mask. ``window`` is static."""
    if window is None:
        return None
    return q_pos - (window - 1)


def visible(k_pos, q_pos, block=1, window=None):
    """Whether the key at ``k_pos`` is seen by the query at ``q_pos``
    (broadcast): ``visible_from(q_pos, window) <= k_pos <=
    visible_upto(q_pos, block)``; without a window the upper bound alone, the
    comparison every mask was."""
    seen = k_pos <= visible_upto(q_pos, block)
    first = visible_from(q_pos, window)
    return seen if first is None else seen & (k_pos >= first)


@hot_path
def decode_attention_reference(q, k, v, pos, scale=None, block=1,
                               window=None):
    """q: [B, H, S, D] query rows, row b starting at global position
    ``pos[b]`` (its k/v already written at ``pos[b] .. pos[b]+S-1``);
    k, v: [B, H, T, D] cache planes; pos: [B] int32 frontiers.
    Key t is visible to query row i iff ``t <= pos[b] + i`` — the causal
    mask against each row's GLOBAL position, which also excludes every
    stale position past the frontier. Returns [B, H, S, D] in q.dtype."""
    B, H, S, D = q.shape
    T = k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    prec = _mxu_precision(q.dtype)
    q_pos = pos[:, None] + jnp.arange(S)[None]               # [B, S]
    mask = visible(jnp.arange(T)[None, None, :], q_pos[:, :, None], block,
                   window)                                    # [B, S, T]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=prec) * scale
    s = jnp.where(mask[:, None], s, jnp.finfo(jnp.float32).min)
    att = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", att, v, precision=prec)


@hot_path
def decode_attention_q8_reference(q, k, v, k_scale, v_scale, pos,
                                  scale=None):
    """int8-cache ground truth: dequantize the whole plane, then the
    dense reference. The q8 kernel must match THIS — the engine's einsum
    (flag-off) path computes exactly this, so kernel-on and kernel-off
    serving agree on the same dequantized math.

    k, v: [B, H, T, D] int8 codes; k_scale, v_scale: [B, H, T] fp32
    per-position scales."""
    kf = dequantize_kv(k, k_scale, q.dtype)
    vf = dequantize_kv(v, v_scale, q.dtype)
    return decode_attention_reference(q, kf, vf, pos, scale=scale)


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *scratch,
                   s_len, block_k, single_kv):
    b_ = pl.program_id(0)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)
    pos_b = pos_ref[b_]
    # Last kv block holding any key visible to this row's queries: the
    # frontier analogue of the training kernel's _last_kv_block(iq).
    last = (pos_b + s_len - 1) // block_k

    def scores():
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_mxu_precision(q_ref.dtype))

        def straddling():
            # Key col (global j*block_k + c) visible to query row i
            # (global pos_b + i) iff k_pos <= q_pos. Padded query rows
            # (i >= s_len) compute garbage the launcher slices off.
            q_pos = pos_b + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            return jnp.where(k_pos <= q_pos, s, NEG_INF)

        # Interior blocks (every key visible to even the FIRST query row)
        # skip the iota/compare/select pass — only the block straddling the
        # frontier pays for masking.
        return jax.lax.cond((j + 1) * block_k - 1 <= pos_b,
                            lambda: s, straddling)

    if single_kv:
        # One kv block: direct softmax, no scratch, no rescale passes.
        s = scores()
        m = jnp.max(s, axis=-1, keepdims=True)
        p = _exp_lowp(s - m, o_ref.dtype)
        pv, l = _pv_rowsum(p, v_ref[0, 0])
        l = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (pv / l).astype(o_ref.dtype)
        return

    acc, m_s, l_s = scratch

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(j <= last)
    def _compute():
        s = scores()
        m_prev = m_s[:, 0:1]
        l_prev = l_s[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = _exp_lowp(s - m_new, o_ref.dtype)
        pv, l_cur = _pv_rowsum(p, v_ref[0, 0])
        l_new = alpha * l_prev + l_cur
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)
        acc[...] = acc[...] * alpha + pv

    # The grid is dense (skipped blocks still step), so the last step
    # always runs and can finalize unconditionally.
    @pl.when(j == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_s[:, 0:1], 1e-30)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)


def _flash_decode_pallas(q, k, v, pos, scale, block_k,
                         name=None):
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    t_kv = k.shape[2]
    n_kv = t_kv // block_k
    # Pre-scale q: one [S, d] pass replaces a [S, T] pass per kernel.
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    pos = pos.astype(jnp.int32)
    # Pad the query dim up to the sublane minimum (decode is S=1); padded
    # rows compute garbage that is sliced off below.
    sub = _sublane(q.dtype)
    s_blk = -(-s // sub) * sub
    if s_blk != s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_blk - s), (0, 0)))

    def kv_index(b_, h_, j, pos_ref):
        # Clamp past-frontier blocks to the last useful one: a repeated
        # block index issues no new DMA, and @pl.when skips the compute.
        last = (pos_ref[b_] + s - 1) // block_k
        return (b_, h_, jnp.minimum(j, last), 0)

    def q_index(b_, h_, j, pos_ref):
        return (b_, h_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, s_blk, d), q_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, s_blk, d), q_index),
        scratch_shapes=[] if n_kv == 1 else [
            pltpu.VMEM((s_blk, d), jnp.float32),
            pltpu.VMEM((s_blk, _STATS_LANES), jnp.float32),
            pltpu.VMEM((s_blk, _STATS_LANES), jnp.float32),
        ],
    )
    out = pallas_mode.kernel_call(
        name or "decode_attn",
        functools.partial(_decode_kernel, s_len=s, block_k=block_k,
                          single_kv=n_kv == 1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s_blk, d), q.dtype),
    )(pos, q, k, v)
    return out[:, :, :s] if s_blk != s else out


# ---------------------------------------------------------------------------
# int8 kernel (family "decode_attention_q8") — the same online-softmax
# program over int8 k/v planes, dequantizing IN-BLOCK: each kv block's
# codes meet their per-position scales in VMEM, so HBM traffic on the
# length dim drops ~4x (int8 codes + one fp32 scale lane vs fp32 rows)
# and the pool stores int8. Frontier clamping, straddle-only masking and
# the scratch accumulator are identical to the fp kernel.
# ---------------------------------------------------------------------------

def _decode_kernel_q8(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                      *scratch, s_len, block_k, single_kv):
    b_ = pl.program_id(0)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)
    pos_b = pos_ref[b_]
    last = (pos_b + s_len - 1) // block_k

    def dequant():
        # In-block dequant: int8 codes * fp32 per-position scales
        # ([block_k, 1] broadcast over [block_k, d]). k stays fp32 into
        # the score GEMM; v casts to the output dtype for _pv_rowsum,
        # matching the fp kernel's operand dtype there.
        k_f = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
        v_f = (v_ref[0, 0].astype(jnp.float32)
               * vs_ref[0, 0]).astype(o_ref.dtype)
        return k_f, v_f

    def scores(k_f):
        s = jax.lax.dot_general(q_ref[0, 0].astype(jnp.float32), k_f,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_mxu_precision(jnp.float32))

        def straddling():
            q_pos = pos_b + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            return jnp.where(k_pos <= q_pos, s, NEG_INF)

        return jax.lax.cond((j + 1) * block_k - 1 <= pos_b,
                            lambda: s, straddling)

    if single_kv:
        k_f, v_f = dequant()
        s = scores(k_f)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = _exp_lowp(s - m, o_ref.dtype)
        pv, l = _pv_rowsum(p, v_f)
        l = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (pv / l).astype(o_ref.dtype)
        return

    acc, m_s, l_s = scratch

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(j <= last)
    def _compute():
        k_f, v_f = dequant()
        s = scores(k_f)
        m_prev = m_s[:, 0:1]
        l_prev = l_s[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = _exp_lowp(s - m_new, o_ref.dtype)
        pv, l_cur = _pv_rowsum(p, v_f)
        l_new = alpha * l_prev + l_cur
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)
        acc[...] = acc[...] * alpha + pv

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_s[:, 0:1], 1e-30)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)


def _flash_decode_q8_pallas(q, k, v, k_scale, v_scale, pos, scale, block_k,
                            name=None):
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    t_kv = k.shape[2]
    n_kv = t_kv // block_k
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    pos = pos.astype(jnp.int32)
    # Scales block along the length dim like k/v, so they need length
    # second-minor too: [B, H, T] -> [B, H, T, 1]. The 1-lane trailing
    # axis pads to a full lane tile in VMEM (the _STATS_LANES trade: a
    # few wasted lanes for a legal layout).
    k_scale = k_scale.astype(jnp.float32)[..., None]
    v_scale = v_scale.astype(jnp.float32)[..., None]
    sub = _sublane(q.dtype)
    s_blk = -(-s // sub) * sub
    if s_blk != s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_blk - s), (0, 0)))

    def kv_index(b_, h_, j, pos_ref):
        last = (pos_ref[b_] + s - 1) // block_k
        return (b_, h_, jnp.minimum(j, last), 0)

    def q_index(b_, h_, j, pos_ref):
        return (b_, h_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, s_blk, d), q_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, 1), kv_index),
            pl.BlockSpec((1, 1, block_k, 1), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, s_blk, d), q_index),
        scratch_shapes=[] if n_kv == 1 else [
            pltpu.VMEM((s_blk, d), jnp.float32),
            pltpu.VMEM((s_blk, _STATS_LANES), jnp.float32),
            pltpu.VMEM((s_blk, _STATS_LANES), jnp.float32),
        ],
    )
    out = pallas_mode.kernel_call(
        name or "decode_attn_q8",
        functools.partial(_decode_kernel_q8, s_len=s, block_k=block_k,
                          single_kv=n_kv == 1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s_blk, d), q.dtype),
    )(pos, q, k, v, k_scale, v_scale)
    return out[:, :, :s] if s_blk != s else out


# ---------------------------------------------------------------------------
# Block selection — autotuner integration (kernel families
# "decode_attention" and "decode_attention_q8"; see ops/autotuner.py and
# tests/perf/autotune_sweep.py)
# ---------------------------------------------------------------------------

def _block_candidates(t_kv):
    return [bk for bk in (128, 256, 512) if bk <= t_kv and t_kv % bk == 0]


def _autotuned_block(shape, dtype, cands, default, arrays=None,
                     family="decode_attention"):
    """Consult the autotuner for a decode block size. ``arrays`` (operand
    concrete values: q, k, v for the fp family; q, codes, codes, scales,
    scales for q8) enables an online sweep under DS_TPU_AUTOTUNE; without
    them (traced engine calls, ``planned_block_k``) only the bundled/user
    tables are consulted. The sweep times the WORST-CASE frontier
    (pos = t - s: every block active) so the tuned tile is the one the
    end of a long generation runs on."""
    from deepspeed_tpu.ops import autotuner

    b, h, s, t_kv, d = shape
    sig = decode_signature(b, h, s, t_kv, d, dtype)
    cand_lists = [[c] for c in cands] if arrays is not None else []

    def make_run(cand):
        (bk,) = cand
        pos = jnp.full((b,), t_kv - s, jnp.int32)
        scale = 1.0 / (d ** 0.5)
        if family == "decode_attention_q8":
            q, kq, vq, ks, vs = arrays[:5]
            jitted = jax.jit(functools.partial(
                _flash_decode_q8_pallas, scale=scale, block_k=int(bk)))

            def run():
                return jitted(q, kq, vq, ks, vs, pos)
        else:
            q, k, v = arrays[:3]
            jitted = jax.jit(functools.partial(
                _flash_decode_pallas, scale=scale, block_k=int(bk)))

            def run():
                return jitted(q, k, v, pos)
        return run

    choice = autotuner.autotune(family, sig, cand_lists,
                                make_run, default=[default])
    bk = int(choice[0] if isinstance(choice, (list, tuple)) else choice)
    # A hand-edited table entry must not break dispatch: reject tiles the
    # kernel cannot take and fall back to the default.
    return bk if bk >= 1 and t_kv % bk == 0 else default


def planned_block_k(b, h, s, t_kv, d, dtype):
    """Table-or-default block_k for a decode shape WITHOUT running a sweep
    (observability; tests are its only caller). None when it cannot take the
    shape at all."""
    if not decode_supported(t_kv):
        return None
    cands = _block_candidates(t_kv)
    default = _DEFAULT_BLOCK_K if _DEFAULT_BLOCK_K in cands else cands[-1]
    return _autotuned_block((b, h, s, t_kv, d), dtype, cands, default)


def resolve_decode_block(q, k, block_k=None, v=None, pos=None, scales=None,
                         family="decode_attention"):
    """The ONE block-selection policy for flash_decode_attention (both
    families): an explicit ``block_k`` (arg or DS_TPU_FLASH_DECODE_BLOCK
    env, for tests and A/B experiments) is honored when legal; otherwise
    the autotuner table / default — with an online sweep when the call is
    eager on TPU and DS_TPU_AUTOTUNE is on (v/pos — plus ``scales`` for
    q8 — supply the sweep operands). Returns None when the shape must
    take the dense fallback."""
    import jax.core

    t_kv = k.shape[2]
    if block_k is None:
        env_bk = os.environ.get("DS_TPU_FLASH_DECODE_BLOCK", "")
        if env_bk:
            block_k = int(env_bk)
    if block_k is not None:
        bk = min(int(block_k), t_kv)
        return bk if bk >= 1 and t_kv % bk == 0 else None
    if not decode_supported(t_kv):
        return None
    b, h, s, d = q.shape
    cands = _block_candidates(t_kv)
    default = _DEFAULT_BLOCK_K if _DEFAULT_BLOCK_K in cands else cands[-1]
    operands = (q, k, v, pos) + (tuple(scales) if scales else ())
    traced = any(isinstance(x, jax.core.Tracer)
                 for x in operands if x is not None)
    arrays = None
    if not traced and not pallas_mode.interpret() and v is not None and pos is not None:
        arrays = (q, k, v) + (tuple(scales) if scales else ())
    return _autotuned_block((b, h, s, t_kv, d), q.dtype, cands, default,
                            arrays=arrays, family=family)


# ---------------------------------------------------------------------------
# On a mesh the kernels launch shard-local through attention.on_shards —
# batch over 'data', heads over 'model', length and head-dim whole; pos is a
# [B] vector split like the batch dim, a page arena [P, H/g, page_len, g*D]
# splits its (stored, packed) heads only, and the queries arrive packed to
# match. Without it XLA would refuse to partition the
# kernel (TPU) or replicate the whole kv pool into every shard.
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------

@hot_path
def flash_decode_attention(q, k, v, pos, scale=None, block_k=None,
                           name=None):
    """Length-aware fused cache attention over a slotted KV plane.

    Args:
      q: [B, H, S, D] query rows; row b's tokens sit at global positions
        ``pos[b] .. pos[b]+S-1`` (S=1 in the decode scan, S=bucket in
        prefill). The row's k/v must ALREADY be written into the plane —
        the convention of models/generation.py's _forward, which writes
        the cache before attending.
      k, v: [B, H, T, D] cache planes; T must be a multiple of BLOCK_MIN
        (128) for the kernel to engage (inference/kv_pool.py pads its
        pool; unsupported T falls back to the dense reference).
      pos: [B] int32 per-row frontiers (pre-write sequence lengths).
      scale: score scale; default 1/sqrt(D).
      block_k: length-dim tile; default consults the autotuner
        ("decode_attention" family). DS_TPU_FLASH_DECODE_BLOCK overrides.
      name: the kernel's name in a trace where the caller is not the decode
        lane (the prefill lane passes ``prefill_attn``); default: the
        family's own (``decode_attn``, ``decode_attn_q8``, ``paged_decode``,
        ``paged_decode_q8``). The same on all four entry points.
    Returns: [B, H, S, D] in q.dtype.
    """
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bk = resolve_decode_block(q, k, block_k=block_k, v=v, pos=pos)
    if bk is None:
        return decode_attention_reference(q, k, v, pos, scale=scale)
    return on_shards(
        functools.partial(_flash_decode_pallas, scale=float(scale),
                          block_k=int(bk), name=name),
        kernel_sharding(q.shape[0], q.shape[1]),
        ("bh", "bh", "bh", "b"), ("bh",))(q, k, v, pos)


@hot_path
def flash_decode_attention_q8(q, k, v, k_scale, v_scale, pos, scale=None,
                              block_k=None, name=None):
    """int8-cache flash decode: same contract as ``flash_decode_attention``
    but k/v are int8 codes with fp32 per-(head, position) scales
    (``quantize_kv``'s output layout, [B, H, T] alongside [B, H, T, D]
    planes). Dequantization happens in-block inside the kernel; shapes
    the kernel cannot take fall back to ``decode_attention_q8_reference``
    (dequantize-then-dense). Autotuned under the "decode_attention_q8"
    family — int8 operands shift the compute/bandwidth balance, so tiles
    are tuned separately from the fp family."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bk = resolve_decode_block(q, k, block_k=block_k, v=v, pos=pos,
                              scales=(k_scale, v_scale),
                              family="decode_attention_q8")
    if bk is None:
        return decode_attention_q8_reference(q, k, v, k_scale, v_scale,
                                             pos, scale=scale)
    return on_shards(
        functools.partial(_flash_decode_q8_pallas, scale=float(scale),
                          block_k=int(bk), name=name),
        kernel_sharding(q.shape[0], q.shape[1]),
        ("bh", "bh", "bh", "bh", "bh", "b"), ("bh",))(
            q, k, v, k_scale, v_scale, pos)


# ---------------------------------------------------------------------------
# Paged kernels (families "decode_attention_paged[_q8]") — block-table
# flash decode over the paged KV pool's page ARENA (inference/kv_pool.py
# paged layout). The arena is [L, P, H/g, page_len, g*D] and each row's
# logical plane is named by an int32 block table [B, n_lp]: logical block j
# of row b lives in arena page ``tbl[b, j]``.
#
# HEADS THAT DO NOT FILL A LANE TILE SHARE ONE (``lane_pack``, PR 30): the
# pool stores ``g = 128 // D`` heads side by side on the 128 lanes, so GPT-2's
# heads of 64 reach these kernels as 8 heads of width 128, the trailing shape
# a head dim of 128 (g = 1: nothing below changes) always had. The launcher
# makes the query block-diagonal, ``[B, H/g, g*S, g*D]`` (``_pack_query``:
# head ``a`` of a group in rows ``a*S ..`` and lanes ``a*D ..``, zeros
# elsewhere, which add exactly nothing to a float32 score), the body attends
# ``H/g`` heads of a whole tile, and the launcher takes each head's rows and
# lanes back out (``_unpack_output``; the other lanes of a row attended a
# neighbour's values and are dropped). In the body a row's query position is
# ``pos + row % S`` and, in the int8 family, its scales are those of the head
# it belongs to (``row // S``): nothing else knows. For S = 1 the ``g`` rows
# sit in the one sublane tile that held one real row and fifteen of padding:
# half the matmuls at full contraction depth, and a page is 512 KB to the
# DMA as it is to the algorithm (at minor dim 64 it was padded to 1 MB).
#
# THE UNIT OF WORK IS ONE PAGE OF ALL HEADS, AND ONLY LIVE PAGES ARE UNITS.
# A grid step that brings one page of one head (16 or 32 KB) costs a
# quarter of a microsecond, six times what the page's bytes do, and a
# page's [H/g, page_len, g*D] block is contiguous in the arena (512 KB at
# GPT-2 355M's shape and at OLMoE's), so a step brings that whole
# block, and the grid is not rows x heads x pages but a WORK LIST
# (``_paged_units``): one entry for every live (row, page) pair, pages
# ``0 .. (pos[b] + S - 1) // page_len`` of each row in order, and NO
# entry for a row with no live page — a freed, frozen slot, told by its
# first table entry being ``paging.TRASH_PAGE`` (which no live row's block
# 0 can be), whose output the engine's scan discards: the kernel never
# visits it and the launcher zeroes its output (a step for it would bring
# its q block in and its output block out, a third of a chat call of
# three live rows in sixteen). The list is made from ``pos`` and the
# table by a few integer operations outside the kernel (identical in every
# layer of a pass, so the compiler keeps one copy), rides scalar prefetch,
# and its LENGTH IS THE GRID: a dynamic bound, so dead pages are not
# stepped over, they do not exist. Pallas's own pipeline brings unit
# t + 1's page in while unit t is attended, across rows too; q, the output
# block and the float32 statistics stay put while the row does. (A loop
# over pages inside the kernel with ``make_async_copy`` from an arena left
# in ``pl.ANY`` was the other form tried: Mosaic refuses to slice an HBM
# ref whose minor dim, GPT-2's 64 until PR 30 packed it, is under a lane
# tile, so it served one family of two; at 128 it was 7% faster a call and
# is open again (PERF.md section 7). The same body on the
# static ``B x n_lp`` grid was 12–14% slower, 2.3 times at three live rows
# of sixteen. See PERF.md, PR 28.)
#
# A UNIT IS K CONSECUTIVE LIVE PAGES OF ITS ROW WHERE A PAGE IS SMALL (PR 39;
# ``_pages_per_unit``, from shapes: K = 1 for the k/v arenas of GPT-2, OLMoE,
# Granite and LFM2, whose page of all stored heads is 512 KB or more; 4 for
# the latent cache's 164 KB page; 8 for a MULTI-QUERY pair, ONE stored head of
# 128, 64 KB a page for k and v together, in the decode scan's call, and 2 in
# the lane's, whose 20 x 128 query rows leave VMEM no room for more). What a grid step costs whatever it
# holds (about half a microsecond: index maps, DMA descriptors, the scalar
# reads, one online-softmax update and the rescale of the accumulator) is
# then paid once for K pages: the arena is an operand K times, each with its
# own page of the unit in its index map, so Pallas's pipeline still brings
# unit t + 1 in while unit t is attended; row b has ``ceil(live[b] / K)``
# units, and the slots of its last unit past its last live page name that
# page again (safe to bring; never attended: the body takes a branch a count
# of live pages, ``lax.switch``, so no matmul runs on a page that is not
# there). The body attends the unit's live pages as one block of keys: a
# score matmul a page, side by side on the lanes, ONE softmax update, a value
# product a page summed into ONE rescale of the accumulator. The other form
# timed, the arena in ``pl.ANY`` and a double-buffered ``make_async_copy`` of
# the unit's live pages, ran within 2.5% of this one at every K (PERF.md, PR
# 39) and is not kept.
#
# The body attends all H heads of the page at once: a batched score
# matmul, one online-softmax update of ``[H, S, 1]`` statistics and a
# batched PV into the ``[H, S, D]`` float32 accumulator, with the dense
# kernels' straddle-only masking (global key positions are
# j * page_len + lane). The LAYER and the page are the block's address, so
# no per-layer value of an arena is ever formed; the int8 family brings
# the page's scales ``[H, page_len]`` (a scale a head of the MODEL, never
# packed) the same way and applies them to the scores and the probabilities
# (positions on the lanes of both), which is the dequantise-then-attend
# arithmetic reassociated.
#
# VMEM, as reckoned for the scoped limit the call is given (v5e: 16 MiB;
# the launcher plans into 12 MiB and leaves the rest to Mosaic's own
# temporaries). Every block but the scratch is double-buffered by the
# pipeline. At S = 128, H = 16, D = 128 in bf16: q and out 0.5 MB each
# (2 MB), k and v 0.5 MB each (2 MB), accumulator 1 MB, statistics
# 2 x 1 MB (a lane tile a row), scores and probabilities 1 MB each: 9 MB.
# The decode scan's S = 1 (a 16-row tile) needs 3 MB. Only a shape past
# the budget gets fewer heads a unit (the largest divisor of H that fits)
# and an outer grid axis over head groups; no cell's does. GROUPED-QUERY
# HEADS (an arena that stores fewer heads than the queries have; Granite
# 4.0-H's 32 over 8) put the ``rep`` query heads of one stored head beside S
# on the sublane axis (``_paged_on_shards``): a page comes in once for all
# of them, S = 1 fills 4 of a 16-row tile's rows where it filled one, and
# the body knows only that row ``r`` sits at position ``r % S``.
# ---------------------------------------------------------------------------

def gather_pages(arena, block_tbl, h, g):
    """Each row's pages as its dense logical plane: one layer's row arena
    ``[P, Hp, page_len, g * D]`` (packed, ``lane_pack``) to
    ``[B, h, n_lp * page_len, D]``, its scale arena ``[P, >= h, page_len]``
    to ``[B, h, n_lp * page_len]``, through one table gather."""
    x = jnp.take(arena, block_tbl, axis=0)         # [B, n_lp, Hp, p, ...]
    x = jnp.moveaxis(x, 2, 1)                      # [B, Hp, n_lp, p, ...]
    x = x.reshape(x.shape[:2] + (-1,) + x.shape[4:])
    if x.ndim == 3:
        return x[:, :h]
    return unpack_heads(x, g, h)


@hot_path
def decode_attention_paged_reference(q, k, v, block_tbl, pos, scale=None,
                                     block=1, window=None):
    """Paged ground truth: gather each row's pages into its dense
    logical plane, then the dense reference — the same math the engine's
    einsum (flag-off) path computes, so kernel-on and kernel-off paged
    serving agree bit-for-bit.

    q: [B, H, S, D]; k, v: page arenas as the pool stores one layer of
    them, [P, ceil(H / g), page_len, g * D] (``lane_pack``; [P, H,
    page_len, D] is g = 1); block_tbl: [B, n_lp] int32; pos: [B] int32
    frontiers."""
    h, g = q.shape[1], k.shape[-1] // q.shape[-1]
    rep = query_group(k, h, q.shape[-1])
    k, v = (gather_pages(a, block_tbl, h // rep, g) for a in (k, v))
    if rep > 1:
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    return decode_attention_reference(q, k, v, pos, scale=scale, block=block,
                                      window=window)


@hot_path
def decode_attention_paged_q8_reference(q, k, v, k_scale, v_scale,
                                        block_tbl, pos, scale=None):
    """int8 paged ground truth: gather codes AND scales through the
    table, dequantize, then the dense reference."""
    h, g = q.shape[1], k.shape[-1] // q.shape[-1]
    kf, vf = (dequantize_kv(gather_pages(c, block_tbl, h, g),
                            gather_pages(sc, block_tbl, h, g), q.dtype)
              for c, sc in ((k, k_scale), (v, v_scale)))
    return decode_attention_reference(q, kf, vf, pos, scale=scale)


def _div(a, b):
    """``a // b`` and ``a % b`` for a frontier (never negative) and a static
    size, as ONE machine operation each: ``//`` and ``%`` round towards
    minus infinity through a sign correction that Pallas traces and lowers
    anew at every use, in every index map of every call: 2.5 ms apiece,
    seconds of set-up over a step's 48 appends."""
    return jax.lax.div(a, jnp.int32(b))


def _rem(a, b):
    return jax.lax.rem(a, jnp.int32(b))


def _whole_arena(layer, *arenas):
    """(layer, arenas) as the launchers index them: ``[L, P, heads, ...]``
    and a static layer. ``layer`` None means the caller holds ONE layer's
    ``[P, heads, ...]`` arena; a leading unit dim is a bitcast, not a
    copy."""
    if layer is None:
        return 0, tuple(a[None] for a in arenas)
    return int(layer), arenas


# Scoped VMEM the launcher plans a unit's blocks into (see the reckoning
# above): three quarters of the v5e's 16 MiB default limit.
_PAGED_VMEM_BUDGET = 12 * 2 ** 20


def _paged_heads_per_unit(h, s_blk, page_len, d, q_dtype, kv_dtype, pack=1,
                          k_pages=1):
    """Heads one unit of the paged kernel attends: all ``h`` of the call
    (a shard's, under tensor parallelism; packed heads of width ``d`` where
    ``pack`` > 1), or the largest divisor of ``h`` whose blocks stay inside
    ``_PAGED_VMEM_BUDGET`` with ``k_pages`` pages a unit. From shapes and
    dtypes alone; the minor dim of every block pads to a lane tile."""
    def lanes(n):
        return -(-n // LANES) * LANES

    q_b, kv_b = jnp.dtype(q_dtype).itemsize, jnp.dtype(kv_dtype).itemsize
    keys = k_pages * page_len
    per_head = (2 * 2 * s_blk * lanes(d) * q_b          # q, out
                + 2 * 2 * keys * lanes(d) * kv_b        # k, v
                + s_blk * lanes(d) * 4                  # accumulator
                + 2 * s_blk * _STATS_LANES * 4          # m, l
                + 2 * s_blk * lanes(keys) * 4)          # scores, probs
    if kv_b == 1:                    # the codes as matmul operands
        per_head += 2 * keys * lanes(d) * q_b
    fit = _PAGED_VMEM_BUDGET // per_head
    if h <= fit:
        return h
    # A group of int8 heads is the sublane dim of its scale block, which
    # holds a scale for each of a packed head's ``pack`` heads.
    groups = [g for g in range(1, h) if h % g == 0 and g <= fit
              and (kv_b > 1 or g * pack % 8 == 0)]
    if groups:
        return max(groups)
    # No group fits (ONE stored head under multi-query rows: 20 x 128 query
    # rows in the lane at eight pages a unit): at one page a unit the heads
    # are the unit whatever it takes; at more, 0 says so, and
    # ``_pages_per_unit`` joins fewer pages.
    return h if k_pages == 1 else 0


# A unit that already reads near its bound: one page of OLMoE's 16 heads of
# 128, keys and values (1 MiB: 1.42 us against 1.28 us of stream). What a
# grid step costs whatever it holds (index maps, DMA descriptors, a scalar
# read of the lists, one online-softmax update and a rescale of the
# accumulator) and the loading of a matmul's stationary tiles are paid a
# UNIT, so a unit far under this size (a page of a latent cache's one
# stored head: 164 KB) joins K consecutive pages of its row and stays under
# it. 512 KB (GPT-2's, Granite's) would reach it exactly at K = 2 and stays
# one page a unit.
_UNIT_BYTES = 2 ** 20


def _pages_per_unit(page_bytes, n_lp, fits):
    """K, the consecutive logical pages of one row that one unit joins: the
    largest of 1, 2, 4, 8 for which K pages' blocks (``page_bytes`` each,
    all arenas together) stay under ``_UNIT_BYTES``, a row's table holds as
    many, and ``fits(K)`` (the VMEM reckoning keeps the heads a unit has at
    one page). From shapes and dtypes alone."""
    return max(k for k in (1, 2, 4, 8) if k == 1 or (
        k <= n_lp and k * page_bytes < _UNIT_BYTES and fits(k)))


def _times(a, k):
    """``a * k`` for a static ``k``; at 1 the value itself, so that a unit
    of one page traces the program it always has."""
    return a if k == 1 else a * k


def _ceil_div(a, k):
    return a if k == 1 else _div(a + (k - 1), k)


def _paged_unit(h, n_rows, d, arenas, n_lp, q_dtype, pack=1, rep=1,
                latent=0):
    """What one unit of a paged call holds, ``(heads, pages)``, from the
    launcher's shapes and dtypes alone: ``q`` is ``[B, h, n_rows, d]``,
    ``arenas`` (anything with a shape and a dtype) whole,
    ``[L, P, heads, page_len, ..]``. A latent call's unit is ONE group of
    its query heads (``rep`` of them: ``_latent_heads_per_unit``'s, the
    caller's); the other families' is ``_paged_heads_per_unit``'s. The
    pages are ``_pages_per_unit``'s, and more than one only where ALL the
    call's heads are one unit and stay one: a call whose heads go in groups
    (a lane's) fills VMEM with its rows and has work enough a page."""
    page_len = arenas[0].shape[3]
    if latent:
        def heads(k):
            return _latent_heads_per_unit(h * rep, n_rows // rep, page_len,
                                          d, latent, q_dtype, k)
        hb, every = 1, h * rep
    else:
        sub = _sublane(q_dtype)

        def heads(k):
            return _paged_heads_per_unit(h, -(-n_rows // sub) * sub,
                                         page_len, d, q_dtype,
                                         arenas[0].dtype, pack, k)
        hb, every = heads(1), h
    # a page's block: the unit's heads of each arena (a latent cache's one)
    page_bytes = sum(
        (1 if latent else hb * (a.shape[2] // h)) * math.prod(a.shape[3:])
        * jnp.dtype(a.dtype).itemsize for a in arenas)
    return hb, _pages_per_unit(page_bytes, n_lp, lambda k: heads(k) == every)


def unit_pages(arenas, heads, head_dim, n_lp, q_dtype, s_len=1, latent=0):
    """K as the launchers resolve it for a pool's arenas (whole, as the pool
    stores them: ``k, v[, k_scale, v_scale]``, or a latent cache's one) and
    a call of ``s_len`` query positions a row over ``heads`` heads: what the
    engine reports as ``kv_unit_pages``."""
    page_len = arenas[0].shape[3]
    if latent:
        w = arenas[0].shape[-1]
        hg = _latent_heads_per_unit(heads, s_len, page_len, w, latent,
                                    q_dtype)
        return _paged_unit(heads // hg, hg * s_len, w, arenas, n_lp, q_dtype,
                           rep=hg, latent=latent)[1]
    g = arenas[0].shape[-1] // head_dim
    rep = query_group(arenas[0], heads, head_dim)
    return _paged_unit(-(-(heads // rep) // g), g * rep * s_len,
                       g * head_dim, arenas, n_lp, q_dtype, g, rep)[1]


def _paged_units(tbl, pos, s_len, page_len, k_pages=1):
    """The paged kernel's work list, from the table and the frontiers.

    Returns ``(rows, us, pages, live, n)``: unit t is the ``us[t]``-th of
    row ``rows[t]`` and attends its logical pages ``K * us[t] ..
    K * us[t] + K - 1`` (``K = k_pages``), which are arena pages
    ``pages[K * t .. K * t + K - 1]``: a slot past the row's last live page
    names that last live page again (safe to bring; the body attends a
    unit's live pages only). Row b has ``live[b]`` live pages and
    ``ceil(live[b] / K)`` units, in order, so a freed row (``live`` 0) has
    none; ``n`` units in all, and one (the last row's, which then has no
    live page) where no row has any. The lists are ``B * ceil(n_lp / K)``
    units long and repeat the last unit past ``n``. Dense compares and sums
    over ``[units, B]``: one or two fusions, no loop."""
    from deepspeed_tpu.inference.paging import TRASH_PAGE

    b, n_lp = tbl.shape
    last = _div(pos + (s_len - 1), page_len)
    live = jnp.where(tbl[:, 0] == TRASH_PAGE, 0, jnp.minimum(last + 1, n_lp))
    units = _ceil_div(live, k_pages)
    r = jnp.arange(b, dtype=jnp.int32)
    ends = jnp.sum(jnp.where(r[None, :] <= r[:, None], units[None, :], 0),
                   axis=1)                              # inclusive prefix sum
    n = jnp.maximum(ends[b - 1], 1)
    t = jnp.minimum(jnp.arange(b * -(-n_lp // k_pages), dtype=jnp.int32),
                    n - 1)
    before = t[:, None] >= ends[None, :]                # rows wholly before t
    rows = jnp.minimum(jnp.sum(before.astype(jnp.int32), axis=1), b - 1)
    us = t - jnp.sum(jnp.where(before, units[None, :], 0), axis=1)
    if k_pages == 1:
        return rows, us, jnp.take(tbl.reshape(-1), rows * n_lp + us), live, n
    js = jnp.minimum(
        us[:, None] * k_pages + jnp.arange(k_pages, dtype=jnp.int32),
        jnp.maximum(jnp.take(live, rows) - 1, 0)[:, None])
    pages = jnp.take(tbl.reshape(-1), rows[:, None] * n_lp + js)
    return rows, us, pages.reshape(-1), live, n


def _paged_kernel(rows_ref, us_ref, pages_ref, pos_ref, live_ref, q_ref,
                  *refs, s_len, q8, single_kv, pack, rep=1, latent=0,
                  k_pages=1, block=1, window=None):
    """One grid step = one unit of ``_paged_units``: ``k_pages`` consecutive
    pages of all the heads (of the group, where all do not fit) of one row,
    a block each (``refs`` holds each arena's ``k_pages`` blocks in turn),
    attended as ONE block of ``k_pages * page_len`` keys: one online-softmax
    update and one rescale of the accumulator a unit. Against a packed
    arena (``pack`` heads a lane tile) a head of the block is ``pack`` heads
    of the model: query row ``r`` is row ``r % s_len`` of head
    ``r // s_len`` of them (``_pack_query``). Grouped-query heads put the
    ``rep`` query heads of a stored head beside ``s_len`` the same way: a
    stored head's rows are ``rep * s_len``, row ``r`` still at position
    ``r % s_len``. ``latent`` > 0 (``latent_decode``): ONE arena, whose page
    is the keys and, in its first ``latent`` lanes, the values. ``block``:
    ``visible_upto``'s (1: causal); the rows of a call start at a block's
    first position and a block never straddles a page, so the live pages and
    the interior ones are the causal rule's. ``window``: ``visible_from``'s
    (None: no lower bound); a unit that holds a key behind the LAST query
    row's window straddles too, at its low end."""
    n_a = 1 if latent else 4 if q8 else 2
    k_refs, *more = (refs[a * k_pages:(a + 1) * k_pages] for a in range(n_a))
    v_refs, scale_refs = (None, ()) if latent else (more[0], more[1:])
    o_ref, stats = refs[n_a * k_pages], refs[n_a * k_pages + 1:]
    page_len = k_refs[0].shape[1]
    t = pl.program_id(1)
    row, u = rows_ref[t], us_ref[t]
    pos_b, n_live = pos_ref[row], live_ref[row]
    j = _times(u, k_pages)                      # the unit's first page

    def attend(n):
        # the unit's first ``n`` pages, the live ones
        q = q_ref[0]                                   # [hb, s_blk, d]
        ks = [ref[...] for ref in k_refs[:n]]          # [hb, page_len, d]
        vs = [k[:, :, :latent] for k in ks] if latent \
            else [ref[...] for ref in v_refs[:n]]
        if q8:
            # The codes are exact in the query's dtype; a key's scale
            # multiplies its score, a value's its probability.
            ks = [k.astype(q.dtype) for k in ks]
            vs = [v.astype(o_ref.dtype) for v in vs]
        shape = q.shape[:2] + (page_len,)              # a page's scores

        def row_scales(ref):
            # [hb * pack, page_len] scales, one a head of the model and
            # key, as a score's [hb, s_blk, page_len]: a row takes the
            # scales of the head it belongs to.
            if pack == 1:
                return ref[...][:, None, :]
            row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            out = ref[pl.ds(0, shape[0], stride=pack), :][:, None, :]
            for a in range(1, pack):
                out = jnp.where(
                    row >= a * s_len * rep,
                    ref[pl.ds(a, shape[0], stride=pack), :][:, None, :],
                    out)
            return out

        def scores(i):
            s = jax.lax.dot_general(q, ks[i], (((2,), (2,)), ((0,), (0,))),
                                    preferred_element_type=jnp.float32,
                                    precision=_mxu_precision(q.dtype))
            return s * row_scales(scale_refs[0][i]) if q8 else s

        # The pages' scores side by side on the lanes, each a whole tile.
        s = scores(0) if n == 1 else jnp.concatenate(
            [scores(i) for i in range(n)], axis=2)

        def straddling():
            # Key col (global j*page_len + c) visible to query row i
            # (global pos_b + i) iff k_pos <= q_pos. Padded query rows
            # (i >= s_len) compute garbage the launcher slices off.
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if pack * rep > 1:
                row = _rem(row, s_len)
            q_pos = pos_b + row
            k_pos = j * page_len + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 2)
            return jnp.where(visible(k_pos, q_pos, block, window), s, NEG_INF)

        # Interior pages (every key visible to even the FIRST query row,
        # and under a window to the LAST) skip the iota/compare/select pass.
        interior = (j + n) * page_len - 1 <= pos_b
        if window is not None:
            interior &= j * page_len >= visible_from(pos_b + (s_len - 1),
                                                     window)
        s = jax.lax.cond(interior, lambda: s, straddling)

        def times_v(p, v):
            return jax.lax.dot_general(p.astype(v.dtype), v,
                                       (((2,), (1,)), ((0,), (0,))),
                                       preferred_element_type=jnp.float32,
                                       precision=_mxu_precision(v.dtype))

        def over_pages(p, product):
            # the sum over the pages of ``product(p's lanes of page i,
            # i)``: a page's keys are its own rows of the value product
            if n == 1:
                return product(p, 0)
            return functools.reduce(jnp.add, [
                product(p[:, :, i * page_len:(i + 1) * page_len], i)
                for i in range(n)])

        def pv_and_rowsum(p):
            if q8:
                # p is scaled by the values' scales before the matmul, so
                # the row-sum is taken from p itself.
                l = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
                return over_pages(p, lambda p_i, i: times_v(
                    p_i.astype(jnp.float32) * row_scales(scale_refs[1][i]),
                    vs[i])), l
            if latent:
                # the values are whole lane tiles already: the row-sum of
                # the probabilities as the matmul sees them, and no
                # ``[v | 1]`` a tile wider
                return over_pages(p, lambda p_i, i: times_v(p_i, vs[i])), \
                    jnp.sum(p.astype(vs[0].dtype).astype(jnp.float32),
                            axis=-1, keepdims=True)
            # p @ [v | 1]: the row-sum rides the PV matmul, as in
            # ``_pv_rowsum``, and shares p's rounding with the numerator.
            d = vs[0].shape[2]
            pv = over_pages(p, lambda p_i, i: times_v(p_i, jnp.concatenate(
                [vs[i], jnp.ones(vs[i].shape[:2] + (1,), vs[i].dtype)],
                axis=2)))
            return pv[:, :, :d], pv[:, :, d:d + 1]

        if single_kv:
            # One page a plane: direct softmax, no scratch, no rescale.
            m = jnp.max(s, axis=-1, keepdims=True)
            pv, l = pv_and_rowsum(_exp_lowp(s - m, o_ref.dtype))
            o_ref[0] = (pv / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
            return
        acc, m_s, l_s = stats

        @pl.when(u == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)
            m_s[...] = jnp.full_like(m_s, NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)

        m_prev, l_prev = m_s[:, :, 0:1], l_s[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pv, l_cur = pv_and_rowsum(_exp_lowp(s - m_new, o_ref.dtype))
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(alpha * l_prev + l_cur, l_s.shape)
        acc[...] = acc[...] * alpha + pv

        @pl.when(u == _ceil_div(n_live, k_pages) - 1)
        def _finalize():
            l = jnp.maximum(l_s[:, :, 0:1], 1e-30)
            o_ref[0] = (acc[...] / l).astype(o_ref.dtype)

    # Only a batch with no live row at all has a unit without a live page
    # (the list is never empty); the launcher zeroes every dead row's output.
    @pl.when(n_live > 0)
    def _some():
        if k_pages == 1:
            return attend(1)
        # A row's last unit may hold fewer live pages than ``k_pages`` (the
        # blocks past them hold its last live page again): a branch a count,
        # so no matmul runs on a page that is not there.
        jax.lax.switch(jnp.minimum(n_live - j, k_pages) - 1,
                       [functools.partial(attend, n)
                        for n in range(1, k_pages + 1)])


def _paged_launch(name, q, arenas, tbl, pos, scale, layer, pack=1, rep=1,
                  latent=0, block=1, window=None):
    """The one launcher of the paged families: ``arenas`` is (k, v) or
    (k, v, k_scale, v_scale), whole or one layer's (``layer`` None), or the
    ONE arena of a latent cache (``latent`` > 0: its value width; ``q`` is
    then ``[B, G, rows, W]``, ``G`` groups of query heads that all read the
    arena's one stored head, a group a unit). With
    ``pack`` > 1 the arenas are packed and ``q`` is ``_pack_query``'s:
    ``[B, Hp, pack * S, pack * D]`` against ``[.., Hp, page_len, pack * D]``,
    which the body attends as ``Hp`` heads of a whole lane tile. With
    ``rep`` > 1 (grouped-query heads) ``q`` holds a stored head's ``rep``
    query heads on its row axis, ``[B, Hkv, rep * S, D]``. How many pages a
    unit joins is ``_pages_per_unit``'s, from these shapes."""
    from jax.experimental.pallas import tpu as pltpu

    layer, arenas = _whole_arena(layer, *arenas)
    b, h, n_rows, d = q.shape
    s = n_rows // (pack * rep)       # query positions a row of the batch
    page_len = arenas[0].shape[3]
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    sub = _sublane(q.dtype)
    s_blk = -(-n_rows // sub) * sub
    if s_blk != n_rows:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_blk - n_rows), (0, 0)))
    hb, k_pages = _paged_unit(h, n_rows, d, arenas, tbl.shape[1], q.dtype,
                              pack, rep, latent)
    d_out = latent or d
    pos = pos.astype(jnp.int32)
    rows, us, pages, live, n_units = _paged_units(
        tbl.astype(jnp.int32), pos, s, page_len, k_pages)
    units = (rows, us, pages, pos, live)      # the scalar-prefetch operands

    def q_index(g, t, rows_ref, *_):
        return (rows_ref[t], g, 0, 0)

    def page_spec(arena, i):
        # The unit's i-th page: [hb, page_len, d] of a row arena,
        # [hb * pack, page_len] of a scale arena (a scale a head of the
        # model).
        zeros = (0,) * (arena.ndim - 3)

        def page(pages_ref, t):
            return pages_ref[t if k_pages == 1 else t * k_pages + i]

        if latent:      # every group of query heads reads the one head
            return pl.BlockSpec(
                (None, None, 1) + arena.shape[3:],
                lambda g, t, rows_ref, us_ref, pages_ref, *_:
                (layer, page(pages_ref, t), 0) + zeros)
        return pl.BlockSpec(
            (None, None, hb * (arena.shape[2] // h)) + arena.shape[3:],
            lambda g, t, rows_ref, us_ref, pages_ref, *_:
            (layer, page(pages_ref, t), g) + zeros)

    single_kv = tbl.shape[1] == 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(units),
        # Head groups outermost: a row's units stay consecutive, so its
        # output block and the statistics live from its first to its last.
        grid=(h // hb, n_units),
        in_specs=[pl.BlockSpec((1, hb, s_blk, d), q_index)]
        + [page_spec(a, i) for a in arenas for i in range(k_pages)],
        out_specs=pl.BlockSpec((1, hb, s_blk, d_out), q_index),
        scratch_shapes=[] if single_kv else [
            pltpu.VMEM((hb, s_blk, d_out), jnp.float32),
            pltpu.VMEM((hb, s_blk, _STATS_LANES), jnp.float32),
            pltpu.VMEM((hb, s_blk, _STATS_LANES), jnp.float32),
        ],
    )
    out = pallas_mode.kernel_call(
        name,
        functools.partial(_paged_kernel, s_len=s, q8=len(arenas) == 4,
                          single_kv=single_kv, pack=pack, rep=rep,
                          latent=latent, k_pages=k_pages, block=block,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s_blk, d_out), q.dtype),
    )(*units, q, *(a for a in arenas for _ in range(k_pages)))
    # A freed row has no unit, so the kernel never wrote its block: zeros,
    # in a select that fuses into whatever reads the output.
    out = jnp.where((live > 0)[:, None, None, None], out, 0)
    return out[:, :, :n_rows] if s_blk != n_rows else out


def _flash_decode_paged_pallas(q, k, v, tbl, pos, scale,
                               name=None, layer=None, pack=1, rep=1, block=1,
                               window=None):
    return _paged_launch(name or "paged_decode", q, (k, v), tbl, pos, scale,
                         layer, pack, rep, block=block, window=window)


def _flash_decode_paged_q8_pallas(q, k, v, k_scale, v_scale, tbl, pos,
                                  scale, name=None, layer=None, pack=1,
                                  rep=1):
    return _paged_launch(name or "paged_decode_q8", q,
                         (k, v, k_scale.astype(jnp.float32),
                          v_scale.astype(jnp.float32)),
                         tbl, pos, scale, layer, pack, rep)


def _paged_on_shards(launch, q, arenas, block_tbl, pos, scale, name, layer,
                     **static):
    """Both paged families' way to their launcher: ``g`` heads a lane tile
    is read from the shapes, the queries are packed to match the arena and
    each head's output taken back out; on a mesh the PACKED heads are what
    'model' splits, of the queries and of the arenas alike. GROUPED-QUERY
    HEADS are read from the shapes too: an arena that stores fewer heads
    than ``q`` has (``rep`` query heads a stored head, query head ``j``
    reading stored head ``j // rep``) gets them as ``[B, Hkv, rep * S, D]``,
    a stored head's queries beside ``S`` on the sublane axis, so a page is
    brought in once for all of them."""
    b, h, s_len, d = q.shape
    g = arenas[0].shape[-1] // d
    rep = query_group(arenas[0], h, d)
    if rep > 1:
        q = q.reshape(b, h // rep, rep * s_len, d)
    q = _pack_query(q, g)
    arena = "-h" if layer is None else "--h"
    out = on_shards(
        functools.partial(launch, scale=float(scale), name=name, layer=layer,
                          pack=g, rep=rep, **static),
        kernel_sharding(q.shape[0], q.shape[1]),
        ("bh",) + (arena,) * len(arenas) + ("b", "b"), ("bh",))(
            q, *arenas, block_tbl, pos)
    return _unpack_output(out, g, h // rep).reshape(b, h, s_len, d)


@hot_path
def flash_decode_attention_paged(q, k, v, block_tbl, pos, scale=None,
                                 name=None, layer=None, block=1, window=None):
    """Block-table flash decode over a page arena.

    Args:
      q: [B, H, S, D] query rows at per-row frontiers ``pos``; each
        row's k/v for those positions must already be written into its
        pages (models/generation.py writes before attending).
      k, v: the paged pool's arenas WHOLE, as it stores them:
        [L, P, ceil(H / g), page_len, g * D] with ``g = lane_pack(D, H)``
        heads a lane tile ([L, P, H, page_len, D] at a head dim that fills
        one; ``g`` is read back as ``k.shape[-1] // D``), with ``layer``
        naming the layer to attend — the serving path: the kernel's index
        map picks the layer, so no per-layer value of the arena is ever
        formed. With ``layer`` None, one layer's [P, .., page_len, g * D]
        arena. Page 0 is the trash page freed rows point at.
      block_tbl: [B, n_lp] int32 — row b's logical block j lives in
        arena page ``block_tbl[b, j]``.
      pos: [B] int32 per-row frontiers.
      scale: score scale; default 1/sqrt(D).
      layer: static int, or None (see k, v).
      block: static; ``visible_upto``'s block of positions (1: causal). Past
        1 every ``pos`` is a block's first position, ``S`` and the page
        length whole blocks.
      window: static; ``visible_from``'s window (None: none). The pages
        before a row's window are still units of the call: a caller that
        wants them skipped hands a table and frontiers that START at the
        window's first page (``window_decode``).

    block_k is page_len by construction (kernel blocks == pages, all
    heads of a page a step), so there is no autotuned tile here;
    page_len must be a multiple of BLOCK_MIN for the kernel to engage,
    and other page sizes take the gather + dense-reference fallback
    (same math). A row whose first table entry is the trash page (a
    freed slot) is not attended: its output is zeros.
    Returns: [B, H, S, D] in q.dtype.
    """
    d = q.shape[-1]
    page_len = k.shape[-2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if not decode_supported(page_len):
        if layer is not None:
            k, v = k[layer], v[layer]
        return decode_attention_paged_reference(q, k, v, block_tbl, pos,
                                                scale=scale, block=block,
                                                window=window)
    return _paged_on_shards(_flash_decode_paged_pallas, q, (k, v), block_tbl,
                            pos, scale, name, layer, block=block,
                            window=window)


# ---------------------------------------------------------------------------
# A window layer's RING OF PAGES (kernel "window_decode"; the lane's call is
# "window_prefill"). A layer whose queries see the last ``window`` positions
# only keeps a FIXED ring of ``n_ring`` pages a slot, whatever the context:
# logical page ``lp`` (positions ``lp * page_len ..``) lives at ring place
# ``lp % n_ring``, physical page ``ring_tbl[b, lp % n_ring]`` of an arena laid
# out like any paged arena (page 0 the trash page; a freed row's ring table is
# all trash, so the work lists below skip it as they skip a freed row of the
# full group). Nothing in the kernels' bodies knows a ring: both calls are
# handed COORDINATES IN WHICH THE RING IS A PLAIN TABLE.
#
# - The append (``kv_append_ring``) takes the frontier modulo the ring's span
#   and the ring's table with its first place repeated at the end, so a write
#   that straddles the ring's last page lands its tail on place 0:
#   ``kv_append`` as it stands, the same bytes at the same (page, offset).
# - The read (``window_decode``) takes the table ROTATED to start at the first
#   page that holds a key some query row of the call still sees (``ring_view``:
#   ``first = max(pos - window + 1, 0) // page_len``) and the frontiers less
#   ``first * page_len``. Both ends of the mask are differences of positions
#   (``visible``), so the shift changes no score; the units of a row are its
#   ``(pos + S - 1) // page_len - first + 1`` pages, never more than
#   ``n_ring``, and no page wholly behind the window is brought in.
#
# STALE ENTRIES ARE MASKED BY POSITION, never trusted to be zero: a ring place
# holds whatever was last written there (this request's page ``lp - n_ring``,
# or another request's keys), and a key's position is REBUILT from the logical
# page the walk is on, so a place not yet overwritten reads as positions past
# the frontier (masked above) and the first page's keys behind the window are
# masked below. ``ring_pages`` sizes the ring so that a call's write never
# lands on a page its own read still needs.
# ---------------------------------------------------------------------------

def ring_pages(window, page_len, s_len=1):
    """``n_ring``: pages a ring holds so that a call of up to ``s_len`` query
    positions a row (1 in the decode scan; the lane's slice) finds every key
    of ``[pos - window + 1, pos + s_len - 1]`` after its own write: a span
    of ``window + s_len - 1`` positions that starts anywhere in a page
    touches ``ceil((window + s_len - 2) / page_len) + 1`` pages (5 at a
    window of 512 over pages of 128 for a decode step, 6 with a lane slice
    of up to 128)."""
    return -(-(window + s_len - 2) // page_len) + 1


def ring_table(slots, n_ring, live):
    """Each row's ring ``[B, n_ring]``: row b of slot ``slots[b]`` owns
    physical pages ``1 + slots[b] * n_ring ..`` of the window arena; a row
    that is not ``live`` (a freed slot: its full-group table starts on the
    trash page) gets the trash page throughout."""
    from deepspeed_tpu.inference.paging import TRASH_PAGE

    tbl = 1 + slots[:, None] * n_ring + jnp.arange(n_ring, dtype=jnp.int32)
    return jnp.where(live[:, None], tbl, TRASH_PAGE).astype(jnp.int32)


def ring_view(ring_tbl, pos, window, page_len):
    """(table, frontiers) in which a call that starts at ``pos`` reads its
    ring as a plain paged row (the block comment above): the ring's places
    in the order of the logical pages from the window's first, and ``pos``
    counted from that page's first position."""
    n_ring = ring_tbl.shape[1]
    pos = pos.astype(jnp.int32)
    first = _div(jnp.maximum(pos - (window - 1), 0), page_len)
    places = _rem(first[:, None] + jnp.arange(n_ring, dtype=jnp.int32),
                  n_ring)
    return jnp.take_along_axis(ring_tbl, places, axis=1), \
        pos - first * page_len


@hot_path
def window_decode(q, k, v, ring_tbl, pos, window, scale=None, name=None,
                  layer=None):
    """Sliding-window attention over a ring of pages: ``q`` [B, H, S, D] at
    frontiers ``pos`` (their keys already appended), ``k, v`` the WINDOW
    arenas whole with a static ``layer`` (or one layer's, ``layer`` None),
    ``ring_tbl`` [B, n_ring]. The paged body on ``ring_view``'s coordinates
    with the mask's lower bound, under a kernel name of its own (readers that
    match ``paged_decode`` at the start multiply by a FULL context); pages
    that are no kernel block take the gather and the reference."""
    tbl, shifted = ring_view(ring_tbl, pos, window, k.shape[-2])
    return flash_decode_attention_paged(
        q, k, v, tbl, shifted, scale=scale, name=name or "window_decode",
        layer=layer, window=window)


@hot_path
def kv_append_ring(arenas, new, ring_tbl, pos, layer):
    """``kv_append`` into a ring: row b's ``S`` new positions land at ring
    places ``(pos[b] + s) // page_len % n_ring``, wrapping (the block comment
    above). ``arenas``: the window group's, whole."""
    page_len = arenas[0].shape[3]
    span = ring_tbl.shape[1] * page_len
    closed = jnp.concatenate([ring_tbl, ring_tbl[:, :1]], axis=1)
    pos = pos.astype(jnp.int32)
    s = new[0].shape[2]
    for lo in range(0, s, page_len):          # at most a page's rows a call
        part = tuple(jax.lax.slice_in_dim(x, lo, min(lo + page_len, s),
                                          axis=2) for x in new)
        arenas = kv_append(arenas, part, closed, _rem(pos + lo, span), layer)
    return arenas


@hot_path
def flash_decode_attention_paged_q8(q, k, v, k_scale, v_scale, block_tbl,
                                    pos, scale=None, name=None, layer=None):
    """int8 block-table flash decode: ``flash_decode_attention_paged``
    over int8 code arenas with fp32 per-(head, position) scale arenas
    ([L, P, g * ceil(H / g), page_len]: a scale a head of the model, never
    packed, beside [L, P, ceil(H / g), page_len, g * D] codes and a static
    ``layer``, or one layer's with ``layer`` None), dequantizing in-block
    exactly like the dense q8 family."""
    d = q.shape[-1]
    page_len = k.shape[-2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if not decode_supported(page_len):
        if layer is not None:
            k, v, k_scale, v_scale = (a[layer]
                                      for a in (k, v, k_scale, v_scale))
        return decode_attention_paged_q8_reference(
            q, k, v, k_scale, v_scale, block_tbl, pos, scale=scale)
    return _paged_on_shards(_flash_decode_paged_q8_pallas, q,
                            (k, v, k_scale, v_scale), block_tbl, pos, scale,
                            name, layer)


# ---------------------------------------------------------------------------
# Latent attention over the paged pool (kernel "latent_decode"): the same
# body and launcher on a cache that is not a k/v pair. A token stores ONE
# head of width W (DeepSeek-V3: [c_kv (512) | k_r (64) | zeros to 640]) and
# all H query heads read it, so the arena is ``[L, P, 1, page_len, W]`` and
# there is no value arena: a page's first ``rank`` lanes ARE its values. A
# UNIT IS A RUN OF UP TO FOUR CONSECUTIVE LIVE PAGES OF ONE ROW (PR 39): one
# page of the one stored head is 164 KB, 0.2 us of stream and 0.19 us of
# matmul under a grid step's half microsecond, so the decode scan's call
# joins K = 4 (656 KB a unit, under the 1 MiB unit that reads near its bound;
# ``_pages_per_unit``), a row of ten live pages is three units where it was
# ten, and the kernel alone runs 0.64 ms a call where it ran 1.10 (K = 8:
# 0.60, past the rule's size). To
# the body this is grouped-query attention taken to its end: the ``H`` query
# heads of the one stored head sit beside S on the sublane axis (H = 128 and
# S = 1 make a full 128-row tile of the MXU where ``paged_decode`` has 1 to
# 4 rows), the score contracts over W and the value product over the page's
# first ``rank`` lanes, both whole lane tiles, so ``v`` is a lane-aligned
# slice of the block ``k`` came in as and costs no copy. 2 x H x (W + rank)
# FLOP for 2 W bytes a cached token: 242 FLOP a byte at 576 + 512 against
# the v5e's 240, where ``paged_decode`` is 1. For a lane of S = 128 the
# 16,384 rows would need 40 MB of VMEM, so the query heads go in GROUPS (the
# launcher's outer grid axis, the largest divisor of H whose blocks fit
# ``_PAGED_VMEM_BUDGET``: 8 heads at S = 128), each group a pass over the
# row's pages, a page a unit (1,024 rows are work enough a page, and VMEM is
# full of them).
# ---------------------------------------------------------------------------

def _latent_heads_per_unit(h, s_len, page_len, w, rank, dtype, k_pages=1):
    """Query heads one unit of ``latent_decode`` attends: the largest
    divisor of ``h`` whose rows' blocks stay in ``_PAGED_VMEM_BUDGET`` (q
    and out double-buffered, the float32 accumulator, statistics, scores
    and probabilities over the unit's ``k_pages`` pages; the pages
    themselves, double-buffered, are small beside them)."""
    b = jnp.dtype(dtype).itemsize
    keys = k_pages * page_len
    per_row = (2 * w * b + 2 * rank * b + rank * 4
               + 2 * _STATS_LANES * 4 + 2 * keys * 4)
    fit = max((_PAGED_VMEM_BUDGET - 2 * keys * w * b)
              // (per_row * s_len), 1)
    return max(g for g in range(1, h + 1) if h % g == 0 and g <= fit)


def latent_decode_reference(q, arena, block_tbl, pos, rank, scale):
    """Ground truth of ``latent_decode`` in ``jax.numpy``: gather each row's
    pages of ONE layer's arena ``[P, 1, page_len, W]`` into its plane, score
    every query head against it, softmax in float32 under the frontier
    mask, and sum the plane's first ``rank`` lanes."""
    plane = gather_pages(arena, block_tbl, 1, 1)           # [B, 1, T, W]
    return decode_attention_reference(q, plane, plane[..., :rank], pos,
                                      scale=scale)


@hot_path
def latent_decode(q, arena, block_tbl, pos, rank, scale, name=None,
                  layer=None):
    """Latent attention over a paged pool's ONE arena.

    Args:
      q: [B, H, S, W] queries carried into the latent (``models/decoder.py``
        ``mla``: ``[q_nope W_uk | q_rope | 0]``), the row's S tokens already
        appended (``kv_append``) at ``pos[b] ..``.
      arena: [L, P, 1, page_len, W] with a static ``layer``, or one layer's
        [P, 1, page_len, W] with ``layer`` None; page 0 is the trash page.
      block_tbl: [B, n_lp] int32; pos: [B] int32 pre-write frontiers.
      rank: the leading lanes of a stored token that are its values.
      scale: on the scores (``DecoderConfig.softmax_scale``).
      name: the kernel's name in a trace (``prefill_attn`` from the lane).

    A page size that is no kernel block takes the gather + reference (same
    math). Returns [B, H, S, rank] in q.dtype; zeros for a freed row."""
    b, h, s_len, w = q.shape
    page_len = arena.shape[-2]
    if not decode_supported(page_len):
        return latent_decode_reference(
            q, arena if layer is None else arena[layer], block_tbl, pos,
            rank, scale=scale)
    hg = _latent_heads_per_unit(h, s_len, page_len, w, rank, q.dtype)

    def launch(q, arena, tbl, pos):
        return _paged_launch(name or "latent_decode", q, (arena,), tbl, pos,
                             float(scale), layer, rep=hg, latent=int(rank))

    out = on_shards(launch, kernel_sharding(b, 1),
                    ("b", "-" if layer is None else "--", "b", "b"), ("b",))(
                        q.reshape(b, h // hg, hg * s_len, w), arena,
                        block_tbl, pos)
    return out.reshape(b, h, s_len, rank)


# ---------------------------------------------------------------------------
# In-place frontier append (kernel "kv_append") — the WRITE half of the
# paged pool. An XLA scatter into one layer of the arena slices the layer
# out, scatters into the slice and updates it back: three arena-sized
# passes for a few rows, and it hands the arena a layout Mosaic has to
# convert back around every decode call. This kernel aliases the arena to
# its output and rewrites only the frontier of each row, addressed
# ``(layer, block_tbl[b, pos[b] // page_len + j], ..)`` through scalar
# prefetch. Everything else in the arena is never touched, so it keeps ONE
# layout — the decode kernel's — from the step's entry to its exit.
#
# SEVERAL ROWS A SLOT (a verify's ``spec_k + 1``, the lane's 128): the
# frontier page(s) by block spec, a grid of ``(B, 2)``: the page's
# [H/g, page_len, g*D] block comes into VMEM, the new rows, regrouped as the
# arena holds heads, are rolled to their offsets and selected in, and the
# block goes back.
#
# ONE ROW A SLOT (the decode scan, once a layer and iteration: 384 calls a
# GPT-2 step): ONE launch walks its rows itself (PR 41). The arenas stay in
# ``pl.ANY``; the LIVE rows (``tbl[b, 0] != TRASH_PAGE``, the test
# ``_paged_units`` uses, and a frontier inside the plane) come as a list on
# scalar prefetch (``_live_rows``: a few integer operations outside the
# kernel, identical in every layer of a pass, so the compiler keeps one
# copy), and the kernel's loops run over the list's length: a freed row
# costs no copy and no branch. For every live row the kernel starts a
# ``make_async_copy`` of the [H/g, 8, g*D] tile that holds the frontier into
# the row's slot of a VMEM scratch, every row's read in flight together;
# waits for them all; then, row by row, selects the new row in (all heads in
# one 32-bit select, bit for bit the scatter) and starts the write-back; and
# last waits for the write-backs. EIGHT rows because that is a whole tile of
# the arena in HBM whatever its dtype (``T(8,128)(2,1)`` bf16, ``(4,1)``
# int8: Mosaic refuses a slice of 2 or 4 and accepts 8, compiled for a
# described v5e); a scale arena brings its [H, page_len] block. All rows are
# one unit where their slots fit the VMEM budget (every cell's do), else the
# grid is ``ceil(B / R)`` units of ``append_unit_rows``' R, from shapes and
# dtypes alone. The form this took the place of gave each row a Pallas grid
# step and a 32-row tile by block spec: a step costs about 0.6 us whatever
# it holds, so 16 rows of GPT-2 355M took 12.2 us a call (9.0 with no live
# row) where the walk takes 4.1 (1.8), OLMoE's 32 rows 33.2 -> 8.5,
# Granite's 64 43.1 -> 10.2, DeepSeek's 128 48.6 -> 14.4 (kernel alone, TPU
# v5 lite; PERF.md, PR 41, where the forms that lost are: a 32-row and a
# 16-row tile, units of 4 to 32 rows, a loop over heads in place of one
# select, the new values by the kernel's own copy, a ``pl.when`` a row in
# place of the list, which cost 0.6 us a call and 12 s of set-up, and a wait
# a row before its select, 0.2 us faster and no proof that the tile is whole).
#
# Rewriting a whole tile or page is sound because a live frontier page
# belongs to ONE row (the block table is injective per row outside the trash
# page; copy-on-write gives a prefix's straddle page a private copy before
# it is written), so no two copies in flight meet. Frozen rows share the
# trash page 0, which nothing reads.
# ---------------------------------------------------------------------------

# Rows a SEVERAL-row append pads its new values to: the packed sublane tile
# of the narrowest pool dtype (int8: 32 rows; bf16: 16).
_APPEND_TILE = 32

# Rows of a page around the frontier that a ONE-row append brings in and
# writes back: one tile of the arena in HBM, whatever the dtype packs.
_APPEND_ROWS = 8


def _append_walks(arenas):
    """Whether a one-row append of these arenas can walk its rows: Mosaic
    slices no HBM ref whose minor dim is not whole lane tiles (a head dim of
    80 or 96, a caller's unpacked arena of 64; no pool ``lane_pack`` stores
    at a head dim that divides or fills a tile). Such a call goes the
    several-row way, a whole page a row by block spec."""
    return all(len(a.shape) == 4 or a.shape[4] % LANES == 0 for a in arenas)


def _append_page(pos_b, j, s_len, page_len, n_lp):
    """Logical page the j-th grid step of a row rewrites. Steps past the
    write's last page REPEAT it (same block: no new DMA, and the body
    recomputes the same page from the same input, so the revisit is
    idempotent); a frontier past the plane clamps to the last page and
    selects nothing."""
    last = _div(pos_b + (s_len - 1), page_len)
    return jnp.minimum(jnp.minimum(_div(pos_b, page_len) + j, last),
                       n_lp - 1)


def _wide(dtype):
    """Selects run on 32-bit values (bf16 -> f32 and int8 -> int32 are
    exact both ways): a packed row cannot be rolled by an odd count."""
    return jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else jnp.int32


def _append_kernel(pos_ref, tbl_ref, *refs, n, s_len, page_len):
    from jax.experimental.pallas import tpu as pltpu

    news, olds, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
    pos_b = pos_ref[pl.program_id(0)]
    lp = _append_page(pos_b, pl.program_id(1), s_len, page_len,
                      tbl_ref.shape[1])
    off = _rem(pos_b, page_len)

    def place(new, axis, rows):
        # [.., s, ..] new values -> the block's ``rows`` positions along
        # ``axis``, rolled by the frontier's offset (a row that lands on
        # the NEXT page wraps to that page's start, which is where it
        # belongs there).
        shape = list(new.shape)
        if shape[axis] != rows:
            shape[axis] = rows - shape[axis]
            new = jnp.concatenate([new, jnp.zeros(shape, new.dtype)], axis)
        return pltpu.roll(new, off, axis)

    def select_in(new_ref, old_ref, out_ref):
        wide = _wide(old_ref.dtype)
        axis = 0 if len(old_ref.shape) == 4 else 1
        rows = old_ref.shape[2]
        # The block's position p holds plane position start + p; it takes
        # new row r = start + p - pos_b where 0 <= r < s_len and keeps
        # what it holds everywhere else.
        r = lp * page_len - pos_b + jax.lax.broadcasted_iota(
            jnp.int32, old_ref.shape[-2:], axis)
        keep = (r < 0) | (r >= s_len)

        def merged(old, new):
            return jnp.where(keep, old.astype(wide),
                             place(new.astype(wide), axis, rows)
                             ).astype(out_ref.dtype)

        if axis == 1:                        # scales [1, H, page_len]
            out_ref[0] = merged(old_ref[0], new_ref[0])
            return

        def one_head(h_, _):                 # rows [1, H, rows, D]
            out_ref[0, h_] = merged(old_ref[0, h_], new_ref[0, h_])

        # A loop, not 16 copies of the body: the step holds this kernel
        # once a layer and lane, and its size is set-up time.
        jax.lax.fori_loop(0, old_ref.shape[1], one_head, None)

    for new_ref, old_ref, out_ref in zip(news, olds, outs):
        select_in(new_ref, old_ref, out_ref)


def _kv_append_pallas(pos, tbl, *ops, layer, s_len):
    """Several rows a slot (and one, of an arena the walk cannot slice):
    whole frontier pages by block spec."""
    from jax.experimental.pallas import tpu as pltpu

    n = len(ops) // 2
    news, arenas = ops[:n], ops[n:]
    page_len = arenas[0].shape[3]
    assert 1 <= s_len <= page_len, (s_len, page_len)
    n_lp = tbl.shape[1]
    pos = pos.astype(jnp.int32)
    tbl = tbl.astype(jnp.int32)

    def new_spec(new):
        zeros = (0,) * (new.ndim - 1)
        return pl.BlockSpec((1,) + new.shape[1:],
                            lambda b_, j, pos_ref, tbl_ref: (b_,) + zeros)

    def arena_spec(arena):
        # A row arena's block is [H, page_len, D], one page; a scale
        # arena's is [H, page_len], positions on the lanes.
        tile = (0, 0) if arena.ndim == 5 else (0,)

        def index(b_, j, pos_ref, tbl_ref):
            lp = _append_page(pos_ref[b_], j, s_len, page_len, n_lp)
            return (layer, tbl_ref[b_, lp], 0) + tile

        return pl.BlockSpec((None, 1) + arena.shape[2:], index)

    arena_specs = [arena_spec(a) for a in arenas]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # a write of at most a page's rows touches two pages
        grid=(pos.shape[0], 2),
        in_specs=[new_spec(x) for x in news] + arena_specs,
        out_specs=arena_specs,
    )
    out = pallas_mode.kernel_call(
        "kv_append",
        functools.partial(_append_kernel, n=n, s_len=s_len,
                          page_len=page_len),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arenas],
        # Operands count the two scalar-prefetch arguments.
        input_output_aliases={2 + n + i: i for i in range(n)},
    )(pos, tbl, *news, *arenas)
    return tuple(out)


def append_unit_rows(arenas, b):
    """R, the rows one unit (grid step) of the one-row append walks, for a
    call of ``b`` rows (the engine reports a pool's, for ``max_slots``, as
    ``kv_append_unit_rows``): all ``b`` where their slots fit
    ``_PAGED_VMEM_BUDGET``, else the largest power of two that does; 0 for
    arenas no launch walks. From the arenas' shapes and dtypes alone
    (anything with a shape and a dtype, whole). A row holds, an arena, its
    [H/g, 8, g*D] tile (a scale arena's [H, page_len] block) and its new
    values twice (the pipeline's two buffers; a head's row of them pads to
    one 32-bit sublane)."""
    if not _append_walks(arenas):
        return 0

    def lanes(n):
        return -(-n // LANES) * LANES

    row_bytes = 0
    for a in arenas:
        item = jnp.dtype(a.dtype).itemsize
        if len(a.shape) == 5:
            row_bytes += a.shape[2] * lanes(a.shape[4]) * (
                _APPEND_ROWS * item + 2 * 4)
        else:
            heads = -(-a.shape[2] // 8) * 8
            row_bytes += heads * (lanes(a.shape[3]) + 2 * LANES) * item
    fit = max(1, _PAGED_VMEM_BUDGET // row_bytes)
    return b if b <= fit else 2 ** (fit.bit_length() - 1)


def _live_rows(tbl, pos, page_len):
    """The one-row append's work list: ``(rows, ends)``, each ``[B]``. Row
    b is LIVE where its table does not start on the trash page (a freed
    row's is all trash page: the test ``_paged_units`` uses) and its
    frontier lies inside its plane; ``ends[b]`` counts the live rows up to
    and including b, and ``rows[t]`` is the t-th live row for
    ``t < ends[B - 1]``. A dense compare and sum over ``[B, B]``, the same in
    every layer of a pass, so the compiler keeps one copy."""
    from deepspeed_tpu.inference.paging import TRASH_PAGE

    b, n_lp = tbl.shape
    live = (tbl[:, 0] != TRASH_PAGE) & (pos < n_lp * page_len)
    r = jax.lax.iota(jnp.int32, b)
    upto = jax.lax.le(r[None, :], r[:, None]) & live[None, :]
    ends = jnp.sum(upto, axis=1, dtype=jnp.int32)
    rows = jnp.sum(jax.lax.ge(r[:, None], ends[None, :]), axis=1,
                   dtype=jnp.int32)
    return jax.lax.min(rows, jnp.int32(b - 1)), ends


def _append_walk_kernel(rows_ref, ends_ref, pos_ref, tbl_ref, *refs, n,
                        layer, unit, page_len):
    """One grid step = one unit: rows ``unit * u .. unit * u + unit - 1``,
    of which it walks the live ones (``_live_rows``' list, so a dead row
    costs no branch either). ``refs``: the unit's new values (VMEM blocks
    ``[unit, H, 1, D]`` / ``[unit, H, 1]``), the arenas twice (input and
    aliased output, both whole in HBM; the output is the one read and
    written), a VMEM slot a row an arena, DMA semaphores ``[2, n]`` (reads,
    write-backs)."""
    from jax.experimental.pallas import tpu as pltpu

    news, outs = refs[:n], refs[2 * n:3 * n]
    slots, sem = refs[3 * n:4 * n], refs[4 * n]
    n_rows = pos_ref.shape[0]
    if unit == n_rows:
        first, lo, hi = 0, 0, ends_ref[n_rows - 1]
    else:
        first = pl.program_id(0) * unit
        lo = jnp.where(first > 0, ends_ref[jnp.maximum(first - 1, 0)], 0)
        hi = ends_ref[jnp.minimum(first + unit, n_rows) - 1]

    def tiles(page, start):
        # what a row rewrites of each arena: the [H, 8, D] tile of its
        # frontier page that holds the frontier; a scale arena's page
        return [out.at[layer, page, :, pl.ds(start, _APPEND_ROWS), :]
                if len(out.shape) == 5 else out.at[layer, page]
                for out in outs]

    def row(t):
        b = rows_ref[t]
        pos_b = pos_ref[b]
        start = _div(_rem(pos_b, page_len), _APPEND_ROWS) * _APPEND_ROWS
        return b - first, pos_b, tiles(
            tbl_ref[b, _div(pos_b, page_len)],
            pl.multiple_of(start, _APPEND_ROWS))

    def bring(t, _):
        i, _, hbm = row(t)
        for a in range(n):
            pltpu.make_async_copy(hbm[a], slots[a].at[i], sem.at[0, a]).start()

    def place(t, _):
        i, pos_b, hbm = row(t)
        for a, slot in enumerate(slots):
            # The slot's positions lie along axis 1 of ``slot[i]``: a
            # tile's 8 on the sublanes of [H, 8, D], a scale block's
            # page_len on the lanes of [H, page_len]. All heads in one
            # select: looping over them was 1.5 to 2 times slower a call.
            # (``lax``, not ``jnp``: the step traces this once a layer.)
            wide, shape = _wide(slot.dtype), slot.shape[1:]
            new = jax.lax.convert_element_type(news[a][i], wide)
            here = jax.lax.eq(jax.lax.broadcasted_iota(jnp.int32, shape, 1),
                              _rem(pos_b, shape[1]))
            slot[i] = jax.lax.convert_element_type(jax.lax.select(
                here,
                jax.lax.broadcast_in_dim(new, shape, tuple(range(new.ndim))),
                jax.lax.convert_element_type(slot[i], wide)), slot.dtype)
            pltpu.make_async_copy(slot.at[i], hbm[a], sem.at[1, a]).start()

    def landed(k):
        # The copies of one direction and arena share ONE semaphore, which
        # counts bytes: as many waits as copies, and only after the last of
        # them is any slot known whole (a wait needs a copy's size and
        # semaphore, not its address).
        hbm = tiles(0, 0)

        def wait(t, _):
            for a in range(n):
                pltpu.make_async_copy(hbm[a], slots[a].at[0],
                                      sem.at[k, a]).wait()
        return wait

    # Loops over the live rows, not R copies of a body: the step holds this
    # kernel once a layer, and its size is set-up time.
    jax.lax.fori_loop(lo, hi, bring, None)
    jax.lax.fori_loop(lo, hi, landed(0), None)
    jax.lax.fori_loop(lo, hi, place, None)
    jax.lax.fori_loop(lo, hi, landed(1), None)


def _kv_append_walk(pos, tbl, *ops, layer):
    """ONE row a slot: the launch walks its rows (the block above)."""
    from jax.experimental.pallas import tpu as pltpu

    n = len(ops) // 2
    news, arenas = ops[:n], ops[n:]
    b = pos.shape[0]
    unit = append_unit_rows(arenas, b)
    pos, tbl = pos.astype(jnp.int32), tbl.astype(jnp.int32)

    def new_spec(new):
        zeros = (0,) * (new.ndim - 1)
        return pl.BlockSpec((unit,) + new.shape[1:],
                            lambda u, *_: (u,) + zeros)

    def slot(arena):
        # a row's tile [H, 8, D] of a row arena, block [H, page_len] of a
        # scale arena
        tile = (_APPEND_ROWS,) + arena.shape[4:] if arena.ndim == 5 \
            else arena.shape[3:]
        return pltpu.VMEM((unit, arena.shape[2]) + tile, arena.dtype)

    whole = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(-(-b // unit),),
        in_specs=[new_spec(x) for x in news] + [whole] * n,
        out_specs=[whole] * n,
        scratch_shapes=[slot(a) for a in arenas]
        + [pltpu.SemaphoreType.DMA((2, n))],
    )
    out = pallas_mode.kernel_call(
        "kv_append",
        functools.partial(_append_walk_kernel, n=n, layer=layer, unit=unit,
                          page_len=arenas[0].shape[3]),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arenas],
        # Operands count the four scalar-prefetch arguments.
        input_output_aliases={4 + n + i: i for i in range(n)},
    )(*_live_rows(tbl, pos, arenas[0].shape[3]), pos, tbl, *news, *arenas)
    return tuple(out)


@hot_path
def kv_append(arenas, new, block_tbl, pos, layer):
    """Append each row's new k/v at its frontier, IN PLACE in the arenas.

    Args:
      arenas: tuple of page arenas of one paged pool (k and v together;
        the one arena of a latent cache), each WHOLE and as the pool stores
        it:
        [L, P, ceil(H / g), page_len, g * D] rows (bf16, or int8 codes;
        ``g = lane_pack(D, H)``) and, for an int8 pool,
        [L, P, g * ceil(H / g), page_len] fp32 scales. Donate them (the
        serving step does): each comes back as the same buffer.
      new: the matching tuple of new values, [B, H, S, D] per row arena
        and [B, H, S] per scale arena: row b's S positions
        ``pos[b] .. pos[b]+S-1`` (S = 1 in the decode scan, spec_k + 1 in
        a verify, prefill_chunk in the lane; any frontier, a write may
        straddle a page boundary).
      block_tbl: [B, n_lp] int32; pos: [B] int32 pre-write frontiers.
      layer: static int.

    Bit for bit ``arena.at[layer, pg, :, off, :].set(pack_heads(new, g))``
    with ``pg, off`` through the table, for every position inside a row's
    plane — except the trash page 0, which several frozen rows may share
    and nothing reads. ``page_len`` must be a kernel block
    (``decode_supported``): the branch of ``models/generation.py``
    ``_forward`` that calls the paged decode kernel calls this.
    Returns the tuple of updated arenas.
    """
    page_len = arenas[0].shape[3]
    assert decode_supported(page_len), page_len
    # The new values in the arenas' stored shape (kilobytes): rows packed
    # ``g`` heads a lane tile, scales a head of the model (a zero head
    # where ``g`` does not divide the count), and the kernel below sees
    # ``ceil(H / g)`` heads of a whole tile.
    g = arenas[0].shape[-1] // new[0].shape[-1]
    new = tuple(pack_heads(x, g) if a.ndim == 5 else
                pad_heads(x, a.shape[2], 1) for x, a in zip(new, arenas))
    s = new[0].shape[2]
    b, h = new[0].shape[:2]
    # One row a slot walks its rows in one launch and takes the new values
    # as they are. A block-spec call pads them: lane-dim padding of the
    # scales' new values cannot be made inside the kernel (an unaligned lane
    # concatenate), so they arrive page-wide; row values pad up to a packed
    # sublane tile at most.
    sub = _APPEND_TILE
    out = tuple(arenas)
    for lo in range(0, s, page_len):      # at most a page's rows a call
        n_rows = min(page_len, s - lo)
        walk = n_rows == 1 and _append_walks(arenas)
        part = []
        for x, a in zip(new, arenas):
            x = jax.lax.slice_in_dim(x, lo, lo + n_rows, axis=2)
            if not walk and a.ndim == 4:
                x = jnp.pad(x, ((0, 0), (0, 0), (0, page_len - n_rows)))
            elif not walk and n_rows % sub:
                x = jnp.pad(x, ((0, 0), (0, 0),
                                (0, sub - n_rows % sub), (0, 0)))
            part.append(x)
        specs = ("-", "-") + tuple("-h" for _ in part) \
            + tuple("--h" for _ in out)
        launch = functools.partial(_kv_append_walk, layer=int(layer)) \
            if walk else functools.partial(
                _kv_append_pallas, layer=int(layer), s_len=n_rows)
        out = on_shards(launch, kernel_sharding(b, h), specs,
                        tuple("--h" for _ in out))(
                            pos + lo, block_tbl, *part, *out)
    return tuple(out)
