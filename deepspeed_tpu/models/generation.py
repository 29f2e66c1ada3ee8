"""Autoregressive generation for GPT2LMHeadModel — KV-cache decode.

Beyond the v0.3.10 reference (which has no generation API; its inference
surface is pipeline eval_batch). Decode-time compute has a different
shape than training — one token's [B, 1, C] activations against a
[B, H, T, D] cache — so rather than threading flag-switched branches
through the training modules, this is a separate pure-functional decode
program over the SAME parameter tree the engine trains (the flax param
names are the contract; `tests/unit/test_generation.py` pins step-logit
parity against the training forward). TPU-first mechanics:

- static shapes end to end: the cache is pre-allocated at
  ``prompt_len + max_new_tokens``; per-step masks come from iota vs a
  traced position scalar, never from dynamic slicing on token count;
- the decode loop is ONE ``lax.scan`` inside ONE jit — no per-token
  dispatch, no host round-trips; sampling (greedy / temperature / top-k)
  runs on-device from a threaded threefry key;
- prefill is a single batched pass over the prompt (MXU-sized GEMMs),
  writing the cache for all prompt positions at once;
- early EOS freezes finished rows (they keep emitting ``eos_token_id``)
  without leaving the scan — the fixed trip count keeps the program
  static; trim host-side.
"""

import collections
import functools
import os

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis.annotations import hot_path
from deepspeed_tpu.ops.transformer.kernels import decode_attention

# Hashable shape/dtype subset of GPT2Config (the dataclass itself is
# unhashable, and jit's static args must hash).
_GenCfg = collections.namedtuple(
    "_GenCfg",
    "n_layer n_head n_embd n_positions dtype layer_norm_epsilon "
    "use_flash_decode sparse_block sparse_num_local sparse_num_global "
    "sparse_threshold kv_page_len", defaults=(False, 0, 0, 0, 0, 0))
# kv_page_len is the PAGED cache-spec variant (adapters declare it via
# ModelAdapter.cache_spec when the engine serves a paged pool): > 0
# names the page quantum the pool and the block-table kernels share;
# 0 (the default) keeps every existing construction dense. _forward
# itself dispatches data-driven on the cache's ``block_tbl`` key — the
# cfg field exists so the static-arg cache key changes with paging.
# The sparse_* tail (defaults keep every existing construction dense and
# bit-identical): when sparse_threshold > 0, einsum-path attention for
# query positions >= the threshold is restricted to the block-sparse
# local+stride layout (FixedSparsityConfig, unidirectional) with block
# side sparse_block, sparse_num_local local blocks per window and
# sparse_num_global global blocks. Positions below the threshold keep the
# full causal mask — the long-context adapter's "dense below, sparse
# above" contract (inference/adapters/longcontext.py).


def default_flash_decode():
    """Policy for configs that don't say (``use_flash_decode=None``):
    the DS_TPU_FLASH_DECODE env overrides; otherwise the Pallas decode
    kernel engages on TPU only. Off-TPU it would run in interpret mode —
    semantically identical but orders of magnitude slower, a test-only
    path the parity suite opts into explicitly."""
    env = os.environ.get("DS_TPU_FLASH_DECODE", "")
    if env:
        return env not in ("0", "false")
    return jax.default_backend() == "tpu"


def as_gencfg(cfg, use_flash_decode=None):
    """Hashable ``_GenCfg`` view of a GPT2Config (or anything with the same
    attrs) — the static-arg form every jitted decode program keys on.
    ``use_flash_decode`` overrides the config's own flag; None defers to
    the config, then to ``default_flash_decode()``."""
    if isinstance(cfg, _GenCfg):
        if use_flash_decode is not None:
            return cfg._replace(use_flash_decode=bool(use_flash_decode))
        return cfg
    flag = use_flash_decode
    if flag is None:
        flag = getattr(cfg, "use_flash_decode", None)
    if flag is None:
        flag = default_flash_decode()
    return _GenCfg(cfg.n_layer, cfg.n_head, cfg.n_embd, cfg.n_positions,
                   cfg.dtype, getattr(cfg, "layer_norm_epsilon", 1e-5),
                   bool(flag))


@functools.lru_cache(maxsize=None)
def _sparse_layout(block, num_local, num_global, num_blocks):
    """Trace-time [num_blocks, num_blocks] bool block-visibility table for
    the fixed (local+stride) unidirectional pattern. Pure numpy metadata —
    cached per geometry, shipped to the device once as a constant."""
    import numpy as np
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
        FixedSparsityConfig)
    layout = FixedSparsityConfig(
        num_heads=1, block=block, num_local_blocks=num_local,
        num_global_blocks=num_global,
        attention="unidirectional").make_layout(num_blocks * block)
    return np.asarray(layout[0], dtype=bool)


def init_cache(cfg, batch, max_len, dtype=None):
    """Zeroed [layers, B, heads, max_len, head_dim] k/v cache + a PER-ROW
    position frontier ``pos`` [B] (each row may sit at a different sequence
    length — the slot semantics the serving engine needs; ``generate``
    simply advances all rows in lockstep)."""
    dtype = dtype or cfg.dtype
    hd = cfg.n_embd // cfg.n_head
    shape = (cfg.n_layer, batch, cfg.n_head, max_len, hd)
    cache = {"k": jnp.zeros(shape, dtype),
             "pos": jnp.zeros((batch,), jnp.int32)}
    if not getattr(cfg, "latent", 0):   # latent: a token's values are lanes
        cache["v"] = jnp.zeros(shape, dtype)    # of its key, one plane
    if getattr(cfg, "window_layers", 0):
        # a window group, dense: planes as long as the full group's, the
        # window a mask (``CacheAttention.windowed``)
        w_shape = (cfg.window_layers,) + shape[1:]
        cache["wk"], cache["wv"] = (jnp.zeros(w_shape, dtype) for _ in "kv")
    return cache


def _ln(x, p, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def _dense(x, p):
    return (x @ p["kernel"].astype(x.dtype) +
            p["bias"].astype(x.dtype))


def _read_in_cfg_dtype(path):
    """True for the leaves EVERY use in ``_forward`` casts to ``cfg.dtype``:
    each ``Dense``'s ``kernel`` and ``bias`` (``_dense``) and ``wpe``. The
    LayerNorms multiply in float32 (``_ln``) and the head reads ``wte`` as
    float32, so those stay as they came."""
    keys = [getattr(k, "key", None) for k in path]
    return keys == ["wpe"] or (
        len(keys) > 1 and keys[-2] in ("c_attn", "c_proj", "c_fc"))


@functools.partial(jax.jit, static_argnums=(1,))
def _cast_leaves(leaves, dtype):
    return [x.astype(dtype) for x in leaves]


def serving_params(params, cfg):
    """``params`` as ``_forward`` READS it: the leaves whose every use casts
    them to ``cfg.dtype`` held in ``cfg.dtype``, so that a step handed the
    result converts no weight (a float32 tree otherwise pays the whole cast
    once a step: XLA hoists the converts out of the decode scan, 2.1 GB of
    traffic at 355M parameters). ``wte`` and the LayerNorms stay float32
    (``_read_in_cfg_dtype``). ONE jitted program casts every such leaf, without
    donation: the caller keeps its tree. A leaf already in ``cfg.dtype``
    passes through as the same object, so a tree cast twice is the same
    tree, and a tree with nothing to cast runs no program."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    dtype = jnp.dtype(cfg.dtype)
    at = [i for i, (path, leaf) in enumerate(flat)
          if _read_in_cfg_dtype(path) and leaf.dtype != dtype]
    if not at:
        return params
    leaves = [leaf for _, leaf in flat]
    for i, cast in zip(at, _cast_leaves([leaves[i] for i in at], dtype)):
        leaves[i] = cast
    return jax.tree_util.tree_unflatten(treedef, leaves)


class CacheAttention(object):
    """The cache side of one forward pass over ``ids`` [B, S]: where each
    layer's new keys and values are written, what attention reads, and the
    attention itself. Built once a pass from the cache dict, then called
    once a layer with that layer's ``q, k, v`` [B, H, S, D]; GPT-2's block
    (``_forward``) and the config-driven decoder block (``models/decoder``)
    share it, so a cache layout or a kernel is wired in one place.

    The regions of a trace it owns (``jax.named_scope``): ``kv_write`` (the
    frontier write into the planes), ``kv_view`` (the planes attention
    reads: the page gather, the prefix select) and the attention proper
    under ``attn``.

    WHERE THE FRONTIER WRITE HAPPENS. A paged pool whose page is a kernel
    block (``"block_tbl" in cache`` and ``decode_supported(page_len)``
    under ``use_flash_decode``: what the chip serves) is written IN PLACE
    by the ``kv_append`` kernel and read by the paged decode kernel with
    the layer in its index map; the arenas pass through whole, and no
    per-layer value of them is formed (``kv_write`` holds the kernel,
    ``kv_view`` nothing). Every other cache (a paged pool with smaller
    pages, the dense slot pool, ``generate()``'s own cache) takes
    ``cache.at[i].set(write(cache[i], new))`` and, on the chip, pays XLA's
    slice, scatter and update of a whole layer for it.

    KV-hierarchy dispatch is DATA-DRIVEN off the cache dict
    (inference/kv_hierarchy): an int8 ``k`` plane means frontier writes
    quantize (codes + per-(head, position) ``k_scale``/``v_scale``) and
    attention dequantizes, in-block in the q8 flash kernel, before the
    einsum otherwise; a ``pk`` key means each row's positions
    ``< pbase[b]`` resolve to its aliased read-only prefix plane via a
    per-position SELECT. The select is elementwise (no arithmetic) and
    the prefix entries are bit-identical to what the row's own prefill
    would have written (causality: position p's k/v depend only on
    tokens <= p, which match by construction), so aliased and private
    greedy streams are bit-identical. A plain cache hits neither branch
    and lowers exactly as before.

    A LATENT cache (``cfg.kv_lora_rank`` > 0: ``models/decoder.py`` ``mla``;
    the cache then has no ``v`` key) holds ONE
    plane, one stored head of width ``W`` a token whose first
    ``cfg.kv_lora_rank`` lanes are also its values. Every query head reads
    that one head; the call gets no ``v``, scales by ``cfg.softmax_scale``
    and returns ``[B, H, S, kv_lora_rank]``. A paged pool of kernel-block pages
    is written by the same ``kv_append`` (one arena) and read by the
    ``latent_decode`` kernel; everything else takes the scatter, the gather
    and two einsums. The int8 and prefix tiers have no latent form
    (``DecoderAdapter.bind`` refuses them by name).

    A WINDOW GROUP (``cfg.sliding_window`` > 0 and ``wk`` / ``wv`` in the
    cache: the decoder block's ``swa`` layers) is a SECOND pair of planes, as
    deep as the window layers, with its own addressing: in a paged pool a
    FIXED RING of ``n_ring`` pages a row (``cache['ring_tbl']`` [B, n_ring],
    ``kv_pool.py``), written by ``kv_append_ring`` and read by
    ``window_decode`` (``decode_attention.py``, A window layer's RING OF
    PAGES), or through a scatter, ``ring_view``'s gather and the einsum where
    a page is no kernel block; in a dense cache planes as long as the full
    group's, the window a mask. ``windowed`` is such a layer's call; the
    mask's lower bound is ``decode_attention.visible``'s wherever it is
    formed. A layer that only READS a plane another layer wrote (the decoder
    block's ``xattn``) calls with ``write=False`` and no ``k``, ``v``. The
    int8 and prefix tiers have no ring form (``bind`` refuses them)."""

    def __init__(self, cfg, cache, S, attn_name=None):
        self.cfg, self.cache, self.S = cfg, cache, S
        self.attn_name = attn_name
        self.nh, self.hd = cfg.n_head, cfg.n_embd // cfg.n_head
        # Grouped-query heads (``cfg.n_kv``, the decoder block's): the
        # planes STORE ``nkv`` heads and query head j reads stored head
        # ``j // rep``. The paged kernels put a stored head's ``rep``
        # queries beside S on the sublane axis; every other path repeats
        # the stored heads. ``attn_scale``: a softmax scale of the model's
        # own in place of 1/sqrt(head_dim).
        self.nkv = getattr(cfg, "n_kv", self.nh)
        self.rep = self.nh // self.nkv
        self.scale = getattr(cfg, "attn_scale", None)
        # A model that generates by diffusion over blocks (the decoder
        # block's ``block_length``; 1: next-token) sees a whole block of
        # positions both ways: ``decode_attention.visible_upto``.
        self.block = getattr(cfg, "block_length", 1)
        B = cache["pos"].shape[0]
        self.pos = pos = cache["pos"]                  # [B] row frontiers
        self.int8 = cache["k"].dtype == jnp.int8
        self.has_prefix = "pk" in cache
        self.latent = bool(getattr(cfg, "kv_lora_rank", 0))
        if self.latent:
            assert not (self.int8 or self.has_prefix), \
                "a latent cache has no int8 and no prefix tier"
            self.hd, self.nkv, self.rep = cache["k"].shape[-1], 1, self.nh
        # PAGED dispatch (inference/kv_pool.py paged layout): a block table
        # means k/v are a page ARENA [L, P, H/g, page_len, g*D] (``g`` heads
        # share a lane tile: decode_attention.lane_pack, read back here from
        # the arena's minor dim) and row b's logical plane is the
        # concatenation of its table's pages. Writes
        # go through the table (an XLA scatter, or in place by kv_append);
        # reads gather through it (or hand the table and the whole arena to
        # the paged flash kernel). The gathered logical plane is
        # elementwise equal to what the dense pool holds at every valid
        # position (trash/unwritten pages are finite garbage the causal
        # mask zeroes exactly), so streams stay bit-identical to dense.
        self.paged = paged = "block_tbl" in cache
        if paged:
            assert not self.has_prefix, "paged pools share prefixes via pages"
            self.tbl = tbl = cache["block_tbl"]        # [B, n_lp]
            self.page_len = page_len = cache["k"].shape[3]
            self.pack = cache["k"].shape[4] // self.hd
            self.n_lp = n_lp = tbl.shape[1]
            self.max_len = n_lp * page_len             # logical plane len
            w_pos = pos[:, None] + jnp.arange(S)[None]  # [B, S]
            self.w_pg = tbl[jnp.arange(B)[:, None],
                            jnp.minimum(w_pos // page_len, n_lp - 1)]
            self.w_off = w_pos % page_len
        else:
            self.max_len = cache["k"].shape[3]
        self.q_pos = pos[:, None] + jnp.arange(S)[None]  # [B, S]
        max_len = self.max_len
        # Flash-decode engages when the flag is on AND the cache plane
        # length fits the kernel's block quantum (kv_pool pads its pool;
        # ad-hoc caches of other lengths take the einsum path below, the
        # same math). Paged pools key on PAGE length instead: kernel
        # blocks == pages, so the paged kernel engages when one page is a
        # whole block quantum; smaller pages (CPU-test geometries) gather
        # + einsum below.
        self.use_flash = cfg.use_flash_decode and \
            decode_attention.decode_supported(
                self.page_len if self.paged else max_len) and \
            (self.paged or not self.latent)    # no dense latent kernel
        if self.block > 1 and self.use_flash and (
                not self.paged or self.int8 or self.latent):
            raise ValueError(
                "block visibility (block_length {}) is built into the einsum "
                "path and the paged decode kernel; the dense, int8 and "
                "latent kernels mask causally".format(self.block))
        sparse_thr = getattr(cfg, "sparse_threshold", 0)
        if sparse_thr and self.use_flash:
            raise ValueError(
                "block-sparse decode (sparse_threshold > 0) requires the "
                "einsum attention path; construct the config with "
                "use_flash_decode=False")
        if not self.use_flash:
            k_pos = jnp.arange(max_len)                # [max_len]
            # Causal vs each row's GLOBAL position: key j visible to query
            # i iff j <= i. Cache slots past a row's frontier are excluded
            # by the same comparison (they hold zeros, or a stale request's
            # k/v, which decode overwrites before the frontier reaches
            # them).
            mask = k_pos[None, None, :] <= decode_attention.visible_upto(
                self.q_pos, self.block)[:, :, None]
            if sparse_thr:
                # Long-context composition: rows whose query position
                # crossed the threshold see only the block-sparse layout;
                # below it the extra term is all-True, leaving the causal
                # mask bit-identical to the dense path (the parity half of
                # the adapter contract).
                blk = cfg.sparse_block
                nb = -(-max_len // blk)
                layout = jnp.asarray(_sparse_layout(
                    blk, cfg.sparse_num_local, cfg.sparse_num_global, nb))
                q_blk = jnp.minimum(self.q_pos // blk, nb - 1)  # [B, S]
                visible = layout[q_blk[:, :, None],
                                 (k_pos // blk)[None, None, :]]
                mask = mask & ((self.q_pos < sparse_thr)[:, :, None]
                               | visible)
            self.mask = mask                           # [B, S, max_len]
            self.neg = jnp.finfo(jnp.float32).min
        if self.has_prefix:
            pbase = cache["pbase"]                     # [B] aliased spans
            # Select masks against the full plane length; pad positions
            # can never be selected because pbase <= prefix_len <= max_len.
            self.psel = jnp.arange(max_len)[None, None, :, None] < \
                pbase[:, None, None, None]             # [B, 1, T, 1]
            self.psel_s = self.psel[..., 0]            # [B, 1, T]
        # What the layers thread: (k, v) or (k, v, k_scale, v_scale); the
        # one plane of a latent cache.
        self.planes = (cache["k"],) if self.latent else \
            (cache["k"], cache["v"]) + (
                (cache["k_scale"], cache["v_scale"]) if self.int8 else ())
        # The window group (class docstring): its two planes, threaded
        # beside ``planes``; None for a model without window layers.
        self.window = getattr(cfg, "sliding_window", 0) if "wk" in cache \
            else 0
        self.wplanes = None
        if self.window:
            assert not (self.int8 or self.has_prefix or self.latent), \
                "a window group has no int8, prefix or latent form"
            self.wplanes = (cache["wk"], cache["wv"])
            if paged:
                self.ring_tbl = cache["ring_tbl"]          # [B, n_ring]

    def _pad_prefix(self, p):
        # [B, H, prefix_len, ...] -> [B, H, max_len, ...]; the pad is
        # inert (never selected), zeros keep it cheap.
        if p.shape[2] == self.max_len:
            return p
        pad = [(0, 0)] * p.ndim
        pad[2] = (0, self.max_len - p.shape[2])
        return jnp.pad(p, pad)

    def _write_rows(self, plane_l, new, at=None):
        """One layer's plane with ``new`` [B, H, S, D] written at the
        frontiers; ``at``: the (page, offset) [B, S] of a paged write where
        they are not the full group's table's (a ring's)."""
        if self.paged:
            # Page arena [P, H/g, page_len, g*D] <- [B, H, S, D], regrouped
            # as the arena stores heads and scattered at (page, offset)
            # through the block table. Distinct live positions map to
            # distinct (page, offset) pairs (the table is injective per
            # row outside the trash page), so the scatter is
            # collision-free wherever it is ever read.
            new = decode_attention.pack_heads(new, self.pack)
            pg, off = at or (self.w_pg, self.w_off)
            return plane_l.at[pg, :, off, :].set(new.transpose(0, 2, 1, 3))
        # [B, H, T, D] cache plane <- [B, H, S, D] at each row's frontier
        # (vmapped dynamic_update_slice lowers to one scatter).
        return jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
            c, n, (0, p, 0)))(plane_l, new, self.pos)

    def _write_scale_rows(self, plane_l, new):
        if self.paged:
            # Scale arena [P, H, page_len] <- [B, H, S] likewise (a scale
            # a head of the model; a zero head where g does not divide H).
            new = decode_attention.pad_heads(new, plane_l.shape[1], 1)
            return plane_l.at[self.w_pg, :, self.w_off].set(
                new.transpose(0, 2, 1))
        # [B, H, T] scale plane <- [B, H, S] at each row's frontier.
        return jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
            c, n, (0, p)))(plane_l, new, self.pos)

    def _gather_pages(self, arena_l):
        # One layer of an arena -> row-major logical planes
        # [B, H, n_lp * page_len, ...] via one table gather, ungrouped.
        return decode_attention.gather_pages(arena_l, self.tbl, self.nkv,
                                             self.pack)

    def _latent(self, i, q, k, planes):
        """Layer ``i`` of a latent cache (class docstring): q [B, H, S, W],
        k [B, 1, S, W] -> (y [B, H, S, rank], (the plane,))."""
        cache, = planes
        rank, scale = self.cfg.kv_lora_rank, self.cfg.softmax_scale
        kernels = self.paged and self.use_flash
        with jax.named_scope("kv_write"):
            if kernels:
                cache, = decode_attention.kv_append(
                    (cache,), (k,), self.tbl, self.pos, layer=i)
            else:
                cache = cache.at[i].set(self._write_rows(cache[i], k))
        if kernels:
            with jax.named_scope("attn"):
                return decode_attention.latent_decode(
                    q, cache, self.tbl, self.pos, rank, scale=scale,
                    name=self.attn_name, layer=i), (cache,)
        with jax.named_scope("kv_view"):
            k_eff = (self._gather_pages(cache[i]) if self.paged
                     else cache[i])[:, 0]                  # [B, T, W]
        with jax.named_scope("attn"):
            att = jnp.einsum("bhqd,bkd->bhqk", q, k_eff).astype(
                jnp.float32) * scale
            att = jnp.where(self.mask[:, None], att, self.neg)
            att = jax.nn.softmax(att, axis=-1).astype(self.cfg.dtype)
            return jnp.einsum("bhqk,bkd->bhqd", att,
                              k_eff[..., :rank]), (cache,)

    def __call__(self, i, q, k, v, planes, write=True, scope="attn"):
        """Layer ``i``'s attention: write ``k, v`` at the frontiers, read
        the cache, attend. Returns (y [B, H, S, D], the planes with layer
        ``i`` written). ``write`` False: a layer that READS plane ``i`` as
        another layer of this pass left it and appends nothing (``k`` and
        ``v`` None). ``scope``: the region word the attention runs under."""
        if self.latent:
            return self._latent(i, q, k, planes)
        if not write:
            assert not self.int8, "a read-only layer has no int8 form"
            return self._attend(i, q, planes, scope), planes
        cfg, cache = self.cfg, self.cache
        int8, paged, use_flash = self.int8, self.paged, self.use_flash
        pos, hd = self.pos, self.hd
        if int8:
            k_cache, v_cache, ks_cache, vs_cache = planes
        else:
            k_cache, v_cache = planes
        with jax.named_scope("kv_write"):
            if int8:
                k, ks = decode_attention.quantize_kv(k)
                v, vs = decode_attention.quantize_kv(v)
            if paged and use_flash:
                # In place: the arenas go through ``kv_append`` WHOLE and
                # come back the same buffers with the frontier pages
                # rewritten. No per-layer value of an arena exists in this
                # branch, on the write side or (below) the read side.
                if int8:
                    k_cache, v_cache, ks_cache, vs_cache = \
                        decode_attention.kv_append(
                            (k_cache, v_cache, ks_cache, vs_cache),
                            (k, v, ks, vs), self.tbl, pos, layer=i)
                else:
                    k_cache, v_cache = decode_attention.kv_append(
                        (k_cache, v_cache), (k, v), self.tbl, pos, layer=i)
            else:
                k_cache = k_cache.at[i].set(self._write_rows(k_cache[i], k))
                v_cache = v_cache.at[i].set(self._write_rows(v_cache[i], v))
                if int8:
                    ks_cache = ks_cache.at[i].set(
                        self._write_scale_rows(ks_cache[i], ks))
                    vs_cache = vs_cache.at[i].set(
                        self._write_scale_rows(vs_cache[i], vs))
        with jax.named_scope("kv_view"):
            # Effective planes: the row's own just-written plane, with the
            # aliased prefix selected in below pbase[b] (codes AND scales:
            # both tiers compose). Paged rows GATHER their logical plane
            # through the block table AFTER the write (the einsum/reference
            # path; the paged flash kernel gathers in its own index map,
            # layer included, and forms no view at all).
            if paged and not use_flash:
                k_eff = self._gather_pages(k_cache[i])
                v_eff = self._gather_pages(v_cache[i])
                if int8:
                    ks_eff = self._gather_pages(ks_cache[i])
                    vs_eff = self._gather_pages(vs_cache[i])
            elif not paged:
                k_eff, v_eff = k_cache[i], v_cache[i]
                if int8:
                    ks_eff, vs_eff = ks_cache[i], vs_cache[i]
            if self.has_prefix:
                psel, psel_s, pad = self.psel, self.psel_s, self._pad_prefix
                k_eff = jnp.where(psel, pad(cache["pk"][i]), k_eff)
                v_eff = jnp.where(psel, pad(cache["pv"][i]), v_eff)
                if int8:
                    ks_eff = jnp.where(
                        psel_s, pad(cache["pk_scale"][i]), ks_eff)
                    vs_eff = jnp.where(
                        psel_s, pad(cache["pv_scale"][i]), vs_eff)
            if self.rep > 1 and not (paged and use_flash):
                k_eff, v_eff = (jnp.repeat(a, self.rep, axis=1)
                                for a in (k_eff, v_eff))
                if int8:
                    ks_eff, vs_eff = (jnp.repeat(a, self.rep, axis=1)
                                      for a in (ks_eff, vs_eff))
        with jax.named_scope(scope):
            if use_flash:
                # Fused QK-score + online softmax + PV over the cache plane,
                # frontier-aware: blocks past pos[b]+S-1 are skipped. The
                # cache was just written, so pos is the PRE-write frontier
                # the kernel's mask convention expects. The q8 family
                # dequantizes in-block from codes + scales.
                scale = self.scale or 1.0 / float(hd) ** 0.5
                if paged:
                    # Block-table flash decode: the kernel steps the list
                    # of live (row, page) pairs, a page of all heads a
                    # step, so pages stream into VMEM straight from the
                    # table with the same straddle-only masking as the
                    # dense kernel and a freed row is not visited at all.
                    # It takes the arenas whole and the layer as part of
                    # the page's address.
                    if int8:
                        y = decode_attention.flash_decode_attention_paged_q8(
                            q, k_cache, v_cache, ks_cache, vs_cache,
                            self.tbl, pos, scale=scale,
                            name=self.attn_name, layer=i)
                    else:
                        y = decode_attention.flash_decode_attention_paged(
                            q, k_cache, v_cache, self.tbl, pos,
                            scale=scale, name=self.attn_name, layer=i,
                            block=self.block)
                elif int8:
                    y = decode_attention.flash_decode_attention_q8(
                        q, k_eff, v_eff, ks_eff, vs_eff, pos,
                        scale=scale, name=self.attn_name)
                else:
                    y = decode_attention.flash_decode_attention(
                        q, k_eff, v_eff, pos, scale=scale,
                        name=self.attn_name)
            else:
                if int8:
                    k_eff = decode_attention.dequantize_kv(k_eff, ks_eff,
                                                           cfg.dtype)
                    v_eff = decode_attention.dequantize_kv(v_eff, vs_eff,
                                                           cfg.dtype)
                att = jnp.einsum("bhqd,bhkd->bhqk", q, k_eff).astype(
                    jnp.float32)
                att = att / jnp.sqrt(hd) if self.scale is None \
                    else att * self.scale
                att = jnp.where(self.mask[:, None], att, self.neg)
                att = jax.nn.softmax(att, axis=-1).astype(cfg.dtype)
                y = jnp.einsum("bhqk,bhkd->bhqd", att, v_eff)
        if int8:
            return y, (k_cache, v_cache, ks_cache, vs_cache)
        return y, (k_cache, v_cache)

    def _einsum(self, q, k_eff, v_eff, mask, scope):
        """The einsum path on one layer's effective planes [B, Hkv, T, D]
        under ``mask`` [B, S, T], grouped-query heads repeated: what
        ``__call__`` computes without the kernels."""
        if self.rep > 1:
            with jax.named_scope("kv_view"):
                k_eff, v_eff = (jnp.repeat(a, self.rep, axis=1)
                                for a in (k_eff, v_eff))
        with jax.named_scope(scope):
            att = jnp.einsum("bhqd,bhkd->bhqk", q, k_eff).astype(jnp.float32)
            att = att / jnp.sqrt(self.hd) if self.scale is None \
                else att * self.scale
            att = jnp.where(mask[:, None], att, jnp.finfo(jnp.float32).min)
            att = jax.nn.softmax(att, axis=-1).astype(self.cfg.dtype)
            return jnp.einsum("bhqk,bhkd->bhqd", att, v_eff)

    def _attend(self, i, q, planes, scope):
        """Plane ``i`` of the full group read as it stands (a read-only
        layer's half of ``__call__``): the paged kernel under the caller's
        name, or the gather / the dense plane and the einsum."""
        k_cache, v_cache = planes
        if self.use_flash:
            scale = self.scale or 1.0 / float(self.hd) ** 0.5
            with jax.named_scope(scope):
                if self.paged:
                    return decode_attention.flash_decode_attention_paged(
                        q, k_cache, v_cache, self.tbl, self.pos, scale=scale,
                        name=self.attn_name, layer=i, block=self.block)
                return decode_attention.flash_decode_attention(
                    q, k_cache[i], v_cache[i], self.pos, scale=scale,
                    name=self.attn_name)
        with jax.named_scope("kv_view"):
            k_eff, v_eff = ((self._gather_pages(a[i]) if self.paged else a[i])
                            for a in (k_cache, v_cache))
        return self._einsum(q, k_eff, v_eff, self.mask, scope)

    def windowed(self, i, q, k, v, planes, scope="swa"):
        """Layer ``i`` OF THE WINDOW GROUP (class docstring): write ``k, v``
        [B, Hkv, S, D] at the frontiers, attend the last
        ``cfg.sliding_window`` positions. Returns (y [B, H, S, D], the
        window planes with layer ``i`` written)."""
        wk, wv = planes
        window, S = self.window, self.S
        if not self.paged:
            # a dense cache holds a window layer's plane whole; the window
            # is the mask's lower bound (no dense kernel masks one)
            with jax.named_scope("kv_write"):
                wk = wk.at[i].set(self._write_rows(wk[i], k))
                wv = wv.at[i].set(self._write_rows(wv[i], v))
            mask = decode_attention.visible(
                jnp.arange(wk.shape[3])[None, None, :],
                self.q_pos[:, :, None], 1, window)
            return self._einsum(q, wk[i], wv[i], mask, scope), (wk, wv)
        page_len, ring = self.page_len, self.ring_tbl
        if self.use_flash:
            with jax.named_scope("kv_write"):
                wk, wv = decode_attention.kv_append_ring(
                    (wk, wv), (k, v), ring, self.pos, layer=i)
            with jax.named_scope(scope):
                return decode_attention.window_decode(
                    q, wk, wv, ring, self.pos, window,
                    scale=self.scale or 1.0 / float(self.hd) ** 0.5,
                    name="window_prefill" if self.attn_name else None,
                    layer=i), (wk, wv)
        with jax.named_scope("kv_write"):
            # the scatter, through the ring: position p at place
            # ``p // page_len % n_ring``
            w_pos = self.q_pos
            at = (jnp.take_along_axis(
                ring, (w_pos // page_len) % ring.shape[1], axis=1),
                w_pos % page_len)
            wk, wv = (a.at[i].set(self._write_rows(a[i], new, at))
                      for a, new in ((wk, k), (wv, v)))
        with jax.named_scope("kv_view"):
            tbl, shifted = decode_attention.ring_view(ring, self.pos, window,
                                                      page_len)
            k_eff, v_eff = (decode_attention.gather_pages(
                a[i], tbl, self.nkv, self.pack) for a in (wk, wv))
            mask = decode_attention.visible(
                jnp.arange(k_eff.shape[2])[None, None, :],
                (shifted[:, None] + jnp.arange(S)[None])[:, :, None], 1,
                window)
        return self._einsum(q, k_eff, v_eff, mask, scope), (wk, wv)

    def advanced(self, planes, wplanes=None):
        """The cache dict after the pass: the written planes (``wplanes``:
        the window group's, where there is one), every frontier moved by S.
        ``dict(cache, ...)``, NOT a fresh literal, so hierarchy keys (scale
        planes, prefix views) and an adapter's ``aux_`` state survive the
        decode scan's cache threading."""
        if self.latent:
            return dict(self.cache, k=planes[0], pos=self.pos + self.S)
        out = dict(self.cache, k=planes[0], v=planes[1],
                   pos=self.pos + self.S)
        if self.int8:
            out["k_scale"], out["v_scale"] = planes[2], planes[3]
        if wplanes is not None:
            out["wk"], out["wv"] = wplanes
        return out


@hot_path
def _forward(params, cfg, ids, cache, last_only=False, attn_name=None):
    """ids [B, S], row b starting at cache['pos'][b]; returns
    (logits [B, S, V] fp32, updated cache). S=prompt_len for prefill, S=1
    inside the decode scan. Positions are PER ROW: each row embeds, masks
    and writes its k/v against its own frontier, so rows at different
    sequence lengths (the serving engine's slots) share one program.
    ``last_only`` evaluates the LM head on the final position only (the
    prefill path: sampling reads just that row, and a [B, Tp, vocab]
    fp32 buffer would otherwise dominate prefill memory). ``attn_name``
    names the attention kernel in a trace where the caller is not the
    decode lane (``append_forward``: ``prefill_attn``).

    The regions of a trace (``jax.named_scope``, under the caller's
    ``prefill_lane`` / ``decode_scan``): ``embed``, then per layer ``attn``
    (LayerNorm, qkv, attention, projection), ``kv_write`` and ``kv_view``
    (``CacheAttention``, which owns the cache's layouts and kernels) and
    ``mlp``, then ``lm_head``."""
    B, S = ids.shape
    nh, hd = cfg.n_head, cfg.n_embd // cfg.n_head
    attend = CacheAttention(cfg, cache, S, attn_name)
    eps = cfg.layer_norm_epsilon
    # gather, THEN cast (bit for bit the cast table's rows): no whole-table
    # convert that XLA would hoist out of the decode scan and run once a step
    pe = params["wpe"][attend.q_pos].astype(cfg.dtype)     # [B, S, C]
    with jax.named_scope("embed"):
        x = params["wte"][ids].astype(cfg.dtype) + pe
    planes = attend.planes

    for i in range(cfg.n_layer):
        blk = params["h_{}".format(i)]
        with jax.named_scope("attn"):
            h = _ln(x, blk["ln_1"], eps)
            qkv = _dense(h, blk["attn"]["c_attn"])
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
            k = k.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
            v = v.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
        y, planes = attend(i, q, k, v, planes)
        with jax.named_scope("attn"):
            y = y.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_embd)
            x = x + _dense(y, blk["attn"]["c_proj"])
        with jax.named_scope("mlp"):
            h = _ln(x, blk["ln_2"], eps)
            h = _dense(h, blk["mlp"]["c_fc"])
            h = jax.nn.gelu(h, approximate=True)
            x = x + _dense(h, blk["mlp"]["c_proj"])

    if last_only:
        x = x[:, -1:]
    with jax.named_scope("lm_head"):
        x = _ln(x, params["ln_f"], eps)
        logits = jnp.einsum("bsc,vc->bsv", x.astype(jnp.float32),
                            params["wte"].astype(jnp.float32))
    return logits, attend.advanced(planes)


@hot_path
def append_forward(params, cfg, ids, cache, n_valid=None):
    """Append ``ids`` [B, S] at each row's frontier ``cache['pos']`` —
    the chunked-prefill primitive: one prompt slice per call, causally
    masked against everything already in the cache (the same per-row
    global-position mask decode uses), k/v written in place at the
    frontier. Returns (fp32 logits [B, S, V], advanced cache).

    ``n_valid`` [B] (default: all S) marks how many LEADING columns per
    row are real tokens; the frontier advances by ``n_valid``, not S.
    Pad columns still write k/v — but at positions >= the advanced
    frontier, where the causal mask hides them until the next append or
    decode write lands on top (the KV pool's stale-cache rule). Their
    logits are garbage the caller must ignore. The cache plane must
    leave S positions of slack past the last admissible frontier so the
    frontier write never clamps (inference/kv_pool.py over-allocates by
    ``prefill_chunk``)."""
    pos0 = cache["pos"]
    logits, cache = _forward(params, cfg, ids, cache,
                             attn_name="prefill_attn")
    if n_valid is not None:
        cache = dict(cache, pos=pos0 + n_valid)
    return logits, cache


@hot_path
def decode_step(params, cfg, tok, cache):
    """Advance every row one token: feed ``tok`` [B] (the token sitting at
    each row's frontier ``cache['pos']``), write its k/v there, and return
    (fp32 logits [B, V] for the next position, advanced cache). THE decode
    step program — ``generate``'s scan body and the serving engine's
    chunked decode (deepspeed_tpu.inference) both drive it, which is what
    keeps single-shot and continuous-batching outputs token-identical."""
    logits, cache = _forward(params, cfg, tok[:, None], cache)
    return logits[:, 0], cache


@hot_path
def verify_forward(params, cfg, ids, cache):
    """Score ``ids`` [B, S] at each row's frontier WITHOUT advancing it —
    the speculative-decoding VERIFY primitive. Row b's ids are
    [last_tok, draft_0 .. draft_{S-2}]: the token sitting at the frontier
    followed by drafted candidates, so ``logits[b, i]`` is the model's
    distribution for position ``pos[b] + i + 1`` — exactly what
    ``decode_step`` would have produced after emitting the first i draft
    tokens. k/v for ALL S positions are written in place (a draft token
    that gets accepted already has correct cache entries — its k/v depend
    only on the token id and position, both fixed at draft time), but
    ``pos`` is returned UNCHANGED: the caller advances it by the accepted
    count only, and rejected positions sit past the frontier where the
    stale-cache rule (kv_pool docstring) masks or overwrites them —
    rollback is simply not moving the frontier. The cache plane needs
    S-1 positions of slack past the last admissible frontier so the
    write never clamps (same contract as ``append_forward``)."""
    pos0 = cache["pos"]
    logits, cache = _forward(params, cfg, ids, cache)
    return logits, dict(cache, pos=pos0)


@hot_path
def ngram_draft(toks, pos, n, k):
    """Prompt-lookup drafting (n-gram self-speculation): for each row,
    find the MOST RECENT earlier occurrence of the row's trailing
    ``n``-gram inside its own context ``toks[b, :pos[b]+1]`` (prompt +
    tokens generated so far, with the undecoded frontier token at
    ``pos[b]``) and propose the ``k`` tokens that followed it.

    ``toks`` [B, T] is the token ring (positions > pos[b] may hold
    garbage — candidates are masked to ``j < pos[b]`` so it is never
    read); ``pos`` [B] the per-row frontiers; ``n``/``k`` are static.
    Rows with no match (or frontiers shorter than the n-gram) fall back
    to repeating the frontier token k times — an arbitrary but valid
    draft: a wrong draft costs nothing beyond the verify FLOPs already
    being paid, which is the whole economics of self-drafting. The
    continuation gather is clipped to ``<= pos[b]``, so a match near the
    frontier drafts from the (valid) suffix it overlaps. Returns int32
    [B, k]."""
    B, T = toks.shape
    idx = jnp.arange(T)

    def per_row(row, p):
        last = row[jnp.clip(p, 0, T - 1)]
        # match[j]: the n-gram ENDING at ring position j equals the one
        # ending at the frontier p. Built from n static shift-compares;
        # roll's wraparound only pollutes j < n-1, which the window mask
        # excludes.
        match = (idx >= n - 1) & (idx < p)
        for i in range(n):
            match &= jnp.roll(row, i) == row[jnp.clip(p - i, 0, T - 1)]
        j = jnp.max(jnp.where(match, idx, -1))          # most recent
        cont = row[jnp.clip(j + 1 + jnp.arange(k), 0, jnp.maximum(p, 0))]
        return jnp.where(j >= 0, cont, jnp.full((k,), last))

    return jax.vmap(per_row)(toks, pos.astype(jnp.int32)).astype(jnp.int32)


@hot_path
def accept_counts(draft, choices, ok=None):
    """Speculative ACCEPT rule: given per-row drafts [B, K] and the
    model's own choices [B, K+1] from a verify pass (choices[:, i] is
    what the model picks at position pos+i+1, via argmax or the
    positional-rng sampler — either way conditioned on the draft prefix,
    which equals the true prefix wherever it matters), return [B] counts
    in ``1..K+1``: 1 (the always-correct choice at the original
    frontier) + the length of the longest prefix where draft agrees with
    choice. This is exact speculative decoding for deterministic
    samplers: every emitted token is conditioned on an accepted —
    therefore model-chosen — prefix, so the output stream is identical
    to one-token-at-a-time decode. ``ok`` [B, 1] or [B, K] (optional)
    vetoes agreement per row/lane (False forces count 1 — the non-spec
    slots cohabiting a spec batch)."""
    agree = draft == choices[:, :draft.shape[1]]
    if ok is not None:
        agree = agree & ok
    return 1 + jnp.sum(jnp.cumprod(agree.astype(jnp.int32), axis=1), axis=1)


def _sample(logits, rng, temperature, top_k):
    """[B, V] fp32 logits -> [B] token ids."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, jnp.finfo(jnp.float32).min, logits)
    return jax.random.categorical(rng, logits, axis=-1)


@functools.partial(jax.jit, static_argnums=(1, 3, 4, 5, 7))
def _generate_jit(params, cfg, prompt_ids, max_new_tokens, temperature,
                  top_k, rng, eos_token_id):
    B, Tp = prompt_ids.shape
    cache_len = Tp + max_new_tokens
    if cfg.use_flash_decode:
        # Round the cache plane up to the kernel's block quantum so the
        # fused path engages; padded positions sit past every frontier
        # (masked, never embedded), so the extra plane is inert.
        cache_len = decode_attention.pad_cache_len(cache_len)
    cache = init_cache(cfg, B, cache_len)
    logits, cache = _forward(params, cfg, prompt_ids, cache,
                             last_only=True)                   # prefill
    rng0, rng = jax.random.split(rng)
    first = _sample(logits[:, -1], rng0, temperature, top_k)
    done = jnp.zeros((B,), bool) if eos_token_id is not None else None

    def step(carry, rng_t):
        tok, cache, done = carry
        logits, cache = decode_step(params, cfg, tok, cache)
        nxt = _sample(logits, rng_t, temperature, top_k)
        if done is not None:
            done = done | (tok == eos_token_id)
            nxt = jnp.where(done, eos_token_id, nxt)
        return (nxt, cache, done), nxt

    (_, _, _), rest = jax.lax.scan(
        step, (first, cache, done),
        jax.random.split(rng, max_new_tokens - 1))
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def generate(model, params, prompt_ids, max_new_tokens, temperature=1.0,
             top_k=None, rng=None, eos_token_id=None):
    """Sample ``max_new_tokens`` continuations of ``prompt_ids`` [B, Tp].

    ``model`` is the GPT2LMHeadModel (its config drives shapes/dtype);
    ``params`` the trained tree (``engine.params`` or a checkpoint).
    ``temperature=0`` is greedy (rng unused); otherwise pass a PRNG key.
    Returns [B, max_new_tokens] int32. Rows that emit ``eos_token_id``
    keep repeating it (fixed-length output; trim host-side).
    """
    cfg = as_gencfg(getattr(model, "config", model))
    assert max_new_tokens >= 1
    if rng is None:
        rng = jax.random.PRNGKey(0)
    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    assert prompt_ids.shape[1] + max_new_tokens <= cfg.n_positions, \
        "prompt + new tokens exceed n_positions={}".format(cfg.n_positions)
    # Host-side profiler scope around the whole-batch dispatch: shows up
    # as one "generation.generate" block on a ``jax.profiler`` capture.
    with jax.profiler.TraceAnnotation("generation.generate"):
        return _generate_jit(params, cfg, prompt_ids, int(max_new_tokens),
                             float(temperature), top_k, rng, eos_token_id)
