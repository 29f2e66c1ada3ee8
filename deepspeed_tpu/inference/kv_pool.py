"""Slotted KV-cache pool — fixed-shape state for continuous batching.

vLLM's paged KV cache (Kwon et al., SOSP'23) exists to fight GPU memory
fragmentation from dynamic allocation; under XLA there IS no dynamic
allocation — the constraint is the opposite: every program shape must be
static. So the TPU-native analogue is a SLOT pool: one pre-allocated
``[layers, slots, heads, max_len, head_dim]`` k/v cache plus per-slot
scalar state, where "admitting a request" writes a slot index and
"evicting" clears a flag. Batch composition changes without reshaping,
so the decode program never recompiles (Orca-style continuous batching,
Yu et al., OSDI'22, under a static shape).

Per-slot state vector (all ``[slots]``-shaped device arrays):

- ``pos``        row frontier: the sequence position the next k/v write
                 lands at (== current sequence length);
- ``last_tok``   the token sitting at the frontier (decode input);
- ``active``     slot is mid-generation; inactive slots keep running in
                 the fused program but are frozen (pos pinned, emissions
                 masked) — same trick as ``generate``'s EOS rows;
- ``remaining``  new tokens this request may still emit;
- ``eos``        per-request EOS id (-1: none);
- ``temp``/``top_k``/``seed``  per-request sampling params, traced (a
                 request mix never changes the program);
- ``spec``       speculative decoding enabled for this request (the
                 accept rule vetoes draft agreement when False, so spec
                 and non-spec requests cohabit one program).

Speculation adds a TOKEN RING ``toks`` [slots, plane_len] (int32):
position p holds the token the row placed there — prompt tokens during
prefill, then every accepted (and the bonus) token as decode advances.
It obeys the SAME stale rule as the k/v planes: positions ``<= pos[b]``
are valid (``toks[b, pos[b]]`` == ``last_tok[b]``, the frontier token
whose k/v are not yet written), anything past the frontier is garbage
that a later write covers before the frontier reaches it. The n-gram
drafter (models.generation.ngram_draft) only ever matches candidates
strictly below the frontier, so it never reads garbage — and even a
"lucky" garbage-continuation draft would merely be verified and
rejected like any other wrong draft.

Stale cache safety: an evicted slot's k/v are NOT cleared. Re-admission
prefills positions ``0..Tp-1``, and decode writes position ``p`` before
any query's causal mask (``k_pos <= q_pos``) can reach it — stale keys
are always either overwritten or masked, never attended.

Layout invariants the flash-decode kernel
(ops/transformer/kernels/decode_attention.py) relies on:

- plane layout is ``[layers, slots, heads, plane_len, head_dim]`` with
  the LENGTH dim fourth — the kernel blocks along it, so it must be the
  second-minor axis of each per-layer ``[slots, heads, len, hd]`` view;
- when flash-decode serves the pool, ``plane_len`` is padded up to a
  multiple of ``decode_attention.BLOCK_MIN`` (128) by ``init_pool``;
  padding is inert because admission still enforces the CONFIGURED
  ``max_len`` (``prompt + max_new_tokens <= max_len``), so no frontier
  ever reaches a padded position and the mask excludes them all;
- under chunked prefill the plane carries ``prefill_chunk`` extra SLACK
  positions past ``max_len`` (then block-quantum padding on top), so an
  append's multi-position frontier write stays in bounds for every
  admissible frontier — slack positions are masked exactly like quantum
  padding, never attended. Speculative decoding raises the floor to
  ``spec_k + 1``: a verify writes k/v at ``pos..pos+spec_k`` and the
  token ring takes the K+1 choices at ``pos+1..pos+spec_k+1``, both
  from frontiers as deep as ``max_len - 1``, so the engine sizes
  ``slack = max(prefill_chunk, spec_k + 1)`` and neither write ever
  clamps (``dynamic_update_slice`` clamping would silently shift a
  frontier write onto LIVE positions — the one failure mode this whole
  slack scheme exists to rule out);
- ``pos[b]`` is the PRE-write frontier: positions ``0..pos[b]-1`` hold
  the row's valid k/v, everything at ``>= pos[b] + S`` (after a write of
  S new positions) is zeros or a stale request's data. The kernel's
  per-row visibility rule ``k_pos <= pos[b] + i`` (query row i) must
  exactly match models/generation.py's einsum mask — parity tests pin
  this — so stale positions are skipped, not merely down-weighted;
- frontiers only move via the jitted programs (prefill sets, decode
  advances by S); host code never writes ``pos`` directly, which is what
  makes ``max_active_frontier`` a safe work-bound hint between chunks.

THE PAGED ARENA IS STORED AS THE KERNELS READ IT. The paged layout
(``init_pool(page_len=)``) keeps ONE arena per k and v,
``[layers, pages, heads / g, page_len, g * head_dim]``: where the head dim
does not fill a 128-lane tile, ``g = 128 // head_dim`` heads share one
(``decode_attention.lane_pack``: GPT-2's heads of 64 give g = 2 and
``[24, 145, 8, 128, 128]`` at 355M; a head dim of 128 gives g = 1 and
``[L, P, H, page_len, 128]``, nothing packed). Head ``g * p + a`` of the
model lives in lanes ``a * D .. a * D + D - 1`` of stored head ``p``; a
head count ``g`` does not divide (gpt2-xl's 25) gets a zero head, and a
model with fewer heads than a tile holds packs them all (``g`` = heads).
The rule reads ``n_embd // n_head`` and ``n_head``, nothing else: no
key, no flag. Why: a minor dim of 64 is padded to 128 lanes wherever the
chip stores or moves it, so XLA kept such an arena page-length minor
between steps and converted all of it (0.9 GB each of k and v) where a
step entered and left, and the kernels moved twice the bytes they
attended (PERF.md, PR 30). The int8 tier's scale arenas stay a scale a
head of the MODEL and position, ``[L, P, g * ceil(H / g), page_len]``.
Every paged pool of a model has this ONE shape, whatever its page size;
whoever indexes pages (swap records, copy on write, handoff: axis 1)
carries the trailing dims through, and whoever needs a head on its own
(the XLA fallback for pages under a kernel block, a prefix record shipped
in the dense format) goes through ``pack_heads`` / ``unpack_heads``. The
dense slot pool and ``generate()``'s cache are not packed.

WHERE A PAGED POOL IS WRITTEN. When ``page_len`` is a kernel block (a
multiple of 128: what the chip serves), no program ever forms a
per-layer value of the arena: the frontier rows are appended in place by
the ``kv_append`` kernel and attention reads pages through the decode
kernel's own index map, both addressing
``arena[layer, block_tbl[slot, pos // page_len], head // g, pos % page_len]``
(ops/transformer/kernels/decode_attention.py). The views below pass the
arenas through untouched, so the donated buffer the step received is the
buffer it returns. An append rewrites the frontier page of each row,
which is sound because a live frontier page belongs to one row; frozen
rows all point at the trash page 0, which nothing reads.

A RECURRENT STATE A SLOT. A model whose ``cache_spec()`` names
``slot_state`` (``((key, shape a row, dtype), ...)``, keys ``slot_*``: the
decoder block's Mamba-2 layers give a ``slot_ssm<j>`` [slots, N, H * P]
float32 and a ``slot_conv<j>`` [slots, K - 1, conv width] a layer, its KDA
layers a ``slot_kda<j>`` and a ``slot_kdaconv<j>``, its gated short
convolutions ONE array a layer, the tail ``slot_shortconv<j>`` [slots, 2, C],
its Mamba-1 layers a ``slot_sel<j>`` [slots, N, W] float32 (N = 16 on the
sublanes, the channels on the lanes) and a ``slot_selconv<j>`` [slots, K - 1,
W]: a kind names as many as it carries, and nothing here counts them)
holds, beside the k and v planes of the layers that DO hold keys (the planes
are as deep as those layers only), state with NO position axis: a fixed size
a slot whatever its context, slot-major so that a slot's share is one
contiguous slice. It IS per-slot state, unlike an adapter's ``aux_`` keys:
``cache_view`` hands it to the decode step whole with ``n_valid`` (1 for a
slot that decodes, 0 for one that must not move: a free slot, a slot still
in the prefill lane), ``slot_cache_view`` / ``write_slot_cache`` carry one
slot's slice through the lane, and the hierarchy's capture and restore ship
it with the slot's scalars (``arr[slot]``, like ``pos``). Nothing of the
stale-cache rule applies to it: there is no position past the frontier to
hide garbage in, so every program that touches it masks instead
(``models/mamba2.py``, ``models/kda.py``, ``models/shortconv.py``,
``models/mamba1.py``), and what
the rule gave for free
(rollback by not advancing ``pos``, aliased prefixes) is refused for such a
model (``adapters/decoder.py``).

A LATENT CACHE. A model whose ``cache_spec()`` gives ``latent`` > 0 (latent
attention: ``models/decoder.py`` ``mla``) stores ONE head a token,
``n_embd`` wide (DeepSeek-V3: ``[c_kv (512) | k_r (64)]`` and zeros up to
640, a whole number of lane tiles), whose first ``latent`` lanes are also
the token's values: the pool holds the ``k`` plane ``[L, P, 1, page_len, W]``
(dense: ``[L, slots, 1, plane_len, W]``) and NO ``v`` plane. Every view
below names the planes the pool HAS (``_PLANES``), so the pager, the
offload and handoff records (which walk the pool's keys) and the stale-cache
rule carry over unchanged; ``kv_append`` appends to the one arena and the
``latent_decode`` kernel reads it. Why 640 and not 576, or a 512 + 128 pair:
a minor dim is stored and moved in whole 128-lane tiles, so 576 costs 640
in HBM and in VMEM whatever the shape says, and left to itself XLA may keep
such an arena page-length minor and convert it where a step enters and
leaves (what PR 30 met at 64); a pair of planes would double the appends and
the page fetches (a unit of the kernel is already smaller than its fixed
cost) for the same bytes. The int8 and prefix tiers are refused for such a
model (``adapters/decoder.py``).

BOTH AT ONCE. ``latent`` and ``slot_state`` compose (Kimi Linear: a latent
plane as deep as its MLA layers only, beside a ``slot_kda<j>``
[slots, H, d_k, d_v] float32 matrix state and a ``slot_kdaconv<j>``
[slots, K - 1, 3 H d] tail for KDA layer ``j``: three kinds of cache in one
pool). Nothing here knows the pair: ``init_pool`` builds the one-plane arena
and ``_with_slot_state`` adds whatever the spec names, the views hand over
the planes the pool HAS and every ``slot_*`` key, and the hierarchy's
records walk the pool's keys, so a captured slot ships its latent pages and
its state together. Such a model gets both sets of refusals.

A WINDOW GROUP. A model whose ``cache_spec()`` gives ``window_layers`` > 0
(layers that see the last ``window`` positions only: ``models/decoder.py``
``swa``) holds a SECOND pair of planes, ``wk`` / ``wv``, as deep as those
layers and as wide as the full group's. A paged pool stores them as A FIXED
RING OF PAGES A SLOT, ``[Lw, 1 + slots * n_ring, H / g, page_len, g * D]``
(page 0 the trash page; slot ``s`` owns pages ``1 + s * n_ring ..``;
``n_ring = decode_attention.ring_pages(window, page_len, slack)``: 5 pages of
128 at a window of 512 for a decode step, 6 with a lane slice of 128), so a
window layer costs a slot the same memory at any context and ``max_len``
does not enter its shape. It is fixed memory a slot, as ``slot_state`` is:
the page allocator, admission and the block table know the full group only.
No table of it is stored: the views make each row's ``ring_tbl`` from its slot
index (``decode_attention.ring_table``; a freed row, told by its full-group
table as everywhere, points at the trash page) and ``CacheAttention`` does the
ring arithmetic (logical page ``lp`` at place ``lp % n_ring``, a key's
position rebuilt from ``lp``, stale entries masked by position). The dense
slot pool keeps the planes whole, ``[Lw, slots, H, plane_len, D]``, the
window a mask. The hierarchy's records do not ship a ring, so what needs one
is refused by name (``adapters/decoder.py``).

CRASH-ONLY: the pool is DISPOSABLE state (docs/RESILIENCE.md). The
durable truth about every request lives host-side in the scheduler's
records; on a fatal step error the engine throws the pool away and
calls ``init_pool`` again — same config, same shapes, so the jitted
step program is a cache hit and ``compile_count`` does not move. Never
add pool state that cannot be reconstructed from (config, request
records): it would silently break request-level recovery.
"""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.analysis.annotations import hot_path
from deepspeed_tpu.ops.transformer.kernels import decode_attention
from deepspeed_tpu.parallel import mesh as mesh_lib

# State fields beside the k/v planes, with init value dtype.
_SLOT_FIELDS = (
    ("pos", jnp.int32, 0),
    ("last_tok", jnp.int32, 0),
    ("active", jnp.bool_, False),
    ("remaining", jnp.int32, 0),
    ("eos", jnp.int32, -1),
    ("temp", jnp.float32, 0.0),
    ("top_k", jnp.int32, 0),
    ("seed", jnp.uint32, 0),
    ("spec", jnp.bool_, False),
)


def plane_len_for(gcfg, max_len, slack=0):
    """Cache-plane length serving ``max_len`` positions under ``gcfg``:
    padded up to the flash-decode block quantum when the kernel serves
    the pool (see module docstring — padding is inert), ``max_len``
    as-is otherwise. ``slack`` adds inert positions past the last
    admissible frontier — chunked prefill needs ``prefill_chunk`` of
    them so an append's S-position frontier write NEVER clamps
    (``dynamic_update_slice`` clamps a start index whose window would
    run off the plane, which would silently shift the write onto live
    positions)."""
    if getattr(gcfg, "use_flash_decode", False):
        return decode_attention.pad_cache_len(max_len + slack)
    return max_len + slack


def paged_plane_len(gcfg, max_len, slack, page_len):
    """Logical plane length of one paged row: the dense plane length
    rounded UP to a whole number of pages, so the gathered logical plane
    ``[n_pages * page_len]`` covers every dense position (the
    bit-identity argument needs gathered and dense mask extents to
    agree; the round-up tail is inert padding like the block quantum)."""
    plane_len = plane_len_for(gcfg, max_len, slack)
    return -(-plane_len // page_len) * page_len


def init_pool(gcfg, num_slots, max_len, dtype=None, slack=0, hier=None,
              page_len=0, num_pages=None):
    """Zeroed pool pytree for ``num_slots`` sequences of up to ``max_len``
    positions under generation config ``gcfg`` (models.generation.as_gencfg).
    The allocated plane length is ``plane_len_for(gcfg, max_len, slack)``.

    ``hier`` (a kv_hierarchy.HierarchySpec, or None for the flat pool)
    widens the pool shape contract:

    - ``hier.int8``: the k/v planes hold int8 codes and the pool gains
      fp32 ``k_scale``/``v_scale`` [L, S, H, plane_len] — one symmetric
      absmax scale per (head, position), written by the same frontier
      writes as the codes and obeying the same stale rule;
    - ``hier.prefix``: read-only shared planes ``pk``/``pv``
      [L, prefix_slots, H, prefix_len, D] (+ scales when int8) plus
      per-slot ``pid`` (aliased row, -1 detached) and ``pbase`` (aliased
      span; positions < pbase resolve to the prefix row). pbase==0 makes
      a stale pid inert, so -1 needs no special casing in the programs.

    ``page_len > 0`` selects the PAGED layout instead: ``k``/``v``
    become a shared page arena ``[L, P, H / g, page_len, g * D]``, ``g``
    heads a lane tile (module docstring; int8 scales
    ``[L, P, g * ceil(H / g), page_len]``; physical
    page 0 is the reserved trash page — inference/paging.py) and the
    pool gains an int32 ``block_tbl`` [slots, plane_len / page_len]
    mapping each slot's logical pages to arena pages. ``num_pages``
    sizes the usable arena (None: dense-parity — ``num_slots`` rows'
    worth of pages). The prefix planes are NOT allocated in paged mode
    even under ``hier.prefix``: prefix sharing happens by installing
    refcounted pages into block tables (copy-on-write for the straddle
    page), so the shared content lives in the one arena.
    """
    dtype = dtype or gcfg.dtype
    hd = gcfg.n_embd // gcfg.n_head
    int8 = hier is not None and hier.int8
    kv_dtype = jnp.int8 if int8 else dtype
    # module docstring, A LATENT CACHE: one plane, no ``v``
    latent = bool(getattr(gcfg, "latent", 0))
    if page_len:
        plane_len = paged_plane_len(gcfg, max_len, slack, page_len)
        n_lp = plane_len // page_len
        usable = num_pages if num_pages is not None else num_slots * n_lp
        P = usable + 1  # + the trash page at index 0
        # Stored as the kernels read it: ``g`` heads share a lane tile
        # (module docstring, THE PAGED ARENA), from these two shapes alone.
        g = decode_attention.lane_pack(hd, gcfg.n_head)
        hp = -(-gcfg.n_head // g)
        kv_shape = (gcfg.n_layer, P, hp, page_len, g * hd)
        pool = {"k": jnp.zeros(kv_shape, kv_dtype),
                "block_tbl": jnp.zeros((num_slots, n_lp), jnp.int32),
                "toks": jnp.zeros((num_slots, plane_len), jnp.int32)}
        if not latent:
            pool["v"] = jnp.zeros(kv_shape, kv_dtype)
        if int8:
            sc_shape = (gcfg.n_layer, P, hp * g, page_len)
            pool["k_scale"] = jnp.zeros(sc_shape, jnp.float32)
            pool["v_scale"] = jnp.zeros(sc_shape, jnp.float32)
        if getattr(gcfg, "window_layers", 0):
            # a fixed ring a slot, page 0 the trash page
            n_ring = decode_attention.ring_pages(gcfg.window, page_len,
                                                 max(slack, 1))
            pool.update(_window_group(
                gcfg, (1 + num_slots * n_ring,) + kv_shape[2:], dtype, int8))
        for name, ft, fill in _SLOT_FIELDS:
            pool[name] = jnp.full((num_slots,), fill, ft)
        return _with_slot_state(pool, gcfg, num_slots)
    plane_len = plane_len_for(gcfg, max_len, slack)
    if getattr(gcfg, "use_flash_decode", False):
        assert decode_attention.decode_supported(plane_len), plane_len
    kv_shape = (gcfg.n_layer, num_slots, gcfg.n_head, plane_len, hd)
    pool = {"k": jnp.zeros(kv_shape, kv_dtype),
            # Token ring for n-gram self-drafting (module docstring) —
            # same length as the planes so ring writes share the slack
            # bound; int32 [slots, plane_len] is noise next to the k/v.
            "toks": jnp.zeros((num_slots, plane_len), jnp.int32)}
    if not latent:
        pool["v"] = jnp.zeros(kv_shape, kv_dtype)
    pool.update(_window_group(gcfg, kv_shape[1:], dtype, int8))
    if int8:
        sc_shape = kv_shape[:-1]
        pool["k_scale"] = jnp.zeros(sc_shape, jnp.float32)
        pool["v_scale"] = jnp.zeros(sc_shape, jnp.float32)
    if hier is not None and hier.prefix:
        p_shape = (gcfg.n_layer, hier.prefix_slots, gcfg.n_head,
                   hier.prefix_len, hd)
        pool["pk"] = jnp.zeros(p_shape, kv_dtype)
        pool["pv"] = jnp.zeros(p_shape, kv_dtype)
        if int8:
            pool["pk_scale"] = jnp.zeros(p_shape[:-1], jnp.float32)
            pool["pv_scale"] = jnp.zeros(p_shape[:-1], jnp.float32)
        pool["pid"] = jnp.full((num_slots,), -1, jnp.int32)
        pool["pbase"] = jnp.zeros((num_slots,), jnp.int32)
    for name, ft, fill in _SLOT_FIELDS:
        pool[name] = jnp.full((num_slots,), fill, ft)
    return _with_slot_state(pool, gcfg, num_slots)


def _window_group(gcfg, layer_shape, dtype, int8):
    """The zeroed ``wk`` / ``wv`` planes ``gcfg.window_layers`` names, each
    layer ``layer_shape`` (module docstring, A WINDOW GROUP); empty for a
    model without window layers."""
    n = getattr(gcfg, "window_layers", 0)
    if not n:
        return {}
    assert not int8, "a window group has no int8 form"
    return {name: jnp.zeros((n,) + tuple(layer_shape), dtype)
            for name in ("wk", "wv")}


def _with_slot_state(pool, gcfg, num_slots):
    """``pool`` with the recurrent state ``gcfg.slot_state`` names, zeroed
    (module docstring, A RECURRENT STATE A SLOT)."""
    for name, shape, dtype in getattr(gcfg, "slot_state", ()):
        assert name.startswith("slot_"), name
        pool[name] = jnp.zeros((num_slots,) + tuple(shape), dtype)
    return pool


def _slot_state(tree):
    return [name for name in tree if name.startswith("slot_")]


# The planes a pool may hold, in the order the views name them: a latent
# cache has no ``v``, only the int8 tier has scales, only a model with window
# layers a window group.
_PLANES = ("k", "v", "k_scale", "v_scale", "wk", "wv")


def _planes(pool):
    return {name: pool[name] for name in _PLANES if name in pool}


def window_pages_slot(pool):
    """Pages of ONE window layer's ring a slot holds in a paged pool (0: no
    window group, or a dense pool): read back from the arena's shape."""
    if "wk" not in pool or "block_tbl" not in pool:
        return 0
    return (pool["wk"].shape[1] - 1) // pool["block_tbl"].shape[0]


def _ring_view(pool, slots, tbl):
    """``{"ring_tbl": [B, n_ring]}`` for the rows of ``slots`` whose
    full-group table rows are ``tbl`` (module docstring, A WINDOW GROUP);
    empty for a pool without one."""
    from deepspeed_tpu.inference.paging import TRASH_PAGE

    n_ring = window_pages_slot(pool)
    if not n_ring:
        return {}
    return {"ring_tbl": decode_attention.ring_table(
        slots.astype(jnp.int32), n_ring, tbl[:, 0] != TRASH_PAGE)}


def slot_state_nbytes(gcfg):
    """Bytes of recurrent state ONE slot holds under ``gcfg`` (0 for a model
    whose rows carry keys only): a fixed size whatever the context."""
    import numpy as np
    return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
               for _, shape, dtype in getattr(gcfg, "slot_state", ()))


def snapshot_of(pool):
    """The leaves of ``pool`` a harvest reads: every per-slot scalar the
    host loop needs (``pos`` / ``active`` / ``last_tok``) and the adapter's
    ``aux_`` accumulators. The serving step returns this beside its tokens
    as outputs of their own (a few hundred bytes that are NOT donated on),
    so they outlive the pool: the next step is dispatched, its pool
    donated, before this step is harvested."""
    names = ["pos", "active", "last_tok"]
    names += [n for n in pool if n.startswith("aux_")]
    return {n: pool[n] for n in names}


def harvest_snapshot(pool):
    """ONE batched device->host transfer of every per-slot scalar the
    host loop reads at a harvest boundary: ``pos`` / ``active`` /
    ``last_tok`` land together, and ``free_slots`` /
    ``max_active_frontier`` derive from the snapshot instead of each
    paying its own sync (three round-trips per chunk collapse to one).
    Adapter ``aux_`` state (global accumulators, not per-slot) rides the
    same transfer so ``ModelAdapter.observe`` never pays its own sync.
    ``pool`` is a pool or the ``snapshot_of`` a step handed out. Read off a
    POOL the leaves are valid until the next program call moves the pool
    (it is donated), so the engine, which dispatches the next step first,
    harvests the step's own ``snapshot_of`` outputs: those stay valid
    until they are dropped. The result is a plain dict of numpy arrays."""
    import numpy as np
    snap = snapshot_of(pool)
    vals = jax.device_get(list(snap.values()))
    return {n: np.asarray(v) for n, v in zip(snap, vals)}


def max_active_frontier(pool, snap=None):
    """Host-side hint: the largest frontier among ACTIVE slots. The
    kernel already bounds its own work PER ROW from ``pool['pos']`` via
    scalar prefetch; this cross-row bound is the observability companion
    — the serving benchmark stamps it, and a future work-partitioned
    grid can cap its length extent with it. Pass ``snap`` (a
    ``harvest_snapshot``) to reuse an already-paid transfer; without it
    the call syncs on its own."""
    if snap is None:
        snap = harvest_snapshot(pool)
    pos, active = snap["pos"], snap["active"]
    return int((pos * active).max()) if pos.size else 0


def pool_nbytes(pool):
    """Total device bytes held by the pool: the k/v planes, a model's
    recurrent state a slot (``slot_*``: most of the pool where nine layers
    in ten hold no keys), the per-slot scalars and the token ring (noise).
    The telemetry
    ``kv_pool_bytes`` gauge reads this — it is a static fact of the
    compiled shapes, so one number describes the whole run."""
    return int(sum(getattr(leaf, "nbytes", 0)
                   for leaf in jax.tree_util.tree_leaves(pool)))


@hot_path
@jax.named_scope("kv_view")
def cache_view(pool):
    """The pool's k/v/pos as a ``models.generation`` cache dict — the
    decode step program consumes the pool's slots directly as batch rows.

    Hierarchy fields ride along data-driven (``_forward`` dispatches on
    the keys present, so the flat pool costs nothing new): int8 scale
    planes pass through, and each slot's aliased prefix row is GATHERED
    to a per-slot ``pk``/``pv`` [L, S, H, prefix_len, D] view — the
    clip makes a detached pid (-1) gather row 0 harmlessly, because its
    pbase of 0 selects none of it.

    PAGED pools pass the arenas WHOLE (no slot axis to slice — _forward
    writes and reads through ``block_tbl``); the table and the frontiers
    ride along as traced values. Where a page is a kernel block the
    arenas STAY whole all the way down: ``kv_append`` writes the frontier
    rows in place and the decode kernel indexes the layer itself, so the
    buffer this view names is the buffer ``fold_cache`` gets back."""
    cache = dict(_planes(pool), pos=pool["pos"])
    if "block_tbl" in pool:
        cache["block_tbl"] = pool["block_tbl"]
        cache.update(_ring_view(
            pool, jnp.arange(pool["block_tbl"].shape[0]), pool["block_tbl"]))
    if "pid" in pool:
        row = jnp.clip(pool["pid"], 0, pool["pk"].shape[1] - 1)
        cache["pk"] = jnp.take(pool["pk"], row, axis=1)
        cache["pv"] = jnp.take(pool["pv"], row, axis=1)
        cache["pbase"] = pool["pbase"]
        if "pk_scale" in pool:
            cache["pk_scale"] = jnp.take(pool["pk_scale"], row, axis=1)
            cache["pv_scale"] = jnp.take(pool["pv_scale"], row, axis=1)
    for name in pool:
        # Adapter aux state (GLOBAL accumulators, no slot axis) passes
        # through whole — the forward reads and re-emits it.
        if name.startswith("aux_"):
            cache[name] = pool[name]
    for name in _slot_state(pool):
        # Recurrent state passes whole too (the slots ARE the rows), with
        # the rows that may move it: only a slot that decodes.
        cache[name] = pool[name]
        cache["n_valid"] = pool["active"].astype(jnp.int32)
    return cache


def _slot_state_view(pool, slot):
    return {name: jax.lax.dynamic_slice_in_dim(pool[name], slot, 1, axis=0)
            for name in _slot_state(pool)}


@hot_path
@jax.named_scope("kv_view")
def slot_cache_view(pool, slot, pos):
    """ONE slot's k/v as a batch-1 cache dict for the prefill lane:
    plane slices (and scale slices when int8) along the slot axis, plus
    the slot's gathered prefix row when the pool carries one. ``slot``
    may be traced; ``pos`` is the [1]-shaped append frontier.

    PAGED pools carry the arenas whole (the scatter/gather indirection
    replaces the slot slice) with the one slot's block-table row."""
    if "block_tbl" in pool:
        cache = dict(_planes(pool), pos=pos,
                     block_tbl=jax.lax.dynamic_slice_in_dim(
                         pool["block_tbl"], slot, 1, axis=0))
        cache.update(_ring_view(pool, jnp.asarray(slot)[None],
                                cache["block_tbl"]))
        for name in pool:
            if name.startswith("aux_"):
                cache[name] = pool[name]
        return dict(cache, **_slot_state_view(pool, slot))
    cache = {name: jax.lax.dynamic_slice_in_dim(plane, slot, 1, axis=1)
             for name, plane in _planes(pool).items()}
    cache["pos"] = pos
    if "pid" in pool:
        row = jnp.clip(jax.lax.dynamic_index_in_dim(
            pool["pid"], slot, keepdims=False), 0, pool["pk"].shape[1] - 1)
        cache["pk"] = jax.lax.dynamic_slice_in_dim(pool["pk"], row, 1, axis=1)
        cache["pv"] = jax.lax.dynamic_slice_in_dim(pool["pv"], row, 1, axis=1)
        cache["pbase"] = jax.lax.dynamic_index_in_dim(
            pool["pbase"], slot, keepdims=False)[None]
        if "pk_scale" in pool:
            cache["pk_scale"] = jax.lax.dynamic_slice_in_dim(
                pool["pk_scale"], row, 1, axis=1)
            cache["pv_scale"] = jax.lax.dynamic_slice_in_dim(
                pool["pv_scale"], row, 1, axis=1)
    for name in pool:
        # Aux accumulators are global — the batch-1 view carries them
        # whole, same as cache_view.
        if name.startswith("aux_"):
            cache[name] = pool[name]
    return dict(cache, **_slot_state_view(pool, slot))


@hot_path
@jax.named_scope("kv_write")
def write_slot_cache(pool, slot, cache):
    """Fold a ``slot_cache_view`` batch-1 cache back into the pool.
    Only the slot's WRITABLE state returns: k/v (+ scales); the prefix
    planes are read-only to aliasers and ``pos`` install stays with the
    caller (the lane's conditional slot-field writes).

    PAGED pools fold the arenas back WHOLESALE: _forward wrote the
    slot's rows through the block table into the arena it was handed
    (in place, by ``kv_append``, where a page is a kernel block; through
    an XLA scatter otherwise), so the updated arena IS the pool's new
    truth. The table itself never folds back — it is host-owned
    (inference/paging.py) and the device only reads it."""
    if "block_tbl" in pool:
        pool = dict(pool)
        for name in _planes(pool):
            pool[name] = cache[name]
        for name in cache:
            if name.startswith("aux_"):
                pool[name] = cache[name]
        return _write_slot_state(pool, slot, cache)
    pool = dict(pool)
    for name in _planes(pool):
        pool[name] = jax.lax.dynamic_update_slice_in_dim(
            pool[name], cache[name], slot, axis=1)
    for name in cache:
        # Global aux accumulators fold back whole (no slot indexing).
        if name.startswith("aux_"):
            pool[name] = cache[name]
    return _write_slot_state(pool, slot, cache)


def _write_slot_state(pool, slot, cache):
    """The one slot's recurrent state back into its slice of the pool."""
    for name in _slot_state(pool):
        pool[name] = jax.lax.dynamic_update_slice_in_dim(
            pool[name], cache[name], slot, axis=0)
    return pool


@hot_path
@jax.named_scope("kv_write")
def fold_cache(pool, cache):
    """Fold a full-batch ``cache_view`` cache back into the pool after a
    decode/verify step: k/v planes and scale planes. The gathered
    ``pk``/``pv`` views are DERIVED state and never fold back."""
    upd = {name: cache[name] for name in _planes(pool)}
    for name in cache:
        if name.startswith(("aux_", "slot_")):
            upd[name] = cache[name]
    return dict(pool, **upd)


def kv_spec(mesh, n_head):
    """PartitionSpec for a k/v plane [L, S, H, T, D]: heads over 'model'
    when divisible (parallel/mesh.py owns the policy — it must stay
    aligned with DEFAULT_TP_RULES' column-parallel qkv split)."""
    return mesh_lib.kv_cache_spec(mesh, n_head)


def pool_shardings(mesh, pool):
    """NamedSharding pytree matching ``pool``: k/v head-sharded over
    'model' (the heads the pool STORES on axis 2: packed ones in a paged
    arena), per-slot state replicated. Used both to place the initial
    pool and to pin jitted programs' out_shardings (without the pin,
    GSPMD may silently replicate the cache on output and the memory
    saving evaporates — same lesson as the pipeline engine's opt state)."""
    kv = NamedSharding(mesh, kv_spec(mesh, pool["k"].shape[2]))
    rep = NamedSharding(mesh, P())
    # Prefix planes share the k/v rank/layout, so the same head-sharded
    # spec applies; scale planes are small — replicate them.
    return {name: (kv if name in ("k", "v", "pk", "pv", "wk", "wv") else rep)
            for name in pool}


def shard_pool(mesh, pool):
    sh = pool_shardings(mesh, pool)
    return {name: jax.device_put(arr, sh[name]) for name, arr in pool.items()}


def free_slots(pool, snap=None):
    """Host-side: indices of inactive slots. Pass ``snap`` (a
    ``harvest_snapshot``) to derive from the harvest's single batched
    transfer; without it the call pays its own device->host sync."""
    import numpy as np
    if snap is None:
        snap = harvest_snapshot(pool)
    return [int(i) for i in np.flatnonzero(~snap["active"])]
