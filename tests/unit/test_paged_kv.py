"""Paged KV cache (inference/paging.py + the paged kv_pool layout).

The contract under test (docs/INFERENCE.md, "Paged KV cache"):
1. BIT-IDENTITY — greedy AND sampled streams out of a paged engine are
   byte-equal to the dense engine's, whatever the page size; the paged
   kernels match the dense reference at ragged frontiers (fp and q8);
   spec-decode rollback works across page boundaries.
2. ONE PROGRAM — block tables are traced state; page churn, COW forks,
   swap traffic and recovery never move compile_count past 1.
3. CAPACITY — page-granular allocation carries >= 3x the dense pool's
   concurrent long_context sessions at fixed (actually FEWER) KV bytes.
4. DISPOSABILITY — crash recovery and mid-stream replica kill lose
   zero requests and replay bit-identically on rebuilt arenas.
5. ACCOUNTING — allocator lifecycle (reserve/map/COW/free) balances,
   pages-shed backpressure is structured, the gauge family exports
   through Prometheus, and the swap victim is scored by live pages.
"""

import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import Fault, FaultPlan, QueueFull
from deepspeed_tpu.inference.kv_hierarchy import pick_swap_victim
from deepspeed_tpu.inference.paging import TRASH_PAGE, PageAllocator
from deepspeed_tpu.loadgen import WorkloadSpec
from deepspeed_tpu.ops.transformer.kernels import decode_attention as da
from tests.unit.test_inference import (
    engine_of,
    make_model,
    prompts_of,
    seq_greedy,
)
from tests.unit.test_telemetry import _parse_prom


# A paged pool STORES its arena ``[L, P, H / g, page_len, g * D]``, ``g`` heads
# a lane tile (``da.lane_pack``). The shared tiny model's four heads of 16 are
# ONE stored head; these have several, and a zero head where g does not
# divide: name -> (n_embd, n_head, the arena's (H / g, g * D)).
STORED = {"4_heads_of_16": (64, 4, (1, 64)),
          "4_heads_of_64": (256, 4, (2, 128)),
          "5_heads_of_64": (320, 5, (3, 128)),
          "8_heads_of_32": (256, 8, (2, 128))}
_STORED_MODELS = {}


def stored_model(name):
    """(cfg, model, params, stored head dims) of ``STORED[name]``."""
    import jax
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    if name not in _STORED_MODELS:
        n_embd, n_head, dims = STORED[name]
        if n_head * 16 == n_embd:
            cfg, model, params = make_model()
        else:
            cfg = GPT2Config(n_embd=n_embd, n_layer=2, n_head=n_head,
                             vocab_size=1024, n_positions=128, dropout=0.0,
                             use_flash_attention=False, dtype=jnp.float32)
            model = GPT2LMHeadModel(cfg)
            params = jax.jit(model.init)(
                jax.random.PRNGKey(0),
                jnp.zeros((2, 12), jnp.int32))["params"]
        _STORED_MODELS[name] = (cfg, model, params, dims)
    return _STORED_MODELS[name]


def paged_engine_of(model, params, **kw):
    kw.setdefault("paged_kv", True)
    kw.setdefault("kv_page_len", 8)
    kw.setdefault("prefill_chunk", 8)
    return engine_of(model, params, **kw)


# ---------------------------------------------------- allocator lifecycle


def test_page_allocator_lifecycle():
    """Reserve -> map (drawing the reservation down) -> free balances
    exactly; freed rows point at the trash page; the admission gate's
    available() never counts promised pages."""
    pg = PageAllocator(num_slots=2, pages_per_slot=4, total_pages=6,
                      page_len=8)
    assert pg.pages_free() == 6 and pg.pages_in_use() == 0
    assert pg.pages_for(1) == 1 and pg.pages_for(8) == 1
    assert pg.pages_for(9) == 2

    pg.reserve(rid=7, n=3)
    assert pg.outstanding() == 3 and pg.available() == 3
    assert pg.can_reserve(3) and not pg.can_reserve(4)
    with pytest.raises(RuntimeError, match="reservation"):
        pg.reserve(rid=8, n=4)

    # Mapping draws the reservation down page for page.
    pg.bind_slot(0, 7)
    pg.ensure_mapped(0, upto_tokens=12)       # 2 pages
    assert pg.mapped[0] == 2 and pg.reserved[7] == 1
    assert pg.pages_in_use() == 2 and pg.available() == 3
    pg.ensure_mapped(0, upto_tokens=12)       # idempotent
    assert pg.pages_in_use() == 2
    rows = pg.row_pages(0)
    assert len(rows) == 2 and TRASH_PAGE not in rows
    assert all(pg.refcount[p] == 1 for p in rows)

    # upto is clamped to the row's logical capacity.
    pg.ensure_mapped(0, upto_tokens=10_000)
    assert pg.mapped[0] == 4

    # Free: every page back, row on trash, reservation dropped.
    pg.free_slot(0)
    pg.release_reservation(7)
    assert pg.pages_free() == 6 and pg.outstanding() == 0
    assert list(pg.table[0]) == [TRASH_PAGE] * 4
    assert pg.fragmentation(live_tokens=0) == 0.0


def test_page_allocator_cow_and_refcounts():
    """install_shared increfs, cow_page claims a private page, decref
    returns a page only at refcount zero — and the double-free guard
    makes decref after reset a no-op."""
    pg = PageAllocator(num_slots=3, pages_per_slot=4, total_pages=8,
                      page_len=4)
    pg.bind_slot(0, 1)
    pg.ensure_mapped(0, upto_tokens=8)
    shared = pg.row_pages(0)

    pg.install_shared(1, shared)              # aliaser: refcount 2
    assert pg.row_pages(1) == shared
    assert all(pg.refcount[p] == 2 for p in shared)
    assert pg.pages_in_use() == 2             # no new physical pages

    cow = pg.cow_page(1, shared[1])           # straddle page goes private
    assert cow not in shared and pg.refcount[cow] == 1
    # (The engine copies arena bytes src -> dst; the allocator only
    # hands out the destination.)

    pg.free_slot(0)                           # owner leaves: shared live
    assert all(pg.refcount[p] == 1 for p in shared)
    assert pg.pages_free() == 8 - 3
    pg.free_slot(1)                           # last ref: all pages back
    assert pg.pages_free() == 8

    # decref racing reset() must not double-insert into the free list.
    pg.bind_slot(2, 9)
    pg.ensure_mapped(2, upto_tokens=4)
    held = pg.row_pages(2)
    pg.reset()
    assert pg.decref(held) == 0
    assert pg.pages_free() == 8


def test_page_allocator_retry_hint_tracks_release_rate():
    pg = PageAllocator(num_slots=1, pages_per_slot=4, total_pages=4,
                      page_len=4)
    assert pg.retry_after_s(2) > 0            # floor before any history
    pg.bind_slot(0, 1)
    pg.ensure_mapped(0, upto_tokens=16)
    pg.free_slot(0, now=100.0)                # 4 releases at t=100
    hint = pg.retry_after_s(8, now=101.0)     # ~4 pages/s -> ~2s for 8
    assert 0.1 <= hint <= 10.0


# ----------------------------------------------------- kernel parity


def _paged_layout(k, v, page_len, seed=11):
    """Scatter dense [B, H, T, D] planes into a shuffled page arena +
    block table (page 0 kept as trash, like the real pool)."""
    b, h, t, d = k.shape
    n_lp = t // page_len
    perm = np.random.RandomState(seed).permutation(b * n_lp) + 1
    tbl = perm.reshape(b, n_lp).astype(np.int32)
    arena_k = np.zeros((b * n_lp + 1, h, page_len, d), k.dtype)
    arena_v = np.zeros_like(arena_k)
    for row in range(b):
        for lp in range(n_lp):
            sl = np.s_[:, lp * page_len:(lp + 1) * page_len]
            arena_k[tbl[row, lp]] = np.asarray(k[row])[sl]
            arena_v[tbl[row, lp]] = np.asarray(v[row])[sl]
    return jnp.asarray(arena_k), jnp.asarray(arena_v), jnp.asarray(tbl)


@pytest.mark.parametrize("s", [1, 3])
def test_paged_kernel_parity_at_ragged_frontiers(s):
    """Block-table gather == dense plane, bit for bit, at ragged
    per-row frontiers including a deep frontier appending into the last
    page — for the reference AND the public flash entry (which takes
    the same-math gather fallback at CPU page sizes)."""
    b, h, t, d, page_len = 3, 2, 24, 4, 4
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    # Frontiers: deep (appending in the LAST page), mid-page straddle,
    # page-aligned — the ragged mix one mixed step actually serves.
    pos = jnp.asarray([t - s, 5, 12], jnp.int32)
    want = np.asarray(da.decode_attention_reference(q, k, v, pos))

    ak, av, tbl = _paged_layout(k, v, page_len)
    got_ref = np.asarray(
        da.decode_attention_paged_reference(q, ak, av, tbl, pos))
    got_pub = np.asarray(
        da.flash_decode_attention_paged(q, ak, av, tbl, pos))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_pub, want)


def test_paged_q8_kernel_parity():
    """int8 paged == int8 dense: codes and scales gathered through the
    same table give the same dequantized attention."""
    b, h, t, d, page_len, s = 2, 2, 16, 4, 4, 1
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    kf = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    vf = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k, k_scale = da.quantize_kv(kf)
    v, v_scale = da.quantize_kv(vf)
    pos = jnp.asarray([t - 1, 6], jnp.int32)
    want = np.asarray(da.decode_attention_q8_reference(
        q, k, v, k_scale, v_scale, pos))

    ak, av, tbl = _paged_layout(np.asarray(k), np.asarray(v), page_len)
    aks, avs, _ = _paged_layout(np.asarray(k_scale)[..., None],
                                np.asarray(v_scale)[..., None], page_len)
    aks, avs = aks[..., 0], avs[..., 0]
    got = np.asarray(da.decode_attention_paged_q8_reference(
        q, ak, av, aks, avs, tbl, pos))
    got_pub = np.asarray(da.flash_decode_attention_paged_q8(
        q, ak, av, aks, avs, tbl, pos))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_pub, want)


# ----------------------------------------------------- engine bit-identity


def test_paged_engine_parity_greedy_sampled_one_program():
    """The tentpole invariant: a paged engine's streams — greedy AND
    sampled, ragged lengths, slot churn — are byte-equal to the dense
    engine's, on ONE compiled program, and the arena drains back to
    zero pages in use."""
    cfg, model, params = make_model()
    lens = [5, 9, 3, 12, 7, 6]

    def serve(**extra):
        eng = engine_of(model, params, max_slots=3, prefill_chunk=8,
                        **extra)
        reqs = []
        for i, p in enumerate(prompts_of(cfg, lens)):
            kw = {"max_new_tokens": 5 + (i % 3)}
            if i % 2:
                kw.update(temperature=0.8, seed=40 + i)
            reqs.append(eng.submit(p, **kw))
        eng.run()
        return eng, [r.tokens for r in reqs]

    dense, want = serve()
    paged, got = serve(paged_kv=True, kv_page_len=8)
    assert got == want, "paged streams diverged from dense"
    assert paged.compile_count == 1
    st = paged.kv_page_stats()
    assert st["pages_in_use"] == 0, "drained engine leaked pages"
    assert st["pages_free"] == st["pages_total"]
    assert dense.kv_page_stats() is None
    m = paged.metrics()
    assert m["paged_kv"] is True and m["kv_page_len"] == 8
    assert m["kv_hbm_bytes"] > 0


def test_paged_flash_engine_streams_equal_the_dense_pools():
    """Page 128 is a kernel block, so this engine takes the branch the
    chip serves: ``kv_append`` writes the arenas in place (one row a slot
    in the scan; the lane's chunks from page-aligned and, after a 5-token
    prompt's neighbour, mid-page frontiers) and the paged kernel reads
    them through the layer's index. Greedy and sampled streams, prompts
    and generations that cross a page boundary, slot churn: byte-equal to
    the dense pool's under the dense kernel, on ONE program."""
    cfg, model, params = make_model(n_positions=512)
    lens = [5, 130, 3, 140, 7, 60]

    def serve(**extra):
        eng = engine_of(model, params, max_slots=3, max_len=384,
                        prefill_chunk=64, use_flash_decode=True, **extra)
        reqs = []
        for i, p in enumerate(prompts_of(cfg, lens)):
            kw = {"max_new_tokens": 5 + (i % 3)}
            if i % 2:
                kw.update(temperature=0.8, seed=40 + i)
            reqs.append(eng.submit(p, **kw))
        eng.run()
        return eng, [r.tokens for r in reqs]

    dense, want = serve()
    paged, got = serve(paged_kv=True, kv_page_len=128)
    assert paged._pool["k"].shape[3] == 128 and "block_tbl" in paged._pool
    assert got == want, "paged + flash streams diverged from dense"
    assert paged.compile_count == dense.compile_count == 1
    assert paged.kv_page_stats()["pages_in_use"] == 0


@pytest.mark.parametrize("n_embd, n_head, mp, sharded", [
    (256, 8, 2, True),      # heads of 32: g 4, 2 packed heads over mp 2
    (64, 4, 4, False),      # heads of 16: ONE packed head, nothing to split
])
def test_paged_flash_engine_with_tensor_sharded_heads(
        eight_devices, n_embd, n_head, mp, sharded):
    """The same branch over a mesh with a 'model' axis: both kernels
    launch shard-local on the arenas' head shards (the append with every
    row on every shard: no shard may keep a row's write to itself), and
    the streams equal the unsharded dense pool's. What 'model' splits is
    the heads the arena STORES, ``H / g`` packed ones; a model whose heads
    all share one lane tile keeps the arena whole on every shard."""
    import jax
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.parallel import mesh as mesh_lib

    cfg = GPT2Config(n_embd=n_embd, n_layer=2, n_head=n_head,
                     vocab_size=1024, n_positions=512, dropout=0.0,
                     use_flash_attention=False, dtype=jnp.float32)
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 12), jnp.int32))["params"]
    mesh = mesh_lib.build_mesh(devices=jax.devices()[:mp], num_mp=mp,
                               num_dp=1)
    prompts = prompts_of(cfg, [5, 130, 60])

    def serve(mesh=None, **extra):
        eng = engine_of(model, params, mesh=mesh, max_slots=3, max_len=384,
                        prefill_chunk=64, use_flash_decode=True, **extra)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run()
        return eng, [r.tokens for r in reqs]

    _, want = serve()
    paged, got = serve(mesh=mesh, paged_kv=True, kv_page_len=128)
    g = paged.metrics()["kv_lane_pack"]
    assert paged._pool["k"].shape[2:] == (n_head // g, 128, 128 if sharded
                                          else 64)
    spec = paged._pool["k"].sharding.spec
    assert (len(spec) > 2 and spec[2] == mesh_lib.MODEL_AXIS) == sharded
    assert got == want, "tensor-sharded paged + flash streams diverged"


def test_spec_decode_rollback_across_page_boundary():
    """Speculative verify writes spec_k+1 positions per step; with
    page_len 4 < spec_k+1 every verify straddles a page boundary, so
    rejected drafts exercise the stale-page rule across pages. Streams
    must still match the non-spec dense engine exactly."""
    cfg, model, params = make_model()
    rng = np.random.RandomState(5)
    # Repetition-heavy prompts: the n-gram drafter finds matches, so
    # steps mix accepted runs and mid-page rollbacks.
    prompts = [np.tile(rng.randint(0, cfg.vocab_size, size=(4,)),
                       4).astype(np.int32) for _ in range(3)]
    eng = paged_engine_of(model, params, kv_page_len=4, max_slots=3,
                          spec_decode=True, spec_k=4, spec_ngram=3)
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    assert eng.compile_count == 1
    m = eng.metrics()
    assert m["accepted_per_step_mean"] is not None
    for r in reqs:
        assert r.tokens == seq_greedy(model, params, r.prompt, 10), \
            "spec rollback across a page boundary corrupted the stream"


@pytest.mark.parametrize("heads", ["4_heads_of_16", "4_heads_of_64",
                                   "5_heads_of_64"])
def test_paged_int8_prefix_offload_tiers_compose(heads):
    """All three hierarchy tiers over the paged pool: int8 arenas (q8
    paged kernel family), COW prefix sharing, live-page swap records.
    int8 is not bit-identical to fp by design — the pin is dense-int8
    == paged-int8, stream for stream. On a packed arena too: codes g heads
    a lane tile, scales a head of the model (with its zero head)."""
    cfg, model, params, dims = stored_model(heads)
    shared = prompts_of(cfg, [12], seed=9)[0]
    tails = prompts_of(cfg, [4, 5, 6], seed=10)
    prompts = [np.concatenate([shared, t]).astype(np.int32) for t in tails]

    def serve(**extra):
        eng = engine_of(model, params, max_slots=2, prefill_chunk=8,
                        int8_kv=True, prefix_cache=True, prefix_slots=2,
                        min_prefix_len=4, host_offload=True, swap_slots=4,
                        **extra)
        first = eng.submit(prompts[0], max_new_tokens=6)
        eng.run()                       # publish the prefix row
        rest = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
        eng.run()
        return eng, [r.tokens for r in (first,) + tuple(rest)]

    dense, want = serve()
    paged, got = serve(paged_kv=True, kv_page_len=8)
    g = paged.metrics()["kv_lane_pack"]
    assert paged._pool["k"].shape[2:] == (dims[0], 8, dims[1])
    assert paged._pool["k_scale"].shape[2:] == (dims[0] * g, 8)
    assert got == want, "paged int8+prefix+offload diverged from dense"
    assert paged.compile_count == dense.compile_count == 1
    assert paged.metrics()["prefix_hits"] == dense.metrics()["prefix_hits"]


@pytest.mark.parametrize("heads", ["4_heads_of_16", "4_heads_of_64",
                                   "8_heads_of_32"])
def test_cow_prefix_fork_divergence(heads):
    """TWO aliasers of one shared prefix admitted in the same round,
    then decoding divergent tails: full pages stay shared (one physical
    copy), each straddle page goes copy-on-write, and neither stream
    sees the other's writes. This exact two-wave shape caught a real
    bug (a stale device write cursor clobbering the shared page through
    a fresh block table), so it is pinned bit-for-bit against dense, on
    packed arenas (g = 4 and 2 heads a lane tile) as well: a page copied
    on write carries every head of its tile."""
    cfg, model, params, dims = stored_model(heads)
    shared = prompts_of(cfg, [13], seed=17)[0]
    tails = prompts_of(cfg, [3, 6], seed=18)
    prompts = [np.concatenate([shared, t]).astype(np.int32) for t in tails]

    def serve(**extra):
        eng = engine_of(model, params, max_slots=3, prefill_chunk=8,
                        prefix_cache=True, prefix_slots=2,
                        min_prefix_len=4, **extra)
        seedr = eng.submit(shared.astype(np.int32), max_new_tokens=4)
        eng.run()                       # wave 1: publish the prefix
        forks = [eng.submit(p, max_new_tokens=8, temperature=0.7,
                            seed=60 + i) for i, p in enumerate(prompts)]
        eng.run()                       # wave 2: both aliasers at once
        m = eng.metrics()
        return eng, [seedr.tokens] + [r.tokens for r in forks], m

    dense, want, dm = serve()
    paged, got, pm = serve(paged_kv=True, kv_page_len=4)
    assert paged._pool["k"].shape[2:] == (dims[0], 4, dims[1])
    assert got == want, "COW fork diverged from dense"
    assert pm["prefix_hits"] == dm["prefix_hits"] >= 2
    assert pm["prefix_inserts"] == dm["prefix_inserts"]
    assert paged.compile_count == 1
    # Drained slots released their COW pages; only the published prefix
    # row still legitimately pins pages (until eviction/reset).
    st = paged.kv_page_stats()
    assert 0 < st["pages_in_use"] < st["pages_total"]


@pytest.mark.parametrize("heads", ["4_heads_of_64", "5_heads_of_64"])
def test_packed_pool_page_stacks_and_prefix_records_round_trip(heads):
    """What leaves a packed pool and comes back: a slot's page stack
    (``capture_slot_paged`` / ``restore_slot_paged``: swap, preempt,
    handoff) carries the arena's own trailing dims, so it restores bit for
    bit into other pages; a prefix exported for another replica is the
    DENSE record ``[L, H, span, D]`` a dense engine exports for the same
    tokens, bit for bit, and a paged engine that adopts it serves the
    next request of that prefix as the donor would."""
    from deepspeed_tpu.inference.kv_hierarchy import offload

    cfg, model, params, dims = stored_model(heads)
    shared = prompts_of(cfg, [12], seed=9)[0]
    tail = prompts_of(cfg, [5], seed=10)[0]
    prompt = np.concatenate([shared, tail]).astype(np.int32)

    def engine(**extra):
        return engine_of(model, params, max_slots=2, prefill_chunk=8,
                         prefix_cache=True, prefix_slots=2, min_prefix_len=4,
                         **extra)

    donor, dense = engine(paged_kv=True, kv_page_len=8), engine()
    for eng in (donor, dense):
        eng.submit(shared.astype(np.int32), max_new_tokens=3)
        eng.run()
    toks = [int(t) for t in shared]
    (matched, record), (d_matched, d_record) = (
        eng.export_prefix(toks) for eng in (donor, dense))
    assert matched == d_matched and sorted(record) == sorted(d_record)
    for name in record:
        assert record[name].shape == d_record[name].shape
        np.testing.assert_array_equal(record[name], d_record[name])
    assert record["pk"].shape[1] == STORED[heads][1]      # every real head

    acceptor = engine(paged_kv=True, kv_page_len=8)
    assert acceptor.adopt_prefix(matched, record)
    want = donor.submit(prompt, max_new_tokens=6)
    donor.run()
    got = acceptor.submit(prompt, max_new_tokens=6)
    acceptor.run()
    assert got.tokens == want.tokens
    assert acceptor.metrics()["prefix_hits"] == 1

    # A live slot's page stack, captured and restored into fresh pages.
    live = donor.submit(prompt, max_new_tokens=40)
    donor.step()
    donor.step()
    pager, pool = donor._pager, donor._pool
    slot = next(s for s in range(2) if pager.row_pages(s))
    pages = pager.row_pages(slot)
    rec = offload.capture_slot_paged(pool, slot, pages)
    assert rec["k"].shape[1:] == (len(pages), dims[0], 8, dims[1])
    fresh = pager.alloc_pages(len(pages))
    assert fresh and not set(fresh) & set(pages)
    restored = offload.restore_slot_paged(pool, 1 - slot, rec, fresh)
    for name in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(restored[name])[:, np.asarray(fresh)], rec[name])
    assert not live.done


# --------------------------------------------------------- capacity pin


def test_capacity_pin_3x_long_context_sessions_at_fixed_hbm():
    """THE capacity claim: at (slightly FEWER) KV bytes than a 2-slot
    dense pool, page-granular allocation carries >= 3x the concurrent
    long_context sessions — every stream still bit-identical to dense,
    on one compiled program."""
    cfg, model, params = make_model()
    spec = WorkloadSpec.long_context(
        n_requests=12, rate=1000.0, seed=7, phrase_len=4,
        vocab_size=cfg.vocab_size,
        prompt_mean=5, prompt_sigma=0.3, prompt_min=4, prompt_max=6,
        output_mean=6, output_sigma=0.2, output_min=6, output_max=6)
    stream = list(spec.requests())   # both arms serve the SAME stream
    # Every request reserves exactly ceil((p + 6 new + 8 slack) / 4)
    # = 5 pages (p in 4..6), so the 34-page arena admits 6 concurrent
    # sessions (30 reserved, 4 free < 5) — the binding constraint.

    def serve(**extra):
        eng = engine_of(model, params, max_len=64, prefill_chunk=8,
                        max_queue=32, **extra)
        reqs = [eng.submit(lr.prompt, max_new_tokens=lr.max_new_tokens)
                for lr in stream]
        peak = 0
        while not eng.idle:
            eng.step()
            # Sessions the pool serves at once: those in a slot and those
            # whose last tokens are still on the chip, their slot given up
            # when that step was dispatched.
            peak = max(peak, len(eng._scheduler.running)
                       + len(eng._scheduler.landing))
        return eng, reqs, peak

    # Dense baseline: 2 slots of 72-position plane = 144 KV positions.
    dense, dense_reqs, dense_peak = serve(max_slots=2)
    # Paged: SAME byte envelope (34-page arena + trash = 140 positions
    # < 144), 8 nominal slots — page-aware admission is the binding
    # constraint, not slot count.
    paged, paged_reqs, paged_peak = serve(max_slots=8, paged_kv=True,
                                          kv_page_len=4, kv_pages=34)

    dense_bytes = dense.metrics()["kv_hbm_bytes"]
    paged_bytes = paged.metrics()["kv_hbm_bytes"]
    assert paged_bytes <= dense_bytes, \
        "capacity pin must hold HBM fixed (paged {} > dense {})".format(
            paged_bytes, dense_bytes)
    assert dense_peak == 2
    assert paged_peak >= 3 * dense_peak, \
        "paged pool carried {}x concurrent sessions, needs >= 3x".format(
            paged_peak / dense_peak)
    assert paged.compile_count == 1
    assert [r.tokens for r in paged_reqs] == \
           [r.tokens for r in dense_reqs], \
        "capacity without parity is cheating"


# ------------------------------------------------- pages backpressure


def test_queue_full_pages_reason_and_retry_hint():
    """When the queue head is blocked on PAGE capacity (slots exist),
    the shed is structured reason='pages' with a page-release-rate
    retry hint — the page-aware half of the admission satellite."""
    cfg, model, params = make_model()
    eng = paged_engine_of(model, params, max_slots=4, max_queue=1,
                          kv_page_len=8, kv_pages=4)
    p = prompts_of(cfg, [8, 9, 10], seed=2)
    # A budget that outlasts the two steps the first call dispatches (one
    # stays in flight): a request whose budget ends inside a dispatched
    # step gives its pages back at once.
    eng.submit(p[0], max_new_tokens=16)
    eng.step()                  # admit: reserves all 4 pages
    eng.submit(p[1], max_new_tokens=8)          # queued head, needs 4 > 0
    with pytest.raises(QueueFull) as ei:
        eng.submit(p[2], max_new_tokens=8)
    assert ei.value.reason == "pages"
    assert ei.value.retry_after_s is not None
    assert ei.value.retry_after_s > 0
    eng.run()


def test_submit_oversize_prompt_for_arena_raises():
    cfg, model, params = make_model()
    eng = paged_engine_of(model, params, max_slots=4, kv_page_len=8,
                          kv_pages=3)
    with pytest.raises(ValueError, match="page"):
        eng.submit(prompts_of(cfg, [20], seed=3)[0], max_new_tokens=30)


# ------------------------------------------------------- observability


def test_prometheus_exports_page_gauge_family():
    """Parser-level pin for the gauge family satellite: the live
    kv_pages_in_use / kv_pages_free / kv_page_fragmentation /
    kv_hbm_bytes gauges ride the standard text exposition."""
    cfg, model, params = make_model()
    eng = paged_engine_of(model, params, kv_page_len=8)
    reqs = [eng.submit(p, max_new_tokens=6)
            for p in prompts_of(cfg, [6, 9])]
    eng.step()
    eng.step()
    kinds, samples = _parse_prom(eng.prometheus())

    def sample(name):
        hits = [v for (n, _), v in samples.items() if n == name]
        assert hits, "missing gauge {}".format(name)
        return hits[0]

    for g in ("ds_tpu_kv_pages_in_use", "ds_tpu_kv_pages_free",
              "ds_tpu_kv_page_fragmentation", "ds_tpu_kv_hbm_bytes"):
        assert kinds[g] == "gauge"
    st = eng.kv_page_stats()
    assert sample("ds_tpu_kv_pages_in_use") == st["pages_in_use"] > 0
    assert sample("ds_tpu_kv_pages_free") == st["pages_free"]
    assert 0.0 <= sample("ds_tpu_kv_page_fragmentation") <= 1.0
    assert sample("ds_tpu_kv_hbm_bytes") == eng.metrics()["kv_hbm_bytes"]
    # The share of the block table that is work for the paged decode
    # kernel: each mapped row's pages up to its frontier, over slots x n_lp.
    pg, pos = eng._pager, eng._last_snap["pos"]
    live = sum(min(int(pos[s]) // 8 + 1, pg.pages_per_slot)
               for s in range(pg.num_slots) if pg.table[s, 0] != 0)
    assert kinds["ds_tpu_kv_live_page_share"] == "gauge"
    assert live > 0 and sample("ds_tpu_kv_live_page_share") == \
        pytest.approx(live / pg.table.size)
    # Heads a lane tile in the stored arena: the tiny model's four heads of
    # 16 are one stored head of 64 lanes. Read back from the arena's shape.
    assert kinds["ds_tpu_kv_lane_pack"] == "gauge"
    assert sample("ds_tpu_kv_lane_pack") == eng.metrics()["kv_lane_pack"] \
        == 4 == eng._pool["k"].shape[-1] // 16
    # Pages a unit of the paged kernel joins: one for pages of 8, which are
    # no kernel block, so every unit is full.
    assert kinds["ds_tpu_kv_unit_pages"] == kinds["ds_tpu_kv_unit_fill"] \
        == "gauge"
    assert sample("ds_tpu_kv_unit_pages") == 1 == \
        eng.metrics()["kv_unit_pages"]
    assert sample("ds_tpu_kv_unit_fill") == 1.0 == \
        eng.metrics()["kv_unit_fill"]
    eng.run()
    _, drained = _parse_prom(eng.prometheus())
    assert [v for (n, _), v in drained.items()
            if n == "ds_tpu_kv_pages_in_use"][0] == 0
    assert [v for (n, _), v in drained.items()
            if n == "ds_tpu_kv_live_page_share"][0] == 0
    assert [v for (n, _), v in drained.items()
            if n == "ds_tpu_kv_unit_fill"][0] == 0


def test_unit_gauges_say_how_far_pages_are_joined():
    """``kv_unit_pages`` is K as the launcher's rule resolves it for the
    pool's own arenas (here pages of 128 of ONE stored head of 64 float32
    lanes, 64 KB of keys and values: the two pages a row's table holds),
    and ``kv_unit_fill`` the live pages over K x units at the last harvest:
    a row in its first page holds one live page of a unit of two."""
    cfg, model, params = make_model()
    eng = paged_engine_of(model, params, kv_page_len=128, max_len=128)
    for p in prompts_of(cfg, [6, 9]):
        eng.submit(p, max_new_tokens=6)
    eng.step()
    eng.step()
    pool, m = eng._pool, eng.metrics()
    n_lp = pool["block_tbl"].shape[1]
    k = da.unit_pages([pool["k"], pool["v"]], cfg.n_head, 16, n_lp,
                      pool["k"].dtype)
    assert n_lp == 2 and k == 2 == m["kv_unit_pages"]
    live = [int(p) // 128 + 1 for s, p in enumerate(eng._last_snap["pos"])
            if eng._pager.table[s, 0] != 0]
    assert live and set(live) == {1}
    assert m["kv_unit_fill"] == sum(live) / (k * sum(
        -(-x // k) for x in live)) == 0.5
    _, samples = _parse_prom(eng.prometheus())
    assert [v for (n, _), v in samples.items()
            if n == "ds_tpu_kv_unit_pages"] == [2]
    assert [v for (n, _), v in samples.items()
            if n == "ds_tpu_kv_unit_fill"] == [0.5]


@pytest.mark.parametrize("name, page_len, rows", [
    # 128 stored lanes and pages of a kernel block: ONE launch walks all
    # four slots' rows
    ("4_heads_of_64", 128, 4), ("8_heads_of_32", 128, 4),
    # 64 stored lanes: Mosaic slices no such arena, a page a row instead
    ("4_heads_of_16", 128, 0),
    # pages of 8 are no kernel block: the scatter
    ("4_heads_of_64", 8, 0)])
def test_append_unit_rows_gauge_is_the_launchers_own_rule(name, page_len,
                                                          rows):
    """``kv_append_unit_rows`` is R as ``decode_attention.append_unit_rows``
    resolves it for the pool's own arenas and ``max_slots`` rows (0 where no
    launch walks its rows), in ``metrics()`` and the export alike."""
    cfg, model, params, _ = stored_model(name)
    eng = paged_engine_of(model, params, kv_page_len=page_len, max_len=128)
    pool = eng._pool
    if page_len == 128:
        assert da.append_unit_rows([pool["k"], pool["v"]],
                                   pool["block_tbl"].shape[0]) == rows
    assert eng.metrics()["kv_append_unit_rows"] == rows
    kinds, samples = _parse_prom(eng.prometheus())
    assert kinds["ds_tpu_kv_append_unit_rows"] == "gauge"
    assert [v for (n, _), v in samples.items()
            if n == "ds_tpu_kv_append_unit_rows"] == [rows]


def test_pick_swap_victim_scores_live_pages():
    """Paged victim ordering: the session holding the most LIVE pages
    (true reclaim) loses, even when dense budget order says otherwise."""
    now = time.time()
    short_budget_many_pages = types.SimpleNamespace(
        rid=1, max_new_tokens=4, tokens=[0, 0, 0], last_touch=now)
    big_budget_few_pages = types.SimpleNamespace(
        rid=2, max_new_tokens=100, tokens=[], last_touch=now)
    cands = [short_budget_many_pages, big_budget_few_pages]
    # Dense scoring: budget order picks rid 2.
    assert pick_swap_victim(cands, now=now).rid == 2
    # Paged scoring: rid 1 holds 40 pages vs 2 — reclaim wins.
    victim = pick_swap_victim(cands, now=now,
                              live_pages={1: 40, 2: 2}, page_len=8)
    assert victim.rid == 1
    # Ties fall to the oldest rid, matching the dense rule.
    tie = pick_swap_victim(cands, now=now, live_pages={1: 3, 2: 3},
                           page_len=8)
    assert tie.rid == 1


# ------------------------------------------------------- disposability


def test_paged_crash_recovery_zero_lost_bit_identical():
    """Mid-stream crash on a paged engine: the arena and allocator are
    rebuilt from zero, durable records replay into fresh pages, and
    every stream (greedy and sampled) finishes byte-equal to the
    fault-free dense run — with the page ledger balanced after drain."""
    cfg, model, params = make_model()
    lens = [5, 9, 6, 8]

    def submit_all(eng):
        reqs = []
        for i, p in enumerate(prompts_of(cfg, lens, seed=6)):
            kw = {"max_new_tokens": 6}
            if i % 2:
                kw.update(temperature=0.7, seed=80 + i)
            reqs.append(eng.submit(p, **kw))
        return reqs

    ref_eng = engine_of(model, params, max_slots=2, prefill_chunk=8)
    ref_reqs = submit_all(ref_eng)
    ref_eng.run()
    want = [r.tokens for r in ref_reqs]

    eng = paged_engine_of(model, params, max_slots=2, kv_page_len=4,
                          fault_injection=True)
    reqs = submit_all(eng)
    eng.inject_faults(FaultPlan(faults=(Fault("raise", step=3),)))
    eng.run()
    assert [r.tokens for r in reqs] == want, \
        "post-recovery paged streams diverged"
    assert all(r.phase == "done" for r in reqs)
    m = eng.metrics()
    assert m["recoveries"] == 1 and m["requests_replayed"] >= 1
    st = eng.kv_page_stats()
    assert st["pages_in_use"] == 0 and st["pages_free"] == st["pages_total"]


def test_paged_fleet_mid_stream_kill_zero_lost_bit_identical():
    """The failover invariant on paged pools: kill a replica mid-decode
    — durable records fail over, survivors re-prefill into their own
    arenas, zero requests lost, streams byte-equal to the fault-free
    dense single-engine run."""
    from deepspeed_tpu.inference import ServingFleet
    cfg, model, params = make_model()
    prompts = prompts_of(cfg, [5, 9, 6, 8, 7, 4], seed=12)

    def kwz(i):
        kw = {"max_new_tokens": 5 + (i % 3)}
        if i % 2:
            kw.update(temperature=0.7, seed=90 + i)
        return kw

    ref = engine_of(model, params, max_slots=3, prefill_chunk=8)
    want = [ref.submit(p, **kwz(i)) for i, p in enumerate(prompts)]
    ref.run()
    want = [r.tokens for r in want]

    fleet = ServingFleet(
        model, params, n_replicas=2, start=False, seed=0,
        window_seconds=0.05,
        config={"max_slots": 3, "max_len": 64, "chunk_size": 4,
                "prefill_chunk": 8, "max_queue": 32, "paged_kv": True,
                "kv_page_len": 8, "fault_injection": True,
                "recovery_max_retries": 0})
    try:
        frs = [fleet.submit(p, **kwz(i)) for i, p in enumerate(prompts)]
        victims = [fr for fr in frs if fr.replica_id == 0]
        assert victims and len(victims) < len(frs)
        for _ in range(200):
            if any(fr.tokens and not fr.done for fr in victims):
                break
            fleet.step()
        else:
            pytest.fail("replica 0 never reached mid-stream")
        fleet.inject_faults(
            FaultPlan(faults=(Fault("raise", step=0),)), replica=0)
        assert fleet.wait_idle(timeout_s=120.0)
        assert all(fr.phase == "done" for fr in frs)      # zero lost
        assert [fr.tokens for fr in frs] == want          # bit-identical
        assert fleet.failovers >= 1
        # The survivor's arena drained clean.
        st = fleet.replicas[1].engine.kv_page_stats()
        assert st["pages_in_use"] == 0
    finally:
        fleet.close()


def test_sustained_report_paged_section():
    """Schema v7: the runner polls kv_page_stats and the report carries
    the additive paged section (dense runs show paged: false)."""
    from deepspeed_tpu.loadgen import (
        SLO,
        SustainedRunner,
        build_report,
    )
    cfg, model, params = make_model()
    # Outputs long enough to span step boundaries: the runner samples
    # page occupancy AFTER each step, and a request whose whole decode
    # fits one fused step frees its pages before the sample.
    spec = WorkloadSpec(n_requests=4, rate=200.0, prompt_min=4,
                        prompt_max=8, prompt_mean=6, output_min=10,
                        output_max=12, output_mean=11,
                        vocab_size=cfg.vocab_size, seed=3)
    eng = paged_engine_of(model, params, kv_page_len=8)
    result = SustainedRunner(eng, spec, window_seconds=0.05).run()
    rep = build_report(spec, result, SLO())
    assert rep["schema_version"] == 7
    sec = rep["paged"]
    assert sec["paged"] is True and sec["page_len"] == 8
    assert sec["pages_total"] > 0 and sec["pages_peak"] > 0
    assert 0.0 < sec["page_utilization"] <= 1.0
