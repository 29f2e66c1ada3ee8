"""Latent attention (``models/decoder.py`` ``mla``: DeepSeek-V3's block, a
leading dense layer, the group-limited sigmoid router over a chip's share of
the experts) against its plain reference (``benchmark/reference/deepseek_v3.py``,
which materialises every head's keys and values) at a tiny size in float32:
hidden 64, 3 layers of which the first dense, 4 heads of nope 16 / rope 8 /
value 16, latent rank 32 (a token stores 32 + 8 values, padded to one
128-lane tile), 16 experts in 4 groups of which 2 stay, top-3, 8 held.

Tolerances: float32 end to end. The absorbed form reassociates two matmuls
a head (``(q W_uk) . c`` for ``q . (W_uk c)``), the cache-free pass, the
chunked lane and the one-token scan differ by the order of their sums:
2e-4 on logits that spread 1.2 (measured 9e-6); paths of the PROGRAM that
must agree with each other do so to 2e-5, and what must resume (capture and
restore, preemption) does token for token.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import deepseek_v3 as reference
from deepspeed_tpu.inference import InferenceEngine, kv_pool
from deepspeed_tpu.inference.adapters import DecoderAdapter
from deepspeed_tpu.inference.kv_hierarchy import offload
from deepspeed_tpu.models import decoder
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from deepspeed_tpu.ops.transformer.kernels import decode_attention as da
from tests.unit.compiled import compiled, served_alone

builder = harness.load_by_name("model_builders", "deepseek_v3")

CFG = DecoderConfig(
    vocab_size=256, n_layer=3, n_head=4, head_dim=24, hidden_size=64,
    n_positions=4096, n_experts=16, experts_per_token=3, expert_width=32,
    rms_norm_eps=1e-6, qk_norm=False, norm_topk_prob=True,
    dtype=jnp.float32, initializer_range=0.15, shared_width=32,
    experts_held=(0, 8), kv_lora_rank=32, q_lora_rank=24, qk_nope_dim=16,
    qk_rope_dim=8, v_head_dim=16, rope_yarn=(40.0, 64, 32.0, 1.0, 1.0, 1.0),
    dense_layers=1, dense_width=96, router_scoring="sigmoid", n_group=4,
    topk_group=2, routed_scaling=2.5)
TOL = dict(rtol=2e-4, atol=2e-4)
SAME = dict(rtol=2e-5, atol=2e-5)
PAGE = 8


@pytest.fixture(scope="module")
def model():
    m = DecoderLM(CFG)
    key = jax.random.PRNGKey(0)
    # the selection bias drawn, not zero: choosing with it and weighting
    # without it then differ
    return m, builder.rescaled(jax.jit(m.init)(key)["params"], key, 1.0, 0.1)


@pytest.fixture(scope="module")
def adapter(model):
    return DecoderAdapter.from_model(model[0], use_flash_decode=False)


def tokens(n, seed=0, rows=1):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, size=(rows, n)).astype(np.int32)


def engine(model, **kw):
    kw = dict(dict(max_slots=3, max_len=64, chunk_size=4, prefill_chunk=8,
                   use_flash_decode=False, paged_kv=True, kv_page_len=PAGE),
              **kw)
    return InferenceEngine(model[0], model[1], config=kw)


def alone(model, prompt, n, **kw):
    return served_alone(engine, model, prompt, n, **kw)


def paged_cache(adapter, rows, page=PAGE, max_len=64):
    pool = kv_pool.init_pool(adapter.cache_spec(), rows, max_len, slack=page,
                             page_len=page)
    n_lp = pool["block_tbl"].shape[1]
    tbl = 1 + jnp.arange(rows * n_lp, dtype=jnp.int32).reshape(rows, n_lp)
    assert "v" not in pool
    return dict(k=pool["k"], block_tbl=tbl,
                pos=jnp.zeros((rows,), jnp.int32), **adapter.aux_state())


# ------------------------------------------------- against the reference


def test_the_absorbed_form_is_the_expanded_one(model):
    """``DecoderLM.apply`` scores queries carried into the latent against
    the cached latent; the reference materialises k_nope and v a head."""
    ids = tokens(40, rows=2)
    want = builder.reference_logits(model[1], ids, CFG)
    got = np.asarray(jax.jit(model[0].apply)({"params": model[1]},
                                             jnp.asarray(ids)))
    assert want.std() > 1.0          # logits of order 1, so TOL means it
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("chunks", [(40,), (8, 8, 8, 8, 8), (5, 16, 3, 16)],
                         ids=["one_chunk", "even_chunks", "unequal_chunks"])
def test_prefill_then_decode_through_the_paged_latent_pool_is_the_reference(
        model, adapter, chunks):
    """The prompt's 40 tokens through the lane in ``chunks``, then 16 tokens
    a step at a time, through a PAGED pool of one plane: every position's
    logits are the reference's full forward pass."""
    ids = tokens(56, seed=3, rows=2)
    want = builder.reference_logits(model[1], ids, CFG)
    cache, out, lo = paged_cache(adapter, 2), [], 0
    for n in chunks:
        logits, cache = compiled(adapter, "prefill_append")(
            model[1], ids[:, lo:lo + n], cache)
        out.append(logits)
        lo += n
    for t in range(lo, ids.shape[1]):
        logits, cache = compiled(adapter, "decode_step")(
            model[1], ids[:, t], cache)
        out.append(logits[:, None])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(out, axis=1)),
                               want, **TOL)
    assert "v" not in cache and cache["k"].shape[2:] == (1, PAGE, 128)


def test_yarn_frequencies_are_the_hand_computed_ones():
    """DeepSeek-V3's: dim 64, theta 10,000, factor 40, original 4,096, beta
    32 and 1. d(32) = 64 ln(4096 / 64 pi) / (2 ln 10000) = 10.47 and d(1) =
    64 ln(4096 / 2 pi) / (2 ln 10000) = 22.51, so low 10, high 23: pairs
    0..10 keep theta ** (-i / 32), pairs 23..31 turn 40 times slower, pair
    16 sits at ramp 6 / 13."""
    inv = decoder.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    f = lambda i: 10000.0 ** (-i / 32.0)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:11], [f(i) for i in range(11)],
                               rtol=1e-6)
    np.testing.assert_allclose(inv[23:], [f(i) / 40 for i in range(23, 32)],
                               rtol=1e-6)
    ramp = 6.0 / 13.0
    np.testing.assert_allclose(
        inv[16], f(16) * (1 - ramp) + f(16) / 40 * ramp, rtol=1e-6)
    np.testing.assert_allclose(inv[16], 0.0055003, rtol=1e-4)
    np.testing.assert_allclose(
        inv, reference.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0),
        rtol=1e-6)
    # the softmax scale: 192 ** -0.5 x (0.1 ln 40 + 1) ** 2
    cfg = CFG._replace(qk_nope_dim=128, qk_rope_dim=64,
                       rope_yarn=(40.0, 4096, 32.0, 1.0, 1.0, 1.0))
    assert abs(cfg.softmax_scale - 0.13523) < 1e-5
    assert abs((0.1 * math.log(40) + 1) - 1.3689) < 1e-4


def test_the_reference_imports_nothing_of_the_program():
    import inspect

    assert "deepspeed_tpu" not in inspect.getsource(reference).replace(
        "``deepspeed_tpu``", "")


# ------------------------------------------------------------ the kernels


def latent_case(rows, heads, s, n_lp, frontiers, page=128, w=256, rank=128,
                layers=2, seed=0):
    rs = np.random.RandomState(seed)
    arena = jnp.asarray(rs.randn(layers, rows * n_lp + 1, 1, page, w),
                        jnp.float32)
    q = jnp.asarray(rs.randn(rows, heads, s, w) * 0.2, jnp.float32)
    tbl = 1 + jnp.arange(rows * n_lp, dtype=jnp.int32).reshape(rows, n_lp)
    return q, arena, tbl, jnp.asarray(frontiers, jnp.int32), rank


def force_pages(monkeypatch, k, page_bytes=128 * 256 * 4):
    """K pages a unit for ``latent_case``'s float32 pages, through the
    rule's own constant (so the rule's other clauses still hold: never more
    pages than a row's table has, one for a one-page table)."""
    monkeypatch.setattr(da, "_UNIT_BYTES", k * page_bytes + 1)


@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("n_lp, frontiers, s", [
    (1, (0, 90, 127 - 4), 5), (3, (200, 17, 383), 1), (3, (130, 250, 5), 4),
    (7, (128 * 5 + 3, 128 * 7 - 1, 300), 1),
    (7, (126, 128 * 4 + 125, 128 * 6 + 60), 4)],
    ids=["one_page", "three_pages_decode", "three_pages_chunk",
         "seven_pages_decode", "seven_pages_chunk"])
def test_latent_decode_interpreted_is_the_jnp_paged_reference(
        n_lp, frontiers, s, k, monkeypatch):
    """The kernel body (Pallas interpreter) against gather + softmax in
    ``jax.numpy``, at ``k`` pages a unit: one page a row (the direct
    softmax) and several (the online one), live pages that are no multiple
    of ``k`` (6, 7 and 3 of seven), frontiers that straddle a page, inside a
    unit's second page (at 4: 128 x 5 + 3) and its last (128 x 7 - 1), and a
    chunk across two pages of one unit and of two, S = 1 and a chunk, and a
    layer picked out of the whole arena by the index map."""
    force_pages(monkeypatch, k)
    q, arena, tbl, pos, rank = latent_case(3, 8, s, n_lp, frontiers)
    assert da.unit_pages([arena], 8, None, n_lp, q.dtype, s_len=s,
                         latent=rank) == max(
                             j for j in (1, 2, 4) if j <= min(k, n_lp))
    got = da.latent_decode(q, arena, tbl, pos, rank, 0.3, layer=1)
    want = da.latent_decode_reference(q, arena[1], tbl, pos, rank, 0.3)
    assert got.shape == (3, 8, s, rank)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **SAME)
    one = da.latent_decode(q, arena[1], tbl, pos, rank, 0.3)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(got))


@pytest.mark.parametrize("k", [1, 2, 4])
def test_latent_decode_skips_dead_pages_and_freed_rows(k, monkeypatch):
    """Pages past a row's frontier are attended by no unit, the dead pages
    of a LIVE unit among them (row 0's third and fourth at 4 pages a unit,
    row 2's last three of its second unit): garbage there, NaN even, changes
    nothing. A freed row (its table at the trash page), here between live
    rows, is not attended: zeros."""
    force_pages(monkeypatch, k)
    q, arena, tbl, pos, rank = latent_case(4, 8, 1, 7,
                                           (140, 500, 128 * 4 + 9, 20))
    tbl = tbl.at[1].set(0)
    keep = np.asarray([0, 2, 3])
    want = da.latent_decode_reference(q[keep], arena[1], tbl[keep], pos[keep],
                                      rank, 0.3)
    dead = arena.at[1, tbl[0, 2:]].set(jnp.nan).at[1, tbl[2, 5:]].set(
        jnp.nan).at[1, tbl[3, 1:]].set(jnp.nan)
    got = da.latent_decode(q, dead, tbl, pos, rank, 0.3, layer=1)
    np.testing.assert_allclose(np.asarray(got[keep]), np.asarray(want),
                               **SAME)
    assert float(jnp.abs(got[1]).max()) == 0.0


@pytest.mark.parametrize("k", [1, 2, 4])
def test_the_work_list_holds_a_rows_live_pages_in_runs_of_k(k):
    """``_paged_units``: ``ceil(live / k)`` units a row, in order, none for
    a freed row; unit ``u`` of a row names its pages ``k u .. k u + k - 1``
    and, past the last live one, that one again."""
    n_lp = 7
    tbl = np.asarray(1 + np.arange(5 * n_lp).reshape(5, n_lp), np.int32)
    tbl[2] = 0                                    # freed, between live rows
    pos = np.asarray([0, 128 * 3 - 1, 50, 128 * 7 - 1, 128 * 4], np.int32)
    live = [1, 3, 0, 7, 5]
    rows, us, pages, got_live, n = (np.asarray(x) for x in da._paged_units(
        jnp.asarray(tbl), jnp.asarray(pos), 1, 128, k))
    assert got_live.tolist() == live
    want = [(b, u) for b in range(5) for u in range(-(-live[b] // k))]
    assert int(n) == len(want) == sum(-(-x // k) for x in live)
    assert rows.shape == us.shape == (5 * -(-n_lp // k),)
    assert list(zip(rows[:n].tolist(), us[:n].tolist())) == want
    assert (rows[n:] == want[-1][0]).all() and (us[n:] == want[-1][1]).all()
    assert pages.shape == (rows.size * k,)
    for t, (b, u) in enumerate(want):
        assert pages[t * k:(t + 1) * k].tolist() == [
            tbl[b, min(k * u + i, live[b] - 1)] for i in range(k)]
    # no live row at all: one unit, the last row's, which the body skips
    rows, us, pages, got_live, n = da._paged_units(
        jnp.zeros((3, n_lp), jnp.int32), jnp.asarray(pos[:3]), 1, 128, k)
    assert int(n) == 1 and int(rows[0]) == 2 and not np.asarray(
        got_live).any() and not np.asarray(pages).any()


def _arenas(shape, n=2, dtype=jnp.bfloat16):
    return [jax.ShapeDtypeStruct(shape, dtype)] * n


@pytest.mark.parametrize("name, arenas, heads, head_dim, n_lp, s, latent, k", [
    # the cell's decode scan: 128 rows of 128 heads, 4 x 164 KB a unit
    ("dsv3_decode", _arenas((6, 3073, 1, 128, 640), 1), 128, 192, 24, 1, 512,
     4),
    ("dsv3_verify", _arenas((6, 3073, 1, 128, 640), 1), 128, 192, 24, 5, 512,
     4),
    # its lane: 8 heads a group, 1,024 rows a unit, work enough a page
    ("dsv3_lane", _arenas((6, 3073, 1, 128, 640), 1), 128, 192, 24, 128, 512,
     1),
    # every ``paged_decode`` shape of the benchmark: 512 KB or 1 MB a page
    ("gpt2_decode", _arenas((24, 145, 8, 128, 128)), 16, 64, 9, 1, 0, 1),
    ("gpt2_lane", _arenas((24, 145, 8, 128, 128)), 16, 64, 9, 128, 0, 1),
    ("olmoe_decode", _arenas((8, 545, 16, 128, 128)), 16, 128, 17, 1, 0, 1),
    ("olmoe_lane", _arenas((8, 545, 16, 128, 128)), 16, 128, 17, 128, 0, 1),
    ("granite_decode", _arenas((1, 1217, 8, 128, 128)), 32, 128, 19, 1, 0, 1),
    ("granite_lane", _arenas((1, 1217, 8, 128, 128)), 32, 128, 19, 128, 0, 1),
], ids=lambda x: x if isinstance(x, str) else None)
def test_pages_a_unit_come_from_the_shapes(name, arenas, heads, head_dim,
                                           n_lp, s, latent, k):
    assert da.unit_pages(arenas, heads, head_dim, n_lp, jnp.bfloat16,
                         s_len=s, latent=latent) == k


def test_a_lane_of_many_heads_goes_in_groups_of_heads():
    """S = 128 rows of 128 heads do not fit VMEM at once: the launcher's
    outer grid axis takes the heads in the largest groups that do."""
    assert da._latent_heads_per_unit(128, 1, 128, 640, 512,
                                     jnp.bfloat16) == 128
    assert da._latent_heads_per_unit(128, 128, 128, 640, 512,
                                     jnp.bfloat16) == 8
    q, arena, tbl, pos, rank = latent_case(1, 4, 8, 2, (100,))
    whole = da.latent_decode(q, arena, tbl, pos, rank, 0.3, layer=0)
    budget, da._PAGED_VMEM_BUDGET = da._PAGED_VMEM_BUDGET, 300 * 1024
    try:
        assert da._latent_heads_per_unit(4, 8, 128, 256, 128,
                                         jnp.float32) < 4
        grouped = da.latent_decode(q, arena, tbl, pos, rank, 0.3, layer=0)
    finally:
        da._PAGED_VMEM_BUDGET = budget
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(whole), **SAME)


def test_kv_append_writes_the_one_plane_in_place():
    rs = np.random.RandomState(1)
    arena = jnp.asarray(rs.randn(2, 7, 1, 128, 256), jnp.float32)
    tbl = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pos = jnp.asarray([126, 300], jnp.int32)
    new = jnp.asarray(rs.randn(2, 1, 5, 256), jnp.float32)
    out, = da.kv_append((arena,), (new,), tbl, pos, layer=1)
    want = np.asarray(arena).copy()
    for b in range(2):
        for r in range(5):
            p = int(pos[b]) + r
            want[1, int(tbl[b, p // 128]), 0, p % 128] = np.asarray(new[b, 0, r])
    np.testing.assert_array_equal(np.asarray(out), want)


def test_the_kernel_path_serves_what_the_gather_path_serves(model):
    """Pages of a kernel block: ``kv_append`` and ``latent_decode`` (the
    lane's call under the name ``prefill_attn``), interpreted, emit the
    tokens the scatter, gather and einsums emit."""
    prompts = [tokens(n, seed=40 + n)[0] for n in (5, 13, 9)]
    served = {}
    for flash in (False, True):
        eng = engine(model, use_flash_decode=flash, kv_page_len=128,
                     max_len=256)
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run()
        assert eng.compile_count == 1
        served[flash] = [r.tokens for r in reqs]
    assert served[True] == served[False]


# -------------------------------------------------------------- the engine


def test_the_engine_serves_it_in_one_program_alone_or_among_neighbours(
        model):
    prompts = [tokens(n, seed=30 + n)[0] for n in (5, 20, 9, 12, 7)]
    eng = engine(model)
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    assert eng.compile_count == 1 and eng.metrics()["adapter"] == "decoder"
    for p, r in zip(prompts, reqs):
        assert r.tokens == alone(model, p, 10)
    # the reference agrees with every served token (teacher forcing)
    seq = np.concatenate([prompts[1], reqs[1].tokens])[None]
    rows = builder.reference_logits(model[1], seq, CFG)[0][
        len(prompts[1]) - 1:-1]
    assert float(np.max(rows.max(axis=1) - rows[
        np.arange(len(reqs[1].tokens)), reqs[1].tokens])) <= 1e-3
    from deepspeed_tpu.telemetry.exporters import prometheus_text

    text = prometheus_text(eng.telemetry)
    gauges = {}
    for line in text.splitlines():
        if line.startswith("ds_tpu_") and "expert=" not in line:
            name, value = line.rsplit(" ", 1)
            gauges[name.split("{")[0][len("ds_tpu_"):]] = float(value)
    assert text.count("moe_expert_load{") == 8
    assert gauges["moe_experts_held"] == 8
    routed_, absent = gauges["moe_tokens_routed"], gauges["moe_tokens_absent"]
    assert 0.2 < absent / (routed_ + absent) < 0.8
    # 3 layers x one head of 128 stored lanes x 4 bytes
    assert gauges["kv_latent_bytes_token"] == 3 * 128 * 4


def test_the_pool_holds_one_plane_and_counts_it(adapter):
    spec = adapter.cache_spec()
    assert (spec.n_layer, spec.n_head, spec.n_embd, spec.latent) == \
        (3, 1, 128, 32)
    paged = kv_pool.init_pool(spec, 3, 64, slack=8, page_len=8)
    assert "v" not in paged and paged["k"].shape == (3, 3 * 9 + 1, 1, 8, 128)
    dense = kv_pool.init_pool(spec, 3, 64, slack=8)
    assert "v" not in dense and dense["k"].shape == (3, 3, 1, 72, 128)
    plain = DecoderAdapter.from_model(DecoderLM(CFG._replace(
        kv_lora_rank=0, head_dim=32)), use_flash_decode=False)
    pair = kv_pool.init_pool(plain.cache_spec(), 3, 64, slack=8, page_len=8)
    assert kv_pool.pool_nbytes(paged) < kv_pool.pool_nbytes(pair)
    view = kv_pool.cache_view(paged)
    assert "v" not in view and view["k"] is paged["k"]
    assert set(kv_pool.fold_cache(paged, view)) == set(paged)


def test_preempt_then_resume_continues_token_for_token(model):
    prompts = [tokens(n, seed=20 + n)[0] for n in (6, 9, 5)]
    eng = engine(model, host_offload=True, swap_slots=2)
    reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
    while not (reqs[0].phase == "decoding" and reqs[0].tokens):
        eng.step()
    assert eng.preempt(reqs[0]) and reqs[0].phase == "swapped"
    record = eng._hier.swap_store.records[reqs[0].rid]
    assert "v" not in record and record["k"].shape[2:] == (1, PAGE, 128)
    for _ in range(6):
        eng.step()
    eng.release_preempted(reqs[0])
    eng.run()
    assert eng.compile_count == 1
    for p, r in zip(prompts, reqs):
        assert r.tokens == alone(model, p, 20, host_offload=True,
                                 swap_slots=2)


def test_capture_and_restore_carry_the_one_plane(model):
    eng = engine(model)
    for n in (6, 9):
        eng.submit(tokens(n, seed=n)[0], max_new_tokens=16)
    eng.step()
    eng.step()
    pool, pager = eng._pool, eng._pager
    pages = pager.row_pages(0)
    rec = offload.capture_slot_paged(pool, 0, pages)
    fresh = pager.alloc_pages(len(pages))
    restored = offload.restore_slot_paged(pool, 2, rec, fresh)
    np.testing.assert_array_equal(
        np.asarray(restored["k"][:, np.asarray(fresh)]),
        np.asarray(pool["k"][:, np.asarray(pages)]))
    assert "v" not in rec and not any(k.startswith("aux_") for k in rec)


def test_speculative_decoding_emits_the_same_tokens(model):
    """The stale-cache rule holds for a latent plane as for keys: a verify
    writes the drafts' latents past the frontier and rollback is not moving
    it."""
    prompt = np.tile(tokens(6, seed=5)[0], 4)      # n-grams that repeat
    plain = alone(model, prompt, 16)
    assert alone(model, prompt, 16, spec_decode=True, spec_k=3) == plain


# ----------------------------------------------------------- the refusals


@pytest.mark.parametrize("key, mechanism", [
    ("int8_kv", "int8 planes"), ("prefix_cache", "prefix cache")])
def test_what_a_latent_pool_cannot_do_yet_is_refused_by_name(model, key,
                                                            mechanism):
    with pytest.raises(ValueError, match=mechanism) as e:
        engine(model, **{key: True})
    assert "latent-attention cache" in str(e.value)
    # the same key serves the block with keys and values a head
    plain = DecoderLM(CFG._replace(kv_lora_rank=0, head_dim=32, n_layer=1,
                                   dense_layers=0, rope_yarn=None))
    InferenceEngine(plain, plain.init(jax.random.PRNGKey(0))["params"],
                    config=dict(max_slots=2, max_len=64, chunk_size=2,
                                use_flash_decode=False, **{key: True}))
