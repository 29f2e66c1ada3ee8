"""The decoder-hybrid-decoder stack (``models/decoder.py``: Mamba-1 layers
without inner norms, WINDOW attention layers on a ring of pages a slot, ONE
full-attention layer whose plane the cross layers after it read, gated memory
units fed by the last Mamba layer's scan output, LayerNorm with a bias)
against the plain reference (``benchmark/reference/phi4flash.py``) at a tiny
size: hidden 64, 8 layers (mamba1, swa, mamba1, swa, mamba1, attention, gmu,
xattn), 4 query heads of 16 over 2 stored (two stored heads a lane tile and
two query heads a stored one, as at the published widths), a window of 8
positions over pages of 4.

The ring holds ``ring_pages(8, 4, 6)`` = 4 pages a slot; the sequences run to
41 positions, past five windows and ten pages, so the ring wraps more than
twice; prompts go through the lane in slices of 6 (neither a page nor the
window divides by it, so slices straddle a window's edge and the ring's
wrap), the last slice with pad columns. The ring's arenas START FULL OF
NOISE: a key no request wrote is masked by its position, never trusted to be
zero. Every bias is drawn away from 0.

Tolerances: float32 end to end, whole logits 2e-4 on a spread of 0.4; bf16
weights and activations 6e-2 (the stack's own rounding at 8 layers).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import phi4flash as reference
from deepspeed_tpu.inference import InferenceEngine, kv_pool
from deepspeed_tpu.inference.adapters import DecoderAdapter
from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.models import decoder, generation
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from deepspeed_tpu.ops.transformer.kernels import decode_attention as da

builder = harness.load_by_name("model_builders", "phi4flash")

WINDOW, PAGE, LANE = 8, 4, 6
KINDS = tuple(builder.KINDS[k] for k in reference.layer_kinds(8))
CFG = DecoderConfig(
    vocab_size=256, n_layer=8, n_head=4, head_dim=16, hidden_size=64,
    n_positions=4096, n_experts=0, experts_per_token=0, expert_width=0,
    rms_norm_eps=1e-5, qk_norm=False, tie_word_embeddings=True,
    dtype=jnp.float32, initializer_range=0.05, n_kv_head=2, rope=False,
    layer_types=KINDS, dense_layers=8, dense_width=96, mamba_state=16,
    mamba_conv=4, mamba_expand=2, mamba_dt_rank=4, sliding_window=WINDOW,
    layer_norm=True, attn_bias=True, mamba_inner_norms=False)
TOL = {"float32": dict(rtol=2e-4, atol=2e-4),
       "bfloat16": dict(rtol=6e-2, atol=6e-2)}
T = 41


def _cfg(dtype):
    return CFG._replace(dtype=jnp.dtype(dtype))


def _params(cfg):
    """Seeded weights with every bias away from 0 (they are zeros as drawn)
    and ``A`` a channel and a state index."""
    params = jax.jit(DecoderLM(cfg).init)(jax.random.PRNGKey(0))["params"]
    key = jax.random.PRNGKey(1)

    def drawn(tree, names):
        return dict(tree, **{
            name: (0.1 * jax.random.normal(jax.random.fold_in(key, n),
                                           tree[name].shape)
                   ).astype(tree[name].dtype)
            for n, name in enumerate(names)})

    params["layers"] = drawn(params["layers"], ["attn_norm_b", "ffn_norm_b"])
    for tree, names in (("attn", ["bqkv", "bo"]), ("swa", ["bqkv", "bo"]),
                        ("xattn", ["bq", "bo"])):
        params[tree] = drawn(params[tree], names)
    params["final_norm_b"] = drawn(params, ["final_norm_b"])["final_norm_b"]
    params["mamba1"] = dict(params["mamba1"], A_log=jnp.log(
        jax.random.uniform(key, params["mamba1"]["A_log"].shape, jnp.float32,
                           0.5, 16.0)))
    return params


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def model(request):
    cfg = _cfg(request.param)
    return DecoderLM(cfg), _params(cfg), request.param


@pytest.fixture(scope="module")
def model32():
    return DecoderLM(CFG), _params(CFG), "float32"


def tokens(n, seed=0, rows=1):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, size=(rows, n)).astype(np.int32)


def reference_logits(model, ids):
    """The reference on the weights AS FLOAT32 VALUES of what the model
    holds (bf16 weights are exact in float32)."""
    return builder.reference_logits(model[1], ids, model[0].config)


def engine(model, **kw):
    kw = dict(dict(max_slots=2, max_len=64, chunk_size=4, prefill_chunk=LANE,
                   use_flash_decode=False, paged_kv=True, kv_page_len=PAGE),
              **kw)
    return InferenceEngine(model[0], model[1], config=kw)


class Rows(object):
    """A paged pool of ``slots`` rows served as the engine's two programs
    serve it: a lane slice into one slot (``slot_cache_view`` /
    ``prefill_append`` / ``write_slot_cache``) and a decode step of every
    active row (``cache_view`` / ``decode_step`` / ``fold_cache``), the
    logits kept. Every page of both groups starts as NOISE."""

    def __init__(self, model, slots=2, max_len=48, flash=False, page=PAGE,
                 lane=LANE):
        self.adapter = DecoderAdapter.from_model(
            model[0], use_flash_decode=flash)
        self.params, self.lane_len = model[1], lane
        spec = self.adapter.cache_spec()._replace(use_flash_decode=flash)
        pool = kv_pool.init_pool(spec, slots, max_len, slack=lane,
                                 page_len=page)
        n_lp = pool["block_tbl"].shape[1]
        noise = jax.random.PRNGKey(7)
        for n, name in enumerate(("k", "v", "wk", "wv")):
            pool[name] = jax.random.normal(jax.random.fold_in(noise, n),
                                           pool[name].shape, pool[name].dtype)
        pool["block_tbl"] = 1 + jnp.arange(slots * n_lp, dtype=jnp.int32
                                           ).reshape(slots, n_lp)
        self.pool = pool

        def lane_fn(params, pool, slot, ids, pos, n_valid):
            cache = kv_pool.slot_cache_view(pool, slot, pos[None])
            logits, cache = self.adapter.prefill_append(
                params, ids, cache, n_valid=n_valid[None])
            pool = kv_pool.write_slot_cache(pool, slot, cache)
            pool["pos"] = pool["pos"].at[slot].set(pos + n_valid)
            pool["active"] = pool["active"].at[slot].set(True)
            return logits[0], pool

        def step_fn(params, pool, toks):
            logits, cache = self.adapter.decode_step(
                params, toks, kv_pool.cache_view(pool))
            pool = dict(kv_pool.fold_cache(pool, cache), pos=jnp.where(
                pool["active"], cache["pos"], pool["pos"]))
            return logits, pool

        self._lane, self._step = jax.jit(lane_fn), jax.jit(step_fn)

    def prompt(self, slot, ids):
        """``ids`` [n] into ``slot`` from its start, in slices of the lane's
        width, the last padded with junk: logits [n, V]."""
        self.pool["pos"] = self.pool["pos"].at[slot].set(0)
        out = []
        for lo in range(0, len(ids), self.lane_len):
            piece = ids[lo:lo + self.lane_len]
            padded = np.full((1, self.lane_len), 7, np.int32)
            padded[0, :len(piece)] = piece
            logits, self.pool = self._lane(
                self.params, self.pool, jnp.int32(slot), jnp.asarray(padded),
                jnp.int32(lo), jnp.int32(len(piece)))
            out.append(logits[:len(piece)])
        return np.concatenate([np.asarray(x) for x in out])

    def step(self, toks):
        logits, self.pool = self._step(self.params, self.pool,
                                       jnp.asarray(toks, jnp.int32))
        return np.asarray(logits)


# --------------------------------------------------------------- the masks


def test_visible_from_without_a_window_adds_nothing():
    """No window, no lower bound: the mask is the comparison it always was
    (the other families' programs), traced to the same operations."""
    assert da.visible_from(jnp.arange(5), None) is None
    q, k = jnp.arange(6)[:, None], jnp.arange(9)[None, :]

    def was(k, q):
        return k <= da.visible_upto(q, 2)

    assert str(jax.make_jaxpr(lambda k, q: da.visible(k, q, 2, None))(k, q)) \
        == str(jax.make_jaxpr(was)(k, q))
    np.testing.assert_array_equal(da.visible_from(jnp.arange(5), 3),
                                  np.arange(5) - 2)
    seen = np.asarray(da.visible(k, q, 1, 3))
    assert all(seen[p, j] == (p - 3 < j <= p)
               for p in range(6) for j in range(9))


def test_ring_pages_hold_a_calls_span_after_its_own_write():
    assert da.ring_pages(512, 128) == 5 and da.ring_pages(512, 128, 128) == 6
    for window, page, s_len in ((8, 4, 1), (8, 4, 6), (5, 4, 3),
                                (512, 128, 16)):
        n_ring = da.ring_pages(window, page, s_len)
        for pos in range(0, 6 * n_ring * page):
            first = max(pos - window + 1, 0) // page
            last = (pos + s_len - 1) // page
            assert last - first + 1 <= n_ring, (window, page, s_len, pos)
        assert any((pos + s_len - 1) // page
                   - max(pos - window + 1, 0) // page + 1 == n_ring
                   for pos in range(6 * n_ring * page))


# ------------------------------------------------- the ring's two kernels


def _history(b, hkv, t, d, seed):
    k, v = (jax.random.normal(jax.random.PRNGKey(seed + n), (b, hkv, t, d))
            for n in range(2))
    return k, v


def _windowed(q, k, v, pos, window):
    """Window attention of ``q`` [B, H, S, D] at ``pos`` over the whole
    history ``k, v`` [B, Hkv, T, D], plainly."""
    rep = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, rep, axis=1) for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = pos[:, None] + jnp.arange(q.shape[2])[None]
    j = jnp.arange(k.shape[2])
    seen = (j[None, None] <= p[:, :, None]) \
        & (j[None, None] > p[:, :, None] - window)
    s = jnp.where(seen[:, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("s_len", [1, 32])
def test_window_decode_on_a_wrapped_ring_is_window_attention(s_len):
    """``kv_append_ring`` then ``window_decode`` (interpreted, pages of 128,
    a window of 200: a ring of 3 or 4 pages) for rows whose frontiers lie
    before the first wrap, on a page's edge, and several wraps in, on a ring
    whose other places hold the row's OLDER pages and noise; one freed row."""
    b, h, hkv, d, page, window = 4, 4, 2, 64, 128, 200
    n_ring = da.ring_pages(window, page, s_len)
    pos = np.asarray([70, 383, 1000, 1290], np.int32)
    t = int(pos.max()) + s_len
    k, v = _history(b, hkv, t, d, 3)
    q = jax.random.normal(jax.random.PRNGKey(9), (b, h, s_len, d))
    g = da.lane_pack(d, hkv)
    arenas = tuple(jax.random.normal(
        jax.random.PRNGKey(20 + n), (2, 1 + b * n_ring, hkv // g, page, g * d))
        for n in range(2))
    live = jnp.asarray([True, True, True, False])
    ring = da.ring_table(jnp.arange(b), n_ring, live)
    # the history as a request leaves it: every position appended in order,
    # a lane slice of 64 at a time, then the call's own keys
    append = jax.jit(da.kv_append_ring, static_argnames=("layer",))
    for lo in range(0, int(pos.max()), 64):
        upto = np.minimum(np.maximum(pos - lo, 0), 64)
        rows = [i for i in range(b) if upto[i] == 64]
        if rows:
            idx = jnp.asarray(rows)
            new = tuple(a[idx, :, lo:lo + 64] for a in (k, v))
            arenas = append(arenas, new, ring[idx], jnp.full(
                (len(rows),), lo, jnp.int32), layer=1)
    for i in range(b):          # the tail that is no whole slice
        lo = int(pos[i]) // 64 * 64
        if pos[i] > lo:
            new = tuple(jnp.pad(a[i:i + 1, :, lo:pos[i]], (
                (0, 0), (0, 0), (0, 64 - (int(pos[i]) - lo)), (0, 0)))
                for a in (k, v))
            arenas = append(arenas, new, ring[i:i + 1],
                            jnp.asarray([lo], jnp.int32), layer=1)
    new = tuple(jnp.stack([a[i, :, pos[i]:pos[i] + s_len] for i in range(b)])
                for a in (k, v))
    arenas = append(arenas, new, ring, jnp.asarray(pos), layer=1)
    got = jax.jit(da.window_decode, static_argnames=("window", "layer"))(
        q, *arenas, ring, jnp.asarray(pos), window=window, layer=1)
    want = _windowed(q, k, v, jnp.asarray(pos), window)
    np.testing.assert_allclose(np.asarray(got[:3]), np.asarray(want[:3]),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[3]).any()          # a freed row is not visited
    # the gather path reads the same ring
    plain = da.window_decode(q[:3], *(a[1] for a in arenas), ring[:3],
                             jnp.asarray(pos[:3]), window)
    assert da.decode_supported(page)
    ref = da.decode_attention_paged_reference(
        q[:3], *(a[1] for a in arenas),
        *da.ring_view(ring[:3], jnp.asarray(pos[:3]), window, page),
        window=window)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_an_append_that_wraps_lands_its_tail_on_the_rings_first_page():
    """A lane slice that straddles the ring's last page: bit for bit the
    scatter through ``place = position // page % n_ring``."""
    b, hkv, d, page, n_ring, s_len = 2, 2, 64, 128, 3, 100
    g = da.lane_pack(d, hkv)
    arena = jax.random.normal(jax.random.PRNGKey(0),
                              (1, 1 + b * n_ring, hkv // g, page, g * d))
    ring = da.ring_table(jnp.arange(b), n_ring, jnp.asarray([True, True]))
    pos = jnp.asarray([n_ring * page - 30, 5 * n_ring * page + 2 * page + 90])
    new = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, s_len, d))
    got, = da.kv_append_ring((arena,), (new,), ring, pos, layer=0)
    at = pos[:, None] + jnp.arange(s_len)[None]
    pages = jnp.take_along_axis(ring, at // page % n_ring, axis=1)
    want = arena.at[0, pages, :, at % page, :].set(
        da.pack_heads(new, g).transpose(0, 2, 1, 3))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------- the stack's logits


def test_whole_sequence_logits_are_the_references(model):
    ids = tokens(T, seed=1, rows=2)
    want = reference_logits(model, ids)
    got = jax.jit(model[0].apply)({"params": model[1]}, jnp.asarray(ids))
    assert 0.2 < want.std(axis=-1).mean() < 1.0
    np.testing.assert_allclose(np.asarray(got), want, **TOL[model[2]])


def test_prefill_then_decode_through_the_ring_is_the_full_forward(model):
    """Two rows of one paged pool at different frontiers: each prompt through
    the lane in slices of 6 (a pad column in the last), then tokens one at a
    time, to 41 positions: past five windows, the ring of 4 pages wrapped
    more than twice; row 1's slot served row 0's sequence first, so its ring
    and its state hold ANOTHER request's. Logits against the reference's full
    forward, teacher-forced."""
    ids = tokens(T, seed=2, rows=2)
    want = reference_logits(model, ids)
    rows = Rows(model)
    assert kv_pool.window_pages_slot(rows.pool) == da.ring_pages(
        WINDOW, PAGE, LANE) == 4
    rows.prompt(1, ids[0, :29])                  # what slot 1 held before
    cut = (17, 10)                               # prompt lengths
    got = [[rows.prompt(b, ids[b, :cut[b]])] for b in range(2)]
    at = list(cut)
    while min(at) < T:
        live = [at[b] < T for b in range(2)]
        rows.pool["active"] = jnp.asarray(live)
        logits = rows.step([ids[b, min(at[b], T - 1)] for b in range(2)])
        for b in range(2):
            if live[b]:
                got[b].append(logits[b][None])
                at[b] += 1
    for b in range(2):
        np.testing.assert_allclose(np.concatenate(got[b]), want[b],
                                   **TOL[model[2]])


def test_the_kernels_serve_what_the_gather_serves(model32):
    """``kv_append`` (through the ring), ``window_prefill`` /
    ``window_decode`` and the shared plane's ``prefill_attn`` /
    ``paged_decode`` (interpreted, pages of 128, a window of 200, ``g = 2`` x
    ``rep = 2``), a prompt of three lane slices and decode steps across a
    page's edge, beside the gather and the einsums on the same pool."""
    cfg = CFG._replace(sliding_window=200)
    model = (DecoderLM(cfg), model32[1], "float32")
    ids = tokens(150, seed=5)
    out = {}
    for flash in (False, True):
        rows = Rows(model, slots=2, max_len=256, flash=flash, page=128,
                    lane=64)
        got = [rows.prompt(1, ids[0, :120])]
        rows.pool["active"] = jnp.asarray([False, True])
        for i in range(120, 135):
            got.append(rows.step([0, ids[0, i]])[1][None])
        out[flash] = np.concatenate(got)
    np.testing.assert_allclose(out[True], out[False], rtol=2e-4, atol=2e-4)
    want = reference_logits(model, ids[:, :135])[0]
    np.testing.assert_allclose(out[True], want, rtol=2e-4, atol=2e-4)


def test_a_row_admitted_into_a_used_slot_gets_the_stream_it_gets_alone(
        model32):
    """Through the engine: one slot, a long request, then a short one into
    the ring and the state the first left (nothing is reset from the host)."""
    first, second = tokens(30, seed=11)[0], tokens(9, seed=12)[0]
    eng = engine(model32, max_slots=1)
    reqs = [eng.submit(first, max_new_tokens=20),
            eng.submit(second, max_new_tokens=24)]
    eng.run()
    assert eng.compile_count == 1
    alone = engine(model32, max_slots=1)
    req = alone.submit(second, max_new_tokens=24)
    alone.run()
    assert reqs[1].tokens == req.tokens
    # and they are the reference's choices, teacher-forced
    seq = np.concatenate([second, req.tokens])[None]
    rows = reference_logits(model32, seq)[0][len(second) - 1:-1]
    assert float(np.max(rows.max(axis=1) - rows[
        np.arange(len(req.tokens)), req.tokens])) <= 1e-3


# ------------------------------------------------------ one plane, readers


def test_a_cross_layer_attends_the_full_layers_keys_and_writes_nothing(
        model32):
    """Layer 5 (``attention``) appends, layer 7 (``xattn``) reads the same
    plane through the paged pool with its own queries: what full causal
    attention over layer 5's keys and values, recomputed plainly, gives."""
    cfg, params = CFG, model32[1]
    t, c = 23, CFG.hidden_size
    h_full, h_cross = (jax.random.normal(jax.random.PRNGKey(n), (1, t, c))
                       for n in (3, 4))
    full = {k: v[0] for k, v in params["attn"].items()}
    cross = {k: v[0] for k, v in params["xattn"].items()}
    assert cfg.kv_plane(5) == cfg.kv_plane(7) == 0 and cfg.kv_layers == (5,)
    g = da.lane_pack(cfg.head_dim, cfg.n_kv)
    n_lp = -(-t // PAGE)
    cache = {"k": jnp.zeros((1, n_lp + 1, cfg.n_kv // g, PAGE,
                             g * cfg.head_dim)),
             "pos": jnp.zeros((1,), jnp.int32),
             "block_tbl": 1 + jnp.arange(n_lp, dtype=jnp.int32)[None]}
    cache["v"] = cache["k"]
    served = decoder.served_config(cfg, use_flash_decode=False)
    attend = generation.CacheAttention(served, cache, t)
    _, planes = decoder.attention_mix(full, served, h_full, 0, None, attend,
                                      attend.planes)
    got, after = decoder.attention_mix(cross, served, h_cross, 0, None,
                                       attend, planes, kind="xattn")
    assert all(a is b for a, b in zip(after, planes))     # nothing appended
    q_w, kv_w = cfg.n_embd, cfg.n_kv * cfg.head_dim
    _, k, v = jnp.split(h_full[0] @ full["wqkv"] + full["bqkv"],
                        [q_w, q_w + kv_w], axis=-1)
    q = h_cross[0] @ cross["wq"] + cross["bq"]
    want = reference._attend(q, k, v, reference.masks(t, WINDOW)[0],
                             cfg.n_head, cfg.n_kv) @ cross["wo"] + cross["bo"]
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_the_mixed_step_names_each_kind_and_each_groups_kernel(model32):
    """The regions take the kinds' words, and the window group's launches
    carry names that do not begin with ``paged_decode`` (readers of that name
    multiply its calls by a FULL context)."""
    from tests.unit.test_trace_names import _lower_mixed, _op_names, _regions

    cfg = CFG._replace(sliding_window=200)
    eng = engine((DecoderLM(cfg), model32[1]), max_len=256, chunk_size=2,
                 prefill_chunk=16, kv_page_len=128, use_flash_decode=True)
    regions, kernels = _regions(_op_names(_lower_mixed(eng),
                                          "jit_mixed_step"))
    for lane in ("prefill_lane", "decode_scan"):
        assert {lane + "/" + word for word in (
            "swa", "attn", "xattn", "gmu", "mamba1", "mlp", "kv_write",
            "lm_head")} <= regions, sorted(regions)
    assert kernels == {"prefill_attn", "paged_decode", "window_decode",
                       "window_prefill"}
    # (``decode_scan/kv_view`` holds the ring tables' few integers, made
    # from the slot indices in ``cache_view``; no arena's view is formed)


# ------------------------------------------------------ the pool's account


def test_a_window_layers_pool_memory_does_not_depend_on_max_len(model32):
    adapter = DecoderAdapter.from_model(model32[0], use_flash_decode=False)
    spec = adapter.cache_spec()
    assert (spec.n_layer, spec.window_layers, spec.window) == (1, 2, WINDOW)
    short, long_ = (kv_pool.init_pool(spec, 3, n, slack=LANE, page_len=PAGE)
                    for n in (64, 4096))
    assert short["wk"].shape == long_["wk"].shape == (
        2, 1 + 3 * 4, 1, PAGE, 2 * CFG.head_dim)
    assert long_["k"].shape[1] > 10 * short["k"].shape[1]
    # fixed memory a slot: the allocator and the table know the full group
    assert long_["block_tbl"].shape == (3, (4096 + LANE + PAGE - 1) // PAGE)


def test_the_gauges_read_the_ring_the_readers_and_the_memory_units(model32):
    eng = engine(model32, max_slots=3)
    m = eng.metrics()
    wk = eng._pool["wk"]
    assert m["kv_window_tokens"] == WINDOW
    assert m["kv_window_pages_slot"] == 4
    assert m["kv_window_bytes"] == 2 * wk.nbytes
    assert m["kv_shared_readers"] == 2 and m["gmu_layers"] == 1
    assert m["kv_lane_pack"] == 2 and m["kv_query_group"] == 2
    text = eng.prometheus()
    for name in ("kv_window_tokens", "kv_window_pages_slot",
                 "kv_window_bytes", "kv_shared_readers", "gmu_layers",
                 "ssm_state_bytes"):
        assert "ds_tpu_" + name in text or name == "ssm_state_bytes", name
    # a model without such layers reports none of them
    from tests.unit.test_mamba1 import CFG as JAMBA
    plain = DecoderAdapter.from_model(DecoderLM(JAMBA))
    assert plain.cache_gauges({}) == {}


@pytest.mark.parametrize("asked, named", [
    (dict(spec_decode=True), "speculative decoding"),
    (dict(prefix_cache=True), "the prefix cache"),
    (dict(int8_kv=True), "int8 planes"),
    (dict(host_offload=True), "host offload"),
    (dict(role="decode"), "the prefill and decode roles"),
])
def test_bind_refuses_by_the_kinds_name_what_has_no_ring_form(model32, asked,
                                                              named):
    adapter = DecoderAdapter.from_model(model32[0])
    config = InferenceConfig(paged_kv=True, kv_page_len=PAGE, **asked)
    with pytest.raises(ValueError) as err:
        adapter.bind(config)
    assert named in str(err.value) and "window layers (2 swa" in str(
        err.value)
