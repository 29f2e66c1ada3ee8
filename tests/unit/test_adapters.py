"""Adapter conformance kit (inference/adapters/ — docs/ADAPTERS.md).

Every ModelAdapter implementation must pass the same battery, because
the engine is model-blind and trusts exactly these properties:

1. CHUNK-VS-WHOLE PREFILL PARITY — consuming a prompt in chunks lands
   the same cache frontier and the same greedy continuation as one
   whole-prompt append (chunked prefill rides on it).
2. DEEP-FRONTIER APPEND + n_valid — an append at a deep frontier with a
   partial-valid override advances ``pos`` by n_valid only, and the
   stale positions it wrote past the frontier are invisible once
   overwritten (the stale-cache rule).
3. VERIFY/ACCEPT ROLLBACK INVISIBILITY — a rejected speculative verify
   leaves no trace: ``pos`` comes back unchanged and the continuation
   is bit-identical to a never-speculated stream.
4. ONE COMPILED PROGRAM — a mixed greedy/sampled/spec workload through
   the engine compiles exactly one mixed-step program per adapter.
5. CAPTURE/RESTORE ROUND-TRIP — a slot captured from the pool restores
   bit-identically into any other slot, and adapter ``aux_`` state
   (global, not per-slot) is excluded from the record but preserved in
   the pool.

The five that touch a cache run on BOTH pools: the dense slotted pool
and the paged arena every benchmark cell serves from (kinds
``gpt2-paged`` / ``decoder-paged``: a paged cache at the primitive
level, ``paged_kv=True`` in the engine, ``capture_slot_paged`` /
``restore_slot_paged`` for the round-trip).

Plus the adapter-specific pins: the long-context parity/capacity pair.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceConfig, InferenceEngine
from deepspeed_tpu.inference.adapters import (
    DecoderAdapter,
    GPT2Adapter,
    LongContextAdapter,
    ModelAdapter,
)
from deepspeed_tpu.inference.kv_hierarchy import offload
from deepspeed_tpu.inference.kv_pool import harvest_snapshot
from deepspeed_tpu.parallel import mesh as mesh_lib
from tests.unit.compiled import compiled
from tests.unit.test_decoder import paged_cache
from tests.unit.test_inference import make_model, prompts_of, seq_greedy

KINDS = ("gpt2", "longcontext", "decoder")
# The cache-touching contract tests also run on the paged pool.
PAGED = ("gpt2-paged", "decoder-paged")
PAGE = 8

_ADAPTERS = {}


def is_paged(kind):
    return kind.endswith("-paged")



def adapter_of(kind):
    """(adapter, params, vocab_size) per kind — memoized, params are
    read-only everywhere downstream. The longcontext conformance
    instance keeps its threshold ABOVE every sequence the kit builds,
    so the battery exercises the adapter plumbing while its masks stay
    dense (the sparse regime has its own pins below)."""
    if kind not in _ADAPTERS:
        if is_paged(kind):
            # The same weights, the adapter bound as a paged engine binds
            # it (the page quantum is part of the static config).
            a, params, vocab = adapter_of(kind.split("-")[0])
            bound = a.bind(InferenceConfig(paged_kv=True, kv_page_len=PAGE))
            _ADAPTERS[kind] = (bound, params, vocab)
        elif kind == "decoder":
            model = decoder_model()
            a = DecoderAdapter.from_model(model, use_flash_decode=False)
            params = model.init(jax.random.PRNGKey(0))["params"]
            _ADAPTERS[kind] = (a, params, 256)
        else:
            cfg, model, params = make_model()
            if kind == "gpt2":
                a = GPT2Adapter.from_model(model, use_flash_decode=False)
            else:
                a = LongContextAdapter.from_model(
                    model, threshold=96, block=8, num_local_blocks=2)
            _ADAPTERS[kind] = (a, params, cfg.vocab_size)
    return _ADAPTERS[kind]


def decoder_model():
    """The config-driven decoder block (OLMoE's) at a tiny size, float32."""
    from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM

    return DecoderLM(DecoderConfig(
        vocab_size=256, n_layer=2, n_head=2, head_dim=16, hidden_size=32,
        n_positions=128, n_experts=4, experts_per_token=2, expert_width=32,
        dtype=jnp.float32, initializer_range=0.15))


def cache_of(kind, rows, max_len):
    """An empty cache for ``rows`` sequences on ``kind``'s pool: the
    adapter's dense planes, or a page arena with a block table of the
    row's own pages."""
    adapter, _, _ = adapter_of(kind)
    if is_paged(kind):
        return paged_cache(adapter, rows, page=PAGE, max_len=max_len)
    return adapter.init_cache(rows, max_len)


def ids_of(vocab, n, seed=5):
    rng = np.random.RandomState(seed)
    return rng.randint(0, vocab, size=(1, n)).astype(np.int32)


def greedy_decode(adapter, params, tok, cache, steps):
    out = []
    for _ in range(steps):
        logits, cache = compiled(adapter, "decode_step")(
            params, jnp.asarray([tok], jnp.int32), cache)
        tok = int(jnp.argmax(logits[0]))
        out.append(tok)
    return out, cache


_PRIM_REFS = {}


def primitive_greedy(kind, prompt, max_new, plane_len=96):
    """Sequential single-request greedy reference built from the
    adapter's OWN primitives — the oracle the slotted engine must match
    (per-row independence makes batch composition irrelevant). Always
    over the DENSE cache: a paged engine is held to the same streams."""
    kind = kind.split("-")[0]
    key = (kind, tuple(int(t) for t in prompt), int(max_new))
    if key not in _PRIM_REFS:
        adapter, params, _ = adapter_of(kind)
        cache = adapter.init_cache(1, plane_len)
        ids = jnp.asarray(np.asarray(prompt)[None].astype(np.int32))
        logits, cache = compiled(adapter, "prefill_append")(params, ids, cache)
        tok = int(jnp.argmax(logits[0, -1]))
        toks = [tok]
        more, _ = greedy_decode(adapter, params, tok, cache, max_new - 1)
        _PRIM_REFS[key] = toks + more
    return _PRIM_REFS[key]


def engine_of_kind(kind, **kw):
    adapter, params, vocab = adapter_of(kind)
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk_size", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("use_flash_decode", False)
    if is_paged(kind):
        kw.setdefault("paged_kv", True)
        kw.setdefault("kv_page_len", PAGE)
    return InferenceEngine(None, params, config=kw, adapter=adapter)


# ----------------------------------------------------- protocol surface


def test_protocol_required_surface_raises_unimplemented():
    base = ModelAdapter()
    with pytest.raises(NotImplementedError):
        base.cache_spec()
    with pytest.raises(NotImplementedError):
        base.init_cache(1, 8)
    # Optional hooks have working defaults.
    assert base.bind(None) is base
    assert base.aux_state() == {}
    assert base.observe(None, None) is None
    tree = {"w": np.zeros(2, np.float32)}
    assert base.serving_params(tree) is tree


@pytest.mark.parametrize("kind", KINDS)
def test_adapter_is_hashable_static_arg(kind):
    adapter, _, _ = adapter_of(kind)
    assert hash(adapter) == hash(adapter)
    assert adapter == type(adapter)(**{
        f.name: getattr(adapter, f.name)
        for f in __import__("dataclasses").fields(adapter)})


@pytest.mark.parametrize("kind", KINDS)
def test_serving_params_keeps_the_structure_and_is_idempotent(kind):
    """``serving_params``: the tree as the adapter's step reads it. The same
    structure and shapes, every leaf in the type it came in or in the
    adapter's compute type, and a second call changes nothing (the same
    leaf objects)."""
    adapter, params, _ = adapter_of(kind)
    once = adapter.serving_params(params)
    assert jax.tree_util.tree_structure(once) == \
        jax.tree_util.tree_structure(params)
    served = jnp.dtype(adapter.gcfg.dtype)
    for given, held in zip(jax.tree_util.tree_leaves(params),
                           jax.tree_util.tree_leaves(once)):
        assert held.shape == given.shape
        assert held.dtype in (given.dtype, served)
    twice = adapter.serving_params(once)
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(once),
                                      jax.tree_util.tree_leaves(twice)))


# ------------------------------------------------- 1. chunk-vs-whole


@pytest.mark.parametrize("kind", KINDS + PAGED)
def test_chunk_vs_whole_prefill_parity(kind):
    adapter, params, vocab = adapter_of(kind)
    ids = jnp.asarray(ids_of(vocab, 12))

    whole = cache_of(kind, 1, 32)
    logits_w, whole = compiled(adapter, "prefill_append")(params, ids, whole)

    chunked = cache_of(kind, 1, 32)
    for lo in (0, 4, 8):
        logits_c, chunked = compiled(adapter, "prefill_append")(
            params, ids[:, lo:lo + 4], chunked)

    assert int(whole["pos"][0]) == int(chunked["pos"][0]) == 12
    np.testing.assert_allclose(np.asarray(logits_w[:, -1]),
                               np.asarray(logits_c[:, -1]),
                               rtol=2e-5, atol=2e-5)
    tok_w = int(jnp.argmax(logits_w[0, -1]))
    tok_c = int(jnp.argmax(logits_c[0, -1]))
    assert tok_w == tok_c
    cont_w, _ = greedy_decode(adapter, params, tok_w, whole, 5)
    cont_c, _ = greedy_decode(adapter, params, tok_c, chunked, 5)
    assert cont_w == cont_c, "chunked prefill diverged from whole-prompt"


# --------------------------------------- 2. deep frontier + stale rule


@pytest.mark.parametrize("kind", KINDS + PAGED)
def test_append_at_deep_frontier_with_n_valid(kind):
    adapter, params, vocab = adapter_of(kind)
    append = compiled(adapter, "prefill_append")
    ids = jnp.asarray(ids_of(vocab, 28, seed=7))

    clean = cache_of(kind, 1, 48)
    logits, clean = append(params, ids, clean)
    want, _ = greedy_decode(adapter, params,
                            int(jnp.argmax(logits[0, -1])), clean, 4)

    # Staged: 24 tokens, then a 4-token append of which only 2 are the
    # true continuation (n_valid=2) — positions 26/27 get k/v for
    # GARBAGE tokens past the frontier.
    garbage = jnp.asarray(ids_of(vocab, 2, seed=99))
    staged = cache_of(kind, 1, 48)
    _, staged = append(params, ids[:, :24], staged)
    tail = jnp.concatenate([ids[:, 24:26], garbage], axis=1)
    _, staged = append(params, tail, staged,
                                       n_valid=jnp.asarray([2]))
    assert int(staged["pos"][0]) == 26, "n_valid must override the advance"
    # The true continuation overwrites the stale positions before any
    # query can attend them — the garbage must be invisible.
    logits, staged = append(params, ids[:, 26:28], staged)
    got, _ = greedy_decode(adapter, params,
                           int(jnp.argmax(logits[0, -1])), staged, 4)
    assert got == want, "stale frontier write leaked into the stream"


# ------------------------------------- 3. verify rollback invisibility


@pytest.mark.parametrize("kind", KINDS + PAGED)
def test_verify_rollback_is_invisible(kind):
    adapter, params, vocab = adapter_of(kind)
    ids = jnp.asarray(ids_of(vocab, 10, seed=3))

    def stream(speculate):
        cache = cache_of(kind, 1, 32)
        logits, cache = compiled(adapter, "prefill_append")(params, ids, cache)
        tok = int(jnp.argmax(logits[0, -1]))
        toks = [tok]
        head, cache = greedy_decode(adapter, params, tok, cache, 2)
        toks += head
        if speculate:
            # A verify whose whole draft gets rejected: k/v written at
            # the frontier are stale garbage, pos must come back
            # unchanged (the adapter's rollback contract).
            pos0 = int(cache["pos"][0])
            draft = jnp.asarray(
                [[toks[-1]] + ids_of(vocab, 2, seed=42)[0].tolist()],
                jnp.int32)
            vlogits, cache = compiled(adapter, "verify_forward")(
                params, draft, cache)
            assert vlogits.shape[1] == 3
            assert int(cache["pos"][0]) == pos0, \
                "verify_forward must not advance the frontier"
        tail, cache = greedy_decode(adapter, params, toks[-1], cache, 4)
        return toks + tail

    assert stream(True) == stream(False), \
        "a rejected speculation changed the stream"


# ----------------------------------- 4. engine: one program, parity


@pytest.mark.parametrize("kind", KINDS + PAGED)
def test_engine_mixed_workload_single_compile_and_parity(kind):
    """Mixed greedy/sampled, spec-on/spec-off requests trickling through
    the slotted engine: ONE compiled program, greedy streams match the
    adapter-primitive oracle, sampled streams reproduce on resubmit."""
    adapter, params, vocab = adapter_of(kind)
    eng = engine_of_kind(kind, spec_decode=True, spec_k=2, spec_ngram=2)
    assert eng.metrics()["adapter"] == adapter.name

    rng = np.random.RandomState(17)
    lens = [5, 9, 6, 12, 7, 8]
    prompts = [rng.randint(0, vocab, size=(n,)).astype(np.int32)
               for n in lens]
    reqs = []
    for i, p in enumerate(prompts):
        kw = {"max_new_tokens": 5 + (i % 3)}
        if i % 2:
            kw["temperature"] = 0.7
            kw["seed"] = 100 + i
        if i % 3 == 0:
            kw["spec_decode"] = False
        reqs.append(eng.submit(p, **kw))
        eng.step()
    eng.run()
    assert eng.compile_count == 1, \
        "{} adapter broke the one-program contract".format(adapter.name)

    for i, (p, r) in enumerate(zip(prompts, reqs)):
        assert len(r.tokens) == 5 + (i % 3)
        if i % 2 == 0:  # greedy rows: exact oracle parity
            assert r.tokens == primitive_greedy(kind, p, len(r.tokens)), \
                "slot-served greedy stream diverged from the primitives"
    # Sampled determinism: resubmitting reproduces the stream (the
    # positional rng is adapter-independent per-row state).
    redo = eng.submit(prompts[1], max_new_tokens=6, temperature=0.7,
                      seed=101)
    eng.run()
    assert redo.tokens == reqs[1].tokens
    assert eng.compile_count == 1


# ------------------------------------- 5. capture/restore round-trip


@pytest.mark.parametrize("kind", KINDS + PAGED)
def test_capture_restore_round_trip_excludes_aux(kind):
    adapter, params, vocab = adapter_of(kind)
    eng = engine_of_kind(kind)
    for n in (6, 9):
        # Budgets that outlast the two steps: both slots are live (a
        # finished paged slot has given its pages back).
        eng.submit(ids_of(vocab, n, seed=n)[0], max_new_tokens=16)
    eng.step()
    eng.step()
    pool = eng._pool

    if is_paged(kind):
        # A slot's record is its LIVE pages in logical order; it restores
        # into whatever fresh physical pages the allocator hands out.
        pager = eng._pager
        rows = [pager.row_pages(s) for s in (0, 1)]
        rec = offload.capture_slot_paged(pool, 0, rows[0])
        assert rec["k"].shape[1] == len(rows[0]) >= 1
        to = 2  # the free slot: slot 1's pages are live
        fresh = pager.alloc_pages(len(rows[0]))
        assert not set(fresh) & set(rows[0] + rows[1])
        restored = offload.restore_slot_paged(pool, to, rec, fresh)
        at = (slice(None), np.asarray(fresh))
        batched = offload.capture_slots_paged(pool, [0, 1], rows)
    else:
        rec = offload.capture_slot(pool, 0)
        to = 1
        restored = offload.restore_slot(pool, to, rec)
        at = (slice(None), to)
        batched = offload.capture_slots(pool, [0, 1])
    assert not any(k.startswith("aux_") or k == "block_tbl" for k in rec), \
        "global aux state must not be captured per-slot"
    np.testing.assert_array_equal(np.asarray(restored["k"])[at], rec["k"])
    np.testing.assert_array_equal(np.asarray(restored["v"])[at], rec["v"])
    for name in ("pos", "last_tok", "active", "toks"):
        np.testing.assert_array_equal(np.asarray(restored[name][to]),
                                      rec[name])
    # Batched capture agrees with the per-slot form.
    for name, val in rec.items():
        np.testing.assert_array_equal(batched[0][name], val)
    if kind.startswith("decoder"):
        # aux rides the harvest snapshot and survives restore untouched.
        assert "aux_moe_load" in restored
        snap = harvest_snapshot(restored)
        assert snap["aux_moe_load"].shape == (4,)
        np.testing.assert_array_equal(snap["aux_moe_load"],
                                      np.asarray(pool["aux_moe_load"]))


# ------------------------------------- the adapter is told by the model


@pytest.mark.parametrize("kind", ["decoder", "gpt2"])
def test_init_inference_picks_the_adapter_from_the_models_class(kind):
    """No ``adapter=`` argument: a DecoderLM is served by DecoderAdapter, a
    GPT2LMHeadModel by GPT2Adapter as ever, through the same paged pool and
    the one mixed-step program."""
    import deepspeed_tpu as deepspeed

    if kind == "decoder":
        model = decoder_model()
        params = model.init(jax.random.PRNGKey(0))["params"]
        want = DecoderAdapter
    else:
        _, model, params = make_model()
        want = GPT2Adapter
    eng = deepspeed.init_inference(model=model, params=params, config={
        "inference": {"max_slots": 2, "max_len": 64, "chunk_size": 4,
                      "prefill_chunk": 8, "paged_kv": True,
                      "kv_page_len": 16, "use_flash_decode": False}})
    assert type(eng.adapter) is want
    assert eng.adapter.gcfg.kv_page_len == 16
    req = eng.submit(ids_of(256, 7)[0], max_new_tokens=5)
    eng.run()
    assert len(req.tokens) == 5 and eng.compile_count == 1
    if kind == "decoder":
        assert req.tokens == primitive_greedy("decoder", ids_of(256, 7)[0], 5)


# ----------------------------------------------- long-context specifics


def test_longcontext_below_threshold_token_identical_to_dense():
    """Every query position below the threshold: the sparse mask term is
    all-true, so streams are BIT-identical to the dense GPT-2 engine."""
    cfg, model, params = make_model()
    adapter = LongContextAdapter.from_model(model, threshold=32, block=8,
                                            num_local_blocks=2)
    eng = engine_of_kind("gpt2")  # dense reference engine
    lc = InferenceEngine(None, params,
                         config={"max_slots": 3, "max_len": 64,
                                 "chunk_size": 4, "prefill_chunk": 8,
                                 "use_flash_decode": False},
                         adapter=adapter)
    assert lc.metrics()["adapter"] == "longcontext"
    prompts = prompts_of(cfg, [5, 9, 6])
    # prompt + new <= 32 for every request: nothing crosses the threshold.
    want = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run()
    got = [lc.submit(p, max_new_tokens=8) for p in prompts]
    lc.run()
    for w, g in zip(want, got):
        assert g.tokens == w.tokens, \
            "below-threshold long-context decode diverged from dense"
    assert lc.compile_count == 1


def test_longcontext_capacity_pin_sparse_decode_with_host_offload():
    """The capacity pin: more concurrent long sessions than HBM slots,
    every stream crossing into the block-sparse regime, host offload
    parking the overflow — all complete, swaps fired, one program. The
    below-threshold prefix of each stream still matches dense bit for
    bit (parity and sparsity in one run)."""
    cfg, model, params = make_model()
    adapter = LongContextAdapter.from_model(model, threshold=32, block=8,
                                            num_local_blocks=2)
    lc = InferenceEngine(None, params,
                         config={"max_slots": 2, "max_len": 64,
                                 "chunk_size": 4, "prefill_chunk": 8,
                                 "host_offload": True, "swap_slots": 8,
                                 "use_flash_decode": False},
                         adapter=adapter)
    prompts = prompts_of(cfg, [8, 6, 7, 9], seed=21)
    news = [40, 38, 36, 34]  # prompt + new > threshold for every request
    reqs = [lc.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    lc.run()
    m = lc.metrics()
    assert all(len(r.tokens) == n for r, n in zip(reqs, news)), \
        "a long session failed to complete under offload pressure"
    assert m["swap_outs"] >= 1 and m["swap_ins"] >= 1, \
        "capacity pin must actually exercise host offload"
    assert m["compile_count"] == 1 and m["adapter"] == "longcontext"
    assert lc.telemetry.gauge("sparse_decode_threshold").value == 32.0
    # Tokens emitted from query positions still under the threshold are
    # dense-identical; the streams then continue block-sparse.
    for p, r in zip(prompts, reqs):
        upto = max(0, 32 - len(p) - 4)  # stay clear of the boundary
        assert r.tokens[:upto] == seq_greedy(model, params, p, upto), \
            "below-threshold prefix diverged from dense"


def test_longcontext_ring_fallback_on_seq_mesh(eight_devices):
    """A mesh carrying a 'seq' axis flips bind into ring mode: dense
    attention over a sequence-sharded plane (sparse masking and seq
    sharding compose poorly — module docstring)."""
    _, model, _ = make_model()
    adapter = LongContextAdapter.from_model(model, threshold=32, block=8,
                                            num_local_blocks=2)
    mesh = mesh_lib.build_mesh(devices=jax.devices()[:2], num_sp=2,
                               num_dp=1)
    bound = adapter.bind(InferenceConfig(), mesh)
    assert bound.mode == "ring"
    assert bound.threshold == 0  # dense masks under sequence sharding
    # No mesh (or no seq axis): block-sparse mode sticks.
    assert adapter.bind(InferenceConfig(), None).mode == "block_sparse"
