"""The config-driven decoder block (models/decoder.py, OLMoE's) against its
plain float32 reference (benchmark/reference/olmoe.py), on LOGITS, through
the PAGED pool: whole-prompt prefill, chunked prefill and prefill-then-decode
must each equal the reference's full forward pass.

TOLERANCES. float32 compute: 2e-4 on logits of standard deviation 1.2 (the
program and the reference order their sums differently; measured 3e-6), tight
enough that bf16 anywhere in the program fails it (bf16 measures 3e-2 to
6e-2). bf16 compute: 0.1, because bf16 keeps 8 bits (2^-8 of a value) through
some ten roundings a layer (measured up to 0.06); a program that kept fewer
bits than bf16 fails it.

TOP-K IS NOT CONTINUOUS. Where a token's last kept router weight and its
first cut one are closer than the program's rounding of them, program and
reference may keep different experts and both are right; at this size (2 of 8
experts, weights near 0.3) that moves the token's logits by up to 0.8, and the
logits of every LATER token of its sequence through attention. The reference
reports each token's smallest such gap; a sequence is compared up to its
first token whose gap is under ``GAP``, never past it, and the tests bound how
many tokens that leaves out. The tolerance is not widened for any token that
is compared.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import olmoe as reference
from deepspeed_tpu.inference import kv_pool
from deepspeed_tpu.inference.adapters import DecoderAdapter
from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
from deepspeed_tpu.moe import routed
from tests.unit.compiled import compiled

B, T, PAGE = 12, 20, 16
DECODE_FROM = 5     # prefill_then_decode: tokens from here on are decoded
# compute dtype -> (tolerance on logits, router gap under which a token's
# choice of experts is the rounding's). Rounding moves a router weight of
# 0.3 by 2^-8 of it (1e-3) in bf16 and by 1e-7 in float32.
CASES = {"float32": (2e-4, 1e-5), "bfloat16": (0.1, 1e-2)}


def tiny_config(dtype, **kw):
    # initializer_range 0.15: at hidden 64 the published 0.02 gives logits
    # of 0.03 and a router that cannot tell experts apart.
    kw.setdefault("n_head", 4)
    kw.setdefault("head_dim", 16)
    return DecoderConfig(
        vocab_size=256, n_layer=2, hidden_size=64,
        n_positions=128, n_experts=8, experts_per_token=2, expert_width=32,
        dtype=jnp.dtype(dtype), initializer_range=0.15, **kw)


_BUILT = {}


def built(dtype):
    """(adapter, params, ids [B, T], reference logits, router gaps)."""
    if dtype not in _BUILT:
        cfg = tiny_config(dtype)
        model = DecoderLM(cfg)
        params = jax.jit(lambda k: model.init(k)["params"])(
            jax.random.PRNGKey(0))
        ids = jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (B, T)), jnp.int32)
        want, gaps = harness.load_by_name(
            "model_builders", "olmoe").reference_logits(
                params, ids, cfg, with_gaps=True)
        adapter = DecoderAdapter.from_model(model, use_flash_decode=False)
        _BUILT[dtype] = (adapter, params, ids, want, gaps)
    return _BUILT[dtype]


def paged_cache(adapter, rows, page=PAGE, max_len=64):
    """An empty PAGED cache for ``rows`` sequences: a page arena and a block
    table that maps row b's logical pages to pages of its own (page 0 is the
    pool's trash page)."""
    pool = kv_pool.init_pool(adapter.cache_spec(), rows, max_len, slack=page,
                             page_len=page)
    n_lp = pool["block_tbl"].shape[1]
    tbl = 1 + jnp.arange(rows * n_lp, dtype=jnp.int32).reshape(rows, n_lp)
    return dict(k=pool["k"], v=pool["v"], block_tbl=tbl,
                pos=jnp.zeros((rows,), jnp.int32), **adapter.aux_state())


def whole(adapter, params, ids):
    return compiled(adapter, "prefill_append")(
        params, ids, paged_cache(adapter, ids.shape[0]))[0]


def chunked(adapter, params, ids):
    cache, out = paged_cache(adapter, ids.shape[0]), []
    for lo in range(0, ids.shape[1], 4):
        logits, cache = compiled(adapter, "prefill_append")(
            params, ids[:, lo:lo + 4], cache)
        out.append(logits)
    return jnp.concatenate(out, axis=1)


def prefill_then_decode(adapter, params, ids):
    cache = paged_cache(adapter, ids.shape[0])
    logits, cache = compiled(adapter, "prefill_append")(
        params, ids[:, :DECODE_FROM], cache)
    out = [logits]
    for t in range(DECODE_FROM, ids.shape[1]):
        logits, cache = compiled(adapter, "decode_step")(
            params, ids[:, t], cache)
        out.append(logits[:, None])
    return jnp.concatenate(out, axis=1)


def compared(gaps, gap_tol):
    """[B, T] bool: a sequence's tokens before its first ambiguous one."""
    return np.cumsum(gaps < gap_tol, axis=1) == 0


@pytest.mark.parametrize("path", [whole, chunked, prefill_then_decode],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("dtype", sorted(CASES))
def test_the_paged_pool_gives_the_references_logits(dtype, path):
    tol, gap_tol = CASES[dtype]
    adapter, params, ids, want, gaps = built(dtype)
    got = np.asarray(path(adapter, params, ids))
    assert got.shape == want.shape and got.dtype == np.float32
    keep = compared(gaps, gap_tol)
    # what the discontinuity leaves out is bounded: float32 compares every
    # token; bf16 at least a quarter of them (one token in ten is that close
    # at 2 of 8 experts, and it ends its sequence), decoded ones among them
    assert keep.sum() >= (B * T if dtype == "float32" else B * T // 4), \
        keep.sum()
    assert keep[:, DECODE_FROM:].sum() >= 20
    err = np.abs(got - want).max(axis=-1)
    assert err[keep].max() <= tol, (err[keep].max(), np.argwhere(
        (err > tol) & keep)[:5])
    assert want.std() > 1.0   # the logits are of order 1, so tol means it


def test_the_three_paths_agree_with_each_other_to_the_bit_in_float32():
    """What the cache holds does not depend on how the prompt was chunked:
    keys are rotated at their own position before they are written."""
    adapter, params, ids, _, _ = built("float32")
    a = np.asarray(whole(adapter, params, ids))
    for path in (chunked, prefill_then_decode):
        np.testing.assert_allclose(np.asarray(path(adapter, params, ids)),
                                   a, rtol=0, atol=1e-5)


def test_a_rows_logits_do_not_depend_on_its_neighbours():
    """Exact top-k with no capacity: permuting the rows of a batch permutes
    the logits and changes nothing else (the protocol's replay invariant)."""
    adapter, params, ids, _, _ = built("float32")
    order = np.random.RandomState(1).permutation(B)
    a = np.asarray(prefill_then_decode(adapter, params, ids))
    b = np.asarray(prefill_then_decode(adapter, params, ids[order]))
    np.testing.assert_array_equal(a[order], b)
    alone = np.asarray(prefill_then_decode(adapter, params, ids[3:4]))
    np.testing.assert_allclose(alone[0], a[3], rtol=0, atol=1e-5)


def test_the_router_keeps_the_k_largest_of_all_experts_unrenormalised():
    logits = jnp.asarray(np.random.RandomState(2).randn(50, 8), jnp.float32)
    weights, experts = routed.route(logits, 2)
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    for t in range(50):
        best = np.argsort(-probs[t])[:2]
        assert set(np.asarray(experts[t])) == set(best)
        np.testing.assert_allclose(np.sort(np.asarray(weights[t])),
                                   np.sort(probs[t, best]), rtol=1e-6)
    assert float(jnp.max(jnp.sum(weights, axis=-1))) < 1.0
    renormalised, _ = routed.route(logits, 2, renormalise=True)
    np.testing.assert_allclose(np.asarray(jnp.sum(renormalised, -1)), 1.0,
                               rtol=1e-6)
    # nothing dropped: every token reaches exactly k experts
    gate, counts = routed.dispatch(weights, experts, 8)
    assert float(jnp.sum(counts)) == 50 * 2
    assert np.array_equal(np.asarray(jnp.sum(gate > 0, axis=-1)), [2] * 50)
    np.testing.assert_allclose(np.asarray(jnp.sum(gate, -1)),
                               np.asarray(jnp.sum(weights, -1)), rtol=1e-6)


def test_expert_ffn_is_the_sum_over_each_tokens_own_experts():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(12, 64), jnp.float32)
    p = {"gate": jnp.asarray(rng.randn(64, 8), jnp.float32),
         "gate_proj": jnp.asarray(0.2 * rng.randn(8, 64, 32), jnp.float32),
         "up_proj": jnp.asarray(0.2 * rng.randn(8, 64, 32), jnp.float32),
         "down_proj": jnp.asarray(0.2 * rng.randn(8, 32, 64), jnp.float32)}
    want = np.asarray(reference.moe_per_token(x, p, top_k=2))
    weights, experts = routed.route(x @ p["gate"], 2)
    got = routed.expert_ffn(
        x, routed.dispatch(weights, experts, 8)[0],
        jnp.concatenate([p["gate_proj"], p["up_proj"]], axis=-1),
        p["down_proj"])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
    # and the reference's own loop over experts is its per-token form
    with jax.default_matmul_precision("highest"):
        kept, _ = reference._router(x, p, 2, False)
        np.testing.assert_allclose(np.asarray(reference._moe(x, p, kept)),
                                   want, rtol=1e-5, atol=1e-6)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(reference))
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)]
    assert names and not any("deepspeed" in n for n in names), names


def test_the_cache_free_forward_is_the_served_one():
    adapter, params, ids, _, _ = built("float32")
    model = DecoderLM(adapter.gcfg)
    np.testing.assert_allclose(
        np.asarray(jax.jit(model.apply)({"params": params}, ids)),
        np.asarray(whole(adapter, params, ids)), rtol=0, atol=1e-5)


@pytest.mark.parametrize("n_head, head_dim, stored", [
    (4, 16, (1, 64)),      # the tiny block: its four heads are one stored head
    (4, 64, (2, 128)),     # GPT-2's head dim: g = 2 heads a lane tile
    (8, 32, (2, 128)),     # g = 4
    (2, 128, (2, 128)),    # OLMoE's head dim fills a tile: nothing is packed
    (5, 64, (3, 128)),     # g does not divide the heads: a zero head
])
def test_the_interpreted_paged_kernels_serve_the_block_too(n_head, head_dim,
                                                           stored):
    """The cache side is GPT-2's (``generation.CacheAttention``): with pages
    of a kernel block (128) and the flag on, ``kv_append`` writes the arena
    in place and the layer-indexed paged kernel reads it, as on the chip.
    The arena is stored ``[L, P, H / g, page_len, g * D]`` (``lane_pack``
    heads a lane tile), and the block's logits do not know."""
    _, _, ids, _, _ = built("float32")
    model = DecoderLM(tiny_config("float32", n_head=n_head,
                                  head_dim=head_dim))
    params = jax.jit(lambda k: model.init(k)["params"])(jax.random.PRNGKey(0))
    flash = DecoderAdapter.from_model(model, use_flash_decode=True)
    cache = paged_cache(flash, 2, page=128, max_len=128)
    assert cache["k"].shape[2:] == (stored[0], 128, stored[1])
    logits, cache = compiled(flash, "prefill_append")(
        params, ids[:2, :12], cache)
    more, _ = compiled(flash, "decode_step")(params, ids[:2, 12], cache)
    want = np.asarray(jax.jit(model.apply)({"params": params},
                                           ids[:2, :13]))
    np.testing.assert_allclose(np.asarray(logits), want[:, :12], rtol=0,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(more), want[:, 12], rtol=0,
                               atol=1e-4)


def test_the_engine_publishes_expert_load_and_drops_nothing():
    from deepspeed_tpu.inference import InferenceEngine

    adapter, params, ids, _, _ = built("float32")
    eng = InferenceEngine(DecoderLM(adapter.gcfg), params, config=dict(
        max_slots=2, max_len=64, chunk_size=4, prefill_chunk=8,
        paged_kv=True, kv_page_len=16, use_flash_decode=False))
    assert eng.metrics()["adapter"] == "decoder"
    eng.submit(np.asarray(ids[0, :9]), max_new_tokens=6)
    eng.run()
    assert eng.compile_count == 1
    from deepspeed_tpu.telemetry.exporters import prometheus_text

    text = prometheus_text(eng.telemetry)
    assert text.count("moe_expert_load{") == 8
    snap = kv_pool.harvest_snapshot(eng._pool)
    load, routed_n = snap["aux_moe_load"], float(snap["aux_moe_routed"])
    assert load.shape == (8,) and routed_n > 0
    # every routed (token, layer) reached exactly k experts: nothing dropped
    assert float(load.sum()) == routed_n
    assert routed_n % (2 * 2) == 0
    assert "moe_drop_rate" not in text
