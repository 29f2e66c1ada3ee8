"""Speculative decoding (the fused draft/verify decode lane).

The contract under test:
1. PARITY — greedy tokens with speculation are bit-identical to
   sequential ``models.generation.generate`` for BOTH repetitive prompts
   (drafts mostly accepted) and adversarial random prompts (drafts
   mostly rejected — the free-rollback path), across chunk sizes and
   spec_k values, and for EOS truncation inside an accepted prefix.
2. SAMPLED PARITY — the positional rng (fold_in(seed, position) names
   every draw) makes spec on/off produce IDENTICAL sampled streams, not
   merely same-distribution ones.
3. ONE COMPILE — speculation is baked into the one mixed-step program:
   a spec/non-spec request mix cohabits it with compile_count == 1.
4. ACCEPTANCE — on a repetitive workload the engine accepts > 1 token
   per occupied slot-step and reports the accept metrics.
5. PRIMITIVES — ngram_draft (most-recent match, frontier masking,
   fallback), accept_counts (prefix rule + veto), verify_forward
   (bitwise-equal logits to stepwise decode_step, frontier unmoved,
   accepted k/v already correct).
6. CONFIG — spec_decode validation, DS_TPU_SPEC_DECODE resolution, the
   submit() guard, and the KV-plane slack floor.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.models.generation import (
    _forward,
    accept_counts,
    as_gencfg,
    decode_step,
    init_cache,
    ngram_draft,
    verify_forward,
)
from tests.unit.test_chunked_prefill import (
    engine_of,
    make_model,
    prompts_of,
    seq_greedy,
)


def spec_engine(model, params, **kw):
    kw.setdefault("spec_decode", True)
    kw.setdefault("spec_k", 4)
    kw.setdefault("spec_ngram", 3)
    return engine_of(model, params, **kw)


def rep_prompt(cfg, phrase=4, reps=5, seed=0):
    """A prompt that is one short phrase tiled — the n-gram drafter's
    best case (greedy continuations repeat the phrase)."""
    rng = np.random.RandomState(seed)
    return np.tile(rng.randint(0, cfg.vocab_size, size=(phrase,)),
                   reps).astype(np.int32)


# ----------------------------------------------------------------- parity


def test_greedy_parity_repetitive_and_adversarial():
    """Bit-identical greedy output whether drafts are mostly accepted
    (repetitive prompt) or mostly rejected (random prompt), in one
    engine, with the one-compile guarantee intact."""
    cfg, model, params = make_model()
    rep = rep_prompt(cfg)
    adv = prompts_of(cfg, [17])[0]
    eng = spec_engine(model, params)
    r_rep = eng.submit(rep, max_new_tokens=20)
    r_adv = eng.submit(adv, max_new_tokens=12)
    eng.run()
    assert r_rep.tokens == seq_greedy(model, params, rep, 20)
    assert r_adv.tokens == seq_greedy(model, params, adv, 12)
    assert eng.compile_count == 1


@pytest.mark.parametrize("spec_k", [1, 2, 4])
@pytest.mark.parametrize("chunk_size", [1, 4])
def test_speculation_invisible_across_chunk_and_k(spec_k, chunk_size):
    """Rejection rollback is exact wherever it lands: chunk boundaries
    and draft lengths shift WHICH verify rejects, never the tokens.
    (A clamped frontier write or a stale-ring read would show up here
    as divergence at some (chunk, K) combination.)"""
    cfg, model, params = make_model()
    p = rep_prompt(cfg, phrase=3, reps=4, seed=2)
    want = seq_greedy(model, params, p, 15)
    eng = spec_engine(model, params, spec_k=spec_k, chunk_size=chunk_size)
    r = eng.submit(p, max_new_tokens=15)
    eng.run()
    assert r.tokens == want, \
        "spec tokens diverge at spec_k={} chunk={}".format(spec_k, chunk_size)


def test_paged_flash_spec_streams_equal_the_dense_pools():
    """Speculation over a paged pool whose page is a kernel block (128):
    every verify appends spec_k + 1 rows in place through ``kv_append``,
    some across the boundary between a row's first and second page, and
    scores them through the layer-indexed paged kernel. Streams equal the
    dense pool's under the dense kernel, and the plain greedy stream."""
    cfg, model, params = make_model(n_positions=256)
    prompts = [rep_prompt(cfg, phrase=4, reps=30, seed=1),    # 120 tokens
               rep_prompt(cfg, phrase=5, reps=25, seed=2),    # 125
               prompts_of(cfg, [9])[0]]

    def serve(**extra):
        eng = spec_engine(model, params, max_len=192, prefill_chunk=64,
                          use_flash_decode=True, **extra)
        reqs = [eng.submit(p, max_new_tokens=14) for p in prompts]
        eng.run()
        assert eng.compile_count == 1
        return eng, [r.tokens for r in reqs]

    _, want = serve()
    paged, got = serve(paged_kv=True, kv_page_len=128)
    assert got == want, "paged + flash spec streams diverged from dense"
    assert paged.metrics()["accepted_per_step_mean"] > 1.0
    assert got[2] == seq_greedy(model, params, prompts[2], 14)


def test_sampled_stream_identical_spec_on_off():
    """Under temperature sampling the verify lane draws each position
    with the SAME fold_in(seed, position) rng the 1-token path uses, so
    spec on/off give the exact same stream — not just the same
    distribution. (This is what makes speculation safe to flip on in
    production: no output change, ever.)"""
    cfg, model, params = make_model()
    p = rep_prompt(cfg, seed=1)

    def run(spec):
        eng = spec_engine(model, params) if spec else engine_of(model, params)
        r = eng.submit(p, max_new_tokens=12, temperature=0.8, top_k=20,
                       seed=5)
        eng.run()
        return r.tokens

    assert run(True) == run(False)


def test_eos_truncation_within_accepted_prefix():
    """EOS inside an accepted draft prefix truncates emission AT the EOS
    (emit-EOS-then-stop), exactly like the sequential path."""
    cfg, model, params = make_model()
    p = rep_prompt(cfg, seed=3)
    free = seq_greedy(model, params, p, 10)
    eos = free[2]                       # stop at the 3rd generated token
    want = free[:free.index(eos) + 1]
    eng = spec_engine(model, params)
    r = eng.submit(p, max_new_tokens=10, eos_token_id=eos)
    eng.run()
    assert r.tokens == want


# ------------------------------------------- cohabitation + compile count


def test_mixed_spec_and_nonspec_cohabit_one_program():
    """submit(spec_decode=False) opts a request out via the traced
    per-slot flag — its agreement is vetoed (1 token/step) while its
    neighbor speculates, in the SAME compiled program."""
    cfg, model, params = make_model()
    eng = spec_engine(model, params)
    p1, p2 = rep_prompt(cfg), prompts_of(cfg, [9])[0]
    a = eng.submit(p1, max_new_tokens=16)
    b = eng.submit(p2, max_new_tokens=10, spec_decode=False)
    eng.run()
    assert a.tokens == seq_greedy(model, params, p1, 16)
    assert b.tokens == seq_greedy(model, params, p2, 10)
    assert eng.compile_count == 1, \
        "spec/non-spec mix must not add a program"


# -------------------------------------------------------------- acceptance


def test_acceptance_exceeds_one_on_repetitive_workload():
    """The perf claim's mechanism: a repetitive prompt's greedy
    continuation repeats the phrase, the drafter finds it, and the mean
    accepted-per-occupied-step clears 1.0 (deterministic in f32 on this
    canned config). The accept metrics come out of metrics()."""
    cfg, model, params = make_model()
    p = rep_prompt(cfg)
    eng = spec_engine(model, params)
    r = eng.submit(p, max_new_tokens=20)
    eng.run()
    assert r.tokens == seq_greedy(model, params, p, 20)
    m = eng.metrics()
    assert m["spec_decode"] is True
    assert m["spec_k"] == 4 and m["spec_ngram"] == 3
    assert m["accepted_per_step_mean"] > 1.0
    assert m["draft_accept_rate"] > 0.0
    assert m["accepted_per_step_p50"] >= 1.0
    assert m["accepted_per_step_p99"] <= eng.config.spec_k + 1
    assert m["tokens_out"] == 20


def test_nonspec_engine_metrics_omit_accept_stats():
    cfg, model, params = make_model()
    eng = engine_of(model, params)
    eng.submit(prompts_of(cfg, [5])[0], max_new_tokens=3)
    eng.run()
    m = eng.metrics()
    assert m["spec_decode"] is False
    assert "accepted_per_step_mean" not in m


# -------------------------------------------------------------- primitives


def test_ngram_draft_most_recent_match_fallback_and_frontier_mask():
    T, n, k = 16, 2, 3
    fill = 100  # unique tail filler; never matches and is never gathered
    rows = np.full((3, T), fill, np.int32) + np.arange(3 * T).reshape(3, T)
    # Row 0: trailing 2-gram (1,2) occurs ending at j=1 (cont 9,9,1) and
    # j=5 (cont 7,8,1) — the MOST RECENT match must win.
    rows[0, :10] = [1, 2, 9, 9, 1, 2, 7, 8, 1, 2]
    # Row 1: no earlier occurrence of the trailing gram — fallback
    # drafts the frontier token k times.
    rows[1, :4] = [3, 4, 5, 6]
    # Row 2: the ONLY matching gram sits past the frontier (stale-ring
    # garbage) — it must be ignored, not drafted from.
    rows[2, :8] = [9, 8, 7, 6, 1, 2, 1, 2]
    pos = np.array([9, 3, 5], np.int32)
    draft = np.asarray(ngram_draft(jnp.asarray(rows), jnp.asarray(pos), n, k))
    np.testing.assert_array_equal(draft[0], [7, 8, 1])
    np.testing.assert_array_equal(draft[1], [6, 6, 6])
    np.testing.assert_array_equal(draft[2], [2, 2, 2])


def test_ngram_draft_continuation_clips_to_frontier():
    """A match just below the frontier drafts from the (valid) suffix it
    overlaps — the gather clips to <= pos, never reading garbage."""
    row = np.full((1, 8), 50, np.int32)
    row[0, :4] = [1, 2, 1, 2]
    draft = np.asarray(ngram_draft(jnp.asarray(row),
                                   np.array([3], np.int32), 2, 3))
    # Match ends at j=1; continuation indices 2,3,4 clip to 2,3,3.
    np.testing.assert_array_equal(draft[0], [1, 2, 2])


def test_accept_counts_prefix_rule_and_veto():
    draft = jnp.asarray([[1, 2, 3], [1, 9, 3], [7, 7, 7]])
    choices = jnp.asarray([[1, 2, 3, 4], [1, 2, 3, 4], [7, 9, 9, 9]])
    np.testing.assert_array_equal(
        np.asarray(accept_counts(draft, choices)), [4, 2, 2])
    ok = jnp.asarray([[True], [False], [True]])
    np.testing.assert_array_equal(
        np.asarray(accept_counts(draft, choices, ok=ok)), [4, 1, 2])


def test_verify_forward_matches_stepwise_decode_and_keeps_pos():
    """The verify primitive's whole contract in one scenario: scoring
    [last_tok, draft] in one pass gives the logits two decode_steps
    would (equal up to GEMM-shape rounding — the [2, C] matmul reduces
    in a different order than two [1, C] ones — with IDENTICAL argmax,
    which is what greedy parity consumes), writes the same k/v (an
    accepted draft needs no cache fixup), and leaves the frontier where
    it was."""
    cfg, model, params = make_model()
    gcfg = as_gencfg(cfg, use_flash_decode=False)
    prompt = prompts_of(cfg, [6])[0]
    cache = init_cache(gcfg, 1, 32)
    # each primitive under one jit: eagerly a forward is some hundred small
    # dispatches, each compiled for its shape
    forward, step, verify = (jax.jit(f, static_argnums=1) for f in (
        _forward, decode_step, verify_forward))
    logits, cache = forward(params, gcfg, jnp.asarray(prompt)[None], cache)
    t0 = jnp.argmax(logits[0, -1]).astype(jnp.int32)

    l0, seq = step(params, gcfg, t0[None], cache)
    t1 = jnp.argmax(l0[0]).astype(jnp.int32)
    l1, seq = step(params, gcfg, t1[None], seq)

    ids = jnp.stack([t0, t1])[None]                    # [1, 2]
    vlog, ver = verify(params, gcfg, ids, cache)
    np.testing.assert_allclose(np.asarray(vlog[0, 0]), np.asarray(l0[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(vlog[0, 1]), np.asarray(l1[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(vlog[0], axis=-1)),
        np.asarray(jnp.stack([jnp.argmax(l0[0]), jnp.argmax(l1[0])])))
    assert int(ver["pos"][0]) == len(prompt)           # frontier unmoved
    assert int(seq["pos"][0]) == len(prompt) + 2
    np.testing.assert_allclose(np.asarray(ver["k"]), np.asarray(seq["k"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ver["v"]), np.asarray(seq["v"]),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ config


@pytest.mark.parametrize("field,bad", [("spec_k", 0), ("spec_ngram", 0)])
def test_config_spec_knobs_validated(field, bad):
    with pytest.raises(ValueError, match=field):
        InferenceConfig(**{field: bad})


def test_config_env_resolution(monkeypatch):
    monkeypatch.delenv("DS_TPU_SPEC_DECODE", raising=False)
    assert InferenceConfig().resolved_spec_decode() is False
    monkeypatch.setenv("DS_TPU_SPEC_DECODE", "1")
    assert InferenceConfig().resolved_spec_decode() is True
    # The explicit field always wins over the env.
    assert InferenceConfig(spec_decode=False).resolved_spec_decode() is False
    monkeypatch.setenv("DS_TPU_SPEC_DECODE", "0")
    assert InferenceConfig().resolved_spec_decode() is False


def test_submit_spec_on_nonspec_engine_raises():
    """spec_decode=True cannot be granted post-hoc — the engine's plane
    slack and compiled program were sized without it."""
    cfg, model, params = make_model()
    eng = engine_of(model, params)
    with pytest.raises(ValueError, match="spec_decode"):
        eng.submit(prompts_of(cfg, [5])[0], max_new_tokens=4,
                   spec_decode=True)


def test_plane_slack_floor_covers_verify_and_ring_writes():
    """slack = max(prefill_chunk, spec_k + 1): a verify writes spec_k+1
    k/v positions at a frontier as deep as max_len-1 and the ring takes
    the choices one past it — the plane (and the same-length ring) must
    absorb both without dynamic_update_slice clamping."""
    cfg, model, params = make_model()
    eng = spec_engine(model, params, prefill_chunk=2, spec_k=4, max_len=64)
    assert eng._pool["k"].shape[3] == 64 + 5
    assert eng._pool["toks"].shape == (3, 64 + 5)
    # prefill_chunk above the floor keeps its own slack.
    eng = spec_engine(model, params, prefill_chunk=8, spec_k=4, max_len=64)
    assert eng._pool["k"].shape[3] == 64 + 8
    assert eng._pool["toks"].shape == (3, 64 + 8)
