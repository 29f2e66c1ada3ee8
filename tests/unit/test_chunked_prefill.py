"""Chunked prefill (deepspeed_tpu/inference/ — the fused mixed step).

The contract under test:
1. PARITY — greedy tokens under chunked prefill are bit-identical to
   sequential ``models.generation.generate``, for prompt lengths
   straddling every chunk-boundary case (C-1, C, C+1, multiples,
   remainders).
2. ONE COMPILE — the documented compile-count constant: a mixed-length
   request stream compiles exactly ONE program, ever (the tier-1
   compile-count regression guard).
3. SCHEDULER PHASES — the ``prefilling`` phase walks its cursor by the
   consumed chunk, FIFO among prefilling slots, and cancellation
   mid-prefill frees the slot for the next queued request.
4. SAMPLING FAST PATH — ``_sample_rows`` guards its [R, V] sort and
   categorical draw behind lax.cond; a mixed greedy/top-k batch must
   match the unguarded reference draw-for-draw.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine, Scheduler
from deepspeed_tpu.inference.engine import _sample_rows
from deepspeed_tpu.models.generation import generate
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel


_MODELS = {}


def make_model(seed=0, **kw):
    kw.setdefault("dropout", 0.0)
    kw.setdefault("use_flash_attention", False)
    kw.setdefault("dtype", jnp.float32)  # parity is exercised in f32
    # Memoized: init is deterministic (PRNGKey(0)) and every inference
    # engine treats params as read-only, so one init per config serves
    # the whole module (and the modules importing these helpers).
    key = (seed, tuple(sorted(kw.items(), key=lambda i: i[0])))
    if key not in _MODELS:
        cfg = GPT2Config.tiny(**kw)
        model = GPT2LMHeadModel(cfg)
        ids = np.random.RandomState(seed).randint(0, cfg.vocab_size,
                                                  size=(2, 12))
        params = model.init(jax.random.PRNGKey(0),
                            jnp.asarray(ids))["params"]
        _MODELS[key] = (cfg, model, params)
    return _MODELS[key]


def prompts_of(cfg, lengths, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in lengths]


def seq_greedy(model, params, prompt, max_new):
    out = generate(model, params, np.asarray(prompt)[None], max_new,
                   temperature=0.0)
    return np.asarray(out)[0].tolist()


def engine_of(model, params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk_size", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_queue", 32)
    return InferenceEngine(model, params, config=kw)


# ----------------------------------------------------------------- parity


def test_chunked_parity_across_ragged_lengths():
    """Prompt lengths straddling every chunk-boundary case against
    sequential generate: C-1, C, C+1, an exact multiple, a
    multiple+remainder, and a tiny prompt."""
    cfg, model, params = make_model()
    C = 8
    lens = [C - 1, C, C + 1, 2 * C, 2 * C + 3, 3]
    news = [6, 5, 7, 4, 6, 8]
    ps = prompts_of(cfg, lens)

    eng = engine_of(model, params, prefill_chunk=C)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, news)]
    eng.run()

    for p, n, r in zip(ps, news, reqs):
        assert r.tokens == seq_greedy(model, params, p, n), \
            "chunked tokens diverge from generate at len {}".format(len(p))


def test_prefill_chunk_size_does_not_change_tokens():
    """The chunking is invisible: any prefill_chunk yields the same
    stream (chunk boundaries shift which step writes which k/v, but the
    math — and therefore the greedy argmax — is identical)."""
    cfg, model, params = make_model()
    p = prompts_of(cfg, [13])[0]
    outs = []
    for C in (3, 8, 32):
        eng = engine_of(model, params, prefill_chunk=C)
        r = eng.submit(p, max_new_tokens=7)
        eng.run()
        outs.append(r.tokens)
    assert outs[0] == outs[1] == outs[2]


def test_sampled_stream_independent_of_chunk_boundaries():
    """Sampling rng is named by (seed, position), so a resubmitted
    request reproduces its stream across DIFFERENT prefill_chunk
    settings, not just across runs."""
    cfg, model, params = make_model()
    p = prompts_of(cfg, [17])[0]

    def run(C):
        eng = engine_of(model, params, prefill_chunk=C)
        r = eng.submit(p, max_new_tokens=8, temperature=0.8, top_k=20,
                       seed=5)
        eng.run()
        return r.tokens

    assert run(4) == run(16)


# --------------------------------------------------- compile-count guard


def test_compile_count_regression_guard():
    """Tier-1 regression guard on the documented constant: a canned
    mixed-length stream (short, boundary, long, trickled in while slots
    churn) compiles exactly ONE program. A change to the constant is an
    API-contract change and must update docs/INFERENCE.md."""
    cfg, model, params = make_model()
    lens = [3, 7, 8, 9, 16, 33, 40, 5]
    news = [5, 4, 6, 3, 5, 4, 6, 5]
    ps = prompts_of(cfg, lens)

    eng = engine_of(model, params)  # prefill_chunk=8
    reqs = [eng.submit(ps[i], max_new_tokens=news[i]) for i in range(3)]
    eng.step()
    assert eng.compile_count == 1, \
        "chunked warmup must compile exactly the one mixed-step program"
    for i in range(3, len(ps)):
        reqs.append(eng.submit(ps[i], max_new_tokens=news[i]))
        eng.step()
    eng.run()
    assert eng.compile_count == 1, \
        "prompt-length mix changed the chunked compile count " \
        "(got {})".format(eng.compile_count)
    for r, n in zip(reqs, news):
        assert r.tokens == seq_greedy(model, params, r.prompt, n)


def test_mixed_sampling_params_never_recompile():
    """Per-request temperature/top_k/seed mixes ride traced args through
    the ONE program — including the lax.cond sampling fast path."""
    cfg, model, params = make_model()
    eng = engine_of(model, params)
    ps = prompts_of(cfg, [5, 9, 12, 7])
    eng.submit(ps[0], max_new_tokens=4)
    eng.step()
    assert eng.compile_count == 1
    eng.submit(ps[1], max_new_tokens=4, temperature=0.9, seed=1)
    eng.submit(ps[2], max_new_tokens=4, temperature=0.7, top_k=10, seed=2)
    eng.submit(ps[3], max_new_tokens=4)
    eng.run()
    assert eng.compile_count == 1, \
        "sampling-param mix recompiled the mixed step"


# ------------------------------------------------------- scheduler phases


def test_scheduler_prefill_cursor_and_fifo():
    s = Scheduler(num_slots=2, max_queue=8)
    a = s.submit(np.arange(20, dtype=np.int32), 4, 0.0, 0, -1, 0)
    b = s.submit(np.arange(5, dtype=np.int32), 4, 0.0, 0, -1, 0)
    s.admissions()
    assert a.phase == b.phase == "prefilling"
    assert a.admit_time is not None
    # FIFO among prefilling slots: the older request's chunks go first.
    assert s.next_prefill() is a
    assert s.advance_prefill(a, 8) is False and a.cursor == 8
    assert s.next_prefill() is a            # still mid-prompt, still first
    assert s.advance_prefill(a, 8) is False and a.cursor == 16
    assert s.advance_prefill(a, 4) is True  # prompt exhausted
    assert a.phase == "decoding"
    assert s.next_prefill() is b            # b's turn only now
    assert s.advance_prefill(b, 5) is True
    assert s.next_prefill() is None


def test_scheduler_cancel_mid_prefill_frees_slot_for_queue():
    """Eviction mid-prefill on queue drain: a cancelled half-prefilled
    request frees its slot, the next queued request admits into it, and
    the cancelled request keeps its partial state but is done."""
    s = Scheduler(num_slots=1, max_queue=4)
    a = s.submit(np.arange(20, dtype=np.int32), 4, 0.0, 0, -1, 0)
    c = s.submit(np.arange(3, dtype=np.int32), 4, 0.0, 0, -1, 0)
    s.admissions()
    s.advance_prefill(a, 8)                 # half-way through the prompt
    assert s.cancel(a) is True
    assert a.phase == "cancelled" and a.done and a.slot is None
    assert s.cancel(a) is False             # idempotent: already finished
    pairs = s.admissions()                  # the freed slot re-admits
    assert [(r.rid, slot) for r, slot in pairs] == [(c.rid, 0)]
    assert s.next_prefill() is c


def test_engine_cancel_mid_prefill_and_decoding():
    """Engine-level cancellation: a long prompt cancelled mid-prefill
    frees its slot (the queued request behind it completes with correct
    tokens); a decoding request cancelled between steps stops emitting
    but keeps what it has."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=1, prefill_chunk=4)
    long_p, short_p = prompts_of(cfg, [40, 6])
    a = eng.submit(long_p, max_new_tokens=4)
    b = eng.submit(short_p, max_new_tokens=5)
    eng.step()                              # consumes one 4-token chunk
    assert a.phase == "prefilling" and 0 < a.cursor < len(long_p)
    assert eng.cancel(a) is True and a.done and a.tokens == []
    eng.run()                               # b admits into the freed slot
    assert b.tokens == seq_greedy(model, params, short_p, 5)

    c = eng.submit(short_p, max_new_tokens=30)
    while c.phase != "decoding":
        eng.step()
    eng.step()
    got = list(c.tokens)
    assert 0 < len(got) < 30
    assert eng.cancel(c) is True
    eng.run()                               # engine drains; c stays put
    assert c.tokens == got and c.phase == "cancelled"
    assert c.tokens == seq_greedy(model, params, short_p, 30)[:len(got)]


def test_cancel_edge_cases_boundary_double_and_after_complete():
    """The cancel() contract at its edges: a mid-prefill cancel landing
    on an EXACT chunk boundary (cursor == k * prefill_chunk) frees the
    slot cleanly; a second cancel of the same request is an idempotent
    False; cancelling an already-completed request returns False and
    mutates nothing."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=1, prefill_chunk=4)
    exact, short = prompts_of(cfg, [12, 6])    # 12 = 3 exact chunks
    a = eng.submit(exact, max_new_tokens=4)
    b = eng.submit(short, max_new_tokens=5)
    eng.step()      # two slices dispatched, the second still in flight
    assert a.phase == "prefilling" and a.cursor == 8   # exact boundary
    assert eng.cancel(a) is True
    assert eng.cancel(a) is False              # double-cancel: idempotent
    assert a.phase == "cancelled" and a.slot is None and a.tokens == []
    eng.run()                                  # b admits into the slot
    assert b.phase == "done"
    assert b.tokens == seq_greedy(model, params, short, 5)
    finish = b.finish_time
    assert eng.cancel(b) is False              # cancel-after-complete
    assert b.phase == "done" and b.finish_time == finish


# ------------------------------------------------------ sampling fast path


def test_sample_rows_fast_path_matches_unguarded_reference():
    """The lax.cond-guarded _sample_rows must be draw-for-draw identical
    to the unguarded reference on every mix: all-greedy (the fast path),
    all-sampled, and mixed greedy/top-k rows in one batch."""

    def reference(logits, temp, top_k, seed, position):
        V = logits.shape[-1]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        srt = jnp.sort(logits, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(
            srt, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=1)
        masked = jnp.where((top_k[:, None] > 0) & (logits < kth),
                           jnp.finfo(jnp.float32).min, logits)
        scaled = masked / jnp.maximum(temp, 1e-6)[:, None]
        keys = jax.vmap(lambda s, p: jax.random.fold_in(
            jax.random.PRNGKey(s), p))(seed, position)
        sampled = jax.vmap(jax.random.categorical)(keys, scaled)
        return jnp.where(temp > 0.0, sampled.astype(jnp.int32), greedy)

    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(6, 97).astype(np.float32))
    seed = jnp.asarray(rng.randint(0, 2**31, size=6), jnp.uint32)
    position = jnp.asarray(rng.randint(0, 50, size=6), jnp.int32)
    cases = [
        (jnp.zeros(6, jnp.float32), jnp.zeros(6, jnp.int32)),       # greedy
        (jnp.full(6, 0.8, jnp.float32), jnp.full(6, 10, jnp.int32)),
        (jnp.asarray([0.0, 0.8, 0.0, 1.2, 0.5, 0.0], jnp.float32),  # mixed
         jnp.asarray([0, 10, 0, 0, 25, 7], jnp.int32)),
    ]
    fast = jax.jit(_sample_rows)
    ref = jax.jit(reference)  # jit both: eager-vs-jit rounding must not
    for temp, top_k in cases:  # masquerade as a fast-path divergence
        got = fast(logits, temp, top_k, seed, position)
        want = ref(logits, temp, top_k, seed, position)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
