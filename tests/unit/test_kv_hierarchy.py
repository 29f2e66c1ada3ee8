"""KV memory hierarchy (deepspeed_tpu/inference/kv_hierarchy/).

The contract under test, in order of importance:
1. BIT-IDENTITY — with the shared-prefix cache and host offload on,
   greedy streams are bit-identical to the hierarchy-off engine AND to
   sequential ``models.generation.generate`` across a shared-prefix
   workload, mid-stream swap-out/swap-in, and an injected
   crash-recovery cycle (ISSUE acceptance criterion). int8 KV is
   deliberately NOT bit-identical — its guards live in
   test_decode_attention.py (dequant error bound) and here (the
   spec-decode accept rate must not collapse).
2. ONE COMPILE — all three tiers together on a mixed spec/non-spec
   chunked workload still compile exactly ONE program; hierarchy
   bookkeeping (attach, insert, swap) is eager and never touches the
   traced step.
3. CAPACITY — the byte accounting shows >= 1.8x concurrent sessions at
   a fixed simulated HBM budget with int8 KV + a 50%-reuse prefix
   workload versus the flat fp pool.
4. BACKPRESSURE — ``QueueFull`` distinguishes "HBM slots full but a
   swap would free capacity" (swap_eligible, retry_after_s while a
   swap is in flight) from truly full, and an armed swap request frees
   a slot on the next step.
"""

import numpy as np
import pytest

from deepspeed_tpu.inference import QueueFull
from deepspeed_tpu.inference.faults import Fault, FaultPlan
from deepspeed_tpu.inference.kv_hierarchy import (
    HostSwapStore,
    PrefixStore,
    RadixTrie,
    capture_slot,
    pick_swap_victim,
    restore_slot,
)
from tests.unit.test_chunked_prefill import (
    engine_of,
    make_model,
    prompts_of,
    seq_greedy,
)

# make_model() is memoized per-config; one init serves the module.
_MODEL = {}


def _shared_model():
    if "m" not in _MODEL:
        _MODEL["m"] = make_model()
    return _MODEL["m"]


# Sequential-generate references are the most expensive part of the
# bit-identity tests (an eager forward per token); the bit-identity and
# recovery tests deliberately share one prompt set so each reference is
# computed once for the module.
_REFS = {}


def greedy_ref(model, params, prompt, n):
    key = (tuple(int(t) for t in prompt), int(n))
    if key not in _REFS:
        _REFS[key] = seq_greedy(model, params, prompt, n)
    return _REFS[key]


def hier_engine(model, params, **kw):
    """engine_of with the fp prefix+offload tiers on (bit-identity
    configs leave int8 off; capacity/compile tests switch it on)."""
    kw.setdefault("prefix_cache", True)
    kw.setdefault("host_offload", True)
    kw.setdefault("prefix_slots", 4)
    kw.setdefault("prefix_len", 16)
    kw.setdefault("min_prefix_len", 4)
    kw.setdefault("swap_slots", 8)
    return engine_of(model, params, **kw)


def shared_prefix_prompts(cfg, prefix_len, tails, seed=11):
    """One shared head of ``prefix_len`` tokens + a distinct tail per
    request — the system-prompt traffic shape the prefix cache serves."""
    rng = np.random.RandomState(seed)
    head = rng.randint(0, cfg.vocab_size, size=(prefix_len,))
    return [np.concatenate([head,
                            rng.randint(0, cfg.vocab_size, size=(t,))])
            .astype(np.int32) for t in tails]


# ------------------------------------------------------------ trie/store


def test_radix_trie_deepest_match():
    t = RadixTrie()
    t.insert((1, 2, 3, 4), row=0)
    t.insert((1, 2, 9), row=1)
    # Every node on an inserted path is annotated: a diverging prompt
    # still aliases the longest shared head.
    assert t.lookup((1, 2, 3, 4, 5)) == (0, 4)
    assert t.lookup((1, 2, 3, 7)) == (0, 3)
    assert t.lookup((1, 2, 9, 9)) == (1, 3)
    # Shared nodes: either annotation is a correct alias (same tokens).
    row, depth = t.lookup((1, 2))
    assert depth == 2 and row in (0, 1)
    assert t.lookup((5, 1)) == (None, 0)
    t.rebuild({0: (1, 2, 3, 4)})
    assert t.lookup((1, 2, 9, 9)) == (0, 2)  # row 1's path is gone


def test_prefix_store_lru_eviction_respects_pins():
    s = PrefixStore(2)
    r0 = s.insert((1, 2, 3))
    r1 = s.insert((4, 5, 6))
    assert {r0, r1} == {0, 1}
    s.acquire(r0, rid=100)              # pin row 0
    r2 = s.insert((7, 8, 9))            # must evict the unpinned LRU: r1
    assert r2 == r1 and s.evictions == 1
    assert s.lookup((4, 5, 6)) == (None, 0)
    assert s.lookup((1, 2, 3))[0] == r0  # pinned row survived
    s.acquire(r2, rid=101)
    assert s.insert((9, 9, 9)) is None  # everything pinned: no row
    s.release(100)
    assert s.insert((9, 9, 9)) == r0    # unpinned -> evictable again


def test_host_swap_store_capacity_and_roundtrip():
    st = HostSwapStore(capacity=1)
    assert st.capacity_left()
    st.put(7, {"pos": 3})
    assert not st.capacity_left() and len(st) == 1
    with pytest.raises(RuntimeError):
        st.put(8, {"pos": 4})
    assert st.pop(99) is None
    assert st.pop(7) == {"pos": 3} and st.capacity_left()


def test_pick_swap_victim_blends_idle_age_into_budget():
    """Victim score = residual budget + idle_weight * seconds idle:
    budget order alone decides among equally-fresh sessions, a long-idle
    small-budget session overtakes them, and exact ties break to the
    oldest rid deterministically."""
    import types

    def req(rid, emitted, budget, touch):
        return types.SimpleNamespace(rid=rid, tokens=[0] * emitted,
                                     max_new_tokens=emitted + budget,
                                     last_touch=touch)

    now = 1000.0
    assert pick_swap_victim([]) is None
    # Equal last_touch: the largest residual budget is the victim.
    fresh = [req(0, 2, 30, now), req(1, 2, 8, now), req(2, 2, 19, now)]
    assert pick_swap_victim(fresh, now=now).rid == 0
    # A stalled small-budget session wins once idle_weight * age
    # dominates: 8 + 32 * 2.0 = 72 > 30.
    stale = [req(0, 2, 30, now), req(1, 2, 8, now - 2.0)]
    assert pick_swap_victim(stale, now=now).rid == 1
    # ...but not for a sub-threshold stall: 8 + 32 * 0.5 = 24 < 30.
    warm = [req(0, 2, 30, now), req(1, 2, 8, now - 0.5)]
    assert pick_swap_victim(warm, now=now).rid == 0
    # Exact score tie: the oldest rid is the deterministic victim, and
    # a missing last_touch stamp scores age 0 (budget-only).
    tied = [req(5, 0, 12, now), req(3, 0, 12, now),
            types.SimpleNamespace(rid=9, tokens=[], max_new_tokens=12)]
    assert pick_swap_victim(tied, now=now).rid == 3


# ---------------------------------------------------------- bit-identity


# One prompt set serves both bit-identity tests below: greedy_ref()
# computes each sequential-generate reference exactly once.
_BI_TAILS = [3, 5, 7, 4, 6, 2]
_BI_NEWS = [6, 5, 7, 4, 6, 5]


def test_prefix_offload_bit_identity_with_mid_stream_swaps():
    """Six shared-prefix requests on three slots with offload on: swaps
    fire mid-stream, the prefix cache aliases the shared head, and every
    greedy stream is bit-identical to the hierarchy-off engine and to
    sequential generate — at ONE compiled program."""
    cfg, model, params = _shared_model()
    ps = shared_prefix_prompts(cfg, prefix_len=10, tails=_BI_TAILS)

    eng = hier_engine(model, params, max_slots=3)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, _BI_NEWS)]
    eng.run()

    flat = engine_of(model, params, max_slots=3)
    freqs = [flat.submit(p, max_new_tokens=n)
             for p, n in zip(ps, _BI_NEWS)]
    flat.run()

    for p, n, r, fr in zip(ps, _BI_NEWS, reqs, freqs):
        want = greedy_ref(model, params, p, n)
        assert r.tokens == want, "hierarchy stream diverged from generate"
        assert r.tokens == fr.tokens, "hierarchy-on != hierarchy-off"

    m = eng.metrics()
    assert m["prefix_hits"] >= 1 and m["prefix_inserts"] >= 1
    assert m["swap_outs"] >= 1 and m["swap_ins"] >= 1, \
        "no swap fired: the test must exercise mid-stream offload"
    assert m["compile_count"] == 1 and m["recompiles"] == 0
    assert flat.metrics()["compile_count"] == 1


def test_recovery_replays_swapped_sessions_bit_identically():
    """A fatal step fault while sessions sit SWAPPED OUT: recovery
    rebuilds the pool, drops the (disposable) hierarchy state, and
    replays everything — including the swapped sessions — to the exact
    fault-free tokens, without recompiling. The pre-fault drive also
    pins the capture/restore roundtrip on the live pool (byte equality
    for the captured slot AND its neighbors)."""
    cfg, model, params = _shared_model()
    ps = shared_prefix_prompts(cfg, prefix_len=10, tails=_BI_TAILS)

    eng = hier_engine(model, params, max_slots=2, fault_injection=True)
    got = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, _BI_NEWS)]
    # Drive until a session is actually swapped out, so the fault lands
    # on a state where host RAM holds live planes.
    while not eng._scheduler.swapped:
        eng.step()

    before = {k: np.asarray(v) for k, v in eng._pool.items()}
    rec = capture_slot(eng._pool, 0)
    # Scribble over a COPY of slot 0, restore, and demand byte equality
    # — for slot 0 AND its neighbor (restore must not disturb others).
    # The engine's own pool is untouched; the run continues below.
    pool = dict(eng._pool)
    pool["k"] = pool["k"].at[:, 0].set(0)
    pool["pos"] = pool["pos"].at[0].set(0)
    pool = restore_slot(pool, 0, rec)
    for name, want in before.items():
        scratch = np.asarray(pool[name])
        assert scratch.dtype == want.dtype
        np.testing.assert_array_equal(scratch, want, err_msg=name)

    eng.inject_faults(FaultPlan(faults=(Fault("raise", step=0),)))
    eng.run()

    assert all(r.phase == "done" for r in got)
    for p, n, r in zip(ps, _BI_NEWS, got):
        assert r.tokens == greedy_ref(model, params, p, n)
    assert len(eng.recovery_log) == 1
    assert eng.compile_count == 1
    m = eng.metrics()
    assert m["recoveries"] == 1 and m["swap_outs"] >= 1


# ----------------------------------------------------------- one compile


def test_all_three_tiers_mixed_spec_nonspec_one_compile():
    """The tier-1 smoke from the ISSUE: int8 + prefix cache + host
    offload together, on a mixed spec/non-spec chunked workload with a
    50%-reuse shared system prompt. Contracts on one engine run (int8
    waives bit-identity), each a count or a ratio of shapes:
    - ONE compiled program, zero recompiles, every request done;
    - every tier engaged: prefix hits, swap-outs and swap-ins all
      counted;
    - the verify lane scores through quantized planes and still accepts
      drafts (corrupted scores would accept none; the speculating
      requests decode long enough for the tiny model's loops to form,
      so the count does not hang on one lucky draw);
    - the ISSUE capacity criterion: >= 1.8x concurrent sessions at a
      fixed simulated HBM budget (the flat fp pool's own footprint)."""
    cfg, model, params = _shared_model()
    eng = hier_engine(model, params, max_slots=2, int8_kv=True,
                      spec_decode=True, spec_k=2, spec_ngram=2)
    rng = np.random.RandomState(7)
    head = rng.randint(0, cfg.vocab_size, size=(8,))
    reqs = []
    for i in range(6):
        # Half share a head (prefix hits), half tile their own phrase
        # (drafter matches); alternate the speculation flag per request.
        if i % 2 == 0:
            p = np.concatenate([
                head, rng.randint(0, cfg.vocab_size, size=(3 + i,))])
        else:
            p = np.tile(rng.randint(0, cfg.vocab_size, size=(4,)), 4)
        reqs.append(eng.submit(p.astype(np.int32),
                               max_new_tokens=5 + i + (16 if i % 2 else 0),
                               spec_decode=bool(i % 2)))
    eng.run()
    assert all(r.phase == "done" for r in reqs)
    assert all(len(r.tokens) >= 1 for r in reqs)
    m = eng.metrics()
    assert m["int8_kv"] and m["prefix_cache"] and m["host_offload"]
    assert m["compile_count"] == 1 and m["recompiles"] == 0
    assert m["prefix_hits"] >= 1
    assert m["swap_outs"] >= 1 and m["swap_ins"] == m["swap_outs"]
    # Occupied slot-steps that emitted more than the bonus token.
    assert int(eng._accept_hist[2:].sum()) > 0, \
        "int8 KV collapsed speculative acceptance: {}".format(
            eng._accept_hist)

    h = eng._hier
    budget = h.flat_bytes_per_slot() * eng.config.max_slots
    ratio = h.effective_slots(budget) / eng.config.max_slots
    assert h.bytes_per_slot() < h.flat_bytes_per_slot()
    assert ratio >= 1.8, \
        "effective/flat slots {} < 1.8 (per-slot {} vs flat {}, mean " \
        "aliased {})".format(ratio, h.bytes_per_slot(),
                             h.flat_bytes_per_slot(),
                             h.mean_aliased_bytes())
    assert m["effective_slots"] >= 1
    assert m["kv_bytes_per_slot"] < m["kv_bytes_per_slot_flat"]


def test_paged_flash_int8_prefix_streams_equal_the_dense_pools():
    """The int8 tier over a paged pool whose page is a kernel block
    (128): codes AND scales are appended in place by ``kv_append`` and
    read by the layer-indexed q8 paged kernel, and a shared prefix puts
    the aliasers' first chunk at a MID-PAGE frontier of a copy-on-write
    page. Pinned stream for stream against the dense int8 pool under the
    dense q8 kernel (int8 waives identity with fp, not with itself)."""
    cfg, model, params = make_model(n_positions=256)
    prompts = shared_prefix_prompts(cfg, 12, [4, 125, 6])

    def serve(**extra):
        eng = engine_of(model, params, max_slots=2, max_len=192,
                        prefill_chunk=64, use_flash_decode=True,
                        int8_kv=True, prefix_cache=True, prefix_slots=2,
                        prefix_len=16, min_prefix_len=4, **extra)
        first = eng.submit(prompts[0], max_new_tokens=6)
        eng.run()                       # publish the prefix
        rest = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
        eng.run()
        return eng, [r.tokens for r in (first,) + tuple(rest)]

    dense, want = serve()
    paged, got = serve(paged_kv=True, kv_page_len=128)
    assert paged._pool["k"].dtype == np.int8
    assert paged._pool["k_scale"].shape[3] == 128
    assert got == want, "paged + flash int8 streams diverged from dense"
    assert paged.compile_count == dense.compile_count == 1
    assert paged.metrics()["prefix_hits"] == dense.metrics()["prefix_hits"] \
        and paged.metrics()["prefix_hits"] >= 1


# ---------------------------------------------------------- backpressure


def test_queue_full_swap_eligible_and_retry_after():
    """QueueFull taxonomy: with offload on and a decoding victim, a full
    queue reports swap_eligible (arming a swap); a second rejection
    while the swap is in flight carries retry_after_s; the armed swap
    frees the slot on the next step and the stream completes exactly."""
    cfg, model, params = _shared_model()
    eng = hier_engine(model, params, max_slots=1, max_queue=1)
    ps = prompts_of(cfg, [8, 7, 6], seed=9)
    r0 = eng.submit(ps[0], max_new_tokens=8)
    while r0.phase != "decoding":
        eng.step()
    r1 = eng.submit(ps[1], max_new_tokens=4)   # fills the queue
    with pytest.raises(QueueFull) as e1:
        eng.submit(ps[2], max_new_tokens=4)
    assert e1.value.swap_eligible is True
    assert e1.value.retry_after_s is None      # no swap in flight yet
    with pytest.raises(QueueFull) as e2:
        eng.submit(ps[2], max_new_tokens=4)
    assert e2.value.swap_eligible is True
    assert e2.value.retry_after_s is not None  # armed swap in flight
    assert e2.value.retry_after_s > 0

    eng.step()                                 # armed swap fires
    assert r0.phase == "swapped"
    # The freed slot went to the queue head THIS step (r1 is either
    # mid-flight in it or already finished through it).
    assert r1.slot is not None or r1.phase == "done"
    r2 = eng.submit(ps[2], max_new_tokens=4)   # queue has room again
    eng.run()
    for r, p in zip((r0, r1, r2), ps):
        assert r.tokens == greedy_ref(model, params, p,
                                      r.max_new_tokens)


def test_queue_full_without_offload_is_not_swap_eligible():
    cfg, model, params = _shared_model()
    eng = engine_of(model, params, max_slots=1, max_queue=1)
    ps = prompts_of(cfg, [8, 7, 6], seed=9)
    r0 = eng.submit(ps[0], max_new_tokens=8)
    while r0.phase == "queued":
        eng.step()                  # admit r0 so it holds the one slot
    eng.submit(ps[1], max_new_tokens=4)
    with pytest.raises(QueueFull) as e:
        eng.submit(ps[2], max_new_tokens=4)
    assert e.value.swap_eligible is False
    assert e.value.retry_after_s is None
