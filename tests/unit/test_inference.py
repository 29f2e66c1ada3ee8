"""Continuous-batching serving engine (deepspeed_tpu/inference/).

The contract under test, in order of importance:
1. GREEDY PARITY — tokens out of the slotted engine are identical to
   sequential ``models.generation.generate`` calls, whatever the
   admission order or slot placement (ISSUE acceptance criterion).
2. BOUNDED COMPILATION — after warmup (ONE mixed-step program), a
   changing request mix causes ZERO recompiles, asserted on the
   engines' jit cache-miss counters. (tests/unit/test_chunked_prefill.py
   holds the compile-count regression guard.)
3. SCHEDULING — FIFO admission at chunk boundaries only, eviction on
   EOS/budget, QueueFull backpressure.
4. TP SERVING — the same engine over a 'model'-axis mesh shards params
   and the KV pool and still matches the unsharded tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from deepspeed_tpu.inference import (
    InferenceConfig,
    InferenceEngine,
    QueueFull,
    Scheduler,
)
from deepspeed_tpu.models.generation import generate
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.parallel import mesh as mesh_lib


_MODELS = {}


def make_model(seed=0, **kw):
    kw.setdefault("dropout", 0.0)
    kw.setdefault("use_flash_attention", False)
    # f32: bf16 rounding differs across program boundaries (prefill vs
    # generate's fused loop), which flips greedy argmax near-ties and
    # would make exact token parity a coin toss.
    kw.setdefault("dtype", jnp.float32)
    # Memoized: init is deterministic (PRNGKey(0)) and every inference
    # engine treats params as read-only, so one init per config serves
    # the whole module.
    key = (seed, tuple(sorted(kw.items(), key=lambda i: i[0])))
    if key not in _MODELS:
        cfg = GPT2Config.tiny(**kw)
        model = GPT2LMHeadModel(cfg)
        ids = np.random.RandomState(seed).randint(0, cfg.vocab_size,
                                                  size=(2, 12))
        params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                     jnp.asarray(ids))["params"]
        _MODELS[key] = (cfg, model, params)
    return _MODELS[key]


def prompts_of(cfg, lengths, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in lengths]


def seq_greedy(model, params, prompt, max_new):
    """Sequential single-request reference: generate's greedy row."""
    out = generate(model, params, np.asarray(prompt)[None], max_new,
                   temperature=0.0)
    return np.asarray(out)[0].tolist()


# ------------------------------------------------------------- scheduler


def test_scheduler_fifo_admission_and_eviction():
    s = Scheduler(num_slots=2, max_queue=8)
    reqs = [s.submit(np.array([i]), 4, 0.0, 0, -1, 0) for i in range(4)]
    # Admission fills free slots FIFO; the rest stay queued.
    pairs = s.admissions()
    assert [(r.rid, slot) for r, slot in pairs] == [(0, 0), (1, 1)]
    assert [r.rid for r in s.queue] == [2, 3]
    assert s.admissions() == []  # no free slots mid-flight
    # Evicting slot 0 frees exactly that slot for the next queued request.
    s.complete(reqs[0])
    assert reqs[0].done and reqs[0].slot is None
    pairs = s.admissions()
    assert [(r.rid, slot) for r, slot in pairs] == [(2, 0)]
    assert s.occupancy() == 1.0
    for req in list(s.running.values()):
        s.complete(req)
    assert not s.idle  # rid 3 still queued
    pairs = s.admissions()
    assert [r.rid for r, _ in pairs] == [3]
    s.complete(pairs[0][0])
    assert s.idle


def test_scheduler_backpressure():
    s = Scheduler(num_slots=1, max_queue=2)
    s.submit(np.array([1]), 1, 0.0, 0, -1, 0)
    s.submit(np.array([2]), 1, 0.0, 0, -1, 0)
    with pytest.raises(QueueFull):
        s.submit(np.array([3]), 1, 0.0, 0, -1, 0)
    # Draining the queue (admission) reopens submission.
    s.admissions()
    s.submit(np.array([3]), 1, 0.0, 0, -1, 0)


# ---------------------------------------------------------------- config


def test_inference_config_unknown_keys_and_position_budget():
    with pytest.raises(ValueError, match="max_slot"):
        InferenceConfig.from_dict({"max_slot": 4})  # typo must be loud
    with pytest.raises(ValueError, match="prefix_len"):
        InferenceConfig(max_len=64, prefix_len=128)
    with pytest.raises(ValueError, match="n_positions"):
        InferenceConfig(max_len=512).validate_against_model(128)


@pytest.mark.parametrize("key", ["chunked_prefill", "prefill_buckets",
                                 "expert_parallel", "sparse_decode"])
def test_removed_inference_keys_are_rejected_by_name(key):
    """The keys that chose the legacy prefill step and the stand-in
    adapter's policies are gone: a config that still carries one fails
    loudly, naming it, through the constructor and the ds_config block
    alike — never served with the key silently ignored."""
    with pytest.raises(TypeError, match=key):
        InferenceConfig(**{key: True})
    with pytest.raises(ValueError, match=key):
        InferenceConfig.from_dict({key: True})
    with pytest.raises(ValueError, match=key):
        deepspeed.DeepSpeedConfig(None, param_dict={
            "train_batch_size": 8, "inference": {key: True}})


def test_ds_config_inference_block_parses():
    ds = deepspeed.DeepSpeedConfig(None, param_dict={
        "train_batch_size": 8,
        "inference": {"max_slots": 2, "chunk_size": 4},
    })
    assert ds.inference["max_slots"] == 2
    assert ds.inference["max_len"] == 512  # default merged in
    with pytest.raises(ValueError, match="max_slot"):
        deepspeed.DeepSpeedConfig(None, param_dict={
            "train_batch_size": 8, "inference": {"max_slot": 2}})
    with pytest.raises(TypeError):
        deepspeed.DeepSpeedConfig(None, param_dict={
            "train_batch_size": 8, "inference": "fast"})


# ---------------------------------------------------------------- engine


def engine_of(model, params, mesh=None, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk_size", 4)
    return InferenceEngine(model, params, config=kw, mesh=mesh)


def test_single_request_greedy_parity():
    cfg, model, params = make_model()
    eng = engine_of(model, params)
    req = eng.submit(prompts_of(cfg, [7])[0], max_new_tokens=9)
    eng.run()
    assert req.tokens == seq_greedy(model, params, req.prompt, 9)
    assert req.first_token_time is not None and req.done


def test_staggered_stream_parity_and_zero_recompiles():
    """The acceptance criterion in one test: mixed prompt lengths arrive
    over time, slots churn, and after warmup (first prefill + first
    chunk) the compile count NEVER moves again — while every request's
    tokens stay identical to its sequential generate."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=3)
    lens = [5, 9, 3, 12, 7, 4, 10, 6]
    news = [6, 3, 9, 5, 7, 4, 8, 6]
    ps = prompts_of(cfg, lens)
    reqs = [eng.submit(ps[i], max_new_tokens=news[i]) for i in range(3)]
    eng.step()  # warmup: the one mixed step (chunked prefill default)
    warm = eng.compile_count
    assert warm == 1, "expected the single mixed-step program, got " \
        "{}".format(warm)
    # Trickle in the rest while earlier requests are mid-flight.
    for i in range(3, len(ps)):
        reqs.append(eng.submit(ps[i], max_new_tokens=news[i]))
        eng.step()
    eng.run()
    assert eng.compile_count == warm, \
        "request churn recompiled a program (cache misses: {} -> {})" \
        .format(warm, eng.compile_count)
    for req, n in zip(reqs, news):
        assert req.tokens == seq_greedy(model, params, req.prompt, n), \
            "slot-served tokens diverge from sequential generate"
    m = eng.metrics()
    assert m["requests_completed"] == len(ps)
    assert m["tokens_out"] == sum(news)
    assert 0.0 < m["slot_occupancy"] <= 1.0
    assert m["queue_depth"] == 0 and m["running"] == 0


def test_metrics_reads_live_gauges_and_engine_idle():
    """metrics() instantaneous keys come from the registry's live
    gauges — one source of truth with the Prometheus export — and the
    public engine.idle mirrors the scheduler (the sustained-load runner
    polls it instead of reaching into _scheduler)."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=3, max_queue=8)
    assert eng.idle
    ps = prompts_of(cfg, [5, 6, 7, 8, 9])
    for p in ps:
        # Budget long enough that nothing completes within the first
        # mixed steps (prefill emits 1 + one decode chunk).
        eng.submit(p, max_new_tokens=20)
    assert not eng.idle
    m = eng.metrics()
    # 5 submitted, 0 admitted yet: all queued, nothing prefilling.
    assert m["queue_depth"] == 5
    assert m["slot_occupancy_now"] == 0.0 and m["slots_prefilling"] == 0
    # Admits into all three slots; the call finds nothing in flight, so it
    # dispatches TWO mixed steps (one is kept in flight) and harvests one.
    eng.step()
    m = eng.metrics()
    assert m["queue_depth"] == 2 and m["slot_occupancy_now"] == 1.0
    # One prefill lane per step: the third admitted request is still
    # mid-prefill — visible on the live gauge.
    assert m["slots_prefilling"] == 1
    # The dict view and the Prometheus text can never disagree.
    assert 'queue_depth{engine="inference"} 2' in eng.prometheus()
    eng.run()
    assert eng.idle
    m = eng.metrics()
    assert m["queue_depth"] == 0 and m["slot_occupancy_now"] == 0.0
    assert m["slots_prefilling"] == 0


def test_queue_wait_stamped_at_admission():
    """Every request admits through Scheduler.admissions(), so
    queue_wait_seconds is populated with one observation per request —
    the windowed queue-wait curve is comparable across configs."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=2)
    ps = prompts_of(cfg, [5, 6, 7, 8, 9], seed=6)
    reqs = [eng.submit(p, max_new_tokens=2) for p in ps]
    eng.run()
    assert all(r.admit_time is not None and
               r.admit_time >= r.submit_time for r in reqs)
    hist = eng.telemetry.histogram("queue_wait_seconds")
    assert hist.count == len(ps)
    assert eng.metrics()["queue_wait_p99_ms"] is not None


def test_eos_evicts_and_frees_slot():
    """A request whose greedy continuation hits EOS stops there, frees
    its slot for the queue, and reports only the tokens up to and
    including EOS."""
    cfg, model, params = make_model()
    p = prompts_of(cfg, [6])[0]
    full = seq_greedy(model, params, p, 12)
    eos = full[4]  # force an early stop on a token we know gets emitted
    eng = engine_of(model, params, max_slots=1)
    r1 = eng.submit(p, max_new_tokens=12, eos_token_id=eos)
    r2 = eng.submit(prompts_of(cfg, [5], seed=9)[0], max_new_tokens=3)
    eng.run()
    assert r1.tokens == full[:5]  # truncated at first EOS emission
    assert r2.done  # the freed slot served the queued request
    assert r2.tokens == seq_greedy(model, params, r2.prompt, 3)


def test_mixed_max_new_tokens_budgets():
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=4, chunk_size=3)
    ps = prompts_of(cfg, [4, 4, 4, 4], seed=11)
    news = [1, 2, 5, 11]
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, news)]
    eng.run()
    for req, p, n in zip(reqs, ps, news):
        assert len(req.tokens) == n
        assert req.tokens == seq_greedy(model, params, p, n)


def test_submit_validation_and_backpressure():
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=1, max_queue=2)
    with pytest.raises(ValueError, match="empty"):
        eng.submit([])
    # Only max_len bounds a prompt.
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(prompts_of(cfg, [10])[0], max_new_tokens=60)
    eng.submit(prompts_of(cfg, [17])[0], max_new_tokens=2)  # fine here
    eng.submit(prompts_of(cfg, [4])[0], max_new_tokens=2)
    with pytest.raises(QueueFull):
        eng.submit(prompts_of(cfg, [4])[0], max_new_tokens=2)


def test_sampled_decode_is_deterministic_per_seed():
    """Sampling determinism: same (seed, position) -> same draw, so a
    resubmitted request reproduces its stream; a different seed moves it."""
    cfg, model, params = make_model()
    p = prompts_of(cfg, [6])[0]
    eng = engine_of(model, params)  # one engine: resubmission IS the claim

    def run(seed):
        r = eng.submit(p, max_new_tokens=8, temperature=0.9, top_k=50,
                       seed=seed)
        eng.run()
        return r.tokens

    first = run(1)
    assert run(1) == first
    assert run(2) != first  # vanishing collision odds over 8 draws


def test_init_inference_facade():
    cfg, model, params = make_model()
    eng = deepspeed.init_inference(
        model=model, params=params,
        config={"train_batch_size": 8,
                "inference": {"max_slots": 2, "max_len": 64,
                              "chunk_size": 4}})
    assert isinstance(eng, InferenceEngine)
    assert eng.config.max_slots == 2
    out = eng.generate(prompts_of(cfg, [5]), max_new_tokens=4)
    assert out[0] == seq_greedy(model, params, prompts_of(cfg, [5])[0], 4)


# ---------------------------------------------------------- flash decode


def test_flash_decode_engine_token_parity_and_zero_recompiles():
    """Engine with the Pallas decode kernel engaged (interpret mode on
    CPU): the pool plane pads to the kernel's 128 quantum, every
    request's greedy tokens stay identical to sequential generate on the
    einsum path, and the compile count is frozen after warmup."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, use_flash_decode=True, max_slots=3)
    assert eng.metrics()["flash_decode"] is True
    # config max_len=64 + prefill_chunk=32 slack -> padded to the quantum.
    assert eng._pool["k"].shape[3] == 128
    lens = [5, 9, 3, 12]
    news = [6, 3, 7, 5]
    ps = prompts_of(cfg, lens)
    reqs = [eng.submit(ps[i], max_new_tokens=news[i]) for i in range(2)]
    eng.step()  # warmup: the one mixed step
    warm = eng.compile_count
    assert warm == 1
    for i in range(2, len(ps)):
        reqs.append(eng.submit(ps[i], max_new_tokens=news[i]))
        eng.step()
    eng.run()
    assert eng.compile_count == warm, \
        "flash-decode serving recompiled after warmup ({} -> {})".format(
            warm, eng.compile_count)
    for req, n in zip(reqs, news):
        assert req.tokens == seq_greedy(model, params, req.prompt, n), \
            "flash-decode tokens diverge from the einsum path"
    assert eng.metrics()["max_active_frontier"] == 0  # all slots drained


def test_flash_decode_flag_resolution():
    """config.use_flash_decode=None defers to the backend default (off
    on CPU -> no pool padding); False forces it off even under the env
    override."""
    cfg, model, params = make_model()
    eng = engine_of(model, params)  # None -> CPU default: off
    assert eng.metrics()["flash_decode"] is False
    # Einsum path: no quantum padding, just max_len=64 + the
    # prefill_chunk=32 append slack.
    assert eng._pool["k"].shape[3] == 96
    eng = engine_of(model, params, use_flash_decode=False)
    assert eng.metrics()["flash_decode"] is False


# ------------------------------------------------------------- tensor parallel


@pytest.mark.parametrize("flash_decode", [False, True])
def test_tensor_sharded_serving_matches_unsharded(eight_devices,
                                                  flash_decode):
    """Serving over a mesh with a 'model' axis: params shard by the TP
    rules, the KV pool shards its heads dim, and the tokens match the
    unsharded engine exactly — on the einsum path, and with the decode
    kernel launched shard-local over the mesh (kernels_on_mesh)."""
    cfg, model, params = make_model()  # tiny: n_head=4, divisible by mp
    mesh = mesh_lib.build_mesh(devices=jax.devices()[:4], num_mp=4,
                               num_dp=1)
    ps = prompts_of(cfg, [5, 9, 3])
    base = engine_of(model, params, use_flash_decode=flash_decode)
    want = [base.submit(p, max_new_tokens=6) for p in ps]
    base.run()

    eng = engine_of(model, params, mesh=mesh, use_flash_decode=flash_decode)
    assert eng.metrics()["flash_decode"] is flash_decode
    got = [eng.submit(p, max_new_tokens=6) for p in ps]
    eng.run()
    for w, g in zip(want, got):
        assert g.tokens == w.tokens
    # The pool's k/v really are head-sharded over 'model'.
    spec = eng._pool["k"].sharding.spec
    assert spec[2] == mesh_lib.MODEL_AXIS
    assert eng.compile_count == 1  # the one mixed-step program
