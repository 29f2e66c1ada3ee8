"""Sustained-load harness (deepspeed_tpu/loadgen/ + telemetry/timeseries).

The contract under test:
1. DETERMINISM — a WorkloadSpec produces byte-identical request streams
   per seed (arrivals, token ids, budgets); different seeds differ.
   Without this, no two sustained runs are comparable.
2. TIME-SERIES — the collector closes windows on cadence, holds bounded
   memory (ring + exact dropped count), reports per-window counter
   DELTAS, and exports schema-valid Chrome counter events.
3. OPEN LOOP — the runner submits on the schedule, records QueueFull
   sheds as samples (signal, not error), and drains to completion.
4. GATE — the noise-aware regression gate passes an A/A (identical
   reports) and FAILS an injected 2x TTFT slowdown and a throughput
   drop, in the regression direction only (improvements never flag).
5. END TO END — a run, a saturation sweep and the gate on one warm
   engine produce the promised report schema: >= 3 windows carrying
   TTFT/ITL percentiles, queue depth, slot occupancy; a non-null max
   sustainable rate; a passing A/A self-check.
6. CHAOS — the runner arms a FaultPlan mid-run, the engine recovers,
   and the report's ``chaos`` section shows requests_lost == 0 with a
   finite recovery time and the SLO attainment split during/outside
   recovery; the rebuild keeps the one compiled program and the
   interrupted request's autopsy reads lost-then-replayed
   (tests/unit/test_resilience.py owns the bit-identity half of the
   recovery invariant).
"""

import copy
import json

import numpy as np
import pytest

from deepspeed_tpu.loadgen import (
    SLO,
    SustainedRunner,
    WorkloadSpec,
    build_report,
    evaluate,
    regression_gate,
    replay_trace,
    saturation_sweep,
    save_trace,
)
from deepspeed_tpu.telemetry import MetricsRegistry, TimeseriesCollector
from tests.unit.test_chunked_prefill import engine_of, make_model

# ---------------------------------------------------------------- workload


def _spec(**kw):
    kw.setdefault("vocab_size", 1024)
    kw.setdefault("n_requests", 16)
    kw.setdefault("prompt_mean", 8)
    kw.setdefault("prompt_max", 16)
    kw.setdefault("output_mean", 6)
    kw.setdefault("output_max", 12)
    return WorkloadSpec(**kw)


def test_workload_deterministic_per_seed():
    a = _spec(seed=7).requests()
    b = _spec(seed=7).requests()
    assert len(a) == 16
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new_tokens == y.max_new_tokens
        assert x.seed == y.seed
    c = _spec(seed=8).requests()
    assert any(x.arrival_s != y.arrival_s for x, y in zip(a, c))
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))


def test_workload_shapes_and_bounds():
    # Burst: groups of burst_size sharing one arrival instant.
    bs = _spec(arrival="burst", n_requests=12, burst_size=4,
               burst_gap_s=0.5).requests()
    assert [r.arrival_s for r in bs[:5]] == [0.0, 0.0, 0.0, 0.0, 0.5]
    # Ramp: early inter-arrival gaps are larger than late ones on
    # average (intensity ramps ramp_from -> rate).
    rp = _spec(arrival="ramp", rate=50.0, ramp_from=1.0,
               n_requests=60).requests()
    gaps = np.diff([r.arrival_s for r in rp])
    assert gaps[:15].mean() > gaps[-15:].mean()
    # Every stream respects the length bounds and the vocab.
    for spec in (_spec(prompt_dist="zipf"), _spec(output_dist="fixed"),
                 _spec(phrase_len=0)):
        for r in spec.requests():
            assert 1 <= r.prompt.size <= 16
            assert 1 <= r.max_new_tokens <= 12
            assert r.prompt.dtype == np.int32
            assert int(r.prompt.max()) < 1024
    # Phrase tiling repeats: a prompt longer than phrase_len contains
    # its own prefix again (what the n-gram drafter matches on).
    long = [r for r in _spec(phrase_len=4, prompt_dist="fixed",
                             prompt_mean=12).requests()]
    assert all(np.array_equal(r.prompt[:4], r.prompt[4:8]) for r in long)


def test_workload_validation():
    with pytest.raises(ValueError):
        _spec(arrival="uniform")
    with pytest.raises(ValueError):
        _spec(rate=0.0)
    with pytest.raises(ValueError):
        _spec(arrival="trace")          # no trace_path
    with pytest.raises(ValueError):
        _spec(prompt_dist="cauchy")
    with pytest.raises(ValueError):
        _spec(prefix_pool=-1)
    with pytest.raises(ValueError):
        _spec(prefix_pool=2, prefix_tokens=0)
    with pytest.raises(ValueError):
        _spec(prefix_pool=2, prefix_zipf_a=1.0)


def test_workload_prefix_pool_zipf_reuse():
    """The shared system-prompt pool: every prompt starts with one of
    ``prefix_pool`` fixed heads, Zipf-skewed so a few dominate — and the
    stream stays deterministic per seed, including the pool draws."""
    kw = dict(seed=7, n_requests=32, prefix_pool=3, prefix_tokens=6,
              prompt_dist="fixed", prompt_mean=12)
    a, b = _spec(**kw).requests(), _spec(**kw).requests()
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.seed == y.seed
    heads = [tuple(r.prompt[:6]) for r in a]
    pool = sorted(set(heads))
    assert 1 <= len(pool) <= 3              # every head from the pool
    counts = sorted((heads.count(h) for h in pool), reverse=True)
    assert counts[0] > len(a) // 3          # Zipf skew: one head dominates
    # Prompt length: the shared head REPLACES the first prefix_tokens of
    # the drawn length (total length unchanged when it exceeds the head,
    # floored at the head length otherwise).
    assert all(r.prompt.size == 12 for r in a)
    short = _spec(seed=7, n_requests=8, prefix_pool=2, prefix_tokens=10,
                  prompt_dist="fixed", prompt_mean=4,
                  prompt_min=4).requests()
    assert all(r.prompt.size == 10 for r in short)


def test_workload_prefix_pool_off_is_legacy_stream():
    """prefix_pool=0 must consume the RandomState exactly as specs
    written before the knob existed: the new pool draws come after every
    legacy draw, so the legacy stream is byte-identical."""
    legacy = _spec(seed=7).requests()
    off = _spec(seed=7, prefix_pool=0, prefix_tokens=99,
                prefix_zipf_a=3.0).requests()
    for x, y in zip(legacy, off):
        assert x.arrival_s == y.arrival_s
        assert np.array_equal(x.prompt, y.prompt)
        assert x.seed == y.seed


def test_workload_template_heavy_preset():
    """The ``template_heavy`` preset is template-dominated by
    construction: every prompt opens with one of a SMALL pool of long
    shared heads, the Zipf skew makes the top template carry the most
    mass, and same-seeded calls stay byte-identical. Overrides pass
    straight through (how tests shrink it to tiny-engine geometry)."""
    a = WorkloadSpec.template_heavy(seed=9).requests()
    b = WorkloadSpec.template_heavy(seed=9).requests()
    assert len(a) == 64
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s
        assert np.array_equal(x.prompt, y.prompt)
        assert x.seed == y.seed
    heads = [tuple(r.prompt[:48]) for r in a]
    pool = sorted(set(heads))
    assert 1 <= len(pool) <= 4               # every head from the pool
    counts = sorted((heads.count(h) for h in pool), reverse=True)
    assert counts[0] >= len(a) // 4          # Zipf: one template dominates
    assert all(50 <= r.prompt.size <= 96 for r in a)
    assert all(4 <= r.max_new_tokens <= 32 for r in a)
    # Overrides shrink the geometry without losing the template shape.
    small = WorkloadSpec.template_heavy(
        seed=9, n_requests=8, prefix_pool=2, prefix_tokens=6,
        prompt_mean=12, prompt_min=10, prompt_max=20,
        output_max=6).requests()
    assert len(small) == 8
    assert len({tuple(r.prompt[:6]) for r in small}) <= 2
    assert all(10 <= r.prompt.size <= 20 for r in small)


def test_workload_long_context_preset():
    """The ``long_context`` preset is heavy-tailed by construction: the
    lognormal body sits in the thousands of tokens and the right tail
    reaches past 32k (the regime block-sparse decode + host offload
    serve). Same-seeded calls stay byte-identical; overrides shrink the
    geometry for tiny engines."""
    a = WorkloadSpec.long_context(seed=3).requests()
    b = WorkloadSpec.long_context(seed=3).requests()
    assert len(a) == 32
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s
        assert np.array_equal(x.prompt, y.prompt)
    sizes = sorted(r.prompt.size for r in a)
    assert all(512 <= s <= 65536 for s in sizes)
    assert sizes[len(sizes) // 2] >= 1024    # body: thousands of tokens
    # The 32k+ tail is reachable and present across nearby seeds (the
    # per-seed probability is a few percent; a handful of seeds sees it
    # without making any single stream pathological).
    tail = [r.prompt.size
            for s in range(6) for r in WorkloadSpec.long_context(
                seed=s).requests() if r.prompt.size > 32768]
    assert tail, "no 32k+ prompt across seeds 0..5 — tail too thin"
    assert all(16 <= r.max_new_tokens <= 512 for r in a)
    small = WorkloadSpec.long_context(
        seed=3, n_requests=6, prompt_mean=24, prompt_min=8,
        prompt_max=40, output_min=2, output_max=8).requests()
    assert len(small) == 6
    assert all(8 <= r.prompt.size <= 40 for r in small)


def test_workload_prefix_pool_trace_roundtrip(tmp_path):
    """Shared-prefix streams replay exactly through the JSONL trace
    path (explicit token ids — the prefix structure survives)."""
    reqs = _spec(seed=5, prefix_pool=2, prefix_tokens=6).requests()
    path = str(tmp_path / "prefix_trace.jsonl")
    save_trace(reqs, path)
    back = replay_trace(path)
    assert len(back) == len(reqs)
    for x, y in zip(reqs, back):
        assert x.arrival_s == y.arrival_s
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new_tokens == y.max_new_tokens
        assert x.seed == y.seed


def test_trace_roundtrip_and_len_only_replay(tmp_path):
    reqs = _spec(seed=3).requests()
    path = str(tmp_path / "trace.jsonl")
    save_trace(reqs, path)
    back = replay_trace(path)
    assert len(back) == len(reqs)
    for x, y in zip(reqs, back):
        assert x.arrival_s == y.arrival_s
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new_tokens == y.max_new_tokens
    # The spec's trace arrival mode replays the same file.
    tr = WorkloadSpec(arrival="trace", trace_path=path,
                      vocab_size=1024).requests()
    assert np.array_equal(tr[0].prompt, reqs[0].prompt)
    # Length-only lines synthesize tokens deterministically per seed.
    p2 = str(tmp_path / "lens.jsonl")
    with open(p2, "w") as f:
        f.write(json.dumps({"arrival_s": 0.5, "prompt_len": 6}) + "\n")
        f.write(json.dumps({"arrival_s": 0.1, "prompt_len": 3}) + "\n")
    r1 = replay_trace(p2, vocab_size=64, seed=5)
    r2 = replay_trace(p2, vocab_size=64, seed=5)
    assert [r.arrival_s for r in r1] == [0.1, 0.5]  # arrival-sorted
    assert all(np.array_equal(a.prompt, b.prompt) for a, b in zip(r1, r2))


# ------------------------------------------------------------- timeseries


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_timeseries_windows_on_cadence_with_counter_deltas():
    reg = MetricsRegistry()
    tok = reg.counter("tokens_out")
    clock = FakeClock()
    col = TimeseriesCollector(reg, window_seconds=1.0, clock=clock)
    col.start()
    tok.inc(10)
    clock.t += 0.5
    assert col.tick() is None            # window not elapsed
    clock.t += 0.6
    w0 = col.tick()                      # 1.1s window closes
    assert w0["metrics"]["tokens_out"] == 10   # the DELTA, not the total
    tok.inc(7)
    clock.t += 1.0
    w1 = col.tick()
    assert w1["metrics"]["tokens_out"] == 7    # next window's own delta
    assert w1["index"] == 1
    assert w1["t_start"] == w0["t_end"]        # contiguous windows
    # A stall closes ONE long window, not a run of empties.
    tok.inc(3)
    clock.t += 5.0
    w2 = col.tick()
    assert w2["duration_s"] == pytest.approx(5.0)
    assert col.tick() is None                  # no fabricated extras


def test_timeseries_ring_bounded_with_exact_dropped_count():
    reg = MetricsRegistry()
    clock = FakeClock()
    col = TimeseriesCollector(reg, window_seconds=1.0, capacity=4,
                              clock=clock)
    col.start()
    for _ in range(10):
        clock.t += 1.0
        col.sample()
    wins = col.windows()
    assert len(wins) == 4                      # bounded
    assert col.dropped == 6                    # exact eviction count
    assert [w["index"] for w in wins] == [6, 7, 8, 9]  # newest win
    j = col.to_json()
    assert j["windows_total"] == 10 and j["dropped"] == 6
    json.dumps(j)                              # export is JSON-safe


def test_timeseries_chrome_counter_events():
    reg = MetricsRegistry()
    reg.gauge("queue_depth").set(3)
    h = reg.histogram("ttft_seconds")
    clock = FakeClock()
    col = TimeseriesCollector(reg, window_seconds=1.0, clock=clock)
    col.start()
    h.observe(0.02)      # after start(): start() opens a fresh window
    clock.t += 1.0
    col.sample()
    events = col.chrome_counter_events(pid=7)
    names = {e["name"] for e in events}
    assert "queue_depth" in names
    assert "ttft_seconds_p50" in names and "ttft_seconds_p99" in names
    for e in events:
        assert e["ph"] == "C" and e["pid"] == 7
        assert isinstance(e["args"]["value"], float)
        assert e["ts"] == pytest.approx(1e6)   # µs since first window
    with pytest.raises(RuntimeError):
        TimeseriesCollector(reg).sample()      # sample before start


# -------------------------------------------------------------------- slo


def _row(ttft=0.01, itl=0.005, tokens=8, shed=False, completed=True):
    return {"shed": shed, "completed": completed, "ttft_s": ttft,
            "itl_s": itl, "tokens_out": tokens}


def test_slo_evaluate_attainment_and_goodput():
    slo = SLO(ttft_p99_ms=100.0, itl_p99_ms=50.0)
    samples = [
        _row(),                               # meets
        _row(ttft=0.5),                       # TTFT bust
        _row(itl=0.2),                        # ITL bust
        _row(shed=True, completed=False, tokens=0),   # shed
        _row(itl=None, tokens=1),             # 1-token: TTFT-only, meets
    ]
    out = evaluate(samples, slo, wall_s=2.0, chips=2)
    assert out["requests"] == 5 and out["shed"] == 1
    assert out["slo_met"] == 2
    assert out["attainment"] == pytest.approx(0.4)
    # goodput counts ONLY the meeting requests' tokens (8 + 1) / wall.
    assert out["goodput_tokens_per_sec"] == pytest.approx(4.5)
    assert out["goodput_tokens_per_sec_per_chip"] == pytest.approx(2.25)


# ------------------------------------------------------------------- gate


def _fake_report(ttft_ms=10.0, itl_ms=1.0, tps=500.0, jitter=0.0,
                 platform="cpu", seed=17):
    """A minimal schema-true report: N windows whose values wobble by
    ``jitter`` (relative) around the aggregates, so the gate has a real
    series to estimate noise from."""
    wobble = [1.0 - jitter, 1.0 + jitter, 1.0, 1.0 - jitter / 2,
              1.0 + jitter / 2, 1.0]
    windows = [{
        "index": i,
        "ttft_p99_ms": ttft_ms * w, "ttft_p50_ms": ttft_ms * w / 2,
        "itl_p99_ms": itl_ms * w, "itl_p50_ms": itl_ms * w / 2,
        "queue_wait_p99_ms": 1.0, "queue_depth": 0.0,
        "slot_occupancy": 0.5, "tokens_per_sec": tps * w,
    } for i, w in enumerate(wobble)]
    return {
        "schema_version": 1,
        "context": {"platform": platform, "seed": seed},
        "aggregate": {
            "ttft_p99_ms": ttft_ms, "ttft_p50_ms": ttft_ms / 2,
            "itl_p99_ms": itl_ms, "itl_p50_ms": itl_ms / 2,
            "tokens_per_sec": tps, "goodput_tokens_per_sec": tps * 0.9,
            "goodput_tokens_per_sec_per_chip": tps * 0.9,
            "slo_attainment": 1.0,
        },
        "timeseries": {"window_seconds": 1.0, "windows": windows},
    }


def test_gate_aa_identical_reports_pass():
    rep = _fake_report(jitter=0.2)
    out = regression_gate(rep, copy.deepcopy(rep))
    assert out["pass"]
    assert out["caveats"] == []
    for row in out["metrics"].values():
        assert row["delta_rel"] == 0.0
        assert not row["flagged"]


def test_gate_flags_injected_2x_ttft_slowdown():
    base = _fake_report(ttft_ms=10.0, jitter=0.05)
    cand = _fake_report(ttft_ms=20.0, jitter=0.05)
    out = regression_gate(base, cand)
    assert not out["pass"]
    row = out["metrics"]["ttft_p99_ms"]
    assert row["flagged"] and row["delta_rel"] == pytest.approx(1.0)
    # The delta cleared the noise-aware threshold, not a lucky default.
    assert row["delta_rel"] > row["threshold"]


def test_gate_flags_throughput_drop_but_not_improvements():
    base = _fake_report(tps=500.0, jitter=0.05)
    out = regression_gate(base, _fake_report(tps=300.0, jitter=0.05))
    assert not out["pass"]
    assert out["metrics"]["tokens_per_sec"]["flagged"]
    # Polarity: a 2x TTFT IMPROVEMENT and a throughput GAIN never flag.
    better = _fake_report(ttft_ms=5.0, tps=900.0, jitter=0.05)
    assert regression_gate(base, better)["pass"]


def test_gate_noise_floor_absorbs_noisy_delta():
    # 12% delta, but both runs wobble 40% window-to-window: the noise
    # floor (3 * combined SEM) exceeds the delta — no flag. The same
    # delta on quiet runs DOES flag at rel_tol=0.05.
    noisy = regression_gate(_fake_report(ttft_ms=10.0, jitter=0.4),
                            _fake_report(ttft_ms=11.2, jitter=0.4),
                            rel_tol=0.05)
    assert not noisy["metrics"]["ttft_p99_ms"]["flagged"]
    quiet = regression_gate(_fake_report(ttft_ms=10.0, jitter=0.001),
                            _fake_report(ttft_ms=11.2, jitter=0.001),
                            rel_tol=0.05)
    assert quiet["metrics"]["ttft_p99_ms"]["flagged"]


def test_gate_caveats_on_context_mismatch():
    out = regression_gate(_fake_report(platform="tpu", seed=1),
                          _fake_report(platform="cpu", seed=2))
    assert any("platform" in c for c in out["caveats"])
    assert any("seed" in c for c in out["caveats"])


# ------------------------------------------------------------- runner e2e


def _warm(engine):
    engine.generate([np.arange(1, 9, dtype=np.int32)], max_new_tokens=2)
    engine.recompile_detector.mark_warm()
    engine.metrics(reset=True)


def test_runner_open_loop_end_to_end():
    cfg, model, params = make_model()
    engine = engine_of(model, params, max_slots=4, max_queue=64)
    _warm(engine)
    spec = _spec(rate=80.0, n_requests=24, vocab_size=cfg.vocab_size,
                 seed=11)
    runner = SustainedRunner(engine, spec, window_seconds=0.1,
                             max_steps=100_000)
    res = runner.run()
    assert res.submitted == 24 and res.shed == 0
    assert res.completed == 24
    assert res.tokens_out > 0 and engine.idle
    assert len(res.windows) >= 1
    done = [s for s in res.samples if s["completed"]]
    assert all(s["ttft_s"] is not None and s["ttft_s"] >= 0 for s in done)
    assert all(s["e2e_s"] >= s["ttft_s"] for s in done)
    # Report over the real run: schema keys + JSON-safe.
    rep = build_report(spec, res, SLO(ttft_p99_ms=1e4, itl_p99_ms=2e3),
                       platform="cpu")
    assert rep["aggregate"]["completed"] == 24
    assert rep["slo"]["attainment"] == 1.0
    json.dumps(rep)


def test_runner_records_queuefull_as_shed_samples():
    cfg, model, params = make_model()
    # max_queue=2 against a 24-request burst: the overflow MUST shed.
    engine = engine_of(model, params, max_slots=2, max_queue=2)
    _warm(engine)
    spec = _spec(arrival="burst", n_requests=24, burst_size=24,
                 vocab_size=cfg.vocab_size, seed=4)
    res = SustainedRunner(engine, spec, window_seconds=0.1,
                          max_steps=100_000).run()
    assert res.shed > 0
    assert res.submitted + res.shed == 24
    shed_rows = [s for s in res.samples if s["shed"]]
    assert len(shed_rows) == res.shed
    assert all(s["tokens_out"] == 0 and not s["completed"]
               for s in shed_rows)
    # Sheds count against attainment: it can't be 1.0.
    rep = build_report(spec, res, SLO(ttft_p99_ms=1e4, itl_p99_ms=2e3))
    assert rep["slo"]["attainment"] < 1.0


def test_report_prefix_section_counts_hits_and_misses():
    """Template-heavy traffic against a prefix-cache engine: the runner
    records counter DELTAS (hits > 0 once the pool re-serves a head) and
    the report's v3 ``prefix`` section carries them with a real
    hit_rate. An engine without the cache never probes — hit_rate is
    None, not 0.0."""
    cfg, model, params = make_model()
    engine = engine_of(model, params, prefix_cache=True, prefix_slots=4,
                       prefix_len=16, min_prefix_len=4)
    _warm(engine)
    spec = WorkloadSpec.template_heavy(
        seed=13, rate=200.0, n_requests=16, prefix_pool=2,
        prefix_tokens=8, prompt_mean=14, prompt_min=12, prompt_max=24,
        output_min=2, output_max=6, vocab_size=cfg.vocab_size)
    res = SustainedRunner(engine, spec, window_seconds=0.1,
                          max_steps=100_000).run()
    assert res.completed == 16
    assert res.prefix_hits > 0
    assert res.prefix_hits + res.prefix_misses >= 16
    rep = build_report(spec, res, SLO(ttft_p99_ms=1e4, itl_p99_ms=2e3))
    assert rep["schema_version"] == 7
    sec = rep["prefix"]
    assert sec["prefix_hits"] == res.prefix_hits
    assert sec["prefix_misses"] == res.prefix_misses
    assert sec["hit_rate"] == pytest.approx(
        res.prefix_hits / (res.prefix_hits + res.prefix_misses))
    # Single engine: nothing shipped, nothing affinity-routed.
    assert sec["prefix_bytes_shipped"] == 0
    assert sec["affinity_routed"] == 0
    json.dumps(rep)
    engine.close()

    plain = engine_of(model, params)
    _warm(plain)
    res2 = SustainedRunner(plain, spec, window_seconds=0.1,
                          max_steps=100_000).run()
    assert res2.prefix_hits == 0 and res2.prefix_misses == 0
    rep2 = build_report(spec, res2, SLO(ttft_p99_ms=1e4, itl_p99_ms=2e3))
    assert rep2["prefix"]["hit_rate"] is None
    plain.close()


def test_report_adapter_section_moe_and_longcontext():
    """The v6 ``adapter`` section: an expert-model run (the tiny
    ``DecoderLM``, top-2 of 4) carries the adapter name, per-expert
    dispatch totals and the imbalance ratio; a long-context
    run carries the sparse threshold plus the EXACT fraction of
    generated tokens served past it (computed from the per-sample
    geometry); a plain GPT-2 run shows the name with empty tallies —
    the section is stable schema, not adapter-conditional."""
    import jax

    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.inference.adapters import LongContextAdapter
    from tests.unit.test_adapters import decoder_model

    moe = decoder_model()
    eng = InferenceEngine(moe, moe.init(jax.random.PRNGKey(0))["params"],
                          config={"max_slots": 4, "max_len": 64,
                                  "chunk_size": 4, "prefill_chunk": 8,
                                  "max_queue": 64,
                                  "use_flash_decode": False})
    _warm(eng)
    spec = _spec(seed=2, n_requests=8, rate=200.0, vocab_size=256)
    res = SustainedRunner(eng, spec, window_seconds=0.1,
                          max_steps=100_000).run()
    assert res.adapter == "decoder" and sum(res.expert_load) > 0
    rep = build_report(spec, res, SLO(ttft_p99_ms=1e4, itl_p99_ms=2e3))
    sec = rep["adapter"]
    assert sec["adapter"] == "decoder"
    assert len(sec["expert_load"]) == 4
    assert sec["expert_load_imbalance"] >= 1.0
    assert sec["sparse_token_fraction"] is None  # no sparse threshold
    json.dumps(rep)
    eng.close()

    cfg, model, params = make_model()
    lc = LongContextAdapter.from_model(model, threshold=32, block=8,
                                       num_local_blocks=2)
    eng = InferenceEngine(None, params,
                          config={"max_slots": 4, "max_len": 64,
                                  "chunk_size": 4, "prefill_chunk": 8,
                                  "max_queue": 64,
                                  "use_flash_decode": False},
                          adapter=lc)
    _warm(eng)
    spec = _spec(seed=2, n_requests=6, rate=200.0,
                 vocab_size=cfg.vocab_size, output_dist="fixed",
                 output_mean=30, output_max=30)
    res = SustainedRunner(eng, spec, window_seconds=0.1,
                          max_steps=100_000).run()
    sec = build_report(spec, res,
                       SLO(ttft_p99_ms=1e4, itl_p99_ms=2e3))["adapter"]
    assert sec["adapter"] == "longcontext"
    assert sec["sparse_decode_threshold"] == 32
    # Every stream runs prompt+30 tokens; those past position 32 are
    # sparse-served — the fraction is exact, strictly inside (0, 1).
    assert 0.0 < sec["sparse_token_fraction"] < 1.0
    assert sec["expert_load"] == []
    eng.close()

    eng = engine_of(model, params)
    _warm(eng)
    res = SustainedRunner(eng, _spec(seed=2, n_requests=4, rate=200.0,
                                     vocab_size=cfg.vocab_size),
                          window_seconds=0.1, max_steps=100_000).run()
    sec = build_report(_spec(seed=2), res,
                       SLO(ttft_p99_ms=1e4, itl_p99_ms=2e3))["adapter"]
    assert sec["adapter"] == "gpt2"
    assert sec["expert_load"] == [] and sec["expert_load_imbalance"] is None
    assert sec["sparse_decode_threshold"] == 0
    assert sec["sparse_token_fraction"] is None
    eng.close()


# ------------------------------------------------------------- saturation


def test_saturation_sweep_reports_knee():
    # run_fn fakes a server that holds SLO to rate 16 and collapses at
    # 24 — the sweep must report 16, not 24 and not None.
    def run_fn(rate):
        ok = rate <= 16
        rep = _fake_report(tps=rate * 30)
        rep["aggregate"]["slo_attainment"] = 1.0 if ok else 0.4
        rep["aggregate"]["shed"] = 0 if ok else 5
        return rep

    out = saturation_sweep(run_fn, (8, 16, 24), attainment_floor=0.95)
    assert out["max_sustainable_rate"] == 16
    flags = [(s["rate"], s["sustainable"]) for s in out["rates"]]
    assert flags == [(8, True), (16, True), (24, False)]


# --------------------------------------------- the report, whole, by shape


def test_sustained_report_windows_sweep_and_gate_self_check():
    """One warm engine (int8 cache, prefix cache, host offload on)
    through everything a sustained report is made of: a 48-request
    Poisson run with an alert manager riding the runner's collector,
    a three-rate saturation sweep on the same engine, the engine's
    cost model, and the gate held against the report itself. Budgets
    are off (``None``), so attainment is the share that completed and
    nothing here turns on how fast this machine is."""
    from deepspeed_tpu.telemetry import AlertManager, default_rules

    cfg, model, params = make_model()
    engine = engine_of(model, params, max_slots=4, max_queue=64,
                       int8_kv=True, host_offload=True, prefix_cache=True,
                       prefix_slots=4, prefix_len=16, min_prefix_len=4)
    _warm(engine)
    slo = SLO(ttft_p99_ms=None, itl_p99_ms=None)
    base = dict(arrival="poisson", rate=60.0, n_requests=48,
                prompt_dist="lognormal", output_dist="lognormal",
                output_min=2, prefix_pool=2, prefix_tokens=8,
                vocab_size=cfg.vocab_size, seed=17)
    fired = []

    def run_spec(**kw):
        spec = _spec(**dict(base, **kw))
        runner = SustainedRunner(engine, spec, window_seconds=0.1,
                                 max_steps=500_000)
        runner.alerts = AlertManager(
            runner.collector, default_rules(queue_saturation=64))
        res = runner.run()
        assert res.alerts_fired == runner.alerts.fired()
        fired.extend(res.alerts_fired)
        return build_report(spec, res, slo, platform="cpu")

    rep = run_spec()
    rep["saturation"] = saturation_sweep(
        lambda rate: run_spec(rate=rate, n_requests=16,
                              seed=int(rate) + 1000),
        (30.0, 60.0, 120.0), attainment_floor=0.5)
    rep["perf_xray"] = engine.perf_xray()
    gate = regression_gate(rep, rep)
    json.dumps(rep)

    assert rep["schema_version"] == 7
    carrying = [w for w in rep["timeseries"]["windows"]
                if w["ttft_p99_ms"] is not None
                and w["itl_p99_ms"] is not None
                and w["queue_depth"] is not None
                and w["slot_occupancy"] is not None]
    assert len(carrying) >= 3
    assert all(w["ttft_p50_ms"] <= w["ttft_p99_ms"]
               and w["itl_p50_ms"] <= w["itl_p99_ms"] for w in carrying)
    assert rep["saturation"]["max_sustainable_rate"] == 120.0
    assert [s["shed"] for s in rep["saturation"]["rates"]] == [0, 0, 0]
    assert gate["pass"] and gate["perf_xray"]["pass"]
    # The workload echo + context make the report self-describing.
    assert rep["workload"]["seed"] == rep["context"]["seed"] == 17
    assert rep["aggregate"]["completed"] == \
        rep["slo"]["requests"] - rep["slo"]["shed"] == 48
    assert engine.metrics()["compile_count"] == 1
    assert not [f for f in fired if f["rule"] == "queue_saturation"]
    engine.close()


# ----------------------------------------------------------------- chaos


def test_chaos_runner_records_recovery_and_zero_lost():
    """Chaos mode end to end on a real engine: a fatal fault armed
    mid-run fires against a live batch, the engine recovers, and the
    run/report carry the recovery facts with zero requests lost."""
    from deepspeed_tpu.inference import Fault, FaultPlan

    cfg, model, params = make_model()
    engine = engine_of(model, params, max_slots=4, max_queue=64,
                       fault_injection=True)
    _warm(engine)
    spec = _spec(rate=80.0, n_requests=24, output_mean=8, output_min=4,
                 vocab_size=cfg.vocab_size, seed=11)
    plan = FaultPlan(faults=(Fault("raise", step=2),))
    runner = SustainedRunner(engine, spec, window_seconds=0.1,
                             max_steps=100_000, chaos_plan=plan,
                             chaos_after_s=0.05)
    res = runner.run()
    assert res.faults_injected == 1
    assert res.requests_lost == 0
    assert res.completed == 24 and engine.idle
    assert engine.health == "healthy"
    assert len(res.recovery) == 1
    rec = res.recovery[0]
    # Run-relative interval: inside the run, after the chaos point.
    assert 0.0 <= rec["t_start_s"] <= rec["t_end_s"] <= res.wall_s
    assert rec["duration_s"] >= 0 and "InjectedFault" in rec["error"]
    rep = build_report(spec, res, SLO(ttft_p99_ms=1e4, itl_p99_ms=2e3),
                       platform="cpu")
    chaos = rep["chaos"]
    assert chaos["requests_lost"] == 0
    assert chaos["recoveries"] == 1
    assert chaos["faults_injected"] == 1
    assert chaos["recovery_time_s"] == pytest.approx(rec["duration_s"],
                                                     abs=1e-6)
    assert chaos["recovery_intervals"] == res.recovery
    for key in ("slo_attainment_during_recovery",
                "slo_attainment_outside_recovery"):
        assert chaos[key] is None or 0.0 <= chaos[key] <= 1.0
    json.dumps(rep)


def test_chaos_section_empty_on_fault_free_run():
    """Fault-free runs still carry the chaos section (schema v2), with
    everything zeroed — consumers need not branch on its presence."""
    cfg, model, params = make_model()
    engine = engine_of(model, params, max_slots=4, max_queue=64)
    _warm(engine)
    spec = _spec(rate=80.0, n_requests=8, vocab_size=cfg.vocab_size,
                 seed=5)
    res = SustainedRunner(engine, spec, window_seconds=0.1,
                          max_steps=100_000).run()
    assert res.recovery == [] and res.requests_lost == 0
    assert res.faults_injected == 0
    rep = build_report(spec, res, SLO(ttft_p99_ms=1e4, itl_p99_ms=2e3))
    assert rep["schema_version"] == 7
    chaos = rep["chaos"]
    assert chaos["recoveries"] == 0 and chaos["recovery_time_s"] == 0.0
    assert chaos["requests_during_recovery"] == 0
    assert chaos["slo_attainment_during_recovery"] is None


def test_chaos_recovery_keeps_the_program_and_tells_the_request_story():
    """What the chaos run above does not hold: the rebuild after the
    fault reuses the ONE compiled program, the report's context echoes
    the fault plan it was given, and a request the fault interrupted
    reads in its autopsy as lost-then-replayed, finished, with no gap
    in its hops."""
    from deepspeed_tpu.inference import Fault, FaultPlan
    from deepspeed_tpu.telemetry import build_autopsy

    cfg, model, params = make_model()
    engine = engine_of(model, params, max_slots=4, max_queue=64,
                       fault_injection=True)
    _warm(engine)
    spec = _spec(arrival="poisson", rate=60.0, n_requests=32,
                 prompt_dist="lognormal", output_dist="lognormal",
                 output_mean=8, output_min=4, vocab_size=cfg.vocab_size,
                 seed=23)
    plan = FaultPlan(faults=(Fault("raise", step=2),))
    res = SustainedRunner(engine, spec, window_seconds=0.1,
                          max_steps=500_000, chaos_plan=plan,
                          chaos_after_s=0.05).run()
    rep = build_report(
        spec, res, SLO(ttft_p99_ms=None, itl_p99_ms=None), platform="cpu",
        extra={"fault_plan": [[f.kind, f.step] for f in plan.faults]})
    json.dumps(rep)
    assert rep["schema_version"] == 7
    assert rep["context"]["fault_plan"] == [["raise", 2]]
    chaos = rep["chaos"]
    assert chaos["faults_injected"] == 1 and chaos["recoveries"] >= 1
    assert chaos["requests_lost"] == 0 and res.completed == 32
    assert sum(r["replayed"] for r in chaos["recovery_intervals"]) >= 1
    assert engine.health == "healthy" and engine.idle
    assert engine.metrics()["compile_count"] == 1

    replayed = sorted({ev["tid"] for ev in engine.tracer.events()
                       if ev["name"] == "request/replayed"})
    assert replayed
    story = build_autopsy(engine.trace_recorders(), replayed[0])
    assert story["replays"] >= 1 and story["hop_gaps"] == []
    assert story["terminal"]["cause"] == "done"
    assert story["terminal"]["lost_then_replayed"]
    engine.close()


@pytest.mark.slow
def test_sustained_ramp_soak_shows_saturation_curve():
    """Fuller soak (slow tier): a ramp workload driven past the tiny
    engine's capacity produces a queue-depth curve that actually rises,
    and the saturation sweep's unsustainable step sheds."""
    cfg, model, params = make_model()
    engine = engine_of(model, params, max_slots=2, max_queue=8)
    _warm(engine)
    spec = _spec(arrival="ramp", ramp_from=2.0, rate=400.0,
                 n_requests=96, output_mean=10, output_max=12,
                 vocab_size=cfg.vocab_size, seed=9)
    res = SustainedRunner(engine, spec, window_seconds=0.2,
                          max_steps=1_000_000).run()
    rep = build_report(spec, res, SLO(ttft_p99_ms=50.0, itl_p99_ms=50.0))
    depths = [w["queue_depth"] for w in rep["timeseries"]["windows"]
              if w["queue_depth"] is not None]
    assert max(depths) > 0                   # backlog became visible
    assert rep["slo"]["attainment"] < 1.0    # the ramp outran the engine
