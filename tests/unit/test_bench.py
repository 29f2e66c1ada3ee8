"""Tests for the bench harness: it measures in the process it was started
in, names its device on every line, and refuses to run without a TPU unless
the tiny CPU smoke was asked for explicitly.
"""

import importlib.util
import json
import os

import pytest

_BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "bench.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("ds_bench", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def serving_smoke(bench):
    """One `bench.py --serve-smoke` measurement, read by every test of its
    report."""
    return bench._measure_serving(smoke=True)


def test_require_tpu_exits_without_explicit_cpu_request(bench, monkeypatch,
                                                        capsys):
    """No TPU and no explicit CPU request: exit non-zero, print no
    metric. JAX_PLATFORMS=cpu, asked for explicitly, passes."""
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(SystemExit) as e:
        bench._require_tpu_or_exit()
    assert e.value.code == 3
    assert capsys.readouterr().out == ""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    bench._require_tpu_or_exit()


def test_emit_names_the_device(bench, capsys):
    import jax

    bench._emit({"metric": "m", "value": 1.0, "unit": "tok/s",
                 "vs_baseline": None, "extra": {}})
    out = json.loads(capsys.readouterr().out.strip())
    assert out["extra"]["device"] == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}
    # Nothing from another run rides along.
    assert not {"last_good_tpu", "fallback", "probe"} & set(out["extra"])


def test_peak_flops_comes_from_the_device_kind_table(bench):
    """The CPU's device kind has no peaks row: asking raises instead of
    handing out the v5e figure."""
    with pytest.raises(KeyError):
        bench._peak_flops()


def test_timed_chunks_log_carries_per_chunk_platform(bench):
    """Every chunk names the backend that executed it."""
    import jax

    log, loss = bench._timed_chunks(
        lambda b: jax.numpy.float32(b), list(range(5)), chunk=2,
        tokens_per_step=10, label="test")
    assert loss == 4.0
    assert [c["steps"] for c in log] == [2, 2, 1]
    for c in log:
        assert c["platform"] == jax.default_backend()
        assert c["rate"] > 0 and c["dt_s"] >= 0


def test_serving_smoke_measures_in_process(serving_smoke):
    """`bench.py --serve-smoke` must run end-to-end on the virtual CPU
    backend and report a well-formed serving line: positive throughput,
    latency percentiles, and ZERO recompiles after warmup (the engine's
    compile-count contract, measured in the benchmark itself)."""
    r = serving_smoke
    assert r["metric"] == "gpt2_tiny_smoke_serving_tokens_per_sec"
    assert r["value"] > 0 and r["unit"] == "tokens/s"
    assert r["vs_baseline"] > 0
    e = r["extra"]
    assert e["tokens_out"] == e["requests"] * e["max_new_tokens"]
    assert e["recompiles_after_warmup"] == 0
    assert 0.0 < e["slot_occupancy"] <= 1.0
    assert e["p50_per_token_latency_ms"] <= e["p99_per_token_latency_ms"]
    # Perf X-ray acceptance: the CPU-only artifact carries a POPULATED
    # cost/memory section — the program the engine dispatched (and none
    # it never ran) with nonzero cost-model flops and predicted peak
    # HBM, honest platform="cpu" labels, and NO fabricated utilization
    # (no peaks row on CPU).
    xray = e["perf_xray"]
    active = [p for p in xray["programs"] if not p["superseded"]]
    assert {p["program"] for p in active} == {"mixed_step"}
    for p in active:
        assert p["flops"] > 0 and p["peak_hbm_bytes"] > 0
        assert p["platform"] == "cpu"
    assert xray["platform"] == "cpu" and xray["peaks"] is None
    assert xray["totals"]["bytes_per_token"] > 0
    assert xray["recompiles"] == []
    assert xray["hbm"]["predicted_bytes"] > 0
    json.dumps(r)  # driver-facing line must be JSON-serializable


def test_serving_smoke_carries_telemetry_snapshot(serving_smoke):
    """The --serve JSON embeds the telemetry snapshot: a Prometheus text
    fingerprint plus exact span counts — enough for a reviewer to tell
    two runs exported the same metric/span shapes without the full text."""
    r = serving_smoke
    t = r["extra"]["telemetry"]
    assert len(t["prometheus_sha256"]) == 64
    assert t["prometheus_lines"] > 0
    assert t["recompiles"] == 0 and t["compile_count"] >= 1
    counts = t["span_counts"]
    # Counts are exact since engine construction, so warmup requests
    # (one per distinct prompt length) ride along with the timed stream.
    assert counts["request"] >= r["extra"]["requests"]
    assert counts["request/queued"] == counts["request"]
    assert counts.get("inference/mixed_step", 0) > 0
    json.dumps(r)
