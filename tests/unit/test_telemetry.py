"""deepspeed_tpu.telemetry — registry, spans, exporters, recompiles.

The contract under test:
1. REGISTRY — counters are monotonic with windowed views, gauges are
   instantaneous (incl. set_fn live reads), histograms hold bounded
   memory with deterministic percentiles, and one name never serves two
   metric kinds.
2. SPANS — the ring is bounded with exact per-name counts across
   wraparound, and ``chrome_trace()`` emits schema-valid, ts-sorted
   trace events ("X" rows carry dur, "i" rows carry s) that Perfetto
   loads.
3. PROMETHEUS — the text exposition parses with a minimal parser,
   counters export ``_total`` values that window resets never rewind,
   and the opt-in stdlib endpoint serves the same text over HTTP.
4. RECOMPILES — the detector's live ``compile_count`` gauge tracks jit
   caches; after ``mark_warm()`` a shape change increments
   ``recompiles`` EXACTLY once, and a mixed serving workload (chunked
   prefill + speculation + sampled + greedy) holds recompiles at 0 —
   read through the registry, not test-local bookkeeping.
5. DEGRADATION — tensorboard-less boxes get a no-op writer plus one
   warning, NullRecorder/NullRegistry accept the full surface, and
   ``import deepspeed_tpu.telemetry`` never needs extras.
"""

import itertools
import json
import math
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.telemetry import (
    MergedRegistry,
    MetricsRegistry,
    NullRecorder,
    NullRegistry,
    PrometheusEndpoint,
    RecompileDetector,
    SpanRecorder,
    TensorBoardScalarWriter,
    TraceContext,
    TraceError,
    merged_trace,
    prometheus_digest,
    prometheus_text,
    validate_trace,
)
from tests.unit.test_chunked_prefill import (
    engine_of,
    make_model,
    prompts_of,
)

# ---------------------------------------------------------------- registry


def test_counter_monotonic_with_windowed_view():
    reg = MetricsRegistry()
    c = reg.counter("tokens_out")
    c.inc(5)
    c.inc(3)
    assert c.value == 8 and c.window_value == 8
    c.reset_window()
    assert c.value == 8 and c.window_value == 0
    c.inc(2)
    assert c.value == 10 and c.window_value == 2
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_fn_is_sampled_at_read_time():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(4)
    assert g.value == 4.0
    box = [7]
    g.set_fn(lambda: box[0])
    assert g.value == 7.0
    box[0] = 9
    assert g.value == 9.0  # live read, not a cached sample


def test_histogram_bounded_and_deterministic():
    reg = MetricsRegistry()
    h = reg.histogram("lat", reservoir_size=64)
    for v in range(1000):
        h.observe(v)
    assert h.count == 1000 and len(h._sample) == 64  # bounded memory
    s = h.stats()
    assert s["min"] == 0 and s["max"] == 999 and s["sum"] == sum(range(1000))
    # Seeded reservoir: a second identical stream gives identical
    # percentiles (reproducible runs).
    h2 = MetricsRegistry().histogram("lat", reservoir_size=64)
    for v in range(1000):
        h2.observe(v)
    assert h.quantiles() == h2.quantiles()
    assert s["p50"] <= s["p99"]


def test_histogram_percentiles_exact_under_reservoir():
    h = MetricsRegistry().histogram("lat")
    assert h.percentile(50) is None
    for v in [1.0, 2.0, 3.0, 4.0]:
        h.observe(v)
    assert h.percentile(0) == 1.0
    assert h.percentile(50) == 3.0  # nearest-rank
    assert h.percentile(100) == 4.0


def test_one_name_never_serves_two_kinds():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


def test_labels_and_const_labels_key_distinct_series():
    reg = MetricsRegistry(engine="inference")
    a = reg.counter("hits", pool="kv")
    b = reg.counter("hits", pool="slot")
    assert a is not b
    assert a is reg.counter("hits", pool="kv")  # get-or-create
    assert a.labels == {"engine": "inference", "pool": "kv"}


def test_snapshot_reset_opens_new_window():
    reg = MetricsRegistry()
    reg.counter("n").inc(3)
    reg.gauge("g").set(5)
    reg.histogram("h").observe(1.5)
    snap = reg.snapshot(reset=True)
    assert snap["n"] == 3 and snap["g"] == 5.0 and snap["h"]["count"] == 1
    snap2 = reg.snapshot()
    # Counters and histograms windowed back to zero; gauges untouched.
    assert snap2["n"] == 0 and snap2["h"]["count"] == 0
    assert snap2["g"] == 5.0
    assert reg.counter("n").value == 3  # internally still monotonic


def test_null_registry_accepts_full_surface():
    reg = NullRegistry()
    reg.counter("a").inc(5)
    reg.gauge("b").set_fn(lambda: 1)
    reg.histogram("c").observe(2.0)
    assert reg.snapshot(reset=True) == {}
    assert list(reg.collect()) == []


# ------------------------------------------------------------------ spans


def test_span_ring_bounded_with_exact_counts():
    rec = SpanRecorder(capacity=4)
    for i in range(10):
        rec.instant("tick", i=i)
    assert len(rec.events()) == 4
    assert rec.dropped == 6
    assert rec.span_counts() == {"tick": 10}  # exact despite wraparound


def test_chrome_trace_schema_and_ordering():
    t = [0.0]
    rec = SpanRecorder(capacity=64, clock=lambda: t[0])
    t[0] = 1.0
    rec.span("long", start=0.0, end=1.0, tid=7, rid=3)
    t[0] = 0.5
    rec.instant("mark")
    t[0] = 0.9
    rec.span("short", start=0.4, end=0.9)
    doc = rec.chrome_trace()
    ev = doc["traceEvents"]
    ts = [e["ts"] for e in ev]
    assert ts == sorted(ts)  # Perfetto wants monotone ts
    for e in ev:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0
        else:
            assert e["ph"] == "i" and e["s"] == "t"
    x = next(e for e in ev if e["name"] == "long")
    assert x["tid"] == 7 and x["args"]["rid"] == 3
    assert x["dur"] == pytest.approx(1e6)  # microseconds


def test_timed_context_and_trace_file_roundtrip(tmp_path):
    rec = SpanRecorder(capacity=16)
    with rec.timed("work", tid=2, chunk=1):
        pass
    path = rec.write_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert doc["displayTimeUnit"] == "ms"
    assert [e["name"] for e in doc["traceEvents"]] == ["work"]
    lines = rec.jsonl_lines()
    assert len(lines) == 1 and json.loads(lines[0])["name"] == "work"


def test_null_recorder_surface():
    rec = NullRecorder()
    with rec.timed("x"):
        rec.instant("y")
    rec.span("z", start=0.0)
    assert rec.span_counts() == {} and rec.events() == []
    with pytest.raises(RuntimeError):
        rec.write_chrome_trace("/nonexistent/trace.json")


# -------------------------------------------------------------- prometheus


def _parse_prom(text):
    """Minimal text-exposition parser: {name: kind}, {(name, labels): v}.

    Deliberately independent of the exporter's formatting helpers so a
    formatting regression fails here instead of round-tripping."""
    kinds, samples = {}, {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            _, _, name, kind = line.split()
            kinds[name] = kind
            continue
        head, val = line.rsplit(" ", 1)
        if "{" in head:
            name, rest = head.split("{", 1)
            labels = tuple(sorted(
                (kv.split("=", 1)[0], kv.split("=", 1)[1].strip('"'))
                for kv in rest.rstrip("}").split(",")))
        else:
            name, labels = head, ()
        samples[(name, labels)] = float(val)
    return kinds, samples


def test_prometheus_text_parses_and_counters_stay_monotonic():
    reg = MetricsRegistry(engine="inference")
    reg.counter("tokens_out").inc(12)
    reg.gauge("queue_depth").set(3)
    h = reg.histogram("ttft")
    h.observe(0.5)
    h.observe(1.5)
    kinds, samples = _parse_prom(prometheus_text(reg))
    assert kinds["ds_tpu_tokens_out_total"] == "counter"
    assert kinds["ds_tpu_queue_depth"] == "gauge"
    assert kinds["ds_tpu_ttft"] == "summary"
    lbl = ("engine", "inference")
    assert samples[("ds_tpu_tokens_out_total", (lbl,))] == 12
    assert samples[("ds_tpu_ttft_count", (lbl,))] == 2
    assert samples[("ds_tpu_ttft_sum", (lbl,))] == 2.0
    assert samples[("ds_tpu_ttft", (lbl, ("quantile", "0.5")))] == 1.5
    # Window reset must NOT rewind the exported counter (Prometheus
    # rate() needs monotonic series).
    reg.reset_window()
    _, after = _parse_prom(prometheus_text(reg))
    assert after[("ds_tpu_tokens_out_total", (lbl,))] == 12


def test_prometheus_empty_histogram_exports_nan_quantiles():
    reg = MetricsRegistry()
    reg.histogram("empty")
    _, samples = _parse_prom(prometheus_text(reg))
    assert math.isnan(samples[("ds_tpu_empty", (("quantile", "0.5"),))])
    assert samples[("ds_tpu_empty_count", ())] == 0


def test_prometheus_digest_fingerprints_shape():
    reg = MetricsRegistry()
    reg.counter("a").inc(1)
    sha, n = prometheus_digest(reg)
    assert len(sha) == 64 and n == 1
    reg.counter("a").inc(1)
    sha2, n2 = prometheus_digest(reg)
    assert sha2 != sha and n2 == 1  # value changed, line count stable


def test_prometheus_label_escaping_and_special_values():
    """Label values with backslash / quote / newline must escape per the
    exposition format (single-pass — no double-escaping the backslash),
    and non-finite values must spell +Inf/-Inf/NaN, not Python's repr
    ('inf' does not parse on the Prometheus side)."""
    from deepspeed_tpu.telemetry.exporters import (_escape_label,
                                                   _fmt_value)

    assert _escape_label('a\\b"c\nd') == 'a\\\\b\\"c\\nd'
    # Order-independence: an already-escaped-looking value escapes each
    # character exactly once.
    assert _escape_label("\\n") == "\\\\n"
    reg = MetricsRegistry()
    reg.gauge("g", path='C:\\tmp\n"x"').set(1)
    text = prometheus_text(reg)
    assert 'path="C:\\\\tmp\\n\\"x\\""' in text
    assert _fmt_value(float("inf")) == "+Inf"
    assert _fmt_value(float("-inf")) == "-Inf"
    assert _fmt_value(float("nan")) == "NaN"
    assert _fmt_value(None) == "NaN"
    assert _fmt_value(3) == "3" and _fmt_value(2.5) == "2.5"
    reg.gauge("inf_gauge").set(float("inf"))
    assert "ds_tpu_inf_gauge +Inf" in prometheus_text(reg)


def test_prometheus_endpoint_survives_concurrent_scrapes():
    """Hammer the endpoint from several threads WHILE the registry grows
    new metrics — the collect() walk is structure-locked, so no scrape
    may 500 on 'dictionary changed size during iteration'."""
    import threading

    reg = MetricsRegistry()
    reg.counter("base").inc(1)
    ep = PrometheusEndpoint(reg, port=0)
    url = "http://{}:{}/metrics".format(ep.host, ep.port)
    errors = []
    stop = threading.Event()

    def scrape():
        for _ in range(15):
            try:
                body = urllib.request.urlopen(url, timeout=30).read()
                assert b"ds_tpu_base_total" in body
            except Exception as e:  # noqa: BLE001 — collected for assert
                errors.append(e)

    def churn():
        # Bounded creation rate: the point is mutation DURING collect,
        # not an unboundedly growing export (which would just make every
        # scrape slower until it times out).
        for i in range(400):
            if stop.is_set():
                return
            reg.counter("churn_{}".format(i % 40)).inc(1)
            reg.histogram("hist_{}".format(i % 40)).observe(0.1)

    t_churn = threading.Thread(target=churn, daemon=True)
    scrapers = [threading.Thread(target=scrape) for _ in range(4)]
    try:
        t_churn.start()
        for t in scrapers:
            t.start()
        for t in scrapers:
            t.join(timeout=30)
    finally:
        stop.set()
        t_churn.join(timeout=5)
        ep.close()
    assert errors == []


def test_prometheus_endpoint_serves_registry():
    reg = MetricsRegistry()
    reg.counter("scrapes").inc(4)
    ep = PrometheusEndpoint(reg, port=0)
    try:
        url = "http://{}:{}/metrics".format(ep.host, ep.port)
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        assert body == prometheus_text(reg)
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                "http://{}:{}/other".format(ep.host, ep.port), timeout=5)
    finally:
        ep.close()


# -------------------------------------------------------------- recompiles


def test_recompile_detector_counts_shape_change_exactly_once():
    reg = MetricsRegistry()
    det = RecompileDetector(reg)
    f = jax.jit(lambda x: x * 2)
    det.watch("f", f)
    with pytest.raises(TypeError):
        det.watch("not_jitted", lambda x: x)
    f(jnp.zeros((4,)))
    assert reg.gauge("compile_count").value == 1  # live gauge
    assert det.observe() == 0  # pre-warm growth is not a recompile
    det.mark_warm()
    f(jnp.zeros((4,)))  # same shape: cache hit
    assert det.observe() == 0
    f(jnp.zeros((8,)))  # shape change: ONE new compilation
    assert det.observe() == 1
    assert det.observe() == 0  # not double-counted
    f(jnp.zeros((8,)))
    assert det.observe() == 0
    assert reg.counter("recompiles").value == 1
    assert reg.gauge("compile_count").value == 2


def test_mixed_serving_workload_reports_zero_recompiles():
    """Chunked prefill + speculation + sampled + greedy in ONE engine:
    the live registry gauge reads compile_count == 1 and the recompile
    counter stays 0 — the runtime form of the one-program contract."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, spec_decode=True, spec_k=3,
                    spec_ngram=3)
    ps = prompts_of(cfg, [5, 9, 13, 3])
    eng.submit(ps[0], max_new_tokens=6)                      # greedy
    eng.submit(ps[1], max_new_tokens=6, temperature=0.8,     # sampled
               seed=7)
    eng.submit(ps[2], max_new_tokens=5, spec_decode=True)    # spec
    eng.submit(ps[3], max_new_tokens=4, temperature=1.2,     # sampled+top_k
               top_k=5, seed=3)
    eng.run()
    snap = eng.telemetry.snapshot()
    assert snap["compile_count"] == 1
    assert snap["recompiles"] == 0
    _, samples = _parse_prom(eng.prometheus())
    lbl = (("engine", "inference"),)
    assert samples[("ds_tpu_compile_count", lbl)] == 1
    assert samples[("ds_tpu_recompiles_total", lbl)] == 0


def test_resilience_counters_and_health_gauge_export():
    """Parser-level (docs/RESILIENCE.md): the resilience counters
    (faults_injected / recoveries / requests_replayed / deadline_sheds /
    step_stalls), the recovery_seconds histogram, and the LIVE
    health_state gauge all ride the standard Prometheus exposition —
    one registry, no parallel wiring."""
    import time

    from deepspeed_tpu.inference import Fault, FaultPlan

    cfg, model, params = make_model()
    eng = engine_of(model, params, fault_injection=True, max_slots=1)
    long_p, short_p = prompts_of(cfg, [8, 5])
    eng.submit(long_p, max_new_tokens=12)
    expired = eng.submit(short_p, max_new_tokens=4, deadline_ms=1)
    eng.inject_faults(FaultPlan(faults=(Fault("raise", step=1),)))
    time.sleep(0.01)
    eng.run()
    assert expired.phase == "expired"
    kinds, samples = _parse_prom(eng.prometheus())
    lbl = (("engine", "inference"),)
    assert kinds["ds_tpu_faults_injected_total"] == "counter"
    assert kinds["ds_tpu_health_state"] == "gauge"
    assert kinds["ds_tpu_recovery_seconds"] == "summary"
    assert samples[("ds_tpu_faults_injected_total", lbl)] == 1
    assert samples[("ds_tpu_recoveries_total", lbl)] == 1
    assert samples[("ds_tpu_requests_replayed_total", lbl)] >= 1
    assert samples[("ds_tpu_deadline_sheds_total", lbl)] == 1
    assert samples[("ds_tpu_step_stalls_total", lbl)] == 0
    assert samples[("ds_tpu_recovery_seconds_count", lbl)] == 1
    assert samples[("ds_tpu_health_state", lbl)] == 0.0   # healthy again
    eng.drain()
    _, after = _parse_prom(eng.prometheus())
    assert after[("ds_tpu_health_state", lbl)] == 2.0     # live: draining
    # Counters never rewind across a metrics window reset.
    eng.metrics(reset=True)
    _, reset = _parse_prom(eng.prometheus())
    assert reset[("ds_tpu_recoveries_total", lbl)] == 1


# ---------------------------------------------------- engine integration


def test_engine_spans_cover_request_lifecycle(tmp_path):
    cfg, model, params = make_model()
    eng = engine_of(model, params)
    r = eng.submit(prompts_of(cfg, [6])[0], max_new_tokens=4)
    eng.run()
    counts = eng.tracer.span_counts()
    for name in ("request/queued", "request/prefill", "request/decode",
                 "request", "inference/mixed_step", "inference/harvest"):
        assert counts.get(name, 0) >= 1, name
    path = eng.write_trace(str(tmp_path / "t.json"))
    doc = json.loads(open(path).read())
    # (the file names its two processes first: the engine's ring and the
    # process recorder's start-up, tests/unit/test_startup_trace.py)
    ts = [e["ts"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts) and len(ts) > 0
    # Request lifecycle rides the request's own track.
    q = next(e for e in doc["traceEvents"] if e["name"] == "request/queued")
    assert q["tid"] == r.rid


def test_engine_telemetry_snapshot_and_windowed_metrics():
    cfg, model, params = make_model()
    eng = engine_of(model, params)
    eng.generate(prompts_of(cfg, [5]), max_new_tokens=4)
    m1 = eng.metrics(reset=True)
    assert m1["tokens_out"] == 4 and m1["requests_completed"] == 1
    m2 = eng.metrics()
    # Fresh window: stream counters back to zero, cumulative compile
    # bookkeeping preserved.
    assert m2["tokens_out"] == 0 and m2["requests_completed"] == 0
    assert m2["compile_count"] == m1["compile_count"] == 1
    eng.generate(prompts_of(cfg, [7]), max_new_tokens=3)
    m3 = eng.metrics(reset=True)
    assert m3["tokens_out"] == 3 and m3["requests_completed"] == 1
    snap = eng.telemetry_snapshot()
    assert set(snap) >= {"prometheus_sha256", "prometheus_lines",
                         "span_counts", "spans_dropped", "compile_count",
                         "recompiles"}
    assert snap["compile_count"] == 1 and snap["recompiles"] == 0


def test_engine_telemetry_off_keeps_metrics_drops_spans():
    cfg, model, params = make_model()
    eng = engine_of(model, params, telemetry=False)
    eng.generate(prompts_of(cfg, [5]), max_new_tokens=4)
    assert isinstance(eng.tracer, NullRecorder)
    assert eng.tracer.span_counts() == {}
    m = eng.metrics()
    assert m["tokens_out"] == 4  # registry stays real: metrics intact
    assert m["recompiles"] == 0
    with pytest.raises(RuntimeError):
        eng.write_trace("/tmp/never.json")


# ------------------------------------------------------------------ degrade


def test_tensorboard_writer_degrades_without_extra(tmp_path, monkeypatch,
                                                   caplog):
    # Simulate a box without the tensorboard extra: a None sys.modules
    # entry makes the lazy import raise.
    monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)
    w = TensorBoardScalarWriter(str(tmp_path / "tb"))
    assert w.available is False
    w.add_scalar("loss", 1.0, 0)  # must not raise
    reg = MetricsRegistry()
    reg.counter("n").inc(1)
    w.publish(reg, step=0)
    w.flush()
    w.close()
    assert not (tmp_path / "tb").exists()  # true no-op


def test_import_without_extras(tmp_path):
    """``import deepspeed_tpu.telemetry`` must succeed without the
    tensorboard/prometheus extras — nothing optional imports at module
    load (jax itself is lazy too: the telemetry package alone imports
    clean even with jax blocked)."""
    import subprocess

    code = ("import sys; "
            "sys.modules['torch.utils.tensorboard'] = None; "
            "sys.modules['prometheus_client'] = None; "
            "import deepspeed_tpu.telemetry as t; "
            "r = t.MetricsRegistry(); r.counter('ok').inc(1); "
            "print(t.prometheus_text(r).strip().splitlines()[-1])")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().endswith("ds_tpu_ok_total 1")


# ------------------------------------------------------ merged registry


def test_merged_registry_replica_labeled_series_parse():
    """The fleet's aggregate view at the PARSER level: one correctly
    labeled series per replica per metric, kind lines intact, counter
    semantics preserved — through the same minimal parser the plain
    exposition test uses, so a label-merge regression fails here."""
    regs = {}
    for rid in (0, 1):
        reg = MetricsRegistry(engine="inference", replica=str(rid))
        reg.counter("tokens_out").inc(10 * (rid + 1))
        reg.gauge("queue_depth").set(rid + 3)
        reg.histogram("ttft").observe(0.5 * (rid + 1))
        regs[rid] = reg
    merged = MergedRegistry(regs)
    kinds, samples = _parse_prom(prometheus_text(merged))
    assert kinds["ds_tpu_tokens_out_total"] == "counter"
    assert kinds["ds_tpu_queue_depth"] == "gauge"
    assert kinds["ds_tpu_ttft"] == "summary"
    for rid in (0, 1):
        lbl = (("engine", "inference"), ("replica", str(rid)))
        assert samples[("ds_tpu_tokens_out_total", lbl)] == 10 * (rid + 1)
        assert samples[("ds_tpu_queue_depth", lbl)] == rid + 3
        assert samples[("ds_tpu_ttft_count", lbl)] == 1
    # Children WITHOUT a replica const label get one injected from the
    # merge axis — the fleet works with pre-PR-8 engine registries too.
    plain = {7: MetricsRegistry(engine="inference")}
    plain[7].counter("tokens_out").inc(5)
    _, injected = _parse_prom(prometheus_text(MergedRegistry(plain)))
    lbl = (("engine", "inference"), ("replica", "7"))
    assert injected[("ds_tpu_tokens_out_total", lbl)] == 5
    # snapshot() keys carry the per-replica label; the common const
    # label (engine) is elided exactly like MetricsRegistry does.
    snap = merged.snapshot()
    assert snap["tokens_out{replica=0}"] == 10
    assert snap["tokens_out{replica=1}"] == 20
    assert not any("engine=" in k for k in snap)


def test_merged_registry_read_only_escaping_and_kind_conflict():
    bad = MetricsRegistry(engine="inference", replica='a"b\\c\n')
    bad.counter("tokens_out").inc(1)
    merged = MergedRegistry({0: bad})
    text = prometheus_text(merged)
    # The exporter's escaping survives the merge's label wrapping:
    # backslash, quote, and newline all escape inside the label value.
    assert 'replica="a\\"b\\\\c\\n"' in text
    assert "\n\n" not in text.strip()
    with pytest.raises(TypeError):
        merged.counter("x")
    with pytest.raises(TypeError):
        merged.gauge("x")
    with pytest.raises(TypeError):
        merged.histogram("x")
    # One name, one kind — fleet-wide.
    a, b = MetricsRegistry(replica="0"), MetricsRegistry(replica="1")
    a.counter("depth").inc(1)
    b.gauge("depth").set(2)
    with pytest.raises(TypeError):
        list(MergedRegistry({0: a, 1: b}).collect())
    # reset_window() reaches every child (counter windows reopen;
    # totals never rewind).
    merged.reset_window()
    _, after = _parse_prom(prometheus_text(merged))
    assert after[("ds_tpu_tokens_out_total",
                  (("engine", "inference"),
                   ("replica", 'a\\"b\\\\c\\n')))] == 1


# ------------------------------------------------ distributed trace parser


def _two_site_recorders():
    """Donor/acceptor recorder pair sharing one TraceContext: one paired
    handoff flow, one key that never lands (a fallback) — the minimal
    cross-replica story for the parser-level contract."""
    ticks = itertools.count()

    def clock():
        return next(ticks) * 0.001

    donor = SpanRecorder(capacity=64, clock=clock)
    acceptor = SpanRecorder(capacity=64, clock=clock)
    ctx = TraceContext(1_000_003, origin="fleet")
    # The prefill-role donor's lane: every slice dispatched is a hop, the
    # prompt's last one twice (docs/OBSERVABILITY.md, the hop table).
    donor.instant("request/slice", tid=ctx.tid, hop=ctx.hop(), slices=1)
    donor.instant("request/last_slice", tid=ctx.tid, hop=ctx.hop(),
                  slices=1)
    donor.span("request/prefill", start=clock(), tid=ctx.tid,
               hop=ctx.hop())
    donor.instant("request/handoff", tid=ctx.tid, hop=ctx.hop(),
                  flow_out="handoff/1000003/1")
    donor.instant("request/handoff", tid=ctx.tid, hop=ctx.hop(),
                  flow_out="handoff/1000003/fallback")     # never lands
    acceptor.instant("request/handoff_in", tid=ctx.tid, hop=ctx.hop(),
                     flow_in="handoff/1000003/1")
    acceptor.span("request/decode", start=clock(), tid=ctx.tid,
                  hop=ctx.hop())
    return donor, acceptor


def test_merged_trace_flow_pairs_cross_pid_ts_sorted_at_parser_level():
    """The merged trace read back the way Perfetto would: JSON
    round-trip, ts-sorted rows, named process tracks, and exactly one
    s/f flow pair — shared id and name, start on the donor pid, finish
    on the acceptor pid at a ts no earlier than the start. The unpaired
    fallback key draws no arrow."""
    donor, acceptor = _two_site_recorders()
    trace = merged_trace({"replica0": donor, "replica1": acceptor})
    n = validate_trace(trace)
    events = json.loads(json.dumps(trace))["traceEvents"]
    assert n == len(events) > 0
    rows = [e for e in events if e["ph"] != "M"]
    assert rows == sorted(rows, key=lambda e: e["ts"])
    pids = {e["pid"]: e["args"]["name"] for e in events
            if e["ph"] == "M" and e["name"] == "process_name"}
    assert sorted(pids.values()) == ["replica0", "replica1"]
    starts = [e for e in events if e["ph"] == "s"]
    finishes = [e for e in events if e["ph"] == "f"]
    assert len(starts) == 1 and len(finishes) == 1
    s, f = starts[0], finishes[0]
    assert s["id"] == f["id"]
    assert s["name"] == f["name"] == "flow/handoff"
    assert s["pid"] != f["pid"]
    assert pids[s["pid"]] == "replica0" and pids[f["pid"]] == "replica1"
    assert f["ts"] >= s["ts"] and f["bp"] == "e"
    # Every request event rides the propagated tid, hop-stamped.
    hops = [e["args"]["hop"] for e in rows
            if e["ph"] in ("X", "i") and e["tid"] == 1_000_003]
    assert sorted(hops) == list(range(7))


def test_validate_trace_rejects_malformed_traces():
    """Each schema clause individually: the validator is the gate
    write_merged_trace and bin/lint.sh rely on, so every malformation
    must raise TraceError, not slip into a file Perfetto rejects at
    2am."""
    ok = {"name": "a", "ph": "X", "ts": 0.0, "dur": 1.0,
          "pid": 0, "tid": 1}
    assert validate_trace({"traceEvents": [ok]}) == 1
    # Counter tracks are per-process: "C" needs no tid, all else does.
    assert validate_trace({"traceEvents": [
        {"name": "queue_depth", "ph": "C", "ts": 0.0, "pid": 0,
         "args": {"value": 1.0}}]}) == 1

    def bad(events):
        with pytest.raises(TraceError):
            validate_trace({"traceEvents": events})

    with pytest.raises(TraceError):
        validate_trace([ok])                       # not a trace object
    bad("not a list")
    bad([{**ok, "ph": "Q"}])                       # unknown phase
    bad([{**ok, "name": ""}])                      # empty name
    bad([{k: v for k, v in ok.items() if k != "pid"}])
    bad([{k: v for k, v in ok.items() if k != "tid"}])
    bad([{**ok, "ts": "now"}])                     # non-numeric ts
    bad([{**ok, "ts": 5.0}, ok])                   # ts goes backwards
    bad([{**ok, "dur": -1.0}])                     # negative span dur
    bad([{k: v for k, v in ok.items() if k != "dur"}])
    bad([{"name": "i", "ph": "i", "ts": 0.0, "pid": 0, "tid": 1}])
    flow = {"name": "flow/h", "ph": "s", "id": 1, "ts": 0.0,
            "pid": 0, "tid": 1}
    bad([{k: v for k, v in flow.items() if k != "id"}])
    bad([flow])                                    # start, no finish
    bad([flow, {**flow, "ts": 1.0}])               # duplicate start
    bad([{**flow, "ph": "f"}])                     # finish, no start
    bad([flow, {**flow, "ph": "f", "name": "flow/x", "ts": 1.0}])
    bad([{**flow, "ph": "f"}, {**flow, "ts": 1.0}])   # finish < start
    # The well-formed pair still passes with the same parser.
    assert validate_trace({"traceEvents": [
        flow, {**flow, "ph": "f", "bp": "e", "ts": 1.0}]}) == 2


def test_trace_spans_dropped_rides_merge_with_replica_label():
    """Satellite: span-ring overflow is a live per-replica series. An
    engine with a tiny trace ring overflows during one run; the gauge
    reads the recorder's exact drop count bare, through Prometheus, and
    through a MergedRegistry with the replica label injected — so a
    truncated autopsy is visible from the same scrape as the alert."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, trace_ring=8)
    for p in prompts_of(cfg, [5, 9, 7]):
        eng.submit(p, max_new_tokens=4)
    eng.run()
    dropped = eng.tracer.dropped
    assert dropped > 0 and len(eng.tracer.events()) == 8
    assert eng.telemetry.snapshot()["trace_spans_dropped"] == dropped
    kinds, samples = _parse_prom(eng.prometheus())
    assert kinds["ds_tpu_trace_spans_dropped"] == "gauge"
    lbl = (("engine", "inference"),)
    assert samples[("ds_tpu_trace_spans_dropped", lbl)] == dropped
    _, merged = _parse_prom(prometheus_text(
        MergedRegistry({0: eng.telemetry})))
    lbl = (("engine", "inference"), ("replica", "0"))
    assert merged[("ds_tpu_trace_spans_dropped", lbl)] == dropped
