"""Perf X-ray (telemetry/xray.py): the compiled-program cost/memory
observatory.

Covers the ISSUE's acceptance surface on the CPU tier-1 path:
- fingerprint + cost-analysis DETERMINISM (same program, same shapes ->
  same record; a shape change is a new identity),
- parser-level Prometheus exposition of the ds_tpu_xray_* / ds_tpu_hbm_*
  families, including label escaping and fleet replica labels through
  MergedRegistry,
- the honesty rule: NO MFU/MBU/roofline gauges on a platform without a
  peaks row; utilization appears only with peaks AND a sampled step,
- HBM ledger arithmetic and its CPU behavior (pressure 0 when capacity
  is unknown — the default alert rule can then never fire),
- cost_model_gate: A/A clean, 2x bytes flagged, improvement recorded,
  platform/schema mismatch caveats,
- the serving-engine integration: a perf_xray() export covers the
  programs with nonzero flops and predicted peak HBM, adds NO compiles
  to the jit dispatch caches and NO recompile events, and the
  RecompileDetector warning + autopsy share the xray identity key.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.telemetry import (
    MergedRegistry,
    MetricsRegistry,
    RecompileDetector,
    prometheus_text,
)
from deepspeed_tpu.telemetry.xray import (
    DEVICE_PEAKS,
    SCHEMA_VERSION,
    HBMLedger,
    ProgramRegistry,
    _self_check,
    _shapes_of,
    _signature,
    cost_model_gate,
)
from tests.unit.test_telemetry import _parse_prom


def _toy():
    fn = jax.jit(lambda a, b: jnp.tanh(a @ b).sum())
    x = jnp.ones((8, 16), jnp.float32)
    y = jnp.ones((16, 4), jnp.float32)
    return fn, x, y


# ------------------------------------------------------------ identity


def test_signature_separates_shapes_dtypes_and_statics():
    x = jnp.ones((2, 3), jnp.int32)
    sig = _signature((x, 7, "mode"), {})
    assert sig[0] == ((2, 3), "int32")
    assert sig[1][0] == "static" and sig[1][1] == "int"
    assert _shapes_of(sig)[0] == "int32[2,3]"
    assert _shapes_of(sig)[1] == "static:int"
    # Same shapes -> same signature; different shape -> different.
    assert _signature((x, 7, "mode"), {}) == sig
    assert _signature((jnp.ones((2, 4), jnp.int32), 7, "mode"), {}) != sig


def test_fingerprint_and_cost_are_deterministic_across_registries():
    fn, x, y = _toy()
    r1 = ProgramRegistry().observe("p", fn, x, y, tokens=1)
    r2 = ProgramRegistry().observe("p", fn, x, y, tokens=1)
    assert r1["fingerprint"] and r1["fingerprint"] == r2["fingerprint"]
    assert r1["flops"] > 0 and r1["flops"] == r2["flops"]
    assert r1["bytes_accessed"] > 0
    assert r1["bytes_accessed"] == r2["bytes_accessed"]
    assert r1["error"] is None
    # A different input shape is a different program identity.
    r3 = ProgramRegistry().observe(
        "p", fn, jnp.ones((4, 16), jnp.float32), y, tokens=1)
    assert r3["fingerprint"] != r1["fingerprint"]


def test_stash_fast_path_and_recompile_events_resolve():
    fn, x, y = _toy()
    xr = ProgramRegistry()
    assert xr.stash("p", fn, x, y) is True           # first capture
    assert xr.stash("p", fn, x, y) is False          # steady state
    assert xr.recompile_events == []
    # A signature change WITH track_change is a recompile event whose
    # shapes are exact immediately; fingerprints resolve at export.
    x2 = jnp.ones((4, 16), jnp.float32)
    assert xr.stash("p", fn, x2, y, track_change=True) is True
    (ev,) = xr.recompile_events
    assert ev["program"] == "p"
    assert ev["old_shapes"][0] == "float32[8,16]"
    assert ev["new_shapes"][0] == "float32[4,16]"
    assert ev["new_fingerprint"] is None             # not yet compiled
    (resolved,) = xr.recompile_dicts()               # materializes
    assert resolved["old_fingerprint"] and resolved["new_fingerprint"]
    assert resolved["old_fingerprint"] != resolved["new_fingerprint"]
    # identity() names old -> new without compiling anything further.
    ident = xr.identity("p")
    assert "->" in ident and "float32[4,16]" in ident


def test_stash_seen_signature_flips_never_accumulate_or_log():
    """Legacy-path bucket variety: alternating between two warm prompt
    buckets must not grow the stash chain (each holds an abstracted
    params tree) nor log recompile events — both signatures are in the
    jit cache, so a flip back is not a recompile."""
    fn, x, y = _toy()
    x2 = jnp.ones((4, 16), jnp.float32)
    xr = ProgramRegistry()
    assert xr.stash("p", fn, x, y, track_change=True) is True
    assert xr.stash("p", fn, x2, y, track_change=True) is True
    assert len(xr.recompile_events) == 1    # genuinely new signature
    for _ in range(50):
        assert xr.stash("p", fn, x, y, track_change=True) is True
        assert xr.stash("p", fn, x2, y, track_change=True) is True
    assert len(xr._programs["p"]) == 2      # one stash per signature
    assert len(xr.recompile_events) == 1    # no event per flip
    assert xr.recompile_events_dropped == 0
    # A genuinely NEW third signature still captures and logs.
    x3 = jnp.ones((2, 16), jnp.float32)
    assert xr.stash("p", fn, x3, y, track_change=True) is True
    assert len(xr._programs["p"]) == 3
    assert len(xr.recompile_events) == 2


def test_recompile_events_are_capped_not_unbounded():
    from deepspeed_tpu.telemetry.xray import RECOMPILE_EVENT_CAP

    fn, _, y = _toy()
    xr = ProgramRegistry()
    n = RECOMPILE_EVENT_CAP + 6
    for i in range(1, n + 2):
        xr.stash("p", fn, jnp.ones((i, 16), jnp.float32), y,
                 track_change=True)
    assert len(xr.recompile_events) == RECOMPILE_EVENT_CAP
    assert xr.recompile_events_dropped == n - RECOMPILE_EVENT_CAP


def test_note_attributes_calls_and_cost_per_signature():
    """Cost totals bill each signature's record for ITS OWN calls —
    a label cycling buckets must not attribute the latest signature's
    cost to every historical call."""
    fn, x, y = _toy()
    x2 = jnp.ones((4, 16), jnp.float32)
    xr = ProgramRegistry()
    xr.stash("p", fn, x, y)
    xr.note("p", tokens=2)
    xr.note("p", tokens=2)
    xr.stash("p", fn, x2, y)
    xr.note("p", tokens=8)
    section = xr.to_json()
    big = next(e for e in section["programs"]
               if "float32[8,16]" in e["input_shapes"][0])
    small = next(e for e in section["programs"]
                 if "float32[4,16]" in e["input_shapes"][0])
    assert big["superseded"] and not small["superseded"]
    assert (big["calls"], big["tokens"]) == (2, 4)
    assert (small["calls"], small["tokens"]) == (1, 8)
    t = section["totals"]
    assert t["calls"] == 3 and t["tokens"] == 12
    assert t["flops_total"] == pytest.approx(
        big["flops"] * 2 + small["flops"] * 1)
    assert t["bytes_total"] == pytest.approx(
        big["bytes_accessed"] * 2 + small["bytes_accessed"] * 1)
    # Flipping BACK re-activates the first signature; its accounting
    # resumes where it left off.
    xr.stash("p", fn, x, y)
    xr.note("p", tokens=1)
    section2 = xr.to_json()
    big2 = next(e for e in section2["programs"]
                if "float32[8,16]" in e["input_shapes"][0])
    assert not big2["superseded"]
    assert (big2["calls"], big2["tokens"]) == (3, 5)


# ----------------------------------------------------------- prometheus


def test_xray_gauges_at_parser_level_no_fabricated_mfu():
    """CPU (no peaks row): cost facts publish with platform labels,
    utilization gauges DO NOT exist."""
    fn, x, y = _toy()
    reg = MetricsRegistry(engine="inference")
    xr = ProgramRegistry(reg, platform="cpu")
    xr.observe("mixed_step", fn, x, y, tokens=4)
    kinds, samples = _parse_prom(prometheus_text(reg))
    assert kinds["ds_tpu_xray_flops"] == "gauge"
    lbl = (("engine", "inference"), ("platform", "cpu"),
           ("program", "mixed_step"))
    assert samples[("ds_tpu_xray_flops", lbl)] > 0
    assert samples[("ds_tpu_xray_bytes_accessed", lbl)] > 0
    assert samples[("ds_tpu_xray_peak_hbm_bytes", lbl)] > 0
    for fabricated in ("ds_tpu_xray_mfu", "ds_tpu_xray_mbu",
                       "ds_tpu_xray_roofline_ratio"):
        assert fabricated not in kinds


def test_xray_roofline_gauges_with_peaks_and_observed_step():
    fn, x, y = _toy()
    reg = MetricsRegistry()
    peaks = {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9, "source": "test"}
    xr = ProgramRegistry(reg, platform="tpu", peaks=peaks)
    xr.observe("mixed_step", fn, x, y, tokens=4)
    _, before = _parse_prom(prometheus_text(reg))
    lbl = (("platform", "tpu"), ("program", "mixed_step"))
    # Gauges exist but read 0 until a step has actually been OBSERVED —
    # utilization against an unmeasured step time would be fabricated.
    assert before[("ds_tpu_xray_mfu", lbl)] == 0.0
    xr.observe_step("mixed_step", dispatch_s=0.001, wait_s=0.004)
    kinds, samples = _parse_prom(prometheus_text(reg))
    assert samples[("ds_tpu_xray_mfu", lbl)] > 0
    assert samples[("ds_tpu_xray_mbu", lbl)] > 0
    assert samples[("ds_tpu_xray_roofline_ratio", lbl)] > 0
    # The decomposition histograms recorded the two span durations.
    assert kinds["ds_tpu_xray_host_dispatch_seconds"] == "summary"
    assert samples[("ds_tpu_xray_device_wait_seconds_count",
                    (("program", "mixed_step"),))] == 1


def test_xray_label_escaping_survives_exposition():
    fn, x, y = _toy()
    reg = MetricsRegistry()
    xr = ProgramRegistry(reg, platform="cpu")
    xr.observe('train[bs=8,"mixed"]\n', fn, x, y)
    text = prometheus_text(reg)
    assert 'program="train[bs=8,\\"mixed\\"]\\n"' in text


def test_xray_series_carry_replica_labels_through_merge():
    """Fleet view: each replica's ProgramRegistry publishes into its own
    replica-labeled MetricsRegistry; MergedRegistry keeps the series
    separate at the parser level."""
    fn, x, y = _toy()
    regs = {}
    for rid in (0, 1):
        reg = MetricsRegistry(engine="inference", replica=str(rid))
        ProgramRegistry(reg, platform="cpu").observe(
            "mixed_step", fn, x, y)
        regs[rid] = reg
    _, samples = _parse_prom(prometheus_text(MergedRegistry(regs)))
    for rid in (0, 1):
        lbl = (("engine", "inference"), ("platform", "cpu"),
               ("program", "mixed_step"), ("replica", str(rid)))
        assert samples[("ds_tpu_xray_flops", lbl)] > 0


# -------------------------------------------------------- decomposition


def test_observe_step_counts_every_step_and_syncs_nothing(monkeypatch):
    """The split is fed on every step from durations the caller already
    has: no sampling cadence, and no device sync of its own."""
    def no_sync(*_a, **_k):
        raise AssertionError("observe_step must not sync the device")
    monkeypatch.setattr(jax, "block_until_ready", no_sync)
    xr = ProgramRegistry()
    for i in range(7):
        assert xr.observe_step("p", 0.001, 0.002 + i) == \
            pytest.approx(0.003 + i)
    assert xr.to_json()["decomposition"]["p"]["samples"] == 7
    assert not hasattr(xr, "due") and not hasattr(xr, "sample_step")


def test_decomposition_lands_in_export():
    fn, x, y = _toy()
    xr = ProgramRegistry()
    xr.observe("p", fn, x, y, tokens=2)
    xr.observe_step("p", dispatch_s=0.002, wait_s=0.010)
    xr.observe_step("p", dispatch_s=0.001, wait_s=0.020)
    section = xr.to_json()
    d = section["decomposition"]["p"]
    assert d["samples"] == 2
    assert d["host_dispatch_s"] == pytest.approx(0.003)
    assert d["device_wait_s"] == pytest.approx(0.030)
    (entry,) = [e for e in section["programs"] if not e["superseded"]]
    # EWMA of the observed totals: 0.8 * 0.012 + 0.2 * 0.021
    assert entry["sampled_step_seconds"] == pytest.approx(0.0138)


# --------------------------------------------------------------- ledger


def test_hbm_ledger_math_and_prometheus_families():
    reg = MetricsRegistry()
    led = HBMLedger(reg, capacity_bytes=1000)
    led.set_component("params", 500)
    led.set_component("kv_arena", lambda: 200)
    assert led.predicted() == 700
    assert led.capacity() == 1000
    assert led.pressure() == pytest.approx(0.7)
    # CPU has no memory_stats: live is None and headroom falls back to
    # the prediction.
    assert led.live() is None
    assert led.headroom() == 300
    kinds, samples = _parse_prom(prometheus_text(reg))
    assert samples[("ds_tpu_hbm_predicted_bytes", ())] == 700
    assert samples[("ds_tpu_hbm_pressure", ())] == pytest.approx(0.7)
    assert samples[("ds_tpu_hbm_headroom_bytes", ())] == 300
    # live gauge is only published when the backend can answer.
    assert "ds_tpu_hbm_live_bytes" not in kinds
    j = led.to_json()
    assert j["predicted_bytes"] == 700 and j["pressure"] == 0.7


def test_hbm_ledger_unknown_capacity_reads_zero_pressure():
    """The default hbm_pressure alert rule must be unable to fire on a
    backend that cannot state its capacity (CPU without a configured
    budget)."""
    reg = MetricsRegistry()
    led = HBMLedger(reg)
    led.set_component("params", 10**12)   # a terabyte of "prediction"
    assert led.capacity() is None
    assert led.pressure() == 0.0
    assert led.headroom() is None
    kinds, samples = _parse_prom(prometheus_text(reg))
    assert samples[("ds_tpu_hbm_pressure", ())] == 0.0
    assert "ds_tpu_hbm_headroom_bytes" not in kinds


# ----------------------------------------------------------------- gate


def _section(**overrides):
    fn, x, y = _toy()
    xr = ProgramRegistry(platform="cpu")
    xr.observe("mixed_step", fn, x, y, tokens=8)
    out = xr.to_json()
    out.update(overrides)
    return out


def test_cost_model_gate_aa_passes_clean():
    a = _section()
    g = cost_model_gate(a, a)
    assert g["pass"] and not g["flagged"] and not g["caveats"]


def test_cost_model_gate_flags_2x_bytes_and_records_improvement():
    import copy

    a = _section()
    worse = copy.deepcopy(a)
    for e in worse["programs"]:
        e["bytes_accessed"] *= 2
    worse["totals"]["bytes_per_token"] *= 2
    g = cost_model_gate(a, worse)
    assert not g["pass"]
    assert any("bytes_accessed" in f for f in g["flagged"])
    assert any("totals.bytes_per_token" in f for f in g["flagged"])
    better = copy.deepcopy(a)
    for e in better["programs"]:
        e["flops"] *= 0.5
    g2 = cost_model_gate(a, better)
    assert g2["pass"]
    assert any("flops" in s for s in g2["improved"])


def test_cost_model_gate_caveats_on_mismatched_context():
    a = _section()
    other_platform = _section(platform="tpu")
    g = cost_model_gate(a, other_platform)
    assert any("platform mismatch" in c for c in g["caveats"])
    other_schema = _section(schema_version=SCHEMA_VERSION + 1)
    g2 = cost_model_gate(a, other_schema)
    assert g2["pass"] and not g2["programs"]
    assert any("schema_version mismatch" in c for c in g2["caveats"])
    g3 = cost_model_gate(a, None)
    assert any("missing" in c for c in g3["caveats"])


def test_regression_gate_carries_cost_model_arm():
    """loadgen.regression_gate: when both reports embed perf_xray, the
    cost-model verdict folds into the overall pass."""
    import copy

    from deepspeed_tpu.loadgen.report import regression_gate

    base = {"schema_version": 99, "context": {}, "aggregate": {},
            "windows": [], "perf_xray": _section()}
    aa = regression_gate(base, base)
    assert aa["pass"] and aa["perf_xray"]["pass"]
    worse = copy.deepcopy(base)
    for e in worse["perf_xray"]["programs"]:
        e["bytes_accessed"] *= 2
    ab = regression_gate(base, worse)
    assert not ab["pass"] and not ab["perf_xray"]["pass"]
    # Reports without the section gate exactly as before.
    plain = {k: v for k, v in base.items() if k != "perf_xray"}
    assert "perf_xray" not in regression_gate(plain, plain)


# ----------------------------------------------------------- self-check


def test_module_self_check_passes():
    assert _self_check() == 0


def test_device_peaks_table_is_honest():
    # Every row states positive peaks with a source; the CPU has no row
    # (a registry on the CPU derives no utilization at all).
    for row in DEVICE_PEAKS.values():
        assert row["flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0
        assert row.get("source")
    assert "cpu" not in DEVICE_PEAKS
    assert ProgramRegistry(platform="cpu").peaks() is None


# ----------------------------------------------------- engine integration


def _serve_engine():
    from tests.unit.test_chunked_prefill import (
        engine_of,
        make_model,
        prompts_of,
    )

    cfg, model, params = make_model()
    eng = engine_of(model, params)
    eng.generate([prompts_of(cfg, [5])[0]], max_new_tokens=3)
    return eng


class _Compilations(object):
    """Counts the compilations asked for (``Lowered.compile``: the AOT
    path the observatory takes; a jit dispatch does not pass here)."""

    def __init__(self, monkeypatch):
        self.n = 0
        real = jax.stages.Lowered.compile

        def counted(lowered, *args, **kwargs):
            self.n += 1
            return real(lowered, *args, **kwargs)
        monkeypatch.setattr(jax.stages.Lowered, "compile", counted)


def test_engine_perf_xray_analyses_only_dispatched_programs(monkeypatch):
    eng = _serve_engine()
    compiles_before = eng.compile_count
    log = _Compilations(monkeypatch)
    out = eng.perf_xray()
    active = [p for p in out["programs"] if not p["superseded"]]
    # The engine dispatches one program, and the export compiles that
    # one and nothing it never ran.
    assert {p["program"] for p in active} == {"mixed_step"}
    assert log.n == 1
    for p in active:
        assert p["flops"] > 0, p
        assert p["peak_hbm_bytes"] > 0, p
        assert p["platform"] == "cpu"
    assert out["platform"] == "cpu" and out["peaks"] is None
    # The dispatched program carries real call/token accounting.
    mixed = next(p for p in active if p["program"] == "mixed_step")
    assert mixed["calls"] > 0 and mixed["tokens"] > 0
    assert out["totals"]["flops_per_token"] > 0
    assert out["totals"]["bytes_per_token"] > 0
    # The pool is donated into the mixed program; the export says so.
    assert "pool" in mixed["donated"]
    # HBM ledger rides along: params + kv_arena + program_temp, and the
    # program_temp component is live after materialization.
    assert out["hbm"]["components"]["params"] > 0
    assert out["hbm"]["components"]["kv_arena"] > 0
    assert out["hbm"]["predicted_bytes"] >= \
        out["hbm"]["components"]["params"]
    # The AOT observatory added NO dispatch-cache compiles and NO
    # recompile events — and the export is stable (same fingerprints).
    assert eng.compile_count == compiles_before
    assert out["recompiles"] == []
    assert eng.metrics()["recompiles"] == 0
    again = eng.perf_xray()
    assert log.n == 1  # the analysis is cached: a second export is free
    assert [p["fingerprint"] for p in again["programs"]] == \
        [p["fingerprint"] for p in out["programs"]]
    # Prometheus surface: cost gauges exist, utilization gauges do not.
    kinds, _ = _parse_prom(eng.prometheus())
    assert "ds_tpu_xray_flops" in kinds
    assert "ds_tpu_hbm_predicted_bytes" in kinds
    assert "ds_tpu_xray_mfu" not in kinds
    assert eng.telemetry_snapshot()["xray_programs"] == 1
    # The export also keeps each analysed program's instruction -> op_name
    # (the named_scope path), for a trace that does not embed the program.
    from deepspeed_tpu.telemetry import xray

    op_names = xray.OP_NAMES["jit_mixed_step"].values()
    assert any("/decode_scan/" in n and "/kv_write/" in n for n in op_names)
    assert any("/prefill_lane/" in n for n in op_names)


def test_engine_perf_xray_off_is_none():
    from tests.unit.test_chunked_prefill import engine_of, make_model

    cfg, model, params = make_model()
    eng = engine_of(model, params, perf_xray=False)
    eng.generate([np.arange(1, 6, dtype=np.int32)], max_new_tokens=2)
    assert eng.perf_xray() is None
    assert eng.telemetry_snapshot()["xray_programs"] == 0


def test_recompile_warning_and_autopsy_share_identity_key():
    """The detector's post-warm warning and the xray recompile record
    name the SAME program identity: fingerprint + old -> new shapes."""
    from deepspeed_tpu.utils.logging import logger as ds_logger

    fn, x, y = _toy()
    reg = MetricsRegistry()
    xr = ProgramRegistry(reg, platform="cpu")
    det = RecompileDetector(reg, describe=xr.identity)
    det.watch("p", fn)
    xr.stash("p", fn, x, y, track_change=det.warm)
    fn(x, y)
    det.mark_warm()
    # Post-warm shape change: stash FIRST (as the engine does), then the
    # dispatch that actually recompiles, then the boundary observe().
    x2 = jnp.ones((4, 16), jnp.float32)
    xr.stash("p", fn, x2, y, track_change=det.warm)
    fn(x2, y)

    # The package logger does not propagate to root (so caplog cannot
    # see it) — capture with a direct handler.
    class _Capture(logging.Handler):
        def __init__(self):
            logging.Handler.__init__(self)
            self.records = []

        def emit(self, record):
            self.records.append(record)

    cap = _Capture()
    ds_logger.addHandler(cap)
    try:
        assert det.observe() == 1
    finally:
        ds_logger.removeHandler(cap)
    (msg,) = [r.getMessage() for r in cap.records
              if "recompiled" in r.getMessage()]
    assert "float32[8,16]" in msg and "float32[4,16]" in msg
    assert "fingerprint" in msg
    # The autopsy-side record resolves the pending fingerprints to the
    # same old/new pair the identity string reports after materialize.
    (ev,) = xr.recompile_dicts()
    ident = xr.identity("p")
    assert ev["old_fingerprint"] in ident
    assert ev["new_fingerprint"] in ident
