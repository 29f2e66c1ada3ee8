"""Telemetry overhead guard — observability must be ~free on the hot path.

The contract under test:
1. NO RECOMPILES — telemetry on vs off runs the IDENTICAL compiled
   program set: same compile_count, zero post-warmup recompiles either
   way (annotations and spans are host-side; nothing telemetry does may
   perturb tracing).
2. A FIXED BUDGET OF HOST WORK — spans + annotations, and distributed
   tracing with a ticking collector and alert rules on top, add a
   counted number of ring events to a step and no registry write:
   counts, which repeat on any CPU (what they cost in host time is a
   device-side reading, ``*.host_ms_step`` in ``PERF.md``).
3. PERF X-RAY — the observatory's per-step stash stays within 5% of
   xray-off, best of ten PAIRED rounds (one clean round proves it).
"""

import time

import pytest

from tests.unit.test_chunked_prefill import (
    engine_of,
    make_model,
    prompts_of,
)


def _steady_engine(model, params, telemetry):
    """A warmed engine holding one slot mid-decode: each step() is then
    a pure decode step of the compiled mixed program — the hot path the
    overhead bound is about."""
    eng = engine_of(model, params, telemetry=telemetry, max_slots=2)
    eng.generate([prompts_of(make_model()[0], [5])[0]],
                 max_new_tokens=2)  # warmup: compile + first harvest
    return eng


def _one_run(eng, prompt, steps):
    """Seconds for ``steps`` decode steps at steady state."""
    r = eng.submit(prompt, max_new_tokens=steps + 2)
    eng.step()  # prefill + first token: outside the timed window
    t0 = time.perf_counter()
    for _ in range(steps):
        eng.step()
    dt = time.perf_counter() - t0
    while not r.done:
        eng.step()
    return dt


def _count_registry_writes(monkeypatch):
    """Count every ``Counter.inc`` / ``Gauge.set`` / ``Histogram.observe``
    made anywhere in the process while the patch lives; returns the
    one-element list the count accumulates in."""
    from deepspeed_tpu.telemetry import registry

    writes = [0]
    for cls, method in ((registry.Counter, "inc"), (registry.Gauge, "set"),
                        (registry.Histogram, "observe")):
        def counted(self, *args, _inner=getattr(cls, method), **kw):
            writes[0] += 1
            return _inner(self, *args, **kw)
        monkeypatch.setattr(cls, method, counted)
    return writes


def _one_counted_run(eng, prompt, steps, writes, trace=None, per_step=None):
    """(registry writes, ring events) of ``steps`` steady decode steps:
    one slot decoding in every one of them (the budget outlasts the
    loop), ``per_step()`` called after each as a fleet replica's drive
    loop calls its collector and alert rules."""
    # The first step decodes a chunk too: a budget of steps + 1 of them
    # and a little more is still open when the counted loop ends.
    r = eng.submit(prompt,
                   max_new_tokens=(steps + 1) * eng.config.chunk_size + 2,
                   trace=trace)
    eng.step()  # prefill + first token: outside the counted window
    w0, e0 = writes[0], sum(eng.tracer.span_counts().values())
    for _ in range(steps):
        eng.step()
        if per_step is not None:
            per_step()
    counted = (writes[0] - w0, sum(eng.tracer.span_counts().values()) - e0)
    assert r.phase == "decoding"
    while not r.done:
        eng.step()
    return counted


def test_telemetry_adds_no_recompiles_and_bounded_host_overhead(monkeypatch):
    cfg, model, params = make_model()
    prompt = prompts_of(cfg, [6])[0]
    steps = 12

    on = _steady_engine(model, params, telemetry=True)
    off = _steady_engine(model, params, telemetry=False)
    assert on.compile_count == off.compile_count == 1

    writes = _count_registry_writes(monkeypatch)
    w_on, e_on = _one_counted_run(on, prompt, steps, writes)
    w_off, e_off = _one_counted_run(off, prompt, steps, writes)

    # Identical program set, still zero recompiles after the runs.
    assert on.compile_count == off.compile_count == 1
    assert on.metrics()["recompiles"] == 0
    assert off.metrics()["recompiles"] == 0

    # Host overhead bound, as the work telemetry adds to a steady decode
    # step: the five step-phase spans and one ``request/chunk`` instant
    # for the one emitting slot, each one ring append; the registry is
    # written the same number of times either way (counters are the
    # engine's own bookkeeping).
    assert e_on == steps * (5 + 1) and e_off == 0
    assert w_on == w_off > 0

    # The on-engine actually recorded: the comparison was not no-op
    # against no-op.
    counts = on.tracer.span_counts()
    assert counts.get("inference/mixed_step", 0) > 0
    assert off.tracer.span_counts() == {}


def test_telemetry_import_is_extras_free():
    """Belt-and-braces for CI images without optional extras: the
    telemetry package import must not pull tensorboard or any exporter
    dependency at module-load time (the deep check — subprocess with
    blocked modules — lives in test_telemetry.py)."""
    import importlib

    import deepspeed_tpu.telemetry as t

    importlib.reload(t)  # module-load path runs clean with no extras
    reg = t.MetricsRegistry()
    reg.counter("ok").inc(1)
    assert "ds_tpu_ok_total 1" in t.prometheus_text(reg)
    # TensorBoard is lazy: constructing the writer must not import it.
    w = t.TensorBoardScalarWriter("/tmp/never-used")
    assert w._writer is None and w._dead is False


def test_distributed_tracing_and_alerts_hold_the_overhead_gate(monkeypatch):
    """PR-14 gate, as counts: distributed tracing ON (propagated
    TraceContext with hop stamping, flow-capable span ring) plus a
    ticking TimeseriesCollector and per-step AlertManager evaluation,
    against telemetry fully off. Same compiled program set (1 program,
    0 recompiles — tracing is host-side only), and what the path ADDS to
    a steady decode step is a fixed budget: the five step-phase spans
    and one ``request/chunk`` instant per emitting slot in the ring, and
    not one registry write beyond what the engine makes with telemetry
    off. (What that costs in host time is a latency and belongs to a
    chip cell: PERF.md section 7.)"""
    from deepspeed_tpu.telemetry import AlertManager, TimeseriesCollector
    from deepspeed_tpu.telemetry import TraceContext, default_rules
    from deepspeed_tpu.telemetry.distributed import FLEET_TID_BASE

    cfg, model, params = make_model()
    prompt = prompts_of(cfg, [6])[0]
    steps, per_window = 12, 4

    on = _steady_engine(model, params, telemetry=True)
    off = _steady_engine(model, params, telemetry=False)
    assert on.compile_count == off.compile_count == 1

    # The collector's windows close on a clock the test advances: one
    # second a step, so every ``per_window`` steps, whatever the CPU.
    now = [0.0]
    collector = TimeseriesCollector(on.telemetry, window_seconds=per_window,
                                    clock=lambda: now[0])
    collector.start()
    alerts = AlertManager(collector, default_rules())

    def drive_loop_hooks():
        now[0] += 1.0
        collector.tick()
        alerts.evaluate()

    writes = _count_registry_writes(monkeypatch)
    w_on, e_on = _one_counted_run(
        on, prompt, steps, writes,
        trace=TraceContext(FLEET_TID_BASE, origin="fleet"),
        per_step=drive_loop_hooks)
    w_off, e_off = _one_counted_run(off, prompt, steps, writes)

    # Tracing + alerting changed NOTHING the compiler sees.
    assert on.compile_count == off.compile_count == 1
    assert on.metrics()["recompiles"] == 0

    assert e_off == 0
    assert e_on == steps * (5 + 1), (
        "tracing put {} events in the ring over {} steps".format(
            e_on, steps))
    assert w_on == w_off > 0, (
        "tracing+alerts made {} registry writes against {} with "
        "telemetry off".format(w_on, w_off))
    assert on.tracer.dropped == 0

    # The propagated context actually rode the hot path: the fleet-base
    # tid shows up hop-stamped in the ring, in order.
    hops = [ev["args"]["hop"] for ev in on.tracer.events()
            if ev.get("tid") == FLEET_TID_BASE]
    assert hops == sorted(hops) and len(hops) >= steps
    # ...and the alert machinery evaluated every window the clock closed.
    assert len(collector.windows()) == steps // per_window
    assert alerts.to_json()["windows_evaluated"] == steps // per_window


def test_perf_xray_holds_the_overhead_gate():
    """Perf-xray gate (this PR): the observatory ON (per-step stash +
    1-in-N sampled decomposition) against perf_xray=False, same compiled
    program set and the same <5% host budget. The export itself — which
    AOT-compiles every program for cost analysis — must add ZERO
    dispatch-cache compiles and zero recompile events."""
    cfg, model, params = make_model()
    prompt = prompts_of(cfg, [6])[0]

    on = _steady_engine(model, params, telemetry=True)
    off = engine_of(model, params, telemetry=True, max_slots=2,
                    perf_xray=False)
    off.generate([prompts_of(make_model()[0], [5])[0]], max_new_tokens=2)
    assert on.compile_count == off.compile_count == 1

    # Paired min-of-ratios: the xray fast path costs ~1% of a tiny-
    # model CPU step (identity-memoized signature), but independent
    # min-of-N floors for the two sides can drift apart by more than
    # the 5% budget on a noisy box. Pairing each on-run with an
    # immediately following off-run and bounding the BEST round's
    # ratio cancels machine drift: one clean round proves the true
    # overhead is inside the budget.
    _one_run(on, prompt, steps=16)   # loop warmup, untimed
    _one_run(off, prompt, steps=16)
    ratio = float("inf")
    for _ in range(10):
        ratio = min(ratio, _one_run(on, prompt, steps=16)
                    / _one_run(off, prompt, steps=16))

    assert on.compile_count == off.compile_count == 1
    assert on.metrics()["recompiles"] == 0

    assert ratio <= 1.05, (
        "perf-xray best paired on/off step-time ratio {:.3f} "
        "(> +5%)".format(ratio))

    # The observatory genuinely observed the hot path...
    assert on.telemetry_snapshot()["xray_programs"] >= 1
    # ...and a full export (AOT lower+compile of what was dispatched)
    # perturbs nothing the dispatch caches or detector see.
    out = on.perf_xray()
    assert len([p for p in out["programs"] if not p["superseded"]]) >= 1
    assert on.compile_count == 1
    assert on.metrics()["recompiles"] == 0
    assert out["recompiles"] == []
