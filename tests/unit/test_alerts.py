"""deepspeed_tpu.telemetry.alerts — SLO burn-rate rules over windows.

The contract under test:
1. RULES — each kind scored against synthetic window records: burn_rate
   estimates error from the windowed percentile ladder and fires only
   when BOTH lookbacks burn at >= threshold; saturation needs N
   CONSECUTIVE windows at the threshold; rate sums counters over real
   window durations. Labelled (MergedRegistry) series match their bare
   name and the worst series wins.
2. MANAGER — incremental over a real TimeseriesCollector with a manual
   clock: rising-edge-once ``fired()`` records, live ``alerts_firing``
   and per-rule ``alert_active`` gauges, firing clears on good windows,
   ``on_fire`` hooks run on the edge and a broken hook never raises.
3. EXPORT — the manager's own registry rides the standard Prometheus
   exposition, so a scrape shows alert state with no parallel wiring.

Windows are hand-driven (manual clocks everywhere) — no sleeps, no
timing sensitivity; the fleet-integration path (a rule firing under a
real saturating load and auto-dumping) lives in
tests/unit/test_distributed_trace.py.
"""

import pytest

from deepspeed_tpu.telemetry import (
    AlertManager,
    AlertRule,
    MergedRegistry,
    MetricsRegistry,
    TimeseriesCollector,
    default_rules,
    prometheus_text,
)

# ----------------------------------------------------- synthetic windows


def _win(i, metrics, duration_s=1.0):
    return {"index": i, "t_start": float(i), "t_end": i + duration_s,
            "duration_s": duration_s, "metrics": metrics}


def _hist(count, p50=None, p95=None, p99=None):
    return {"count": count, "p50": p50, "p95": p95, "p99": p99}


# ----------------------------------------------------------------- rules


def test_rule_validation():
    with pytest.raises(ValueError):
        AlertRule("x", "weather", "m", 1.0)          # unknown kind
    with pytest.raises(ValueError):
        AlertRule("x", "burn_rate", "m", 1.0)        # needs budget_s
    with pytest.raises(ValueError):
        AlertRule("x", "rate", "m", 1.0, objective=1.0)
    r = AlertRule("x", "burn_rate", "m", 2.0, budget_s=0.5,
                  short=2, long=12)
    assert r.lookback == 12
    assert AlertRule("y", "rate", "m", 1.0, windows=3).lookback == 3
    assert "budget_s" in r.to_json() and r.to_json()["kind"] == "burn_rate"


def test_burn_rate_percentile_ladder_and_two_window_guard():
    # objective 0.95 -> 5% budget. p99 over = 1% errors = burn 0.2;
    # p95 over = 5% = burn 1.0; p50 over = 50% = burn 10.
    rule = AlertRule("ttft_burn", "burn_rate", "ttft_seconds", 2.0,
                     objective=0.95, budget_s=1.0, short=2, long=12)
    good = {"ttft_seconds": _hist(10, p50=0.1, p95=0.4, p99=0.8)}
    bad = {"ttft_seconds": _hist(10, p50=1.5, p95=2.0, p99=3.0)}
    p99_only = {"ttft_seconds": _hist(10, p50=0.1, p95=0.4, p99=1.4)}
    # Too little history: never fires before the short lookback exists.
    firing, ev = rule.evaluate([_win(0, bad)])
    assert not firing and ev is None
    # One bad window at the end of a good long tail: the short lookback
    # burns hot but the long lookback dilutes it under threshold — the
    # two-window guard ignores a single spike.
    hist = [_win(i, good) for i in range(11)] + [_win(11, bad)]
    firing, ev = rule.evaluate(hist)
    assert not firing and ev["short_burn"] == pytest.approx(5.0)
    assert ev["long_burn"] < rule.threshold
    # Sustained: both lookbacks over threshold -> fires with evidence.
    hist = [_win(i, bad) for i in range(4)]
    firing, ev = rule.evaluate(hist)
    assert firing
    assert ev["short_burn"] == pytest.approx(10.0)
    assert ev["long_burn"] == pytest.approx(10.0)
    assert ev["budget_s"] == 1.0 and ev["objective"] == 0.95
    # p99-only breach burns at 0.2 — an order of magnitude under the
    # page threshold; the ladder is conservative, not hair-trigger.
    firing, ev = rule.evaluate([_win(i, p99_only) for i in range(4)])
    assert not firing and ev["short_burn"] == pytest.approx(0.2)
    # An empty histogram (count 0) contributes zero error.
    firing, _ = rule.evaluate(
        [_win(i, {"ttft_seconds": _hist(0, p50=9.9)}) for i in range(4)])
    assert not firing


def test_burn_rate_matches_labelled_series_worst_wins():
    rule = AlertRule("ttft_burn", "burn_rate", "ttft_seconds", 2.0,
                     objective=0.95, budget_s=1.0, short=2, long=2)
    # Replica 0 healthy, replica 1 melting: the merged snapshot's
    # labelled keys match the bare rule metric and the WORST burns.
    m = {"ttft_seconds{replica=0}": _hist(10, p50=0.1),
         "ttft_seconds{replica=1}": _hist(10, p50=3.0),
         "other_seconds": _hist(10, p50=9.0)}
    firing, ev = rule.evaluate([_win(0, m), _win(1, m)])
    assert firing and ev["short_burn"] == pytest.approx(10.0)


def test_saturation_needs_consecutive_windows():
    rule = AlertRule("queue", "saturation", "queue_depth", 8, windows=3)
    high = {"queue_depth": 9}
    low = {"queue_depth": 2}
    assert not rule.evaluate([_win(0, high), _win(1, high)])[0]
    # A dip inside the tail breaks the streak.
    firing, ev = rule.evaluate(
        [_win(0, high), _win(1, low), _win(2, high)])
    assert not firing and ev["maxima"] == [9.0, 2.0, 9.0]
    firing, ev = rule.evaluate([_win(i, high) for i in range(3)])
    assert firing and ev["maxima"] == [9.0, 9.0, 9.0]
    # Labelled gauges: max across replicas is the scored value.
    split = {"queue_depth{replica=0}": 1, "queue_depth{replica=1}": 8}
    assert rule.evaluate([_win(i, split) for i in range(3)])[0]
    # A window missing the metric scores 0 and breaks the streak.
    assert not rule.evaluate(
        [_win(0, high), _win(1, {}), _win(2, high)])[0]


def test_rate_sums_counters_over_real_durations():
    rule = AlertRule("fallbacks", "rate", "handoff_fallbacks", 1.0,
                     windows=2)
    # 3 fallbacks over 2s of windows = 1.5/s >= 1.0 -> fires.
    hist = [_win(0, {"handoff_fallbacks": 2}),
            _win(1, {"handoff_fallbacks": 1})]
    firing, ev = rule.evaluate(hist)
    assert firing and ev["rate_per_s"] == pytest.approx(1.5)
    # Same counts over long windows: the rate falls under threshold.
    slow = [_win(0, {"handoff_fallbacks": 2}, duration_s=4.0),
            _win(1, {"handoff_fallbacks": 1}, duration_s=4.0)]
    firing, ev = rule.evaluate(slow)
    assert not firing and ev["rate_per_s"] == pytest.approx(0.375)
    # Labelled counters SUM across replicas (fleet-wide rate).
    split = [_win(i, {"handoff_fallbacks{replica=0}": 1,
                      "handoff_fallbacks{replica=1}": 1})
             for i in range(2)]
    assert rule.evaluate(split)[0]


def test_default_rules_cover_stack_and_take_knobs():
    rules = {r.name: r for r in default_rules(
        ttft_budget_s=0.2, itl_budget_s=0.05, objective=0.9,
        burn_threshold=3.0, queue_saturation=16, fallback_rate=2.0)}
    assert sorted(rules) == ["breaker_open", "handoff_fallbacks",
                             "hbm_pressure", "itl_burn", "queue_saturated",
                             "ttft_burn"]
    assert rules["ttft_burn"].budget_s == 0.2
    assert rules["ttft_burn"].threshold == 3.0
    assert rules["itl_burn"].metric == "inter_token_seconds"
    assert rules["queue_saturated"].threshold == 16
    assert rules["breaker_open"].windows == 1
    assert rules["handoff_fallbacks"].kind == "rate"
    # HBM saturation (perf x-ray ledger): saturation rule on the
    # hbm_pressure gauge; the gauge reads 0 when capacity is unknown
    # (CPU), so the default rule can never fire there.
    assert rules["hbm_pressure"].kind == "saturation"
    assert rules["hbm_pressure"].metric == "hbm_pressure"
    assert rules["hbm_pressure"].threshold == pytest.approx(0.92)


# --------------------------------------------------------------- manager


def _manager_over(rules, **kw):
    """A manager over a real registry + collector on a manual clock.
    Returns (registry, collector, manager, advance) where advance(s)
    moves the shared clock and ticks the collector."""
    t = [0.0]
    reg = MetricsRegistry(engine="inference")
    col = TimeseriesCollector(reg, window_seconds=1.0, clock=lambda: t[0])
    col.start()
    mgr = AlertManager(col, rules, clock=lambda: t[0], **kw)

    def advance(s=1.0):
        t[0] += s
        col.tick()

    return reg, col, mgr, advance


def test_manager_rising_edge_clear_and_refire():
    rules = [AlertRule("ttft_burn", "burn_rate", "ttft_seconds", 2.0,
                       objective=0.95, budget_s=0.1, short=1, long=1)]
    reg, col, mgr, advance = _manager_over(rules)
    h = reg.histogram("ttft_seconds")
    fired_hook = []
    mgr.add_on_fire(lambda rule, rec: fired_hook.append(rule.name))
    mgr.add_on_fire(lambda rule, rec: 1 / 0)   # broken hook: swallowed
    assert mgr.evaluate() == []                # no windows yet
    # Window 0: every request blows the budget -> rising edge.
    for _ in range(8):
        h.observe(1.0)
    advance()
    edges = mgr.evaluate()
    assert [r.name for r, _ in edges] == ["ttft_burn"]
    assert fired_hook == ["ttft_burn"]
    assert "ttft_burn" in mgr.firing()
    rec = mgr.firing()["ttft_burn"]
    assert rec["evidence"]["short_burn"] >= 2.0
    assert rec["window_index"] == 0
    # Window 1 still bad: NO second fired record (edge-once), evidence
    # in firing() refreshes.
    for _ in range(8):
        h.observe(1.0)
    advance()
    assert mgr.evaluate() == []
    assert len(mgr.fired()) == 1
    # Window 2 healthy: the alert clears but the fired record stays
    # for the post-mortem.
    for _ in range(8):
        h.observe(0.01)
    advance()
    assert mgr.evaluate() == [] and mgr.firing() == {}
    assert [r["rule"] for r in mgr.fired()] == ["ttft_burn"]
    # Window 3 bad again: a NEW edge, a second fired record.
    for _ in range(8):
        h.observe(1.0)
    advance()
    assert len(mgr.evaluate()) == 1
    assert [r["rule"] for r in mgr.fired()] == ["ttft_burn", "ttft_burn"]
    assert fired_hook == ["ttft_burn", "ttft_burn"]
    # evaluate() is idempotent per window: no new windows, no rescoring.
    assert mgr.evaluate() == []
    j = mgr.to_json()
    assert j["windows_evaluated"] == 4 and j["firing"] == ["ttft_burn"]


def test_manager_saturation_over_merged_fleet_registry():
    """The fleet shape: rules score a MergedRegistry's collector, where
    every series is replica-labelled; one saturated replica fires the
    fleet-wide rule with no per-replica rule copies."""
    t = [0.0]
    regs = {rid: MetricsRegistry(engine="inference", replica=str(rid))
            for rid in (0, 1)}
    depth = {0: 0, 1: 0}
    for rid, reg in regs.items():
        reg.gauge("queue_depth").set_fn(lambda rid=rid: depth[rid])
    col = TimeseriesCollector(MergedRegistry(regs), window_seconds=1.0,
                              clock=lambda: t[0])
    col.start()
    mgr = AlertManager(
        col, [AlertRule("queue_saturated", "saturation", "queue_depth",
                        4, windows=2)], clock=lambda: t[0])
    depth[1] = 9                     # only replica 1 saturates
    for _ in range(2):
        t[0] += 1.0
        col.tick()
    edges = mgr.evaluate()
    assert [r.name for r, _ in edges] == ["queue_saturated"]
    assert edges[0][1]["evidence"]["maxima"] == [9.0, 9.0]


def test_manager_prometheus_export_and_gauges():
    rules = [AlertRule("queue_saturated", "saturation", "queue_depth",
                       2, windows=1),
             AlertRule("fallbacks", "rate", "handoff_fallbacks", 99.0,
                       windows=1)]
    reg, col, mgr, advance = _manager_over(rules)
    reg.gauge("queue_depth").set(5)
    snap = mgr.telemetry.snapshot()
    assert snap["alerts_firing"] == 0
    advance()
    mgr.evaluate()
    snap = mgr.telemetry.snapshot()
    assert snap["alerts_firing"] == 1
    assert snap["alerts_fired_total"] == 1
    assert snap["alert_active{rule=queue_saturated}"] == 1
    assert snap["alert_active{rule=fallbacks}"] == 0
    text = prometheus_text(mgr.telemetry)
    assert 'ds_tpu_alert_active{engine="alerts",' \
           'rule="queue_saturated"} 1' in text
    assert "ds_tpu_alerts_fired_total" in text
    assert "ds_tpu_alerts_firing" in text
