"""A request's way to its first token, phase by phase.

``prefill_ms`` (admitted -> first token at the host) is three parts, stamped
on the clock of ``admit_time``: the wait for the one prefill lane (admit ->
the dispatch of the prompt's first slice), the lane's run (-> the dispatch
of its last slice) and the lag of the step in flight (-> first token). The
engine stamps them in ``_dispatch_step``, carries them on the request's
instants (``Request.phase_ms``), names each slice (``request/slice``,
``request/last_slice``), observes three histograms once a request and counts
the lane's load (``lane_steps``, ``lane_busy_share``, ``lane_fill``).
"""

import pytest

from deepspeed_tpu.inference import Fault, FaultPlan
from deepspeed_tpu.telemetry import NullRecorder
from tests.unit.test_chunked_prefill import engine_of, make_model, prompts_of

PARTS = ("lane_wait_ms", "lane_run_ms", "first_lag_ms")
HISTOGRAMS = ("lane_wait_seconds", "lane_run_seconds",
              "first_token_lag_seconds")
# An engine built with speculation harvests each step in the call that
# dispatched it (depth 0); a plain one keeps one step in flight (depth 1).
DEPTHS = {1: {}, 0: {"spec_decode": True, "spec_k": 2}}


def _engine(depth=1, **kw):
    cfg, model, params = make_model()
    eng = engine_of(model, params, **dict(DEPTHS[depth], **kw))
    assert eng._depth == depth
    return cfg, eng


def _events(eng, name, rid=None):
    return [e for e in eng.tracer.events() if e["name"] == name
            and (rid is None or e["args"].get("rid") == rid)]


def _between(eng, rid, earlier, later):
    """Names and steps of the engine's spans that the ring holds between a
    request's ``earlier`` and ``later`` instants (a span is written when it
    ends)."""
    events = eng.tracer.events()
    at = {e["name"]: i for i, e in enumerate(events)
          if e["args"].get("rid") == rid and e["name"] in (earlier, later)}
    return [(e["name"], e["args"]["step"])
            for e in events[at[earlier]:at[later]]
            if e["name"].startswith("inference/")]


@pytest.mark.parametrize("depth", sorted(DEPTHS))
def test_one_slice_prompt_stamps_and_the_lag_of_the_step_in_flight(depth):
    """A prompt of one slice into an engine that is decoding: both lane
    stamps are the one dispatch, and between that dispatch and the first
    token the host waits for TWO device steps at depth 1 (the one in flight,
    then the slice's own, with the next dispatched in between) and for ONE
    on an engine built at depth 0."""
    cfg, eng = _engine(depth)
    long_one, short = prompts_of(cfg, [6, 5])
    eng.submit(long_one, max_new_tokens=40)
    for _ in range(3):
        eng.step()
    req = eng.submit(short, max_new_tokens=6)
    eng.run()
    assert req.lane_time == req.last_slice_time
    assert req.admit_time <= req.lane_time <= req.first_token_time
    (piece,) = _events(eng, "request/slice", req.rid)
    (last,) = _events(eng, "request/last_slice", req.rid)
    (first,) = _events(eng, "request/first_token", req.rid)
    step = piece["args"]["step"]
    assert last["args"]["step"] == first["args"]["step"] == step
    assert piece["args"]["tokens"] == 5 and piece["args"]["cursor"] == 0
    assert piece["args"]["slot"] == 1
    assert piece["args"]["slices"] == last["args"]["slices"] == 1
    assert last["args"]["lane_run_ms"] == 0.0
    spans = _between(eng, req.rid, "request/last_slice",
                     "request/first_token")
    harvested = [s for name, s in spans if name == "inference/harvest"]
    dispatched = [s for name, s in spans if name == "inference/mixed_step"]
    if depth:
        assert harvested == [step - 1, step]
        assert dispatched == [step, step + 1]
    else:
        assert harvested == [step] and dispatched == [step]


def test_three_slice_prompt_runs_the_lane_for_two_more_dispatches():
    cfg, eng = _engine()
    (prompt,) = prompts_of(cfg, [20])            # prefill_chunk 8: 8 + 8 + 4
    req = eng.submit(prompt, max_new_tokens=3)
    eng.run()
    pieces = _events(eng, "request/slice", req.rid)
    assert [p["args"]["cursor"] for p in pieces] == [0, 8, 16]
    assert [p["args"]["tokens"] for p in pieces] == [8, 8, 4]
    assert [p["args"]["slices"] for p in pieces] == [1, 2, 3]
    steps = [p["args"]["step"] for p in pieces]
    assert steps == list(range(steps[0], steps[0] + 3))
    # lane_run_ms is known from the last slice on, and only from there
    assert ["lane_run_ms" in p["args"] for p in pieces] == [False, False,
                                                            True]
    (last,) = _events(eng, "request/last_slice", req.rid)
    assert last["args"]["slices"] == 3 and last["args"]["step"] == steps[-1]
    assert req.last_slice_time > req.lane_time
    assert last["args"]["lane_run_ms"] == pytest.approx(
        (req.last_slice_time - req.lane_time) * 1e3, abs=1e-3)
    assert req.slices == 3


def test_a_request_behind_another_waits_for_the_lane():
    """Two requests admitted in one round: the second's first slice cannot
    be dispatched before the first's last, so its wait for the lane is at
    least the first's run of it."""
    cfg, eng = _engine()
    ahead, behind = [eng.submit(p, max_new_tokens=3)
                     for p in prompts_of(cfg, [20, 5])]
    eng.run()
    assert behind.lane_time > ahead.last_slice_time
    waited = behind.phase_ms()["lane_wait_ms"]
    assert waited > ahead.phase_ms()["lane_run_ms"] > 0
    assert waited > ahead.phase_ms()["lane_wait_ms"]
    first_of_behind = _events(eng, "request/slice", behind.rid)[0]
    last_of_ahead = _events(eng, "request/last_slice", ahead.rid)[0]
    assert first_of_behind["args"]["step"] == last_of_ahead["args"]["step"] + 1


@pytest.mark.parametrize("depth", sorted(DEPTHS))
def test_the_parts_add_up_to_the_observed_time_to_first_token(depth):
    cfg, eng = _engine(depth)
    reqs = [eng.submit(p, max_new_tokens=5)
            for p in prompts_of(cfg, [20, 5, 9, 13])]
    eng.run()
    total_ms = 0.0
    for req in reqs:
        phases = req.phase_ms()
        assert sum(phases[k] for k in PARTS) == pytest.approx(
            phases["prefill_ms"], abs=0.01)
        ttft_ms = (req.first_token_time - req.submit_time) * 1e3
        assert phases["queue_ms"] + sum(phases[k] for k in PARTS) == \
            pytest.approx(ttft_ms, abs=0.01)
        total_ms += ttft_ms
        # every instant after the first token carries all five
        finished = _events(eng, "request/finished", req.rid)[0]["args"]
        assert {k: finished[k] for k in phases} == phases
    registry = eng.telemetry
    assert registry.histogram("ttft_seconds").count == len(reqs)
    assert registry.histogram("ttft_seconds").sum * 1e3 == pytest.approx(
        total_ms, abs=0.01)
    parts_s = sum(registry.histogram(h).sum for h in HISTOGRAMS) \
        + registry.histogram("queue_wait_seconds").sum
    assert parts_s * 1e3 == pytest.approx(total_ms, abs=0.01)


def test_recovery_replay_keeps_the_first_stamps_and_observes_once():
    """A replayed request rides the lane again (new slices, new instants,
    no hop lost) but its stamps are the first admission's and each
    histogram holds one observation, as ``ttft_seconds`` does."""
    cfg, eng = _engine(max_slots=2, prefill_chunk=4, fault_injection=True)
    (prompt,) = prompts_of(cfg, [6])
    req = eng.submit(prompt, max_new_tokens=20)
    while req.phase != "decoding":
        eng.step()
    eng.step()
    stamps = (req.lane_time, req.last_slice_time, req.first_token_time)
    assert None not in stamps
    before = len(_events(eng, "request/slice", req.rid))
    assert before == 2 and req.slices == 2
    eng.inject_faults(FaultPlan(faults=(Fault("raise", step=0),)))
    eng.run()
    assert req.phase == "done" and req.replays == 1
    assert (req.lane_time, req.last_slice_time,
            req.first_token_time) == stamps
    replayed = _events(eng, "request/slice", req.rid)[before:]
    assert replayed and replayed[0]["args"]["slices"] == 1  # a new walk
    assert replayed[0]["args"]["cursor"] == 0
    assert len(_events(eng, "request/last_slice", req.rid)) == 2
    assert len(_events(eng, "request/first_token", req.rid)) == 1
    for name in HISTOGRAMS + ("ttft_seconds",):
        assert eng.telemetry.histogram(name).count == 1, name
    assert eng.explain(req.rid)["hop_gaps"] == []


def test_lane_steps_share_and_fill_against_a_hand_count():
    cfg, eng = _engine()
    for p in prompts_of(cfg, [20, 5, 9]):        # 3 + 1 + 2 slices of 8
        eng.submit(p, max_new_tokens=10)
    eng.run()
    m = eng.metrics()
    assert m["lane_steps"] == eng.counters["lane_steps"] == 6
    assert m["prefill_tokens"] == 34
    assert m["chunks"] > 6                       # decode-only steps follow
    assert m["lane_busy_share"] == pytest.approx(6.0 / m["chunks"])
    assert m["lane_fill"] == pytest.approx(34.0 / (6 * 8))
    assert len(_events(eng, "request/slice")) == 6
    assert len(_events(eng, "request/last_slice")) == 3
    text = eng.prometheus()
    for series in ("ds_tpu_lane_steps_total", "ds_tpu_lane_busy_share",
                   "ds_tpu_lane_fill", "ds_tpu_lane_wait_seconds_count",
                   "ds_tpu_lane_run_seconds_count",
                   "ds_tpu_first_token_lag_seconds_count"):
        assert series in text, series
    snap = eng.telemetry.snapshot()
    assert snap["lane_busy_share"] == pytest.approx(6.0 / m["chunks"])
    assert snap["lane_fill"] == pytest.approx(34.0 / 48)


def test_metrics_reset_windows_the_new_percentiles_and_the_lane_load():
    cfg, eng = _engine()
    keys = ["{}_p{}_ms".format(stem, p)
            for stem in ("lane_wait", "lane_run", "first_token_lag")
            for p in (50, 99)]
    assert all(eng.metrics()[k] is None for k in keys)
    first, second = prompts_of(cfg, [20, 5])
    eng.submit(first, max_new_tokens=3)
    eng.run()
    opened = eng.metrics(reset=True)
    assert all(opened[k] is not None for k in keys)
    assert opened["lane_run_p50_ms"] > 0 and opened["lane_steps"] == 3
    empty = eng.metrics()
    assert all(empty[k] is None for k in keys)
    assert empty["lane_steps"] == 0 and empty["lane_busy_share"] == 0.0
    assert empty["lane_fill"] == 0.0
    req = eng.submit(second, max_new_tokens=3)
    eng.run()
    window = eng.metrics()
    assert window["lane_steps"] == 1
    assert window["lane_run_p50_ms"] == window["lane_run_p99_ms"] == 0.0
    assert window["lane_fill"] == pytest.approx(5.0 / 8)
    assert window["first_token_lag_p50_ms"] == pytest.approx(
        req.phase_ms()["first_lag_ms"], abs=2e-3)   # both rounded to 1e-3
    assert eng.counters["lane_steps"] == 4       # totals never rewind


def test_telemetry_off_keeps_the_stamps_and_the_registry():
    cfg, eng = _engine(telemetry=False)
    assert isinstance(eng.tracer, NullRecorder)
    req = eng.submit(prompts_of(cfg, [20])[0], max_new_tokens=3)
    eng.run()
    assert eng.tracer.events() == []
    phases = req.phase_ms()
    assert phases["lane_run_ms"] > 0 and req.slices == 3
    assert sum(phases[k] for k in PARTS) == pytest.approx(
        phases["prefill_ms"], abs=0.01)
    m = eng.metrics()
    assert m["lane_steps"] == 3
    assert m["lane_run_p50_ms"] == pytest.approx(phases["lane_run_ms"],
                                                 abs=2e-3)
    assert m["first_token_lag_p50_ms"] == pytest.approx(
        phases["first_lag_ms"], abs=2e-3)
    assert eng.compile_count == 1


def test_every_slice_consumes_a_hop_and_the_autopsy_stays_gap_free():
    cfg, eng = _engine()
    reqs = [eng.submit(p, max_new_tokens=5)
            for p in prompts_of(cfg, [20, 5])]
    eng.run()
    for req in reqs:
        story = eng.explain(req.rid)
        assert story["hop_gaps"] == []
        names = [h["name"] for h in story["hops"]]
        assert names.count("request/slice") == req.slices
        assert names.count("request/last_slice") == 1
        order = [names.index(n) for n in (
            "request/admitted", "request/slice", "request/last_slice",
            "request/first_token", "request/finished")]
        assert order == sorted(order)
    assert eng.compile_count == 1                # tracing compiles nothing
