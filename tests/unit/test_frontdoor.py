"""Streaming, SLO-aware front door (deepspeed_tpu/inference/frontdoor/).

The contract under test:
1. STREAMING — ``stream()`` yields token ids as they harvest,
   bit-identical (order and values) to a batch harvest of the same
   submission and to the sequential reference, greedy AND sampled,
   with compile_count pinned at 1; closing a stream early cancels the
   underlying request.
2. ADMISSION — the predictor stays optimistic cold, predicts
   TTFT/E2E from live queue-wait + throughput evidence warm, and every
   shed is a structured QueueFull carrying reason (rate_limit /
   frontdoor_full / deadline / slo), the submitting class/tenant, and
   a CLASS-AWARE retry_after_s clamped to RETRY_AFTER_CAP_S.
3. FAIRNESS — strict latency-before-throughput tiers; inside a tier a
   weighted fair queue over (class, tenant) lanes: a heavy tenant gets
   proportionally more turns, a light one is never starved.
4. BATCH GATE — throughput work enters the target only while the
   target queue is clear (slots saturate, the FIFO stays open for
   interactive prefill) or while the warm predictor says a
   hypothetical latency arrival still meets headroom * budget.
5. OBSERVABILITY — per-class/per-tenant counters in metrics() and in
   the Prometheus exposition (parser-level, labelled).
6. ACCEPTANCE — one mixed-tenant flood, counted in engine steps under
   a clock that moves only when the engine does: behind the front door
   every interactive request has its first token within a stated
   number of steps while batch saturates (zero lost, compile_count 1);
   the SAME workload on the bare engine leaves the first interactive
   request behind the whole flood (head-of-line FIFO burial).
"""

import collections
import math
import time

import pytest

from deepspeed_tpu.inference import (
    FrontDoor,
    FrontDoorConfig,
    PriorityClass,
    QueueFull,
    Scheduler,
    TenantPolicy,
)
from deepspeed_tpu.inference.frontdoor import AdmissionController, TokenBucket
from deepspeed_tpu.inference.scheduler import RETRY_AFTER_CAP_S
from deepspeed_tpu.loadgen import (
    SLO,
    SustainedRunner,
    WorkloadSpec,
    build_report,
)
from tests.unit.test_chunked_prefill import (
    engine_of,
    make_model,
    prompts_of,
    seq_greedy,
)
from tests.unit.test_telemetry import _parse_prom


class _Clock(object):
    """Manually advanced clock shared by the front door under test."""

    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# ----------------------------------------------------------- fake target


class _FakeReq(object):
    def __init__(self, rid, prompt, max_new_tokens, priority, tenant, now):
        self.rid = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.priority = priority
        self.tenant = tenant
        self.tokens = []
        self.phase = "decoding"
        self.submit_time = now
        self.first_token_time = None
        self.finish_time = None

    @property
    def done(self):
        return self.finish_time is not None


class _FakeTarget(object):
    """Engine-shaped stub: the duck-typed surface FrontDoor probes,
    with a switchable submit() refusal and a finish-on-step engine."""

    class _Config(object):
        def __init__(self, max_slots, host_offload):
            self.max_slots = max_slots
            self.host_offload = host_offload
            self.max_new_tokens = 16
            self.max_len = 64

    class _Sched(object):
        def __init__(self):
            self.queue = collections.deque()

    def __init__(self, clock, max_slots=2, host_offload=False,
                 refuse=False):
        self.config = self._Config(max_slots, host_offload)
        self._scheduler = self._Sched()
        self._clock = clock
        self.refuse = refuse
        self.submitted = []
        self.preempt_calls = []
        self.release_calls = []
        self.counters = {"requests_completed": 0, "tokens_out": 0}
        self._rids = iter(range(10**6))
        self.compile_count = 1

    def submit(self, prompt, max_new_tokens=None, priority=None,
               tenant=None, **kw):
        if self.refuse:
            raise QueueFull("fake target full", queue_depth=0)
        req = _FakeReq(next(self._rids), prompt, max_new_tokens,
                       priority, tenant, self._clock())
        self.submitted.append(req)
        return req

    def step(self):
        # Finish the oldest unfinished submission, one per step.
        for req in self.submitted:
            if not req.done:
                now = self._clock()
                req.tokens.extend(range(req.max_new_tokens or 1))
                req.first_token_time = now
                req.finish_time = now
                req.phase = "done"
                self.counters["requests_completed"] += 1
                self.counters["tokens_out"] += len(req.tokens)
                return

    @property
    def idle(self):
        return not self._scheduler.queue and all(
            r.done for r in self.submitted)

    def cancel(self, req):
        if req.done:
            return False
        req.phase = "cancelled"
        req.finish_time = self._clock()
        return True

    def preempt(self, req):
        self.preempt_calls.append(req.rid)
        req.phase = "swapped"
        return True

    def release_preempted(self, req=None):
        self.release_calls.append(None if req is None else req.rid)
        if req is not None and req.phase == "swapped":
            req.phase = "decoding"

    def metrics(self, reset=False):
        return {"compile_count": self.compile_count}

    def prometheus(self):
        return ""


def _warm_admission(fd, clk, rate=10.0, token_rate=100.0, service_s=0.01):
    """Feed the estimators two poll windows + two finishes so the
    predictor leaves its optimistic cold state with known rates."""
    adm = fd._admission
    adm.observe_poll(0, 0)
    clk.advance(1.0)
    adm.observe_poll(int(rate), int(token_rate))
    adm.observe_finish("interactive", service_s)
    clk.advance(1.0)
    adm.observe_poll(int(2 * rate), int(2 * token_rate))
    adm.observe_finish("interactive", service_s)
    assert not adm.cold


def _fd_of(clk, target, **cfg_kw):
    cfg_kw.setdefault("classes", (
        PriorityClass("interactive", ttft_budget_ms=100.0, weight=4.0),
        PriorityClass("batch", weight=1.0, preemptible=True),
    ))
    return FrontDoor(target, FrontDoorConfig(**cfg_kw), clock=clk,
                     sleep=lambda s: clk.advance(s))


# ----------------------------------------------------- admission math


def test_admission_cold_then_warm_prediction():
    clk = _Clock()
    adm = AdmissionController(alpha=0.5, slots=2, clock=clk)
    # Cold: no evidence -> no prediction, optimistic admit upstream.
    assert adm.cold
    assert adm.predict_ttft_s(5) is None
    assert adm.predict_e2e_s(5, 16) is None
    adm.observe_poll(0, 0)
    clk.advance(1.0)
    adm.observe_poll(10, 200)       # 10 req/s, 200 tok/s
    adm.observe_finish("interactive", 0.05)
    clk.advance(1.0)
    adm.observe_poll(20, 400)
    adm.observe_finish("interactive", 0.05)
    assert not adm.cold
    # predicted_ttft = ahead / rate + service_base.
    assert adm.predict_ttft_s(10) == pytest.approx(10 / 10.0 + 0.05)
    # e2e adds the decode tail at the per-slot token rate (200/2).
    assert adm.predict_e2e_s(10, 100) == pytest.approx(
        10 / 10.0 + 0.05 + 100 / 100.0)


def test_admission_poll_skips_sub_interval_noise():
    clk = _Clock()
    adm = AdmissionController(clock=clk)
    adm.observe_poll(0, 0)
    clk.advance(0.05)               # below MIN_POLL_DT_S
    adm.observe_poll(1000, 1000)
    assert adm._rate is None        # folded into the next wide window
    clk.advance(1.0)
    adm.observe_poll(10, 100)
    assert adm._rate == pytest.approx(10 / 1.05, rel=1e-3)


def test_admission_retry_hint_prefers_class_evidence():
    clk = _Clock()
    adm = AdmissionController(clock=clk)
    # Global evidence: 1 completion/s. Interactive: 10/s.
    for _ in range(4):
        clk.advance(1.0)
        adm.observe_finish("batch")
    for _ in range(4):
        clk.advance(0.1)
        adm.observe_finish("interactive")
    hint_i = adm.retry_hint_s("interactive")
    hint_b = adm.retry_hint_s("batch")
    assert hint_i == pytest.approx(0.1, rel=1e-3)
    assert hint_b > hint_i
    # Unknown class falls back to the global deque, never None here.
    assert adm.retry_hint_s("gold") is not None


def test_token_bucket_refill_and_retry_after():
    b = TokenBucket(rate=2.0, burst=2.0, now=0.0)
    assert b.take(0.0) and b.take(0.0)      # burst spent
    assert not b.take(0.0)
    # Next token exists in 1/rate seconds.
    assert b.retry_after(0.0) == pytest.approx(0.5)
    assert b.take(0.6)                       # refilled
    assert not b.take(0.6)


# ---------------------------------------------------- config validation


def test_frontdoor_config_validates_loudly():
    with pytest.raises(ValueError, match="unknown FrontDoorConfig key"):
        FrontDoorConfig.from_dict({"clases": ()})
    with pytest.raises(ValueError, match="duplicate class names"):
        FrontDoorConfig(classes=(PriorityClass("a"), PriorityClass("a")),
                        default_class="a")
    with pytest.raises(ValueError, match="default_class"):
        FrontDoorConfig(classes=(PriorityClass("a"),), default_class="b")
    with pytest.raises(ValueError, match="ttft_budget_ms"):
        PriorityClass("x", ttft_budget_ms=0.0)
    with pytest.raises(ValueError, match="rate"):
        TenantPolicy("t", rate=-1.0)
    # from_dict builds nested classes/tenants from plain dicts.
    cfg = FrontDoorConfig.from_dict({
        "classes": [{"name": "gold", "ttft_budget_ms": 50.0},
                    {"name": "bulk"}],
        "tenants": [{"name": "t1", "rate": 5.0}],
        "default_class": "gold"})
    assert cfg.classes[0].is_latency and not cfg.classes[1].is_latency
    assert cfg.tenants[0].bucket_burst == 5.0


# ------------------------------------------------------------ shedding


def test_rate_limit_shed_is_structured_and_clamped():
    clk = _Clock()
    fd = _fd_of(clk, _FakeTarget(clk),
                tenants=(TenantPolicy("slow", rate=1e-6, burst=1.0),))
    fd.submit([1, 2], max_new_tokens=2, tenant="slow")   # spends the burst
    with pytest.raises(QueueFull) as ei:
        fd.submit([1, 2], max_new_tokens=2, tenant="slow")
    exc = ei.value
    assert exc.reason == "rate_limit"
    assert exc.priority == "interactive" and exc.tenant == "slow"
    # The bucket's honest hint is ~1e6 s; the structured field clamps.
    assert exc.retry_after_s == RETRY_AFTER_CAP_S
    assert fd.metrics()["frontdoor"]["sheds"] == {
        "interactive/slow/rate_limit": 1}


def test_frontdoor_full_shed_per_lane_cap():
    clk = _Clock()
    target = _FakeTarget(clk, refuse=True)   # nothing dispatches
    fd = _fd_of(clk, target, classes=(
        PriorityClass("interactive", ttft_budget_ms=100.0, max_pending=1),
        PriorityClass("batch"),
    ))
    fd.submit([1], max_new_tokens=1)
    with pytest.raises(QueueFull) as ei:
        fd.submit([1], max_new_tokens=1)
    assert ei.value.reason == "frontdoor_full"
    assert ei.value.queue_depth == 1
    # The cap is PER (class, tenant) lane: batch still admits.
    fd.submit([1], max_new_tokens=1, priority="batch")


def test_deadline_shed_at_submit_when_eta_exceeds_deadline():
    clk = _Clock()
    target = _FakeTarget(clk)
    fd = _fd_of(clk, target)
    _warm_admission(fd, clk, rate=10.0, token_rate=100.0)
    target._scheduler.queue.extend(range(5))    # 5 ahead -> 0.5 s TTFT
    with pytest.raises(QueueFull) as ei:
        # predicted e2e ~= 0.5 + 0.01 + 50/(100/2) = 1.51 s >> 100 ms.
        fd.submit([1], max_new_tokens=50, deadline_ms=100.0)
    assert ei.value.reason == "deadline"
    # A feasible deadline admits (and dispatches) fine.
    target._scheduler.queue.clear()
    h = fd.submit([1], max_new_tokens=2, deadline_ms=10_000.0)
    assert h.phase == "decoding"


def test_slo_shed_when_warm_prediction_exceeds_budget():
    clk = _Clock()
    target = _FakeTarget(clk)          # host_offload off: no preemption
    fd = _fd_of(clk, target)
    _warm_admission(fd, clk, rate=10.0)
    target._scheduler.queue.extend(range(50))   # 5 s predicted TTFT
    with pytest.raises(QueueFull) as ei:
        fd.submit([1], max_new_tokens=2)
    exc = ei.value
    assert exc.reason == "slo" and exc.priority == "interactive"
    assert exc.retry_after_s is not None
    # shed_on_budget=False admits anyway (lateness over rejection).
    fd2 = _fd_of(clk, target, classes=(
        PriorityClass("interactive", ttft_budget_ms=100.0,
                      shed_on_budget=False),
        PriorityClass("batch"),
    ))
    _warm_admission(fd2, clk, rate=10.0)
    h = fd2.submit([1], max_new_tokens=2)
    assert h.phase in ("pending", "decoding")


def test_deadline_expires_in_lane_without_dispatch():
    clk = _Clock()
    target = _FakeTarget(clk, refuse=True)
    fd = _fd_of(clk, target)
    h = fd.submit([1], max_new_tokens=2, deadline_ms=50.0)
    assert h.phase == "pending"
    clk.advance(0.2)
    fd.step()
    assert h.phase == "expired" and h.done
    assert target.submitted == []       # dead work never dispatched
    assert fd.metrics()["frontdoor"]["stats"]["expired"] == 1
    assert [x.hid for x in fd.harvest()] == [h.hid]


# ------------------------------------------------- tiers, WFQ, the gate


def test_latency_tier_dispatches_before_batch():
    clk = _Clock()
    target = _FakeTarget(clk, refuse=True)
    fd = _fd_of(clk, target)
    fd.submit([1], max_new_tokens=1, priority="batch")
    fd.submit([2], max_new_tokens=1, priority="interactive")
    target.refuse = False
    fd.step()
    assert [r.priority for r in target.submitted[:2]] == [
        "interactive", "batch"]


def test_weighted_fair_queue_shares_by_tenant_weight():
    clk = _Clock()
    target = _FakeTarget(clk, refuse=True)
    fd = _fd_of(clk, target,
                tenants=(TenantPolicy("heavy", weight=3.0),
                         TenantPolicy("light", weight=1.0)))
    for _ in range(4):
        fd.submit([1], max_new_tokens=1, tenant="heavy")
        fd.submit([2], max_new_tokens=1, tenant="light")
    target.refuse = False
    fd.step()
    order = [r.tenant for r in target.submitted]
    assert len(order) == 8
    # 3:1 shares: three heavy turns in the first four, but light's very
    # first turn comes no later than second round — never starved.
    assert order[:4].count("heavy") == 3
    assert "light" in order[:4]


def test_batch_gate_holds_batch_behind_nonempty_queue():
    clk = _Clock()
    target = _FakeTarget(clk)
    fd = _fd_of(clk, target)
    target._scheduler.queue.append(object())    # target FIFO occupied
    h = fd.submit([1], max_new_tokens=1, priority="batch")
    assert h.phase == "pending" and target.submitted == []
    assert fd.metrics()["frontdoor"]["stats"]["deferrals"] >= 1
    # Queue clears -> gate opens on the cold path, bounded by slots.
    target._scheduler.queue.clear()
    fd.submit([2], max_new_tokens=1, priority="batch")
    assert len(target.submitted) == 2
    # Cold bound: batch in flight never exceeds the slot count (2).
    fd.submit([3], max_new_tokens=1, priority="batch")
    assert len(target.submitted) == 2


def test_batch_flows_when_warm_predictor_has_headroom():
    clk = _Clock()
    target = _FakeTarget(clk)
    fd = _fd_of(clk, target, batch_headroom=1.0, classes=(
        PriorityClass("interactive", ttft_budget_ms=60_000.0),
        PriorityClass("batch"),
    ))
    _warm_admission(fd, clk, rate=100.0)
    # Warm + huge budget: the gate admits batch PAST the slot bound.
    for i in range(5):
        fd.submit([i], max_new_tokens=1, priority="batch")
    assert len(target.submitted) == 5


def test_preemption_parks_batch_for_latency_budget():
    clk = _Clock()
    target = _FakeTarget(clk, host_offload=True)
    fd = _fd_of(clk, target)
    b = fd.submit([1], max_new_tokens=8, priority="batch")
    assert b.phase == "decoding"
    _warm_admission(fd, clk, rate=10.0)
    target._scheduler.queue.extend(range(50))   # budget at risk
    with pytest.raises(QueueFull):
        fd.submit([2], max_new_tokens=1)        # slo shed, but first...
    assert target.preempt_calls == [b._req.rid]  # ...batch was parked
    assert b._req.phase == "swapped"
    stats = fd.metrics()["frontdoor"]
    assert stats["stats"]["preemptions"] == 1
    assert stats["preempted_held"] == 1
    assert stats["preemptions_by_class"] == {"batch": 1}
    # Pressure gone -> the hold lifts and the victim resumes.
    target._scheduler.queue.clear()
    fd.step()
    assert target.release_calls == [b._req.rid]
    assert fd.metrics()["frontdoor"]["preempted_held"] == 0


# ------------------------------------------------- class-aware scheduler


def test_scheduler_retry_after_is_class_aware():
    sched = Scheduler(num_slots=2, max_queue=4)
    # Global: one completion every 2 s. Interactive: every 0.1 s.
    sched._finish_times.extend([0.0, 2.0, 4.0, 6.0])
    sched._finish_by_class["interactive"] = collections.deque(
        [10.0, 10.1, 10.2], maxlen=32)
    assert sched.retry_after_s() == pytest.approx(2.0)
    assert sched.retry_after_s("interactive") == pytest.approx(0.1)
    # A class without evidence of its own falls back to the global rate.
    assert sched.retry_after_s("batch") == pytest.approx(2.0)
    # The structured error carries class, tenant and the class hint.
    err = sched.queue_full_error(priority="interactive", tenant="t9")
    assert err.reason == "queue_full"
    assert err.priority == "interactive" and err.tenant == "t9"
    assert err.retry_after_s == pytest.approx(0.1)
    # The hint clamp: absurdly slow evidence caps at RETRY_AFTER_CAP_S.
    sched._finish_by_class["interactive"] = collections.deque(
        [0.0, 1e6], maxlen=32)
    assert sched.retry_after_s("interactive") == RETRY_AFTER_CAP_S


# -------------------------------------------------------- observability


def test_metrics_and_prometheus_carry_class_tenant_labels():
    clk = _Clock()
    fd = _fd_of(clk, _FakeTarget(clk),
                tenants=(TenantPolicy("acme", rate=1e-6, burst=1.0),))
    fd.submit([1], max_new_tokens=2, tenant="acme")
    fd.step()
    with pytest.raises(QueueFull):
        fd.submit([1], max_new_tokens=2, tenant="acme")
    m = fd.metrics()["frontdoor"]
    assert m["stats"]["admitted"] == 1 and m["stats"]["sheds"] == 1
    assert m["admissions"] == {"interactive/acme": 1}
    assert m["sheds"] == {"interactive/acme/rate_limit": 1}
    assert m["predictor"]["cold"] in (True, False)
    kinds, samples = _parse_prom(fd.prometheus())
    assert kinds["ds_tpu_frontdoor_admissions_total"] == "counter"
    assert kinds["ds_tpu_frontdoor_sheds_total"] == "counter"
    assert samples[("ds_tpu_frontdoor_admissions_total",
                    (("engine", "frontdoor"),
                     ("priority", "interactive"),
                     ("tenant", "acme")))] == 1.0
    assert samples[("ds_tpu_frontdoor_sheds_total",
                    (("engine", "frontdoor"),
                     ("priority", "interactive"),
                     ("reason", "rate_limit"),
                     ("tenant", "acme")))] == 1.0
    assert samples[("ds_tpu_frontdoor_completed_total",
                    (("engine", "frontdoor"),
                     ("priority", "interactive"),
                     ("tenant", "acme")))] == 1.0


# ------------------------------------------------------------ streaming


_STREAM_LENS = [5, 9, 6, 12]


def _stream_kw(i):
    kw = {"max_new_tokens": 5 + (i % 3)}
    if i % 2:
        kw["temperature"] = 0.7
        kw["seed"] = 100 + i
    return kw


def _drain_round_robin(streams):
    """Interleave consumption across all streams — the harshest
    ordering for a cursor bug — and return each stream's token list."""
    out = [[] for _ in streams]
    live = set(range(len(streams)))
    while live:
        for i in sorted(live):
            try:
                out[i].append(next(streams[i]))
            except StopIteration:
                live.discard(i)
    return out


def test_stream_parity_greedy_and_sampled_vs_batch_harvest():
    cfg, model, params = make_model()
    prompts = prompts_of(cfg, _STREAM_LENS)
    # Reference: the same submissions batch-harvested on a bare engine.
    ref_eng = engine_of(model, params)
    ref = [ref_eng.submit(p, **_stream_kw(i))
           for i, p in enumerate(prompts)]
    ref_eng.run()

    eng = engine_of(model, params)
    fd = FrontDoor(eng, FrontDoorConfig(classes=(
        PriorityClass("interactive", ttft_budget_ms=60_000.0),
        PriorityClass("batch", preemptible=True),
    )))
    streams = [fd.stream(p, **_stream_kw(i))
               for i, p in enumerate(prompts)]
    got = _drain_round_robin(streams)
    assert got == [list(r.tokens) for r in ref]
    # Greedy streams also match the sequential oracle.
    for i, p in enumerate(prompts):
        if i % 2 == 0:
            want = seq_greedy(model, params, p,
                              _stream_kw(i)["max_new_tokens"])
            assert got[i] == want
    # Streaming is pure host-side plumbing: ONE compiled program.
    assert fd.compile_count == 1
    assert fd.idle
    stats = fd.metrics()["frontdoor"]["stats"]
    assert stats["completed"] == len(prompts)


def test_stream_close_cancels_in_flight_request():
    cfg, model, params = make_model()
    prompts = prompts_of(cfg, [6, 7])
    eng = engine_of(model, params)
    fd = FrontDoor(eng, FrontDoorConfig(classes=(
        PriorityClass("interactive", ttft_budget_ms=60_000.0),
        PriorityClass("batch"),
    )))
    victim = fd.stream(prompts[0], max_new_tokens=8)
    other = fd.stream(prompts[1], max_new_tokens=4)
    first = next(victim)
    victim.close()
    assert victim.handle.phase == "cancelled"
    with pytest.raises(StopIteration):
        next(victim)
    # The surviving stream still completes bit-identically.
    rest = [t for t in other]
    want = seq_greedy(model, params, prompts[1], 4)
    assert rest == want
    assert isinstance(first, int)
    assert fd.wait_idle(timeout_s=30.0)


def test_stream_for_existing_handle_and_context_manager():
    cfg, model, params = make_model()
    p = prompts_of(cfg, [6])[0]
    eng = engine_of(model, params)
    fd = FrontDoor(eng, FrontDoorConfig(classes=(
        PriorityClass("interactive", ttft_budget_ms=60_000.0),
        PriorityClass("batch"),
    )))
    h = fd.submit(p, max_new_tokens=5)
    with fd.stream_for(h) as s:
        got = list(s)
    assert got == seq_greedy(model, params, p, 5)
    # Iterating a finished handle from scratch replays the full list.
    assert list(fd.stream_for(h)) == got


# ----------------------------------------------------------- acceptance


class _StepClock(object):
    """Time that moves only when the engine steps (one tick a step) or
    the runner sleeps to its next arrival (whole ticks, rounded up: a
    float remainder would never be slept off). Under it the flood's
    arrival schedule, the front door's rates and the engine's stamps
    are all functions of the step count, on any machine."""

    TICK_S = 0.01

    def __init__(self):
        self.ticks = 0

    def __call__(self):
        return self.ticks * self.TICK_S

    def sleep(self, dt):
        self.ticks += max(1, int(math.ceil(dt / self.TICK_S)))


class _StepCounting(object):
    """The runner's target, passed through: counts ``step()`` calls,
    and notes at which count each request was submitted and at which
    its first token had come."""

    def __init__(self, target, clock):
        self._target = target
        self._clock = clock
        self.steps = 0
        self.rows = []

    def __getattr__(self, name):
        return getattr(self._target, name)

    def submit(self, prompt, **kw):
        handle = self._target.submit(prompt, **kw)
        self.rows.append({"priority": kw["priority"], "handle": handle,
                          "submitted": self.steps, "first_token": None})
        return handle

    def step(self):
        out = self._target.step()
        self.steps += 1
        self._clock.ticks += 1
        for row in self.rows:
            if row["first_token"] is None and row["handle"].tokens:
                row["first_token"] = self.steps
        return out


# A batch request is 32 tokens at 4 a step: a slot comes free within 8
# steps. Behind the front door an interactive request waits for that,
# for its own prefill, and for what the gate let into the engine's
# queue ahead of it: within 32 steps in all (26 is the most this flood
# shows). Bare, the first one waits out the flood: 428 steps.
_FLOOD_FIRST_TOKEN_STEPS = 32


@pytest.mark.parametrize("frontdoor", [True, False],
                         ids=["frontdoor", "bare_engine"])
def test_flood_interactive_first_token_by_steps(frontdoor, monkeypatch):
    """THE acceptance A/B, in engine steps: two tenants each flood two
    slots with 60 batch requests of 32 tokens while 8 interactive
    requests of 3 tokens trickle in. Through the front door every
    interactive request has its first token within
    ``_FLOOD_FIRST_TOKEN_STEPS`` of its submission and overtakes batch
    work submitted before it; the SAME offered load on the bare engine
    leaves the first interactive request behind the whole flood. Either
    way nothing is lost and the engine stays on its one program."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=2, max_queue=256,
                    host_offload=True, swap_slots=8)
    eng.generate([prompts_of(cfg, [8])[0]], max_new_tokens=2)
    eng.recompile_detector.mark_warm()
    eng.metrics(reset=True)
    clock = _StepClock()
    # The engine stamps with time.time(): give it the same clock, so
    # the front door's predictor never subtracts one clock from another.
    monkeypatch.setattr(time, "time", clock)
    spec = WorkloadSpec.mixed_tenants(
        tenants=("tenant_a", "tenant_b"), seed=29,
        interactive_rate=2.0, interactive_n=8,
        batch_rate=200.0, batch_ramp_from=200.0, batch_n=60,
        prompt_dist="lognormal", prompt_mean=6, prompt_min=2,
        prompt_max=10,
        interactive_overrides={"output_dist": "fixed", "output_mean": 3},
        batch_overrides={"output_dist": "fixed", "output_mean": 32},
        vocab_size=cfg.vocab_size)
    target = eng
    if frontdoor:
        target = FrontDoor(eng, FrontDoorConfig(
            classes=(
                PriorityClass("interactive", ttft_budget_ms=1000.0,
                              weight=4.0, shed_on_budget=False),
                PriorityClass("batch", weight=1.0, preemptible=True),
            ),
            tenants=(TenantPolicy("tenant_a"), TenantPolicy("tenant_b")),
            batch_headroom=0.25), clock=clock, sleep=clock.sleep)
    counted = _StepCounting(target, clock)
    res = SustainedRunner(counted, spec, window_seconds=0.25,
                          max_steps=100_000, clock=clock,
                          sleep=clock.sleep).run()
    no_budget = SLO(ttft_p99_ms=None, itl_p99_ms=None)
    rep = build_report(spec, res, no_budget,
                       class_slos={"interactive": no_budget,
                                   "batch": no_budget})["frontdoor"]

    assert res.requests_lost == 0 and res.shed == 0
    assert target.metrics()["compile_count"] == 1
    assert rep["classes"]["batch"]["completed"] == 120
    assert rep["classes"]["interactive"]["completed"] == 16
    assert set(rep["tenants"]) == {"tenant_a", "tenant_b"}

    batch = [r for r in counted.rows if r["priority"] == "batch"]
    inter = [r for r in counted.rows if r["priority"] == "interactive"]
    waits = [r["first_token"] - r["submitted"] for r in inter]
    # The first interactive request arrives with the whole flood
    # already submitted: how much of it is served a first token first?
    first = inter[0]
    assert all(b["submitted"] <= first["submitted"] for b in batch)
    ahead = sum(1 for b in batch if b["first_token"] <= first["first_token"])
    if frontdoor:
        assert max(waits) <= _FLOOD_FIRST_TOKEN_STEPS
        assert ahead < len(batch) // 4
    else:
        assert waits[0] > 10 * _FLOOD_FIRST_TOKEN_STEPS
        assert ahead == len(batch)
