"""Replicated serving fleet (inference/fleet.py + router.py).

The contract under test (docs/RESILIENCE.md, fleet section):
1. FAILOVER INVARIANT — killing a replica mid-stream loses ZERO
   requests: its durable records re-submit to survivors with residual
   budgets, and every stream (greedy AND sampled, spec AND non-spec)
   completes bit-identically to a fault-free single-engine run — the
   positional fold_in(seed, pos) rng makes emissions independent of
   replica, batch composition, and chunk timing. Survivors' compile
   counts do not move (same shapes -> jit cache hits).
2. ROUTING — health-weighted least-loaded over the live gauges;
   deterministic under a fixed router seed; one circuit breaker per
   replica (closed/open/half-open, exponential backoff floored by the
   shed's own retry_after_s hint).
3. EDGES — all breakers open -> fleet-level structured QueueFull with
   the MIN retry hint; submit during a rolling drain lands on the
   non-draining replica; cancel() reaches the owning replica wherever
   the request lives (live owner, dead owner, orphan mid-failover).
4. ROLLING RESTART — one replica at a time, SLO headroom verified from
   the timeseries window first; no headroom -> skipped, not forced.
5. LIFECYCLE — close() joins the stepping threads and stops every
   watchdog timer; idempotent.
"""

import time
import types

import numpy as np
import pytest

from deepspeed_tpu.inference import (
    CircuitBreaker,
    EngineDeadError,
    EngineDraining,
    Fault,
    FaultPlan,
    QueueFull,
    Router,
    Scheduler,
    ServingFleet,
)
from deepspeed_tpu.inference.router import BREAKER_STATES, DEGRADED_PENALTY
from deepspeed_tpu.inference.scheduler import RETRY_AFTER_CAP_S
from deepspeed_tpu.loadgen import SustainedRunner, WorkloadSpec
from deepspeed_tpu.parallel.mesh import replica_devices
from tests.unit.test_chunked_prefill import (
    engine_of,
    make_model,
    prompts_of,
)
from tests.unit.test_telemetry import _parse_prom

# One deterministic model init for the whole module (the same sharing
# move test_resilience.py makes — model.init dominates test wall time,
# and every engine treats params as read-only).
_MODEL = {}


def _shared_model():
    if "m" not in _MODEL:
        _MODEL["m"] = make_model()
    return _MODEL["m"]


def fleet_of(model, params, n_replicas=2, start=False, seed=0,
             breaker_factory=None, **cfg):
    cfg.setdefault("max_slots", 3)
    cfg.setdefault("max_len", 64)
    cfg.setdefault("chunk_size", 4)
    cfg.setdefault("prefill_chunk", 8)
    cfg.setdefault("max_queue", 32)
    return ServingFleet(model, params, n_replicas=n_replicas, config=cfg,
                        seed=seed, start=start, window_seconds=0.05,
                        breaker_factory=breaker_factory)


class _Clock(object):
    """Manually advanced monotonic clock for breaker tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# The mixed stream every fleet parity test submits: spec + non-spec,
# greedy + sampled, ragged prompt lengths — same shape as the single-
# engine recovery-invariant workload, doubled so both replicas serve.
_MIX_LENS = [5, 9, 6, 12, 7, 8]


def _mix_kw(i):
    kw = {"max_new_tokens": 5 + (i % 3)}
    if i % 2:
        kw["temperature"] = 0.7
        kw["seed"] = 100 + i
    if i % 3 == 0:
        kw["spec_decode"] = False
    return kw


_REF_CACHE = {}


def _reference_tokens(model, params, prompts, **cfg):
    """Fault-free single-engine run of the mixed stream — the oracle
    every fleet stream must match bit for bit. Memoized: the parity and
    failover tests share one workload, so the oracle runs once. Only
    pass numerics-affecting config here (fault plumbing changes no
    tokens and would just split the cache)."""
    key = (id(model), tuple(tuple(p) for p in prompts),
           tuple(sorted(cfg.items())))
    if key not in _REF_CACHE:
        eng = engine_of(model, params, **cfg)
        reqs = [eng.submit(p, **_mix_kw(i)) for i, p in enumerate(prompts)]
        eng.run()
        _REF_CACHE[key] = [list(r.tokens) for r in reqs]
    return _REF_CACHE[key]


# ----------------------------------------------------- circuit breaker


def test_breaker_trips_after_threshold_and_probes():
    clk = _Clock()
    b = CircuitBreaker(failure_threshold=3, backoff_base_s=0.5, clock=clk)
    assert BREAKER_STATES == ("closed", "open", "half_open")
    assert b.state == "closed" and b.allow()
    b.record_failure()
    b.record_failure()
    assert b.state == "closed" and b.allow()    # under threshold: load
    b.record_failure()                          # third consecutive: sick
    assert b.state == "open" and b.trips == 1
    assert b.backoff_s == 0.5
    assert not b.allow()
    assert b.retry_after_s() == pytest.approx(0.5)
    clk.advance(0.5)
    # The allow() that finds an elapsed backoff IS the half-open probe:
    # exactly one passes, the next caller is refused.
    assert b.allow() and b.state == "half_open" and b.probes == 1
    assert not b.allow()
    assert b.retry_after_s() == 0.0             # would grant (probe) now
    b.record_failure()                          # failed probe: re-trip...
    assert b.state == "open" and b.backoff_s == 1.0  # ...at 2x backoff
    clk.advance(1.0)
    assert b.allow() and b.probes == 2
    b.record_success()                          # probe served: recovered
    assert b.state == "closed" and b.backoff_s == 0.0
    assert b.consecutive_failures == 0 and b.allow()


def test_breaker_backoff_floor_from_retry_hint_and_cap():
    clk = _Clock()
    b = CircuitBreaker(failure_threshold=1, backoff_base_s=0.5,
                       backoff_max_s=30.0, clock=clk)
    # A shed's retry_after_s hint floors the backoff: never re-probe
    # faster than the replica said it could free a queue position.
    b.record_failure(retry_after_s=5.0)
    assert b.state == "open" and b.backoff_s == 5.0
    clk.advance(5.0)
    assert b.allow()
    b.record_failure()                           # no hint: pure doubling
    assert b.backoff_s == 10.0
    clk.advance(10.0)
    assert b.allow()
    # An absurd hint is clamped to the scheduler's cap (60s) and the
    # result to the breaker's own ceiling.
    b.record_failure(retry_after_s=1e6)
    assert b.backoff_s == min(RETRY_AFTER_CAP_S, 30.0) == 30.0
    assert b.retry_after_s() == pytest.approx(30.0)
    with pytest.raises(ValueError):
        CircuitBreaker(failure_threshold=0)
    with pytest.raises(ValueError):
        CircuitBreaker(backoff_base_s=0.0)
    with pytest.raises(ValueError):
        CircuitBreaker(backoff_base_s=2.0, backoff_max_s=1.0)


# --------------------------------------------------------------- router


def _view(occ, q, slots=4, health="healthy"):
    return types.SimpleNamespace(slot_occupancy=occ, queue_depth=q,
                                 max_slots=slots, health=health)


def test_router_scores_load_and_health():
    assert Router.score(_view(0.5, 2, slots=4)) == pytest.approx(1.0)
    assert Router.score(_view(0.0, 0)) == 0.0
    # Degraded keeps serving but only after healthier peers: the
    # penalty multiplier dominates any realistic load gap.
    healthy_full = Router.score(_view(1.0, 4, slots=4))
    degraded_idle = Router.score(_view(0.0, 0, health="degraded"))
    assert degraded_idle == pytest.approx(DEGRADED_PENALTY)
    assert degraded_idle > healthy_full
    assert Router.score(_view(0.0, 0, health="dead")) == float("inf")


def test_router_orders_least_loaded_first_dead_last():
    light, heavy = _view(0.25, 0), _view(1.0, 3)
    degraded, dead = _view(0.0, 0, health="degraded"), \
        _view(0.0, 0, health="dead")
    got = Router(seed=1).order([dead, heavy, degraded, light])
    assert got == [light, heavy, degraded, dead]


def test_router_tie_break_deterministic_under_seed():
    views = [_view(0.5, 1) for _ in range(4)]
    for v, name in zip(views, "abcd"):
        v.name = name
    seq_a = [[v.name for v in Router(seed=9).order(views)]
             for _ in range(3)]
    seq_b = [[v.name for v in Router(seed=9).order(views)]
             for _ in range(3)]
    # Same seed -> the same choice SEQUENCE (draws advance the rng, so
    # individual calls may differ — the sequence is the contract).
    assert seq_a == seq_b
    assert all(sorted(s) == ["a", "b", "c", "d"] for s in seq_a)


# --------------------------------------------- structured backpressure


def test_retry_after_clamped_and_replica_id_in_payload():
    s = Scheduler(2, 1, replica_id=7)
    assert s.retry_after_s() is None            # no rate, no guess
    # A glacial completion rate would suggest a 10000s wait — the hint
    # is clamped to the cap so breaker backoff math stays sane.
    s._finish_times.extend([0.0, 10000.0])
    assert s.retry_after_s() == RETRY_AFTER_CAP_S
    p = np.arange(4, dtype=np.int32)
    s.submit(p, 4, 0.0, None, None, 0)
    with pytest.raises(QueueFull) as ei:
        s.submit(p, 4, 0.0, None, None, 0)
    e = ei.value
    assert e.replica_id == 7
    assert e.queue_depth == 1
    assert 0.0 <= e.retry_after_s <= RETRY_AFTER_CAP_S


# ---------------------------------------------------- fleet: routing


def test_fleet_routing_deterministic_under_seed():
    cfg, model, params = _shared_model()
    prompts = prompts_of(cfg, [6, 6, 6, 6, 6, 6])

    def owners(seed):
        fleet = fleet_of(model, params, seed=seed)
        try:
            return [fleet.submit(p, max_new_tokens=4).replica_id
                    for p in prompts]
        finally:
            fleet.close()

    a = owners(5)
    assert a == owners(5)                       # same seed, same routing
    # Least-loaded: with live queue gauges, consecutive submits to an
    # un-stepped fleet must alternate (the loaded replica scores worse).
    assert all(a[i] != a[i + 1] for i in range(0, len(a), 2))
    assert sorted(set(a)) == [0, 1]


def test_replica_devices_round_robin():
    devs = replica_devices(3, devices=["d0", "d1"])
    assert devs == ["d0", "d1", "d0"]
    assert len(replica_devices(2)) == 2
    with pytest.raises(ValueError):
        replica_devices(0)


# ------------------------------------------- fleet: serve + telemetry


def test_fleet_serves_bit_identical_with_replica_labeled_metrics():
    """Threaded fleet, mixed spec/non-spec greedy/sampled stream: every
    stream matches the single-engine oracle bit for bit (positional rng
    — placement must not matter), one compile per replica, and the
    merged prometheus exposition carries one replica-labeled series per
    engine."""
    cfg, model, params = _shared_model()
    prompts = prompts_of(cfg, _MIX_LENS)
    serve = {"spec_decode": True, "spec_k": 2, "spec_ngram": 2}
    ref = _reference_tokens(model, params, prompts, **serve)
    fleet = fleet_of(model, params, start=True, **serve)
    try:
        frs = [fleet.submit(p, **_mix_kw(i))
               for i, p in enumerate(prompts)]
        assert fleet.wait_idle(timeout_s=120.0)
        assert [fr.tokens for fr in frs] == ref
        assert all(fr.phase == "done" and fr.done for fr in frs)
        assert all(fr.submit_time <= fr.first_token_time <= fr.finish_time
                   for fr in frs)
        assert sorted(set(fr.replica_id for fr in frs)) == [0, 1]
        # Both replicas compiled the mixed program exactly once.
        assert fleet.compile_counts == {0: 1, 1: 1}
        got = fleet.harvest()
        assert sorted(fr.fid for fr in got) == [fr.fid for fr in frs]
        assert fleet.harvest() == []            # harvest drains the table
        m = fleet.metrics()
        assert m["fleet"]["requests_completed"] == len(prompts)
        assert m["fleet"]["alive"] == 2 and m["fleet"]["health"] == "healthy"
        assert m["fleet"]["failovers"] == 0 and m["fleet"]["orphans"] == 0
        assert m["fleet"]["breaker_states"] == {0: "closed", 1: "closed"}
        assert set(m["replicas"]) == {0, 1}
        kinds, samples = _parse_prom(fleet.prometheus())
        assert kinds["ds_tpu_tokens_out_total"] == "counter"
        for rid in ("0", "1"):
            lbl = (("engine", "inference"), ("replica", rid))
            assert samples[("ds_tpu_tokens_out_total", lbl)] > 0
            assert ("ds_tpu_queue_depth", lbl) in samples
    finally:
        fleet.close()


# -------------------------------------------------- failover invariant


def test_failover_invariant_mid_stream_kill():
    """THE invariant: kill replica 0 mid-decode under a mixed workload
    — zero requests lost, every stream bit-identical to the fault-free
    single-engine run, survivor's compile count unchanged, fleet still
    healthy. Driven start=False so the kill lands at a deterministic
    point."""
    cfg, model, params = _shared_model()
    prompts = prompts_of(cfg, _MIX_LENS)
    numerics = {"spec_decode": True, "spec_k": 2, "spec_ngram": 2}
    serve = dict(numerics, fault_injection=True, recovery_max_retries=0)
    ref = _reference_tokens(model, params, prompts, **numerics)
    fleet = fleet_of(model, params, start=False, **serve)
    try:
        frs = [fleet.submit(p, **_mix_kw(i))
               for i, p in enumerate(prompts)]
        victims = [fr for fr in frs if fr.replica_id == 0]
        assert victims and len(victims) < len(frs)
        # Step until replica 0 is mid-stream: some victim has emitted
        # tokens but not finished — the kill must interrupt live decode.
        for _ in range(200):
            if any(fr.tokens and not fr.done for fr in victims):
                break
            fleet.step()
        else:
            pytest.fail("replica 0 never reached mid-stream")
        survivor_compiles = fleet.compile_counts[1]
        emitted_at_kill = {fr.fid: len(fr.tokens) for fr in victims}
        unfinished_at_kill = {fr.fid for fr in victims if not fr.done}
        fleet.inject_faults(
            FaultPlan(faults=(Fault("raise", step=0),)), replica=0)
        assert fleet.wait_idle(timeout_s=120.0)

        assert all(fr.phase == "done" for fr in frs)         # zero lost
        assert [fr.tokens for fr in frs] == ref              # bit-identical
        moved = [fr for fr in frs if fr.failovers > 0]
        assert {fr.fid for fr in moved} == unfinished_at_kill
        assert all(fr.replica_id == 1 for fr in moved)
        assert fleet.failovers == len(moved) >= 1
        # Survivor absorbed the orphans without recompiling (same
        # request shapes -> jit cache hit): its one program, still.
        assert fleet.compile_counts[1] == survivor_compiles == 1
        m = fleet.metrics()["fleet"]
        assert m["health"] == "healthy" and m["alive"] == 1
        assert m["faults_injected"] == 1 and m["orphans"] == 0
        assert [rep.rid for rep in fleet.replicas if not rep.alive] == [0]
        # TTFT stamped once: tokens emitted pre-kill keep their stamp.
        pre_kill = [fr for fr in moved if emitted_at_kill[fr.fid] > 0]
        assert all(fr.first_token_time is not None for fr in pre_kill)
        # Rolling drain on the survivor fleet: the dead replica is
        # skipped outright, and the LONE survivor is refused (nobody
        # left to absorb its load) unless the caller forces it.
        report = fleet.rolling_drain(timeout_s=30.0)
        assert report[0] == {"replica": 0, "drained": False,
                             "skipped": "dead"}
        assert report[1]["skipped"] == "no_headroom"
        assert report[1]["headroom"]["survivors"] == []
        forced = fleet.rolling_drain(timeout_s=30.0, require_headroom=False)
        assert forced[1]["drained"]
        assert fleet.replicas[1].engine.health == "healthy"
        assert fleet.health == "healthy"                  # at exit too
    finally:
        fleet.close()


# ------------------------------------------------------- fleet: edges


def test_all_open_breakers_raise_fleet_queuefull_with_min_hint():
    cfg, model, params = _shared_model()
    clk = _Clock()
    fleet = fleet_of(model, params, breaker_factory=lambda: CircuitBreaker(
        failure_threshold=1, backoff_base_s=2.0, clock=clk))
    try:
        (p,) = prompts_of(cfg, [6])
        fleet.replicas[0].breaker.trip()                  # backoff 2.0
        fleet.replicas[1].breaker.trip(retry_after_s=5.0)  # backoff 5.0
        with pytest.raises(QueueFull) as ei:
            fleet.submit(p, max_new_tokens=4)
        e = ei.value
        assert e.replica_id is None                       # fleet-level
        assert e.retry_after_s == pytest.approx(2.0)      # MIN across hints
        # Backoff elapsed on replica 0: the next submit is its half-open
        # probe, and serving it closes the breaker.
        clk.advance(2.0)
        fr = fleet.submit(p, max_new_tokens=4)
        assert fr.replica_id == 0
        assert fleet.replicas[0].breaker.state == "closed"
        assert fleet.replicas[1].breaker.state == "open"
    finally:
        fleet.close()


def test_submit_during_drain_lands_on_open_replica():
    cfg, model, params = _shared_model()
    fleet = fleet_of(model, params, seed=3)
    try:
        (p,) = prompts_of(cfg, [6])
        fleet.replicas[0].engine.close_admissions()   # rolling-drain state
        owners = [fleet.submit(p, max_new_tokens=4).replica_id
                  for _ in range(4)]
        assert owners == [1, 1, 1, 1]
        fleet.replicas[1].engine.close_admissions()
        with pytest.raises(EngineDraining):
            fleet.submit(p, max_new_tokens=4)
        fleet.undrain_all()
        # Replica 0 is now the least loaded — admission reopens there.
        assert fleet.submit(p, max_new_tokens=4).replica_id == 0
        for rep in fleet.replicas:
            rep.failed = True
        with pytest.raises(EngineDeadError):
            fleet.submit(p, max_new_tokens=4)
    finally:
        fleet.close()


def test_cancel_reaches_live_owner_and_dead_owner():
    cfg, model, params = _shared_model()
    fleet = fleet_of(model, params)
    try:
        ps = prompts_of(cfg, [6, 6])
        fr0 = fleet.submit(ps[0], max_new_tokens=8)
        fr1 = fleet.submit(ps[1], max_new_tokens=8)
        assert fr0.replica_id != fr1.replica_id
        assert fleet.cancel(fr0)                   # live owner: engine path
        assert fr0.phase == "cancelled" and fr0.done
        assert not fleet.cancel(fr0)               # already finished
        # Dead owner, failover not yet run: cancel must stay host-side
        # (the dead pool's buffers are gone) and still succeed.
        fleet.replicas[fr1.replica_id].failed = True
        assert fleet.cancel(fr1)
        assert fr1.phase == "cancelled"
        assert fleet.idle
    finally:
        fleet.close()


def test_cancel_reaches_orphan_mid_failover():
    """Kill a replica whose request CANNOT be placed (the survivor is
    saturated): the request parks in the orphan list, idle stays False
    so drive loops keep pumping, and cancel() settles it there."""
    cfg, model, params = _shared_model()
    fleet = fleet_of(model, params, start=False, max_slots=1, max_queue=1,
                     fault_injection=True, recovery_max_retries=0)
    try:
        ps = prompts_of(cfg, [6, 6, 6])
        fleet.replicas[1].engine.close_admissions()
        fr_a = fleet.submit(ps[0], max_new_tokens=8)     # -> replica 0
        assert fr_a.replica_id == 0
        fleet.replicas[1].engine.undrain()
        fleet.replicas[0].engine.close_admissions()
        fr_b = fleet.submit(ps[1], max_new_tokens=6)     # -> replica 1
        fleet.step()                                     # B takes the slot
        fr_c = fleet.submit(ps[2], max_new_tokens=6)     # fills 1's queue
        assert fr_b.replica_id == fr_c.replica_id == 1
        fleet.inject_faults(
            FaultPlan(faults=(Fault("raise", step=0),)), replica=0)
        fleet.step()                       # replica 0 dies; A orphans
        assert fr_a.replica_id is None and fr_a.phase == "queued"
        assert not fleet.idle              # orphan pins the fleet busy
        assert fleet.cancel(fr_a)
        assert fr_a.phase == "cancelled" and fr_a.done
        assert fleet.wait_idle(timeout_s=120.0)
        assert fr_b.phase == "done" and fr_c.phase == "done"
        done = fleet.harvest()
        assert {fr.fid for fr in done} == {fr_a.fid, fr_b.fid, fr_c.fid}
    finally:
        fleet.close()


# ------------------------------------------------------ rolling drain


def test_rolling_drain_verifies_headroom_then_rotates():
    cfg, model, params = _shared_model()
    fleet = fleet_of(model, params, start=True)
    try:
        frs = [fleet.submit(p, max_new_tokens=3)
               for p in prompts_of(cfg, [6, 8])]
        assert fleet.wait_idle(timeout_s=120.0)
        report = fleet.rolling_drain(timeout_s=30.0)
        assert [r["replica"] for r in report] == [0, 1]
        assert all(r["drained"] for r in report)
        for r in report:
            h = r["headroom"]
            assert h["spare_capacity"] >= h["in_flight"]
            assert h["survivors"] == [1 - r["replica"]]
        # Rotation complete: both replicas reopened and accepting.
        assert all(rep.engine.health == "healthy"
                   for rep in fleet.replicas)
        fr = fleet.submit(prompts_of(cfg, [5])[0], max_new_tokens=2)
        assert fleet.wait_idle(timeout_s=60.0) and fr.phase == "done"
        assert all(fr.done for fr in frs)
    finally:
        fleet.close()


def test_rolling_drain_skips_without_headroom_unless_forced():
    cfg, model, params = _shared_model()
    fleet = fleet_of(model, params, n_replicas=1)
    try:
        # A lone replica has no survivors to absorb its load: the safe
        # path refuses, the forced path proceeds.
        report = fleet.rolling_drain()
        assert report == [{
            "replica": 0, "drained": False, "skipped": "no_headroom",
            "headroom": report[0]["headroom"]}]
        assert report[0]["headroom"]["survivors"] == []
        forced = fleet.rolling_drain(require_headroom=False)
        assert forced[0]["drained"]
        assert fleet.replicas[0].engine.health == "healthy"
    finally:
        fleet.close()


# ---------------------------------------------------------- lifecycle


def test_close_joins_threads_and_stops_watchdogs():
    cfg, model, params = _shared_model()
    fleet = fleet_of(model, params, start=True)
    threads = [rep.thread for rep in fleet.replicas]
    assert all(t.is_alive() for t in threads)
    fleet.close()
    assert all(not t.is_alive() for t in threads)
    assert all(rep.engine._watchdog._timer is None
               for rep in fleet.replicas)
    fleet.close()                                  # idempotent
    with pytest.raises(RuntimeError):
        fleet.submit(prompts_of(cfg, [4])[0], max_new_tokens=2)
    with pytest.raises(ValueError):
        ServingFleet(model, params, n_replicas=0)


# ------------------------------------------------- loadgen chaos mode


def test_runner_chaos_kills_replica_mid_run_zero_lost():
    """The loadgen chaos mode against a fleet: chaos_replica targets
    one replica's injector, the kill fires against live traffic, and
    the open-loop run completes with zero requests lost."""
    cfg, model, params = _shared_model()
    fleet = fleet_of(model, params, start=True, max_slots=4, max_queue=64,
                     fault_injection=True, recovery_max_retries=0)
    try:
        spec = WorkloadSpec(rate=80.0, n_requests=10, prompt_mean=8,
                            prompt_max=16, output_mean=4, output_max=8,
                            vocab_size=cfg.vocab_size, seed=11)
        plan = FaultPlan(faults=(Fault("raise", step=0),))
        runner = SustainedRunner(fleet, spec, window_seconds=0.1,
                                 max_steps=200_000, chaos_plan=plan,
                                 chaos_after_s=0.0, chaos_replica=0)
        res = runner.run()
        assert res.faults_injected == 1
        assert res.requests_lost == 0 and res.shed == 0
        assert res.completed == res.submitted == 10
        m = fleet.metrics()["fleet"]
        assert m["alive"] == 1 and m["health"] == "healthy"
        assert not fleet.replicas[0].alive
        assert fleet.compile_counts[1] == 1
    finally:
        fleet.close()


# ------------------------------------- failover under the fleet's threads


def test_threaded_kill_with_a_wave_in_flight_bit_identical():
    """What the two tests above leave open: the failover invariant
    under the fleet's OWN stepping threads, with the prefix cache and
    the fleet's prefix directory on, and a second wave of submissions
    landing around the kill. A template-heavy mixed stream (greedy +
    sampled, spec + non-spec) of 32-token answers; replica 0 is armed
    to die on its seventh working step, which no answer can have
    reached its end by (prefill alone is three) -> zero lost, every
    stream bit-identical to the lone fault-free engine, the survivor
    still on its one program. Nothing asserted depends on how the
    threads interleave."""
    cfg, model, params = _shared_model()
    numerics = {"spec_decode": True, "spec_k": 2, "spec_ngram": 2,
                "prefix_cache": True, "prefix_slots": 4,
                "prefix_len": 16, "min_prefix_len": 4}
    shape = dict(numerics, max_slots=2, chunk_size=2, prefill_chunk=4)
    rng = np.random.RandomState(11)
    templates = rng.randint(0, cfg.vocab_size, size=(2, 8))
    prompts = [np.concatenate(
        [templates[i % 2], rng.randint(0, cfg.vocab_size, size=4 + i % 5)]
    ).astype(np.int32) for i in range(8)]

    def kw(i):
        return {"max_new_tokens": 32, "temperature": 0.7 if i % 2 else 0.0,
                "seed": 1000 + i, "spec_decode": i % 3 != 0}

    ref_eng = engine_of(model, params, **shape)
    ref = [ref_eng.submit(p, **kw(i)) for i, p in enumerate(prompts)]
    ref_eng.run()

    fleet = fleet_of(model, params, start=True, fault_injection=True,
                     recovery_max_retries=0, **shape)
    try:
        # An idle replica takes no engine step, so the plan counts
        # replica 0's WORKING steps from its first request on.
        fleet.inject_faults(
            FaultPlan(faults=(Fault("raise", step=6),)), replica=0)
        wave1 = [fleet.submit(p, **kw(i))
                 for i, p in enumerate(prompts[:4])]
        assert any(fr.replica_id == 0 for fr in wave1)
        # The second wave lands while replica 0 is mid-stream or just
        # dead (where exactly is the threads' business).
        for _ in range(60_000):
            if not fleet.replicas[0].alive or any(
                    fr.tokens for fr in wave1 if fr.replica_id == 0):
                break
            time.sleep(0.001)
        wave2 = [fleet.submit(p, **kw(4 + i))
                 for i, p in enumerate(prompts[4:])]
        frs = wave1 + wave2
        assert fleet.wait_idle(timeout_s=300.0)

        assert all(fr.phase == "done" for fr in frs)         # zero lost
        assert [fr.tokens for fr in frs] == [r.tokens for r in ref]
        assert [rep.rid for rep in fleet.replicas if not rep.alive] == [0]
        assert fleet.failovers >= 1
        assert any(fr.failovers for fr in wave1)
        assert fleet.compile_counts[1] == 1
        assert fleet.health == "healthy"
    finally:
        fleet.close()


# ------------------------------------------------ fleet metrics windows


def test_fleet_metrics_reset_brackets_like_a_lone_engine():
    """Satellite: ``fleet.metrics(reset=True)`` windows the AGGREGATE
    exactly like a lone engine's metrics — two resets bracket the work
    between them (a caller's warmup scrub) — even though the fleet's own
    timeseries collector clobbers the per-engine counter windows on
    every tick, and even for replicas that die between brackets."""
    cfg, model, params = _shared_model()
    fleet = fleet_of(model, params, n_replicas=2, start=False)
    try:
        ps = prompts_of(cfg, [5, 9, 7])
        batch_a = [fleet.submit(p, max_new_tokens=4) for p in ps]
        assert fleet.wait_idle(timeout_s=120.0)
        m1 = fleet.metrics(reset=True)
        assert m1["fleet"]["requests_completed"] == len(batch_a)
        tokens_a = m1["fleet"]["tokens_out"]
        assert tokens_a == sum(len(fr.tokens) for fr in batch_a) > 0
        # The window reopened: an immediate read shows nothing.
        m2 = fleet.metrics()
        assert m2["fleet"]["requests_completed"] == 0
        assert m2["fleet"]["tokens_out"] == 0
        # Second bracket sees ONLY the work since the first reset.
        batch_b = [fleet.submit(p, max_new_tokens=4) for p in ps[:2]]
        assert fleet.wait_idle(timeout_s=120.0)
        m3 = fleet.metrics(reset=True)
        assert m3["fleet"]["requests_completed"] == len(batch_b)
        tokens_b = m3["fleet"]["tokens_out"]
        assert tokens_b == sum(len(fr.tokens) for fr in batch_b) > 0
        # Cumulative truth never rewinds: the brackets partition it.
        assert fleet.counters["tokens_out"] == tokens_a + tokens_b
        assert fleet.counters["requests_completed"] == (
            len(batch_a) + len(batch_b))
    finally:
        fleet.close()


# -------------------------------------------- failover: expert model


_MOE = {}


def _moe_setup():
    """Shared expert model (the tiny ``DecoderLM``: top-2 of 4 experts,
    served by ``DecoderAdapter``) + params + mixed prompt set."""
    if "a" not in _MOE:
        import jax

        from tests.unit.test_adapters import decoder_model
        model = decoder_model()
        params = model.init(jax.random.PRNGKey(0))["params"]
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, 256, size=(n,)).astype(np.int32)
                   for n in _MIX_LENS]
        _MOE["a"] = (model, params, prompts)
    return _MOE["a"]


def test_moe_failover_invariant_mid_stream_kill():
    """The GPT-2 failover invariant, re-pinned for the expert model
    (``DecoderAdapter``): kill a replica mid-decode and every replayed
    stream is BIT-identical to the fault-free single-engine run. This is
    only true because (a) the positional fold_in(seed, pos) rng is
    per-row state that expert routing cannot perturb, and (b) routing is
    exact top-k with no capacity, so no token's output ever depends on
    which rows share its batch (a dropped-token MoE would replay
    DIFFERENT tokens after failover — the invariant this test exists to
    hold)."""
    from deepspeed_tpu.inference import InferenceEngine
    model, params, prompts = _moe_setup()
    numerics = {"max_slots": 3, "max_len": 64, "chunk_size": 4,
                "prefill_chunk": 8, "spec_decode": True, "spec_k": 2,
                "spec_ngram": 2, "use_flash_decode": False}

    ref_eng = InferenceEngine(model, params, config=dict(numerics))
    assert ref_eng.adapter.name == "decoder"
    ref_reqs = [ref_eng.submit(p, **_mix_kw(i))
                for i, p in enumerate(prompts)]
    ref_eng.run()
    ref = [list(r.tokens) for r in ref_reqs]

    serve = dict(numerics, fault_injection=True, recovery_max_retries=0,
                 max_queue=32)
    fleet = ServingFleet(model, params, n_replicas=2, config=serve,
                         seed=0, start=False, window_seconds=0.05)
    try:
        frs = [fleet.submit(p, **_mix_kw(i))
               for i, p in enumerate(prompts)]
        victims = [fr for fr in frs if fr.replica_id == 0]
        assert victims and len(victims) < len(frs)
        for _ in range(200):
            if any(fr.tokens and not fr.done for fr in victims):
                break
            fleet.step()
        else:
            pytest.fail("replica 0 never reached mid-stream")
        unfinished_at_kill = {fr.fid for fr in victims if not fr.done}
        fleet.inject_faults(
            FaultPlan(faults=(Fault("raise", step=0),)), replica=0)
        assert fleet.wait_idle(timeout_s=120.0)

        assert all(fr.phase == "done" for fr in frs)       # zero lost
        assert [fr.tokens for fr in frs] == ref            # bit-identical
        moved = [fr for fr in frs if fr.failovers > 0]
        assert {fr.fid for fr in moved} == unfinished_at_kill
        assert all(fr.replica_id == 1 for fr in moved)
        m = fleet.metrics()["fleet"]
        assert m["health"] == "healthy" and m["orphans"] == 0
        # Per-expert load reaches the fleet's merged scrape.
        kinds, samples = _parse_prom(fleet.prometheus())
        assert kinds.get("ds_tpu_moe_expert_load") == "gauge"
        load = [v for (n, _lbl), v in samples.items()
                if n == "ds_tpu_moe_expert_load"]
        assert load and sum(load) > 0
        # Exact top-k has nothing to drop: every computed row routed.
        routed = [v for (n, _lbl), v in samples.items()
                  if n == "ds_tpu_moe_tokens_routed"]
        assert routed and all(v > 0.0 for v in routed)
    finally:
        fleet.close()
