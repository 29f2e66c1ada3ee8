"""Distributed request tracing (telemetry/distributed.py + autopsy.py).

The contract under test (docs/OBSERVABILITY.md, distributed tracing):
1. CONTEXT — one TraceContext per request, created at the entry layer
   (front door, fleet, or the scheduler's local fallback) and carried
   BY REFERENCE through every hop; ``hop()`` mints a total order that
   is exhaustive and duplicate-free even when replica threads, pump
   threads and the stream consumer stamp concurrently.
2. AUTOPSY — ``explain()`` at every layer folds all rings into one
   hop-ordered timeline with admission/routing evidence and a terminal
   cause; a request that crossed a KV handoff, sat preempted, AND was
   failed over off a killed replica still reads as ONE contiguous
   story (zero hop gaps).
3. MERGE — ``write_trace()`` produces a Perfetto-loadable file where
   flow (s/f) events bind the cross-replica hops; the validator is the
   gate (an invalid trace is never written).
4. AUTO-DUMP — a replica death (or a firing alert) with ``dump_dir``
   armed writes the merged trace + worst-K autopsies unprompted.
"""

import json
import threading
import time

import pytest

from deepspeed_tpu.inference import (
    Fault,
    FaultPlan,
    FrontDoor,
    FrontDoorConfig,
    PriorityClass,
    ServingFleet,
)
from deepspeed_tpu.telemetry import (
    TraceContext,
    build_autopsy,
    validate_trace,
    worst_requests,
)
from deepspeed_tpu.telemetry.distributed import (
    FLEET_TID_BASE,
    FRONTDOOR_TID_BASE,
)
from tests.unit.test_chunked_prefill import engine_of, make_model, prompts_of

# One deterministic model init for the whole module (same sharing move
# as test_fleet.py — model.init dominates test wall time).
_MODEL = {}


def _shared_model():
    if "m" not in _MODEL:
        _MODEL["m"] = make_model()
    return _MODEL["m"]


def fleet_of(model, params, n_replicas=2, start=False, seed=0, roles=None,
             dump_dir=None, **cfg):
    cfg.setdefault("max_slots", 3)
    cfg.setdefault("max_len", 64)
    cfg.setdefault("chunk_size", 4)
    cfg.setdefault("prefill_chunk", 8)
    cfg.setdefault("max_queue", 32)
    return ServingFleet(model, params, n_replicas=n_replicas, config=cfg,
                        seed=seed, start=start, window_seconds=0.05,
                        roles=roles, dump_dir=dump_dir)


def _hops_of(autopsy):
    return [h["hop"] for h in autopsy["hops"] if h["hop"] is not None]


# ------------------------------------------------------------- context


def test_trace_context_total_order_across_threads():
    """hop() is the total order the merged timeline sorts by: N threads
    hammering one context must consume every sequence number exactly
    once — no duplicates, no holes."""
    ctx = TraceContext(FLEET_TID_BASE + 1, origin="fleet")
    assert ctx.tid == FLEET_TID_BASE + 1 and ctx.origin == "fleet"
    got = [[] for _ in range(4)]

    def worker(bucket):
        for _ in range(500):
            bucket.append(ctx.hop())

    threads = [threading.Thread(target=worker, args=(g,)) for g in got]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    allhops = sorted(h for g in got for h in g)
    assert allhops == list(range(2000))
    # Per-thread views are strictly increasing (the shared counter
    # never hands the same thread an earlier number).
    for g in got:
        assert g == sorted(g)


def test_engine_local_fallback_tid_is_rid_and_explains():
    """A bare engine (no fleet, no front door) mints the local fallback
    context: tid == rid, hops contiguous from 0, and engine.explain()
    returns a done autopsy without any distributed plumbing."""
    cfg, model, params = _shared_model()
    eng = engine_of(model, params)
    reqs = [eng.submit(p, max_new_tokens=4)
            for p in prompts_of(cfg, [5, 7])]
    eng.run()
    for req in reqs:
        assert req.trace.tid == req.rid
        a = eng.explain(req.rid)
        assert a["tid"] == req.rid
        assert a["terminal"]["cause"] == "done"
        assert not a["terminal"]["lost_then_replayed"]
        assert a["hop_gaps"] == []
        hops = _hops_of(a)
        assert hops and hops == sorted(hops)
        names = [h["name"] for h in a["hops"]]
        assert "request/submitted" in names
    with pytest.raises(KeyError):
        eng.explain(999)


# ---------------------------------------------- the full-chain autopsy


def test_fleet_explain_handoff_preempt_failover_one_story(tmp_path):
    """THE acceptance scenario: one request crosses a KV-plane handoff
    (prefill -> decode), sits preempted and resumes, then its owner is
    killed and the orphan pump re-homes it to the survivor — and
    fleet.explain() still reads it as ONE hop-ordered story with zero
    gaps, terminal done, lost_then_replayed set. The merged trace
    carries flow arrows for BOTH cross-replica moves, and the replica
    death auto-dumps trace + autopsies into dump_dir."""
    cfg, model, params = _shared_model()
    prompts = prompts_of(cfg, [6, 9, 5])
    fleet = fleet_of(model, params, n_replicas=3,
                     roles=("prefill", "decode", "decode"),
                     start=False, host_offload=True, swap_slots=8,
                     fault_injection=True, recovery_max_retries=0,
                     dump_dir=str(tmp_path))
    try:
        frs = [fleet.submit(p, max_new_tokens=24) for p in prompts]
        assert all(fr.replica_id == 0 for fr in frs)  # role routing

        # Step until a request has been handed off to a decode replica
        # and is mid-decode there (tokens out, not done).
        victim = None
        for _ in range(400):
            fleet.step()
            live = [fr for fr in frs
                    if fr.replica_id in (1, 2) and fr.tokens
                    and not fr.done]
            if live:
                victim = live[0]
                break
        assert victim is not None, "no request reached decode mid-stream"
        owner = fleet.replicas[victim.replica_id]

        # Preempt it on its owner, hold it parked for a few steps, then
        # release — the preempt/release instants land on the owner ring
        # with the request's own hops.
        with owner.lock:
            assert owner.engine.preempt(victim._req)
        for _ in range(5):
            fleet.step()
        with owner.lock:
            owner.engine.release_preempted(victim._req)
        for _ in range(3):
            fleet.step()
        assert not victim.done, "victim finished before the kill"

        # Kill the owner; the orphan pump must re-home the request to
        # the OTHER decode replica and finish the stream.
        dead_rid = victim.replica_id
        fleet.inject_faults(FaultPlan(faults=(Fault("raise", step=0),)),
                            replica=dead_rid)
        assert fleet.wait_idle(timeout_s=120.0)
        assert all(fr.phase == "done" for fr in frs)
        assert victim.failovers >= 1
        assert victim.replica_id != dead_rid

        a = fleet.explain(victim)
        assert a["tid"] == victim.trace.tid >= FLEET_TID_BASE
        # One story: every consumed hop accounted for, in order.
        hops = _hops_of(a)
        assert hops == sorted(hops) and a["hop_gaps"] == []
        assert a["handoff_events"] >= 1
        assert a["preemptions"] >= 1
        assert a["failovers"] >= 1
        assert a["terminal"]["cause"] == "done"
        assert a["terminal"]["lost_then_replayed"]
        # Routing evidence rides the fleet-ring routed event.
        assert a["routing"] is not None and "replica" in a["routing"]
        names = [h["name"] for h in a["hops"]]
        sites = {h["name"]: h["site"] for h in a["hops"]}
        for needed in ("request/routed", "request/handoff",
                       "request/handoff_in", "request/preempted",
                       "request/preempt_released", "request/failover_out",
                       "request/failover_in"):
            assert needed in names, "missing {} in {}".format(
                needed, names)
        assert sites["request/routed"] == "fleet"
        assert sites["request/handoff"] == "replica0"
        assert sites["request/failover_out"] == "replica{}".format(
            dead_rid)
        assert sites["request/failover_in"] != \
            sites["request/failover_out"]
        # explain() by fid resolves to the same autopsy.
        assert fleet.explain(victim.fid)["tid"] == a["tid"]

        # Merged trace: loads, validates, and carries flow arrows for
        # both cross-replica moves — each crossing pids.
        path = fleet.write_trace(str(tmp_path / "merged.json"))
        doc = json.loads(open(path).read())
        validate_trace(doc)
        flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
        by_name = {}
        for e in flows:
            by_name.setdefault(e["name"], []).append(e)
        for flow_name in ("flow/handoff", "flow/failover"):
            pair = by_name.get(flow_name)
            assert pair, "no {} arrow in merged trace".format(flow_name)
            starts = [e for e in pair if e["ph"] == "s"]
            ends = [e for e in pair if e["ph"] == "f"]
            assert starts and ends
            crossing = [(s, f) for s in starts for f in ends
                        if f["id"] == s["id"] and f["pid"] != s["pid"]]
            assert crossing, "{} arrow never crosses pids".format(
                flow_name)

        # The replica death auto-dumped trace + autopsies unprompted.
        death_dumps = [d for d in fleet.dumps
                       if d["cause"].startswith("replica_death")]
        assert death_dumps, "replica death did not auto-dump"
        dump = death_dumps[0]
        validate_trace(json.loads(open(dump["trace"]).read()))
        autopsies = json.loads(open(dump["autopsies"]).read())
        assert autopsies["cause"].startswith("replica_death")
        assert autopsies["worst_requests"], "dump has no autopsies"
        worst = autopsies["worst_requests"][0]
        assert {"tid", "hops", "terminal", "hop_gaps"} <= set(worst)
    finally:
        fleet.close()


def test_fleet_failover_autopsy_threaded_fleet():
    """Same failover story under the REAL threading (start=True):
    replica threads, orphan pump and watchdogs all stamping hops —
    the autopsy must still come out gap-free and hop-ordered."""
    cfg, model, params = _shared_model()
    prompts = prompts_of(cfg, [6, 9, 5, 12])
    fleet = fleet_of(model, params, start=True, fault_injection=True,
                     recovery_max_retries=0)
    try:
        # Armed before the traffic, for replica 0's third step() call: by
        # then its first requests have tokens on their handles and a dozen
        # steps to go. (Arming from this thread once tokens show races the
        # replica's thread for its lock, and the engine, which keeps a step
        # in flight, can be through sixteen tokens before the lock is won.)
        fleet.inject_faults(FaultPlan(faults=(Fault("raise", step=2),)),
                            replica=0)
        frs = [fleet.submit(p, max_new_tokens=48) for p in prompts]
        assert fleet.wait_idle(timeout_s=120.0)
        moved = [fr for fr in frs if fr.failovers > 0]
        assert moved
        assert any(fr._prior for fr in moved), "no failover was mid-stream"
        for fr in moved:
            a = fleet.explain(fr)
            hops = _hops_of(a)
            assert hops == sorted(hops) and a["hop_gaps"] == []
            assert a["failovers"] >= 1
            assert a["terminal"]["cause"] == "done"
            assert a["terminal"]["lost_then_replayed"]
    finally:
        fleet.close()


# --------------------------------------------------- front-door explain


def test_frontdoor_explain_admission_evidence_and_stream_hops():
    """The front-door layer: explain() carries the admission
    predictor's evidence AT DECISION TIME (cold flag, rates, service
    floor) plus the dispatch hop, and the TokenStream's first-token /
    drained marks ride the same tid."""
    cfg, model, params = _shared_model()
    p = prompts_of(cfg, [6])[0]
    eng = engine_of(model, params)
    fd = FrontDoor(eng, FrontDoorConfig(classes=(
        PriorityClass("interactive", ttft_budget_ms=60_000.0),
        PriorityClass("batch", preemptible=True),
    )))
    h = fd.submit(p, max_new_tokens=5, tenant=None)
    got = list(fd.stream_for(h))
    assert len(got) == 5
    a = fd.explain(h)
    assert a["tid"] == FRONTDOOR_TID_BASE + h.hid
    assert a["hop_gaps"] == []
    assert a["terminal"]["cause"] == "done"
    adm = a["admission"]
    assert adm is not None
    for key in ("predictor_cold", "completion_rate", "token_rate",
                "service_base_s", "priority", "work_ahead"):
        assert key in adm, "admission evidence missing {}".format(key)
    assert adm["priority"] == "interactive"
    names = [hp["name"] for hp in a["hops"]]
    assert "request/admitted" in names
    assert "request/dispatched" in names
    assert "stream/first_token" in names
    assert "stream/drained" in names
    sites = {(hp["name"], hp["site"]) for hp in a["hops"]}
    # both layers admit, and each says so at its own site: the front door
    # past its rate limit and predictor, the engine's scheduler into a slot
    assert ("request/admitted", "frontdoor") in sites
    assert ("request/admitted", "engine") in sites
    assert ("request/submitted", "engine") in sites
    # explain by hid works too; unknown hid raises.
    assert fd.explain(h.hid)["tid"] == a["tid"]


def test_frontdoor_shed_autopsy_keeps_predictor_evidence():
    """A shed request's autopsy must answer WHY: terminal cause shed
    with the structured reason, and the predictor evidence that backed
    the verdict — copied at decision time, not reconstructed."""
    from deepspeed_tpu.inference import QueueFull, TenantPolicy

    cfg, model, params = _shared_model()
    eng = engine_of(model, params)
    fd = FrontDoor(eng, FrontDoorConfig(
        classes=(
            PriorityClass("interactive", ttft_budget_ms=60_000.0),
            PriorityClass("batch"),
        ),
        tenants=(TenantPolicy("acme", rate=1.0),)))
    p = prompts_of(cfg, [5])[0]
    # burst == rate == 1: the first submit drains the bucket, the
    # second sheds deterministically with the tenant-rate reason.
    fd.submit(p, max_new_tokens=2, tenant="acme")
    shed_tid = None
    try:
        fd.submit(p, max_new_tokens=2, tenant="acme")
    except QueueFull:
        # The shed event is the LAST thing stamped on the frontdoor
        # ring before the raise.
        shed_events = [e for e in fd.tracer.events()
                       if e["name"] == "request/shed"]
        assert shed_events
        shed_tid = shed_events[-1]["tid"]
    assert shed_tid is not None, "second submit was not shed"
    a = build_autopsy(fd.trace_recorders(), shed_tid)
    assert a["terminal"]["cause"] == "shed"
    assert a["terminal"]["reason"]
    assert a["admission"] is not None
    assert "predictor_cold" in a["admission"]
    fd.close()


# ------------------------------------------------------- worst_requests


def test_worst_requests_ranks_pathology_first():
    def mk(tid, cause, rescued=0, gaps=(), t1=1.0):
        return {"tid": tid, "hops": [{"t_ms": 0.0}, {"t_ms": t1}],
                "admission": None, "routing": None,
                "terminal": {"cause": cause, "reason": None,
                             "lost_then_replayed": bool(rescued)},
                "replays": rescued, "failovers": 0, "preemptions": 0,
                "handoff_events": 0, "lifetime": None,
                "hop_gaps": list(gaps), "spans_dropped": {}}

    clean = mk(1, "done")
    slow = mk(2, "done", t1=50.0)
    rescued = mk(3, "done", rescued=1)
    shed = mk(4, "shed")
    stuck = mk(5, "in-flight")
    ranked = worst_requests([clean, slow, rescued, shed, stuck], k=3)
    assert [a["tid"] for a in ranked] == [5, 4, 3]
    assert worst_requests([clean], k=0) == []


def test_burn_rate_alert_fires_and_auto_dumps(tmp_path):
    """Acceptance: a burn-rate rule firing takes the same evidence path
    a replica death does — the AlertManager's on_fire hook auto-dumps
    the merged (Perfetto-valid) trace plus the worst-K autopsies to
    dump_dir, with the firing rule recorded alongside."""
    from deepspeed_tpu.telemetry import AlertRule

    cfg, model, params = _shared_model()
    rule = AlertRule("ttft_burn_tight", "burn_rate", "ttft_seconds", 2.0,
                     objective=0.95, budget_s=1e-6, short=1, long=1)
    fleet = ServingFleet(
        model, params, n_replicas=2, start=False, seed=0,
        window_seconds=0.05, alert_rules=[rule], dump_dir=str(tmp_path),
        config=dict(max_slots=3, max_len=64, chunk_size=4,
                    prefill_chunk=8, max_queue=32))
    try:
        for p in prompts_of(cfg, [5, 9, 7, 6]):
            fleet.submit(p, max_new_tokens=8)
        assert fleet.wait_idle(timeout_s=120.0)
        # Keep ticking until the window holding the (budget-blowing)
        # TTFT observations closes, scores, fires, and dumps.
        deadline = time.time() + 30.0
        while not fleet.dumps and time.time() < deadline:
            time.sleep(0.06)
            fleet.step()
        assert [r["rule"] for r in fleet.alerts.fired()] == [
            "ttft_burn_tight"]
        assert fleet.metrics()["fleet"]["alerts_fired"] == 1
        dump = next(d for d in fleet.dumps
                    if d["cause"] == "alert:ttft_burn_tight")
        with open(dump["trace"]) as f:
            validate_trace(json.load(f))
        with open(dump["autopsies"]) as f:
            doc = json.load(f)
        assert doc["cause"] == "alert:ttft_burn_tight"
        assert "ttft_burn_tight" in doc["firing"]
        evidence = doc["firing"]["ttft_burn_tight"]["evidence"]
        assert evidence["short_burn"] >= rule.threshold
        worst = doc["worst_requests"]
        assert worst and len(worst) == dump["requests"]
        # The window can close (and dump) MID-serve, so requests may be
        # done or still in flight — but every autopsy must be a
        # structurally complete, gap-free story either way.
        for a in worst:
            assert {"tid", "hops", "terminal", "hop_gaps"} <= set(a)
            assert a["terminal"]["cause"] in ("done", "in-flight")
            assert a["hop_gaps"] == []
    finally:
        fleet.close()
