"""Disaggregated prefill/decode serving (fleet roles + KV handoff).

The contract under test (docs/INFERENCE.md, disaggregation section):
1. ROLES — a role-typed fleet routes NEW prompts only to prefill (or
   mixed) replicas and handed-off KV planes only to decode (or mixed)
   replicas; an all-mixed fleet is byte-for-byte the historical one,
   down to the router's seeded tie-break sequence (ineligible views are
   skipped before scoring — no score, no rng draw).
2. HANDOFF INVARIANT — when a prompt's final chunk lands on a prefill
   replica, its finished KV plane migrates to a decode replica and the
   stream continues BIT-IDENTICALLY (greedy AND sampled) to a
   fault-free single-engine run: emissions depend only on (prompt,
   seed, absolute position), never on which replica decodes. Decode
   replicas never run a prefill lane (``prefills`` stays 0), yet every
   replica compiles the ONE mixed-step program exactly once.
3. LIFECYCLE EDGES — cancel and deadline expiry reach a request that
   is mid-handoff (slotless, bound for another scheduler); an admitted
   request whose deadline passes mid-migration still completes
   (deadline sheds are queue-side only); a rolling drain of the prefill
   replica settles its in-flight handoffs before reopening.
4. RESILIENCE — the decode target dying mid-handoff re-prefills the
   stream on a survivor through the orphan path: zero requests lost,
   still bit-identical, and surviving prefill replicas degrade to
   effective-mixed (capture off) so streams stop bouncing into a pump
   with no acceptors.
5. PERF ACCEPTANCE — at the same offered rate, the disaggregated fleet
   shows strictly lower decode ITL p99 than the all-mixed one (decode
   steps never share a dispatch with someone else's prefill chunk),
   with zero lost and one compile per replica; the loadgen report's v4
   ``disagg`` section attributes the migration traffic.
"""

import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceConfig, Router, ServingFleet
from deepspeed_tpu.loadgen import (
    SLO,
    SustainedRunner,
    WorkloadSpec,
    build_report,
)
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from tests.unit.test_chunked_prefill import engine_of, make_model, prompts_of

# One deterministic model init for the whole module (same sharing move
# as test_fleet.py — model.init dominates test wall time).
_MODEL = {}


def _shared_model():
    if "m" not in _MODEL:
        _MODEL["m"] = make_model()
    return _MODEL["m"]


def disagg_fleet(model, params, roles=("prefill", "decode", "decode"),
                 start=False, seed=0, **cfg):
    cfg.setdefault("max_slots", 3)
    cfg.setdefault("max_len", 64)
    cfg.setdefault("chunk_size", 4)
    cfg.setdefault("prefill_chunk", 8)
    cfg.setdefault("max_queue", 32)
    return ServingFleet(model, params, n_replicas=len(roles), config=cfg,
                        seed=seed, start=start, window_seconds=0.05,
                        roles=roles)


# The mixed stream (same shape as test_fleet.py's): greedy + sampled,
# spec + non-spec, ragged prompts — every stream must survive a handoff
# bit-identically.
_MIX_LENS = [5, 9, 6, 12, 7, 8]


def _mix_kw(i):
    kw = {"max_new_tokens": 5 + (i % 3)}
    if i % 2:
        kw["temperature"] = 0.7
        kw["seed"] = 100 + i
    if i % 3 == 0:
        kw["spec_decode"] = False
    return kw


def _reference_tokens(model, params, prompts, **cfg):
    eng = engine_of(model, params, **cfg)
    reqs = [eng.submit(p, **_mix_kw(i)) for i, p in enumerate(prompts)]
    eng.run()
    return [list(r.tokens) for r in reqs]


def _step_until(fleet, rep, pred, max_steps=400):
    """Step ONE replica until ``pred()`` (the single-threaded way to
    park a request mid-handoff: the donor captures, nobody pumps)."""
    for _ in range(max_steps):
        fleet._step_replica(rep)
        if pred():
            return
    pytest.fail("condition not reached in {} steps".format(max_steps))


# ------------------------------------------------------- roles plumbing


def test_roles_validation():
    cfg, model, params = _shared_model()
    with pytest.raises(ValueError):        # one role per replica
        ServingFleet(model, params, n_replicas=2, start=False,
                     roles=("prefill",))
    with pytest.raises(ValueError):        # prefill with nobody to feed
        ServingFleet(model, params, n_replicas=2, start=False,
                     roles=("prefill", "prefill"))
    with pytest.raises(ValueError):        # unknown role string
        InferenceConfig(role="draft")
    # Default stays all-mixed: no handoff plumbing engaged.
    fleet = disagg_fleet(model, params, roles=("mixed", "mixed"))
    assert fleet.roles == ("mixed", "mixed")
    assert not fleet._disagg
    assert all(not rep.engine._handoff_enabled for rep in fleet.replicas)
    fleet.close()


def test_router_eligible_skips_score_and_rng():
    def view(name, occ):
        return types.SimpleNamespace(name=name, queue_depth=0,
                                     slot_occupancy=occ, max_slots=4,
                                     health="healthy")

    views = [view("a", 0.5), view("b", 0.5), view("c", 0.25)]
    # Ineligible views are absent from the result.
    got = Router(seed=3).order(views, eligible=[True, False, True])
    assert [v.name for v in got] == ["c", "a"]
    # SKIPPED means no score computation at all: a view whose gauges
    # would blow up is harmless when masked out.
    booby = types.SimpleNamespace(name="boom")   # no gauges to read
    got = Router(seed=3).order([booby, view("a", 0.5)],
                               eligible=[False, True])
    assert [v.name for v in got] == ["a"]
    # And no rng draw: with every view eligible the seeded tie-break
    # sequence is bit-for-bit the mask-free one, while masking view 0
    # of an all-tied field yields exactly the ordering a fresh
    # same-seeded router gives the surviving views alone.
    tied = [view(str(i), 0.5) for i in range(6)]
    assert ([v.name for v in Router(seed=9).order(tied, eligible=[True] * 6)]
            == [v.name for v in Router(seed=9).order(tied)])
    masked = [v.name for v in Router(seed=9).order(
        tied, eligible=[False] + [True] * 5)]
    assert masked == [v.name for v in Router(seed=9).order(tied[1:])]


# ------------------------------------------- the handoff invariant


def test_disagg_streams_bit_identical_compile_once():
    """The tentpole end to end: new prompts route to the prefill
    replica, every finished plane migrates, decode replicas never
    prefill, and all streams (greedy AND sampled) match the
    single-engine oracle bit for bit with one compile per replica."""
    cfg, model, params = _shared_model()
    prompts = prompts_of(cfg, _MIX_LENS)
    reference = _reference_tokens(model, params, prompts)
    fleet = disagg_fleet(model, params)
    try:
        handles = [fleet.submit(p, **_mix_kw(i))
                   for i, p in enumerate(prompts)]
        # Role routing: every new prompt lands on the prefill replica.
        assert all(fr.replica_id == 0 for fr in handles)
        assert fleet.wait_idle(timeout_s=120.0)
        assert [list(fr.tokens) for fr in handles] == reference
        assert all(fr.phase == "done" for fr in handles)
        # Handoff conservation: every captured plane was adopted
        # exactly once across the decode pair (streams short enough to
        # finish the same step their final chunk lands never leave the
        # donor — capture is for requests that still owe tokens), and
        # BOTH decode replicas took work (least-loaded spread), without
        # ever running a prefill lane.
        donor, d1, d2 = (rep.engine for rep in fleet.replicas)
        assert 0 < donor.counters["handoffs"] <= len(prompts)
        assert (d1.counters["handoffs_in"] + d2.counters["handoffs_in"]
                == donor.counters["handoffs"])
        assert d1.counters["handoffs_in"] > 0
        assert d2.counters["handoffs_in"] > 0
        assert d1.counters["prefills"] == d2.counters["prefills"] == 0
        assert donor.counters["handoff_bytes_shipped"] > 0
        assert donor.counters["handoff_fallbacks"] == 0
        # One mixed-step program per replica, whatever the role.
        assert fleet.compile_counts == {0: 1, 1: 1, 2: 1}
        # The fleet metrics carry the new facts; the donor's registry
        # owns the migration clock.
        m = fleet.metrics()["fleet"]
        assert m["roles"] == {0: "prefill", 1: "decode", 2: "decode"}
        assert m["pending_handoffs"] == 0
        assert m["handoffs"] == m["handoffs_in"] == \
            donor.counters["handoffs"]
        assert "handoff_latency_seconds" in fleet.prometheus()
    finally:
        fleet.close()


def test_all_mixed_fleet_never_hands_off():
    cfg, model, params = _shared_model()
    prompts = prompts_of(cfg, _MIX_LENS[:4])
    reference = _reference_tokens(model, params, prompts[:4])
    fleet = disagg_fleet(model, params, roles=("mixed", "mixed"))
    try:
        handles = [fleet.submit(p, **_mix_kw(i))
                   for i, p in enumerate(prompts)]
        assert fleet.wait_idle(timeout_s=120.0)
        assert [list(fr.tokens) for fr in handles] == reference
        m = fleet.metrics()["fleet"]
        assert m["handoffs"] == m["handoffs_in"] == 0
        assert m["roles"] == {0: "mixed", 1: "mixed"}
    finally:
        fleet.close()


# --------------------------------------------------- lifecycle edges


def test_cancel_reaches_request_mid_handoff():
    cfg, model, params = _shared_model()
    fleet = disagg_fleet(model, params, roles=("prefill", "decode"))
    try:
        fr = fleet.submit(prompts_of(cfg, [9])[0], max_new_tokens=8)
        _step_until(fleet, fleet.replicas[0],
                    lambda: fleet._handoffs.pending)
        assert fr._req.phase == "handoff"
        assert fleet.cancel(fr) is True
        assert fr.phase == "cancelled"
        # The pump finds the cancelled stream and settles it on the
        # donor: no scheduler record, no pending migration, fleet idle.
        assert fleet.wait_idle(timeout_s=30.0)
        assert not fleet.replicas[0].engine._scheduler.handoff
        assert fleet.metrics()["fleet"]["pending_handoffs"] == 0
        assert fleet.replicas[1].engine.counters["handoffs_in"] == 0
    finally:
        fleet.close()


def test_deadline_expiry_mid_handoff_still_completes():
    """Deadline sheds are QUEUE-side only: a request whose deadline
    passes while its KV plane is mid-migration was already admitted —
    it finishes its full budget on the acceptor, not shed."""
    cfg, model, params = _shared_model()
    fleet = disagg_fleet(model, params, roles=("prefill", "decode"))
    try:
        fr = fleet.submit(prompts_of(cfg, [9])[0], max_new_tokens=8,
                          deadline_ms=200)
        _step_until(fleet, fleet.replicas[0],
                    lambda: fleet._handoffs.pending)
        time.sleep(0.3)                       # deadline passes in flight
        assert fleet.wait_idle(timeout_s=30.0)
        assert fr.phase == "done"
        assert len(fr.tokens) == 8
        assert all(rep.engine.counters["deadline_sheds"] == 0
                   for rep in fleet.replicas)
    finally:
        fleet.close()


def test_rolling_drain_prefill_with_inflight_handoffs():
    cfg, model, params = _shared_model()
    prompts = prompts_of(cfg, _MIX_LENS)
    reference = _reference_tokens(model, params, prompts)
    fleet = disagg_fleet(model, params)
    try:
        handles = [fleet.submit(p, **_mix_kw(i))
                   for i, p in enumerate(prompts)]
        _step_until(fleet, fleet.replicas[0],
                    lambda: fleet._handoffs.pending)
        # Drain with migrations parked in the pump: the donor is not
        # idle until they settle, so the rotation waits for them.
        report = fleet.rolling_drain(timeout_s=60.0)
        assert [r["drained"] for r in report] == [True, True, True]
        assert fleet.wait_idle(timeout_s=120.0)
        assert [list(fr.tokens) for fr in handles] == reference
        assert all(fr.phase == "done" for fr in handles)
        assert fleet.health == "healthy"
        # Admissions reopened: the next prompt routes and completes.
        fr = fleet.submit(prompts_of(cfg, [6])[0], max_new_tokens=3)
        assert fr.replica_id == 0
        assert fleet.wait_idle(timeout_s=60.0)
        assert fr.phase == "done" and len(fr.tokens) == 3
    finally:
        fleet.close()


# ----------------------------------------------------------- resilience


def test_decode_target_death_mid_handoff_reprefills_bit_identical():
    """The fallback half of the handoff invariant: the only decode
    replica dies with migrations in flight -> the streams re-prefill on
    the surviving (now effective-mixed) prefill replica through the
    orphan path. Zero lost, greedy AND sampled still bit-identical."""
    cfg, model, params = _shared_model()
    prompts = prompts_of(cfg, _MIX_LENS[:2])   # greedy + sampled
    reference = _reference_tokens(model, params, prompts[:2])
    fleet = disagg_fleet(model, params, roles=("prefill", "decode"))
    try:
        handles = [fleet.submit(p, **_mix_kw(i))
                   for i, p in enumerate(prompts)]
        _step_until(fleet, fleet.replicas[0],
                    lambda: fleet._handoffs.pending)
        fleet.replicas[1].failed = True        # acceptor dies mid-flight
        assert fleet.wait_idle(timeout_s=120.0)
        donor = fleet.replicas[0].engine
        assert donor.counters["handoff_fallbacks"] >= 1
        # Capture is OFF on the survivor: a re-prefilled stream must
        # complete there instead of bouncing back into an acceptor-less
        # pump.
        assert donor._handoff_enabled is False
        assert [list(fr.tokens) for fr in handles] == reference
        assert all(fr.phase == "done" for fr in handles)
        assert fleet.replicas[1].engine.counters["handoffs_in"] == 0
        assert fleet.metrics()["fleet"]["pending_handoffs"] == 0
    finally:
        fleet.close()


# ------------------------------------------------- the ITL acceptance


_AB_MODEL = {}


def _ab_model():
    """A 3-layer/128-wide model for the A/B: big enough that per-step
    compute dominates thread-scheduling noise on a 1-core CI box (the
    tiny 2x64 model's margins drown in jitter)."""
    if "m" not in _AB_MODEL:
        import jax

        cfg = GPT2Config(vocab_size=1024, n_positions=256, n_embd=128,
                         n_layer=3, n_head=4, dropout=0.0,
                         dtype=jnp.float32, use_flash_attention=False)
        model = GPT2LMHeadModel(cfg)
        rng = np.random.RandomState(0)
        params = model.init(
            jax.random.PRNGKey(0),
            jnp.asarray(rng.randint(0, cfg.vocab_size,
                                    size=(2, 16))))["params"]
        _AB_MODEL["m"] = (cfg, model, params)
    return _AB_MODEL["m"]


def _ab_run(roles, **stream):
    """One warmed run of a 24-request stream (one burst unless
    ``stream`` says otherwise); returns (result, report, steps,
    handoffs_in) where ``steps[replica]`` lists ``(prefill_tokens,
    active_slots)`` of every ``inference/mixed_step`` span that replica
    recorded, warmup included, and ``handoffs_in`` counts the planes the
    decode side adopted during the run. Long prompts against a small prefill
    chunk give every prompt eight prefill steps; all 24 arriving at once
    puts several prompts on every replica that takes prompts, so prefill
    chunks and decoding slots must share steps wherever one replica
    serves both phases (the interference under test)."""
    cfg, model, params = _ab_model()
    serve_cfg = {"max_slots": 4, "max_len": 128, "chunk_size": 2,
                 "prefill_chunk": 8, "max_queue": 128,
                 "trace_ring": 1 << 16}
    spec_kw = dict(arrival="burst", burst_size=24, n_requests=24,
                   prompt_dist="fixed", prompt_mean=64, prompt_max=64,
                   output_dist="fixed", output_mean=32, output_max=32,
                   vocab_size=cfg.vocab_size, seed=23)
    spec_kw.update(stream)
    spec = WorkloadSpec(**spec_kw)
    fleet = ServingFleet(model, params, n_replicas=3, config=serve_cfg,
                         window_seconds=0.1, seed=0, roles=roles,
                         idle_wait_s=0.002)
    try:
        wrng = np.random.RandomState(7)
        for i in range(6):       # warmup: compile every replica first
            fleet.submit(wrng.randint(0, cfg.vocab_size,
                                      size=64).astype(np.int32),
                         max_new_tokens=8, temperature=0.0, seed=900 + i)
        assert fleet.wait_idle(timeout_s=300.0)
        assert all(c == 1 for c in fleet.compile_counts.values())
        fleet.metrics(reset=True)
        adopted = int(fleet.counters["handoffs_in"])
        runner = SustainedRunner(fleet, spec, window_seconds=0.1,
                                 max_steps=500_000)
        result = runner.run()
        adopted = int(fleet.counters["handoffs_in"]) - adopted
        assert fleet.health == "healthy"
        report = build_report(spec, result,
                              SLO(ttft_p99_ms=30000.0, itl_p99_ms=10000.0))
        assert result.requests_lost == 0 and result.shed == 0
        # The measured stream must not have recompiled anything.
        assert all(c == 1 for c in fleet.compile_counts.values())
        steps = {}
        for rep in fleet.replicas:
            tracer = rep.engine.tracer
            assert tracer.dropped == 0   # the ring held every step
            steps[rep.rid] = [
                (e["args"]["prefill_tokens"], e["args"]["active_slots"])
                for e in tracer.events()
                if e["name"] == "inference/mixed_step"]
        return result, report, steps, adopted
    finally:
        fleet.close()


def test_disagg_decode_steps_never_carry_a_prefill_chunk():
    """The acceptance A/B as counts: 1 prefill + 2 decode vs the same
    three replicas all-mixed, same offered burst. Disaggregated, NO step
    of a decode replica carries a prefill chunk and no step of the
    prefill replica carries a decoding slot; all-mixed, every replica
    runs steps that carry both — the interference disaggregation
    removes. (What that does to decode ITL p99 is a latency and belongs
    to a chip cell: PERF.md section 7.)"""
    on_res, on_rep, on_steps, _ = _ab_run(("prefill", "decode", "decode"))
    off_res, off_rep, off_steps, _ = _ab_run(None)
    assert all(on_steps[r] for r in (0, 1, 2))
    assert all(active == 0 for _, active in on_steps[0])
    # Every prompt token, the six warmup prompts' included, went through
    # the one prefill replica — and through some replica when all-mixed.
    assert sum(n for n, _ in on_steps[0]) == (24 + 6) * 64
    for r in (1, 2):
        assert all(n == 0 for n, _ in on_steps[r]), \
            "decode replica {} ran a prefill chunk".format(r)
        assert any(active > 0 for _, active in on_steps[r])
    for r in (0, 1, 2):
        assert any(n > 0 and active > 0 for n, active in off_steps[r]), \
            "mixed replica {} never shared a step between phases".format(r)
    assert sum(n for st in off_steps.values()
               for n, _ in st) == (24 + 6) * 64
    # Attribution: every stream migrated exactly once on the disagg
    # side, never on the mixed side — and the loadgen report's v4
    # ``disagg`` section carries the same counters.
    assert on_res.handoffs == 24 and on_res.handoff_fallbacks == 0
    assert on_res.handoff_bytes_shipped > 0
    assert off_res.handoffs == 0
    assert on_rep["schema_version"] == 7
    assert on_rep["disagg"] == {
        "handoffs": 24, "handoff_fallbacks": 0,
        "handoff_bytes_shipped": on_res.handoff_bytes_shipped}
    assert off_rep["disagg"]["handoffs"] == 0


# ------------------------------------------- an open-loop stream, by counts


def test_disagg_poisson_stream_hands_every_request_off_once():
    """Where the A/B above offers one burst, arrivals here are spread
    over the run (Poisson at 60 a second, 32-token prompts, 24-token
    answers), so hand-offs leave the prefill replica while earlier
    planes are still being adopted and decoded. Every one of the 24
    requests leaves once and is adopted once (out == in), none falls
    back to a re-prefill, none is lost, and each replica is still on
    its one program (``_ab_run`` holds that, and the fleet's health at
    exit)."""
    res, rep, steps, adopted = _ab_run(
        ("prefill", "decode", "decode"), arrival="poisson", rate=60.0,
        prompt_mean=32, prompt_max=48, output_mean=24, output_max=24)
    assert res.submitted == res.completed == 24
    assert res.handoffs == adopted == 24
    assert res.handoff_fallbacks == 0 and res.handoff_bytes_shipped > 0
    assert rep["disagg"]["handoffs"] == 24
    assert all(n == 0 for r in (1, 2) for n, _ in steps[r])
