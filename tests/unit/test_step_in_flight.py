"""The serving engine keeps one step in flight (``InferenceEngine._step_once``).

Counts and orders only, on the CPU; no times. The contract, in order:
1. SAME TOKENS — with step N+1 scheduled and dispatched before step N is
   harvested, every stream is bit-identical to sequential ``generate``
   (greedy) and to the same engine harvesting each step at once (sampled).
2. THE ORDER — from the tracer's ring, ``inference/mixed_step`` of N+1
   begins before ``inference/harvest`` of N ends; the counter
   ``steps_dispatched_ahead`` is steps - 1 in a steady run.
3. WHO HOLDS STATE ACROSS THE BOUNDARY — cancel, an injected raise and a
   corrupted harvest with a step in flight drop that step's tokens and
   replay bit-identically; a feature built at depth 0 never has one.
4. ``run`` / ``drain`` / ``close`` / ``idle`` leave nothing in flight.
5. PAGES — every position a step writes goes through a mapped table entry
   though the host's harvested positions lag a step, and no page is in two
   rows of one dispatched table.
"""

import numpy as np
import pytest

from deepspeed_tpu.inference import Fault, FaultPlan
from deepspeed_tpu.inference.paging import TRASH_PAGE
from tests.unit.test_inference import (
    engine_of,
    make_model,
    prompts_of,
    seq_greedy,
)


def harvest_at_once(eng):
    """The same engine with no step kept in flight: the test's own steer
    (depth follows from what an engine was BUILT with; no option sets it)."""
    assert eng._flight is None
    eng._depth = 0
    return eng


PAGED = dict(paged_kv=True, kv_page_len=4)


# ------------------------------------------------------------ same tokens


@pytest.mark.parametrize("pool", ["dense", "paged"])
def test_greedy_streams_match_generate_with_a_step_in_flight(pool):
    """More requests than slots, prompts of one and of several chunks, so
    slots are released at dispatch and re-admitted a step early."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=3, prefill_chunk=8,
                    **(PAGED if pool == "paged" else {}))
    assert eng._depth == 1
    ps = prompts_of(cfg, [5, 19, 8, 27, 6, 11, 9])
    budgets = [9, 4, 13, 6, 5, 17, 8]
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, budgets)]
    eng.run()
    for r, p, n in zip(reqs, ps, budgets):
        assert r.tokens == seq_greedy(model, params, p, n)
    assert eng.compile_count == 1
    assert eng.counters["steps_dispatched_ahead"] > 0


@pytest.mark.parametrize("pool", ["dense", "paged"])
def test_sampled_streams_match_the_engine_that_harvests_at_once(pool):
    cfg, model, params = make_model()
    kw = dict(max_slots=2, prefill_chunk=8,
              **(PAGED if pool == "paged" else {}))
    ps = prompts_of(cfg, [7, 21, 5, 12], seed=9)

    def serve(eng):
        reqs = [eng.submit(p, max_new_tokens=11, temperature=0.8, top_k=20,
                           seed=40 + i) for i, p in enumerate(ps)]
        eng.run()
        return [r.tokens for r in reqs]

    ahead = engine_of(model, params, **kw)
    got = serve(ahead)
    assert got == serve(harvest_at_once(engine_of(model, params, **kw)))
    assert ahead.counters["steps_dispatched_ahead"] > 0
    assert len({tuple(t) for t in got}) == len(got)      # really sampled


def test_an_end_by_eos_is_learnt_a_step_late_and_emits_nothing_more():
    """The host cannot know an EOS before the harvest: the step after is
    already dispatched with the slot in its rows. On the chip the slot is
    inactive there; the stream ends AT the EOS token."""
    cfg, model, params = make_model()
    prompt = prompts_of(cfg, [9], seed=5)[0]
    full = seq_greedy(model, params, prompt, 24)
    cut = 9                                   # inside the third step
    eos = full[cut]
    want = full[:full.index(eos) + 1]
    eng = engine_of(model, params, max_slots=2)
    req = eng.submit(prompt, max_new_tokens=24, eos_token_id=eos)
    other = eng.submit(prompts_of(cfg, [6], seed=6)[0], max_new_tokens=30)
    while not req.done:
        eng.step()
    assert req.tokens == want
    # The step dispatched before the EOS was seen still names the slot.
    assert eng._flight is not None and req in eng._flight.rows.values()
    eng.run()
    assert req.tokens == want and req.phase == "done"
    assert other.tokens == seq_greedy(model, params, other.prompt, 30)


@pytest.mark.parametrize("pool", ["dense", "paged"])
def test_max_new_tokens_one_ends_in_the_step_that_prefilled_it(pool):
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=2, prefill_chunk=8,
                    **(PAGED if pool == "paged" else {}))
    ps = prompts_of(cfg, [6, 13, 4, 9])
    reqs = [eng.submit(p, max_new_tokens=1) for p in ps]
    eng.run()
    for r, p in zip(reqs, ps):
        assert r.tokens == seq_greedy(model, params, p, 1)
        assert r.phase == "done" and r.first_token_time is not None


# -------------------------------------------------------------- the order


def _spans(eng, name):
    return {e["args"]["step"]: e for e in eng.tracer.events()
            if e["name"] == name and e.get("ph") == "X"}


def test_next_dispatch_begins_before_the_harvest_ends_and_is_counted():
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=2, prefill_chunk=8)
    eng.submit(prompts_of(cfg, [5])[0], max_new_tokens=41)
    calls = 0
    while not eng.idle:
        eng.step()
        calls += 1
    steps = eng.counters["chunks"]
    assert steps == 10 == eng._steps          # 1 + 4, then 4 a step
    assert eng.counters["steps_dispatched_ahead"] == steps - 1
    # The first call dispatches two steps, the last one only harvests: a
    # call a device step, as before.
    assert calls == steps
    m = eng.metrics()
    assert m["steps_dispatched_ahead"] == steps - 1
    assert m["steps_ahead_share"] == pytest.approx((steps - 1) / steps)
    assert 'steps_ahead_share{engine="inference"}' in eng.prometheus()
    assert "steps_dispatched_ahead" in eng.prometheus()

    sched, mixed = _spans(eng, "inference/schedule"), \
        _spans(eng, "inference/mixed_step")
    harvest, deliver = _spans(eng, "inference/harvest"), \
        _spans(eng, "inference/deliver")
    assert sorted(mixed) == sorted(harvest) == sorted(deliver) \
        == list(range(1, steps + 1)) == sorted(sched)
    for n in range(1, steps):
        assert mixed[n + 1]["ts"] < harvest[n]["ts"] + harvest[n]["dur"]
        assert sched[n + 1]["ts"] <= mixed[n + 1]["ts"] <= harvest[n]["ts"]
        assert harvest[n]["ts"] <= deliver[n]["ts"]
    # TTFT stamps at harvest, never at dispatch.
    first = [e for e in eng.tracer.events()
             if e["name"] == "request/first_token"]
    assert len(first) == 1 and first[0]["ts"] >= harvest[1]["ts"]


def test_engines_built_with_a_feature_that_reads_the_result_hold_depth_0():
    """Depth follows from what the engine was built with; such an engine
    has nothing in flight between two step() calls."""
    cfg, model, params = make_model()
    p = prompts_of(cfg, [6, 9])
    for extra in (dict(spec_decode=True, spec_k=2, spec_ngram=2),
                  dict(prefix_cache=True, prefix_len=8, prefix_slots=2),
                  dict(host_offload=True),
                  dict(role="prefill")):
        eng = engine_of(model, params, max_slots=2, **extra)
        assert eng._depth == 0, extra
        eng.submit(p[0], max_new_tokens=12)
        eng.submit(p[1], max_new_tokens=12)
        for _ in range(3):
            eng.step()
            assert eng._flight is None
        assert eng.counters["steps_dispatched_ahead"] == 0
    for extra in (dict(), dict(int8_kv=True), dict(PAGED, prefill_chunk=8),
                  dict(fault_injection=True), dict(role="decode")):
        assert engine_of(model, params, **extra)._depth == 1, extra


# ------------------------------------- who holds state across the boundary


def test_cancel_with_a_step_in_flight_drops_its_late_tokens():
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=1, prefill_chunk=4, **PAGED)
    long_p, short_p = prompts_of(cfg, [6, 7])
    a = eng.submit(long_p, max_new_tokens=30)
    b = eng.submit(short_p, max_new_tokens=9)
    while len(a.tokens) < 9:
        eng.step()
    got = list(a.tokens)
    flight = eng._flight
    assert flight is not None and flight.rows[0] is a
    assert eng.cancel(a) is True and a.slot is None
    eng.step()          # harvests the step that decoded ``a``: dropped
    assert a.tokens == got and a.phase == "cancelled"
    eng.run()
    assert a.tokens == got == seq_greedy(model, params, long_p, 30)[:9]
    assert b.tokens == seq_greedy(model, params, short_p, 9)
    assert eng.idle and eng._pager.pages_in_use() == 0


def test_cancel_of_a_request_whose_slot_was_released_at_dispatch():
    """Its budget ran out inside the step in flight, so it holds no slot
    and no page; the cancel settles it and its last tokens are dropped."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=1, prefill_chunk=8, **PAGED)
    a = eng.submit(prompts_of(cfg, [5])[0], max_new_tokens=9)
    b = eng.submit(prompts_of(cfg, [6], seed=8)[0], max_new_tokens=5)
    eng.step()          # steps 1 and 2 dispatched: 5 + 4 = the budget
    assert a.slot is None and a.phase == "decoding" and len(a.tokens) == 5
    assert list(eng._scheduler.landing) == [a.rid] and not eng.idle
    assert eng.cancel(a) is True and eng.cancel(a) is False
    eng.run()
    assert len(a.tokens) == 5 and a.phase == "cancelled"
    assert b.tokens == seq_greedy(model, params, b.prompt, 5)
    assert eng.idle and not eng._scheduler.landing


def _serve_mixed(model, params, prompts, plan=None, **kw):
    eng = engine_of(model, params, max_slots=3, prefill_chunk=4,
                    fault_injection=True, **kw)
    reqs = [eng.submit(prompts[0], max_new_tokens=14),
            eng.submit(prompts[1], max_new_tokens=9, temperature=0.8,
                       seed=11),
            eng.submit(prompts[2], max_new_tokens=12),
            eng.submit(prompts[3], max_new_tokens=7, temperature=0.5,
                       top_k=12, seed=7)]
    dropped = None
    if plan is not None:
        while not any(r.phase == "decoding" and r.tokens for r in reqs):
            eng.step()
        assert eng._flight is not None      # the fault finds one in flight
        eng.inject_faults(plan)
        before = [list(r.tokens) for r in reqs]
        eng.step()                          # the fault fires in this call
        dropped = before == [r.tokens for r in reqs]
    eng.run()
    return eng, reqs, dropped


@pytest.mark.parametrize("pool", ["dense", "paged"])
@pytest.mark.parametrize("kind", ["raise", "nan"])
def test_fault_with_a_step_in_flight_loses_nothing_and_replays_identically(
        kind, pool):
    """An injected raise (in place of dispatching N+1) and a corrupted
    harvest (of N, with N+1 dispatched) both discard the step in flight
    too: no token of either reaches a handle, the host's records replay
    greedy and sampled streams bit-identically, nothing recompiles."""
    cfg, model, params = make_model()
    prompts = prompts_of(cfg, [12, 7, 20, 5])
    kw = PAGED if pool == "paged" else {}
    ref_eng, ref, _ = _serve_mixed(model, params, prompts, **kw)
    eng, got, dropped = _serve_mixed(
        model, params, prompts,
        plan=FaultPlan(faults=(Fault(kind, step=0),)), **kw)
    assert dropped, "a token of a discarded step reached a handle"
    assert all(r.phase == "done" for r in got)
    assert [r.tokens for r in got] == [r.tokens for r in ref]
    assert len(eng.recovery_log) == 1 and eng.recovery_log[0]["replayed"] >= 1
    assert ("InjectedFault" if kind == "raise" else "NumericsError") \
        in eng.recovery_log[0]["error"]
    assert eng.compile_count == ref_eng.compile_count == 1
    assert eng.health == "healthy" and eng.idle and eng._flight is None
    assert not eng._scheduler.landing


def test_preempt_parks_a_session_on_an_engine_that_keeps_nothing_in_flight():
    """Preemption needs the offload tier, which is built at depth 0: the
    capture reads the pool a harvest just settled."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=1, host_offload=True)
    a = eng.submit(prompts_of(cfg, [6])[0], max_new_tokens=20)
    while len(a.tokens) < 5:
        eng.step()
    assert eng._flight is None
    got = list(a.tokens)
    assert eng.preempt(a) is True and a.phase == "swapped"
    b = eng.submit(prompts_of(cfg, [5], seed=4)[0], max_new_tokens=6)
    while not b.done:
        eng.step()
    assert a.tokens == got                    # parked: nothing reached it
    eng.release_preempted(a)
    eng.run()
    assert a.tokens == seq_greedy(model, params, a.prompt, 20)
    assert b.tokens == seq_greedy(model, params, b.prompt, 6)


# ------------------------------------------------- nothing left in flight


def test_run_drain_close_and_idle_leave_nothing_in_flight():
    cfg, model, params = make_model()
    prompt = prompts_of(cfg, [9], seed=5)[0]
    full = seq_greedy(model, params, prompt, 24)
    eos = full[9]
    want = full[:full.index(eos) + 1]

    eng = engine_of(model, params)
    req = eng.submit(prompt, max_new_tokens=24, eos_token_id=eos)
    while not req.done:
        eng.step()
    # Every request ended, by an EOS the host learnt a step late: the
    # scheduler is empty and a step is still on the chip.
    assert eng._scheduler.idle and eng._flight is not None and not eng.idle
    assert eng.step() == [] and eng.idle and eng._flight is None
    assert req.tokens == want

    eng = engine_of(model, params)
    req = eng.submit(prompt, max_new_tokens=24, eos_token_id=eos)
    assert eng.run() == [req] and eng.idle and eng._flight is None

    eng = engine_of(model, params)
    req = eng.submit(prompt, max_new_tokens=24, eos_token_id=eos)
    assert eng.drain() == [req] and eng.idle and eng._flight is None
    assert eng.health == "draining"

    # close() mid-run harvests the step in flight (its tokens reach the
    # handle) and dispatches nothing.
    eng = engine_of(model, params)
    req = eng.submit(prompt, max_new_tokens=24)
    eng.step()
    n, steps = len(req.tokens), eng._steps
    assert eng._flight is not None
    eng.close()
    assert eng._flight is None and eng._steps == steps
    assert len(req.tokens) == n + 4 and req.tokens == full[:n + 4]
    eng.close()                               # idempotent


# ------------------------------------------------------------------ pages


def test_every_written_position_is_mapped_and_no_page_is_in_two_rows():
    """The table of each dispatched step, read back as it was uploaded,
    against where that step's rows stood when it ended: every page a row
    wrote through is mapped (the host mapped it from ``sent``, not from the
    tokens it had harvested, which lag a step), and no page is in two rows
    (a page freed at dispatch is granted again only in a LATER table)."""
    cfg, model, params = make_model()
    eng = engine_of(model, params, max_slots=3, max_len=64, kv_page_len=4,
                    paged_kv=True, prefill_chunk=8, kv_pages=24)
    page_len = 4
    tables, lag = {}, []
    mixed = eng._mixed

    def recording(*args):
        tables[eng._steps] = np.asarray(args[4]["block_tbl"]).copy()
        # How far the tokens the host has harvested are behind where the
        # rows of this step really start.
        lag.append(max([r.sent - len(r.tokens)
                        for r in eng._scheduler.running.values()
                        if r.phase == "decoding"], default=0))
        return mixed(*args)

    eng._mixed = recording
    ps = prompts_of(cfg, [5, 19, 8, 11, 6, 14, 9, 7], seed=13)
    budgets = [9, 6, 13, 5, 17, 8, 10, 12]
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, budgets)]
    while not eng.idle:
        flight = eng._flight
        eng.step()
        if flight is None:
            continue
        # ``flight`` was harvested by this call: its snapshot is the last.
        table, snap = tables[flight.step], eng._last_snap
        for slot in flight.rows:
            # The frontier when the step ended: every position under it
            # holds a key this row wrote or read in the step.
            last = int(snap["pos"][slot]) - 1
            for page in range(last // page_len + 1):
                assert table[slot, page] != TRASH_PAGE, (flight.step, slot)
    # (a whole step behind: a first token and a chunk, or a chunk)
    assert max(lag) == 5 and len(tables) == eng._steps
    for step, table in tables.items():
        used = table[table != TRASH_PAGE]
        assert len(used) == len(set(used.tolist())), step
    for r, p, n in zip(reqs, ps, budgets):
        assert r.tokens == seq_greedy(model, params, p, n)
    assert eng._pager.pages_in_use() == 0 and eng.compile_count == 1
