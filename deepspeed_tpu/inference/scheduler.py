"""Continuous-batching scheduler — host-side request lifecycle.

The device side (kv_pool / engine programs) is shape-static; ALL dynamic
serving behavior lives here: a bounded FIFO queue, admission of queued
requests into free slots at step boundaries, a PREFILLING phase that
walks a cursor through the prompt ``prefill_chunk`` tokens at a time
(Sarathi-style chunked prefill — Agrawal et al., OSDI'24), eviction of
finished slots, and completion bookkeeping. Orca-style iteration-level
scheduling (Yu et al., OSDI'22) degenerates to exactly this once the
batch is a fixed slot set: the only decisions left are "which queued
request takes which free slot" (FIFO), "whose prompt chunk rides the
next step" (FIFO among prefilling slots), and "when" (every step).

Request phases: ``queued -> prefilling -> decoding -> done`` (or
``cancelled`` from any live phase, or ``expired`` from ``queued`` when
a request's deadline passes before admission).

Disaggregated serving (inference/fleet.py) adds one more live phase:
``handoff`` — the request finished prefill on a prefill-role replica,
left its slot (the slot's device state was captured to a host record),
and is mid-migration to a decode replica. It is slotless here exactly
like ``swapped``, but its destination is another scheduler entirely:
``finish_handoff`` forgets it once the acceptor's record (or a
re-prefill fallback) owns the stream. Deadlines never shed a handoff —
expiry is QUEUE-side only, and a handoff was admitted long ago
("admitted work always finishes"); cancel() reaches it like any live
phase.

The engine keeps one step in flight (``InferenceEngine._step_once``), so
the records here run AHEAD of the tokens harvested by what the host can
know by arithmetic: ``cursor`` and ``sent`` move when a step is dispatched,
and a request whose budget ends inside the step just dispatched (for a model
that generates by diffusion over blocks: whose last block's commit pass is in
it, which at a fixed number of passes a block is arithmetic too) gives up
its slot at once (``release``): still ``decoding``, slotless, it waits in
``landing`` for the harvest that completes it.

Recovery (docs/RESILIENCE.md) adds one extra move: after a fatal step
error the engine calls ``requeue_running()`` — every in-flight request
returns to the FRONT of the queue in rid (= admission) order, to be
re-admitted and replayed against a rebuilt KV pool. The request records
here are the durable truth that makes the device state disposable.

Timestamps are stamped here (submit / admit / first token / finish) so
the serving benchmark and the engine's metrics read one source of truth.
The optional ``tracer`` (telemetry.SpanRecorder) turns those same
timestamps into per-request Chrome trace spans — each request rides its
own track (tid=rid): a ``request/queued`` span (submit -> admit), a
``request/prefill`` span (admit -> first token sampled), a
``request/decode`` span (first token -> finish) and a whole-lifetime
``request`` span, with ``request/cancelled`` instants for evictions.
Those spans are retroactive, so they reach the ring (the Chrome export)
only; each TRANSITION is also an instant (``request/submitted``,
``request/admitted``, ``request/first_token`` from the engine's harvest,
``request/finished``, and the preempt / swap / handoff / expiry ones) that
the one span call writes to the ring and, with the request's ``rid``, onto
the profiler's clock: in a device trace a request is its instants.
"""

import collections
import itertools
import time

from deepspeed_tpu.telemetry.distributed import TraceContext

# retry_after_s ceiling: on a cold completions window (two completions
# minutes apart) the naive 1/rate estimate is astronomical, and router
# backoff math multiplying it would park a replica forever. One minute
# is long past any sane re-probe interval.
RETRY_AFTER_CAP_S = 60.0


class QueueFull(RuntimeError):
    """Raised by submit() when the pending queue is at max_queue — the
    backpressure signal for upstream callers. STRUCTURED: carries the
    queue depth at rejection, a ``retry_after_s`` hint derived from
    the recent completions rate (seconds until one queue position
    plausibly frees; None before enough completions exist to estimate;
    always clamped to [0, RETRY_AFTER_CAP_S] so backoff math cannot go
    negative or absurd on a cold completions window), and the
    ``replica_id`` of the rejecting engine (None outside a fleet) so a
    router can attribute the shed to one breaker.

    ``swap_eligible`` distinguishes "truly full" from "full but the KV
    hierarchy can free a slot by swapping an idle session to host RAM"
    (engine._augment_queue_full sets it and arms the swap): the caller
    should retry after ``retry_after_s`` instead of failing over —
    capacity is about to appear on THIS replica.

    ``priority``/``tenant`` stamp the rejected submission's class and
    tenant (None for untagged traffic) so upstream backoff is
    CLASS-AWARE: the hint for a priority-tagged shed comes from that
    class's own completions rate, not the global one. ``reason``
    classifies the shed (``queue_full`` here; the front door adds
    ``slo``/``deadline``/``rate_limit``/``tenant_queue``) so shed
    accounting can be split by cause, not just counted."""

    def __init__(self, message, queue_depth=None, retry_after_s=None,
                 replica_id=None, swap_eligible=False, priority=None,
                 tenant=None, reason=None):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.retry_after_s = retry_after_s
        self.replica_id = replica_id
        self.swap_eligible = swap_eligible
        self.priority = priority
        self.tenant = tenant
        self.reason = reason


def _ms(earlier, later):
    return round((later - earlier) * 1e3, 3)


class Request(object):
    """One generation request and its accumulated output."""

    __slots__ = ("rid", "prompt", "max_new_tokens", "temperature", "top_k",
                 "eos_token_id", "seed", "spec", "tokens", "slot", "phase",
                 "cursor", "submit_time", "admit_time", "lane_time",
                 "last_slice_time", "slices", "first_token_time",
                 "finish_time", "deadline", "replays", "last_touch",
                 "priority", "tenant", "trace", "sent", "denoising_steps",
                 "passes", "open_lanes", "block_passes", "replayed")

    def __init__(self, rid, prompt, max_new_tokens, temperature, top_k,
                 eos_token_id, seed, spec=False, deadline=None,
                 priority=None, tenant=None, trace=None,
                 denoising_steps=None):
        self.rid = rid
        # Propagated trace identity (telemetry/distributed.py): the
        # Chrome tid every lifecycle event rides plus the shared hop
        # counter. Created upstream (FrontDoor / fleet) and carried by
        # reference across handoffs and failovers; a bare engine mints
        # a local one so tid == rid exactly as before.
        self.trace = trace if trace is not None \
            else TraceContext(rid, origin="local")
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.eos_token_id = eos_token_id
        self.seed = seed
        # Speculative decoding for THIS request (engine-wide switch AND
        # per-request opt-in resolved at submit). Rides to the device as
        # the slot's traced ``spec`` flag; a decode step may then emit
        # 1..spec_k+1 tokens for the slot — ``tokens`` grows by the
        # ACCEPTED count per step and the device-side ``remaining`` clamp
        # keeps len(tokens) <= max_new_tokens exactly as in 1-token mode.
        self.spec = spec
        self.tokens = []
        self.slot = None
        self.phase = "queued"
        # Prompt tokens DISPATCHED so far (chunked prefill walks this to
        # len(prompt)); the engine moves it when it dispatches a slice, by
        # a count it chose itself, not when the slice's step is harvested.
        self.cursor = 0
        # Tokens of ``max_new_tokens`` that the steps dispatched for this
        # admission will have emitted once they are all harvested, an end
        # by EOS aside: ``len(tokens)`` lags it by the step in flight.
        # Arithmetic on the budget, so it is kept only where a step's
        # emission count is the host's to know (no speculation).
        self.sent = 0
        # Generation by diffusion over blocks (None, and the three below
        # unused, for a model that makes its tokens one a pass). A pass over
        # a slot yields no token, or several, of a block's positions in no
        # order: ``denoising_steps`` is the request's passes a block before
        # its commit; ``passes[i]`` the pass of its block in which
        # ``tokens[i]`` was unmasked (a byte a token); ``open_lanes`` the
        # positions, within the block still open, of the tokens at the tail
        # of ``tokens`` (which reads in position order at every moment, so a
        # delivery INSERTS there); ``block_passes`` the passes DISPATCHED for
        # the open block, the host's arithmetic beside ``sent``, which then
        # counts the tokens of the blocks whose commit pass is dispatched.
        self.denoising_steps = denoising_steps
        self.passes = bytearray()
        self.open_lanes = []
        self.block_passes = 0
        self.submit_time = time.time()
        self.admit_time = None
        # The way through the one prefill lane, on the clock of
        # ``admit_time``: the dispatch of the prompt's FIRST slice, the
        # dispatch of the slice that exhausts the prompt (the same instant
        # for a one-slice prompt), and the slices dispatched since the
        # admission. The engine stamps the two times once a request
        # (``_dispatch_step``): a recovery replay keeps the first stamps, as
        # ``admit_time`` does.
        self.lane_time = None
        self.last_slice_time = None
        self.slices = 0
        self.first_token_time = None
        self.finish_time = None
        # Absolute wall-clock expiry (None: no deadline). Checked QUEUE-
        # side at each admission round: a request whose deadline passes
        # before it reaches a slot is shed as ``expired`` — once work is
        # admitted, it finishes (mid-stream abandonment is cancel()'s
        # job, a caller decision).
        self.deadline = deadline
        # Times this request was re-admitted by recovery (replay). The
        # emitted stream stays one stream across replays — tokens only
        # ever grow.
        self.replays = 0
        # Tokens of ``tokens`` that recovery's replays have folded into
        # ``prompt`` (``engine._replay_requests``): the handle keeps them,
        # and this admission's tokens come after them.
        self.replayed = 0
        # Wall clock of the last PROGRESS this request made (submit,
        # then each step that emitted it tokens — the engine stamps at
        # harvest). The swap-victim policy reads it: staleness here
        # means an idle session whose slot is cheap to park
        # (kv_hierarchy.offload.pick_swap_victim).
        self.last_touch = self.submit_time
        # Front-door annotations (inference/frontdoor): the priority
        # class and tenant this request was admitted under. Pure
        # metadata to the scheduler EXCEPT that completions feed the
        # per-class retry_after_s estimator; None for the legacy
        # untagged surface, which behaves exactly as before.
        self.priority = priority
        self.tenant = tenant

    @property
    def done(self):
        return self.finish_time is not None

    def phase_ms(self):
        """The phases this request has left behind, in milliseconds, as
        arguments for its instants: ``queue_ms`` (submit -> admit) once
        admitted, ``prefill_ms`` (admit -> first token) once it has one,
        and the three parts of ``prefill_ms`` as each becomes known:
        ``lane_wait_ms`` (admit -> the dispatch of its first slice),
        ``lane_run_ms`` (-> the dispatch of its last slice) and
        ``first_lag_ms`` (-> first token at the host). A reader of a trace
        that opened after a transition finds the phase on any later
        instant of the request."""
        out = {}
        if self.admit_time is None:
            return out
        out["queue_ms"] = _ms(self.submit_time, self.admit_time)
        if self.lane_time is not None:
            out["lane_wait_ms"] = _ms(self.admit_time, self.lane_time)
            if self.last_slice_time is not None:
                out["lane_run_ms"] = _ms(self.lane_time, self.last_slice_time)
        if self.first_token_time is not None:
            out["prefill_ms"] = _ms(self.admit_time, self.first_token_time)
            if self.last_slice_time is not None:
                out["first_lag_ms"] = _ms(self.last_slice_time,
                                          self.first_token_time)
        return out


class Scheduler(object):
    """FIFO admission over a fixed slot set."""

    def __init__(self, num_slots, max_queue, tracer=None, registry=None,
                 replica_id=None):
        self.num_slots = num_slots
        self.max_queue = max_queue
        # Stamped into every QueueFull this scheduler raises so a fleet
        # router can attribute the shed to one replica's breaker. None
        # for a standalone engine.
        self.replica_id = replica_id
        self.queue = collections.deque()
        self.running = {}           # slot -> Request (prefilling | decoding)
        # rid -> Request in the ``swapped`` phase: mid-decode but holding
        # NO slot — its device state lives in the host swap store
        # (kv_hierarchy.offload). Insertion order IS swap-out order, so
        # next_swap_in() resumes the longest-waiting session first.
        self.swapped = {}
        # rid -> Request in the ``handoff`` phase: prefill finished, slot
        # captured and freed, stream mid-migration to another replica
        # (disaggregated serving — module docstring). Still this
        # scheduler's responsibility (``idle`` counts it) until
        # finish_handoff hands the durable truth to the new owner.
        self.handoff = {}
        # rid -> Request whose budget runs out inside a step that is
        # dispatched and not yet harvested (``release``): slotless, phase
        # still ``decoding``, its last tokens on the chip. ``idle`` counts
        # it; ``complete`` or ``cancel`` ends it, recovery requeues it.
        self.landing = {}
        self.completed = {}         # rid -> Request (incl. cancelled)
        self._ids = itertools.count()
        # Telemetry is strictly additive: tracer gets lifecycle spans,
        # registry gets the queue-wait histogram. Both optional — a bare
        # Scheduler(num_slots, max_queue) behaves exactly as before.
        self.tracer = tracer
        self._queue_wait = (registry.histogram("queue_wait_seconds")
                            if registry is not None else None)
        self._deadline_sheds = (registry.counter("deadline_sheds")
                                if registry is not None else None)
        # Recent completion timestamps — the retry_after_s estimator's
        # evidence. Bounded: backpressure hints need recency, not
        # history. ``_finish_by_class`` keeps the same evidence split by
        # priority class so a class-tagged shed gets a hint from ITS
        # completions rate — batch backpressure (slow, long outputs)
        # must not inflate the interactive hint.
        self._finish_times = collections.deque(maxlen=32)
        self._finish_by_class = {}
        # True once any queued request carries a deadline: admissions()
        # skips the expiry scan entirely on deadline-free workloads.
        self._has_deadlines = False

    # ------------------------------------------------------------ submit

    @staticmethod
    def _rate_hint(times):
        """1/rate over a completion-timestamp deque, clamped to
        [0, RETRY_AFTER_CAP_S]; None below two observations (no rate,
        no guess)."""
        if times is None or len(times) < 2:
            return None
        span = times[-1] - times[0]
        if span <= 0:
            return None
        rate = (len(times) - 1) / span
        return round(min(max(1.0 / rate, 0.0), RETRY_AFTER_CAP_S), 4)

    def retry_after_s(self, priority=None):
        """Backpressure hint: estimated seconds until one queue position
        frees, from the recent completions rate (None before two recent
        completions exist). CLASS-AWARE: with ``priority`` the estimate
        comes from that class's own completions — an interactive shed
        during a batch-dominated window hints at the interactive rate,
        not the global one — falling back to the global evidence until
        the class has two completions of its own."""
        if priority is not None:
            hint = self._rate_hint(self._finish_by_class.get(priority))
            if hint is not None:
                return hint
        return self._rate_hint(self._finish_times)

    def queue_full_error(self, reason=None, priority=None, tenant=None,
                         cause=None, retry_after_s=None):
        """The structured QueueFull for the CURRENT queue state — also
        built by the engine for admission-pressure sheds (injected
        faults, drain, paged-pool page exhaustion) so every shed carries
        the same backpressure fields. ``priority`` selects the
        class-aware hint and is stamped on the error along with
        ``tenant``. ``cause`` overrides the structured ``reason`` field
        (default ``queue_full``; the paged admission gate sheds with
        ``pages``) and ``retry_after_s`` overrides the completions-rate
        hint with a better-informed one (the page-release-rate estimate
        — paging.PageAllocator.retry_after_s)."""
        depth = len(self.queue)
        hint = retry_after_s if retry_after_s is not None \
            else self.retry_after_s(priority)
        msg = reason or ("inference queue is full ({} pending); retry "
                         "later or raise inference.max_queue".format(depth))
        if hint is not None:
            msg += " (retry_after_s hint: {})".format(hint)
        return QueueFull(msg, queue_depth=depth, retry_after_s=hint,
                         replica_id=self.replica_id, priority=priority,
                         tenant=tenant, reason=cause or "queue_full")

    def submit(self, prompt, max_new_tokens, temperature, top_k,
               eos_token_id, seed, spec=False, deadline=None,
               priority=None, tenant=None, trace=None, denoising_steps=None):
        if len(self.queue) >= self.max_queue:
            raise self.queue_full_error(priority=priority, tenant=tenant)
        req = Request(next(self._ids), prompt, max_new_tokens, temperature,
                      top_k, eos_token_id, seed, spec, deadline=deadline,
                      priority=priority, tenant=tenant, trace=trace,
                      denoising_steps=denoising_steps)
        if deadline is not None:
            self._has_deadlines = True
        self.queue.append(req)
        if self.tracer is not None:
            self.tracer.instant("request/submitted", tid=req.trace.tid,
                                rid=req.rid, hop=req.trace.hop(),
                                queue_depth=len(self.queue))
        return req

    # --------------------------------------------------------- admission

    def free_slot_ids(self):
        return [s for s in range(self.num_slots) if s not in self.running]

    def expire_deadlines(self, now=None):
        """QUEUE-side deadline expiry: shed every queued request whose
        deadline has passed (phase ``expired``, counted as a
        ``deadline_sheds``). Runs at each admission round — a deadline
        is a promise about WAITING, checked at the only point waiting
        can end. Returns the expired requests. Free on deadline-free
        workloads (one bool test)."""
        if not self._has_deadlines:
            return []
        now = time.time() if now is None else now
        expired = [r for r in self.queue
                   if r.deadline is not None and now >= r.deadline]
        for req in expired:
            self.queue.remove(req)
            req.phase = "expired"
            req.finish_time = now
            self.completed[req.rid] = req
            if self._deadline_sheds is not None:
                self._deadline_sheds.inc()
            if self.tracer is not None:
                self.tracer.instant("request/expired", tid=req.trace.tid,
                                    rid=req.rid, hop=req.trace.hop(),
                                    waited_s=round(now - req.submit_time, 4))
                self.tracer.span("request", req.submit_time, req.finish_time,
                                 tid=req.trace.tid, rid=req.rid,
                                 hop=req.trace.hop(), tokens=0,
                                 phase="expired")
        return expired

    def admissions(self, gate=None):
        """FIFO: pop (request, slot) pairs for every free slot while the
        queue lasts, moving each request into the ``prefilling`` phase
        (admit_time stamped — queue-wait ends here). Every admission
        comes through this one method, so queue_wait_seconds is stamped
        at one point — the windowed queue-wait curve is comparable
        across configs. Called by the engine ONLY at
        step boundaries — the device programs never see a mid-step batch
        change. Expired-deadline requests are shed before slots are
        filled; a replayed request (recovery re-admission) keeps its
        FIRST admit_time, so queue-wait is observed exactly once per
        request.

        ``gate``: optional callable(Request) -> bool consulted on the
        queue HEAD before it pops — the paged engine's page-reservation
        check. A rejected head ENDS the round (strict FIFO: younger
        requests must not jump a head that is merely waiting for pages
        to free — the same no-starvation rule the slot FIFO enforces)."""
        self.expire_deadlines()
        pairs = []
        for slot in self.free_slot_ids():
            if not self.queue:
                break
            if gate is not None and not gate(self.queue[0]):
                break
            req = self.queue.popleft()
            first_admission = req.admit_time is None
            req.slot = slot
            req.phase = "prefilling"
            req.cursor = 0
            req.slices = 0
            req.sent = 0
            req.block_passes = 0
            self.running[slot] = req
            pairs.append((req, slot))
            if not first_admission:
                continue  # replay re-admission: stats already stamped
            req.admit_time = time.time()
            if self._queue_wait is not None:
                self._queue_wait.observe(req.admit_time - req.submit_time)
            if self.tracer is not None:
                self.tracer.instant(
                    "request/admitted", tid=req.trace.tid, rid=req.rid,
                    hop=req.trace.hop(), slot=slot, **req.phase_ms())
                self.tracer.span("request/queued", req.submit_time,
                                 req.admit_time, tid=req.trace.tid,
                                 rid=req.rid, hop=req.trace.hop(), slot=slot,
                                 prompt_tokens=int(req.prompt.size))
        return pairs

    # ----------------------------------------------------------- prefill

    def next_prefill(self):
        """The prefilling request whose next prompt chunk rides the
        coming step: FIFO by admission order (admission is FIFO over a
        FIFO queue, so rid order IS admission order). None when no slot
        is mid-prefill."""
        pf = [r for r in self.running.values() if r.phase == "prefilling"]
        return min(pf, key=lambda r: r.rid) if pf else None

    def advance_prefill(self, req, n):
        """Record ``n`` prompt tokens DISPATCHED; returns True when the
        prompt is exhausted (the step just dispatched samples the
        request's first token and it moves to ``decoding``: it decodes in
        that step's lane already). ``prefill_done`` closes the phase's
        span when that step is harvested."""
        req.cursor += n
        if req.cursor >= req.prompt.size:
            req.phase = "decoding"
            return True
        return False

    def prefill_done(self, req, slot):
        """The step that held the prompt's last slice was harvested: the
        ``request/prefill`` span ends here, at the first token."""
        if self.tracer is not None:
            self.tracer.span("request/prefill", req.admit_time,
                             tid=req.trace.tid, rid=req.rid,
                             hop=req.trace.hop(), slot=slot,
                             prompt_tokens=int(req.prompt.size))

    # ------------------------------------------------------ host offload

    def swap_out(self, req):
        """Move a DECODING request out of its slot into the ``swapped``
        phase. The engine owns the device side (capture the slot to the
        host store, then deactivate it); this records only the truth
        that the session is paused and slotless."""
        assert req.phase == "decoding", req.phase
        self.running.pop(req.slot)
        req.slot = None
        req.phase = "swapped"
        self.swapped[req.rid] = req
        if self.tracer is not None:
            self.tracer.instant("request/swapped_out", tid=req.trace.tid,
                                rid=req.rid, hop=req.trace.hop(),
                                tokens=len(req.tokens))

    def next_swap_in(self, skip=()):
        """The longest-swapped session, or None — resume-first fairness:
        a swapped session outranks fresh queue admissions for the next
        free slot, so swaps time-slice the slot set instead of starving
        whoever lost the first eviction. ``skip`` (rids) excludes
        sessions deliberately HELD in the swapped phase — the front
        door's priority preemption parks batch work there and must not
        see it swapped straight back in on the next step."""
        for rid, req in self.swapped.items():
            if rid not in skip:
                return req
        return None

    def swap_in(self, req, slot):
        """Resume a swapped request into ``slot`` (need not be the slot
        it was captured from — the record carries every positional
        fact). The engine restores the device state before the next
        program call."""
        self.swapped.pop(req.rid)
        req.slot = slot
        req.phase = "decoding"
        self.running[slot] = req
        if self.tracer is not None:
            self.tracer.instant("request/swapped_in", tid=req.trace.tid,
                                rid=req.rid, hop=req.trace.hop(), slot=slot,
                                tokens=len(req.tokens))

    # ----------------------------------------------- disaggregated handoff

    def begin_handoff(self, req):
        """Move a DECODING request out of its slot into the ``handoff``
        phase (disaggregated serving): the prompt's final chunk landed
        on this prefill-role replica, the engine captured the slot's
        device state to a host record, and the stream is mid-migration
        to a decode replica. Slotless like ``swapped``, but bound for a
        DIFFERENT scheduler — the fleet's pump either places the record
        on an acceptor or falls back to re-prefill, then calls
        finish_handoff either way."""
        assert req.phase == "decoding", req.phase
        self.running.pop(req.slot)
        req.slot = None
        req.phase = "handoff"
        self.handoff[req.rid] = req
        if self.tracer is not None:
            self.tracer.instant("request/handoff", tid=req.trace.tid,
                                rid=req.rid, hop=req.trace.hop(),
                                tokens=len(req.tokens))

    def finish_handoff(self, req):
        """The migration settled — adopted by a peer replica, or fallen
        back to re-prefill elsewhere: drop the request from this
        scheduler's books entirely (NOT completed(); the new owner's
        record is the durable truth now and stamps the terminal
        phase)."""
        self.handoff.pop(req.rid, None)

    def adopt(self, prompt, max_new_tokens, temperature, top_k,
              eos_token_id, seed, slot, spec=False, deadline=None,
              submit_time=None, admit_time=None, first_token_time=None,
              priority=None, tenant=None, trace=None, flow=None):
        """ACCEPTOR-side constructor: install a request migrated from a
        prefill-role peer straight into ``slot`` in the ``decoding``
        phase — it never queues here and never rides the prefill lane
        (the restored KV record IS its prefill). ``prompt`` is the
        residual respec form (original prompt + tokens already emitted
        on the donor) so a later recovery replay on THIS replica is
        bit-identical, exactly like an orphan re-submission. The donor's
        submit/admit/first-token stamps carry over so queue-wait and
        TTFT are observed exactly once, on the replica where they
        actually happened."""
        assert slot not in self.running, slot
        req = Request(next(self._ids), prompt, max_new_tokens, temperature,
                      top_k, eos_token_id, seed, spec, deadline=deadline,
                      priority=priority, tenant=tenant, trace=trace)
        if submit_time is not None:
            req.submit_time = submit_time
            req.last_touch = submit_time
        req.admit_time = admit_time if admit_time is not None \
            else req.submit_time
        req.first_token_time = first_token_time
        req.cursor = int(prompt.size)
        req.slot = slot
        req.phase = "decoding"
        self.running[slot] = req
        if self.tracer is not None:
            args = {"rid": req.rid, "slot": slot,
                    "prompt_tokens": int(prompt.size),
                    "hop": req.trace.hop()}
            if flow is not None:
                args["flow_in"] = flow
            self.tracer.instant("request/handoff_in", tid=req.trace.tid,
                                **args)
        return req

    # -------------------------------------------------------- completion

    def release(self, req):
        """Free ``req``'s slot for the next admission round BEFORE its last
        tokens are harvested: the engine calls this right after it
        dispatched the step inside which the request's budget runs out, so
        the slot is certain to be inactive on the chip when that step ends
        and the next step may prefill another request into it. The request
        waits in ``landing`` for ``complete``."""
        self.running.pop(req.slot)
        req.slot = None
        self.landing[req.rid] = req

    def complete(self, req):
        """``req`` is finished: its slot, unless ``release`` freed it
        already, is free for the next admission round."""
        if req.slot is None:
            self.landing.pop(req.rid)
        else:
            self.running.pop(req.slot)
        req.finish_time = time.time()
        req.phase = "done"
        req.slot = None
        self.completed[req.rid] = req
        self._finish_times.append(req.finish_time)
        if req.priority is not None:
            self._finish_by_class.setdefault(
                req.priority,
                collections.deque(maxlen=32)).append(req.finish_time)
        if self.tracer is not None:
            self.tracer.instant("request/finished", tid=req.trace.tid,
                                rid=req.rid, hop=req.trace.hop(),
                                tokens=len(req.tokens), **req.phase_ms())
            if req.first_token_time is not None:
                self.tracer.span("request/decode", req.first_token_time,
                                 req.finish_time, tid=req.trace.tid,
                                 rid=req.rid, hop=req.trace.hop(),
                                 tokens=len(req.tokens))
            self.tracer.span("request", req.submit_time, req.finish_time,
                             tid=req.trace.tid, rid=req.rid,
                             hop=req.trace.hop(),
                             tokens=len(req.tokens), phase="done")
        return req

    def cancel(self, req):
        """Evict ``req`` wherever it lives — queued, mid-prefill, or
        decoding. Its slot (if any) frees for the next admission round;
        tokens emitted so far stay on the request. Returns True when the
        request was live (False: already finished). The caller owns any
        device-side deactivation (the engine clears the slot's active
        flag for decoding-phase cancels; a prefilling slot has no device
        state to clear — its frontier is overwritten at re-admission)."""
        if req.done:
            return False
        if req.phase == "queued":
            self.queue.remove(req)
        elif req.phase == "swapped":
            self.swapped.pop(req.rid)  # slotless; host record is the
            # engine's to drop (hierarchy on_release)
        elif req.phase == "handoff":
            # Slotless and already off the device (the slot was captured
            # and deactivated at begin_handoff) — host bookkeeping only.
            # pop() tolerates a record the pump already claimed: the
            # placement commit re-checks the phase under the fleet lock
            # and aborts on the adopted copy (fleet._pump_handoffs).
            self.handoff.pop(req.rid, None)
        elif req.slot is None:
            self.landing.pop(req.rid)  # released: slot and pages are free
        else:
            self.running.pop(req.slot)
            req.slot = None
        req.phase = "cancelled"
        req.finish_time = time.time()
        self.completed[req.rid] = req
        if self.tracer is not None:
            self.tracer.instant("request/cancelled", tid=req.trace.tid,
                                rid=req.rid, hop=req.trace.hop(),
                                tokens=len(req.tokens))
            self.tracer.span("request", req.submit_time, req.finish_time,
                             tid=req.trace.tid, rid=req.rid,
                             hop=req.trace.hop(),
                             tokens=len(req.tokens), phase="cancelled")
        return True

    # ---------------------------------------------------------- recovery

    def requeue_running(self):
        """Crash-only recovery (docs/RESILIENCE.md): pull EVERY in-flight
        request out of its slot and push all of them back onto the FRONT
        of the queue in rid (= original admission) order, ahead of
        never-admitted work. The engine calls this after a fatal step
        error — device state is being rebuilt, so each request restarts
        prefill from cursor 0; the ENGINE rewrites its prompt to
        prompt + tokens-emitted-so-far first, which is what makes the
        replayed stream bit-identical (the positional fold_in(seed, pos)
        rng names every draw by absolute position — see
        engine._replay_requests). Returns the requeued requests in rid
        order. SWAPPED sessions requeue too: their host swap records
        described a pool that no longer exists (the engine drops them
        via hierarchy reset), but the request records are the durable
        truth and replay rebuilds the stream bit-identically.
        HANDOFF requests deliberately stay put: their device state was
        already captured to host records that survive the pool rebuild
        untouched — the fleet's pump migrates or falls back regardless
        of what happens to this replica's pool. Requests in ``landing``
        requeue like running ones: the tokens they waited for were on the
        pool that died."""
        reqs = sorted(list(self.running.values())
                      + list(self.swapped.values())
                      + list(self.landing.values()), key=lambda r: r.rid)
        self.running.clear()
        self.swapped.clear()
        self.landing.clear()
        for req in reversed(reqs):
            req.slot = None
            req.phase = "queued"
            req.cursor = 0
            req.replays += 1
            self.queue.appendleft(req)
            if self.tracer is not None:
                self.tracer.instant("request/replayed", tid=req.trace.tid,
                                    rid=req.rid, hop=req.trace.hop(),
                                    replay=req.replays,
                                    tokens=len(req.tokens))
        return reqs

    @property
    def idle(self):
        return (not self.queue and not self.running and not self.swapped
                and not self.handoff and not self.landing)

    def occupancy(self):
        return len(self.running) / float(self.num_slots)
