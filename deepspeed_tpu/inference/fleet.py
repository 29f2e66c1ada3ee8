"""Replicated serving fleet — N engines, one front door.

PR 7 made a single engine crash-only: host-side Request records are the
durable truth, device state is disposable, and a dead engine is a
terminal, attributable event (EngineDeadError). This module is the step
the ROADMAP's "serve millions of users" item actually needs: a
``ServingFleet`` that owns N data-parallel ``InferenceEngine`` replicas
and extends the crash-only invariant ACROSS them — when a replica dies,
its durable request records re-submit to survivors with residual
budgets, and every stream (greedy and sampled) completes bit-identically
to a fault-free run, because emissions depend only on
(prompt, seed, absolute position) via the positional ``fold_in(seed,
pos)`` rng — never on which replica, batch composition, or chunk
boundary produced them. Zero requests lost; survivors' compile_count
unchanged (same request shapes -> jit cache hits).

Topology: replicas are IN-PROCESS, one stepping thread each, so tier-1
CPU tests exercise the real concurrent code path. Replica->device
placement comes from ``parallel.mesh.replica_devices`` — on a multi-chip
host each replica gets its own device (params ``device_put`` there, the
engine built under ``jax.default_device``); on a single-device host
(CPU tests) replicas share the device and the host params. Per-replica
tensor parallelism (a mesh per replica) is out of scope here — a fleet
replica is one device.

Routing (router.py): health-weighted least-loaded over the live
``queue_depth`` / ``slot_occupancy`` / ``health_state`` gauges, one
circuit breaker per replica fed by structured ``QueueFull.retry_after_s``
sheds, watchdog ``step_stalls``, and fatal-step recoveries. The fleet
consults ``breaker.allow()`` only for replicas it actually attempts, so
half-open probes are never burned on untried candidates.

Prefix affinity (this PR): when the replicas run PR 9's prefix cache,
the fleet keeps a ``PrefixDirectory`` — a host-side map of published
prefix rows per replica, re-synced after clean steps (gated by the
store's ``version`` counter) and invalidated wholesale on replica
death or recovery. ``submit()`` folds the directory's longest-match
depth into the router score (``score - AFFINITY_WEIGHT * depth /
prefix_len``), so template traffic lands on the replica already
holding its prefix planes; when load wins the route anyway, the cold
winner ADOPTS the holder's planes (``export_prefix`` on the donor,
``adopt_prefix`` on the acceptor — int8 codes ship as-is, no
dequantize round-trip) before submitting, so the prefill skips the
shared span either way. The directory is derived state and never
authoritative: both adoption ends re-validate against their live
PrefixStore under their own replica lock, and the failover/recovery
invariants never depend on it (``prefix_affinity=False`` disables the
whole plane for a clean A/B).

Locking discipline (the whole concurrency story, in one place):

- ``rep.lock`` (one per replica) serializes EVERY call into that
  replica's engine — submit, step, cancel, health transitions. An
  engine is single-threaded by contract; the fleet supplies that
  contract.
- ``self._lock`` (fleet RLock) guards fleet bookkeeping: the request
  table, the orphan list, failover counters.
- ORDER: ``self._lock`` may be taken while holding a ``rep.lock``,
  NEVER the reverse — so a submit registering its request can nest, and
  a failover scanning the table cannot deadlock against it.

Failure of a replica (recovery retries exhausted, or any unexpected
step exception — crash-only means we don't diagnose, we fail over)
triggers ``_failover``: every live FleetRequest owned by the dead
replica snapshots its resubmission spec (prompt + all emitted tokens,
residual token budget, original sampling params and seed) and joins the
orphan list; ``_pump`` then places orphans on survivors — directly via
the scheduler, bypassing admission health, because ACCEPTED IS A
PROMISE: a draining survivor still takes failover work, and a full one
is retried until a slot frees (``idle`` stays False while orphans
exist, so drive loops keep pumping).

Disaggregated prefill/decode serving (this PR): ``roles=`` types each
replica ``prefill`` / ``decode`` / ``mixed`` (default all-``mixed`` —
nothing above changes unless you opt in). The router sends NEW requests
only to prefill-capable replicas (role eligibility SKIPS ineligible
views before scoring — no score, no tie-break rng draw — so an
all-mixed fleet routes bit-identically to before); when a prompt's
final chunk lands on a prefill replica, the engine captures the
finished slot — every plane exactly as stored, int8 codes + scales
never dequantized, all completers of one step in ONE batched transfer —
and the fleet's ``HandoffPump`` migrates the stream into a
decode-capable replica's slot pool, chosen by the same health/affinity
ordering. The acceptor installs it straight into the ``decoding`` phase
(the restored record IS the prefill), so decode replicas never run a
prefill lane and their inter-token latency is interference-free. The
durable host-side record plus the residual respec (prompt + emitted,
residual budget, positional ``fold_in(seed, pos)`` rng) keep every
stream bit-identical to a single-engine run whatever happens
mid-migration: cancel reaches a mid-handoff stream (the pump's commit
and the cancel path serialize on the fleet lock), donor death drops the
pump item and replays via the normal orphan path, and when NO
decode-capable replica survives, the surviving prefill replicas degrade
to effective-mixed (capture disabled) and the stream re-prefills on a
survivor — zero lost, counted as ``handoff_fallbacks``.
"""

import dataclasses
import itertools
import json
import os
import threading
import time

import jax
import numpy as np

from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.kv_hierarchy import PrefixDirectory
from deepspeed_tpu.inference.resilience import (
    EngineDeadError,
    EngineDraining,
)
from deepspeed_tpu.inference.router import CircuitBreaker, Router
from deepspeed_tpu.inference.scheduler import QueueFull, RETRY_AFTER_CAP_S
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.telemetry import (
    MergedRegistry,
    NullRecorder,
    SpanRecorder,
    TimeseriesCollector,
    prometheus_text,
)
from deepspeed_tpu.telemetry.alerts import AlertManager, default_rules
from deepspeed_tpu.telemetry.autopsy import build_autopsy, worst_requests
from deepspeed_tpu.telemetry.distributed import (
    FLEET_TID_BASE,
    TraceContext,
    merged_trace,
    write_merged_trace,
)
from deepspeed_tpu.utils.logging import logger


class FleetRequest(object):
    """Fleet-side handle for one submitted request — the object a
    caller (or the loadgen runner) holds across failovers. Exposes the
    same read surface as a scheduler Request (rid/phase/tokens/
    submit_time/first_token_time/finish_time/done) but stitches the
    stream across replicas: ``tokens`` is every token emitted on dead
    prior owners plus the current owner's record, in emission order —
    one continuous bit-identical stream."""

    __slots__ = ("fid", "replica_id", "failovers", "trace", "_req",
                 "_prior", "_submit_time", "_first_token_time",
                 "_finish_time", "_cancelled", "_respec")

    def __init__(self, fid, replica_id, req):
        self.fid = fid
        self.replica_id = replica_id   # current owner; None mid-failover
        self.failovers = 0
        # The propagated trace identity — shared BY REFERENCE with the
        # engine Request, so it survives _req being detached and
        # re-pointed across failovers/handoffs.
        self.trace = req.trace
        self._req = req                # current engine Request record
        self._prior = []               # tokens emitted on dead replicas
        self._submit_time = req.submit_time
        self._first_token_time = None  # preserved across failover
        self._finish_time = None       # set only by orphan-cancel
        self._cancelled = False
        self._respec = None

    # -- the Request-compatible read surface ----------------------------

    @property
    def rid(self):
        return self.fid

    @property
    def tokens(self):
        req = self._req
        if req is None:
            return list(self._prior)
        return self._prior + list(req.tokens)

    @property
    def phase(self):
        req = self._req
        if req is not None:
            return req.phase
        return "cancelled" if self._cancelled else "queued"

    @property
    def submit_time(self):
        return self._submit_time

    @property
    def first_token_time(self):
        if self._first_token_time is not None:
            return self._first_token_time
        req = self._req
        return None if req is None else req.first_token_time

    @property
    def finish_time(self):
        if self._finish_time is not None:
            return self._finish_time
        req = self._req
        return None if req is None else req.finish_time

    @property
    def done(self):
        return self.finish_time is not None

    # -- failover internals (called under the fleet lock) ---------------

    def _orphan(self):
        """Snapshot the resubmission spec from the (dead) owner's record
        and detach. Residual replay is the same move PR 7's single-
        engine ``_replay_requests`` makes, lifted across replicas: the
        new prompt is original-prompt + every emitted token (none is
        EOS — it would have completed), the budget shrinks by what was
        already delivered, and sampling params + seed carry over so the
        positional rng reproduces the remaining stream bit-identically
        on ANY survivor."""
        req = self._req
        if req.first_token_time is not None and \
                self._first_token_time is None:
            self._first_token_time = req.first_token_time
        emitted = [int(t) for t in req.tokens]
        self._prior.extend(emitted)
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if emitted:
            prompt = np.concatenate(
                [prompt, np.asarray(emitted, np.int32)])
        self.failovers += 1
        self._respec = {
            "prompt": prompt,
            "max_new_tokens": req.max_new_tokens - len(emitted),
            "temperature": req.temperature,
            "top_k": req.top_k,
            "eos_token_id": req.eos_token_id,
            "seed": req.seed,
            "spec": req.spec,
            "deadline": req.deadline,
            "priority": req.priority,
            "tenant": req.tenant,
            # Trace carries BY REFERENCE so the survivor's events stay
            # on the same tid with the same hop counter; ``flow`` is
            # the failover arrow's key — the dead owner's failover_out
            # and the survivor's failover_in both stamp it, and the
            # merge pairs them into one s/f pair.
            "trace": req.trace,
            "flow": "failover/{}/{}".format(req.trace.tid,
                                            self.failovers),
        }
        self._req = None
        self.replica_id = None

    def _mark_cancelled(self, now):
        self._cancelled = True
        self._finish_time = now


class _Replica(object):
    """One engine plus its fleet-side fixtures: the serialization lock,
    the stepping thread's wake/stop events, the circuit breaker, and
    cached handles to the live gauges the router scores from."""

    __slots__ = ("rid", "engine", "device", "breaker", "lock", "wake",
                 "stop", "thread", "failed", "last_stalls",
                 "last_recoveries", "last_prefix_version", "_g_queue",
                 "_g_occ")

    def __init__(self, rid, engine, device, breaker):
        self.rid = rid
        self.engine = engine
        self.device = device
        self.breaker = breaker
        self.lock = threading.Lock()
        self.wake = threading.Event()
        self.stop = threading.Event()
        self.thread = None
        self.failed = False
        self.last_stalls = 0
        self.last_recoveries = 0
        # PrefixStore.version at the last directory sync — gates the
        # publish walk so clean steps with an unchanged prefix set pay
        # one int compare, not a store scan.
        self.last_prefix_version = -1
        self._g_queue = engine.telemetry.gauge("queue_depth")
        self._g_occ = engine.telemetry.gauge("slot_occupancy")

    # Router view (router.Router.score reads these).
    @property
    def queue_depth(self):
        return self._g_queue.value

    @property
    def slot_occupancy(self):
        return self._g_occ.value

    @property
    def max_slots(self):
        return self.engine.config.max_slots

    @property
    def health(self):
        return self.engine.health

    @property
    def alive(self):
        return not self.failed and self.engine.health != "dead"


class _FleetCounters(object):
    """Read-only dict-shaped SUM of every replica's counter bank — the
    same duck type as ``engine.counters`` (``in`` / ``[]`` / items), so
    the loadgen runner's counter reads work on a fleet unchanged. Dead
    replicas keep counting (their totals are history, not garbage)."""

    __slots__ = ("_replicas",)

    def __init__(self, replicas):
        self._replicas = replicas

    def _banks(self):
        return [r.engine.counters for r in self._replicas]

    def __contains__(self, name):
        return any(name in b for b in self._banks())

    def __getitem__(self, name):
        banks = [b for b in self._banks() if name in b]
        if not banks:
            raise KeyError(name)
        return sum(b[name] for b in banks)

    def __iter__(self):
        seen = set()
        for b in self._banks():
            for n in b:
                if n not in seen:
                    seen.add(n)
                    yield n

    def keys(self):
        return list(self)

    def items(self):
        return [(n, self[n]) for n in self]


class HandoffPump(object):
    """In-flight KV-plane migrations, donor -> decode replica. One per
    fleet; every replica thread (and the single-threaded ``step()``
    driver) drains it, so a migration never depends on any particular
    thread surviving. Items are ``(fr, donor_rep, req, record,
    t_capture)`` tuples: the fleet handle, the prefill replica that
    captured, its (slotless, phase-``handoff``) engine Request, the
    host-side slot record, and the capture wall clock the donor's
    ``handoff_latency_seconds`` histogram observes at commit.

    Thread contract: ``claim()`` atomically empties the list, so
    concurrent pumps from several replica threads each get disjoint
    items and never double-place one stream; ``requeue()`` puts
    unplaceable items back at the FRONT (oldest migration retries
    first). Every attribute write outside ``__init__`` holds
    ``self.lock`` — graftlint THREADRACE checks this class."""

    _THREAD_OWNED = frozenset()

    def __init__(self):
        self.lock = threading.Lock()
        self.pending = []
        self.total = 0

    def put(self, items):
        with self.lock:
            self.pending.extend(items)
            self.total += len(items)

    def claim(self):
        with self.lock:
            items, self.pending = self.pending, []
        return items

    def requeue(self, items):
        with self.lock:
            self.pending = list(items) + self.pending

    def __len__(self):
        with self.lock:
            return len(self.pending)


class ServingFleet(object):
    """N replicas, one submit()/harvest()/cancel()/drain() surface.

    ``start=True`` (default) launches one daemon stepping thread per
    replica; ``start=False`` leaves the fleet single-threaded — callers
    drive ``step()`` themselves, which is what the deterministic routing
    tests do (no thread is racing the load the router scores).

    ``breaker_factory`` builds one CircuitBreaker per replica (tests
    inject fake-clock breakers); ``seed`` fixes the router's tie-break
    rng. The fleet owns a TimeseriesCollector over the merged registry
    — its windows are the SLO evidence ``rolling_drain`` checks before
    taking a replica out of rotation."""

    # graftlint THREADRACE manifest — deliberately EMPTY: the fleet is
    # the multi-threaded half of the stack (replica pump threads, the
    # caller, watchdogs, __del__), so every shared attribute write
    # outside __init__ must hold self._lock. Per-replica state lives on
    # _Replica and is serialized by rep.lock instead.
    _THREAD_OWNED = frozenset()

    def __init__(self, model, params, n_replicas=2, config=None, seed=0,
                 window_seconds=1.0, window_capacity=512, start=True,
                 breaker_factory=None, idle_wait_s=0.01, poll_s=0.002,
                 prefix_affinity=None, roles=None,
                 latency_classes=("interactive",), alert_rules=None,
                 dump_dir=None, adapter=None):
        if n_replicas < 1:
            raise ValueError("n_replicas must be >= 1, got "
                             "{}".format(n_replicas))
        if isinstance(config, dict):
            config = InferenceConfig.from_dict(config)
        config = config or InferenceConfig()
        self.config = config
        # Disaggregated serving: one role string per replica. Default —
        # every replica takes config.role (itself defaulting "mixed"),
        # so an undecorated fleet behaves exactly as before. Per-role
        # field validation runs in InferenceConfig.__post_init__ via the
        # per-replica replace().
        if roles is None:
            roles = [config.role] * n_replicas
        roles = [str(r) for r in roles]
        if len(roles) != n_replicas:
            raise ValueError(
                "roles must name one role per replica: got {} for "
                "{} replicas".format(len(roles), n_replicas))
        if "prefill" in roles and \
                not any(r in ("decode", "mixed") for r in roles):
            raise ValueError(
                "a prefill-role replica needs at least one decode or "
                "mixed replica to hand finished prompts to; got "
                "roles={}".format(roles))
        self.roles = tuple(roles)
        self._disagg = any(r != "mixed" for r in roles)
        if breaker_factory is None:
            breaker_factory = CircuitBreaker
        devices = mesh_lib.replica_devices(n_replicas)
        multi_device = len(set(devices)) > 1
        self.replicas = []
        for i in range(n_replicas):
            cfg = dataclasses.replace(config, replica_id=i, role=roles[i])
            if multi_device:
                # Own device per replica: params land there once, and
                # the engine's pool/programs follow via default_device.
                p = jax.device_put(params, devices[i])
                with jax.default_device(devices[i]):
                    # Same adapter instance per replica: equal static
                    # args, so replicas share one compiled program.
                    eng = InferenceEngine(model, p, config=cfg,
                                          adapter=adapter)
                # Commit the fresh pool to its device. default_device
                # only PLACES it there (uncommitted); the first step's
                # output pool comes back committed, and a commitment
                # flip on an otherwise identical argument re-keys the
                # jit cache — a spurious second compile per replica.
                eng._pool = jax.device_put(eng._pool, devices[i])
            else:
                # Single-device host (CPU tests): replicas share the
                # device AND the host params — no copies.
                eng = InferenceEngine(model, params, config=cfg,
                                      adapter=adapter)
            self.replicas.append(
                _Replica(i, eng, devices[i], breaker_factory()))
        # The resolved adapter (every replica shares one instance —
        # engines fall back to GPT2Adapter when none was passed, so read
        # it back rather than echoing the argument).
        self.adapter = self.replicas[0].engine.adapter
        self.router = Router(seed=seed)
        # Fleet-global prefix directory: on by default whenever the
        # replicas run a prefix cache (there is nothing to publish
        # without one); prefix_affinity=False forces it off for a clean
        # affinity-free A/B on the same config.
        if prefix_affinity is None:
            prefix_affinity = bool(config.prefix_cache)
        self.prefix_affinity = bool(prefix_affinity)
        self._directory = PrefixDirectory() if self.prefix_affinity \
            else None
        # Class-aware placement (inference/frontdoor): submissions
        # tagged with one of these priority classes are routed only to
        # the SHALLOWEST live queues (minimum queue depth among the
        # otherwise-eligible views) — a latency-class request must not
        # land behind a replica's batch backlog when an emptier peer
        # exists. Untagged and non-latency traffic takes the historical
        # router order untouched (eligibility is an ineligible-view
        # SKIP, so the seeded tie-break sequence is preserved).
        self._latency_classes = frozenset(latency_classes or ())
        self.telemetry = MergedRegistry(
            {r.rid: r.engine.telemetry for r in self.replicas})
        self.collector = TimeseriesCollector(
            self.telemetry, window_seconds=window_seconds,
            capacity=window_capacity)
        self.collector.start()
        self.counters = _FleetCounters(self.replicas)
        # Fleet-window base: metrics(reset=True) snapshots the cumulative
        # sums here so the aggregate windows like a lone engine's metrics
        # without touching the counter windows the collector owns.
        self._agg_base = {}
        # Fleet-plane flight ring: routing decisions, failover arrows,
        # prefix-ship flows — everything that happens BETWEEN replicas
        # and so belongs to no engine's ring. Merged with the replica
        # rings by write_trace()/explain().
        self.tracer = (SpanRecorder(capacity=2048)
                       if config.telemetry else NullRecorder())
        # SLO burn-rate alerting over the collector's windows
        # (telemetry/alerts.py), evaluated from _tick() whenever a
        # window closes. ``dump_dir`` arms the auto-dump: a firing rule
        # or a replica death writes the merged trace + worst-K
        # autopsies there before anyone has to ask.
        self._dump_dir = dump_dir
        self.dumps = []
        self.alerts = AlertManager(
            self.collector,
            default_rules() if alert_rules is None else alert_rules,
            on_fire=[lambda rule, rec:
                     self._auto_dump("alert:" + rule.name)])
        self._lock = threading.RLock()
        self._tick_lock = threading.Lock()
        self._fids = itertools.count()
        self._flow_ids = itertools.count(1)  # prefix-ship flow keys
        self._requests = {}     # fid -> FleetRequest (until harvested)
        self._orphans = []      # FleetRequests awaiting resubmission
        self._handoffs = HandoffPump()
        self.failovers = 0      # requests moved off dead replicas
        self._idle_wait_s = idle_wait_s
        self._poll_s = poll_s
        self._started = False
        self._closed = False
        if start:
            self.start()

    # ------------------------------------------------------------ threads

    def start(self):
        """Launch the per-replica stepping threads (idempotent)."""
        # Check-and-set under the fleet lock: two racing start() calls
        # (or a start() racing close()) must not both pass the guard and
        # double-spawn replica threads.
        with self._lock:
            if self._started or self._closed:
                return
            self._started = True
        for rep in self.replicas:
            rep.thread = threading.Thread(
                target=self._replica_loop, args=(rep,),
                name="ds-fleet-replica-{}".format(rep.rid), daemon=True)
            rep.thread.start()

    def _replica_loop(self, rep):
        while not rep.stop.is_set():
            if self._orphans:
                self._pump()
            if self._handoffs.pending:
                self._pump_handoffs()
            progressed = self._step_replica(rep)
            if rep.failed:
                return  # dead is terminal; the thread's work is done
            self._tick()
            if not progressed:
                rep.wake.wait(self._idle_wait_s)
                rep.wake.clear()

    def _step_replica(self, rep):
        """One guarded engine step; returns True when work was done.
        ANY escape from step() — EngineDeadError (recovery retries
        exhausted) or an unexpected exception (crash-only: we fail
        over, we don't diagnose) — fails the replica and triggers
        failover of its live requests."""
        dead = None
        with rep.lock:
            if rep.failed or rep.engine.health == "dead":
                return False
            if rep.engine.idle:
                return False
            try:
                rep.engine.step()
            except EngineDeadError as e:
                dead = e
            except Exception as e:  # noqa: BLE001 — crash-only failover
                logger.exception(
                    "fleet: replica %d step raised unexpectedly — "
                    "failing it over", rep.rid)
                dead = e
                try:
                    rep.engine._health.to("dead")
                except Exception:  # noqa: BLE001 — already dead is fine
                    pass
            else:
                self._observe_resilience(rep)
                self._sync_prefixes(rep)
                self._collect_handoffs(rep)
        if dead is not None:
            self._failover(rep, dead)
            return False
        return True

    def _observe_resilience(self, rep):
        """Feed the breaker from the engine's own resilience counters
        (called under rep.lock, right after a step): a watchdog stall
        or a fatal-step recovery is sickness, not load — trip
        immediately, no failure threshold."""
        c = rep.engine.counters
        stalls = c["step_stalls"]
        recoveries = c["recoveries"]
        if stalls > rep.last_stalls or recoveries > rep.last_recoveries:
            rep.breaker.trip()
        if recoveries > rep.last_recoveries and \
                self._directory is not None:
            # A recovery rebuilt the pool (KVHierarchy.reset) — every
            # plane the directory described for this replica is gone.
            # Drop them wholesale; the store's bumped version re-syncs
            # whatever the replay re-earns. Directory lock is a leaf,
            # safe under rep.lock.
            self._directory.invalidate(rep.rid)
        rep.last_stalls = stalls
        rep.last_recoveries = recoveries

    def _sync_prefixes(self, rep):
        """Publish this replica's live prefix rows into the directory
        (called under rep.lock, right after a clean step). The store's
        ``version`` counter — bumped only when row CONTENTS change —
        gates the walk, so the steady state costs one int compare."""
        if self._directory is None:
            return
        hier = rep.engine._hier
        if hier is None or hier.store is None:
            return
        version = hier.store.version
        if version == rep.last_prefix_version:
            return
        self._directory.sync(rep.rid, hier.store.tokens.values())
        rep.last_prefix_version = version

    # ----------------------------------------------- disaggregated handoff

    def _collect_handoffs(self, rep):
        """Pull freshly captured migrations off a prefill replica's
        outbox (called under rep.lock, right after a clean step) and
        enqueue them on the pump. A captured request whose fleet handle
        is already gone (cancelled AND harvested between capture and
        collect) settles on the donor immediately."""
        if not rep.engine._handoff_outbox:
            return
        items = []
        with self._lock:
            for req, record, t0 in rep.engine.take_handoffs():
                fr = next((f for f in self._requests.values()
                           if f._req is req), None)
                if fr is None:
                    rep.engine.finish_handoff(req)
                    continue
                items.append((fr, rep, req, record, t0))
        if items:
            self._handoffs.put(items)

    def _pump_handoffs(self):
        """Drain the pump: place each claimed migration on a
        decode-capable replica (or settle it — cancelled, donor-died,
        or fallen back to re-prefill); what cannot place RIGHT NOW
        (every acceptor's slot pool full) requeues for the next pass —
        ``idle`` stays False until the pump empties, so drive loops
        keep pumping exactly like the orphan path."""
        items = self._handoffs.claim()
        if not items:
            return
        remaining = [item for item in items
                     if not self._place_handoff(*item)]
        if remaining:
            self._handoffs.requeue(remaining)

    def _build_handoff_spec(self, req):
        """The durable residual respec for a mid-handoff stream — the
        same snapshot ``FleetRequest._orphan`` takes (prompt + emitted,
        residual budget, params + seed, so the positional rng continues
        bit-identically anywhere), PLUS the donor's submit/admit/first-
        token stamps so the acceptor adopts them instead of re-stamping
        (queue-wait and TTFT are observed exactly once, where they
        happened). Caller holds the fleet lock."""
        emitted = [int(t) for t in req.tokens]
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if emitted:
            prompt = np.concatenate(
                [prompt, np.asarray(emitted, np.int32)])
        return {
            "prompt": prompt,
            "max_new_tokens": req.max_new_tokens - len(emitted),
            "temperature": req.temperature,
            "top_k": req.top_k,
            "eos_token_id": req.eos_token_id,
            "seed": req.seed,
            "spec": req.spec,
            "deadline": req.deadline,
            "submit_time": req.submit_time,
            "admit_time": req.admit_time,
            "first_token_time": req.first_token_time,
            "priority": req.priority,
            "tenant": req.tenant,
            "trace": req.trace,
        }

    def _place_handoff(self, fr, donor, req, record, t0):
        """One migration attempt. Returns True when the item SETTLED —
        adopted, dropped (cancelled / donor failed over), or fallen
        back to re-prefill — and False to retry on a later pass."""
        with self._lock:
            if fr._cancelled or fr.done or fr._req is not req \
                    or req.phase != "handoff":
                # The stream moved on without us: cancel reached it, or
                # the donor died and _failover orphaned it (fr._req is
                # None / a survivor's record now). Nothing to migrate.
                self._settle_handoff(donor, req, t0, "dropped")
                return True
            spec = self._build_handoff_spec(req)
        # Donor-side anchor for the migration arrow: the acceptor's
        # handoff_in (scheduler.adopt) closes the same flow key. The
        # key reuses the anchor's own hop number so every consumed hop
        # is stamped on exactly one event (hop_gaps stays empty).
        hop = req.trace.hop()
        spec["flow"] = "handoff/{}/{}".format(req.trace.tid, hop)
        donor.engine.tracer.instant(
            "request/handoff_out", tid=req.trace.tid, rid=req.rid,
            hop=hop, flow_out=spec["flow"], fid=fr.fid,
            tokens_emitted=len(spec["prompt"]) - len(req.prompt))
        pbase = int(np.asarray(record["pbase"])) if "pbase" in record else 0
        acceptors = self._ordered(include_draining=True, role="decode")
        if not acceptors:
            return self._handoff_fallback(fr, donor, req, t0)
        for acc in acceptors:
            placed = self._try_acceptor(acc, donor, fr, req, record,
                                        spec, pbase, t0)
            if placed is not None:
                return placed
        return False

    def _try_acceptor(self, acc, donor, fr, req, record, spec, pbase, t0):
        """Try ONE decode-capable acceptor. Returns True (settled on
        this acceptor, or found cancelled at commit), or None — this
        acceptor cannot take it (dead, slot pool full, or it lacks the
        aliased prefix span even after a ship attempt) and the caller
        moves to the next candidate.

        Lock choreography: adopt + commit both run under acc.lock, with
        the fleet lock nested for the commit — the same rep.lock ->
        self._lock order every other path uses. Holding acc.lock across
        the commit closes the window where the acceptor could fail
        between adoption and the handle pointing at it; holding
        self._lock for the phase re-check serializes against cancel()'s
        handoff branch, so a cancel either lands before (we abort the
        freshly adopted copy) or after (it retries against the new
        owner) — never half-way."""
        shipped = False
        while True:
            committed = None
            with acc.lock:
                if acc.failed:
                    return None
                if not acc.engine._scheduler.free_slot_ids():
                    return None  # full right now — not this acceptor
                new_req = acc.engine.adopt_handoff(spec, record)
                if new_req is not None:
                    with self._lock:
                        if fr._cancelled or fr._req is not req \
                                or req.phase != "handoff":
                            acc.engine.cancel(new_req)
                            committed = False
                        else:
                            if req.first_token_time is not None and \
                                    fr._first_token_time is None:
                                fr._first_token_time = req.first_token_time
                            fr._prior.extend(
                                int(t) for t in req.tokens)
                            fr._req = new_req
                            fr.replica_id = acc.rid
                            committed = True
            if committed is not None:
                self._settle_handoff(
                    donor, req, t0,
                    "adopted" if committed else "dropped")
                if committed:
                    acc.wake.set()
                return True
            if shipped or pbase <= 0:
                return None
            # adopt_handoff had a free slot but refused: the record
            # aliases a prefix span this acceptor's store does not
            # hold. Ship the row from the donor (the PR 11 affinity
            # transport — int8 codes as-is) and retry once.
            shipped = True
            if not self._ship_prefix(donor, acc, spec["prompt"], pbase):
                return None

    def _ship_prefix(self, donor, acc, prompt, pbase):
        """Move the aliased prefix row ahead of a handoff: the captured
        record's private plane only covers positions past ``pbase``, so
        the acceptor must hold the same prefix content to alias. The
        donor still holds the row — the migrating request's pin is not
        released until finish_handoff. Donor and acceptor locks taken
        SEQUENTIALLY, never nested (same rule as _maybe_adopt)."""
        toks = [int(t) for t in np.asarray(prompt).reshape(-1)[:pbase]]
        with donor.lock:
            if donor.failed:
                return False
            exported = donor.engine.export_prefix(toks)
        if exported is None:
            return False
        matched, prec = exported
        with acc.lock:
            if acc.failed:
                return False
            ok = acc.engine.adopt_prefix(matched, prec)
        if ok:
            key = "prefix/{}".format(next(self._flow_ids))
            donor.engine.tracer.instant(
                "prefix/ship_out", flow_out=key, tokens=len(matched),
                to_replica=acc.rid)
            acc.engine.tracer.instant(
                "prefix/ship_in", flow_in=key, tokens=len(matched),
                from_replica=donor.rid)
            if self._directory is not None:
                self._directory.add(acc.rid, matched)
        return ok

    def _settle_handoff(self, donor, req, t0, outcome):
        """Donor-side epilogue for one settled migration: forget the
        scheduler record and unpin the request's prefix row; a real
        adoption also observes the capture->adopt latency on the
        DONOR's histogram (the donor owns the migration's clock), a
        fallback counts on the donor's bank. Safe on a failed donor —
        everything here is host-side bookkeeping."""
        with donor.lock:
            donor.engine.finish_handoff(req)
            if outcome == "adopted":
                donor.engine._handoff_latency_hist.observe(
                    time.time() - t0)
            elif outcome == "fallback":
                donor.engine.counters["handoff_fallbacks"] += 1

    def _handoff_fallback(self, fr, donor, req, t0):
        """No decode-capable replica is alive: degrade every surviving
        prefill replica to effective-mixed (capture OFF — a re-prefilled
        stream must COMPLETE there, not bounce straight back into the
        pump) and re-prefill this stream through the normal orphan path
        on any survivor. Zero lost, bit-identical: the residual respec
        is exactly the failover snapshot."""
        for rep in self.replicas:
            if rep.alive and rep.engine.role == "prefill":
                with rep.lock:
                    rep.engine._handoff_enabled = False
        with self._lock:
            live = not (fr._cancelled or fr.done) and fr._req is req \
                and req.phase == "handoff"
            if live:
                fr._orphan()
                self._orphans.append(fr)
        if live:
            # The migration degraded into a re-prefill: open the arrow
            # the survivor's failover_in closes (same key _orphan
            # minted into the respec).
            donor.engine.tracer.instant(
                "request/handoff_fallback", tid=fr.trace.tid,
                fid=fr.fid, hop=fr.trace.hop(),
                flow_out=fr._respec["flow"])
        self._settle_handoff(donor, req, t0,
                             "fallback" if live else "dropped")
        self._pump()
        return True

    def _tick(self):
        # Non-blocking: whichever thread hits the window boundary first
        # closes it; everyone else skips rather than queueing up.
        closed = None
        if self._tick_lock.acquire(False):
            try:
                closed = self.collector.tick()
            finally:
                self._tick_lock.release()
        if closed is not None:
            # A window just closed — score the alert rules against it.
            # Outside the tick lock: evaluate() serializes on its own
            # lock and fires dump hooks, which must not block ticking.
            self.alerts.evaluate()

    # ------------------------------------------------------------- submit

    def _ordered(self, include_draining=False, match=None, role=None,
                 shallow=False):
        views = [rep for rep in self.replicas
                 if rep.alive and (rep.engine.health in
                                   ("healthy", "degraded")
                                   or include_draining)]
        # Role eligibility (disaggregated fleets): a view qualifies for
        # ``role`` work if it holds that role or is mixed. The router
        # SKIPS ineligible views before scoring — no score, no rng draw
        # — so role plumbing leaves an all-mixed fleet's seeded
        # tie-break sequence untouched (role=None passes no mask at
        # all, the historical call).
        eligible = None
        if role is not None:
            eligible = [rep.engine.role in (role, "mixed")
                        for rep in views]
        # Latency-class placement: restrict to the minimum queue depth
        # among the views still eligible — same SKIP mechanism as
        # roles, so untagged traffic's rng sequence is untouched.
        if shallow and views:
            depths = [rep.queue_depth for rep in views]
            base = eligible if eligible is not None \
                else [True] * len(views)
            pool = [d for d, e in zip(depths, base) if e]
            if pool:
                dmin = min(pool)
                eligible = [e and d <= dmin
                            for d, e in zip(depths, base)]
        if not match:
            return self.router.order(views, eligible=eligible)
        # Prefix affinity: matched depth over the prefix plane length,
        # zeroed below min_prefix_len (the acceptor's on_admit probe
        # would not alias a shorter span anyway). Scoring happens in
        # the router (score - AFFINITY_WEIGHT * affinity); dead stays
        # inf and breakers are still consulted per attempted candidate.
        plen = float(max(self.config.prefix_len, 1))
        minp = self.config.min_prefix_len
        affinity = []
        for rep in views:
            d = match.get(rep.rid, 0)
            affinity.append(min(d, plen) / plen if d >= minp else 0.0)
        return self.router.order(views, affinity, eligible=eligible)

    def _match_prefix(self, prompt):
        """Directory longest-match for one prompt: {replica_id: depth},
        or {} when affinity is off / the prompt is malformed (admission
        validation in engine.submit is the authority on that)."""
        if self._directory is None:
            return {}
        try:
            toks = [int(t) for t in np.asarray(prompt).reshape(-1)]
        except (TypeError, ValueError):
            return {}
        if not toks:
            return {}
        return self._directory.match(toks)

    def _maybe_adopt(self, rep, prompt, match):
        """Cross-replica plane adoption: the routed-to replica does not
        hold the prompt's best published prefix, so ship the planes
        from a holder instead of recomputing the prefill. Returns True
        when ``rep`` now holds a usable prefix.

        Locking: the donor's rep.lock and the acceptor's rep.lock are
        taken SEQUENTIALLY, never nested — two submits adopting in
        opposite directions must not deadlock. Both sides re-validate
        against their LIVE PrefixStore under their own lock (the
        directory is derived state; export_prefix returns None when the
        donor's row was evicted since publish, adopt_prefix refuses
        when the acceptor already covers the span)."""
        minp = self.config.min_prefix_len
        own = match.get(rep.rid, 0)
        best, donors = 0, []
        for rid, d in match.items():
            if rid == rep.rid:
                continue
            peer = self.replicas[rid]
            if not peer.alive:
                continue
            if d > best:
                best, donors = d, [peer]
            elif d == best and d > 0:
                donors.append(peer)
        if best < minp or best <= own:
            return own >= minp
        toks = [int(t) for t in np.asarray(prompt).reshape(-1)]
        exported = None
        for donor in sorted(donors, key=lambda r: r.rid):
            with donor.lock:
                if donor.failed:
                    continue
                exported = donor.engine.export_prefix(toks[:best])
            if exported is not None:
                break
        if exported is None:
            return own >= minp
        matched, record = exported
        with rep.lock:
            if rep.failed:
                return False
            ok = rep.engine.adopt_prefix(matched, record)
        if ok:
            key = "prefix/{}".format(next(self._flow_ids))
            donor.engine.tracer.instant(
                "prefix/ship_out", flow_out=key, tokens=len(matched),
                to_replica=rep.rid)
            rep.engine.tracer.instant(
                "prefix/ship_in", flow_in=key, tokens=len(matched),
                from_replica=donor.rid)
            self._directory.add(rep.rid, matched)
        return ok or own >= minp

    def submit(self, prompt, **kw):
        """Route one request to the best live replica; returns a
        FleetRequest. Tries replicas in router order — prefix affinity
        folded into the score when the fleet runs a prefix directory —
        consulting each breaker only at its attempt (allow() in open
        state IS the half-open probe — never burned on an untried
        candidate). A winning candidate that lacks the prompt's best
        published prefix adopts the holder's planes first
        (_maybe_adopt), so even cold replicas serve template traffic
        without re-prefilling it. Raises the fleet-level analogue of
        the engine's admission errors: QueueFull (structured: summed
        queue_depth, MIN retry_after across shed hints and open
        breakers, replica_id=None) when every candidate rejected;
        EngineDraining when every live replica has admissions closed;
        EngineDeadError when the whole fleet is dead."""
        if self._closed:
            raise RuntimeError("submit() on a closed fleet")
        if self._orphans:
            self._pump()
        # fid and trace context are allocated BEFORE placement so the
        # routing decision itself lands on the request's track. The
        # front door passes the context it minted (kw["trace"]); a bare
        # fleet submission gets a fleet-origin one (tid = base + fid).
        fid = next(self._fids)
        ctx = kw.pop("trace", None)
        if ctx is None:
            ctx = TraceContext(FLEET_TID_BASE + fid, origin="fleet")
        kw["trace"] = ctx
        match = self._match_prefix(prompt)
        role = "prefill" if self._disagg else None
        shallow = kw.get("priority") in self._latency_classes
        candidates = self._ordered(match=match, role=role, shallow=shallow)
        if not candidates and role is not None:
            # Every prefill-capable replica is gone: route to ANY
            # survivor — zero-lost beats role purity (a decode-role
            # survivor completes the stream locally; it never captures).
            candidates = self._ordered(match=match)
        if not candidates:
            if any(rep.alive for rep in self.replicas):
                raise EngineDraining(
                    "fleet: every live replica is draining — admissions "
                    "reopen after undrain_all()/rolling_drain()")
            raise EngineDeadError("fleet: every replica is dead")
        depth = 0
        hints = []
        for rep in candidates:
            if not rep.breaker.allow():
                hints.append(rep.breaker.retry_after_s())
                continue
            affine = bool(match) and self._maybe_adopt(rep, prompt, match)
            with rep.lock:
                if rep.failed:
                    continue
                try:
                    req = rep.engine.submit(prompt, **kw)
                except QueueFull as e:
                    rep.breaker.record_failure(e.retry_after_s)
                    depth += e.queue_depth or 0
                    if e.retry_after_s is not None:
                        hints.append(e.retry_after_s)
                    continue
                except (EngineDraining, EngineDeadError):
                    continue
                rep.breaker.record_success()
                if affine:
                    rep.engine.counters["affinity_routed"] += 1
                with self._lock:
                    fr = FleetRequest(fid, rep.rid, req)
                    self._requests[fr.fid] = fr
            # Routing evidence on the fleet plane: which replica won,
            # what the router saw. The per-replica score inputs are the
            # live gauges — copy the winner's so the autopsy shows the
            # decision-time facts, not a later scrape.
            self.tracer.instant(
                "request/routed", tid=ctx.tid, hop=ctx.hop(),
                fid=fid, replica=rep.rid,
                queue_depth=int(rep.queue_depth),
                slot_occupancy=round(float(rep.slot_occupancy), 4),
                affinity=bool(affine), shallow=bool(shallow),
                role=role or "any")
            rep.wake.set()
            return fr
        # MIN across per-replica hints (each already class-aware — the
        # engines stamped the submitting class's own completions rate),
        # clamped to the same ceiling a single scheduler enforces:
        # breaker backoff hints are arbitrary floats and must not leak
        # an unclamped wait upstream. priority/tenant ride the fleet
        # error so the front door's per-class payload survives routing.
        retry = min(hints) if hints else None
        if retry is not None:
            retry = round(min(max(retry, 0.0), RETRY_AFTER_CAP_S), 4)
        raise QueueFull(
            "fleet: all {} candidate replica(s) rejected the request "
            "(open breaker or full queue){}".format(
                len(candidates),
                "" if retry is None else
                " (retry_after_s hint: {})".format(retry)),
            queue_depth=depth, retry_after_s=retry, replica_id=None,
            priority=kw.get("priority"), tenant=kw.get("tenant"),
            reason="queue_full")

    # --------------------------------------------------------- preemption

    def preempt(self, fr):
        """Park ``fr`` on its owning replica (engine.preempt: swapped
        phase + hold) — the fleet half of front-door priority
        preemption. Returns False when the request is not parkable
        right now (mid-failover, wrong phase, owner dead, or no swap
        room); retries internally if a failover moves it between the
        ownership read and the replica lock, exactly like cancel()."""
        while True:
            rep_id = fr.replica_id
            if rep_id is None:
                return False  # mid-failover; replay re-queues it anyway
            rep = self.replicas[rep_id]
            with rep.lock:
                if fr.replica_id != rep_id or fr._req is None:
                    continue  # failover moved it — retry
                if not rep.alive:
                    return False
                return rep.engine.preempt(fr._req)

    def release_preempted(self, fr):
        """Lift the preemption hold on ``fr`` so its replica's
        resume-first swap-in can pick it back up. Returns False when
        the request is mid-failover or its owner died (the hold died
        with the engine's ledgers — replay re-queues the stream)."""
        while True:
            rep_id = fr.replica_id
            if rep_id is None:
                return False
            rep = self.replicas[rep_id]
            with rep.lock:
                if fr.replica_id != rep_id or fr._req is None:
                    continue
                if not rep.alive:
                    return False
                rep.engine.release_preempted(fr._req)
            rep.wake.set()
            return True

    # ------------------------------------------------------------ harvest

    def harvest(self):
        """Completed FleetRequests not yet harvested, completion order.
        Harvested handles leave the fleet's table (bounded bookkeeping —
        the caller's reference is the remaining owner); unfinished
        requests stay tracked for failover."""
        with self._lock:
            done = [fr for fr in self._requests.values() if fr.done]
            for fr in done:
                del self._requests[fr.fid]
        return sorted(done, key=lambda fr: fr.finish_time or 0.0)

    # ------------------------------------------------------------- cancel

    def cancel(self, fr):
        """Cancel wherever the request lives RIGHT NOW: on its owning
        replica (engine.cancel — device-side slot freeze included), on
        a DEAD replica's scheduler (host-side only: the dead pool's
        buffers were donated away and must not be touched), or in the
        orphan list mid-failover. Returns False when it had already
        finished. Retries internally if a failover moves the request
        between the ownership read and the replica lock."""
        while True:
            rep_id = fr.replica_id
            if rep_id is None:
                with self._lock:
                    if fr.done:
                        return False
                    if fr.replica_id is not None:
                        continue  # resubmitted between read and lock
                    if fr in self._orphans:
                        self._orphans.remove(fr)
                    fr._mark_cancelled(time.time())
                    return True
            rep = self.replicas[rep_id]
            with rep.lock:
                if fr.replica_id != rep_id or fr._req is None:
                    continue  # failover moved it — retry
                if rep.alive:
                    if fr._req.phase == "handoff":
                        # Mid-migration: serialize with the pump's
                        # commit (self._lock nests under rep.lock —
                        # the allowed order). Either we cancel first
                        # and the pump's re-check aborts the adopted
                        # copy, or the pump committed first and the
                        # ownership re-read sends us to the acceptor.
                        with self._lock:
                            if fr.replica_id != rep_id:
                                continue  # pump won — retry there
                            return rep.engine.cancel(fr._req)
                    return rep.engine.cancel(fr._req)
                # Dead owner, failover not yet run: host-side cancel
                # only (the scheduler record is durable; the pool is
                # gone) — _failover skips finished records.
                return rep.engine._scheduler.cancel(fr._req)

    # ----------------------------------------------------------- failover

    def _failover(self, rep, exc):
        """Move every live request off a failed replica. The records
        are durable host-side state (crash-only: PR 7) — each snapshots
        its residual resubmission spec and joins the orphan list; then
        one pump pass tries to place them immediately."""
        with rep.lock:
            with self._lock:
                if rep.failed:
                    return
                rep.failed = True
                moved = [fr for fr in self._requests.values()
                         if fr.replica_id == rep.rid and not fr.done]
                for fr in moved:
                    fr._orphan()
                    # The dead owner's last word on this stream: a
                    # host-side instant on ITS ring (the ring outlives
                    # the pool) opening the failover arrow the
                    # survivor's failover_in closes.
                    rep.engine.tracer.instant(
                        "request/failover_out", tid=fr.trace.tid,
                        fid=fr.fid, hop=fr.trace.hop(),
                        flow_out=fr._respec["flow"],
                        tokens_emitted=len(fr._prior),
                        error=type(exc).__name__)
                self._orphans.extend(moved)
                self.failovers += len(moved)
                if self._directory is not None:
                    # The dead pool's planes are gone — no adoption or
                    # affinity may ever point at them again. (Leaf
                    # lock: safe under rep.lock + self._lock.)
                    self._directory.invalidate(rep.rid)
        logger.warning(
            "fleet: replica %d is dead (%s: %s) — failing over %d live "
            "request(s) to survivors", rep.rid, type(exc).__name__, exc,
            len(moved))
        self._auto_dump("replica_death:{}".format(rep.rid))
        self._pump()

    def _pump(self):
        """Place orphaned requests on survivors. Atomically claims the
        orphan list (so concurrent pumps from several replica threads
        never double-submit one request), tries each orphan against
        router-ordered survivors, and re-queues what still doesn't fit
        — ``idle`` stays False until the list empties."""
        with self._lock:
            orphans, self._orphans = self._orphans, []
        if not orphans:
            return
        remaining = []
        for fr in orphans:
            if fr._cancelled or not self._place_orphan(fr):
                if not fr._cancelled:
                    remaining.append(fr)
        if remaining:
            with self._lock:
                self._orphans.extend(remaining)

    def _place_orphan(self, fr):
        """One placement attempt across router-ordered survivors —
        DRAINING replicas included (accepted is a promise; a drain
        finishes accepted work, and failover work was accepted by the
        fleet). Submission goes straight to the survivor's scheduler:
        health-gated admission and shape validation were already passed
        at original submit, and the residual request can only be
        shorter. Breakers are not consulted — an open breaker means
        sheds, and the scheduler's QueueFull tells us that directly."""
        spec = fr._respec
        for rep in self._ordered(include_draining=True):
            with rep.lock:
                if rep.failed:
                    continue
                try:
                    req = rep.engine._scheduler.submit(
                        spec["prompt"], spec["max_new_tokens"],
                        spec["temperature"], spec["top_k"],
                        spec["eos_token_id"], spec["seed"],
                        spec=spec["spec"], deadline=spec["deadline"],
                        priority=spec.get("priority"),
                        tenant=spec.get("tenant"),
                        trace=spec.get("trace"))
                except QueueFull:
                    continue
                # Close the failover arrow on the survivor's ring —
                # the flow key pairs with the dead owner's
                # failover_out (or the fallback's handoff_fallback).
                rep.engine.tracer.instant(
                    "request/failover_in", tid=req.trace.tid,
                    fid=fr.fid, hop=req.trace.hop(),
                    flow_in=spec.get("flow"), replica=rep.rid,
                    budget_left=int(spec["max_new_tokens"]))
                with self._lock:
                    fr._req = req
                    fr.replica_id = rep.rid
            rep.wake.set()
            logger.info("fleet: request %d failed over to replica %d "
                        "(%d tokens emitted, %d budget left)", fr.fid,
                        rep.rid, len(fr._prior), spec["max_new_tokens"])
            return True
        return False

    # ------------------------------------------------------------ driving

    def step(self):
        """One fleet 'step' for single-threaded drivers (the loadgen
        runner, start=False tests): pump orphans, then either yield to
        the stepping threads (started fleets) or step each replica
        inline round-robin. Completions are read back through the
        FleetRequest handles / harvest(), so this returns []."""
        if self._orphans:
            self._pump()
        if self._handoffs.pending:
            self._pump_handoffs()
        if self._started:
            time.sleep(self._poll_s)
            self._tick()
            return []
        for rep in self.replicas:
            self._step_replica(rep)
        self._tick()
        return []

    @property
    def idle(self):
        """True when nothing is queued, running, orphaned, or
        mid-handoff anywhere — dead replicas excluded (their live work
        was failed over; what remains in their schedulers is
        history)."""
        if self._orphans or self._handoffs.pending:
            return False
        return all(rep.failed or rep.engine.idle for rep in self.replicas)

    def _wait(self, pred, timeout_s):
        t0 = time.time()
        while not pred():
            if self._started:
                if self._orphans:
                    self._pump()
                if self._handoffs.pending:
                    self._pump_handoffs()
                time.sleep(self._poll_s)
            else:
                self.step()
            if timeout_s is not None and time.time() - t0 >= timeout_s:
                return False
        return True

    def wait_idle(self, timeout_s=None):
        """Block until the fleet settles idle (or timeout; returns
        whether it did). With stepping threads this is a pure wait; on
        a start=False fleet it drives step() itself."""
        return self._wait(lambda: self.idle, timeout_s)

    # -------------------------------------------------------------- drain

    def drain(self, timeout_s=None):
        """Fleet-wide graceful drain: close admissions on every live
        replica (no stepping here — the replica threads finish the
        in-flight work, failover orphans included), settle idle, and
        return the completed requests (harvest()). Admissions STAY
        closed; ``undrain_all()`` reopens."""
        for rep in self.replicas:
            if rep.alive:
                with rep.lock:
                    if rep.engine.health in ("healthy", "degraded"):
                        rep.engine.close_admissions()
        self._wait(lambda: self.idle, timeout_s)
        return self.harvest()

    def undrain_all(self):
        """Reopen admissions on every drained (live) replica."""
        for rep in self.replicas:
            if rep.alive:
                with rep.lock:
                    if rep.engine.health == "draining":
                        rep.engine.undrain()

    def drain_headroom(self, rep):
        """Can the OTHERS absorb ``rep``'s load if it leaves rotation?
        Two pieces of evidence, both must pass: live spare capacity
        (survivors' free slots + free queue positions vs the draining
        replica's in-flight count) and the timeseries window (the
        survivors' queue depth at the last window close must sit below
        half their combined queue capacity — a fleet already backed up
        has no drain headroom even if this instant looks clear)."""
        others = [r for r in self.replicas
                  if r is not rep and r.alive
                  and r.engine.health in ("healthy", "degraded")]
        spare = sum(
            (r.engine.config.max_slots
             - len(r.engine._scheduler.running))
            + (r.engine.config.max_queue - len(r.engine._scheduler.queue))
            for r in others)
        inflight = (len(rep.engine._scheduler.running)
                    + len(rep.engine._scheduler.queue))
        queue_cap = sum(r.engine.config.max_queue for r in others)
        # Force-close the current window so the check reads NOW, not
        # up-to-window_seconds-stale state.
        with self._tick_lock:
            windowed = self.collector.sample()["metrics"]
        window_queue = sum(
            v for k, v in windowed.items()
            if k.startswith("queue_depth{")
            and "replica={}".format(rep.rid) not in k
            and isinstance(v, (int, float)))
        ok = (bool(others) and spare >= inflight
              and window_queue <= queue_cap / 2.0)
        return ok, {
            "survivors": [r.rid for r in others],
            "spare_capacity": spare,
            "in_flight": inflight,
            "windowed_survivor_queue": window_queue,
            "survivor_queue_cap": queue_cap,
        }

    def rolling_drain(self, timeout_s=30.0, require_headroom=True):
        """Rolling restart support: one replica at a time — verify SLO
        headroom (drain_headroom), close its admissions, let its thread
        finish the in-flight work, reopen, move on. A replica with no
        headroom is SKIPPED, not forced (report says why); dead
        replicas are skipped. Returns one report dict per replica."""
        report = []
        for rep in self.replicas:
            if not rep.alive:
                report.append({"replica": rep.rid, "drained": False,
                               "skipped": "dead"})
                continue
            ok, detail = self.drain_headroom(rep)
            if require_headroom and not ok:
                report.append({"replica": rep.rid, "drained": False,
                               "skipped": "no_headroom",
                               "headroom": detail})
                continue
            with rep.lock:
                rep.engine.close_admissions()
            drained = self._wait(
                lambda: rep.failed or rep.engine.idle, timeout_s)
            with rep.lock:
                if rep.alive and rep.engine.health == "draining":
                    rep.engine.undrain()
            report.append({"replica": rep.rid,
                           "drained": drained and rep.alive,
                           "headroom": detail})
        return report

    # -------------------------------------------------------------- chaos

    def inject_faults(self, plan, replica=0):
        """Arm a FaultPlan on ONE replica (chaos: kill replica
        ``replica`` mid-run while the fleet keeps serving). Same
        contract as engine.inject_faults — requires
        ``fault_injection=True`` in the shared config."""
        rep = self.replicas[replica]
        with rep.lock:
            return rep.engine.inject_faults(plan)

    @property
    def recovery_log(self):
        """Every replica's recovery records merged in time order, each
        stamped with its replica id — the loadgen runner's chaos
        windows read this exactly like a single engine's log."""
        out = []
        for rep in self.replicas:
            for rec in rep.engine.recovery_log:
                d = dict(rec)
                d["replica"] = rep.rid
                out.append(d)
        out.sort(key=lambda d: d["t_start"])
        return out

    # ------------------------------------------------------------ metrics

    @property
    def health(self):
        """Fleet health = the best any replica offers: one healthy
        accepting replica makes a healthy fleet (that IS the point of
        replication); degraded-only -> degraded; live-but-closed ->
        draining; nobody left -> dead."""
        states = [rep.engine.health if not rep.failed else "dead"
                  for rep in self.replicas]
        for s in ("healthy", "degraded", "draining"):
            if s in states:
                return s
        return "dead"

    def metrics(self, reset=False):
        """Aggregated fleet view + per-replica engine metrics. NOTE:
        ``reset=True`` forwards to every engine and so touches the same
        windows the fleet's TimeseriesCollector owns — same single-
        window-owner caveat as a lone engine (telemetry/timeseries.py).

        The aggregate counters window against the FLEET's own base (a
        cumulative read minus the snapshot taken at the last
        ``metrics(reset=True)``), never against the per-engine counter
        windows — those belong to the collector and are clobbered on
        every tick. Two successive metrics(reset=True) calls therefore
        bracket exactly the work between them (how a caller scrubs
        warmup), fleet and single-engine runs alike; with no reset the
        values are since-construction, including dead replicas'
        history."""
        per_replica = {rep.rid: rep.engine.metrics(reset=reset)
                       for rep in self.replicas}
        agg = {}
        for name in ("tokens_out", "requests_completed", "recoveries",
                     "requests_replayed", "deadline_sheds", "step_stalls",
                     "faults_injected", "prefix_hits", "prefix_misses",
                     "prefix_adoptions", "prefix_bytes_shipped",
                     "affinity_routed", "handoffs", "handoffs_in",
                     "handoff_fallbacks", "handoff_bytes_shipped",
                     "preemptions", "preempt_resumes"):
            if name in self.counters:
                total = self.counters[name]
                agg[name] = total - self._agg_base.get(name, 0)
                if reset:
                    self._agg_base[name] = total
        agg.update({
            "n_replicas": len(self.replicas),
            "alive": sum(1 for rep in self.replicas if rep.alive),
            "health": self.health,
            "failovers": self.failovers,
            "orphans": len(self._orphans),
            "roles": {rep.rid: rep.engine.role for rep in self.replicas},
            "pending_handoffs": len(self._handoffs.pending),
            "breaker_states": {rep.rid: rep.breaker.state
                               for rep in self.replicas},
        })
        if self._directory is not None:
            agg["prefix_directory"] = self._directory.snapshot()
            agg["prefix_hit_rate"] = self.prefix_hit_rate()
        agg["alerts_firing"] = sorted(self.alerts.firing())
        agg["alerts_fired"] = len(self.alerts.fired())
        return {"fleet": agg, "replicas": per_replica}

    def perf_xray(self):
        """Per-replica ``perf_xray`` sections (engine.perf_xray()),
        keyed by rid — the fleet face of the compiled-program
        observatory. The roofline/HBM GAUGES already flow through the
        merged registry with ``replica`` labels; this is the artifact-
        shaped view the regression gate consumes (tests only). Replicas
        with perf_xray off (or failed) contribute None."""
        out = {}
        for rep in self.replicas:
            try:
                out[rep.rid] = (rep.engine.perf_xray()
                                if not rep.failed else None)
            except Exception as e:
                logger.warning("fleet: perf_xray on replica %d failed "
                               "(%s)", rep.rid, e)
                out[rep.rid] = None
        return out

    def prefix_hit_rate(self):
        """Fleet-wide prefix hit rate (hits / probes, 0.0 when no
        probes) — what ``metrics()`` reports as ``prefix_hit_rate``."""
        c = self.counters
        hits = c["prefix_hits"] if "prefix_hits" in c else 0
        misses = c["prefix_misses"] if "prefix_misses" in c else 0
        total = hits + misses
        return hits / total if total else 0.0

    def prometheus(self):
        """One text-exposition snapshot of the WHOLE fleet: the merged
        registry exports every replica's series side by side, each
        carrying its ``replica`` label, plus the alert manager's own
        registry (``alerts_firing``, ``alerts_fired_total``, per-rule
        ``alert_active``) — one scrape covers serving AND paging."""
        return (prometheus_text(self.telemetry)
                + prometheus_text(self.alerts.telemetry))

    # ------------------------------------------------------------- tracing

    def trace_recorders(self):
        """Every ring a fleet request may have stamped, labelled:
        ``fleet`` (routing / failover plane) plus each replica's
        engine ring. The recorder set explain()/write_trace()/the
        auto-dump all read."""
        recs = {"fleet": self.tracer}
        for rep in self.replicas:
            recs.update(rep.engine.trace_recorders())
        return recs

    def write_trace(self, path):
        """Merge every ring into ONE Perfetto-loadable trace: each ring
        becomes its own process row (re-anchored to a shared epoch),
        flow arrows bind the cross-replica hops (handoff donor ->
        acceptor, failover dead owner -> survivor, prefix ship), and
        the collector's windowed counters ride along as counter
        tracks."""
        if isinstance(self.tracer, NullRecorder):
            raise RuntimeError("telemetry is disabled: no trace to write")
        return write_merged_trace(
            path, self.trace_recorders(),
            extra_events=self.collector.chrome_counter_events())

    def _resolve_tid(self, fr_or_fid):
        with self._lock:
            if isinstance(fr_or_fid, FleetRequest):
                return fr_or_fid.trace.tid
            fr = self._requests.get(fr_or_fid)
        if fr is None:
            raise KeyError("unknown fleet request: {!r}".format(fr_or_fid))
        return fr.trace.tid

    def explain(self, fr_or_fid):
        """Structured autopsy of one request (telemetry/autopsy.py):
        the hop-ordered timeline across every ring it touched, the
        admission/routing evidence at decision time, and the terminal
        cause. Accepts the FleetRequest handle or its fid (handles of
        harvested requests keep working — the rings remember them)."""
        if isinstance(self.tracer, NullRecorder):
            raise RuntimeError(
                "telemetry is disabled: no trace to explain")
        return build_autopsy(self.trace_recorders(),
                             self._resolve_tid(fr_or_fid))

    def _auto_dump(self, cause):
        """Evidence-on-disk hook for a firing alert or a replica death:
        write the merged trace plus the worst-K request autopsies into
        ``dump_dir`` and record the dump in ``self.dumps``. No-op
        without a dump_dir or with telemetry off; never raises (the
        serving loop must not die of its own black box)."""
        if self._dump_dir is None or isinstance(self.tracer, NullRecorder):
            return None
        try:
            n = len(self.dumps)
            stem = "dump{:03d}_{}".format(
                n, "".join(ch if ch.isalnum() else "_"
                           for ch in str(cause)))
            trace_path = os.path.join(self._dump_dir, stem + ".trace.json")
            self.write_trace(trace_path)
            with self._lock:
                frs = list(self._requests.values())
            recs = self.trace_recorders()
            autopsies = [build_autopsy(recs, fr.trace.tid) for fr in frs]
            worst = worst_requests(autopsies, k=4)
            autopsy_path = os.path.join(
                self._dump_dir, stem + ".autopsies.json")
            with open(autopsy_path, "w") as f:
                json.dump({"cause": str(cause),
                           "firing": self.alerts.firing(),
                           "worst_requests": worst}, f, indent=1)
            record = {"cause": str(cause), "trace": trace_path,
                      "autopsies": autopsy_path, "requests": len(worst)}
            self.dumps.append(record)
            logger.warning("fleet: auto-dump (%s) -> %s", cause,
                           trace_path)
            return record
        except Exception:  # noqa: BLE001 — the black box must never
            # take down the serving loop that feeds it.
            logger.exception("fleet: auto-dump failed (%s)", cause)
            return None

    @property
    def param_devices(self):
        """The device each replica's parameters actually live on, read
        back from the arrays (not from the placement plan) — what a
        multi-chip smoke checks: one replica per chip."""
        return [next(iter(jax.tree_util.tree_leaves(
            rep.engine._params)[0].devices())) for rep in self.replicas]

    @property
    def compile_counts(self):
        """Per-replica compiled-program counts — what the failover
        invariant pins: killing replica K must leave every other
        entry unchanged."""
        return {rep.rid: rep.engine.compile_count
                for rep in self.replicas}

    # ------------------------------------------------------------ teardown

    def close(self, timeout_s=5.0):
        """Stop and JOIN every replica thread, stop every watchdog.
        Idempotent; a closed fleet still reads (metrics, harvest) but
        never steps or submits again. __del__ calls this so interpreter
        exit never hangs on a fleet the test forgot."""
        # Flag flip under the lock (close() is reachable from any thread
        # via __del__ / GC); the joins below run OUTSIDE it — replica
        # threads take self._lock in _pump, so holding it across join()
        # would deadlock the drain.
        with self._lock:
            if self._closed:
                return
            self._closed = True
        for rep in self.replicas:
            rep.stop.set()
            rep.wake.set()
        for rep in self.replicas:
            if rep.thread is not None:
                rep.thread.join(timeout=timeout_s)
        for rep in self.replicas:
            rep.engine.close()

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
