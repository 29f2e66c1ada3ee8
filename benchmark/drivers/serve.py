"""Driver of the ``serve`` kind: one ``deepspeed.init_inference`` engine under
a closed or an open loop, from one thread.

The engine is single-threaded by contract, so the host can submit only
between steps; how late that makes the generator is measured, not hidden.
Every time is the benchmark's own: a request's clock starts when it was DUE
(open loop) or handed over (closed loop), a token's time is the end of the
``engine.step()`` that delivered it to the host.

Closed loop (``"loop": "closed"``): ``clients`` callers, each sends its next
request when its last one ends. Warm-up runs until every slot decodes.
Open loop (``"loop": "open"``): arrivals at the fixed ``rate`` of the mix for
``warmup_s`` seconds before the window, through it, and to its end;
``attempted`` are the requests due up to ``drain_s`` before the end.
"""

import itertools
import time

import numpy as np

from benchmark import traffic
from benchmark.harness import median_chunk_rate, note, span

# A served token is right when the float32 reference, teacher-forced on the
# served stream, prefers no other token by more than this many logits. The
# engine computes in bf16: two logits closer than its rounding (measured up
# to 0.03 over a thousand positions) may fall either way, a wrong token is
# off by the spread of the logits (about 0.6 at random weights).
TOKEN_MARGIN_TOL = 0.1
CHECKED_REQUESTS = 4
# Streams of the one seed.
WARMUP, WINDOW, TAIL = 0, 1, 2


class Flight(object):
    """One request as the benchmark sees it."""

    __slots__ = ("prompt", "max_new", "due", "submitted", "first", "finish",
                 "handle", "seen")

    def __init__(self, prompt, max_new, due):
        self.prompt, self.max_new, self.due = prompt, max_new, due
        self.submitted = self.first = self.finish = self.handle = None
        self.seen = 0


class Loop(object):
    """The serving loop and its records: steps, tokens and request times."""

    def __init__(self, engine):
        self.engine = engine
        self.flying = []
        self.done = []
        self.refused = 0
        self.steps = []      # (start, end, tokens delivered, active slots)
        self.context = []    # per step: context lengths of decoding slots

    def submit(self, flight, now):
        from deepspeed_tpu.inference.scheduler import QueueFull

        flight.submitted = now
        try:
            flight.handle = self.engine.submit(flight.prompt,
                                               max_new_tokens=flight.max_new)
        except QueueFull:
            self.refused += 1
            flight.finish = now
            self.done.append(flight)
            return
        self.flying.append(flight)

    def step(self):
        # What the decode kernel must read in this step, from the
        # benchmark's own bookkeeping: the context of each decoding slot.
        self.context.append([len(f.prompt) + f.seen for f in self.flying
                             if f.seen])
        with span("bench/step"):
            start = time.perf_counter()
            self.engine.step()
            end = time.perf_counter()
        delivered, still = 0, []
        for f in self.flying:
            n = len(f.handle.tokens)
            delivered += n - f.seen
            if n and f.first is None:
                f.first = end
            f.seen = n
            if f.handle.done:
                f.finish = end
                self.done.append(f)
            else:
                still.append(f)
        self.steps.append((start, end, delivered, len(self.flying)))
        self.flying = still


def _closed(loop, source, clients, stop):
    """``clients`` callers, each taking its next request from ``source``
    when its last one has ended."""
    while not stop():
        with span("bench/refill"):
            now = time.perf_counter()
            while len(loop.flying) < clients:
                prompt, max_new = next(source)
                loop.submit(Flight(prompt, max_new, now), now)
        loop.step()


def _open(loop, schedule, stop):
    """Arrivals on a schedule of (due, prompt, max_new), whatever the
    engine does."""
    i = 0
    while not stop():
        with span("bench/submit"):
            now = time.perf_counter()
            while i < len(schedule) and schedule[i][0] <= now:
                due, prompt, max_new = schedule[i]
                i += 1
                loop.submit(Flight(prompt, max_new, due), now)
        if loop.flying:
            loop.step()
        elif i < len(schedule):
            with span("bench/wait_arrival"):
                time.sleep(max(0.0, min(schedule[i][0] - time.perf_counter(),
                                        0.05)))
        else:
            with span("bench/wait_arrival"):
                time.sleep(0.01)


def _warm_admission_shapes(slots):
    """The engine pins the frontier of the slots it admits in one round with
    an eager scatter (``inference/engine.py`` ``_admit``), whose shapes, and
    so whose tiny programs, depend on HOW MANY it admits: 1 to ``slots``.
    Traffic decides that number, so the window would compile. Until the
    program does this in one program of fixed shape, the same expression on
    a stand-in array warms every count (PERF.md, Open questions)."""
    import jax.numpy as jnp

    pos = jnp.zeros((slots,), jnp.int32)
    for k in range(1, slots + 1):
        idx = jnp.asarray(list(range(k)), jnp.int32)
        cur = jnp.asarray([0] * k, jnp.int32)
        pos.at[idx].set(cur).block_until_ready()


def _schedule(run, mix, vocab, stream, start, seconds):
    due = start + traffic.arrivals(run.seed, stream, seconds, mix)
    reqs = traffic.requests(run.seed, stream, len(due), mix, vocab)
    return [(float(t), p, o) for t, (p, o) in zip(due, reqs)]


def _token_margins(model, params, flights, width):
    """Teacher forcing through the reference: for every served token of
    every flight, by how many logits the reference prefers its own argmax
    (0 = agrees). One batch padded to ``width``, the engine's ``max_len``,
    so that every run uses the one cached program: a causal model's earlier
    positions do not see the padding."""
    import jax.numpy as jnp

    seqs = [np.concatenate([f.prompt, np.asarray(f.handle.tokens, np.int32)])
            for f in flights]
    ids = np.zeros((len(seqs), width), np.int32)
    for row, s in zip(ids, seqs):
        row[:len(s)] = s
    logits = model.reference_logits(params, jnp.asarray(ids))
    worst = []
    for k, f in enumerate(flights):
        p, n = len(f.prompt), len(f.handle.tokens)
        lg = logits[k, p - 1:p - 1 + n]
        picked = jnp.take_along_axis(
            lg, jnp.asarray(f.handle.tokens, jnp.int32)[:, None], axis=1)
        worst.append(float(jnp.max(jnp.max(lg, axis=1) - picked[:, 0])))
    return worst


def run(run):
    import deepspeed_tpu as deepspeed

    config, mix = run.cell.config, run.cell.traffic
    model = run.model
    vocab = model.vocab_size
    note(event="sizes", engine=mix["engine"], loop=mix["loop"],
         **model.sizes())
    params = model.init_params(run.seed)
    engine = deepspeed.init_inference(
        model=model.module, params=params,
        config={"inference": dict(mix["engine"])})
    loop = Loop(engine)
    slots = int(mix["engine"]["max_slots"])
    closed = mix["loop"] == "closed"
    if closed:
        clients = int(mix["clients"])
        source = itertools.cycle(traffic.requests(
            run.seed, WINDOW, int(mix["request_pool"]), mix, vocab))

    _warm_admission_shapes(slots)
    # Warm-up: the one program compiles on the first step; then the loop
    # runs until it is in the state the window is meant to measure.
    if closed:
        want = min(slots, clients)
        _closed(loop, source, clients, lambda: len(loop.steps) >= 3 * slots
                or sum(1 for f in loop.flying if f.seen) >= want)
    else:
        first = Flight(*traffic.requests(run.seed, WARMUP, 1, mix, vocab)[0],
                       due=time.perf_counter())
        loop.submit(first, first.due)
        while loop.flying:
            loop.step()
        t_warm = time.perf_counter()
        warm = _schedule(run, mix, vocab, WARMUP, t_warm, mix["warmup_s"])
        _open(loop, warm, lambda: time.perf_counter() - t_warm
              >= mix["warmup_s"])
    compiles_at_open = engine.compile_count
    note(event="warmup", steps=len(loop.steps),
         compile_count=compiles_at_open, in_flight=len(loop.flying))

    run.window_opens()
    t0 = time.perf_counter()
    t_end = t0 + run.seconds
    first_step = len(loop.steps)
    slot_steps0 = (engine.counters["occupied_slot_steps"],
                   engine.counters["slot_steps"])
    if closed:
        _closed(loop, source, clients, lambda: time.perf_counter() >= t_end)
    else:
        _open(loop, _schedule(run, mix, vocab, WINDOW, t0, run.seconds),
              lambda: time.perf_counter() >= t_end)
    run.window_closes()
    last_step = len(loop.steps)
    # Freeze the window's view before the traced tail moves anything on.
    window_done = list(loop.done)
    unfinished = list(loop.flying)
    compiles_in_window = engine.compile_count - compiles_at_open
    # The engine's own count, from the harvest it already makes: the share
    # of (decode iteration x slot) places that emitted a token.
    occupancy = 100.0 * (
        engine.counters["occupied_slot_steps"] - slot_steps0[0]) / max(
        engine.counters["slot_steps"] - slot_steps0[1], 1)

    trace_first = last_step
    if run.trace:
        n_trace = int(mix["trace_steps"])
        with run.traced():
            stop = lambda: len(loop.steps) - trace_first >= n_trace  # noqa
            if closed:
                _closed(loop, source, clients, stop)
            else:
                t1 = time.perf_counter()
                _open(loop, _schedule(run, mix, vocab, TAIL, t1,
                                      mix["trace_tail_s"]),
                      lambda: stop() or time.perf_counter() - t1
                      >= mix["trace_tail_s"])

    steps = [s for s in loop.steps[first_step:last_step] if s[1] <= t_end]
    in_window = [f for f in window_done + unfinished
                 if f.due is not None and t0 <= f.due]
    finished = [f for f in window_done if f.handle is not None
                and f.finish is not None and t0 <= f.finish <= t_end]
    values, counters = {}, {}
    if closed:
        # Tokens delivered between step boundaries inside the window, over
        # the time between them: the median over chunks of steps.
        span_s = steps[-1][1] - steps[0][1]
        tokens = sum(s[2] for s in steps[1:])
        values["serve_tok_s"], chunks = median_chunk_rate(
            [s[1] for s in steps], [s[2] for s in steps],
            int(mix["rate_chunk_steps"]))
        attempted = len(finished) + loop.refused
        wrong_len = [f for f in finished
                     if len(f.handle.tokens) != f.max_new]
        failed = loop.refused + len(wrong_len)
        counters.update(window_tokens=tokens, boundary_span_s=span_s,
                        rate_chunks=chunks,
                        whole_window_tok_s=tokens / span_s)
    else:
        due = [f for f in in_window
               if f.due <= t_end - float(mix["drain_s"])]
        ok = [f for f in due if f.handle is not None and f.finish is not None
              and f.finish <= t_end and len(f.handle.tokens) == f.max_new]
        attempted, failed = len(due), len(due) - len(ok)
        ttft = [(f.first - f.due) * 1e3 for f in due if f.first is not None]
        timed = [f for f in finished
                 if f.first >= t0 and len(f.handle.tokens) > 1]
        # A request whose tokens all reached the host in one step (outputs
        # up to about chunk_size) has no gap to measure: counted, not 0.
        tpot = [(f.finish - f.first) / (len(f.handle.tokens) - 1) * 1e3
                for f in timed if f.finish > f.first]
        late = [(f.submitted - f.due) * 1e3 for f in in_window]
        if ttft:
            values["serve_ttft_p50_ms"] = float(np.median(ttft))
        if tpot:
            values["serve_tpot_p50_ms"] = float(np.median(tpot))
        if late:
            values["gen_late_p50_ms"] = float(np.median(late))
            values["gen_late_max_ms"] = float(np.max(late))
        counters.update(ttft_samples=len(ttft), tpot_samples=len(tpot),
                        tpot_one_step=len(timed) - len(tpot),
                        offered=len(in_window))
    values["engine_step_ms"] = float(np.median(
        [(s[1] - s[0]) * 1e3 for s in steps]))
    values["slot_occupancy_pct"] = float(occupancy)

    # Correct, outside the window: a seeded sample of finished requests
    # against the reference, token by token.
    rng = np.random.RandomState([run.seed, 4])
    pool = [f for f in finished if f.handle.tokens]
    sample = [pool[i] for i in rng.choice(
        len(pool), size=min(CHECKED_REQUESTS, len(pool)), replace=False)]
    margins = _token_margins(model, params, sample,
                             int(mix["engine"]["max_len"])) if sample else []
    checks = {
        "sampled_requests": len(sample),
        "max_token_margin": max(margins) if margins else None,
        "tokens_match_reference": bool(margins)
        and max(margins) <= TOKEN_MARGIN_TOL,
        "engine_compile_count_steady": compiles_in_window == 0,
        "none_failed": failed == 0,
    }
    correct = all(v for v in checks.values() if isinstance(v, bool))
    note(event="window", steps=len(steps), finished=len(finished),
         attempted=attempted, failed=failed, refused=loop.refused,
         unfinished_at_end=len(unfinished),
         engine_compile_count=engine.compile_count, token_margins=margins,
         **dict(values, **counters))
    counters.update(
        trace_steps=len(loop.steps) - trace_first, slots=slots,
        n_layer=model.n_layer, n_head=model.n_head, head_dim=model.head_dim,
        kv_bytes_token_layer=model.kv_bytes_per_token_layer(),
        chunk_size=int(mix["engine"]["chunk_size"]),
        trace_context=loop.context[trace_first:])
    engine.close()
    return {"correct": correct, "checks": checks, "attempted": attempted,
            "failed": failed, "values": values, "counters": counters}
