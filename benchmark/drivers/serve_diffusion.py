"""Driver of the ``serve_diffusion`` kind: one ``deepspeed.init_inference``
engine over a model that generates by DIFFUSION OVER BLOCKS, under the closed
loop of ``drivers/serve.py`` (its ``Loop``, ``Flight`` and ``_closed``, not
copies of them).

What differs from ``serve`` is what a served token is held to. A pass over a
slot yields no token or several, of a block's positions in no order, so there
is no next-token stream to teacher-force. ``correct`` is decided by a
TEACHER-FORCED REPLAY OF WHAT THE TIMED PATH PRODUCED: a request's handle
records, a token, the pass of its block in which it was unmasked
(``handle.passes``, a byte a token); for ``CHECKED_REQUESTS`` sampled finished
requests the builder rebuilds every block's state before every pass and asks
the reference for the noisy logits there (``Model.pass_readings``), and the
run is held to:

(i)   every unmasked token within ``TOKEN_MARGIN_TOL`` logits of the
      reference's argmax at its position (``serve.py``'s tolerance, for its
      reason: the engine computes in bf16);
(ii)  every position chosen in a pass at a confidence no lower than any
      masked position passed over, less ``CONFIDENCE_TOL``; and every
      denoising pass unmasking ``block / steps`` positions, or all that were
      left;
(iii) no compile inside the window;
(iv)  every finished request exactly ``max_new`` tokens long;
and the router's logits, on identical inputs, within the builder's limit.

``serve_tok_s`` is counted exactly as ``serve.py`` counts it: tokens
delivered between step boundaries inside the window, the median over chunks
of steps.
"""

import itertools
import time

import numpy as np

from benchmark import harness, traffic
from benchmark.harness import median_chunk_rate, note

serve = harness.load_by_name("drivers", "serve")

TOKEN_MARGIN_TOL = serve.TOKEN_MARGIN_TOL
CHECKED_REQUESTS = serve.CHECKED_REQUESTS
# A confidence is exp(largest logit - logsumexp over the vocabulary). The sum
# over 151,936 ids averages the program's rounding away, so the log of a
# confidence is off by what its largest logit is off: the token margin's
# tolerance, for its reason. Two masked positions of a block whose confidences
# lie closer than that may be unmasked in either order, and both are right.
CONFIDENCE_TOL = TOKEN_MARGIN_TOL
WINDOW = serve.WINDOW


class DiffusionLoop(serve.Loop):
    """``serve.Loop`` handing over a request with its denoising steps."""

    def __init__(self, engine, steps):
        super().__init__(engine)
        self.denoising_steps = steps

    def submit(self, flight, now):
        from deepspeed_tpu.inference.scheduler import QueueFull

        flight.submitted = now
        try:
            flight.handle = self.engine.submit(
                flight.prompt, max_new_tokens=flight.max_new,
                denoising_steps=self.denoising_steps)
        except QueueFull:
            self.refused += 1
            flight.finish = now
            self.done.append(flight)
            return
        self.flying.append(flight)


def held(readings, steps, a_pass):
    """One request's readings (``Model.pass_readings``) against the record:
    -> (the largest token margin, the largest amount by which a position
    passed over was more confident than one chosen, passes that unmasked
    another count than the rule's, positions compared, positions exempt).
    A block that holds a position past the request's end is left out: the
    record holds nothing of those positions, so the block's states cannot be
    rebuilt."""
    when, margin = readings["when"], readings["margin"]
    whole = (when < steps).all(axis=1)
    worst_margin, worst_order, wrong_count, compared, exempt = \
        0.0, 0.0, 0, 0, 0
    for k in range(steps):
        masked = (when >= k) & whole[:, None]
        chosen = (when == k) & whole[:, None]
        near = readings["exempt"][k]
        compared += int(chosen.sum())
        exempt += int((chosen & near).sum())
        mine = chosen & ~near
        if mine.any():
            worst_margin = max(worst_margin, float(margin[mine].max()))
        # the rule's count: block / steps, or all that were left
        want = np.minimum(masked.sum(axis=1), a_pass)
        wrong_count += int((chosen.sum(axis=1) != want)[whole].sum())
        # order: the least confident chosen against the most confident
        # passed over, a block, where none of either is exempt
        conf = readings["confidence"][k]
        passed = masked & ~chosen
        clear = ~(near & masked).any(axis=1) & passed.any(axis=1) \
            & chosen.any(axis=1)
        if clear.any():
            least = np.where(chosen, conf, np.inf).min(axis=1)
            most = np.where(passed, conf, -np.inf).max(axis=1)
            worst_order = max(worst_order, float((most - least)[clear].max()))
    return worst_margin, worst_order, wrong_count, compared, exempt


def run(run):
    import deepspeed_tpu as deepspeed

    mix = run.cell.traffic
    model = run.model
    vocab = model.vocab_size
    steps = int(mix["denoising_steps"])
    note(event="sizes", engine=mix["engine"], loop=mix["loop"],
         denoising_steps=steps, **model.sizes())
    params = model.init_params(run.seed)
    engine = deepspeed.init_inference(
        model=model.module, params=params,
        config={"inference": dict(mix["engine"], denoising_steps=steps)})
    loop = DiffusionLoop(engine, steps)
    slots = int(mix["engine"]["max_slots"])
    clients = int(mix["clients"])
    source = itertools.cycle(traffic.requests(
        run.seed, WINDOW, int(mix["request_pool"]), mix, vocab))

    serve._warm_admission_shapes(slots)
    want = min(slots, clients)
    serve._closed(loop, source, clients,
                  lambda: len(loop.steps) >= 3 * slots
                  or sum(1 for f in loop.flying if f.seen) >= want)
    compiles_at_open = engine.compile_count
    note(event="warmup", steps=len(loop.steps),
         compile_count=compiles_at_open, in_flight=len(loop.flying))

    run.window_opens()
    t0 = time.perf_counter()
    t_end = t0 + run.seconds
    first_step = len(loop.steps)
    names = ("occupied_slot_steps", "slot_steps", "diffusion_passes",
             "diffusion_commit_passes", "diffusion_tokens_unmasked",
             "diffusion_blocks_committed")
    at_open = {n: engine.counters[n] for n in names}
    serve._closed(loop, source, clients,
                  lambda: time.perf_counter() >= t_end)
    run.window_closes()
    last_step = len(loop.steps)
    window_done = list(loop.done)
    unfinished = list(loop.flying)
    compiles_in_window = engine.compile_count - compiles_at_open
    counted = {n: engine.counters[n] - at_open[n] for n in names}
    # A LIVE slot an iteration is occupied, a commit pass too.
    occupancy = 100.0 * counted["occupied_slot_steps"] / max(
        counted["slot_steps"], 1)

    trace_first = last_step
    if run.trace:
        n_trace = int(mix["trace_steps"])
        with run.traced():
            serve._closed(loop, source, clients,
                          lambda: len(loop.steps) - trace_first >= n_trace)

    steps_in = [s for s in loop.steps[first_step:last_step] if s[1] <= t_end]
    finished = [f for f in window_done if f.handle is not None
                and f.finish is not None and t0 <= f.finish <= t_end]
    values, counters = {}, {}
    span_s = steps_in[-1][1] - steps_in[0][1]
    tokens = sum(s[2] for s in steps_in[1:])
    values["serve_tok_s"], chunks = median_chunk_rate(
        [s[1] for s in steps_in], [s[2] for s in steps_in],
        int(mix["rate_chunk_steps"]))
    attempted = len(finished) + loop.refused
    wrong_len = [f for f in finished if len(f.handle.tokens) != f.max_new]
    failed = loop.refused + len(wrong_len)
    counters.update(window_tokens=tokens, boundary_span_s=span_s,
                    rate_chunks=chunks, whole_window_tok_s=tokens / span_s,
                    **counted)
    values["engine_step_ms"] = float(np.median(
        [(s[1] - s[0]) * 1e3 for s in steps_in]))
    values["slot_occupancy_pct"] = float(occupancy)
    passes = max(counted["diffusion_passes"], 1)
    values["tokens_per_pass"] = counted["diffusion_tokens_unmasked"] / passes
    values["commit_pass_pct"] = \
        100.0 * counted["diffusion_commit_passes"] / passes
    hist = engine.metrics()["unmasked_per_pass_hist"]

    # What the traced tail's attention read, by the benchmark's own count:
    # a slot's open block starts where its prompt's whole blocks and the
    # tokens of its closed blocks end (it grows a block every ``steps + 1``
    # passes, not a position an iteration).
    length = model.block_length
    trace_context = [[n // length * length for n in step]
                     for step in loop.context[trace_first:]]
    engine.close()
    # The reference's check wants the chip's memory: the pool goes first.
    loop.engine = engine = None

    # Correct, outside the window: a seeded sample of finished requests,
    # every pass of every block against the reference.
    rng = np.random.RandomState([run.seed, 4])
    pool = [f for f in finished if f.handle.tokens]
    sample = [pool[i] for i in rng.choice(
        len(pool), size=min(CHECKED_REQUESTS, len(pool)), replace=False)]
    margins, orders, miscounts, compared, exempt, routing = \
        [], [], 0, 0, 0, {}
    if sample:
        readings, routing = model.pass_readings(
            params, [(f.prompt, list(f.handle.tokens),
                      list(f.handle.passes), steps) for f in sample],
            int(mix["engine"]["max_len"]),
            int(mix["engine"]["kv_page_len"]))
        for one in readings:
            margin, order, count, n, n_exempt = held(
                one, steps, length // steps)
            margins.append(margin)
            orders.append(order)
            miscounts, compared, exempt = \
                miscounts + count, compared + n, exempt + n_exempt
    checks = {
        "sampled_requests": len(sample),
        "max_token_margin": max(margins) if margins else None,
        "tokens_match_reference": bool(margins)
        and max(margins) <= TOKEN_MARGIN_TOL,
        "max_confidence_inversion": max(orders) if orders else None,
        "unmasked_in_confidence_order": bool(orders)
        and max(orders) <= CONFIDENCE_TOL and miscounts == 0,
        "router_logit_err": routing.get("router_logit_err"),
        "router_held": bool(routing)
        and routing["router_logit_err"] <= routing["router_limit"],
        "engine_compile_count_steady": compiles_in_window == 0,
        "none_failed": failed == 0,
    }
    correct = all(v for v in checks.values() if isinstance(v, bool))
    note(event="precision", positions=compared, exempt_positions=exempt,
         passes_with_another_count=miscounts, **routing)
    note(event="window", steps=len(steps_in), finished=len(finished),
         attempted=attempted, failed=failed, refused=loop.refused,
         unfinished_at_end=len(unfinished), token_margins=margins,
         confidence_inversions=orders, unmasked_per_pass_hist=hist,
         **dict(values, **counters))
    counters.update(
        trace_steps=len(loop.steps) - trace_first, slots=slots,
        n_layer=model.n_layer, n_head=model.n_head, head_dim=model.head_dim,
        kv_bytes_token_layer=model.kv_bytes_per_token_layer(),
        chunk_size=int(mix["engine"]["chunk_size"]),
        block_length=length, denoising_steps=steps,
        trace_context=trace_context)
    return {"correct": correct, "checks": checks, "attempted": attempted,
            "failed": failed, "values": values, "counters": counters}
