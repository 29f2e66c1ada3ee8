"""Find the capacity of an open-loop serving cell, once, on the chip:

    python benchmark/capacity.py --workload <name> --seed <n> [--requests 600]

``--requests`` requests of the cell's mix are served with the engine's queue
kept full: before every step requests are handed over while fewer than
``max_queue`` of them wait for a slot, so the engine never lacks work and is
never refused any. Requests completed per second, from the first step to the
last request finished, is the capacity; run it on three seeds (every seed
serves the same lengths, in an order of its own) and take the median. The
cell's file then fixes its rate at about four fifths of it, as a number: the
benchmark itself never searches for a rate. Prints one JSON line.
"""

import argparse
import json
import math
import os
import sys
import time


def serve_topped_up(engine, reqs, max_queue):
    """Serve ``reqs`` (prompt, max_new_tokens) through ``engine`` with its
    queue kept topped up: hand over while fewer than ``max_queue`` handles
    wait for a slot (``admit_time`` is None), then step, until every request
    is done. Returns the handles, the steps, the seconds from the first step
    to the last request finished, and the most that ever waited."""
    handles, live = [], []
    steps = most_waiting = 0
    t0 = time.perf_counter()
    while len(handles) < len(reqs) or live:
        waiting = sum(h.admit_time is None for h in live)
        while len(handles) < len(reqs) and waiting < max_queue:
            prompt, max_new = reqs[len(handles)]
            handles.append(engine.submit(prompt, max_new_tokens=max_new))
            live.append(handles[-1])
            waiting += 1
        most_waiting = max(most_waiting, waiting)
        engine.step()
        steps += 1
        live = [h for h in live if not h.done]
    return handles, steps, time.perf_counter() - t0, most_waiting


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import harness, traffic

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--requests", type=int, default=600)
    args = parser.parse_args()

    import jax

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    if jax.devices()[0].platform != "tpu":
        sys.exit("capacity: needs a TPU")
    place_compile_cache()
    cell = harness.Cell(harness.load_json(harness.MANIFEST), args.workload)
    model = harness.load_model(cell)
    # The cell replays one recorded schedule; here every seed draws its own
    # order of the same lengths.
    mix = {k: v for k, v in cell.traffic.items() if k != "schedule_seed"}
    engine = deepspeed.init_inference(
        model=model.module, params=model.init_params(args.seed),
        config={"inference": dict(mix["engine"])})
    reqs = traffic.requests(args.seed, 0, args.requests + 1, mix,
                            model.vocab_size)
    # The first request alone, so that compilation is not in the timing.
    engine.submit(reqs[0][0], max_new_tokens=reqs[0][1])
    engine.run()
    handles, steps, seconds, most_waiting = serve_topped_up(
        engine, reqs[1:], engine.config.max_queue)
    chunk = int(mix["engine"]["prefill_chunk"])
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "requests": len(handles), "seconds": seconds, "steps": steps,
        "capacity_requests_per_s": len(handles) / seconds,
        "four_fifths": 0.8 * len(handles) / seconds,
        "step_ms": 1e3 * seconds / steps,
        "tokens_out": sum(len(h.tokens) for h in handles),
        "prompt_tokens": sum(len(p) for p, _ in reqs[1:]),
        "lane_chunks_per_request": sum(
            math.ceil(len(p) / chunk) for p, _ in reqs[1:]) / len(handles),
        "max_queue": engine.config.max_queue,
        "most_waiting": most_waiting}))
    engine.close()
