"""Find the capacity of an open-loop serving cell, once, on the chip:

    python benchmark/capacity.py --workload <name> --seed <n> [--requests 48]

Every request of the cell's mix is queued at the start; requests completed
per second until the last one ends is the capacity. The cell's file then
fixes its rate at about four fifths of it, as a number: the benchmark itself
never searches for a rate. Prints one JSON line.
"""

import argparse
import json
import os
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import harness, traffic

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--requests", type=int, default=48)
    args = parser.parse_args()

    import jax

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    if jax.devices()[0].platform != "tpu":
        sys.exit("capacity: needs a TPU")
    place_compile_cache()
    cell = harness.Cell(harness.load_json(harness.MANIFEST), args.workload)
    model = harness.load_model(cell)
    mix = cell.traffic
    engine = deepspeed.init_inference(
        model=model.module, params=model.init_params(args.seed),
        config={"inference": dict(mix["engine"])})
    reqs = traffic.requests(args.seed, 0, args.requests + 1, mix,
                            model.vocab_size)
    # The first request alone, so that compilation is not in the timing.
    engine.submit(reqs[0][0], max_new_tokens=reqs[0][1])
    engine.run()
    handles = [engine.submit(p, max_new_tokens=o) for p, o in reqs[1:]]
    t0 = time.perf_counter()
    steps = 0
    while not all(h.done for h in handles):
        engine.step()
        steps += 1
    seconds = time.perf_counter() - t0
    print(json.dumps({
        "workload": args.workload, "requests": len(handles),
        "seconds": seconds, "steps": steps,
        "capacity_requests_per_s": len(handles) / seconds,
        "four_fifths": 0.8 * len(handles) / seconds,
        "tokens_out": sum(len(h.tokens) for h in handles),
        "prompt_tokens": sum(len(p) for p, _ in reqs[1:])}))
    engine.close()
