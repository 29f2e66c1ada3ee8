"""Device self time under ``moe/router``, ``moe/dispatch`` and
``moe/combine`` over device busy time: what routing costs beside the
experts' matmuls (the float32 router, its softmax and top-k, scattering the
weights, folding them in). None for a program without the region ``moe``."""

from benchmark import scope_reduce

WORDS = ("router", "dispatch", "combine")


def read(run):
    reduced = scope_reduce.of_run(run)
    if "moe" not in reduced["regions"]:
        return None
    routing = sum(s for region, s in reduced["scope_s"].items()
                  if "moe" in region.split("/")
                  and any(w in region.split("/") for w in WORDS))
    return 100.0 * routing / run["trace"]["busy_s"]
