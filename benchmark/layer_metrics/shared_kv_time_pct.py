"""Device self time under the regions ``attn`` (the ONE full-attention layer's
mixer, which appends the shared plane and reads it) and ``xattn`` (the cross
layers', which project queries only and read that plane) over device busy
time: what the one plane's eight readers cost. None for a program without a
cross layer's region."""

from benchmark import scope_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    if "xattn" not in reduced["regions"]:
        return None
    table = reduced["scope_s"]
    return 100.0 * (scope_reduce.under(table, "attn")
                    + scope_reduce.under(table, "xattn")) \
        / run["trace"]["busy_s"]
