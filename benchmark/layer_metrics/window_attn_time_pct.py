"""Device self time under the region ``swa`` (a WINDOW attention layer's
mixer: its LayerNorm, the fused q | k | v projection and its bias, the
``window_decode`` kernel over the slot's ring of pages, or ``window_prefill``
in the lane, the output projection and the residual; the ring's append counts
under ``kv_write``, as every append does) over device busy time. None for a
program without the region."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.region_pct(run, "swa")
