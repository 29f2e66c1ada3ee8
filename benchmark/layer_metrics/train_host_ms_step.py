"""Host self time inside ``train_batch`` a step: the engine's ``train/*``
spans (``train/shard_batch``, ``train/dispatch``, ``train/bookkeeping`` and
what ``train/step`` spends outside them), over the ``train/step`` spans of
the traced tail."""

from benchmark import scope_reduce

SPANS = ("train/step", "train/shard_batch", "train/dispatch",
         "train/bookkeeping", "train/forward", "train/backward",
         "train/update")


def read(run):
    return scope_reduce.host_ms_a_step(scope_reduce.of_run(run)["host"],
                                       SPANS, "train/step")
