"""The plain reference of the rehearsal's family. Its program model is the
GPT-2 module, so its mathematics is GPT-2's; a real family writes its own
from its published description, as ``benchmark/reference/gpt2.py`` is."""

from benchmark.reference import gpt2


def logits(params, ids, n_head):
    return gpt2.logits(params, ids, n_head)
