"""Token source ``uniform``: every token independent and uniform over the
vocabulary (no tiled phrase that would flatter an n-gram drafter)."""

import numpy as np


def prompts(rng, lengths, vocab_size, mix):
    return [rng.randint(0, vocab_size, size=(int(p),)).astype(np.int32)
            for p in lengths]


def batches(rng, n, batch, seq_len, vocab_size, mix):
    return rng.randint(0, vocab_size, size=(n, batch, seq_len)).astype(
        np.int32)
