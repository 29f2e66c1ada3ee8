"""Token source ``rehearsal_skewed``: tokens drawn with a Zipf-like skew
(weight 1 / (rank + 1) ** ``token_skew`` of the mix) over the vocabulary, as
a mix with uneven topics would: what a later PR adds as
``benchmark/traffic_sources/<name>.py``. Serving only."""

import numpy as np


def prompts(rng, lengths, vocab_size, mix):
    weights = 1.0 / (np.arange(vocab_size) + 1.0) ** float(mix["token_skew"])
    weights /= weights.sum()
    return [rng.choice(vocab_size, size=int(p), p=weights).astype(np.int32)
            for p in lengths]
