"""Token batches from the seed.

Batch ``i`` of a seed is always the same, and no two batches of a seed are
alike. Tokens follow a Zipf-like marginal over the vocabulary, as code and
text do (the arithmetic of the program's ``SyntheticLMPipeline._batch_at``,
kept here so that no change to the program moves the benchmark's inputs);
labels are the inputs shifted by one.
"""
from __future__ import annotations

import numpy as np

ZIPF_A = 1.3


def batch_at(seed: int, index: int, *, batch: int, seq_len: int,
             vocab: int) -> dict:
    rng = np.random.default_rng((seed % 2**64, index))
    raw = rng.zipf(ZIPF_A, size=(batch, seq_len + 1))
    tokens = (raw % (vocab - 1)).astype(np.int32) + 1
    return {"inputs": tokens[:, :-1], "labels": tokens[:, 1:]}
