"""Token sequences from ``--seed``: fixed-length rows of ids drawn from a
Zipf law over the configuration's vocabulary slice (there is no network, so
no corpus; real text is heavy-tailed, and a uniform draw would make every
row of the embedding and the head equally hot). Made on the host in bulk,
because the token loop gathers each step's rows from host memory."""

from __future__ import annotations

import numpy as np


def make(spec: dict, seed: int) -> np.ndarray:
    """``spec``: ``vocab`` (ids are in [0, vocab)), ``seq_len``,
    ``train_sequences``, ``zipf_exponent``. Returns int32 (N, seq_len).
    Rank r (1-based) has weight r**-exponent; which id holds which rank is
    a seeded shuffle."""
    vocab, n, t = spec["vocab"], spec["train_sequences"], spec["seq_len"]
    rng = np.random.default_rng([seed, 0x746F6B73])
    weights = np.arange(1, vocab + 1, dtype=np.float64) \
        ** -float(spec["zipf_exponent"])
    cdf = np.cumsum(weights / weights.sum())
    ranks = np.searchsorted(cdf, rng.random((n, t)), side="right")
    ids = rng.permutation(vocab).astype(np.int32)
    return ids[np.minimum(ranks, vocab - 1)]
