"""Token rows for the benchmark, made from the run's seed.

A copy of the program's `data/tokens.py::synthetic_token_batches`, kept here
so that the traffic a cell trains on cannot change with the program. Each
position t+1 follows t through a fixed random successor table with 30%
noise, so a model has something to learn.
"""
from __future__ import annotations

import numpy as np


def token_batches(*, vocab: int, seq_len: int, batch: int, num_batches: int,
                  num_clients: int, seed: int) -> np.ndarray:
    """(clients, num_batches, batch, seq_len + 1) int32 tokens in [0, vocab)."""
    rng = np.random.default_rng(seed)
    succ = rng.permutation(vocab)
    out = np.empty((num_clients, num_batches, batch, seq_len + 1), np.int32)
    x = rng.integers(0, vocab, size=(num_clients, num_batches, batch))
    for t in range(seq_len + 1):
        out[..., t] = x
        noise = rng.random(x.shape) < 0.3
        x = np.where(noise, rng.integers(0, vocab, size=x.shape), succ[x])
    return out
