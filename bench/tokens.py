"""The token batches a training round is fed, made again for the reference.

A copy of the program's synthetic LM stream (``repro.data.synthetic.
lm_token_stream``): each sequence follows x_{t+1} = (a·x_t + b) mod V with
per-sequence (a, b), drawn from ``default_rng(seed·1_000_003 + step)``.
The program's pipeline feeds the timed path; the reference takes its
batches from here, so a pipeline that feeds other tokens fails the check.
"""

from __future__ import annotations

import numpy as np


def lm_batch(vocab: int, batch: int, seq: int, seed: int, step: int):
    rng = np.random.default_rng(seed * 1_000_003 + step)
    a = rng.integers(1, vocab - 1, size=(batch, 1))
    b = rng.integers(0, vocab - 1, size=(batch, 1))
    x0 = rng.integers(0, vocab, size=(batch, 1))
    toks = np.zeros((batch, seq + 1), np.int64)
    toks[:, :1] = x0
    for t in range(seq):
        toks[:, t + 1] = (a[:, 0] * toks[:, t] + b[:, 0]) % vocab
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)
