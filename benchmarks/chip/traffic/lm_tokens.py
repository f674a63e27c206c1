"""Token batches for LM cells, from the traffic file's parameters.

A copy of the launcher's synthetic stream (``repro.data.lm_pipeline``:
Zipf unigram with every 4th token repeating its predecessor, one
restart-stable stream per row), kept here so the yardstick does not move
when the program's pipeline does.  Every row of every step differs.
"""
from __future__ import annotations

import numpy as np


def token_stream(vocab: int, seed: int, row: int):
    """Infinite generator of int32 token ids for one batch row."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, row]))
    probs = 1.0 / np.arange(1, vocab + 1, dtype=np.float64)
    probs /= probs.sum()
    while True:
        block = rng.choice(vocab, size=8192, p=probs)
        block[::4] = np.roll(block, 1)[::4]
        yield from block.astype(np.int32)


def batches(vocab: int, rows: int, seq_len: int, seed: int):
    """Yield ``(rows, seq_len)`` int32 arrays, one per step."""
    streams = [token_stream(vocab, seed, i) for i in range(rows)]
    while True:
        yield np.stack([np.fromiter(s, np.int32, seq_len) for s in streams])
