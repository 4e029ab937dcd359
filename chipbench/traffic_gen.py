"""Training traffic: a fixed set of token batches made from the seed.

The rows follow an order-1 Markov language (each token a fixed random
function of the one before it, a tenth of them replaced by noise), the
arithmetic of the program's ``SyntheticLM`` at ``order=1``, copied so that
the yardstick does not move with the program.  Every batch is made and
placed on the device in set-up; the window only cycles through them, so the
generator's Python loop is never timed.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def make_rows(vocab: int, seq: int, rows: int, seed: int, index: int,
              noise: float = 0.1) -> Dict[str, np.ndarray]:
    """One batch: ``tokens`` and next-token ``labels``, both (rows, seq)."""
    table = np.random.default_rng(seed).integers(0, vocab, size=(vocab,))
    rng = np.random.default_rng((seed, index + 1))
    toks = np.empty((rows, seq + 1), dtype=np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=rows)
    toks[:, 1] = rng.integers(0, vocab, size=rows)
    for t in range(2, seq + 1):
        nxt = table[toks[:, t - 1]]
        swap = rng.random(rows) < noise
        toks[:, t] = np.where(swap, rng.integers(0, vocab, size=rows), nxt)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()}


class Feed:
    """The batches of one run, handed out in order and then again from the
    first: ``batch(batch_size, step)`` is the loader interface the program's
    training loop calls, and it ignores ``step`` so that successive calls
    always move on to the next batch."""

    def __init__(self, batches: List[dict], on_batch=None):
        self.batches = batches
        self.cursor = 0
        self.on_batch = on_batch

    def next(self) -> dict:
        b = self.batches[self.cursor % len(self.batches)]
        self.cursor += 1
        return b

    def batch(self, batch_size: int, step: int) -> dict:
        del batch_size, step
        if self.on_batch is None:
            return self.next()
        with self.on_batch():
            return self.next()


def host_batches(traffic: dict, vocab: int, seed: int) -> List[dict]:
    """The run's batches on the host, as ``traffic`` sizes them."""
    return [make_rows(vocab, traffic["seq"], traffic["batch"], seed, i,
                      traffic.get("noise", 0.1))
            for i in range(traffic["distinct_batches"])]
