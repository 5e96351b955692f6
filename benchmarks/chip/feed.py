"""Token batches from the seed: Zipfian unigrams with order-1 structure.

The arithmetic is that of the repository's synthetic language stream (rank-r
token drawn with probability proportional to 1/r; with probability
``copy_prob`` a token repeats its predecessor shifted by ``shift``), kept here
so that the yardstick does not move with the program. Only the rows a run uses
are drawn: ``pool`` batches of ``[W, B, S + 1]`` tokens, every row its own.
"""
from __future__ import annotations

import numpy as np


def zipf_rows(rng: np.random.RandomState, rows: int, length: int, vocab: int,
              copy_prob: float, shift: int) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = 1.0 / ranks
    probs /= probs.sum()
    base = rng.choice(vocab, size=(rows, length), p=probs).astype(np.int32)
    copy = rng.rand(rows, length) < copy_prob
    shifted = (np.roll(base, 1, axis=1) + shift) % vocab
    return np.where(copy, shifted, base).astype(np.int32)


def batches(seed_words, traffic: dict, vocab: int) -> list:
    """``traffic['pool']`` batches, each ``(tokens, labels)`` of ``[W, B, S]``
    int32 numpy arrays, drawn from the seed."""
    W, B, S = traffic["workers"], traffic["per_worker_batch"], traffic["seq"]
    rng = np.random.RandomState(seed_words)
    out = []
    for _ in range(traffic["pool"]):
        rows = zipf_rows(rng, W * B, S + 1, vocab, traffic["copy_prob"],
                         traffic["shift"]).reshape(W, B, S + 1)
        out.append((rows[..., :-1], rows[..., 1:]))
    return out
