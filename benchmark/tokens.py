"""Token batches of a training traffic mix, from the seed alone.

A copy of the program's Zipf-plus-motif stream (``repro.data.synthetic``,
one host): unigrams drawn with probability proportional to
``rank ** -zipf_a``, with copies of 64 fixed motifs of ``motif_len`` tokens
spliced over ``motif_prob`` of the positions.  The copy lives here so that
the yardstick stays fixed when the program's generator changes.  Batch ``i``
of a seed is a pure function of ``(seed, i)``; every seed gives batches of
the same sizes.
"""

from __future__ import annotations

import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words from a whole number of any size, for JAX keys."""
    return np.random.SeedSequence(abs(int(seed))).generate_state(2, np.uint32)


def batch(traffic: dict, vocab: int, seed: int, index: int) -> np.ndarray:
    """``(global_batch, seq_len + 1)`` int32 token ids in ``[0, vocab)``."""
    T, B = traffic["seq_len"], traffic["global_batch"]
    mlen = traffic["motif_len"]
    seed = abs(int(seed))
    motifs = np.random.default_rng(seed).integers(
        0, vocab, size=(64, mlen)).astype(np.int32)
    p = np.arange(1, vocab + 1, dtype=np.float64) ** (-traffic["zipf_a"])
    p /= p.sum()
    rng = np.random.default_rng((seed, index, 0))
    toks = rng.choice(vocab, size=(B, T + 1), p=p).astype(np.int32)
    n_splice = int(T * traffic["motif_prob"] / mlen)
    for b in range(B):
        pos = rng.integers(0, T - mlen, size=n_splice)
        mid = rng.integers(0, len(motifs), size=n_splice)
        for p0, m in zip(pos, mid):
            toks[b, p0:p0 + mlen] = motifs[m]
    return toks


def pool(traffic: dict, vocab: int, seed: int, n: int) -> list[np.ndarray]:
    """The ``n`` batches a run cycles through, all rows different."""
    return [batch(traffic, vocab, seed, i) for i in range(n)]
