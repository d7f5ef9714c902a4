"""The token rows of each round, made from the seed.

A copy of the program's data feed: per-group synthetic streams (each next
token an affine map of the last, or a uniform draw) and, per round, one
roster draw and then, group by group, ``(H, micro_batch)`` window starts.
"""
from __future__ import annotations

import numpy as np

STREAM_TOKENS = 200_000


def stream(n_tokens: int, vocab: int, seed: int, structure: float):
    rng = np.random.default_rng(seed)
    a, b = 31, 7
    toks = np.empty(n_tokens, dtype=np.int32)
    toks[0] = rng.integers(0, vocab)
    det = rng.random(n_tokens) < structure
    rnd = rng.integers(0, vocab, size=n_tokens)
    for i in range(1, n_tokens):
        toks[i] = (a * toks[i - 1] + b) % vocab if det[i] else rnd[i]
    return toks


def group_streams(G: int, vocab: int, seed: int) -> list:
    return [stream(STREAM_TOKENS, vocab, seed + g, 0.75 + 0.2 * (g % 3) / 2)
            for g in range(G)]


def rounds(streams, *, seed: int, n_rounds: int, H: int, micro: int,
           seq: int, p_drop: float):
    """Yield ``(tokens, labels)`` of shape (G, H, micro, seq) per round."""
    G = len(streams)
    rng = np.random.default_rng(seed)
    for _ in range(n_rounds):
        roster = rng.random(G) >= p_drop
        if not roster.all():
            raise ValueError("the reference covers rounds where every "
                             "group takes part")
        tokens = np.zeros((G, H, micro, seq), np.int32)
        labels = np.zeros((G, H, micro, seq), np.int32)
        for g in range(G):
            idx = rng.integers(0, len(streams[g]) - seq - 1, size=(H, micro))
            for h in range(H):
                for i in range(micro):
                    j = idx[h, i]
                    tokens[g, h, i] = streams[g][j:j + seq]
                    labels[g, h, i] = streams[g][j + 1:j + seq + 1]
        yield tokens, labels
