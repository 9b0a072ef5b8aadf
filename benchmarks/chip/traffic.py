"""Seeded training tokens: the benchmark's own copy of the program's
``data.pipeline.SyntheticTokens``, so that no program change can move it.

Each row holds Zipf-distributed tokens in which every position with
``i % period != 0`` is a fixed function of the token before it, a structure
that a model learns within a few hundred steps. Rows differ within a batch
and from step to step. Unlike the original, iteration goes on from where the
last one stopped: a trainer that iterates once per call still gets a fresh
batch at every step across calls.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int) -> tuple[int, int]:
    """Any whole number as two non-negative words (numpy and jax take those)."""
    seed = int(seed) % 2**64
    return seed >> 32, seed & 0xFFFFFFFF


class TokenStream:
    def __init__(self, global_batch: int, seq_len: int, vocab: int,
                 seed: int, *, zipf_a: float = 1.2, period: int = 4):
        self.global_batch, self.seq_len, self.vocab = global_batch, seq_len, vocab
        self.seed = seed_words(seed)
        self.zipf_a, self.period = zipf_a, period
        self.step = 0

    @classmethod
    def for_traffic(cls, traffic: dict, vocab: int, seed: int):
        tok = traffic.get("tokens", {})
        return cls(traffic["global_batch"], traffic["seq_len"], vocab, seed,
                   zipf_a=tok.get("zipf_a", 1.2), period=tok.get("period", 4))

    def batch(self, step: int) -> dict[str, np.ndarray]:
        """The batch of step ``step`` (0-based): tokens (B, S + 1) int32."""
        rng = np.random.default_rng((*self.seed, step))
        b, s = self.global_batch, self.seq_len
        toks = (rng.zipf(self.zipf_a, size=(b, s + 1)).astype(np.int64) - 1) \
            % self.vocab
        for k in range(1, self.period):
            idx = np.arange(k, s + 1, self.period)
            toks[:, idx] = (toks[:, idx - 1] * 31 + 7) % self.vocab
        return {"tokens": toks.astype(np.int32)}

    def __iter__(self):
        return self

    def __next__(self):
        out = self.batch(self.step)
        self.step += 1
        return out
