"""Training batches of whole sequences of one length, tokens uniform over
the vocabulary from the seed, next-token targets.

Traffic keys: ``seq_len``, ``sequences_per_step``.  Every step is a new
batch (rows all differ); the same seed gives the same batches.
"""

from __future__ import annotations

import numpy as np


class Batches:
    def __init__(self, traffic: dict, config: dict, seed: int):
        self.seq_len = int(traffic["seq_len"])
        self.n = int(traffic["sequences_per_step"])
        self.vocab = int(config["vocab_size"])
        self.seed = int(seed)
        self.layouts = [np.full(self.n, self.seq_len)]   # as lm_packed's
        if self.seq_len > int(config["n_positions"]):
            raise ValueError("seq_len is beyond the model's positions")

    def __iter__(self):
        """Yields (samples, real_tokens); a sample is (tokens, positions,
        targets) as int32 arrays."""
        rng = np.random.default_rng(self.seed)
        pos = np.arange(self.seq_len, dtype=np.int32)
        while True:
            samples = []
            for _ in range(self.n):
                t = rng.integers(0, self.vocab, size=self.seq_len + 1,
                                 dtype=np.int32)
                samples.append((t[:-1], pos, t[1:]))
            yield samples, self.n * self.seq_len


def make(traffic: dict, config: dict, seed: int) -> Batches:
    return Batches(traffic, config, seed)
