"""Requests for a closed loop of clients: prompt and answer lengths
lognormal, no shared prefixes, greedy.

Traffic keys: ``clients``, ``prompt`` and ``answer`` {median, sigma, min,
max}, ``pool`` (how many (prompt, answer) length pairs), ``shape_seed``.

The ``pool`` pairs and the order they are sent in (shuffled again each time
the pool is used up) come from ``shape_seed``, so every ``--seed`` replays
the same sizes in the same order, and the engine, whose schedule depends on
sizes alone, makes the same ticks: a tail such as the 95th percentile of
the time to the first token then differs between seeds by timing only, not
by which long prompts met.  The seed draws the prompt tokens, uniform over
the vocabulary (and the weights, in the driver).  The first request of client ``i`` asks for the share
``(i + 1) / clients`` of its answer, so that the clients do not end in the
order they started in.
"""

from __future__ import annotations

import numpy as np


def draw(rng, n: int, spec: dict) -> np.ndarray:
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def make_pool(traffic: dict) -> np.ndarray:
    rng = np.random.default_rng(int(traffic["shape_seed"]))
    n = int(traffic["pool"])
    return np.stack([draw(rng, n, traffic["prompt"]),
                     draw(rng, n, traffic["answer"])], axis=1)


class Requests:
    def __init__(self, traffic: dict, config: dict, seed: int):
        self.pool = make_pool(traffic)
        self.clients = int(traffic["clients"])
        self.vocab = int(config["vocab_size"])
        self.seed = int(seed)
        self.shape_seed = int(traffic["shape_seed"])
        longest = int(traffic["prompt"]["max"]) + int(traffic["answer"]["max"])
        if longest > int(config["n_positions"]):
            raise ValueError("a request is beyond the model's positions")

    def __iter__(self):
        """Yields (prompt tokens int32, max_tokens)."""
        rng = np.random.default_rng(self.seed)
        order = np.random.default_rng(self.shape_seed + 1)
        sent = 0
        while True:
            for i in order.permutation(len(self.pool)):
                n_prompt, n_answer = (int(x) for x in self.pool[i])
                if sent < self.clients:
                    n_answer = max(1, n_answer * (sent + 1) // self.clients)
                sent += 1
                yield (rng.integers(0, self.vocab, size=n_prompt,
                                    dtype=np.int32), n_answer)


def make(traffic: dict, config: dict, seed: int) -> Requests:
    return Requests(traffic, config, seed)
