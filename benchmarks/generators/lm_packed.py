"""Training batches of packed documents of lognormal length.

Traffic keys: ``tokens_per_step`` (the buffer), ``docs_per_step``,
``length`` {median, sigma, min, max}, ``layouts``, ``layout_seed``.

The trainer's feeder compiles one program per (token capacity, longest-
document bucket, number of documents): capacity is the next power of two
over the step's tokens, the bucket the next power of two over its longest
document, and the document count is the length of an array.  To keep all
three to one value a step's layout is drawn until it has

- exactly ``docs_per_step`` documents,
- more than half and at most all of ``tokens_per_step`` tokens, and
- a longest document above half of ``length.max``.

A layout is ``docs_per_step`` lengths drawn from the clipped lognormal and
packed first-fit into the buffer; a draw that does not fit, or misses a
condition, is drawn again.  ``layouts`` such layouts are made once from
``layout_seed``, so every ``--seed`` trains on the same set of sizes; the
seed shuffles their order and the documents inside each, and draws the
tokens.  Only real tokens are counted; the rest of the buffer is padding.
"""

from __future__ import annotations

import numpy as np


def draw_lengths(rng, n: int, length: dict) -> np.ndarray:
    raw = rng.lognormal(np.log(length["median"]), length["sigma"], size=n)
    return np.clip(np.rint(raw), length["min"], length["max"]).astype(int)


def make_layouts(traffic: dict) -> list:
    cap = int(traffic["tokens_per_step"])
    docs = int(traffic["docs_per_step"])
    length = traffic["length"]
    rng = np.random.default_rng(int(traffic["layout_seed"]))
    out = []
    while len(out) < int(traffic["layouts"]):
        lens = draw_lengths(rng, docs, length)
        # first-fit into the one buffer: a document goes in where the
        # tokens before it end; the layout stands only if all fit
        if not (cap // 2 < lens.sum() <= cap):
            continue
        if lens.max() <= length["max"] // 2:
            continue
        out.append(lens)
    return out


class Batches:
    def __init__(self, traffic: dict, config: dict, seed: int):
        self.layouts = make_layouts(traffic)
        self.vocab = int(config["vocab_size"])
        self.seed = int(seed)
        if int(traffic["length"]["max"]) > int(config["n_positions"]):
            raise ValueError("a document is beyond the model's positions")

    def __iter__(self):
        rng = np.random.default_rng(self.seed)
        while True:
            for i in rng.permutation(len(self.layouts)):
                lens = rng.permutation(self.layouts[i])
                samples = []
                for n in lens:
                    t = rng.integers(0, self.vocab, size=n + 1,
                                     dtype=np.int32)
                    samples.append((t[:-1], np.arange(n, dtype=np.int32),
                                    t[1:]))
                yield samples, int(lens.sum())


def make(traffic: dict, config: dict, seed: int) -> Batches:
    return Batches(traffic, config, seed)
