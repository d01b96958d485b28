"""The three generators: the same seed gives the same traffic, another
seed gives the same sizes in another order, and the lengths follow the
distributions the traffic files state."""

import os

import numpy as np
import pytest

from harness import cells

BENCH = cells.ROOT


def load(kind, name):
    return cells.load_json(os.path.join(BENCH, kind, name + ".json"))


CONFIG = load("configs", "cerebras-gpt-1.3b")


def gen(mix):
    traffic = load("traffic", mix)
    mod = cells.load_module(os.path.join(
        BENCH, "generators", traffic["generator"] + ".py"))
    return traffic, mod


def take(it, n):
    it = iter(it)
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("mix", ["lm-seq2048", "lm-packed-docs"])
@pytest.mark.parametrize("seed", [0, 2**31 + 12345])
def test_train_batches_are_deterministic_and_one_shape(mix, seed):
    traffic, mod = gen(mix)
    a = take(mod.make(traffic, CONFIG, seed), 5)
    b = take(mod.make(traffic, CONFIG, seed), 5)
    c = take(mod.make(traffic, CONFIG, seed + 1), 5)
    for (sa, ta), (sb, tb) in zip(a, b):
        assert ta == tb
        for x, y in zip(sa, sb):
            for u, v in zip(x, y):
                assert np.array_equal(u, v)
    assert any(not np.array_equal(a[0][0][0][0], s[0][0][0]) for s in c)
    docs = {len(s) for s, _ in a + c}
    assert len(docs) == 1                       # one lengths-array shape
    for samples, real in a + c:
        lens = [len(t) for t, _, _ in samples]
        assert real == sum(lens)
        # the feeder's buckets: capacity and longest document
        assert traffic["tokens_per_step"] // 2 < real <= traffic["tokens_per_step"]
        top = CONFIG["n_positions"]
        assert top // 2 < max(lens) <= top
        for toks, pos, tgt in samples:
            assert toks.dtype == np.int32 and toks.max() < CONFIG["vocab_size"]
            assert np.array_equal(pos, np.arange(len(toks)))
            assert np.array_equal(tgt[:-1], toks[1:])   # next-token targets


def test_rows_of_a_fixed_batch_all_differ():
    traffic, mod = gen("lm-seq2048")
    (samples, real), = take(mod.make(traffic, CONFIG, 7), 1)
    assert real == 8192 and len(samples) == 4
    rows = [tuple(t) for t, _, _ in samples]
    assert len(set(rows)) == 4


def test_packed_lengths_follow_the_stated_lognormal():
    traffic, mod = gen("lm-packed-docs")
    lens = np.concatenate(mod.make_layouts(traffic))
    spec = traffic["length"]
    assert lens.min() >= spec["min"] and lens.max() <= spec["max"]
    # the conditions (fit, one long document) pull the median a little
    # below the stated 256 and fatten the tail; both stay near
    assert 0.75 * spec["median"] < np.median(lens) < 1.1 * spec["median"]
    assert 0.8 < np.std(np.log(lens)) < 1.25
    every_seed = [sorted(map(tuple, mod.make(traffic, CONFIG, s).layouts))
                  for s in (1, 2)]
    assert every_seed[0] == every_seed[1]       # the same set of sizes


@pytest.mark.parametrize("seed", [0, 2**31 + 12345])
def test_chat_requests(seed):
    traffic, mod = gen("chat-closed32")
    a = take(mod.make(traffic, CONFIG, seed), 600)
    b = take(mod.make(traffic, CONFIG, seed), 600)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))
    steady = a[traffic["clients"]:]
    prompts = np.array([len(p) for p, _ in steady])
    answers = np.array([n for _, n in steady])
    assert traffic["prompt"]["min"] <= prompts.min()
    assert prompts.max() <= traffic["prompt"]["max"]
    assert traffic["answer"]["min"] <= answers.min()
    assert answers.max() <= traffic["answer"]["max"]
    assert 0.8 * 192 < np.median(prompts) < 1.2 * 192
    assert 0.8 * 64 < np.median(answers) < 1.2 * 64
    # the first request of each client is a share of its answer
    first = [n for _, n in a[:traffic["clients"]]]
    assert all(n >= 1 for n in first)
    # every seed replays the same sizes in the same order, other tokens
    other = take(mod.make(traffic, CONFIG, seed + 1), 600)
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in other]
    assert any(not np.array_equal(x[0], y[0]) for x, y in zip(a, other))
    pool = sorted(map(tuple, mod.make_pool(traffic).tolist()))
    cycle = a[traffic["clients"]:len(pool)]
    assert all((len(p), n) in pool for p, n in cycle)
