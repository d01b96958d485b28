"""A recurrent state beside the pages behind ``ServingEngine``: a tiny
``HybridSsmLM`` (2 blocks, hidden 64, 4 : 2 query to KV heads of 16 and a
second case at 5 : 1, 4 state-space heads of 8, state 16, 2 groups, chunk
8, every multiplier moved from 1; seeded weights) against the benchmark's
plain reference (``benchmarks/references/falcon_h1.py``: the recurrence
token by token).  What is compared is LOGITS: the engine's row of the
step that produced a token (chunked prefill, a state carried from chunk
to chunk, then one token a tick through the state and the cache) against
the reference's row of ONE full forward over the prompt and the served
tokens."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.gated_delta import causal_conv
from paddle_tpu.ops.ssd import ssd_chunks, ssd_step
from paddle_tpu.platform.enforce import EnforceError
from paddle_tpu.serving import (DecoderLM, RequestStatus,
                                ServingEngine, export_chain)
from paddle_tpu.serving.kv_cache import (RecurrentState, recurrent_state,
                                         split_pool_bytes)

pytestmark = pytest.mark.serving

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import cells, weights  # noqa: E402

REF = cells.load_module(os.path.join(BENCH, "references", "falcon_h1.py"))
FAMILY = cells.load_module(os.path.join(BENCH, "families", "falcon_h1.py"))
TINY = cells.load_json(os.path.join(BENCH, "tests", "configs",
                                    "tiny-falcon-h1.json"))
# the same block at a GQA group that is no power of two (the 34B's is 5)
FIVE = {**TINY, "num_attention_heads": 5, "num_key_value_heads": 1}
VOCAB = TINY["vocab_size"]
PAGE, CHUNK = 4, 8
PAD = 96              # rows of the reference's buffer
# Engine and reference are both float32 here and differ by the order of
# their sums alone (the engine's attention streams pages, its recurrence
# runs in chunks): 1e-6 of logits whose size is about 1.
TOL = 2e-5
# What the program states is bfloat16 operands in the projections: the
# reference in that mode lies 0.012 from the float32 one, inside
# TOL_STATED; every fault below (a multiplier left out: 0.08 to 2.4; a
# state not carried, not reset, advanced by a padding row: 0.2 to 1.6)
# and float8 operands (0.15) lie outside it.
TOL_STATED = 0.04


def library_draws(made, config):
    """The harness's leaves made sensitive at this tiny size.  The leaves
    no config fixes are drawn as the library draws them (the harness's
    kinds give a state that halves a token: too forgetful to show a state
    that was lost two chunks ago): ``A`` uniform in -16..-1, ``dt``
    log-uniform in 0.001..0.1.  And the matrices (std 0.02, which at
    hidden 64 leaves every branch a whisper beside the embedding) are
    scaled to keep their rows' size, so that each branch, and so each
    multiplier, moves the logits."""
    made = dict(made)
    rng = np.random.default_rng(7)
    for name, (shape, kind) in FAMILY.leaves(config, "serve").items():
        if kind == "matrix" and name != "wte":
            made[name] = made[name] * (shape[-2] ** -0.5 / weights.MATRIX_STD)
    for l in range(config["serve"]["n_layer"]):
        n = config["mamba_n_heads"]
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), n))
        made[f"blocks.{l}.a_log"] = jnp.asarray(
            np.log(rng.uniform(1, 16, n)), jnp.float32)
        made[f"blocks.{l}.dt_bias"] = jnp.asarray(
            dt + np.log(-np.expm1(-dt)), jnp.float32)
    return made


@pytest.fixture(scope="module")
def made():
    return library_draws(
        weights.make(FAMILY.leaves(TINY, "serve"), 20261002), TINY)


@pytest.fixture(scope="module")
def made_five():
    return library_draws(
        weights.make(FAMILY.leaves(FIVE, "serve"), 20261003), FIVE)


def engine(made, config=TINY, **kw):
    prog = FAMILY.serve_program(config, [None])
    params = {name: made[ref] for name, ref in prog["names"].items()}
    kw = {"page_size": PAGE, "num_pages": 80, "max_pages_per_seq": 24,
          "max_slots": 4, "buckets": (8, 16), "prefill_chunk": CHUNK, **kw}
    return ServingEngine(prog["model"], params, eos_id=VOCAB, **kw)


def record(eng):
    """{rid: [logits row [V] of each token the request was given, in
    order]}: the row of the step's logits (they stay on the device) that
    the token came of."""
    got, now = {}, {}
    walk, emit = eng._walk_rows, eng._emit
    bd = eng._max_slots * eng._k1

    def spy_walk(flight, words, poisoned, t):
        now["logits"] = flight.logits
        now["row"] = {p[0].rid: p[1] * eng._k1 for p in flight.passes}
        now["row"].update({c[0].rid: bd + c[0].slot for c in flight.chunks
                           if c[0].slot is not None})
        walk(flight, words, poisoned, t)

    def spy_emit(req, tok, t):
        got.setdefault(req.rid, []).append(
            np.asarray(now["logits"][now["row"][req.rid]]))
        emit(req, tok, t)

    eng._walk_rows, eng._emit = spy_walk, spy_emit
    return got


def prompt_of(seed: int, n: int):
    return [int(t) for t in np.random.default_rng(seed).integers(0, VOCAB, n)]


def reference_rows(made, prompt, answer, mode="f32", config=TINY):
    """Row ``p - 1`` judges the token at position ``p``, as the serve
    driver reads it."""
    toks = np.zeros(PAD, np.int32)
    toks[:len(prompt) + len(answer)] = list(prompt) + list(answer)
    pos = jnp.arange(PAD, dtype=jnp.int32)
    rows = FAMILY.reference_logits(
        REF, config, weights.unflatten(made), jnp.asarray(toks), pos,
        jnp.zeros((PAD,), jnp.int32), mode=mode, block_rows=32)
    return np.asarray(rows[0:PAD])


def worst_gap(rows, ref, n_prompt):
    return max(float(np.abs(row - ref[n_prompt + i - 1]).max())
               for i, row in enumerate(rows))


def serve_one(eng, prompt, max_tokens):
    got = record(eng)
    rid = eng.submit(prompt, max_tokens)
    answer = eng.run()[rid]
    return answer, got[rid]


# ---- the recurrence's two forms ----------------------------------------------

H, P, G, N = 4, 8, 2, 16


def token_by_token(xs, dt, a, bm, cm, d, state):
    ys = []
    for t in range(xs.shape[0]):
        b, c = np.repeat(bm[t], H // G, 0), np.repeat(cm[t], H // G, 0)
        state = np.exp(dt[t] * a)[:, None, None] * state + \
            (dt[t][:, None] * xs[t])[:, :, None] * b[:, None, :]
        ys.append((state * c[:, None, :]).sum(-1) + d[:, None] * xs[t])
    return np.stack(ys), state


def scan_inputs(rows: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.normal(size=s).astype(np.float32)  # noqa: E731
    return dict(xs=f(rows, H, P), dt=0.3 * np.abs(f(rows, H)),
                a=-np.exp(f(H)), bm=f(rows, G, N), cm=f(rows, G, N), d=f(H))


# slot -> its rows in a packed buffer of 64: starts inside a chunk of 8,
# spans several, three padding rows behind slot 0's, a tail of no one's
RUNS = {2: (0, 20), 0: (20, 25), 1: (28, 51)}


@pytest.mark.parametrize("with_state", [False, True])
def test_ssd_chunks_is_the_recurrence_over_a_packed_buffer(with_state):
    i = scan_inputs(64)
    seg = np.full(64, -1, np.int32)
    live = np.zeros(64, bool)
    for slot, (lo, hi) in RUNS.items():
        seg[lo:hi], live[lo:hi] = slot, True
    seg[25:28] = 0           # a chunk's padding rows stay its slot's
    state = np.random.default_rng(1).normal(size=(4, H, P, N)).astype(
        np.float32) if with_state else np.zeros((4, H, P, N), np.float32)
    y, out = jax.jit(lambda *a: ssd_chunks(*a, chunk=8))(
        np.where(live[:, None, None], i["xs"], 0),
        np.where(live[:, None], i["dt"], 0), i["a"], i["bm"], i["cm"],
        i["d"], seg, state)
    for slot, (lo, hi) in RUNS.items():
        want, end = token_by_token(i["xs"][lo:hi], i["dt"][lo:hi], i["a"],
                                   i["bm"][lo:hi], i["cm"][lo:hi], i["d"],
                                   state[slot])
        np.testing.assert_allclose(np.asarray(y[lo:hi]), want, atol=2e-5)
        np.testing.assert_allclose(np.asarray(out[slot]), end, atol=2e-5)
    # a state whose slot has no row comes back as it went in, bit for bit
    assert np.array_equal(np.asarray(out[3]), state[3])
    assert float(jnp.abs(y[51:]).max()) == 0.0


def test_ssd_step_n_times_is_ssd_chunks():
    i = scan_inputs(19, seed=3)
    state = np.random.default_rng(4).normal(size=(2, H, P, N)).astype(
        np.float32)
    y, out = ssd_chunks(i["xs"], i["dt"], i["a"], i["bm"], i["cm"], i["d"],
                        jnp.ones((19,), jnp.int32), state, chunk=8)
    one, rows = jnp.asarray(state[1:2]), []
    for t in range(19):
        row, one = ssd_step(i["xs"][t:t + 1], i["dt"][t:t + 1], i["a"],
                            i["bm"][t:t + 1], i["cm"][t:t + 1], i["d"], one)
        rows.append(row[0])
    np.testing.assert_allclose(np.asarray(y), np.stack(rows), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(one[0]),
                               atol=2e-5)
    assert np.array_equal(np.asarray(out[0]), state[0])


def test_a_row_with_dt_and_xs_zero_is_the_identity_in_both_forms():
    i = scan_inputs(16, seed=5)
    state = np.random.default_rng(6).normal(size=(16, H, P, N)).astype(
        np.float32)
    zero = np.zeros_like
    _, stepped = ssd_step(zero(i["xs"]), zero(i["dt"]), i["a"], i["bm"],
                          i["cm"], i["d"], state)
    assert np.array_equal(np.asarray(stepped), state)
    _, walked = ssd_chunks(zero(i["xs"]), zero(i["dt"]), i["a"], i["bm"],
                           i["cm"], i["d"],
                           jnp.repeat(jnp.arange(2, dtype=jnp.int32), 8),
                           state[:2], chunk=8)
    assert np.array_equal(np.asarray(walked), state[:2])


# ---- the convolution's carry ----------------------------------------------------

def conv_inputs():
    rng = np.random.default_rng(8)
    return (rng.normal(size=(23, 6)).astype(np.float32),
            rng.normal(size=(6, 4)).astype(np.float32),
            rng.normal(size=(6,)).astype(np.float32))


def test_causal_conv_without_a_carry_is_the_training_call():
    x, w, _ = conv_inputs()
    seg = jnp.asarray([0] * 9 + [1] * 14, jnp.int32)
    y = causal_conv(x, w, seg)
    assert isinstance(y, jax.Array) and y.shape == x.shape
    for lo, hi in ((0, 9), (9, 23)):
        ext = np.concatenate([np.zeros((3, 6), np.float32), x[lo:hi]])
        want = np.stack([(ext[i:i + 4] * w.T).sum(0)
                         for i in range(hi - lo)])
        np.testing.assert_allclose(np.asarray(y[lo:hi]), want, atol=1e-5)
    # and the program it compiles to reads no carry: none of its gathers
    text = jax.jit(causal_conv).lower(x, w, seg).as_text()
    assert "gather" not in text and "scatter" not in text


@pytest.mark.parametrize("cut", range(24))
def test_causal_conv_split_at_every_row_is_the_unsplit_call(cut):
    x, w, b = conv_inputs()
    whole = np.asarray(causal_conv(x, w, jnp.zeros((23,), jnp.int32),
                                   bias=b))
    carry0 = np.random.default_rng(9).normal(size=(3, 3, 6)).astype(
        np.float32)
    carry = jnp.asarray(carry0).at[1].set(0.0)
    parts = []
    for lo, hi in ((0, cut), (cut, 23)):
        if hi > lo:
            y, carry = causal_conv(x[lo:hi], w,
                                   jnp.ones((hi - lo,), jnp.int32),
                                   carry=carry, bias=b)
            parts.append(np.asarray(y))
    np.testing.assert_allclose(np.concatenate(parts), whole, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(carry[1]), x[20:23])
    # a segment without a row keeps its carry
    for s in (0, 2):
        assert np.array_equal(np.asarray(carry[s]), carry0[s])


def test_causal_conv_carries_several_segments_and_passes_rows_of_none():
    x, w, b = conv_inputs()
    seg = np.asarray([2] * 2 + [-1] * 3 + [0] * 10 + [-1] * 8, np.int32)
    carry0 = np.random.default_rng(10).normal(size=(3, 3, 6)).astype(
        np.float32)
    y, carry = causal_conv(x, w, seg, carry=carry0, bias=b)
    for slot, (lo, hi) in {2: (0, 2), 0: (5, 15)}.items():
        ext = np.concatenate([carry0[slot], x[lo:hi]])
        want = np.stack([(ext[i:i + 4] * w.T).sum(0) + b
                         for i in range(hi - lo)])
        np.testing.assert_allclose(np.asarray(y[lo:hi]), want, atol=1e-5)
        # (two rows: the old carry's last row moved up)
        np.testing.assert_array_equal(np.asarray(carry[slot]), ext[-3:])
    assert np.array_equal(np.asarray(carry[1]), carry0[1])


# ---- prefill in chunks, then decoding, through the state and the cache -------

CASES = [
    # prompt length, max_tokens
    (1, 5),               # one token: the state begins and is read at once
    (CHUNK - 1, 4),
    (CHUNK, 4),
    (CHUNK + 1, 4),       # the second chunk is one row behind a carry
    (3 * CHUNK + 5, 9),   # a state carried over three chunk boundaries
]


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("n_prompt,max_tokens", CASES)
def test_logits_of_every_served_token_are_the_references(made, n_prompt,
                                                         max_tokens,
                                                         use_kernel):
    prompt = prompt_of(n_prompt, n_prompt)
    answer, rows = serve_one(engine(made, use_kernel=use_kernel), prompt,
                             max_tokens)
    assert len(answer) == max_tokens == len(rows)
    ref = reference_rows(made, prompt, answer)
    assert worst_gap(rows, ref, n_prompt) < TOL
    # and the tokens are the reference's own first choices
    assert answer == [int(np.argmax(ref[n_prompt + i - 1]))
                      for i in range(max_tokens)]


@pytest.mark.parametrize("use_kernel", [False, True])
def test_a_gqa_group_of_five(made_five, use_kernel):
    prompt = prompt_of(55, 21)
    answer, rows = serve_one(
        engine(made_five, FIVE, use_kernel=use_kernel), prompt, 6)
    ref = reference_rows(made_five, prompt, answer, config=FIVE)
    assert worst_gap(rows, ref, 21) < TOL


def test_the_stated_precision_passes_and_float8_fails(made):
    prompt = prompt_of(7, 29)
    answer, rows = serve_one(engine(made), prompt, 8)
    stated = reference_rows(made, prompt, answer, mode="bf16")
    assert TOL < worst_gap(rows, stated, 29) < TOL_STATED
    low = reference_rows(made, prompt, answer, mode="fp8")
    assert worst_gap(rows, low, 29) > 2 * TOL_STATED


@pytest.mark.parametrize("use_kernel", [False, True])
def test_chunks_of_several_requests_in_one_bucket(made, use_kernel):
    """Two and more chunks ride one bucket of 16 rows: they do not see
    each other, nor the decode rows beside them."""
    eng = engine(made, use_kernel=use_kernel)
    got = record(eng)
    prompts = {eng.submit(prompt_of(100 + n, n), 6): prompt_of(100 + n, n)
               for n in (3, 30, 13, 22)}
    done = eng.run()
    for rid, prompt in prompts.items():
        ref = reference_rows(made, prompt, done[rid])
        assert worst_gap(got[rid], ref, len(prompt)) < TOL
    snap = eng.metrics.snapshot()
    # the branch's counts, summed over the 2 blocks: every prompt token a
    # chunk row, every token but a request's first a decode row, a chunk
    # at position 0 a request and the later ones continued
    assert snap["ssm_rows_prefill"] == 2 * (3 + 30 + 13 + 22)
    assert snap["ssm_rows_decode"] == 2 * 4 * 5
    assert snap["ssm_segments_started"] == 2 * 4
    assert snap["ssm_segments_continued"] == 2 * (3 + 1 + 2)
    eng.check_page_conservation()


def test_a_slot_used_again_by_a_shorter_request_sees_nothing_of_the_last(
        made):
    eng = engine(made, max_slots=1)
    got = record(eng)
    long, short = prompt_of(1, 30), prompt_of(2, 3)
    first = eng.submit(long, 8)
    eng.run()
    held = float(jnp.abs(eng._rec_kv[0]["ssm"]).max())
    assert held > 0                     # the slot holds the first's state
    second = eng.submit(short, 5)
    done = eng.run()
    assert eng._requests[first].slot is None
    ref = reference_rows(made, short, done[second])
    assert worst_gap(got[second], ref, 3) < TOL


def test_a_dirty_slot_is_read_as_zeros_at_position_zero(made):
    """Nothing clears a slot on the host: the step masks by position."""
    eng = engine(made)
    eng._rec_kv = tuple({k: jnp.full_like(v, 3.0) for k, v in layer.items()}
                        for layer in eng._rec_kv)
    prompt = prompt_of(3, 13)
    answer, rows = serve_one(eng, prompt, 5)
    assert worst_gap(rows, reference_rows(made, prompt, answer), 13) < TOL


@pytest.mark.parametrize("use_kernel", [False, True])
def test_preemption_frees_the_pages_and_prefills_the_state_again(
        made, use_kernel):
    # 16 pages: two sequences of 40 tokens do not fit beside each other
    eng = engine(made, num_pages=17, use_kernel=use_kernel)
    got = record(eng)
    free = eng.free_bytes()
    prompts = {eng.submit(prompt_of(200 + i, 26), 14): prompt_of(200 + i, 26)
               for i in range(3)}
    victim = eng.submit(prompt_of(300, 20), 30)
    for _ in range(12):
        eng.step()
        eng.check_page_conservation()
    eng.cancel(victim)
    done = eng.run()
    assert eng.metrics.preemptions >= 1
    assert eng.status(victim) is RequestStatus.CANCELLED
    for rid, prompt in prompts.items():
        assert eng.status(rid) is RequestStatus.COMPLETED
        # a preempted request re-prefills from position 0 into whatever
        # its new slot held: every token it was given, before and after,
        # is the reference's
        ref = reference_rows(made, prompt, done[rid])
        assert worst_gap(got[rid], ref, len(prompt)) < TOL
    assert eng.free_bytes() == free
    eng.check_page_conservation()


def test_idle_slots_keep_their_state_bit_for_bit(made):
    eng = engine(made)
    junk = np.random.default_rng(11)
    eng._rec_kv = tuple(
        {k: jnp.asarray(junk.normal(size=v.shape), jnp.float32)
         for k, v in layer.items()} for layer in eng._rec_kv)
    before = jax.tree.map(np.asarray, eng._rec_kv)
    rid = eng.submit(prompt_of(4, 19), 6)     # chunks with padding rows
    eng.step()
    slot = eng._requests[rid].slot
    eng.run()
    idle = [s for s in range(4) if s != slot]
    for was, now in zip(before, eng._rec_kv):
        for leaf in was:
            assert np.array_equal(np.asarray(now[leaf])[idle],
                                  was[leaf][idle]), leaf
            assert not np.array_equal(np.asarray(now[leaf])[slot],
                                      was[leaf][slot])


def test_non_finite_logits_fail_the_request_and_the_slot_serves_on(made):
    from paddle_tpu.serving import FaultPlan

    eng = engine(made, max_slots=1, faults=FaultPlan(seed=0))
    rid = eng.submit(prompt_of(5, 10), 6)
    eng.faults.nan_rids = {rid}
    eng.run()
    assert eng.status(rid) is RequestStatus.FAILED
    eng.faults.nan_rids = set()
    prompt = prompt_of(6, 9)
    answer, rows = serve_one(eng, prompt, 4)
    assert worst_gap(rows, reference_rows(made, prompt, answer), 9) < TOL
    eng.check_page_conservation()


# ---- what the rules are worth: each fault fails the stated tolerance --------

def faulty(eng, fault):
    """``eng`` with its model's ``mix`` handed a tick's layout, or a
    state, that breaks one rule."""
    mix = eng.model.mix

    def broken(params, layer, x, state, rows):
        rows = dict(rows)
        bd = rows["decode_rows"]
        if fault == "not carried between chunks":
            # every chunk starts from zeros, as if it began a sequence
            state = {k: v.at[rows["row_seq"][bd]].set(0.0)
                     for k, v in state.items()}
        elif fault == "not reset at position 0":
            rows["pos"] = rows["pos"] + 1
        elif fault == "advanced by a padding row":
            rows["live"] = jnp.ones_like(rows["live"])
        return mix(params, layer, x, state, rows)

    eng.model.mix = broken
    eng._step_fns.clear()
    return eng


@pytest.mark.parametrize("fault", ["not carried between chunks",
                                   "not reset at position 0",
                                   "advanced by a padding row"])
def test_a_state_that_breaks_a_rule_fails(made, fault):
    eng = faulty(engine(made, max_slots=1), fault)
    if fault == "not reset at position 0":
        eng._rec_kv = tuple({k: jnp.full_like(v, 0.5)
                             for k, v in layer.items()}
                            for layer in eng._rec_kv)
    prompt = prompt_of(12, 3 * CHUNK + 5)
    answer, rows = serve_one(eng, prompt, 6)
    ref = reference_rows(made, prompt, answer)
    assert worst_gap(rows, ref, len(prompt)) > TOL_STATED


def _without(config, key, index=None):
    """``config`` with one multiplier (an entry of one) back at 1."""
    if index is None:
        return {**config, key: 1.0}
    return {**config, key: [1.0 if i == index else m
                            for i, m in enumerate(config[key])]}


@pytest.mark.parametrize("key,index", [
    ("embedding_multiplier", None), ("lm_head_multiplier", None),
    ("key_multiplier", None), ("attention_in_multiplier", None),
    ("attention_out_multiplier", None), ("ssm_in_multiplier", None),
    ("ssm_out_multiplier", None), ("mlp_multipliers", 0),
    ("mlp_multipliers", 1)] + [("ssm_multipliers", i) for i in range(5)])
def test_a_multiplier_left_out_fails(made, key, index):
    """The tiny configuration moves every multiplier from 1: a program
    that leaves one out is the reference's forward with it at 1."""
    assert TINY[key] != 1 and (index is None or TINY[key][index] != 1)
    prompt = prompt_of(13, 21)
    answer, rows = serve_one(engine(made), prompt, 5)
    left_out = reference_rows(made, prompt, answer,
                              config=_without(TINY, key, index))
    assert worst_gap(rows, left_out, 21) > TOL_STATED


# ---- the third kind in the one manager --------------------------------------

def test_the_recurrent_kind_is_read_off_the_model():
    prog = FAMILY.serve_program(TINY, [None])
    kind = recurrent_state(prog["model"], 4)
    assert kind == RecurrentState(
        (0, 1), 4, (("conv", (3, 96), "float32"),
                    ("ssm", (4, 8, 16), "float32")))
    per_layer = 4 * (3 * 96 + 4 * 8 * 16)
    assert kind.bytes_per_slot() == 2 * per_layer
    assert kind.kv_bytes() == 4 * 2 * per_layer
    arrays = kind.init()
    assert len(arrays) == 2 and arrays[0]["ssm"].shape == (4, 4, 8, 16)
    assert arrays[1]["conv"].shape == (4, 3, 96)
    dense = DecoderLM(vocab_size=64, num_layers=3, num_heads=2, head_dim=8)
    assert recurrent_state(dense, 4) is None


def test_one_budget_is_divided_between_the_pages_and_the_states(made):
    prog = FAMILY.serve_program(TINY, [None])
    params = {name: made[ref] for name, ref in prog["names"].items()}
    budget = 120_000
    eng = ServingEngine(prog["model"], params, eos_id=VOCAB, page_size=PAGE,
                        pool_bytes=budget, max_pages_per_seq=24, max_slots=4,
                        buckets=(8,), prefill_chunk=CHUNK)
    kind = eng._recurrent
    assert split_pool_bytes(budget, (), kind) == budget - kind.kv_bytes()
    # both branches of a layer keep their state: the pool holds 2 layers
    assert eng.kv_cfg.num_layers == 2 and eng.cache is None
    total = eng.kv_cfg.kv_bytes() + kind.kv_bytes()
    assert budget - eng.kv_cfg.bytes_per_page() < total <= budget
    assert eng.free_bytes() == eng.pool.num_free * \
        eng.kv_cfg.bytes_per_page() + 4 * kind.bytes_per_slot()
    rid = eng.submit(prompt_of(1, 9), 50)
    for _ in range(4):
        eng.step()
    assert eng.free_bytes() == eng.pool.num_free * \
        eng.kv_cfg.bytes_per_page() + 3 * kind.bytes_per_slot()
    eng.cancel(rid)
    eng.run()
    with pytest.raises(EnforceError,
                       match=rf"{kind.kv_bytes()} bytes.*give the pool more"):
        split_pool_bytes(kind.kv_bytes(), (), kind)


def test_the_host_counts_the_states_that_hold_a_sequence(made):
    eng = engine(made)
    for n in (5, 17):
        eng.submit(prompt_of(n, n), 4)
    eng.run()
    snap = eng.metrics.snapshot()
    steps = snap["step_dispatches"]
    assert 0 < snap["state_slots_live"] <= 2 * steps
    assert snap["state_bytes_live"] == \
        snap["state_slots_live"] * eng._recurrent.bytes_per_slot()
    assert snap["full_kv_tokens_held"] > 0
    assert eng._kind_counted == ("full_kv_tokens_held", "state_slots_live",
                                 "state_bytes_live")


def test_a_lost_slot_is_a_state_leak(made):
    eng = engine(made)
    eng.scheduler._free_slots.pop()
    with pytest.raises(Exception, match="STATE-LEAK"):
        eng.check_page_conservation()


def test_a_model_without_a_recurrent_state_builds_the_step_it_built():
    model = DecoderLM(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                      num_kv_heads=2)
    eng = ServingEngine(model, model.init_params(jax.random.PRNGKey(0)),
                        eos_id=1, page_size=8, pool_bytes=100_000,
                        max_pages_per_seq=8, max_slots=4, buckets=(8,))
    assert eng._recurrent is None and eng._rec_kv == ()
    assert eng._kind_kv() == () and eng._donate_kv == (1,)
    assert eng.cache is not None and eng._kind_counted == ()
    out = jax.eval_shape(eng._step_fn(0, 1), eng.params, eng._kv,
                         eng._empty_tick(0, 1), eng._last_words())
    assert len(out) == 3
    assert "ssm" not in eng._step_fn(0, 1).lower(
        eng.params, eng._kv, eng._empty_tick(0, 1),
        eng._last_words()).as_text()


def test_the_step_names_the_branch_and_hands_the_state_through(made):
    eng = engine(made)
    args = (eng.params, eng._kv, eng._empty_tick(16, 1), eng._last_words(),
            eng._rec_kv)
    text = eng._step_fn(16, 1).lower(*args).as_text(debug_info=True)
    for scope in ("ssm.proj", "ssm.conv", "ssm.scan", "ssm.out", "ssd_step",
                  "ssd_chunks", "attn", "ffn", "head"):
        assert scope in text, scope
    out = jax.eval_shape(eng._step_fn(16, 1), *args)
    assert len(out) == 4 and len(out[3]) == 2
    assert out[3][0]["ssm"].shape == (4, 4, 8, 16)
    assert eng._donate_kv == (1, 4)


# ---- what refuses a model with a recurrent state ---------------------------------

@pytest.mark.parametrize("kw,says", [
    ({"prefix_cache": True}, "snapshot of it at page boundaries"),
    ({"spec_mode": "ngram", "spec_k": 2}, "already advanced"),
    ({"host_tier_bytes": 1 << 20}, "host tier"),
    ({"role": "prefill"}, "exports pages only"),
    ({"mesh": "a mesh"}, "no placement over a mesh"),
])
def test_what_cannot_hold_over_a_state_is_refused_by_name(made, kw, says):
    with pytest.raises(EnforceError, match=says):
        engine(made, **kw)


def test_a_block_model_with_a_state_is_refused(made):
    prog = FAMILY.serve_program(TINY, [None])
    model = prog["model"]
    model.block_length, model.denoise_steps, model.mask_token_id = 4, 2, 0
    params = {name: made[ref] for name, ref in prog["names"].items()}
    with pytest.raises(EnforceError, match="rewrites its current block"):
        ServingEngine(model, params, eos_id=VOCAB, page_size=PAGE,
                      num_pages=40, max_slots=2, buckets=(8,),
                      prefill_chunk=CHUNK)


def test_a_chain_is_not_handed_over(made):
    eng = engine(made)
    rid = eng.submit(prompt_of(9, 6), 8)
    for _ in range(4):
        eng.step()
    assert eng.migratable_rids() == []
    with pytest.raises(EnforceError, match="leave the slot's state behind"):
        export_chain(eng, rid)
    eng.run()
