"""The one-token tick chooses its token on the device and is read a call
later (PR 40).

What is pinned here:

- **lag and parity**: a plain greedy engine's ``step()`` dispatches a
  tick and reads the one before, so ``on_token`` runs one call after the
  dispatch with ``_flying`` set meanwhile; an engine with a fault plan
  bound lands every tick in its own call; both serve
  ``greedy_decode_reference``'s tokens, and the lag costs one call
  (chunked prefill, a full-cover prefix-cache hit, a forced preemption,
  a mesh over CPU devices);
- **endings in the air**: ``cancel`` of a request with a step in the air,
  and an EOS that lands while a further row of its slot is in the air:
  nothing is emitted past it, pages are conserved;
- **the guards read flags the device made**: a NaN in one slot's decode
  row fails that request alone, a NaN in a chunk-final row rolls back only
  the failing chunk's cache entries;
- **what crosses to the host**: a tick's ``d2h_bytes`` are its words'
  bytes for greedy traffic, and a request that samples fetches its own
  slot's row and draws the tokens it drew before;
- **the counter**: ``steps_lagged`` beside ``step_dispatches``, for a
  ``DecoderLM`` and for a block model.
"""

import os
import sys

import jax
import numpy as np
import pytest

from paddle_tpu.ops.attention import mha_reference
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.serving import (DecoderLM, FaultPlan, RequestStatus,
                                SamplingParams, ServingEngine)
from paddle_tpu.serving.engine import greedy_decode_reference
from paddle_tpu.serving.speculate import next_token

from conftest import assert_serving_drained as assert_drained  # noqa: E402

pytestmark = pytest.mark.serving

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import cells, weights  # noqa: E402

V, EOS = 64, 1


@pytest.fixture(scope="module")
def lm():
    model = DecoderLM(vocab_size=V, num_layers=2, num_heads=4, head_dim=8,
                      max_positions=128)
    return model, model.init_params(jax.random.PRNGKey(7))


def engine(lm, **kw):
    model, params = lm
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_pages_per_seq", 12)
    kw.setdefault("max_slots", 4)
    kw.setdefault("buckets", (4, 8, 16))
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("eos_id", EOS)
    return ServingEngine(model, params, **kw)


def prompt_of(seed: int, n: int):
    return np.random.RandomState(seed).randint(2, V, size=n).tolist()


def drive(eng, submit):
    """Step ``eng`` to the end; ``submit(eng, call)`` may add requests
    before a call.  Returns per call (tokens seen so far, a step in the
    air) and the tokens each request's ``on_token`` saw."""
    seen, calls = {}, []
    while True:
        submit(eng, len(calls), seen)
        if not eng.has_work:
            return calls, seen
        eng.step()
        calls.append((sum(len(v) for v in seen.values()),
                      eng._flying is not None))


# ---- (a) lag and parity ------------------------------------------------------

def _chunked(eng, call, seen):
    if call == 0:         # 19 tokens: chunks of 8, 8 and 3
        for i, n in enumerate((19, 5)):
            p = prompt_of(40 + i, n)
            seen[tuple(p)] = []
            eng.submit(p, 7, on_token=seen[tuple(p)].append)


def _full_cover(eng, call, seen):
    # the second request's prompt is two whole pages the first one left
    # in the cache: a full-cover hit forks the last page and recomputes
    # one token, whose row is a chunk-final row like any other
    p = prompt_of(50, 8)
    if call == 0:
        seen[("a",) + tuple(p)] = []
        eng.submit(p, 5, on_token=seen[("a",) + tuple(p)].append)
    elif not eng.has_work and len(seen) == 1:
        assert len(eng.cache) == 2
        seen[("b",) + tuple(p)] = []
        eng.submit(p, 6, on_token=seen[("b",) + tuple(p)].append)


def _preempting(eng, call, seen):
    if call == 0:         # 4 + 12 tokens a request on 9 pages of 4
        for i in range(3):
            p = prompt_of(60 + i, 4)
            seen[tuple(p)] = []
            eng.submit(p, 12, on_token=seen[tuple(p)].append)


CASES = {
    "chunked-prefill": (_chunked, {}),
    "full-cover-cache-hit": (_full_cover, {}),
    "forced-preemption": (_preempting, dict(num_pages=10)),
    "mesh-of-2": (_chunked, dict(mesh=2)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_read_back_lags_its_dispatch_by_one_call(lm, case):
    submit, kw = CASES[case]
    kw = dict(kw)
    if "mesh" in kw:
        kw["mesh"] = make_mesh((kw["mesh"],), ("model",),
                               jax.devices()[:kw["mesh"]])
    eng, own = engine(lm, **kw), engine(lm, faults=FaultPlan(), **kw)
    calls, seen = drive(eng, submit)
    ticks, want = drive(own, submit)
    model, params = lm
    assert seen and list(seen) == list(want)
    for key, toks in seen.items():
        prompt = [t for t in key if not isinstance(t, str)]
        assert toks == want[key] == greedy_decode_reference(
            model, params, prompt, len(toks), EOS), key
        assert len(toks) in (5, 6, 7, 12) or toks[-1] == EOS
    # a fault plan lands every tick in its own call; a plain engine's
    # first call emits nothing and leaves its step in the air
    assert not any(flying for _, flying in ticks)
    assert own.metrics.steps_lagged == 0
    # (a prompt inside one chunk yields its first token in the control's
    # first call)
    assert calls[0] == (0, True) and ticks[0][0] > 0
    assert calls[-1] == (ticks[-1][0], False)
    busy = [i for i, (_, flying) in enumerate(calls) if flying]
    if case == "full-cover-cache-hit":
        # two runs, each one call longer than the control's
        assert len(calls) == len(ticks) + 2
        assert eng.metrics.cow_forks == own.metrics.cow_forks == 1
    elif case == "forced-preemption":
        assert eng.metrics.preemptions > 0 and own.metrics.preemptions > 0
        assert len(calls) >= len(ticks) + 1
    else:
        assert len(calls) == len(ticks) + 1      # the lag costs one call
        # what the control emitted in call k, the engine emitted in k + 1
        assert [n for n, _ in calls[1:]] == [n for n, _ in ticks]
        assert busy == list(range(len(calls) - 1))
    # the words came from the device and from the zeros alike: one
    # program a bucket, compiled once
    assert all(fn._cache_size() == 1 for fn in eng._step_fns.values())
    a, b = eng.metrics.snapshot(), own.metrics.snapshot()
    assert a["tokens_generated"] == b["tokens_generated"]
    if case != "forced-preemption":
        for name in ("step_dispatches", "decode_rows", "prefill_tokens"):
            assert a[name] == b[name], name
    assert_drained(eng)
    assert_drained(own)


# ---- (b) endings with a step in the air --------------------------------------

def test_cancelled_with_a_step_in_the_air(lm):
    eng = engine(lm, max_slots=1)
    seen = []
    prompt = prompt_of(70, 6)
    rid = eng.submit(prompt, 12, on_token=seen.append)
    while len(seen) < 3:
        eng.step()
    assert eng._flying is not None and eng._requests[rid].pending == 1
    assert eng.cancel(rid)
    eng.check_page_conservation()
    n = len(seen)
    other = prompt_of(71, 5)
    after = eng.submit(other, 6)            # takes the slot and the pages
    out = eng.run()
    model, params = lm
    assert len(seen) == n and rid not in out
    assert eng.status(rid) is RequestStatus.CANCELLED
    assert out[after] == greedy_decode_reference(model, params, other, 6, EOS)
    assert not eng.has_work and eng._flying is None
    assert_drained(eng)


def test_an_eos_lands_with_a_further_row_in_the_air(lm):
    """EOS is known when the words arrive, a call after the slot's next
    row was dispatched: that row is passed over, nothing is emitted past
    the EOS, and its slot and pages serve the next request."""
    model, params = lm
    prompt, other = prompt_of(72, 6), prompt_of(73, 7)
    full = greedy_decode_reference(model, params, prompt, 12, EOS)
    rest = greedy_decode_reference(model, params, other, 6, EOS)
    eos = next(t for t in full[2:] if t not in rest and t not in full[:2])
    cut = full[:full.index(eos) + 1]
    assert 2 < len(cut) < len(full)
    eng = engine(lm, eos_id=eos, max_slots=1)
    seen, in_air = [], []

    def on_token(tok):
        seen.append(tok)
        flight = eng._flying
        in_air.append(flight is not None and any(
            p[0].rid == rid for p in flight.passes))

    rid = eng.submit(prompt, 12, on_token=on_token)
    after = eng.submit(other, 6)
    out = eng.run()
    assert seen == out[rid] == cut
    assert in_air[-1]          # a further row of the slot was in the air
    assert out[after] == rest
    assert eng.metrics.tokens_generated == len(cut) + len(rest)
    assert_drained(eng)


# ---- (c) the guards read what the device flagged -----------------------------

def test_a_nan_in_a_decode_row_fails_its_slot_alone(lm):
    """Position 9's embedding is NaN: the request that decodes through
    it fails when the flag arrives, its batchmate, which ends before,
    is served whole (no fault plan: the flag is the device's)."""
    model, params = lm
    bad = dict(params)
    bad["pos"] = params["pos"].at[9].set(np.nan)
    eng = engine((model, bad))
    long, short = prompt_of(74, 6), prompt_of(75, 3)
    seen = []
    a = eng.submit(long, 10, on_token=seen.append)
    b = eng.submit(short, 5)
    out = eng.run()
    assert eng.status(a) is RequestStatus.FAILED
    assert eng.status(b) is RequestStatus.COMPLETED
    # positions 6, 7, 8 yielded tokens; the row at position 9 did not
    want = greedy_decode_reference(model, params, long, 4, EOS)
    assert seen == want[:len(seen)] and len(seen) == 4
    assert out[b] == greedy_decode_reference(model, params, short, 5, EOS)
    assert eng.metrics.failed == 1 and eng.metrics.steps_lagged > 0
    assert_drained(eng)


def test_a_nan_in_a_chunk_final_row_forgets_that_chunks_pages_only(lm):
    """Token 7's embedding is infinite and stands in a prompt's third
    chunk: the two chunks before it passed their guards and stay in the
    cache, the third chunk's pages are forgotten, the request fails, and
    a sharer of the clean pages is served."""
    model, params = lm
    bad = dict(params)
    bad["emb"] = params["emb"].at[7].set(np.inf)
    eng = engine((model, bad), prefill_chunk=4, buckets=(4, 8))
    clean = [t if t != 7 else 8 for t in prompt_of(76, 8)]
    a = eng.submit(clean + [7, 9], 4)              # chunk 3 is poisoned
    while not eng.status(a).terminal:
        eng.step()
    assert eng.status(a) is RequestStatus.FAILED
    assert len(eng.cache) == 2                     # the vouched pages
    tail = [t if t != 7 else 8 for t in prompt_of(77, 3)]
    b = eng.submit(clean + tail, 5)
    out = eng.run()
    assert eng._requests[b].cached_len == 8        # it stitched them
    assert out[b] == greedy_decode_reference(model, bad, clean + tail, 5,
                                             EOS)
    assert eng.metrics.steps_lagged > 0
    assert_drained(eng)


# ---- (d) what crosses to the host --------------------------------------------

def test_a_greedy_ticks_read_back_is_its_words(lm):
    eng = engine(lm)
    for i, n in enumerate((11, 3)):
        eng.submit(prompt_of(80 + i, n), 6)
    eng.run()
    m = eng.metrics
    # a choice and a finite flag for each slot's decode row and
    # chunk-final row, int32: nothing else comes down
    assert m.d2h_bytes == m.step_dispatches * 2 * (4 + 4) * 4
    assert m.steps_lagged == m.step_dispatches - 1


def test_a_sampling_request_fetches_its_own_row_and_draws_as_before(lm):
    """A request that samples lands its ticks in their own call, fetches
    its slot's row of the logits alone, and draws what the host drew
    from the same float32 logits before the choice moved to the device:
    ``next_token`` over the reference's logits, position by position."""
    model, params = lm
    sp = SamplingParams(temperature=0.8, top_k=12, seed=1234)
    prompt, other = prompt_of(82, 6), prompt_of(83, 5)
    eng = engine(lm)
    rid = eng.submit(prompt, 6, sampling=sp)
    greedy = eng.submit(other, 6)
    flying = []
    while eng.has_work:
        eng.step()
        flying.append(eng._flying is not None)
    assert not any(flying[:6])      # (while the request that samples ran)
    got = eng.result(rid)
    twin = engine(lm)               # a replay draws the same tokens
    rid2 = twin.submit(prompt, 6, sampling=sp)
    twin.run()
    assert twin.result(rid2) == got and len(got) == 6
    assert got != greedy_decode_reference(model, params, prompt, 6, EOS)
    assert eng.result(greedy) == greedy_decode_reference(
        model, params, other, 6, EOS)
    m = eng.metrics
    words = 2 * (4 + 4) * 4
    # six rows of V float32 for the six sampled tokens, beside the words
    assert m.d2h_bytes == m.step_dispatches * words + 6 * V * 4
    # each drawn token is next_token's over the row the oracle's forward
    # gives (no cache, the whole history again)
    toks = list(prompt)
    for i, tok in enumerate(got):
        x = model.embed(params, np.asarray(toks)[None],
                        np.arange(len(toks))[None])
        for l in range(model.num_layers):
            q, k, v = model.qkv(params, l, x)
            x = model.attn_out(params, l, mha_reference(q, k, v,
                                                        causal=True), x)
        row = np.asarray(model.logits(params, x[0, -1]))
        assert next_token(row, sp, i) == tok, i
        toks.append(tok)


# ---- (e) the counter ----------------------------------------------------------

@pytest.mark.parametrize("kind", ["decoder-lm", "block-model"])
@pytest.mark.parametrize("plan", ["plain", "fault-plan"])
def test_steps_lagged_counts_the_steps_read_a_call_later(lm, kind, plan):
    kw = dict(faults=FaultPlan()) if plan == "fault-plan" else {}
    if kind == "block-model":
        # the tiny block model of tests/test_serving_block.py
        family = cells.load_module(os.path.join(BENCH, "families",
                                                "sdar_moe.py"))
        tiny = cells.load_json(os.path.join(BENCH, "tests", "configs",
                                            "tiny-sdar.json"))
        made = weights.make(family.leaves(tiny, "serve"), 20260930)
        prog = family.serve_program(tiny, [None])
        params = {name: made[ref] for name, ref in prog["names"].items()}
        eng = ServingEngine(prog["model"], params,
                            eos_id=tiny["vocab_size"], page_size=16,
                            num_pages=40, max_pages_per_seq=4, max_slots=3,
                            buckets=(16,), prefill_chunk=8, **kw)
        prompts = [[int(t) for t in np.random.default_rng(90 + i).integers(
            0, tiny["vocab_size"] - 1, n)] for i, n in enumerate((8, 12))]
    else:
        eng = engine(lm, **kw)
        prompts = [prompt_of(90, 11), prompt_of(91, 3)]
    for p in prompts:
        eng.submit(p, 8)
    eng.run()
    snap = eng.metrics.snapshot()
    # (a block model's 8 tokens are two blocks of two ticks, the first
    # beside the prompt's last chunk)
    assert snap["step_dispatches"] >= 5
    if plan == "fault-plan":
        assert snap["steps_lagged"] == 0
    else:
        # every step but the last, which the call after it read with
        # nothing left to dispatch
        assert snap["steps_lagged"] == snap["step_dispatches"] - 1
    assert_drained(eng)


def test_a_step_before_a_possible_preemption_lands_first(lm):
    """Where growth could preempt, the flight lands before the
    scheduler runs (a preempted request re-prefills from tokens the host
    must have): those steps do not count as lagged, and the tokens are
    the oracle's."""
    model, params = lm
    eng = engine(lm, num_pages=10)
    prompts = [prompt_of(60 + i, 4) for i in range(3)]
    rids = [eng.submit(p, 12) for p in prompts]
    out = eng.run()
    m = eng.metrics
    assert m.preemptions > 0
    assert 0 < m.steps_lagged < m.step_dispatches - 1
    for rid, p in zip(rids, prompts):
        assert out[rid] == greedy_decode_reference(model, params, p, 12,
                                                   EOS)
    assert_drained(eng)
