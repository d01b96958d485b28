"""A stack that runs several times over shared weights behind
``ServingEngine``: a tiny ``LoopedLM`` (2 layers, hidden 64, 4 heads of
16, SwiGLU 96, 3 passes; seeded weights) against the benchmark's plain
reference (``benchmarks/references/ouro.py``: every pass a full forward,
no cache).  What is compared is LOGITS: the engine's row of the step that
produced a token (chunked prefill, a chunk carried through all passes in
its tick, then one token a tick through each pass's own cache layers)
against the reference's row of ONE full forward over the prompt and the
served tokens."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.platform.enforce import EnforceError
from paddle_tpu.serving import (DecoderLM, RequestStatus, ServingEngine,
                                export_chain)
from paddle_tpu.serving.engine import exit_distribution
from paddle_tpu.serving.kv_cache import pages_for_budget

pytestmark = pytest.mark.serving

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import cells, weights  # noqa: E402

REF = cells.load_module(os.path.join(BENCH, "references", "ouro.py"))
FAMILY = cells.load_module(os.path.join(BENCH, "families", "ouro.py"))
TINY = cells.load_json(os.path.join(BENCH, "tests", "configs",
                                    "tiny-ouro.json"))
VOCAB, LOOPS, LAYERS = (TINY["vocab_size"], TINY["total_ut_steps"],
                        TINY["num_hidden_layers"])
PAGE, CHUNK = 4, 8
PAD = 96              # rows of the reference's buffer
# Engine and reference are both float32 here and differ by the order of
# their sums alone (the engine's attention streams a pass's pages, the
# reference's is one dense softmax): 1e-6 of logits whose size is about 1,
# through 3 x 2 layers.
TOL = 3e-5
# What the program states is bfloat16 operands in the projections: the
# reference in that mode lies about 0.02 from the float32 one, inside
# TOL_STATED; every fault below (a pass on another pass's cache layers:
# 0.3 and more; the last pass dropped: 0.5 and more) and float8 operands
# (0.2) lie outside it.
TOL_STATED = 0.08


@pytest.fixture(scope="module")
def made():
    """The harness's leaves; the matrices (std 0.02, which at hidden 64
    makes the logits a whisper) scaled to keep their rows' size, so that
    the head and the gate spread."""
    made = dict(weights.make(FAMILY.leaves(TINY, "serve"), 20261004))
    for name, (shape, kind) in FAMILY.leaves(TINY, "serve").items():
        if kind == "matrix" and name != "wte":
            made[name] = made[name] * (shape[-2] ** -0.5 / weights.MATRIX_STD)
    return made


def program(made, config=TINY):
    prog = FAMILY.serve_program(config, [None])
    return prog["model"], {name: made[ref]
                           for name, ref in prog["names"].items()}


def engine(made, config=TINY, model=None, **kw):
    built, params = program(made, config)
    kw = {"page_size": PAGE, "num_pages": 80, "max_pages_per_seq": 24,
          "max_slots": 4, "buckets": (8, 16), "prefill_chunk": CHUNK, **kw}
    return ServingEngine(model or built, params, eos_id=VOCAB, **kw)


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


def buffer_of(prompt, answer):
    toks = np.zeros(PAD, np.int32)
    toks[:len(prompt) + len(answer)] = list(prompt) + list(answer)
    return jnp.asarray(toks), jnp.arange(PAD, dtype=jnp.int32)


def reference_rows(made, prompt, answer, mode="f32", config=TINY):
    """Row ``p - 1`` judges the token at position ``p``, as the serve
    driver reads it."""
    toks, pos = buffer_of(prompt, answer)
    rows = FAMILY.reference_logits(
        REF, config, weights.unflatten(made), toks, pos,
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


# ---- prefill, then decode through the cache -------------------------------------

# shorter than a chunk; one chunk to the row; three chunks
CASES = [(5, 6), (8, 4), (21, 9)]


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("n_prompt,max_tokens", CASES)
def test_logits_of_every_served_token_are_the_references(made, n_prompt,
                                                         max_tokens,
                                                         use_kernel):
    eng = engine(made, use_kernel=use_kernel)
    assert eng.kv_cfg.num_layers == LOOPS * LAYERS
    prompt = prompt_of(n_prompt, n_prompt)
    answer, rows = serve_one(eng, prompt, max_tokens)
    assert len(answer) == max_tokens == len(rows)
    ref = reference_rows(made, prompt, answer)
    assert worst_gap(rows, ref, n_prompt) < TOL
    eng.check_page_conservation()


def test_the_stated_precision_passes_and_float8_fails(made):
    prompt = prompt_of(3, 19)
    answer, rows = serve_one(engine(made), prompt, 8)
    assert worst_gap(rows, reference_rows(made, prompt, answer, "bf16"),
                     len(prompt)) < TOL_STATED
    assert worst_gap(rows, reference_rows(made, prompt, answer, "fp8"),
                     len(prompt)) > TOL_STATED


def test_the_last_pass_is_not_dropped(made):
    """``T`` passes against ``T - 1``: the engine's logits are the
    reference's at ``T`` and far from the reference's at ``T - 1``, and an
    engine built for ``T - 1`` passes serves other logits."""
    prompt = prompt_of(4, 13)
    answer, rows = serve_one(engine(made), prompt, 6)
    fewer = {**TINY, "total_ut_steps": LOOPS - 1}
    ref = reference_rows(made, prompt, answer, config=fewer)
    assert worst_gap(rows, ref, len(prompt)) > 0.5
    eng = engine(made, fewer)
    assert eng.kv_cfg.num_layers == (LOOPS - 1) * LAYERS
    got = record(eng)
    rid = eng.submit(prompt, 1)
    eng.run()
    assert float(np.abs(got[rid][0] - rows[0]).max()) > 0.5
    assert float(np.abs(got[rid][0] - ref[len(prompt) - 1]).max()) < TOL


# ---- a pass reads its OWN cache layers -------------------------------------------

def chunked_against_whole(made, rule=None, **kw):
    """The widest gap between a 21-token prompt's first logits served in
    three chunks of 8 and in one chunk of 32."""
    prompt, rows = prompt_of(5, 21), []
    for chunk in (CHUNK, 32):
        eng = engine(made, buckets=(32,), prefill_chunk=chunk, **kw)
        if rule is not None:
            eng._cache_layer = rule
        got = record(eng)
        rid = eng.submit(prompt, 4)
        eng.run()
        rows.append(np.stack(got[rid]))
    return float(np.abs(rows[0] - rows[1]).max()), rows


@pytest.mark.parametrize("use_kernel", [False, True])
def test_a_prompt_in_three_chunks_is_the_prompt_in_one(made, use_kernel):
    gap, rows = chunked_against_whole(made, use_kernel=use_kernel)
    assert gap < TOL
    prompt = prompt_of(5, 21)
    ref = reference_rows(made, prompt, [int(np.argmax(r)) for r in rows[0]])
    assert worst_gap(rows[0], ref, len(prompt)) < TOL


def test_the_cache_layer_map_is_what_ties_a_pass_to_its_keys(made):
    """The comparison above is the detector: with every pass on the FIRST
    pass's cache layers a whole prompt still comes out right (a pass
    writes its rows' K/V before it attends over them) and a later chunk
    reads what the LAST pass left of the earlier chunks, so three chunks
    differ from one; a map that only renames the cache layers (the passes
    in reverse) changes nothing."""
    gap, _ = chunked_against_whole(made, rule=lambda t, layer: layer)
    assert gap > 0.3
    gap, _ = chunked_against_whole(
        made, rule=lambda t, layer: (LOOPS - 1 - t) * LAYERS + layer)
    assert gap < TOL


# ---- one pass is the model the engine always served --------------------------------

def test_one_pass_is_loops_absent(made):
    """``loops = 1`` builds what a model that never said ``loops``
    builds: the same pool, the same step text, no counters of passes."""
    once = {**TINY, "total_ut_steps": 1}
    said, _ = program(made, once)
    absent, _ = program(made, once)
    assert type(said).__name__ == "LoopedLM"
    del absent.loops
    assert not hasattr(absent, "loops") and said.loops == 1
    texts, tokens = [], []
    for model in (said, absent):
        eng = engine(made, once, model=model)
        assert eng._loops == 1 and eng._loop_counted == ()
        assert eng.kv_cfg.num_layers == LAYERS
        assert "loop_passes" not in eng.metrics.snapshot()
        texts.append(eng._step_fn(16, 1).lower(
            eng.params, eng._kv, eng._empty_tick(16, 1),
            eng._last_words()).as_text(debug_info=True))
        rid = eng.submit(prompt_of(6, 11), 5)
        tokens.append(eng.run()[rid])
    assert texts[0] == texts[1] and "pass0" not in texts[0]
    assert tokens[0] == tokens[1]


def test_a_dense_model_builds_the_step_it_built():
    model = DecoderLM(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                      num_kv_heads=2)
    eng = ServingEngine(model, model.init_params(jax.random.PRNGKey(0)),
                        eos_id=1, page_size=8, pool_bytes=100_000,
                        max_pages_per_seq=8, max_slots=4, buckets=(8,))
    assert eng._loops == 1 and eng._loop_counted == ()
    assert eng.kv_cfg.num_layers == 2
    text = eng._step_fn(8, 1).lower(
        eng.params, eng._kv, eng._empty_tick(8, 1),
        eng._last_words()).as_text(debug_info=True)
    assert "pass0" not in text and "close" not in text


def test_the_step_names_its_passes_and_holds_each_leaf_once(made):
    eng = engine(made)
    lowered = eng._step_fn(16, 1).lower(
        eng.params, eng._kv, eng._empty_tick(16, 1), eng._last_words())
    text = lowered.as_text(debug_info=True)
    for scope in ("pass0/l0/attn/proj", "pass2/l1/ffn", "pass1/close",
                  "pass2/l1/attn", "head"):
        assert scope in text, scope
    assert f"pass{LOOPS}" not in text
    # the parameters enter once: one argument a leaf, none of them stacked
    # or tiled over the passes (a layer's two [64, 96] SwiGLU matrices)
    main = next(line for line in lowered.as_text().splitlines()
                if "func.func public @main(" in line)
    assert main.count("tensor<64x96xf32>") == 2 * LAYERS
    assert main.count("tensor<64xf32>") == 4 * LAYERS + 1


# ---- the exit gate ---------------------------------------------------------------

def test_the_exit_distribution_is_the_references_and_sums_to_one(made):
    prompt = prompt_of(7, 17)
    toks, pos = buffer_of(prompt, [])
    out = REF.forward(weights.unflatten(made), toks, pos, mode="f32",
                      block_rows=32, **FAMILY.arch(TINY))
    p_ref = np.asarray(out["exit_p"])
    assert p_ref.shape == (LOOPS, PAD)
    np.testing.assert_allclose(p_ref.sum(axis=0), 1.0, atol=1e-6)
    assert 0.05 < p_ref[0].min() and p_ref[0].max() < 0.95    # a live gate
    # the engine's distribution from the gate on each pass's (normed) rows
    _model, params = program(made)
    hs = REF.passes(weights.unflatten(made), toks, pos, mode="f32",
                    block_rows=32, **FAMILY.arch(TINY))
    lam = jnp.stack([jax.nn.sigmoid((h @ params["gate_w"])[:, 0]
                                    + params["gate_b"][0]) for h in hs])
    np.testing.assert_allclose(np.asarray(exit_distribution(lam)), p_ref,
                               atol=1e-5)
    # at the published threshold 1.0 nothing leaves before the last pass
    assert np.all(np.asarray(REF.exit_step(out["exit_p"], 1.0)) == LOOPS)
    assert np.any(np.asarray(REF.exit_step(out["exit_p"], 0.5)) < LOOPS)


def test_the_step_counts_its_passes_and_the_gate_on_its_decode_rows(made):
    eng = engine(made)
    prompt = prompt_of(8, 11)
    rid = eng.submit(prompt, 10)
    answer = eng.run()[rid]
    snap = eng.metrics.snapshot()
    assert snap["loop_passes"] == LOOPS * snap["step_dispatches"]
    # a decode row feeds token i of the answer at position n + i and
    # yields token i + 1: nine rows for ten tokens
    assert snap["exit_rows"] == len(answer) - 1
    toks, pos = buffer_of(prompt, answer)
    p = np.asarray(REF.forward(
        weights.unflatten(made), toks, pos, mode="f32", block_rows=32,
        **FAMILY.arch(TINY))["exit_p"])
    rows = slice(len(prompt), len(prompt) + len(answer) - 1)
    want = (p[:, rows] * np.arange(1, LOOPS + 1)[:, None]).sum(axis=0)
    assert abs(snap["exit_step_milli"] - 1e3 * want.sum()) \
        <= len(answer) - 1           # rounding: half a thousandth a row
    assert 1.0 < snap["exit_step_milli"] / snap["exit_rows"] / 1e3 < LOOPS


# ---- the scheduler's side: preemption, the prefix cache, the pages ----------------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_preemption_frees_the_pages_and_prefills_every_pass_again(
        made, use_kernel):
    # 16 pages: two sequences of 40 tokens do not fit beside each other
    eng = engine(made, num_pages=17, use_kernel=use_kernel,
                 prefix_cache=False)
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
        # a preempted request re-prefills every pass's cache layers from
        # position 0: every token it was given, before and after, is the
        # reference's
        ref = reference_rows(made, prompt, done[rid])
        assert worst_gap(got[rid], ref, len(prompt)) < TOL
    assert eng.free_bytes() == free
    eng.check_page_conservation()


def test_a_cached_prefix_holds_every_pass(made):
    """A page holds all ``loops x layers`` cache layers of its tokens, so
    a prefix hit skips every pass of the prefix."""
    eng = engine(made)
    assert eng.cache is not None
    prompt = prompt_of(9, 19)
    first, _ = serve_one(eng, prompt, 5)
    saved = eng.metrics.prefill_tokens_saved
    got = record(eng)
    rid = eng.submit(prompt, 5)
    again = eng.run()[rid]
    assert eng.metrics.prefill_tokens_saved - saved == 16   # 4 whole pages
    assert again == first
    ref = reference_rows(made, prompt, again)
    assert worst_gap(got[rid], ref, len(prompt)) < TOL
    eng.check_page_conservation()


def test_a_token_costs_every_pass_its_keys_and_values(made):
    """Pages for a ``pool_bytes`` fall by ``loops``, and the accounting
    after a drain reads the same one number."""
    once = {**TINY, "total_ut_steps": 1}
    budget = 1 << 20
    engines = [engine(made, config, num_pages=None, pool_bytes=budget)
               for config in (once, TINY)]
    plain, looped = engines
    assert looped.kv_cfg.num_layers == LOOPS * plain.kv_cfg.num_layers
    assert looped.kv_cfg.bytes_per_page() == \
        LOOPS * plain.kv_cfg.bytes_per_page()
    assert looped.kv_cfg.num_pages == plain.kv_cfg.num_pages // LOOPS == \
        pages_for_budget(budget, LOOPS * LAYERS, 4, 16, PAGE, "float32")
    assert looped._kv.k.shape == (LOOPS * LAYERS, looped.kv_cfg.num_pages,
                                  PAGE, 4 * 16)
    free = looped.free_bytes()
    assert free == looped.pool.num_free * looped.kv_cfg.bytes_per_page()
    rids = [looped.submit(prompt_of(20 + i, 9 + 7 * i), 6) for i in range(3)]
    for _ in range(3):
        looped.step()
    held = sum(len(r.pages) for r in looped.scheduler.running.values())
    assert held > 0 and looped.free_bytes() == \
        free - held * looped.kv_cfg.bytes_per_page()
    assert looped.healthz()["pages_total"] == looped.pool.num_usable
    looped.run()
    assert all(looped.status(r) is RequestStatus.COMPLETED for r in rids)
    looped.cache.flush()
    assert looped.free_bytes() == free
    looped.check_page_conservation()
    # the kernel's calls a step are the cache layers'
    eng = engine(made, use_kernel=True)
    eng.submit(prompt_of(1, 5), 3)
    eng.run()
    snap = eng.metrics.snapshot()
    assert snap["attn_kernel_calls"] == \
        LOOPS * LAYERS * snap["step_dispatches"]


# ---- what refuses a looped model ---------------------------------------------------

@pytest.mark.parametrize("kw,says", [
    ({"spec_mode": "ngram", "spec_k": 2}, "not been driven through a looped"),
    ({"host_tier_bytes": 1 << 20}, "host tier has not been driven"),
    ({"role": "prefill"}, "a looped model serves as 'unified'"),
    ({"mesh": "a mesh"}, "collective budget counts one pass"),
])
def test_what_has_not_been_driven_over_passes_is_refused_by_name(made, kw,
                                                                 says):
    with pytest.raises(EnforceError, match=says):
        engine(made, **kw)


@pytest.mark.parametrize("attrs,says", [
    ({"layer_window": lambda l: 8 if l else None}, "a ring for every pass"),
    ({"layer_state": lambda l: {"s": ((2,), jnp.float32)},
      "mix": lambda *a: None}, "a state for every pass"),
    ({"block_length": 4, "denoise_steps": 2, "mask_token_id": 0},
     "rewrites its current block at every denoising pass"),
    ({"loops": 0}, "loops must be at least 1"),
])
def test_a_looped_model_of_another_kind_is_refused(made, attrs, says):
    model, _ = program(made)
    for name, value in attrs.items():
        setattr(model, name, value)
    with pytest.raises(EnforceError, match=says):
        engine(made, model=model)


def test_a_chain_is_not_handed_over(made):
    eng = engine(made)
    rid = eng.submit(prompt_of(9, 6), 8)
    for _ in range(4):
        eng.step()
    with pytest.raises(EnforceError, match="a looped model is not handed"):
        export_chain(eng, rid)
    eng.run()


def test_a_model_of_loops_needs_no_gate(made):
    """``close_pass`` may say nothing of a gate: the passes are counted,
    no row is read."""
    model, _ = program(made)
    close = model.close_pass
    model.close_pass = lambda params, t, x: (close(params, t, x)[0], None)
    eng = engine(made, model=model)
    rid = eng.submit(prompt_of(2, 7), 4)
    eng.run()
    snap = eng.metrics.snapshot()
    assert eng.status(rid) is RequestStatus.COMPLETED
    assert snap["loop_passes"] == LOOPS * snap["step_dispatches"]
    assert snap["exit_rows"] == 0 == snap["exit_step_milli"]
