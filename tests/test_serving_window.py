"""Window layers beside full ones behind ``ServingEngine``: a tiny
``WindowMoeLM`` (5 layers: full + dense MLP, three window layers, full; 6
and 8 query heads on 2 KV heads; 8 of 16 experts held, top-4, a shared
expert; window 12; seeded weights) against the benchmark's plain
reference (``benchmarks/references/laguna.py``).  What is compared is
LOGITS: the engine's row of the step that produced a token against the
reference's row of one full forward over the prompt and the served
tokens."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.rotary import rotary, rotary_lanes, yarn_inv_freq
from paddle_tpu.platform.enforce import EnforceError
from paddle_tpu.serving import (DecoderLM, RequestStatus, ServingEngine,
                                export_chain)
from paddle_tpu.serving.decode_attention import (BLOCK_ROWS,
                                                 ragged_paged_attention)
from paddle_tpu.serving.kv_cache import (KVPages, LayerKind, layer_kinds,
                                         layer_pages, pages_for_budget,
                                         window_pages)

pytestmark = pytest.mark.serving

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import cells, weights  # noqa: E402

from conftest import WALK_CASES, WALK_MIXES, walk_batch  # noqa: E402

REF = cells.load_module(os.path.join(BENCH, "references", "laguna.py"))
FAMILY = cells.load_module(os.path.join(BENCH, "families", "laguna.py"))
TINY = cells.load_json(os.path.join(BENCH, "tests", "configs",
                                    "tiny-laguna.json"))
VOCAB, WINDOW = TINY["vocab_size"], TINY["sliding_window"]
PAGE, CHUNK = 4, 8
PAD = 96              # rows of the reference's buffer
# Engine and reference are both float32 here and differ by the order of
# their sums alone (the engine's attention streams pages, the expert layer
# sorts rows): a few 1e-6 of logits whose size is about 1.  Float8
# operands move them by 1e-2 and more, which this has to refuse.
TOL = 2e-4


@pytest.fixture(scope="module")
def made():
    return weights.make(FAMILY.leaves(TINY, "serve"), 20261001)


def engine(made, **kw):
    prog = FAMILY.serve_program(TINY, [None])
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


# ---- prefill in chunks, then decoding, through the two kinds of state ------

CASES = [
    # prompt length, max_tokens
    (5, 6),      # shorter than the window, and staying inside it
    (9, 8),      # shorter than the window, decoding across it
    (12, 4),     # exactly the window
    (14, 6),     # crossing the window inside the second chunk
    (31, 8),     # longer than the window: four chunks
    (60, 10),    # five windows: the ring has wrapped many times
]


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("n_prompt,max_tokens", CASES)
def test_logits_of_every_served_token_are_the_references(made, n_prompt,
                                                         max_tokens,
                                                         use_kernel):
    eng = engine(made, use_kernel=use_kernel)
    got = record(eng)
    prompt = prompt_of(n_prompt, n_prompt)
    rid = eng.submit(prompt, max_tokens)
    answer = eng.run()[rid]
    assert len(answer) == max_tokens == len(got[rid])
    ref = reference_rows(made, prompt, answer)
    assert worst_gap(got[rid], ref, n_prompt) < TOL
    # and the tokens are the reference's own first choices
    assert answer == [int(np.argmax(ref[n_prompt + i - 1]))
                      for i in range(max_tokens)]


def test_float8_operands_fail_the_tolerance(made):
    eng = engine(made)
    got = record(eng)
    prompt = prompt_of(7, 31)
    rid = eng.submit(prompt, 8)
    answer = eng.run()[rid]
    low = reference_rows(made, prompt, answer, mode="fp8")
    assert worst_gap(got[rid], low, 31) > 10 * TOL


@pytest.mark.parametrize("use_kernel", [False, True])
def test_a_batch_of_mixed_lengths_with_chunks_in_flight(made, use_kernel):
    eng = engine(made, use_kernel=use_kernel)
    got = record(eng)
    prompts = {eng.submit(prompt_of(100 + n, n), 6): prompt_of(100 + n, n)
               for n in (3, 40, 13, 22)}
    done = eng.run()
    for rid, prompt in prompts.items():
        ref = reference_rows(made, prompt, done[rid])
        assert worst_gap(got[rid], ref, len(prompt)) < TOL
    eng.check_page_conservation()


# ---- the ring: what a window layer holds, and that it is given back --------

def test_a_long_sequence_holds_no_more_than_the_rings_bound(made):
    eng = engine(made, max_pages_per_seq=32)
    (ring,) = eng._rings
    # window + the chunk in flight + page rounding
    assert ring.ring_pages == -(-(WINDOW + CHUNK - 1) // PAGE) + 1
    assert ring.ring_pages * PAGE <= WINDOW + CHUNK + PAGE
    n = 8 * WINDOW
    rid = eng.submit(prompt_of(1, n), 8)
    held = []
    while eng.has_work:
        eng.step()
        req = eng._requests[rid]
        if req.status is RequestStatus.RUNNING:
            # the full layers hold every page of it, the window layers'
            # pages are the slot's ring and nothing else
            held.append((len(req.pages),
                         int(ring.tokens_held(req.cache_len))))
    assert max(p for p, _ in held) == -(-(n + 8) // PAGE)
    assert max(t for _, t in held) <= WINDOW - 1 + PAGE - 1
    m = eng.metrics.snapshot()
    assert m["window_pages_released"] == int(ring.released(n + 7))
    assert 0 < m["window_kv_tokens_held"] < m["full_kv_tokens_held"]
    assert m["window_kv_tokens_live"] <= m["window_kv_tokens_held"] + \
        CHUNK * m["step_dispatches"]
    eng.check_page_conservation()


def test_the_kinds_visit_counters_against_a_count_by_hand(made):
    """One request through the kernel path (4 slots, pages of 4, a window
    of 12): a tick's visits are, in the 2 full layers, the pages up to its
    decode row's (or its chunk's last row's) and one step for each of the
    blocks that see nothing; in the 3 window layers the same from the
    first page the window of its lowest row reaches."""
    eng = engine(made, use_kernel=True)
    eng.submit(prompt_of(3, 5), 24)
    m = eng.metrics
    names = ("window_kernel_calls", "window_grid_cells", "window_live_cells")
    seen = []
    for _ in range(20):
        before = [m.attn_grid_cells, m.attn_live_cells, m.attn_pages_needed,
                  *(m.model_counters[n] for n in names)]
        eng.step()
        eng.land()
        after = [m.attn_grid_cells, m.attn_live_cells, m.attn_pages_needed,
                 *(m.model_counters[n] for n in names)]
        seen.append(tuple(b - a for a, b in zip(before, after)))
    # tick 0: the chunk of 5 rows (positions 0-4) in the bucket's tall
    # block, 4 decode blocks that see nothing; then a decode row at
    # position 5, 6, ...: 3 idle blocks
    rows = [(0, 4, 4)] + [(p, p, 3) for p in range(5, 24)]
    want = []
    for lo, hi, idle in rows:
        full = hi // PAGE + 1
        win = hi // PAGE - max(lo - WINDOW + 1, 0) // PAGE + 1
        want.append((2 * (full + idle), 2 * full, 2 * full,
                     3, 3 * (win + idle), 3 * win))
    assert seen == want
    assert seen[-1][0] > seen[-1][4] * 2 // 3      # the window cuts visits
    eng.run()


def test_one_budget_is_divided_between_the_kinds(made):
    prog = FAMILY.serve_program(TINY, [None])
    params = {name: made[ref] for name, ref in prog["names"].items()}
    budget = 300_000
    eng = ServingEngine(prog["model"], params, eos_id=VOCAB, page_size=PAGE,
                        pool_bytes=budget, max_pages_per_seq=24, max_slots=4,
                        buckets=(8,), prefill_chunk=CHUNK)
    (ring,) = eng._rings
    assert ring.cfg.num_layers == 3 and eng.kv_cfg.num_layers == 2
    assert eng._kv.k.shape[0] == 2 and eng._ring_kv[0].k.shape[0] == 3
    assert ring.cfg.num_pages == 1 + 4 * ring.ring_pages
    total = eng.kv_cfg.kv_bytes() + ring.kv_bytes()
    assert budget - eng.kv_cfg.bytes_per_page() < total <= budget
    assert eng.free_bytes() == eng.pool.num_free * \
        eng.kv_cfg.bytes_per_page() + 4 * ring.bytes_per_slot()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_preemption_cancellation_and_finishing_give_everything_back(
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
    eng.check_page_conservation()
    done = eng.run()
    assert eng.metrics.preemptions >= 1
    assert eng.status(victim) is RequestStatus.CANCELLED
    for rid, prompt in prompts.items():
        assert eng.status(rid) is RequestStatus.COMPLETED
        # a preempted request re-prefills into its new slot's ring: every
        # token it was given, before and after, is the reference's
        ref = reference_rows(made, prompt, done[rid])
        assert worst_gap(got[rid], ref, len(prompt)) < TOL
    assert eng.free_bytes() == free
    eng.check_page_conservation()


def test_a_lost_slot_is_a_ring_leak(made):
    eng = engine(made)
    eng.scheduler._free_slots.pop()
    with pytest.raises(Exception, match="RING-LEAK"):
        eng.check_page_conservation()


def test_non_finite_logits_scrub_the_slots_ring(made):
    from paddle_tpu.serving import FaultPlan

    eng = engine(made, faults=FaultPlan(seed=0))
    rid = eng.submit(prompt_of(5, 10), 6)
    eng.faults.nan_rids = {rid}
    eng.run()
    assert eng.status(rid) is RequestStatus.FAILED
    assert float(jnp.abs(eng._ring_kv[0].k).max()) == 0.0
    eng.check_page_conservation()


# ---- what refuses a model with window layers --------------------------------

@pytest.mark.parametrize("kw,says", [
    ({"prefix_cache": True}, "prefix cache"),
    ({"spec_mode": "ngram", "spec_k": 2}, "speculative"),
    ({"kv_dtype": "int8"}, "int8"),
    ({"host_tier_bytes": 1 << 20}, "host tier"),
    ({"role": "prefill"}, "chain"),
    ({"mesh": "a mesh"}, "tensor-parallel"),
])
def test_refused_at_construction_with_a_message(made, kw, says):
    with pytest.raises(EnforceError, match=says):
        engine(made, **kw)


def test_the_prefix_cache_is_not_built_and_a_chain_is_not_handed_over(made):
    eng = engine(made)
    assert eng.cache is None
    rid = eng.submit(prompt_of(2, 9), 6)
    for _ in range(4):
        eng.step()
    assert eng.migratable_rids() == []
    with pytest.raises(EnforceError, match="window layers"):
        export_chain(eng, rid)
    eng.run()


# ---- the kernel and its references with a window ----------------------------

def _pool(rng, layers, pages, page, kvh, d):
    shape = (layers, pages, page, kvh * d)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


def _blocks(seq, positions):
    out = []
    for i in range(0, max(len(positions), 1), BLOCK_ROWS):
        blk = list(positions[i:i + BLOCK_ROWS])
        out += [(seq, p) for p in blk + [-1] * (BLOCK_ROWS - len(blk))]
    return out


@pytest.mark.parametrize("group", [3, 4])      # 6 : 2 and 8 : 2 heads
@pytest.mark.parametrize("ring", [True, False])
def test_kernel_with_a_window_against_its_reference(group, ring):
    rng = np.random.default_rng(group)
    layers, page, kvh, d, window, slots = 2, 8, 2, 16, 20, 4
    width = 6 if ring else 12
    k, v = _pool(rng, layers, 1 + slots * width, page, kvh, d)
    table = jnp.asarray((1 + np.arange(slots * width)).reshape(slots, width),
                        jnp.int32)
    # a decode row far beyond the ring's length, one inside the window, a
    # chunk whose rows cross the window, an empty slot
    far = 70 if ring else 90
    rows = _blocks(0, [far]) + _blocks(1, [3]) + \
        _blocks(2, list(range(14, 30))) + _blocks(3, [])
    row_seq = jnp.asarray([r[0] for r in rows], jnp.int32)
    qpos = jnp.asarray([r[1] for r in rows], jnp.int32)
    lens = jnp.asarray([far + 1, 4, 30, 0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(len(rows), kvh * group, d)),
                    jnp.float32)
    real = np.asarray(qpos) >= 0
    outs = [np.asarray(ragged_paged_attention(
        q, k, v, table, lens, row_seq, qpos, layer=1, window=window,
        use_kernel=uk, interpret=True))[real] for uk in (False, True)]
    np.testing.assert_allclose(outs[1], outs[0], atol=2e-6)
    if not ring:
        # a table that never wraps: the window is a mask over the plain
        # gather and nothing else
        kl, vl, _, _ = layer_pages(KVPages(k, v, head_dim=d), 1)
        pt = table[row_seq]
        kk = jnp.repeat(kl[pt].reshape(len(rows), -1, kvh, d), group, 2)
        vv = jnp.repeat(vl[pt].reshape(len(rows), -1, kvh, d), group, 2)
        tok = jnp.arange(kk.shape[1])[None]
        live = (tok <= qpos[:, None]) & (tok > qpos[:, None] - window)
        s = jnp.einsum("thd,tkhd->thk", q, kk) * d ** -0.5
        p = jax.nn.softmax(jnp.where(live[:, None], s, -1e30), axis=-1)
        hand = np.asarray(jnp.einsum("thk,tkhd->thd", p, vv))[real]
        np.testing.assert_allclose(outs[0], hand, atol=2e-6)


@pytest.mark.parametrize("mix,group,pool,k1", WALK_CASES)
def test_the_walk_under_a_window_matches_the_reference(monkeypatch, mix,
                                                       group, pool, k1):
    """The mixes of ``test_serving_ragged`` (decode rows in short blocks, the
    bucket's rows regrouped into tall ones) in a window layer: the table is
    a ring as long as the step's most rows of one sequence need, a run
    starts at the first page its lowest row's window reaches, and every
    real row is the reference's."""
    from paddle_tpu.serving import decode_attention

    decode, chunks, bucket, tall = WALK_MIXES[mix]
    window, page = 20, 8
    monkeypatch.setattr(decode_attention, "_TALL_SCORE_ROWS", tall * group)
    ring = window_pages(window, max([k1] + [n for n, _ in chunks]), page,
                        1 << 20)
    batch = walk_batch(np.random.default_rng(group), decode, chunks, k1=k1,
                       group=group, pool=pool, bucket=bucket, page=page,
                       width=ring)
    q = batch.pop("q")
    rest = [batch.pop(n) for n in ("k_pool", "v_pool", "page_table",
                                   "kv_lens", "row_seq", "qpos")]
    td = batch.pop("decode_rows")
    got = np.asarray(ragged_paged_attention(
        q, *rest, **batch, window=window, use_kernel=True, interpret=True,
        decode_rows=td))
    want = np.asarray(ragged_paged_attention(
        q, *rest, **batch, window=window, use_kernel=False))
    real = np.asarray(rest[-1]) >= 0
    np.testing.assert_allclose(got[real], want[real], rtol=2e-5, atol=2e-5)
    assert np.isfinite(got).all()


def test_a_window_wider_than_the_sequence_is_the_full_mask():
    rng = np.random.default_rng(0)
    k, v = _pool(rng, 1, 9, 8, 2, 16)
    table = jnp.asarray((1 + np.arange(8)).reshape(2, 4), jnp.int32)
    rows = _blocks(0, [25]) + _blocks(1, list(range(8, 16)))
    row_seq = jnp.asarray([r[0] for r in rows], jnp.int32)
    qpos = jnp.asarray([r[1] for r in rows], jnp.int32)
    lens = jnp.asarray([26, 16], jnp.int32)
    q = jnp.asarray(rng.normal(size=(len(rows), 4, 16)), jnp.float32)
    real = np.asarray(qpos) >= 0
    for uk in (False, True):
        full, wide = (np.asarray(ragged_paged_attention(
            q, k, v, table, lens, row_seq, qpos, layer=0, window=w,
            use_kernel=uk, interpret=True))[real] for w in (None, 64))
        np.testing.assert_allclose(wide, full, atol=2e-6)


def test_window_pages_bounds_what_a_block_can_see():
    assert window_pages(512, 1, 128, 130) == 5
    assert window_pages(512, BLOCK_ROWS, 128, 130) == 6
    assert window_pages(512, BLOCK_ROWS, 128, 4) == 4
    for window, rows, page in ((12, 8, 4), (512, 8, 128), (7, 1, 8)):
        worst = max((p + rows - 1) // page - max(p - window + 1, 0) // page
                    + 1 for p in range(4 * window))
        assert worst <= window_pages(window, rows, page, 1 << 20)


# ---- the kinds, and a model that has one ------------------------------------

def test_layer_kinds_puts_full_attention_first():
    prog = FAMILY.serve_program(TINY, [None])
    assert layer_kinds(prog["model"]) == (
        LayerKind(None, (0, 4)), LayerKind(WINDOW, (1, 2, 3)))
    dense = DecoderLM(vocab_size=64, num_layers=3, num_heads=2, head_dim=8)
    assert layer_kinds(dense) == (LayerKind(None, (0, 1, 2)),)
    assert window_pages(512, 512, 128, 130) == 9      # a ring's length
    assert window_pages(512, 1 << 30, 128, 130) == 130


def test_a_model_without_windows_builds_the_pool_it_always_built():
    model = DecoderLM(vocab_size=64, num_layers=3, num_heads=4, head_dim=8,
                      num_kv_heads=2)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, eos_id=1, page_size=8,
                        pool_bytes=200_000, max_pages_per_seq=8, max_slots=4,
                        buckets=(8,))
    assert eng._rings == () and eng._ring_kv == ()
    assert eng.kv_cfg.num_layers == 3
    assert eng.kv_cfg.num_pages == pages_for_budget(
        200_000, 3, 4, 8, 8, "float32", num_kv_heads=2)
    assert eng._kv.k.shape == (3, eng.kv_cfg.num_pages, 8, 16)
    assert eng.cache is not None
    assert eng._kind_counted == ()
    assert not any("window" in k for k in eng.metrics.snapshot())
    # the step's arguments and results are what they were: parameters,
    # the pool, the tick's buffer, the last words -> words, logits, pool
    step = eng._step_fn(0, 1)
    out = jax.eval_shape(step, eng.params, eng._kv, eng._empty_tick(0, 1),
                         eng._last_words())
    assert len(out) == 3 and isinstance(out[2], KVPages)
    text = step.lower(eng.params, eng._kv, eng._empty_tick(0, 1),
                      eng._last_words()).as_text()
    assert "attn.full" not in text and "attn.window" not in text


def test_the_step_names_the_kinds_of_a_model_that_has_two(made):
    eng = engine(made)
    lowered = eng._step_fn(0, 1).lower(
        eng.params, eng._kv, eng._empty_tick(0, 1), eng._last_words(),
        *eng._ring_kv)
    text = lowered.as_text(debug_info=True)
    for scope in ("attn.full", "attn.window", "moe.route", "moe.experts",
                  "moe.shared", "ffn.dense"):
        assert scope in text, scope


# ---- the shares of an expert layer add up -----------------------------------

def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    from paddle_tpu.parallel.moe import moe_dropless

    rng = np.random.default_rng(3)
    e, f, n, k, t = 32, 16, 16, 4, 24
    p = {"router": rng.normal(size=(e, n)) * e ** -0.5,
         "bias": 0.02 * rng.normal(size=(n,)),
         "w_gate": rng.normal(size=(n, e, f)) * e ** -0.5,
         "w_up": rng.normal(size=(n, e, f)) * e ** -0.5,
         "w_down": rng.normal(size=(n, f, e)) * f ** -0.5,
         "shared_gate": rng.normal(size=(e, f)) * e ** -0.5,
         "shared_up": rng.normal(size=(e, f)) * e ** -0.5,
         "shared_down": rng.normal(size=(f, e)) * f ** -0.5}
    p = {name: jnp.asarray(a, jnp.float32) for name, a in p.items()}
    x = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
    experts = ("w_gate", "w_up", "w_down")

    def share(first, count, shared):
        mine = {name: a[first:first + count] if name in experts else a
                for name, a in p.items()
                if shared or not name.startswith("shared_")}
        y, stats = moe_dropless(x, mine, top_k=k, held=(first, count),
                                routing="sigmoid", scaling=2.5, tile_m=8,
                                operand_dtype=jnp.float32)
        return np.asarray(y), stats

    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(REF.experts(x, p, top_k=k, scaling=2.5,
                                       held=(0, n), mode="f32"))
        (low, s0), (high, s1) = share(0, n // 2, True), \
            share(n // 2, n // 2, False)
        # the reference given a share is the program given that share
        ref_low = np.asarray(REF.experts(
            x, {name: a[:n // 2] if name in experts else a
                for name, a in p.items()},
            top_k=k, scaling=2.5, held=(0, n // 2), mode="f32"))
    np.testing.assert_allclose(low + high, uncut, atol=2e-5)
    np.testing.assert_allclose(low, ref_low, atol=2e-5)
    assert float(s0["rows_held"] + s1["rows_held"]) == t * k == \
        float(s0["rows_total"])


# ---- rotary: YaRN frequencies and partial lanes -----------------------------

# transformers 4.57.6 modeling_rope_utils._compute_yarn_parameters for
# Laguna-XS.2's full layers (rope_theta 500000, 64 of 128 lanes, factor 64,
# original_max_position_embeddings 4096, beta_fast 64, beta_slow 1),
# written out on this machine
LAGUNA_YARN = [
    1.00000000e+00, 6.63601279e-01, 4.40366626e-01, 2.92227834e-01,
    1.93922758e-01, 1.28687382e-01, 7.77550265e-02, 4.65270430e-02,
    2.75100935e-02, 1.60225071e-02, 9.15058423e-03, 5.08890115e-03,
    2.72439071e-03, 1.37483550e-03, 6.24954759e-04, 2.24009680e-04,
    2.20970851e-05, 1.46636539e-05, 9.73081933e-06, 6.45738373e-06,
    4.28512794e-06, 2.84361613e-06, 1.88702722e-06, 1.25223357e-06,
    8.30983709e-07, 5.51441815e-07, 3.65937467e-07, 2.42836563e-07,
    1.61146644e-07, 1.06937115e-07, 7.09636012e-08, 4.70915325e-08]


def test_yarn_frequencies_are_the_librarys():
    rope = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5}
    mine = yarn_inv_freq(64, 500000, 64, 4096, 64, 1)
    np.testing.assert_allclose(mine, LAGUNA_YARN, rtol=2e-6)
    theirs, scale = REF.frequencies(128, rope)
    np.testing.assert_allclose(theirs, LAGUNA_YARN, rtol=2e-6)
    assert scale == rope["attention_factor"]
    # pairs that turn often keep their frequency, slow ones take 1 / 64
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(mine[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(mine[16:], plain[16:] / 64, rtol=1e-6)
    from paddle_tpu.serving.window_moe_lm import rope_frequencies

    inv, s = rope_frequencies(128, rope)
    np.testing.assert_array_equal(inv, mine)
    assert s == 1.4158883083359672


def test_partial_rotary_turns_the_first_lanes_only():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 3, 16)), jnp.float32)
    pos = jnp.asarray([0, 1, 7, 100, 4000], jnp.int32)
    inv = 10000.0 ** (-np.arange(0, 16, 2) / 16)
    np.testing.assert_allclose(rotary_lanes(x, pos, inv), rotary(x, pos, 1e4),
                               atol=1e-6)
    half = rotary_lanes(x, pos, inv[:4], 1.5)
    np.testing.assert_array_equal(half[..., 8:], x[..., 8:])
    np.testing.assert_allclose(half[0, :, :8], 1.5 * x[0, :, :8], atol=1e-6)
    np.testing.assert_allclose(
        half[..., :8], REF.rotary(x[..., :8], pos,
                                  np.asarray(inv[:4], np.float32), 1.5),
        atol=1e-6)
