"""A block model (generation by diffusion over blocks) behind
``ServingEngine``: a tiny ``BlockMoeLM`` (2 layers, 8 experts top-2, block
4, 2 denoising passes a block, seeded weights) against the benchmark's
plain reference (``benchmarks/references/sdar_moe.py``).  What is compared
is LOGITS: at every position a pass fixed, the engine's row of the tick
that fixed it against the reference's row of that state (earlier blocks
clean, the position's block masked from where that pass found it).  A
block is committed in the tick that opens the next one (the fold: the
slot brings both blocks' rows), and a prompt's last chunk carries the first
open block, so a block costs ``S`` ticks and the first tokens come of the
tick that ends the prefill."""

import gc
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.platform.enforce import EnforceError
from paddle_tpu.serving import (DecoderLM, FaultPlan, RequestStatus,
                                SamplingParams, ServingEngine, export_chain,
                                greedy_decode_reference)
from paddle_tpu.serving.scheduler import pack_prefill_chunks

pytestmark = pytest.mark.serving

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import cells, weights  # noqa: E402

REF = cells.load_module(os.path.join(BENCH, "references", "sdar_moe.py"))
FAMILY = cells.load_module(os.path.join(BENCH, "families", "sdar_moe.py"))
TINY = cells.load_json(os.path.join(BENCH, "tests", "configs",
                                    "tiny-sdar.json"))
B, S = TINY["serve"]["block_length"], TINY["serve"]["denoise_steps"]
MASK, VOCAB = TINY["serve"]["mask_token_id"], TINY["vocab_size"]
PAD = 64              # rows of the reference's buffer
# Engine and reference are both float32 here and differ by the order of
# their sums alone (the engine's attention streams pages, the expert layer
# sorts rows): a few 1e-6 of logits whose size is about 1.  Float8
# operands move them by 1e-2 and more, which this has to refuse.
TOL = 2e-4


@pytest.fixture(scope="module")
def made():
    return weights.make(FAMILY.leaves(TINY, "serve"), 20260930)


def engine(made, **kw):
    prog = FAMILY.serve_program(TINY, [None])
    params = {name: made[ref] for name, ref in prog["names"].items()}
    kw = {"page_size": 16, "num_pages": 40, "max_pages_per_seq": 4,
          "max_slots": 4, "buckets": (16,), "prefill_chunk": 8, **kw}
    return ServingEngine(prog["model"], params, eos_id=VOCAB, **kw)


def record(eng):
    """Every pass of every slot, as the engine walks it: {rid: [(position,
    pass of its block, logits row [V])]} for the positions a denoising
    pass fixed, and the list of (rid, block start) of committing passes."""
    fixed, commits, passes = {}, [], {}
    walk = eng._finish_block_pass

    def spy(req, stood, picks, logits, now):
        # as the pass's dispatch found the block; ``fold`` 1: the tick also
        # committed the block before it
        at, have, n, fold = stood
        if not n or fold == 1:
            commits.append((req.rid, at if not n else at - B))
        if n:
            nth = passes[req.rid, at] = passes.get((req.rid, at), -1) + 1
            rows = np.asarray(logits[req.slot])     # they stay on the device
            for i in range(n):
                fixed.setdefault(req.rid, []).append(
                    (at + have + i, nth, rows[i]))
        walk(req, stood, picks, logits, now)

    eng._finish_block_pass = spy
    return fixed, commits


def dispatched(eng):
    """Every slot participation as its step's dispatch laid it out, landed
    or not: [(rid, block start, tokens the block had, tokens to fix,
    fold)]."""
    out, advance = [], eng._advance

    def spy(flight):
        out.extend((p[0].rid,) + tuple(p[2:]) for p in flight.passes)
        advance(flight)

    eng._advance = spy
    return out


def prompt_of(seed: int, n: int):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, VOCAB - 1, n)]


def reference_rows(made, prompt, answer, mode="f32", prompt_len=None):
    """Row ``p - 1`` judges the token at position ``p``, as the serve
    driver reads it."""
    toks = np.zeros(PAD, np.int32)
    toks[:len(prompt) + len(answer)] = list(prompt) + list(answer)
    pos = jnp.arange(PAD, dtype=jnp.int32)
    return np.asarray(FAMILY.reference_logits(
        REF, TINY, weights.unflatten(made), jnp.asarray(toks), pos,
        jnp.zeros((PAD,), jnp.int32), mode=mode, block_rows=32,
        prompt_len=prompt_len))


def worst_gap(fixed, ref):
    """Largest difference, over the fixed positions, between the engine's
    logits and the reference's (the mask token's column apart: the
    reference puts it at the least value, the engine never chooses it)."""
    keep = np.arange(VOCAB) != MASK
    return max(float(np.abs(row[keep] - ref[p - 1][keep]).max())
               for p, _nth, row in fixed)


# ---- (a) (b) (c): logits at every fixed position, in its own state ----------

CASES = [
    # prompt length, max_tokens
    (8, 8),      # (a) whole blocks in, whole blocks out
    (16, 12),    # (a) two chunks of prefill
    (5, 8),      # (b) the prompt ends inside a block, one token in
    (7, 6),      # (b) ... three tokens in: one pass fills the block
    (3, 7),      # (b) a prompt shorter than a block: nothing to prefill
    (8, 6),      # (c) the answer ends inside a block
    (8, 1),      # (c) max_tokens smaller than a pass's share
    (6, 3),      # (b) and (c) at once
]


@pytest.mark.parametrize("n_prompt,max_tokens", CASES)
def test_logits_at_each_fixed_position_are_the_references(made, n_prompt,
                                                          max_tokens):
    eng = engine(made)
    fixed, commits = record(eng)
    prompt = prompt_of(n_prompt, n_prompt)
    seen = []
    rid = eng.submit(prompt, max_tokens, on_token=seen.append)
    out = eng.run()[rid]
    assert out == seen and len(out) == max_tokens    # never past max_tokens
    assert MASK not in out
    # on_token came in position order, a pass's share at a time
    assert [p for p, _, _ in fixed[rid]] == \
        list(range(n_prompt, n_prompt + max_tokens))
    assert [int(np.argmax(np.where(np.arange(VOCAB) == MASK, -np.inf, row)))
            for _, _, row in fixed[rid]] == out
    # every block that filled was committed once, before the next began
    # (the answer's last block is not: nothing will read it)
    end = (n_prompt + max_tokens - 1) // B * B
    assert commits == [(rid, at) for at in range(n_prompt // B * B, end, B)]
    # ... each in the tick that opened the next block, none alone; the
    # first pass rode with the prompt's last chunk where there was one
    m = eng.metrics.snapshot()
    assert (m["folded_passes"], m["commit_passes"]) == (len(commits), 0)
    assert m["folded_first_passes"] == (n_prompt >= B)
    ref = reference_rows(made, prompt, out, prompt_len=n_prompt)
    assert worst_gap(fixed[rid], ref) < TOL
    # (f) the family's account of which pass fixed which position, told
    # where the prompt ended, is the engine's own record
    _, state = FAMILY.fixing_states(TINY, jnp.arange(PAD), n_prompt)
    assert [nth for _, nth, _ in fixed[rid]] == \
        [int(state[p]) for p, _, _ in fixed[rid]]
    eng.check_page_conservation()


def test_float8_operands_fail_the_tolerance(made):
    eng = engine(made)
    fixed, _ = record(eng)
    prompt = prompt_of(1, 8)
    rid = eng.submit(prompt, 8)
    out = eng.run()[rid]
    assert worst_gap(fixed[rid], reference_rows(made, prompt, out)) < TOL
    assert worst_gap(fixed[rid],
                     reference_rows(made, prompt, out, mode="fp8")) > 10 * TOL


def test_aligned_prompts_need_no_prompt_length(made):
    """What the cell relies on: with a prompt that ends on a block
    boundary the state a position was fixed in follows from the position
    alone, so the driver's call (no ``prompt_len``) judges every token by
    the right row."""
    eng = engine(made)
    fixed, _ = record(eng)
    prompt = prompt_of(2, 12)
    rid = eng.submit(prompt, 10)
    out = eng.run()[rid]
    assert worst_gap(fixed[rid], reference_rows(made, prompt, out)) < TOL
    a = FAMILY.fixing_states(TINY, jnp.arange(PAD))
    b = FAMILY.fixing_states(TINY, jnp.arange(PAD), 12)
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(a, b))


def test_a_batch_of_slots_at_different_passes(made):
    """Four slots that stand at different passes in one tick (staggered
    arrivals, a prefill chunk beside block passes): each request's logits
    are still the reference's, and the counters add up."""
    eng = engine(made)
    fixed, commits = record(eng)
    sizes = [(8, 8), (13, 7), (4, 9), (21, 5)]
    prompts = [prompt_of(10 + i, n) for i, (n, _) in enumerate(sizes)]
    rids = []
    for prompt, (_, mt) in zip(prompts, sizes):
        rids.append(eng.submit(prompt, mt))
        eng.step()
    out = eng.run()
    for rid, prompt, (n, mt) in zip(rids, prompts, sizes):
        assert len(out[rid]) == mt
        ref = reference_rows(made, prompt, out[rid], prompt_len=n)
        assert worst_gap(fixed[rid], ref) < TOL
    m = eng.metrics.snapshot()
    assert m["tokens_fixed"] == m["tokens_generated"] == sum(
        mt for _, mt in sizes)
    # a block is committed alone or in the tick that opens the next one,
    # which brings both blocks' rows; a slot's other rows are not counted
    assert m["commit_passes"] + m["folded_passes"] == len(commits)
    assert m["folded_passes"] > 0
    assert m["block_rows"] == B * (m["denoise_passes"] + m["commit_passes"]
                                   + m["folded_passes"]) == m["decode_rows"]
    assert m["decode_slots"] == m["denoise_passes"] + m["commit_passes"]
    # the expert layer's counters came back with the logits: every valid
    # row took top-2 experts in each of the 2 layers
    rows = m["decode_rows"] + m["prefill_tokens"]
    assert m["moe_rows_total"] == rows * 2 * TINY["num_experts_per_tok"]
    assert 0 < m["moe_live_tiles"] <= m["moe_grid_tiles"]
    assert 0 < m["moe_live_experts"] <= m["moe_live_tiles"]
    # one dispatch a busy tick, whatever passes its slots stood at
    assert m["step_dispatches"] <= m["ticks"]


@pytest.mark.parametrize("n_prompt,blocks", [(8, 2), (8, 5), (6, 3),
                                             (16, 4)])
def test_an_answer_of_n_blocks_takes_n_times_s_ticks(made, n_prompt, blocks):
    """A block costs ``S`` ticks of its slot: its first pass rides in the
    tick that commits the block before it (or prefills the prompt's last
    chunk), so ``n`` blocks take ``n x S`` slot participations, ``n - 1``
    of them folded, and no commit runs alone (the answer's last block is
    not committed: nothing follows it)."""
    eng = engine(made)
    log = dispatched(eng)
    # the answer fills its blocks to the end of the last one
    max_tokens = blocks * B - n_prompt % B
    rid = eng.submit(prompt_of(20 + blocks, n_prompt), max_tokens)
    assert len(eng.run()[rid]) == max_tokens
    m = eng.metrics.snapshot()
    # (the prompt's tail takes its share of the first block's passes)
    passes = blocks * S - n_prompt % B // (B // S)
    assert m["decode_slots"] == m["denoise_passes"] == len(log) == passes
    assert (m["folded_passes"], m["commit_passes"]) == (blocks - 1, 0)
    assert m["tokens_fixed"] == max_tokens
    # rows: a folded tick brings two blocks, every other one
    assert m["block_rows"] == m["decode_rows"] == B * (passes + blocks - 1)
    # a prompt's last chunk and the slot's first pass shared a tick: a
    # step more than the passes only where the prompt needs two chunks
    assert m["step_dispatches"] == passes + (n_prompt > 8)
    assert eng._ticks_per_token == S / B
    eng.check_page_conservation()


def test_a_full_block_at_its_pages_end_commits_alone_without_a_page(made):
    """The fold takes the next block's page the way lookahead does: where
    the pool is dry the slot commits alone, nothing is preempted for it,
    and growth finds the page a tick later.  The tokens are the same."""
    prompt = prompt_of(26, 8)
    calm = engine(made)
    want = calm.submit(prompt, 24)
    want = calm.run()[want]
    # ticks 0-3: chunk + pass, pass, fold, pass: the block [12, 16) is full
    # at tick 4, at the end of page 0, and every free page is held then
    plan = FaultPlan(page_pressure=(1, 5, 100))
    eng = engine(made, faults=plan)
    log = dispatched(eng)
    fixed, commits = record(eng)
    rid = eng.submit(prompt, 24)
    for _ in range(5):
        eng.step()
    assert log[-1] == (rid, 12, B, 0, 0)          # the lone commit
    assert eng.pool.num_free == 0 and eng.metrics.preemptions == 0
    out = eng.run()[rid]
    assert out == want
    m = eng.metrics.snapshot()
    # six blocks: four folds, and the one commit that ran alone
    assert (m["folded_passes"], m["commit_passes"]) == (4, 1)
    assert m["decode_slots"] == 6 * S + 1 and m["preemptions"] == 0
    assert commits == [(rid, at) for at in range(8, 28, B)]
    assert worst_gap(fixed[rid], reference_rows(made, prompt, out)) < TOL
    eng.check_page_conservation()


def test_step_compiles_once_per_prefill_bucket(made):
    eng = engine(made)
    for i, n in enumerate((8, 5, 16, 3)):
        eng.submit(prompt_of(30 + i, n), 6)
    eng.run()
    assert set(eng._step_fns) <= {(0, 2 * B), (16, 2 * B)}


def test_a_compiled_step_leaves_the_collectors_generations(made):
    """What a step program's compilation built is frozen once it has run
    (``engine._settle_heap``): a later full collection walks the young
    heap only and finds a tick's objects, not JAX's."""
    eng = engine(made)
    eng.submit(prompt_of(31, 8), 4)
    gc.collect()
    frozen = gc.get_freeze_count()
    eng.step()                              # compiles (16, B)
    assert gc.get_freeze_count() > frozen
    eng.step()                              # compiles (0, B)
    frozen = gc.get_freeze_count()
    eng.run()                               # compiles nothing more
    assert gc.get_freeze_count() <= frozen   # (a frozen object may die)


# ---- the read back lags its dispatch by a tick ------------------------------

def test_the_read_back_lags_its_dispatch_by_one_step(made):
    """``step()`` dispatches a tick and reads the one before: a pass's
    tokens reach ``on_token`` one call later, the next step having taken
    them from the device; a fault plan (or a request that samples) lands
    every tick in its own call, and both serve the same tokens."""
    eng, own = engine(made), engine(made, faults=FaultPlan())
    prompt, seen, calls = prompt_of(33, 8), [], []
    rid = eng.submit(prompt, 10, on_token=seen.append)
    want = own.submit(prompt, 10)
    while eng.has_work:
        eng.step()
        calls.append((len(seen), eng._flying is not None))
    # prefill with the first pass, pass, commit with the next block's
    # first pass, ...: the first tokens come with the second call, not the
    # first, and a step is in the air meanwhile
    assert calls[:4] == [(0, True), (2, True), (4, True), (6, True)]
    assert calls[-1] == (10, False) and eng._flying is None
    ticks = 0
    while own.has_work:
        own.step()
        ticks += 1
        assert own._flying is None
    assert ticks == len(calls) - 1             # the lag costs one call
    assert seen == own.result(want) == eng.result(rid)
    # the words came from the device and from the zeros alike: one program
    # a bucket, compiled once
    assert all(fn._cache_size() == 1 for fn in eng._step_fns.values())
    a, b = eng.metrics.snapshot(), own.metrics.snapshot()
    for name in ("step_dispatches", "decode_rows", "denoise_passes",
                 "commit_passes", "folded_passes", "folded_first_passes",
                 "tokens_fixed", "moe_rows_total"):
        assert a[name] == b[name], name
    eng.check_page_conservation()


@pytest.mark.parametrize("eos_at,fold_in_the_air", [(3, True), (4, False)])
def test_an_answer_that_ends_early_leaves_a_pass_in_the_air(
        made, eos_at, fold_in_the_air):
    """EOS is known when the words arrive, a tick after the next pass was
    dispatched: that pass is passed over, and its slot and page serve the
    next request.  The pass in the air is a folded one where the EOS ends
    a block (the block was committed and the next one opened for nothing),
    a block's second pass where the EOS came of a folded pass."""
    probe = engine(made)
    prompt = prompt_of(54, 8)
    rid, other = probe.submit(prompt, 12), probe.submit(prompt_of(35, 9), 6)
    full, rest = probe.run()[rid], probe.result(other)
    eos = full[eos_at]
    assert eos not in full[:eos_at] and eos not in rest
    cut = full[:eos_at + 1]
    prog = FAMILY.serve_program(TINY, [None])
    params = {name: made[ref] for name, ref in prog["names"].items()}
    eng = ServingEngine(prog["model"], params, eos_id=eos, page_size=16,
                        num_pages=40, max_pages_per_seq=4, max_slots=1,
                        buckets=(16,), prefill_chunk=8)
    log = dispatched(eng)
    fixed, _ = record(eng)
    rid = eng.submit(prompt, 12)
    after = eng.submit(prompt_of(35, 9), 6)
    out = eng.run()
    assert out[rid] == cut and out[after] == rest
    mine = [p for p in log if p[0] == rid]
    assert (mine[-1][-1] == 1) == fold_in_the_air
    # that pass was dispatched and never walked
    assert len(mine) == len(fixed[rid]) // (B // S) + 1
    assert eng.metrics.folded_passes == sum(
        p[-1] == 1 for p in log) - fold_in_the_air
    ref = reference_rows(made, prompt_of(35, 9), out[after], prompt_len=9)
    assert worst_gap(fixed[after], ref) < TOL
    eng.check_page_conservation()


@pytest.mark.parametrize("landed,fold_in_the_air", [(1, False), (2, True)])
def test_cancelled_with_a_pass_in_the_air(made, landed, fold_in_the_air):
    """... a block's second pass, or the folded pass that committed the
    block and opened the next (``cache_len`` has moved on: the request is
    gone, nothing is unwound)."""
    eng = engine(made, max_slots=1)
    log = dispatched(eng)
    fixed, _ = record(eng)
    rid = eng.submit(prompt_of(36, 8), 12)
    while len(fixed.get(rid, ())) < landed * (B // S):
        eng.step()
    assert (log[-1][-1] == 1) == fold_in_the_air
    assert eng._flying is not None and eng.cancel(rid)
    eng.check_page_conservation()
    prompt = prompt_of(37, 6)
    after = eng.submit(prompt, 7)           # takes the slot and the page
    out = eng.run()
    assert rid not in out and len(fixed[rid]) == landed * (B // S)
    ref = reference_rows(made, prompt, out[after], prompt_len=6)
    assert worst_gap(fixed[after], ref) < TOL
    assert not eng.has_work and eng._flying is None
    eng.check_page_conservation()


# ---- (d) preemption and cancellation between passes -------------------------

def test_preemption_between_passes_keeps_pages_and_tokens(made):
    calm = engine(made)
    sizes = [(14, 20), (15, 20), (13, 20), (12, 20)]
    prompts = [prompt_of(40 + i, n) for i, (n, _) in enumerate(sizes)]
    want = []
    for p, (_, mt) in zip(prompts, sizes):
        rid = calm.submit(p, mt)
        want.append(calm.run()[rid])
    # 7 usable pages of 16: four sequences growing to 34 tokens need 12
    eng = engine(made, num_pages=8, max_pages_per_seq=3)
    rids = [eng.submit(p, mt) for p, (_, mt) in zip(prompts, sizes)]
    while eng.has_work:
        eng.step()
        eng.check_page_conservation()          # at every tick, not at rest
    out = eng.run()
    assert eng.metrics.preemptions > 0
    # a preempted request re-prefills its whole blocks and takes its block
    # up where it stood: the same passes fix the same positions
    assert [out[r] for r in rids] == want


def test_cancellation_between_passes_returns_the_blocks_page(made):
    eng = engine(made)
    fixed, _ = record(eng)
    rid = eng.submit(prompt_of(50, 8), 12)
    other = eng.submit(prompt_of(51, 9), 6)
    while not fixed.get(rid):
        eng.step()                 # one pass of its first block has run
    assert len(fixed[rid]) == B // S
    assert eng.cancel(rid)
    eng.check_page_conservation()
    out = eng.run()
    assert eng.status(rid) is RequestStatus.CANCELLED and rid not in out
    assert len(out[other]) == 6
    assert eng.healthz()["pages_in_use"] == 0


def test_cancel_from_on_token_stops_inside_a_pass(made):
    eng = engine(made)
    seen = []

    def on_token(tok):
        seen.append(tok)
        eng.cancel(rid)

    rid = eng.submit(prompt_of(52, 8), 8, on_token=on_token)
    eng.run()
    assert len(seen) == 1 and eng.status(rid) is RequestStatus.CANCELLED


def test_non_finite_logits_fail_their_slot_alone(made):
    plan = FaultPlan()
    eng = engine(made, faults=plan)
    calm = engine(made)
    good, bad = prompt_of(53, 8), prompt_of(54, 12)
    want = calm.submit(good, 8)
    want = calm.run()[want]
    rid, poisoned = eng.submit(good, 8), eng.submit(bad, 8)
    plan.poison_nan(poisoned)
    out = eng.run()
    assert eng.status(poisoned) is RequestStatus.FAILED
    assert out[rid] == want          # its batchmate's passes went on
    eng.check_page_conservation()


def test_non_finite_logits_with_a_folded_pass_in_the_air(made):
    """The finite flag is read a call behind the dispatch: the slot whose
    block's second pass comes back non-finite fails while the tick that
    committed that block and opened the next is in the air; that tick is
    passed over, the pages are scrubbed, the batchmate goes on."""
    plan = FaultPlan()
    eng = engine(made, faults=plan)
    eng._lands_now = lambda flight: False     # the plan poisons; the read lags
    log = dispatched(eng)
    fixed, _ = record(eng)
    calm = engine(made)
    good, bad = prompt_of(53, 8), prompt_of(54, 8)
    want = calm.submit(good, 12)
    want = calm.run()[want]
    rid, poisoned = eng.submit(good, 12), eng.submit(bad, 12)
    while len(fixed.get(poisoned, ())) < B // S:
        eng.step()                    # its first pass landed, the second flies
    plan.poison_nan(poisoned)
    eng.step()                        # dispatches the fold, reads the second
    assert eng.status(poisoned) is RequestStatus.FAILED
    assert [p for p in log if p[0] == poisoned][-1][-1] == 1
    assert eng._flying is not None
    out = eng.run()
    # (the failing pass was walked, and fixed nothing)
    assert out[rid] == want
    assert len(eng._requests[poisoned].generated) == B // S
    assert eng.metrics.failed == 1
    eng.check_page_conservation()


# ---- the prefix cache, sampling ---------------------------------------------

def test_prefix_cache_shares_whole_pages_of_prefilled_blocks(made):
    eng = engine(made, prefix_cache=True)
    fixed, _ = record(eng)
    shared = prompt_of(60, 32)                  # two whole pages
    first = eng.submit(shared + prompt_of(61, 5), 6)
    eng.run()
    again = eng.submit(shared + prompt_of(62, 3), 6)     # a partial hit
    cover = eng.submit(shared, 5)               # every page of it cached
    out = eng.run()
    assert eng.metrics.prefill_tokens_saved == 64
    assert eng.metrics.cow_forks == 0     # full cover: no page is forked
    for rid, prompt in ((again, shared + prompt_of(62, 3)), (cover, shared)):
        ref = reference_rows(made, prompt, out[rid], prompt_len=len(prompt))
        assert worst_gap(fixed[rid], ref) < TOL
    assert len(out[first]) == 6


def test_sampling_draws_from_the_unmasked_tokens(made):
    outs = []
    for _ in range(2):
        eng = engine(made)
        rid = eng.submit(prompt_of(70, 8), 12,
                         sampling=SamplingParams(temperature=1.5, seed=7))
        outs.append(eng.run()[rid])
    assert outs[0] == outs[1] and MASK not in outs[0]   # seeded, replayable
    greedy = engine(made)
    rid = greedy.submit(prompt_of(70, 8), 12)
    assert greedy.run()[rid] != outs[0]


# ---- what a block model cannot be built with --------------------------------

@pytest.mark.parametrize("kw,says", [
    ({"spec_mode": "ngram"}, "speculat"),
    ({"host_tier_bytes": 1 << 20}, "host tier"),
    ({"role": "prefill"}, "migration"),
    ({"page_size": 6}, "page_size"),
    ({"prefill_chunk": 6}, "prefill_chunk"),
    ({"mesh": object()}, "tensor-parallel"),
])
def test_refused_at_construction_with_a_message(made, kw, says):
    with pytest.raises(EnforceError, match=says):
        engine(made, **kw)


def test_chain_migration_refuses_a_block_model(made):
    eng = engine(made)
    rid = eng.submit(prompt_of(80, 8), 8)
    for _ in range(3):
        eng.step()
    assert eng.migratable_rids() == []
    with pytest.raises(EnforceError, match="block model"):
        export_chain(eng, rid)
    eng.run()


def test_prefill_chunks_end_on_block_boundaries():
    class R:
        def __init__(self, n, at):
            self.cache_tokens, self.cache_len = [0] * n, at

    reqs = [R(23, 0), R(9, 8), R(30, 8)]
    got, total = pack_prefill_chunks(reqs, 8, 1, 64, block=4)
    # 23 tokens: 20 in whole blocks, a chunk of 8; 9 tokens at 8: nothing
    # left; 30 tokens at 8: 28 in whole blocks, a chunk of 8
    assert [(r is reqs[i], s, n) for (r, s, n, _), i in zip(got, (0, 2))] \
        == [(True, 0, 8), (True, 8, 8)] and total == 16
    assert pack_prefill_chunks([R(23, 16)], 8, 1, 64, block=4)[0][0][2] == 4
    assert pack_prefill_chunks([R(23, 16)], 8, 1, 64)[0][0][2] == 7


# ---- (e) the one-token-a-tick model through the changed contract ------------

# sha256 (16 hex digits) of the StableHLO text of this model's steps at the
# parent of PR 49 (1ed5ac8), decode-only and with the prefill bucket: the
# fold is a block model's, and a model that says no ``block_length`` lowers
# the step it lowered
PARENT_STEPS = {(2, 0): "f633be80c9c16375", (2, 16): "e77ce542de4bb4f1",
                (1, 0): "6a73855ce39f4a53", (1, 16): "5dbbac5a9cdfd6b7"}


@pytest.mark.parametrize("kv_heads", [2, 1])
def test_decoder_lm_serves_the_same_tokens_as_before(kv_heads):
    model = DecoderLM(vocab_size=61, num_layers=2, num_heads=2, head_dim=8,
                      num_kv_heads=kv_heads)
    params = model.init_params(jax.random.PRNGKey(3))
    eng = ServingEngine(model, params, eos_id=60, page_size=8, num_pages=32,
                        max_pages_per_seq=4, max_slots=3, buckets=(16,),
                        prefill_chunk=8)
    assert eng._block is None and eng._counted == () and eng._k1 == 1
    assert len(eng._tick_shapes(16, 1)) == 9      # no tenth array
    prompts = [prompt_of(90 + i, n) for i, n in enumerate((5, 17))]
    rids = [eng.submit([t % 60 for t in p], 4) for p in prompts]
    out = eng.run()
    for rid, p in zip(rids, prompts):
        assert out[rid] == greedy_decode_reference(
            model, params, [t % 60 for t in p], 4, 60)
    m = eng.metrics.snapshot()
    assert m["block_rows"] == m["denoise_passes"] == m["tokens_fixed"] == 0
    # the step returns its words (each row's choice and finite flag, the
    # three decode rows and then each slot's chunk-final row), the logits
    # of those rows, which stay on the device, and the pool
    buf = eng._empty_tick(16, 1)
    shapes = jax.eval_shape(eng._step_fn(16, 1), params, eng._kv, buf,
                            eng._last_words())
    assert len(shapes) == 3 and shapes[0].shape == (2 * 6,) \
        and shapes[0].dtype == jnp.int32 and shapes[1].shape == (6, 61)
    for pb in (0, 16):
        text = eng._step_fn(pb, 1).lower(
            params, eng._kv, eng._empty_tick(pb, 1),
            eng._last_words()).as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
            PARENT_STEPS[kv_heads, pb]
