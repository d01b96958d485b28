"""Automatic prefix caching + chunked prefill (round 9).

Covers the tentpole contract end to end: refcounted PagePool with the
set-backed double-free guard, chained-hash PrefixCache (verified
collisions, LRU eviction), cache-on/off greedy parity against the
non-paged oracle, the copy-on-write fork on full-cover hits, refcount
conservation (REF-LEAK) at every drain, LRU eviction under fault-plan
page pressure and eviction storms, and decode ticks interleaving with a
chunked prefill.  Deterministic throughout — injected clocks, no sleeps.
"""

import numpy as np
import jax
import pytest

from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.serving import (DecoderLM, FaultPlan, ManualClock,
                                PageLeakError, PagePool, PrefixCache,
                                RequestStatus, ServingEngine,
                                greedy_decode_reference)

from conftest import assert_serving_drained as assert_drained  # noqa: E402

serving = pytest.mark.serving
prefix = pytest.mark.prefix

pytestmark = [serving, prefix]


@pytest.fixture(autouse=True)
def f32():
    old = FLAGS.use_bf16
    FLAGS.use_bf16 = False
    yield
    FLAGS.use_bf16 = old


def _small_model(seed=0, **kw):
    kw.setdefault("vocab_size", 50)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("head_dim", 8)
    kw.setdefault("max_positions", 128)
    model = DecoderLM(**kw)
    return model, model.init_params(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# PagePool: refcounts + set-backed free list
# ---------------------------------------------------------------------------


def test_page_pool_refcounts_and_set_backed_guard():
    pool = PagePool(8)                    # 7 usable
    got = pool.alloc(3)
    assert [pool.refcount(p) for p in got] == [1, 1, 1]
    assert pool.total_refs == 3 and pool.num_live == 3
    pool.ref(got[:2])                     # share two pages
    assert pool.refcount(got[0]) == 2
    pool.free(got)                        # first holder drops all three
    assert pool.num_free == 5             # got[2] hit zero and freed
    assert pool.refcount(got[0]) == 1
    pool.free(got[:2])                    # second holder drops the shared
    assert pool.num_free == 7 and pool.total_refs == 0
    # double free is refused in O(1) via the set mirror
    with pytest.raises(Exception, match="double free"):
        pool.free([got[0]])
    # the mirror agrees with the list and LIFO grant order is preserved:
    # the most recently freed page comes back first
    assert set(pool._free) == pool._free_set
    last_freed = pool._free[-1]
    assert pool.alloc(1) == [last_freed]


def test_page_pool_cached_pages_park_and_release():
    pool = PagePool(6)
    (p,) = pool.alloc(1)
    pool.mark_cached(p)
    pool.free([p])                        # refcount 0 but cached: parked
    assert pool.num_free == 4 and pool.num_reclaimable == 1
    assert pool.refcount(p) == 0 and p not in pool._free_set
    pool.ref([p])                         # a later prefix hit revives it
    assert pool.refcount(p) == 1
    pool.free([p])                        # parked again
    pool.release_cached(p)                # eviction returns it for real
    assert pool.num_free == 5 and pool.num_cached == 0
    assert set(pool._free) == pool._free_set


# ---------------------------------------------------------------------------
# PrefixCache: chained lookup, verification, LRU eviction
# ---------------------------------------------------------------------------


def test_prefix_cache_chain_lookup_and_lru_eviction():
    pool = PagePool(10)
    cache = PrefixCache(pool, page_size=4)
    pages = pool.alloc(3)
    toks = list(range(100, 112))          # 3 full blocks
    cache.insert(toks, pages, upto=12)
    assert len(cache) == 3
    hit, n = cache.lookup(toks)
    assert hit == pages and n == 12
    # a diverging third block stops the chain after two pages
    hit, n = cache.lookup(toks[:8] + [1, 2, 3, 4])
    assert hit == pages[:2] and n == 8
    # partial last block is never matched (full pages only)
    hit, n = cache.lookup(toks[:7])
    assert hit == pages[:1] and n == 4
    # eviction skips pages with live holders...
    pool.free([pages[2]])                 # only block 2 reaches refcount 0
    assert cache.evict(3) == 1
    assert len(cache) == 2 and pool.refcount(pages[0]) == 1
    # ...and frees the rest once their holders are gone
    pool.free(pages[:2])
    assert cache.flush() == 2
    assert pool.num_free == pool.num_usable and len(cache) == 0


def test_prefix_cache_collisions_are_verified_away():
    pool = PagePool(10)
    cache = PrefixCache(pool, page_size=2, hash_fn=lambda prev, blk: 7)
    a = pool.alloc(1)
    cache.insert([5, 6], a, upto=2)
    # same degenerate key, different tokens: verified away, no hit, no
    # second entry clobbering the first
    hit, n = cache.lookup([8, 9])
    assert hit == [] and n == 0
    b = pool.alloc(1)
    cache.insert([8, 9], b, upto=2)
    assert len(cache) == 1                # existing entry wins
    hit, n = cache.lookup([5, 6])
    assert hit == a and n == 2            # the original still verifies


# ---------------------------------------------------------------------------
# engine: cache-on/off parity, sharing, COW forks
# ---------------------------------------------------------------------------


def _engine(model, params, **kw):
    kw.setdefault("eos_id", 1)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 48)
    kw.setdefault("max_pages_per_seq", 10)
    kw.setdefault("max_slots", 4)
    kw.setdefault("buckets", (4, 8, 16))
    return ServingEngine(model, params, **kw)


def test_cache_on_off_parity_with_shared_prefix(rng):
    model, params = _small_model()
    system = rng.randint(2, 50, size=8).tolist()   # page-aligned prefix
    prompts = [system + rng.randint(2, 50, size=k).tolist()
               for k in (3, 1, 5, 2, 4, 6)]
    results = {}
    for pc in (False, True):
        eng = _engine(model, params, prefix_cache=pc)
        rids = [eng.submit(p, max_tokens=8) for p in prompts]
        res = eng.run(max_ticks=400)
        results[pc] = [res[r] for r in rids]
        snap = eng.metrics.snapshot()
        if pc:
            assert snap["prefill_tokens_saved"] > 0
            assert snap["prefix_hit_rate"] > 0
            # cached-prefix requests forwarded fewer prompt tokens
            assert snap["prefill_tokens"] < sum(len(p) for p in prompts)
        else:
            assert snap["prefill_tokens_saved"] == 0
        assert_drained(eng)
    # token-identical with and without the cache, and both match the
    # non-paged oracle
    assert results[True] == results[False]
    for p, toks in zip(prompts, results[True]):
        assert toks == greedy_decode_reference(model, params, p, 8, 1)


def test_cow_fork_full_cover_hit_and_divergence(rng):
    model, params = _small_model()
    eng = _engine(model, params)
    prompt = rng.randint(2, 50, size=8).tolist()   # exactly 2 full pages
    a = eng.submit(prompt, max_tokens=6)
    eng.run(max_ticks=100)                         # prompt pages now cached
    assert eng.metrics.cow_forks == 0
    # identical prompt: full-cover hit -> COW fork, only the last token
    # is recomputed
    b = eng.submit(prompt, max_tokens=6)
    # shares the first page, diverges inside the second block: the
    # divergent tail must not corrupt the pages b reads
    c = eng.submit(prompt[:7] + [49 if prompt[7] != 49 else 48],
                   max_tokens=6)
    res = eng.run(max_ticks=100)
    assert eng.metrics.cow_forks == 1
    assert eng.metrics.prefill_tokens_saved >= (len(prompt) - 1) + 4
    want = greedy_decode_reference(model, params, prompt, 6, 1)
    assert eng.result(a) == want and res[b] == want
    assert res[c] == greedy_decode_reference(
        model, params, prompt[:7] + [49 if prompt[7] != 49 else 48], 6, 1)
    # a fourth identical request after b decoded PAST the forked page
    # proves b's appends landed in private pages, not the shared prefix
    d = eng.submit(prompt, max_tokens=6)
    res = eng.run(max_ticks=100)
    assert res[d] == want
    assert_drained(eng)


def test_mid_prompt_hit_partial_page_tail(rng):
    model, params = _small_model()
    eng = _engine(model, params)
    base = rng.randint(2, 50, size=10).tolist()    # 2 full pages + 2 tail
    a = eng.submit(base, max_tokens=5)
    eng.run(max_ticks=100)
    # same first 8 tokens (the cached full pages), different tail: the
    # mid-prompt-hit path — stitch 8, prefill from position 8
    other = base[:8] + rng.randint(2, 50, size=4).tolist()
    saved_before = eng.metrics.prefill_tokens_saved
    b = eng.submit(other, max_tokens=5)
    res = eng.run(max_ticks=100)
    assert eng.metrics.prefill_tokens_saved - saved_before == 8
    assert res[b] == greedy_decode_reference(model, params, other, 5, 1)
    assert_drained(eng)


def test_preempted_request_reprefills_from_its_own_cache(rng):
    model, params = _small_model(num_layers=1)
    # the known-thrashing geometry: growth must preempt, and the re-
    # prefill should hit the pages the victim itself cached
    eng = _engine(model, params, num_pages=8, max_pages_per_seq=4,
                  max_slots=3)
    prompts = [rng.randint(2, 50, size=4).tolist() for _ in range(3)]
    rids = [eng.submit(p, max_tokens=12) for p in prompts]
    res = eng.run(max_ticks=500)
    assert eng.metrics.preemptions > 0
    assert eng.metrics.prefill_tokens_saved > 0    # re-prefill was cheap
    for p, rid in zip(prompts, rids):
        assert res[rid] == greedy_decode_reference(model, params, p, 12, 1)
    assert_drained(eng)


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_parity_and_decode_interleave(rng):
    model, params = _small_model()
    long_p = rng.randint(2, 50, size=26).tolist()
    short_p = rng.randint(2, 50, size=3).tolist()
    eng = _engine(model, params, prefill_chunk=8, buckets=(4, 8),
                  prefix_cache=False)
    ticks_at_emit = []
    srid = eng.submit(short_p, max_tokens=12,
                      on_token=lambda t: ticks_at_emit.append(eng._tick))
    eng.step()                             # short request starts decoding
    lrid = eng.submit(long_p, max_tokens=4)
    res = eng.run(max_ticks=200)
    assert res[srid] == greedy_decode_reference(model, params, short_p,
                                                12, 1)
    assert res[lrid] == greedy_decode_reference(model, params, long_p,
                                                4, 1)
    # the long prompt needed ceil(26/8)=4 chunk ticks, and the short
    # request kept emitting one token EVERY tick through all of them —
    # chunked prefill interleaves instead of stalling the decode batch.
    # (the first two emissions share a tick: prefill's first token and
    # the same tick's decode — pre-existing single-tick pipelining)
    gaps = np.diff(ticks_at_emit[1:])
    assert (gaps == 1).all()
    assert_drained(eng)


def test_chunked_prefill_with_cached_prefix_positions_offset(rng):
    # cached prefix + chunked tail in one request: prefill starts at the
    # stitched offset and still chunks the remainder
    model, params = _small_model()
    system = rng.randint(2, 50, size=12).tolist()  # 3 full pages
    eng = _engine(model, params, prefill_chunk=4, buckets=(4, 8))
    a = eng.submit(system + rng.randint(2, 50, size=2).tolist(),
                   max_tokens=4)
    eng.run(max_ticks=100)
    tail = rng.randint(2, 50, size=9).tolist()
    b = eng.submit(system + tail, max_tokens=6)    # 12 cached + 9 chunked
    saved_before = eng.metrics.prefill_tokens_saved
    res = eng.run(max_ticks=100)
    assert eng.metrics.prefill_tokens_saved - saved_before == 12
    assert res[b] == greedy_decode_reference(model, params, system + tail,
                                             6, 1)
    assert_drained(eng)


# ---------------------------------------------------------------------------
# eviction under pressure + fault injection
# ---------------------------------------------------------------------------


def test_lru_eviction_under_fault_plan_page_pressure(rng):
    model, params = _small_model(num_layers=1)
    plan = FaultPlan(clock=ManualClock(tick_s=0.01),
                     page_pressure=(2, 30, 10))
    # warm the cache first so the pressure window finds reclaimable pages
    eng = ServingEngine(model, params, eos_id=1, page_size=4, num_pages=16,
                        max_pages_per_seq=4, max_slots=2, buckets=(4, 8),
                        faults=plan)
    warm = [rng.randint(2, 50, size=8).tolist() for _ in range(3)]
    wrids = [eng.submit(p, max_tokens=3) for p in warm]
    eng.run(max_ticks=60)
    assert eng.pool.num_reclaimable > 0
    # under pressure, admissions must evict cached pages instead of
    # stalling or preempting forever
    fresh = [rng.randint(2, 50, size=8).tolist() for _ in range(3)]
    frids = [eng.submit(p, max_tokens=3) for p in fresh]
    res = eng.run(max_ticks=200)
    assert eng.cache.evictions > 0
    for p, rid in zip(warm + fresh, wrids + frids):
        assert res[rid] == greedy_decode_reference(model, params, p, 3, 1)
    assert plan.held_pages == []
    assert_drained(eng)


def test_cache_eviction_storm_keeps_parity(rng):
    model, params = _small_model()
    plan = FaultPlan(clock=ManualClock(tick_s=0.01), cache_storm=(0, 1000))
    eng = _engine(model, params, faults=plan)
    system = rng.randint(2, 50, size=8).tolist()
    prompts = [system + rng.randint(2, 50, size=k).tolist()
               for k in (2, 3, 4)]
    # staggered max_tokens: completions park pages while peers still
    # run, so the storm has something to flush mid-flight
    rids = [eng.submit(p, max_tokens=m)
            for p, m in zip(prompts, (2, 6, 10))]
    res = eng.run(max_ticks=200)
    # the storm flushes every reclaimable page every tick: hits become
    # rare-to-impossible but nothing corrupts and nothing leaks
    assert eng.cache.evictions > 0
    for p, rid, m in zip(prompts, rids, (2, 6, 10)):
        assert res[rid] == greedy_decode_reference(model, params, p, m, 1)
    assert_drained(eng)
    hz = eng.healthz()
    assert hz["ok"] is True and hz["pages_cached"] == hz["pages_reclaimable"]


def test_hash_collision_fault_degrades_to_miss_not_corruption(rng):
    model, params = _small_model()
    plan = FaultPlan(clock=ManualClock(tick_s=0.01), hash_collisions=True)
    eng = _engine(model, params, faults=plan)
    system = rng.randint(2, 50, size=8).tolist()
    prompts = [system + rng.randint(2, 50, size=k).tolist()
               for k in (2, 3, 4)]
    rids = [eng.submit(p, max_tokens=6) for p in prompts]
    res = eng.run(max_ticks=200)
    # with EVERY block hashing identically, token verification caps the
    # cache at one entry: at most the first shared block can ever hit
    assert len(eng.cache) <= 1
    assert eng.metrics.prefill_tokens_saved <= 4 * len(prompts)
    for p, rid in zip(prompts, rids):
        assert res[rid] == greedy_decode_reference(model, params, p, 6, 1)
    assert_drained(eng)


# ---------------------------------------------------------------------------
# conservation + healthz
# ---------------------------------------------------------------------------


def test_ref_leak_checker_counts_refs_and_tags_ref_leak(rng):
    model, params = _small_model()
    eng = _engine(model, params)
    rid = eng.submit(rng.randint(2, 50, size=6).tolist(), max_tokens=4)
    eng.step()
    eng.check_page_conservation()          # balanced while running
    req = eng.scheduler.running_requests()[0]
    eng.pool.ref([req.pages[0]])           # a ref nobody accounts for
    with pytest.raises(PageLeakError, match="REF-LEAK"):
        eng.check_page_conservation()
    assert eng.healthz()["page_leak"] is True
    eng.pool.free([req.pages[0]])
    eng.check_page_conservation()
    eng.run(max_ticks=100)
    assert eng.status(rid) is RequestStatus.COMPLETED
    assert_drained(eng)


@pytest.mark.parametrize("chunk", [0, 4])
def test_failed_prefill_never_caches_poisoned_pages(rng, chunk):
    # a prompt whose forward pass produces non-finite logits must not
    # leave its (suspect) K/V pages hittable: one overflowing prompt
    # would otherwise poison every future request sharing the prefix.
    # chunk=4 exercises the per-chunk guard — the poisoned first chunk
    # is caught BEFORE its pages are indexed, so there is no multi-tick
    # window in which a sharer could stitch them
    model, params = _small_model()
    params = dict(params)
    params["emb"] = params["emb"].at[7].set(np.inf)    # token 7 poisons
    eng = _engine(model, params, prefill_chunk=chunk)
    bad = [7] + rng.randint(8, 50, size=9).tolist()    # 2 full pages
    b1 = eng.submit(bad, max_tokens=4)
    eng.run(max_ticks=50)
    assert eng.status(b1) is RequestStatus.FAILED
    assert len(eng.cache) == 0                 # nothing hittable
    # a resubmit finds NO cached prefix (saved stays 0) and fails on its
    # own forward pass, not on stitched poisoned pages
    b2 = eng.submit(bad, max_tokens=4)
    eng.run(max_ticks=50)
    assert eng.status(b2) is RequestStatus.FAILED
    assert eng.metrics.prefill_tokens_saved == 0
    # forgotten pages skipped the reclaimable park: everything is free
    assert eng.pool.num_free == eng.pool.num_usable
    assert_drained(eng)


def test_sharer_of_mid_prefill_chunks_survives_late_poison(rng):
    # A's early chunks pass the finite guard and are cached mid-prefill;
    # B stitches them while A is STILL prefilling; A's LATER chunk then
    # overflows.  The rollback/scrub must be scoped to the failing chunk
    # — wiping A's earlier vouched pages would zero K/V that B is
    # reading, and B would complete with silently wrong tokens
    model, params = _small_model()
    params = dict(params)
    params["emb"] = params["emb"].at[7].set(np.inf)
    eng = _engine(model, params, prefill_chunk=4, buckets=(4, 8),
                  max_slots=2)
    clean8 = rng.randint(8, 50, size=8).tolist()
    a = eng.submit(clean8 + [7, 8], max_tokens=4)  # chunk 3 poisons
    eng.step()                         # (a chunk's pages are indexed
    eng.step()                         # when its guard's flag is read:
    eng.land()                         # now, for the one in the air)
    assert len(eng.cache) == 2 and eng.status(a) is RequestStatus.RUNNING
    bprompt = clean8 + rng.randint(8, 50, size=3).tolist()
    b = eng.submit(bprompt, max_tokens=6)
    res = eng.run(max_ticks=100)    # B stitches 8; A fails on chunk 3
    assert eng.status(a) is RequestStatus.FAILED
    assert eng.status(b) is RequestStatus.COMPLETED
    assert eng._requests[b].cached_len == 8         # it really stitched
    assert res[b] == greedy_decode_reference(model, params, bprompt, 6, 1)
    assert len(eng.cache) >= 2                      # vouched pages kept
    assert_drained(eng)


def test_failed_tail_keeps_shared_prefix_cached(rng):
    # rollback scope: a request whose UNIQUE TAIL overflows forgets only
    # the pages it wrote — the shared system prompt it stitched was
    # finite-vouched by its original owner and must stay hittable
    model, clean = _small_model()
    system = rng.randint(8, 50, size=8).tolist()       # 2 clean pages
    a_prompt = system + rng.randint(8, 50, size=2).tolist()
    c_prompt = system + rng.randint(8, 50, size=3).tolist()
    # the poisoned id is one no clean request can meet: prompts draw from
    # [8, 50), and a clean request feeds back what it emits, so the id
    # is chosen from the model among those it emits for neither
    emitted = {t for p in (a_prompt, c_prompt)
               for t in greedy_decode_reference(model, clean, p, 4, 1)}
    poison = next(t for t in range(2, 8) if t not in emitted)
    params = dict(clean)
    params["emb"] = params["emb"].at[poison].set(np.inf)
    eng = _engine(model, params)
    a = eng.submit(a_prompt, max_tokens=4)
    eng.run(max_ticks=50)
    assert eng.status(a) is RequestStatus.COMPLETED
    cached_before = len(eng.cache)
    assert cached_before == 2
    bad = eng.submit(system + [poison, 8], max_tokens=4)    # poisoned tail
    eng.run(max_ticks=50)
    assert eng.status(bad) is RequestStatus.FAILED
    assert len(eng.cache) == cached_before             # prefix survived
    # and it still serves hits
    saved_before = eng.metrics.prefill_tokens_saved
    c = eng.submit(c_prompt, max_tokens=4)
    eng.run(max_ticks=50)
    assert eng.status(c) is RequestStatus.COMPLETED
    assert eng.metrics.prefill_tokens_saved - saved_before == 8
    assert_drained(eng)


def test_healthz_exposes_cache_occupancy_and_drains_steady(rng):
    model, params = _small_model()
    eng = _engine(model, params)
    rids = [eng.submit(rng.randint(2, 50, size=9).tolist(), max_tokens=4)
            for _ in range(3)]
    eng.step()
    hz = eng.healthz()
    assert hz["pages_in_use"] > 0          # live holders mid-run
    eng.run(max_ticks=200)
    assert all(eng.status(r) is RequestStatus.COMPLETED for r in rids)
    hz = eng.healthz()
    # steady state: no live pages, the cache fully reclaimable, free +
    # cached covering the whole pool
    assert hz["ok"] is True and hz["pages_in_use"] == 0
    assert hz["pages_cached"] > 0
    assert hz["pages_cached"] == hz["pages_reclaimable"]
    assert hz["pages_free"] + hz["pages_cached"] == eng.pool.num_usable
    # flushing the cache returns every page to the free list
    eng.cache.flush()
    assert eng.healthz()["pages_cached"] == 0
    assert eng.pool.num_free == eng.pool.num_usable
