"""Page-migration plane (round 16): live KV chain handoff, cross-replica
prefix seeding, disaggregated prefill/decode routing, and MIGRATE-LEAK
conservation — all on injected clocks, no wall-clock sleeps.

The roundtrip tests move STORED bytes: an int8 page migrates as its int8
payload plus f32 scales with no re-quantization, so the destination's
pages compare bit-identical to the source's.  The fleet tests replay the
same seeded traces disaggregated vs unified and demand token-identical
streams — migration is a placement optimization, never a semantics
change.
"""

import jax
import numpy as np
import pytest

from paddle_tpu.platform.enforce import EnforceError
from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.serving import (DecoderLM, FleetFaultPlan, FleetRouter,
                                ManualClock, ReplicaState, RequestStatus,
                                ServingEngine, check_migration_conservation,
                                export_chain, export_prefix,
                                greedy_decode_reference, import_chain,
                                import_prefix)
from paddle_tpu.serving.kv_cache import read_pages

from conftest import assert_serving_drained as assert_drained  # noqa: E402

serving = pytest.mark.serving
migrate_mark = pytest.mark.migrate

pytestmark = [serving, migrate_mark]

PAGE = 4
EOS = 1


@pytest.fixture(autouse=True)
def f32():
    old = FLAGS.use_bf16
    FLAGS.use_bf16 = False
    yield
    FLAGS.use_bf16 = old


@pytest.fixture(scope="module")
def model_params():
    model = DecoderLM(vocab_size=50, num_layers=1, num_heads=2, head_dim=8,
                      max_positions=128)
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(model, params, **kw):
    base = dict(eos_id=EOS, page_size=PAGE, num_pages=32,
                max_pages_per_seq=8, max_slots=4, buckets=(8, 16))
    base.update(kw)
    return ServingEngine(model, params, **base)


def _run_until_migratable(eng, rid, max_ticks=50):
    for _ in range(max_ticks):
        if rid in eng.migratable_rids():
            return
        eng.step()
    raise AssertionError(f"rid {rid} never became migratable")


def _drain(eng, max_ticks=200):
    for _ in range(max_ticks):
        if not eng.has_work:
            return
        eng.step()
    raise AssertionError("engine failed to drain")


def _page_bytes(kv, pages):
    return tuple(None if a is None else np.asarray(a).tobytes()
                 for a in read_pages(kv, pages))


def _make_fleet(model, params, n, plan=None, **kw):
    if plan is None:
        plan = FleetFaultPlan(clock=ManualClock(tick_s=0.01))
    engine_kw = dict(eos_id=EOS, page_size=PAGE, num_pages=32,
                     max_pages_per_seq=8, max_slots=4, buckets=(8, 16))
    engine_kw.update(kw.pop("engine_kw", {}))
    kw.setdefault("heartbeat_s", 0.05)
    kw.setdefault("resubmit_budget", 2)

    def mk(i, time_fn):
        return ServingEngine(model, params, time_fn=time_fn, **engine_kw)

    return FleetRouter(mk, n, faults=plan, **kw), plan


def _drain_fleet(fl, max_ticks=800):
    out = fl.run(max_ticks=max_ticks)
    assert not fl.has_work, "fleet failed to drain"
    return out


# ---------------------------------------------------------------------------
# export/import roundtrip: bit-identical stored bytes, every pool dtype
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_chain_roundtrip_bit_identical(model_params, kv_dtype):
    model, params = model_params
    src = _engine(model, params, kv_dtype=kv_dtype)
    dst = _engine(model, params, kv_dtype=kv_dtype)
    prompt = list(range(2, 12))                 # 10 tokens: partial tail
    rid = src.submit(prompt, max_tokens=8)
    _run_until_migratable(src, rid)
    blob = export_chain(src, rid)
    assert blob.kind == "chain" and blob.num_pages >= 1
    assert blob.cache_len % PAGE != 0           # tail page in flight
    if kv_dtype == "int8":
        assert blob.quantized and blob.k_scale is not None
    rid2 = import_chain(dst, blob)
    assert rid2 is not None
    req2 = dst._requests[rid2]
    # the destination's spliced pages hold the EXACT bytes the source
    # stored — no requantization, no dtype round-trip
    got = _page_bytes(dst._kv, req2.pages[:blob.num_pages])
    want = tuple(None if a is None else np.asarray(a).tobytes()
                 for a in (blob.k, blob.v, blob.k_scale, blob.v_scale))
    assert got == want
    # mid-migration: BOTH pools conserve while both copies are live
    src.check_page_conservation()
    dst.check_page_conservation()
    src.cancel(rid)
    _drain(dst)
    full = req2.generated
    assert dst.status(rid2) is RequestStatus.COMPLETED
    if kv_dtype == "float32":                   # exact paths only
        ref = greedy_decode_reference(model, params, prompt, 8, EOS)
        assert full == ref
    _drain(src)
    assert_drained(src)
    assert_drained(dst)


def test_import_chain_refuses_geometry_mismatch(model_params):
    model, params = model_params
    src = _engine(model, params)
    dst = _engine(model, params, page_size=8, buckets=(8, 16))
    rid = src.submit(list(range(2, 12)), max_tokens=4)
    _run_until_migratable(src, rid)
    blob = export_chain(src, rid)
    with pytest.raises(EnforceError):
        import_chain(dst, blob)
    dst.check_page_conservation()               # refusal leaks nothing
    _drain(src)
    assert_drained(src)


def test_import_chain_returns_none_when_dest_full(model_params):
    model, params = model_params
    src = _engine(model, params)
    dst = _engine(model, params, max_slots=1)
    blocker = dst.submit(list(range(2, 10)), max_tokens=12)
    _run_until_migratable(dst, blocker)         # the one slot is taken
    rid = src.submit(list(range(2, 12)), max_tokens=4)
    _run_until_migratable(src, rid)
    blob = export_chain(src, rid)
    before = dst.pool.num_free
    assert import_chain(dst, blob) is None
    assert dst.pool.num_free == before          # no slot -> no pages held
    dst.check_page_conservation()
    _drain(src)
    _drain(dst)
    assert_drained(src)
    assert_drained(dst)


def test_cow_shared_chain_survives_migration(model_params):
    """Two requests sharing a cached prefix on the source: migrating one
    must not disturb the sharer's pages or its token stream."""
    model, params = model_params
    src = _engine(model, params)
    dst = _engine(model, params)
    shared = list(range(2, 10))                 # 2 full pages
    warm = src.submit(shared + [20, 21], max_tokens=2)
    _drain(src)                                 # prefix now cached
    assert src.status(warm) is RequestStatus.COMPLETED
    a = src.submit(shared + [22, 23], max_tokens=6)
    b = src.submit(shared + [24, 25], max_tokens=6)
    _run_until_migratable(src, a)
    blob = export_chain(src, a)
    rid2 = import_chain(dst, blob)
    assert rid2 is not None
    src.cancel(a)                               # the handoff's source exit
    _drain(src)
    _drain(dst)
    # the sharer kept decoding on the source, unperturbed
    ref_b = greedy_decode_reference(model, params, shared + [24, 25], 6, EOS)
    assert src.result(b) == ref_b
    ref_a = greedy_decode_reference(model, params, shared + [22, 23], 6, EOS)
    assert dst._requests[rid2].generated == ref_a
    assert_drained(src)
    assert_drained(dst)


# ---------------------------------------------------------------------------
# prefix seeding
# ---------------------------------------------------------------------------


def test_prefix_seed_warms_peer_cache(model_params):
    model, params = model_params
    a = _engine(model, params)
    b = _engine(model, params)
    shared = list(range(2, 14))                 # 3 full pages
    _drain_rid = a.submit(shared + [20], max_tokens=2)
    _drain(a)
    blob = export_prefix(a, shared + [30, 31])
    assert blob is not None and blob.kind == "prefix"
    blocks, nbytes = import_prefix(b, blob)
    assert blocks == 3 and nbytes > 0
    # seeded pages are parked RECLAIMABLE — cached, not held
    assert b.pool.total_refs == 0
    b.check_page_conservation()
    # a same-prefix prompt on B stitches instead of re-prefilling
    rid = b.submit(shared + [32, 33], max_tokens=4)
    _drain(b)
    assert b.metrics.prefill_tokens_saved >= 3 * PAGE - 1
    ref = greedy_decode_reference(model, params, shared + [32, 33], 4, EOS)
    assert b.result(rid) == ref
    assert_drained(a)
    assert_drained(b)


def test_prefix_seed_transfers_only_missing_tail(model_params):
    model, params = model_params
    a = _engine(model, params)
    b = _engine(model, params)
    shared = list(range(2, 14))                 # 3 full pages
    a.submit(shared + [20], max_tokens=2)
    _drain(a)
    b.submit(shared[:PAGE] + [21], max_tokens=2)   # B caches block 0
    _drain(b)
    blob = export_prefix(a, shared)
    blocks, _ = import_prefix(b, blob)
    assert blocks == 2                          # only blocks 1..2 moved
    # idempotent: a second import finds nothing missing
    assert import_prefix(b, blob) == (0, 0)
    assert_drained(a)
    assert_drained(b)


# ---------------------------------------------------------------------------
# scheduler backlog probe (the O(1) signal disagg routing balances on)
# ---------------------------------------------------------------------------


def test_backlog_probe_matches_recompute_and_surfaces(model_params):
    model, params = model_params
    eng = _engine(model, params, role="prefill")
    rng = np.random.RandomState(0)
    sched = eng.scheduler
    assert sched.prefill_backlog_tokens == 0
    rids = [eng.submit(rng.randint(2, 50, size=rng.randint(5, 15)).tolist(),
                       max_tokens=4) for _ in range(6)]
    assert sched.prefill_backlog_tokens == sched.recompute_backlog() > 0
    assert eng.load()["prefill_backlog_tokens"] == \
        sched.prefill_backlog_tokens
    assert eng.load()["role"] == "prefill"
    assert eng.healthz()["role"] == "prefill"
    for _ in range(60):
        eng.step()
        # the incremental probe never drifts from ground truth
        assert sched.prefill_backlog_tokens == sched.recompute_backlog()
        if not eng.has_work:
            break
    assert not eng.has_work
    assert sched.prefill_backlog_tokens == 0
    assert all(eng.status(r) is RequestStatus.COMPLETED for r in rids)
    assert_drained(eng)


# ---------------------------------------------------------------------------
# disaggregated fleet: routing, handoff, fallback, re-adopt — end to end
# ---------------------------------------------------------------------------


def _trace(rng, n, shared=8):
    sysp = rng.randint(2, 50, size=shared).tolist()
    return [sysp + rng.randint(2, 50, size=4).tolist() for _ in range(n)]


def test_disagg_outputs_token_identical_to_unified(model_params):
    model, params = model_params
    prompts = _trace(np.random.RandomState(0), 8)
    outs = []
    for roles in (None, ("prefill", "prefill", "decode", "decode")):
        kw = {} if roles is None else {"roles": roles}
        fl, _ = _make_fleet(model, params, n=4, migrate_budget=8, **kw)
        frids = [fl.submit(p, max_tokens=6) for p in prompts]
        _drain_fleet(fl)
        check_migration_conservation(fl)
        snap = fl.snapshot()
        if roles is None:
            assert snap["fleet_migrations_started"] == 0   # paths dormant
        else:
            assert snap["fleet_migrations_applied"] > 0
            # prompts only ever dispatch to prefill-class replicas
            for fr in fl._requests.values():
                pass                             # bindings already moved
        outs.append([fl.result(f) for f in frids])
    assert outs[0] == outs[1]                    # migration changed WHERE,
    #                                              never WHAT
    ref = greedy_decode_reference(model, params, prompts[0], 6, EOS)
    assert outs[0][0] == ref


def test_disagg_first_tokens_wait_fewer_ticks_on_a_hot_tenant(model_params):
    """What the split is for, as a count of ticks on the injected clock:
    affinity pins a hot tenant (70% of a Poisson burst) to one unified
    replica, whose prompts then queue behind its busy decode slots;
    2 prefill + 2 decode replicas spread the prompts by backlog and
    hand the chains over.  The first token's wait at the 95th
    percentile is shorter, the trace takes no more ticks a token, and
    the streams are the same."""
    model, params = model_params
    rng = np.random.RandomState(0)
    hot = rng.randint(2, 50, size=4 * PAGE).tolist()
    cold = [rng.randint(2, 50, size=2 * PAGE).tolist() for _ in range(3)]
    arrivals = np.cumsum(rng.exponential(1.0 / 400.0, 24))
    prompts = [(hot if rng.random_sample() < 0.7 else cold[rng.randint(3)])
               + rng.randint(2, 50, size=rng.randint(2, 6)).tolist()
               for _ in arrivals]
    seen = {}
    for roles in (None, ("prefill", "prefill", "decode", "decode")):
        kw = {} if roles is None else {"roles": roles}
        clock = ManualClock(tick_s=0.01)
        fl, _ = _make_fleet(model, params, n=4, migrate_budget=8,
                            plan=FleetFaultPlan(seed=0, clock=clock), **kw)
        submitted, first, frids = {}, {}, []
        while len(frids) < len(prompts) or fl.has_work:
            while len(frids) < len(prompts) and \
                    arrivals[len(frids)] <= clock():
                k = len(frids)
                submitted[k] = fl._tick
                frids.append(fl.submit(
                    prompts[k], max_tokens=10,    # 21 + 10 fit 8 pages
                    on_token=lambda tok, k=k: first.setdefault(k, fl._tick)))
            fl.step()
            assert fl._tick < 3000, "fleet failed to drain"
        check_migration_conservation(fl)
        assert len(first) == len(prompts)
        waits = sorted(first[k] - submitted[k] for k in first)
        outs = [fl.result(f) for f in frids]
        seen[roles is None] = (waits[int(0.95 * (len(waits) - 1))],
                               fl._tick / sum(len(o) for o in outs), outs)
    (p95_uni, tpt_uni, outs_uni), (p95_dis, tpt_dis, outs_dis) = \
        seen[True], seen[False]
    assert outs_dis == outs_uni
    assert p95_dis < p95_uni, (p95_dis, p95_uni)
    assert tpt_dis <= tpt_uni * 1.05, (tpt_dis, tpt_uni)


def test_disagg_decode_replicas_never_take_prompts(model_params):
    model, params = model_params
    fl, _ = _make_fleet(model, params, n=3,
                        roles=("prefill", "decode", "decode"),
                        migrate_budget=8)
    seen = []
    orig = fl._dispatch

    def spy(freq, now):
        ok = orig(freq, now)
        if ok and freq.replica is not None:
            seen.append(freq.replica)
        return ok

    fl._dispatch = spy
    for p in _trace(np.random.RandomState(1), 6):
        fl.submit(p, max_tokens=4)
    _drain_fleet(fl)
    assert seen and set(seen) == {0}             # only the prefill replica
    check_migration_conservation(fl)


def test_migration_drop_falls_back_exactly_once(model_params):
    model, params = model_params
    plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.01),
                          drop_migration_at={0, 2})
    fl, _ = _make_fleet(model, params, n=4, plan=plan,
                        roles=("prefill", "prefill", "decode", "decode"),
                        migrate_budget=8)
    prompts = _trace(np.random.RandomState(2), 6)
    streams = {}

    def cb_for(i):
        def cb(tok):
            streams.setdefault(i, []).append(tok)
        return cb

    frids = [fl.submit(p, max_tokens=6, on_token=cb_for(i))
             for i, p in enumerate(prompts)]
    _drain_fleet(fl)
    check_migration_conservation(fl)
    snap = fl.snapshot()
    assert snap["fleet_migration_fallbacks"] == 2
    assert snap["fleet_duplicate_completions"] == 0
    for i, f in enumerate(frids):
        assert fl.status(f) is RequestStatus.COMPLETED
        # exactly-once: the dropped blob's re-prefill replays silently
        # under the high-water fence — streamed == final, no dups
        assert streams[i] == fl.result(f)
        ref = greedy_decode_reference(model, params, prompts[i], 6, EOS)
        assert streams[i] == ref


def test_kill_decode_readopts_surviving_pages(model_params):
    model, params = model_params
    plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.01),
                          kill_at={5: 2})
    fl, _ = _make_fleet(model, params, n=4, plan=plan,
                        roles=("prefill", "prefill", "decode", "decode"),
                        migrate_budget=8)
    prompts = _trace(np.random.RandomState(0), 6)
    frids = [fl.submit(p, max_tokens=6) for p in prompts]
    _drain_fleet(fl)
    check_migration_conservation(fl)
    snap = fl.snapshot()
    assert fl.replicas[2].state is ReplicaState.DEAD
    assert snap["fleet_migrations_applied"] > 0
    # the killed decoder's rids re-dispatched AND re-adopted cached
    # prefix pages from a surviving replica through the page plane
    assert snap["fleet_resubmits"] > 0
    assert snap["fleet_migration_resubmits"] > 0
    assert snap["fleet_seed_pages"] > 0
    for f, p in zip(frids, prompts):
        assert fl.status(f) is RequestStatus.COMPLETED
        assert fl.result(f) == greedy_decode_reference(model, params, p,
                                                       6, EOS)


def test_affinity_seeding_warms_the_chosen_prefill(model_params):
    """Second-wave prompts whose prefix owner is a decode replica (the
    chain migrated there) seed the prefill target instead of letting it
    re-prefill cold."""
    model, params = model_params
    fl, _ = _make_fleet(model, params, n=4,
                        roles=("prefill", "prefill", "decode", "decode"),
                        migrate_budget=8)
    rng = np.random.RandomState(0)
    sysp = rng.randint(2, 50, size=8).tolist()
    frids = [fl.submit(sysp + rng.randint(2, 50, size=4).tolist(),
                       max_tokens=6) for _ in range(6)]
    for _ in range(4):        # wave 1's chains migrate; owners now live
        fl.step()             # on the decode side
    frids += [fl.submit(sysp + rng.randint(2, 50, size=4).tolist(),
                        max_tokens=6) for _ in range(3)]
    _drain_fleet(fl)
    check_migration_conservation(fl)
    snap = fl.snapshot()
    assert snap["fleet_cross_replica_seeds"] > 0
    assert snap["fleet_seed_bytes"] > 0
    assert all(fl.status(f).terminal for f in frids)


def test_int8_migration_bytes_fraction_of_f32():
    """The acceptance arithmetic: an int8 page moves its stored int8
    payload + f32 scales.  Per token-head that is D + 4 bytes against
    f32's 4D, so at the bench geometry (D=16) the ratio is exactly
    20/64 = 0.3125 — under the 0.35 acceptance bar.  (At D=8 the scale
    overhead would be 0.375: the bound is geometry-specific, which is
    why this test pins the bench's head_dim.)"""
    model = DecoderLM(vocab_size=50, num_layers=1, num_heads=2,
                      head_dim=16, max_positions=128)
    params = model.init_params(jax.random.PRNGKey(0))
    per = {}
    for kv_dtype in ("float32", "int8"):
        fl, _ = _make_fleet(model, params, n=2,
                            roles=("prefill", "decode"), migrate_budget=8,
                            engine_kw=dict(kv_dtype=kv_dtype))
        prompts = _trace(np.random.RandomState(0), 4)
        for p in prompts:
            fl.submit(p, max_tokens=6)
        _drain_fleet(fl)
        check_migration_conservation(fl)
        snap = fl.snapshot()
        assert snap["fleet_migrations_applied"] > 0
        assert snap["fleet_pages_migrated"] > 0
        per[kv_dtype] = (snap["fleet_migration_bytes"] /
                         snap["fleet_pages_migrated"])
    assert per["int8"] / per["float32"] <= 0.35


def test_migrate_selfcheck_gate_is_green(model_params):
    from paddle_tpu.serving.migrate import main
    assert main(["check"]) == 0
