"""Fault-tolerant prefix-aware serving fleet (round 11).

Every replica lifecycle transition, death-retry routing, and the
fleet-level conservation contract, driven end-to-end on ONE injected
clock — no wall-clock sleeps anywhere (the lint wall-clock rule holds
on ``fleet.py`` with zero escapes), mirroring how the single-engine
chaos suite drives its FaultPlan.
"""

import jax
import numpy as np
import pytest

from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.serving import (DecoderLM, FleetFaultPlan, FleetRouter,
                                ManualClock, PageLeakError, ReplicaState,
                                RequestStatus, ServingEngine,
                                greedy_decode_reference,
                                prefix_chain_hashes)
from paddle_tpu.serving.kv_cache import PrefixCache

from conftest import assert_serving_drained as assert_drained  # noqa: E402

serving = pytest.mark.serving
faults = pytest.mark.faults
fleet_mark = pytest.mark.fleet

pytestmark = [serving, faults, fleet_mark]

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


def _make_fleet(model, params, n=2, plan=None, **kw):
    if plan is None:
        plan = FleetFaultPlan(clock=ManualClock(tick_s=0.01))
    engine_kw = dict(eos_id=EOS, page_size=PAGE, num_pages=32,
                     max_pages_per_seq=8, max_slots=2, buckets=(4, 8))
    engine_kw.update(kw.pop("engine_kw", {}))
    kw.setdefault("heartbeat_s", 0.05)
    kw.setdefault("resubmit_budget", 2)

    def mk(i, time_fn):
        return ServingEngine(model, params, time_fn=time_fn, **engine_kw)

    return FleetRouter(mk, n, faults=plan, **kw), plan


def _prompts(rng, n, shared=0, lo=3, hi=9):
    sysp = rng.randint(2, 50, size=shared).tolist() if shared else []
    return [sysp + rng.randint(2, 50, size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


def _drain_all(fl, max_ticks=400):
    out = fl.run(max_ticks=max_ticks)
    assert not fl.has_work, "fleet failed to drain"
    return out


def _assert_fleet_drained(fl):
    fl.check_fleet_conservation()
    for rep in fl.replicas:
        assert rep.engine.pool.total_refs == 0
        if rep.state is not ReplicaState.DEAD:
            assert_drained(rep.engine)
    assert fl.metrics.duplicate_completions == 0


# ---------------------------------------------------------------------------
# replica lifecycle: every transition
# ---------------------------------------------------------------------------


def test_initial_replicas_come_up_ready(model_params):
    fl, _ = _make_fleet(*model_params, n=3)
    assert [r.state for r in fl.replicas] == [ReplicaState.READY] * 3
    hz = fl.healthz()
    assert hz["ok"] and hz["ready"] == 3


def test_join_is_observable_then_promoted(model_params):
    fl, _ = _make_fleet(*model_params, n=1)
    idx = fl.add_replica()
    assert fl.replica_state(idx) is ReplicaState.JOINING   # JOINING tick
    fl.step()
    assert fl.replica_state(idx) is ReplicaState.READY     # -> READY


def test_drain_stops_routing_finishes_work_then_dead(model_params):
    model, params = model_params
    rng = np.random.RandomState(0)
    fl, _ = _make_fleet(model, params, n=2)
    prompts = _prompts(rng, 4)
    frids = [fl.submit(p, max_tokens=4) for p in prompts]
    target = 0
    fl.drain_replica(target)
    assert fl.replica_state(target) is ReplicaState.DRAINING
    # new traffic only lands on the survivor
    extra = fl.submit(_prompts(rng, 1)[0], max_tokens=3)
    assert fl._requests[extra].replica == 1
    _drain_all(fl)
    # READY -> DRAINING -> DEAD (clean retirement), work all finished
    assert fl.replica_state(target) is ReplicaState.DEAD
    assert fl.replicas[target].dead_reason == "drained"
    for f in frids + [extra]:
        assert fl.status(f) is RequestStatus.COMPLETED
    assert fl.metrics.replicas_drained == 1
    _assert_fleet_drained(fl)


def test_drain_join_elasticity_round_trip(model_params):
    """Drain one replica out, join a fresh one, keep serving: the fleet
    shape changes under live traffic without losing a request."""
    model, params = model_params
    rng = np.random.RandomState(1)
    fl, _ = _make_fleet(model, params, n=2)
    first = [fl.submit(p, max_tokens=3) for p in _prompts(rng, 3)]
    for _ in range(2):
        fl.step()
    fl.drain_replica(0)
    idx = fl.add_replica()
    for _ in range(2):
        fl.step()
    assert fl.replica_state(idx) is ReplicaState.READY
    second = [fl.submit(p, max_tokens=3) for p in _prompts(rng, 3)]
    # the drained replica takes no new bindings
    assert all(fl._requests[f].replica != 0 for f in second)
    _drain_all(fl)
    for f in first + second:
        assert fl.status(f) is RequestStatus.COMPLETED
    assert fl.replica_state(0) is ReplicaState.DEAD
    _assert_fleet_drained(fl)


def test_missed_heartbeats_mark_replica_dead(model_params):
    """READY -> DEAD via lease expiry: a heartbeat partition longer than
    the TTL kills the replica without any explicit kill call."""
    model, params = model_params
    plan = FleetFaultPlan(clock=ManualClock(tick_s=0.01),
                          partitions={0: (1, 10_000)})
    fl, _ = _make_fleet(model, params, n=2, plan=plan, heartbeat_s=0.03)
    # long enough to still be decoding when the TTL (3 heartbeats ~ 9
    # ticks) lapses — the death must catch it in flight
    frid = fl.submit([5, 6, 7], max_tokens=25)
    assert fl._requests[frid].replica == 0   # least-loaded pick is 0
    for _ in range(15):
        fl.step()
        if fl.replica_state(0) is ReplicaState.DEAD:
            break
    assert fl.replica_state(0) is ReplicaState.DEAD
    assert not fl._requests[frid].finished, \
        "setup: the request must outlive its replica"
    assert "lease" in fl.replicas[0].dead_reason
    _drain_all(fl)
    # the request survived its replica's death via resubmission
    assert fl.status(frid) is RequestStatus.COMPLETED
    assert fl.metrics.resubmits >= 1
    _assert_fleet_drained(fl)


def test_zombie_lease_token_cannot_ack_after_reclaim(model_params):
    """The master's zombie-fencing semantics, at fleet level: a DEAD
    replica's (slot, token) can never heartbeat again — even after a
    new replica reclaims the same slot number."""
    model, params = model_params
    fl, _ = _make_fleet(model, params, n=2)
    slot, token = fl.replicas[0].slot, fl.replicas[0].token
    fl.kill_replica(0)
    assert fl.replica_state(0) is ReplicaState.DEAD
    assert fl._lease.heartbeat(slot, token) is False      # lease dropped
    idx = fl.add_replica()                                # reclaims slot 0
    assert fl.replicas[idx].slot == slot
    assert fl._lease.heartbeat(slot, token) is False      # token mismatch
    assert fl._lease.heartbeat(fl.replicas[idx].slot,
                               fl.replicas[idx].token) is True


# ---------------------------------------------------------------------------
# death-retry routing
# ---------------------------------------------------------------------------


def test_kill_mid_decode_resubmits_token_identical(model_params):
    """The headline robustness claim: kill the replica holding running
    decodes mid-trace; every request still completes with EXACTLY the
    tokens a single healthy engine (and the non-paged oracle) produces,
    and nothing completes twice."""
    model, params = model_params
    rng = np.random.RandomState(2)
    fl, plan = _make_fleet(model, params, n=2)
    prompts = _prompts(rng, 4, shared=PAGE)   # one shared full page
    frids = [fl.submit(p, max_tokens=6) for p in prompts]
    for _ in range(3):
        fl.step()                             # decode is mid-flight
    victim = fl._requests[frids[0]].replica
    in_flight = [f for f in frids
                 if fl._requests[f].replica == victim
                 and not fl._requests[f].finished]
    assert in_flight, "setup: victim replica must hold live requests"
    fl.kill_replica(victim, "kill mid-decode")
    results = _drain_all(fl)
    assert fl.metrics.resubmits >= len(in_flight)
    for f, p in zip(frids, prompts):
        assert fl.status(f) is RequestStatus.COMPLETED
        want = greedy_decode_reference(model, params, p, 6, EOS)
        assert results[f] == want, "kill-resubmit broke greedy parity"
    _assert_fleet_drained(fl)


def test_on_token_stream_is_exactly_once_across_kill(model_params):
    model, params = model_params
    rng = np.random.RandomState(3)
    fl, _ = _make_fleet(model, params, n=2)
    # deterministically pick a prompt whose greedy trajectory doesn't
    # hit EOS early — the kill must land mid-stream
    prompt = want = None
    while True:
        cand = _prompts(rng, 1, shared=PAGE)[0]
        ref = greedy_decode_reference(model, params, cand, 12, EOS)
        if len(ref) >= 8:
            prompt, want = cand, ref
            break
    seen = []
    frid = fl.submit(prompt, max_tokens=12, on_token=seen.append)
    for _ in range(3):
        fl.step()
    assert seen, "setup: some tokens must stream before the kill"
    assert not fl._requests[frid].finished, \
        "setup: the stream must be mid-flight at the kill"
    fl.kill_replica(fl._requests[frid].replica)
    _drain_all(fl)
    # the replayed prefix was NOT re-delivered: one copy of each token
    assert seen == want
    assert fl.result(frid) == want
    _assert_fleet_drained(fl)


def test_resubmit_budget_exhaustion_ends_failed(model_params):
    """Serial kills burn the budget; the request ends FAILED — a real
    terminal status, not an infinite kill->resubmit loop."""
    model, params = model_params
    fl, _ = _make_fleet(model, params, n=3, resubmit_budget=1)
    frid = fl.submit([3, 4, 5, 6], max_tokens=8)
    fl.step()
    fl.kill_replica(fl._requests[frid].replica)      # resubmit #1
    assert not fl._requests[frid].finished
    fl.step()
    fl.kill_replica(fl._requests[frid].replica)      # budget burned
    assert fl.status(frid) is RequestStatus.FAILED
    # only the re-dispatch that actually happened is counted; the
    # refused second one is not
    assert fl._requests[frid].resubmits == 1
    assert fl.metrics.resubmits == 1
    _drain_all(fl)
    _assert_fleet_drained(fl)


def test_correlated_deaths_fence_before_resubmit(model_params):
    """Two replicas lapse on the SAME lease sweep (one partition taking
    out both): the displaced request must not burn its resubmit budget
    on a dispatch to the other doomed replica — every death in the
    sweep is fenced first, then resubmission sees only true survivors."""
    model, params = model_params
    plan = FleetFaultPlan(clock=ManualClock(tick_s=0.01),
                          partitions={0: (1, 10_000), 1: (1, 10_000)})
    fl, _ = _make_fleet(model, params, n=3, plan=plan, heartbeat_s=0.03,
                        resubmit_budget=1)
    # victim on replica 0 (first least-loaded pick), a short filler on 1
    # that FINISHES before the sweep (so 1 looks idle — the tempting
    # wrong resubmit target), a long filler keeping 2 busy (so the
    # survivor looks WORSE by load than the doomed idle replica)
    frid = fl.submit([5, 6, 7], max_tokens=25)
    f_short = fl.submit([8, 9, 10], max_tokens=1)
    f_long = fl.submit([11, 12, 13], max_tokens=20)
    assert [fl._requests[f].replica for f in (frid, f_short, f_long)] \
        == [0, 1, 2]
    for _ in range(15):
        fl.step()
        if fl.replica_state(0) is ReplicaState.DEAD:
            break
    # both lapsed on the same sweep
    assert fl.replica_state(0) is ReplicaState.DEAD
    assert fl.replica_state(1) is ReplicaState.DEAD
    assert not fl._requests[frid].finished, \
        "setup: the victim must outlive its replica"
    # ONE resubmit, straight to the sole survivor — budget intact
    assert fl._requests[frid].replica == 2
    assert fl._requests[frid].resubmits == 1
    _drain_all(fl)
    assert fl.status(frid) is RequestStatus.COMPLETED
    _assert_fleet_drained(fl)


def test_no_ready_replica_rejects_submit(model_params):
    model, params = model_params
    fl, _ = _make_fleet(model, params, n=2, resubmit_budget=0)
    fl.kill_replica(0)
    fl.kill_replica(1)
    frid = fl.submit([2, 3, 4], max_tokens=2)
    assert fl.status(frid) is RequestStatus.REJECTED
    _drain_all(fl)
    _assert_fleet_drained(fl)


def test_deadline_carries_over_resubmit_no_fresh_budget(model_params):
    """A request resubmitted after its replica dies keeps its ORIGINAL
    absolute deadline: the re-prefill cannot mint a new time budget, so
    an unmeetable deadline ends TIMED_OUT/shed, never COMPLETED late."""
    model, params = model_params
    plan = FleetFaultPlan(clock=ManualClock(tick_s=0.01))
    fl, _ = _make_fleet(model, params, n=2, plan=plan)
    # 20 tokens at ~1 token/tick (0.01s): ~0.2s of work against a 0.08s
    # deadline, doomed only AFTER the kill forces a restart
    frid = fl.submit([2, 3, 4, 5], max_tokens=20, deadline_s=0.12)
    for _ in range(4):
        fl.step()
    fl.kill_replica(fl._requests[frid].replica)
    _drain_all(fl)
    assert fl.status(frid) in (RequestStatus.TIMED_OUT,
                               RequestStatus.REJECTED)
    assert fl._requests[frid].terminal_transitions == 1
    _assert_fleet_drained(fl)


# ---------------------------------------------------------------------------
# routing: prefix affinity, load balancing, overflow
# ---------------------------------------------------------------------------


def test_shared_prefix_routes_to_owner_replica(model_params):
    model, params = model_params
    rng = np.random.RandomState(4)
    # a high overflow limit isolates pure affinity (all 5 submits land
    # before a single tick runs, so the owner's queue is briefly deep)
    fl, _ = _make_fleet(model, params, n=3, overflow_queue_depth=32)
    sysp = rng.randint(2, 50, size=2 * PAGE).tolist()
    frids = [fl.submit(sysp + rng.randint(2, 50, size=3).tolist(),
                       max_tokens=2) for _ in range(5)]
    owners = {fl._requests[f].replica for f in frids}
    assert len(owners) == 1, f"shared prefix split across {owners}"
    assert fl.metrics.affinity_hits >= 4     # all but the first submit
    _drain_all(fl)
    # the owner's engine saw real prefix-cache hits from the co-routing
    owner = owners.pop()
    assert fl.replicas[owner].engine.metrics.prefix_hit_rate() > 0.3
    _assert_fleet_drained(fl)


def test_routing_key_is_the_prefix_cache_key(model_params):
    """The router and the cache agree by construction: the chain hashes
    the router keys on are exactly the keys a PrefixCache would index
    the same tokens under."""
    from paddle_tpu.serving.kv_cache import PagePool

    rng = np.random.RandomState(5)
    toks = rng.randint(2, 50, size=3 * PAGE + 2).tolist()
    hashes = prefix_chain_hashes(toks, PAGE)
    assert len(hashes) == 3                  # full pages only
    pool = PagePool(8)
    cache = PrefixCache(pool, PAGE)
    pages = pool.alloc(3)
    cache.insert(toks, pages, 3 * PAGE)
    assert [cache._index[h].page for h in hashes] == pages


def test_distinct_prefixes_balance_by_load(model_params):
    model, params = model_params
    rng = np.random.RandomState(6)
    fl, _ = _make_fleet(model, params, n=2)
    frids = [fl.submit(p, max_tokens=2) for p in _prompts(rng, 6)]
    used = {fl._requests[f].replica for f in frids}
    assert used == {0, 1}, "no-affinity traffic should spread"
    _drain_all(fl)
    _assert_fleet_drained(fl)


def test_affinity_overflows_to_least_loaded_when_saturated(model_params):
    model, params = model_params
    rng = np.random.RandomState(7)
    fl, _ = _make_fleet(model, params, n=2, overflow_queue_depth=2)
    sysp = rng.randint(2, 50, size=PAGE).tolist()
    frids = [fl.submit(sysp + rng.randint(2, 50, size=3).tolist(),
                       max_tokens=2) for _ in range(8)]
    used = {fl._requests[f].replica for f in frids}
    assert len(used) == 2, "owner saturated: overflow must spill"
    _drain_all(fl)
    _assert_fleet_drained(fl)


def test_round_robin_control_policy_spreads_evenly(model_params):
    model, params = model_params
    rng = np.random.RandomState(8)
    fl, _ = _make_fleet(model, params, n=2, routing="round_robin")
    sysp = rng.randint(2, 50, size=PAGE).tolist()
    frids = [fl.submit(sysp + rng.randint(2, 50, size=3).tolist(),
                       max_tokens=2) for _ in range(6)]
    by_rep = [sum(1 for f in frids if fl._requests[f].replica == i)
              for i in range(2)]
    assert by_rep == [3, 3]
    assert fl.metrics.affinity_hits == 0
    _drain_all(fl)
    _assert_fleet_drained(fl)


def test_affinity_keeps_prefix_hits_that_round_robin_spreads_thin(
        model_params):
    """The two policies on ONE sequenced trace of three tenants, each
    with its own two-page system prompt: affinity gives a prefix one
    home, so all but a tenant's first request hit; round-robin makes
    both replicas serve every tenant and pays each prefix's prefill
    twice (a share of prompt tokens counted, not a rate).  What a request completes
    with is the same under both, and the oracle's."""
    model, params = model_params
    rng = np.random.RandomState(12)
    systems = [rng.randint(2, 50, size=2 * PAGE).tolist() for _ in range(3)]
    prompts = [systems[j % 3] + rng.randint(2, 50, size=3).tolist()
               for j in range(12)]
    outs, snaps = {}, {}
    for routing in ("affinity", "round_robin"):
        fl, _ = _make_fleet(model, params, n=2, routing=routing)
        frids = []
        for p in prompts:               # sequenced: a hit needs the
            frids.append(fl.submit(p, max_tokens=3))    # writer admitted
            fl.step()
        _drain_all(fl)
        _assert_fleet_drained(fl)
        outs[routing] = [fl.result(f) for f in frids]
        snaps[routing] = fl.snapshot()
    assert outs["affinity"] == outs["round_robin"]
    assert outs["affinity"][0] == greedy_decode_reference(
        model, params, prompts[0], 3, EOS)
    assert snaps["affinity"]["fleet_prefix_hit_rate"] > \
        snaps["round_robin"]["fleet_prefix_hit_rate"]


def test_slow_replica_fault_and_fleet_still_drains(model_params):
    """A slow replica (steps every 3rd fleet tick) stretches the drain
    in FLEET ticks — its per-engine work is unchanged, it just runs
    less often — and nothing is lost."""
    model, params = model_params

    def ticks_to_drain(plan):
        rng = np.random.RandomState(9)
        fl, _ = _make_fleet(model, params, n=2, plan=plan)
        frids = [fl.submit(p, max_tokens=3) for p in _prompts(rng, 6)]
        _drain_all(fl)
        for f in frids:
            assert fl.status(f) is RequestStatus.COMPLETED
        _assert_fleet_drained(fl)
        return fl._tick

    fast = ticks_to_drain(FleetFaultPlan(clock=ManualClock(tick_s=0.01)))
    slow = ticks_to_drain(FleetFaultPlan(clock=ManualClock(tick_s=0.01),
                                         slow_replicas={1: 3}))
    assert slow > fast, (slow, fast)


# ---------------------------------------------------------------------------
# conservation + seeded chaos
# ---------------------------------------------------------------------------


def test_conservation_check_catches_duplicate_completion(model_params):
    model, params = model_params
    fl, _ = _make_fleet(model, params, n=1)
    frid = fl.submit([2, 3, 4], max_tokens=2)
    _drain_all(fl)
    fl.metrics.duplicate_completions = 1     # seeded violation
    with pytest.raises(PageLeakError, match="FLEET-LEAK"):
        fl.check_fleet_conservation()


def test_conservation_check_catches_nonterminal_rid(model_params):
    model, params = model_params
    fl, _ = _make_fleet(model, params, n=1)
    fl.submit([2, 3, 4], max_tokens=4)       # still in flight
    with pytest.raises(PageLeakError, match="FLEET-LEAK"):
        fl.check_fleet_conservation()


def test_seeded_fleet_chaos_conserves_everything(model_params):
    """The kitchen sink on one injected clock: Poisson arrivals with a
    shared prefix, one scheduled kill, one slow replica, one heartbeat
    partition — every fleet rid reaches exactly one terminal status and
    no page or ref leaks anywhere, including the dead replicas."""
    model, params = model_params
    rng = np.random.RandomState(10)
    plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.01),
                          kill_at={8: 0}, slow_replicas={2: 2},
                          partitions={1: (2, 10_000)})
    fl, _ = _make_fleet(model, params, n=4, plan=plan, heartbeat_s=0.03)
    arrivals = np.cumsum(rng.exponential(0.01, 12))
    prompts = _prompts(rng, 12, shared=PAGE)
    frids = []
    i = 0
    while i < len(prompts) or fl.has_work:
        while i < len(prompts) and arrivals[i] <= plan.clock():
            frids.append(fl.submit(prompts[i], max_tokens=4))
            i += 1
        fl.step()
        assert fl._tick < 2000, "chaos fleet failed to drain"
    _assert_fleet_drained(fl)
    assert fl.replica_state(0) is ReplicaState.DEAD      # scheduled kill
    assert fl.replica_state(1) is ReplicaState.DEAD      # partition
    statuses = [fl.status(f) for f in frids]
    assert all(s.terminal for s in statuses)
    assert all(fl._requests[f].terminal_transitions == 1 for f in frids)
    # completions are token-exact even after the chaos
    for f, p in zip(frids, prompts):
        if fl.status(f) is RequestStatus.COMPLETED:
            assert fl.result(f) == greedy_decode_reference(
                model, params, p, 4, EOS)


def test_fleet_metrics_snapshot_shape(model_params):
    model, params = model_params
    fl, _ = _make_fleet(model, params, n=2)
    frid = fl.submit([2, 3, 4, 5], max_tokens=3)
    _drain_all(fl)
    snap = fl.snapshot()
    assert snap["fleet_completed"] == 1
    assert snap["fleet_duplicate_completions"] == 0
    assert snap["fleet_tokens_emitted"] == len(fl.result(frid))
    assert snap["fleet_tokens_per_s"] > 0
    assert len(snap["per_replica_prefix_hit_rate"]) == 2
    assert snap["replica_states"] == ["ready", "ready"]
    hz = fl.healthz()
    assert hz["ok"] and hz["in_flight"] == 0
