"""Multi-tenant SLO control plane (round 17).

Tenant classes / quotas / preemption precedence, weighted-fair
admission isolating a seeded tenant storm, role-aware autoscaling with
scale-up-under-kill chaos, tenant identity across resubmit/migration,
per-tenant scrape labels, and the CONTROL-LEAK admission-ledger
conservation — all on ONE injected clock, no wall-clock sleeps.
"""

import jax
import numpy as np
import pytest

from paddle_tpu.obs.registry import MetricsRegistry
from paddle_tpu.platform.enforce import EnforceError
from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.serving import (AdmissionLedger, AutoscalePolicy, DecoderLM,
                                FleetFaultPlan, FleetRouter, ManualClock,
                                ReplicaState, RequestStatus, ServingEngine,
                                TenantRegistry, WeightedFairQueue,
                                check_control_conservation, export_chain,
                                import_chain)
from paddle_tpu.serving.scheduler import Request

from conftest import assert_serving_drained as assert_drained  # noqa: E402

serving = pytest.mark.serving
faults = pytest.mark.faults
fleet_mark = pytest.mark.fleet
control = pytest.mark.control

pytestmark = [serving, faults, fleet_mark, control]

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


# ---------------------------------------------------------------------------
# tenant registry: classes, overrides, quotas on the injected clock
# ---------------------------------------------------------------------------


def test_default_classes_and_auto_register():
    reg = TenantRegistry()
    reg.register("alice", "interactive")
    reg.register("bulk", "batch")
    assert reg.deadline_s("alice") == 0.5
    assert reg.deadline_s("bulk") is None          # batch: no deadline
    assert reg.weight("alice") > reg.weight("bulk")
    assert reg.precedence("bulk") > reg.precedence("alice")
    # unknown tenants auto-register as standard on first touch
    assert reg.deadline_s("nobody") == 2.0
    assert "nobody" in reg.tenants()


def test_per_tenant_deadline_override_beats_class_default():
    reg = TenantRegistry()
    reg.register("vip", "interactive", deadline_s=0.1)
    assert reg.deadline_s("vip") == 0.1


def test_registry_from_flag_parses_pairs_and_bare_names():
    reg = TenantRegistry.from_flag("alice:interactive, bulk:batch, eve")
    assert reg.deadline_s("alice") == 0.5
    assert reg.deadline_s("bulk") is None
    assert reg.spec("eve").cls.name == "standard"
    with pytest.raises(EnforceError):
        TenantRegistry.from_flag("x:warp9")


def test_token_bucket_refills_on_injected_clock_and_caps_at_burst():
    reg = TenantRegistry()
    reg.register("m", "standard", quota_tokens_per_s=10.0, burst_tokens=20.0)
    # bucket starts full (burst): two 10-token takes pass, a third fails
    assert reg.admit_quota("m", 10, now=0.0)
    assert reg.admit_quota("m", 10, now=0.0)
    assert not reg.admit_quota("m", 10, now=0.0)
    # 0.5s at 10 tok/s refills 5 — still short of 10
    assert not reg.admit_quota("m", 10, now=0.5)
    # long idle refills to the burst cap, no further
    assert reg.admit_quota("m", 20, now=100.0)
    assert not reg.admit_quota("m", 1, now=100.0)
    # unmetered tenants always pass
    assert reg.admit_quota("free", 10 ** 9, now=0.0)


# ---------------------------------------------------------------------------
# WFQ: virtual-time order, storm isolation, removal
# ---------------------------------------------------------------------------


def test_wfq_serves_by_weighted_virtual_time():
    q = WeightedFairQueue()
    # equal cost, alice at 4x bob's weight: alice's finish tags pack 4x
    # denser, so she gets ~4 of every 5 service slots
    for i in range(8):
        q.push("alice", 8, 4.0, ("a", i))
        q.push("bob", 8, 1.0, ("b", i))
    order = [q.pop()[0] for _ in range(10)]
    assert order.count("alice") >= 6
    # both make progress — WFQ never starves the light tenant entirely
    assert order.count("bob") >= 1


def test_wfq_storm_backlogs_only_the_storming_tenant():
    q = WeightedFairQueue()
    for i in range(50):
        q.push("storm", 8, 1.0, ("s", i))      # 10x the polite tenants
    for i in range(5):
        q.push("alice", 8, 1.0, ("a", i))
        q.push("bob", 8, 1.0, ("b", i))
    served = [q.pop() for _ in range(20)]
    tenants = [t for t, _ in served]
    # every polite item clears within the first 20 slots; the storm's
    # backlog is entirely its own
    assert tenants.count("alice") == 5 and tenants.count("bob") == 5
    assert set(q.backlog()) == {"storm"}


def test_wfq_remove_and_expire_return_their_tenants():
    q = WeightedFairQueue()
    q.push("a", 4, 1.0, "x")
    q.push("a", 4, 1.0, "y")
    q.push("b", 4, 1.0, "z")
    assert q.remove("y") == "a"
    assert q.remove("y") is None
    gone = q.expire(lambda item: item == "z")
    assert gone == [("b", "z")]
    assert len(q) == 1 and q.pop() == ("a", "x")


def test_admission_ledger_flags_an_unbalanced_partition():
    led = AdmissionLedger()
    led.on_submit("t")
    led.on_submit("t")
    led.on_admit("t")
    assert led.problems()                       # 2 != 1 + 0 + 0
    led.on_shed("t")
    assert not led.problems()
    assert led.snapshot()["t"]["shed"] == 1


# ---------------------------------------------------------------------------
# fleet integration: quotas, class deadlines, WFQ isolation under storm
# ---------------------------------------------------------------------------


def test_fleet_quota_defers_over_budget_submits(model_params):
    reg = TenantRegistry()
    reg.register("metered", "batch", quota_tokens_per_s=1.0,
                 burst_tokens=12.0)
    fl, _ = _make_fleet(*model_params, n=1, tenants=reg)
    ok = fl.submit([2, 3, 4, 5], max_tokens=4, tenant="metered")   # 8 <= 12
    over = fl.submit([2, 3, 4, 5], max_tokens=4, tenant="metered")
    assert fl.status(over) is RequestStatus.REJECTED
    assert fl.ledger.quota_deferred["metered"] == 1
    _drain_all(fl)
    assert fl.status(ok) is RequestStatus.COMPLETED
    check_control_conservation(fl)


def test_class_deadline_stamped_when_submit_has_none(model_params):
    reg = TenantRegistry()
    reg.register("vip", "interactive")
    reg.register("bulk", "batch")
    fl, _ = _make_fleet(*model_params, n=1, tenants=reg)
    t0 = fl._time()
    a = fl.submit([2, 3, 4], max_tokens=2, tenant="vip")
    b = fl.submit([2, 3, 4], max_tokens=2, tenant="bulk")
    c = fl.submit([2, 3, 4], max_tokens=2, tenant="vip", deadline_s=9.0)
    assert fl._requests[a].deadline_at == pytest.approx(t0 + 0.5)
    assert fl._requests[b].deadline_at is None     # batch: unbounded
    assert fl._requests[c].deadline_at == pytest.approx(t0 + 9.0)
    _drain_all(fl)


def test_wfq_isolates_non_storming_tenants_deadlines(model_params):
    """The tentpole behavior: under a one-tenant prompt storm, WFQ-on
    keeps every NON-storming tenant's deadline misses at zero — the
    storm's backlog is charged to the storming tenant alone — where the
    same arrivals through the FIFO router (``wfq=False``) make the
    polite tenants miss."""
    model, params = model_params
    misses = {}
    for wfq in (True, False):
        # 0.25 s sits inside the band (0.2-0.3) where the two routers
        # part on this trace: looser and FIFO misses nothing either,
        # tighter and nothing could serve the polite tenants in time
        reg = TenantRegistry()
        reg.register("alice", "interactive", deadline_s=0.25)
        reg.register("bob", "standard", deadline_s=0.25)
        reg.register("storm", "batch")
        plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.02),
                              tenant_storm=("storm", 0, 6, 10))
        fl, _ = _make_fleet(model, params, n=2, plan=plan, tenants=reg,
                            wfq=wfq)
        rng = np.random.RandomState(0)
        tick = 0
        while tick < 6 or fl.has_work:
            if tick < 6 and tick % 2 == 0:
                for tenant in ("alice", "bob", "storm"):
                    for _ in range(plan.storm_factor(tick, tenant)):
                        fl.submit(rng.randint(2, 50, size=6).tolist(),
                                  max_tokens=3, tenant=tenant)
            fl.step()
            tick += 1
            assert tick < 600, "fleet failed to drain"
        check_control_conservation(fl)
        tenants = fl.healthz()["tenants"]
        misses[wfq] = (tenants["alice"]["deadline_misses"],
                       tenants["bob"]["deadline_misses"])
        led = fl.ledger.snapshot()
        assert led["storm"]["submitted"] > led["alice"]["submitted"] * 5
    assert misses[True] == (0, 0)
    assert sum(misses[False]) > 0, misses


def test_wfq_buffered_requests_expire_and_cancel_balance_ledger(
        model_params):
    reg = TenantRegistry()
    fl, plan = _make_fleet(*model_params, n=1, tenants=reg, wfq=True)
    # saturate the engine so later submits stay buffered in the WFQ
    busy = [fl.submit([2, 3, 4, 5], max_tokens=6, tenant="t")
            for _ in range(4)]
    fl.step()
    doomed = fl.submit([2, 3, 4], max_tokens=2, tenant="t", deadline_s=0.01)
    victim = fl.submit([2, 3, 4, 5], max_tokens=2, tenant="t")
    assert len(fl.wfq) >= 2
    assert fl.cancel(victim) is True
    assert fl.status(victim) is RequestStatus.CANCELLED
    for _ in range(3):                  # past doomed's 0.01s deadline
        fl.step()
    assert fl.status(doomed) is RequestStatus.TIMED_OUT
    _drain_all(fl)
    check_control_conservation(fl)      # ledger: shed covers both exits
    assert fl.ledger.shed["t"] == 2
    assert all(fl.status(f) is RequestStatus.COMPLETED for f in busy)


# ---------------------------------------------------------------------------
# preemption precedence: batch slots are victimized before interactive
# ---------------------------------------------------------------------------


def test_precedence_fn_bound_to_every_replica_incl_late_joins(model_params):
    reg = TenantRegistry()
    fl, _ = _make_fleet(*model_params, n=1, tenants=reg)
    assert fl.replicas[0].engine.scheduler.precedence_fn == reg.precedence
    idx = fl.add_replica()
    assert fl.replicas[idx].engine.scheduler.precedence_fn == reg.precedence


def test_victim_selection_prefers_batch_over_older_interactive(
        model_params):
    reg = TenantRegistry()
    fl, _ = _make_fleet(*model_params, n=1, tenants=reg)
    sched = fl.replicas[0].engine.scheduler
    # batch request is OLDER — pure youngest-first would pick the
    # interactive one; precedence must override
    batch = Request(prompt=[2, 3], max_tokens=2, tenant="bulk")
    batch.submitted_at, batch.slot = 1.0, 0
    inter = Request(prompt=[2, 3], max_tokens=2, tenant="vip")
    inter.submitted_at, inter.slot = 2.0, 1
    reg.register("bulk", "batch")
    reg.register("vip", "interactive")
    sched.running = {0: batch, 1: inter}
    probe = Request(prompt=[2], max_tokens=1, tenant="vip")
    assert sched._youngest_victim(exclude=probe) is batch
    # without a control plane, classic youngest-first returns
    sched.precedence_fn = None
    assert sched._youngest_victim(exclude=probe) is inter


# ---------------------------------------------------------------------------
# tenant identity survives resubmit and migration
# ---------------------------------------------------------------------------


def test_tenant_survives_death_resubmit(model_params):
    model, params = model_params
    plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.01),
                          kill_at={3: 0})
    fl, _ = _make_fleet(model, params, n=2, plan=plan)
    rng = np.random.RandomState(0)
    frids = [fl.submit(rng.randint(2, 50, size=5).tolist(), max_tokens=4,
                       tenant="carol") for _ in range(3)]
    _drain_all(fl)
    assert fl.metrics.resubmits >= 1
    for frid in frids:
        assert fl._requests[frid].tenant == "carol"
    # the SURVIVOR's engine billed carol, not default
    survivor = fl.replicas[1].engine
    assert set(survivor.tenant_counts()) <= {"carol"}
    assert fl.metrics.tenant_tokens.get("carol", 0) > 0
    check_control_conservation(fl)


def test_tenant_rides_the_migration_blob(model_params):
    model, params = model_params
    clock = ManualClock(tick_s=0.01)
    src = ServingEngine(model, params, eos_id=EOS, page_size=PAGE,
                        num_pages=32, max_pages_per_seq=8, max_slots=2,
                        buckets=(4, 8), time_fn=clock)
    dst = ServingEngine(model, params, eos_id=EOS, page_size=PAGE,
                        num_pages=32, max_pages_per_seq=8, max_slots=2,
                        buckets=(4, 8), time_fn=clock)
    rid = src.submit([2, 3, 4, 5, 6], max_tokens=6, tenant="mover")
    for _ in range(30):
        clock.advance(clock.tick_s)
        src.step()
        if rid in src.migratable_rids():
            break
    blob = export_chain(src, rid)
    assert blob.tenant == "mover"
    rid2 = import_chain(dst, blob)
    assert rid2 is not None
    assert dst._requests[rid2].tenant == "mover"
    src.cancel(rid)
    while dst.has_work:
        clock.advance(clock.tick_s)
        dst.step()
    assert_drained(dst)


# ---------------------------------------------------------------------------
# per-tenant observability: counters and labeled exposition
# ---------------------------------------------------------------------------


def test_per_tenant_counters_in_load_and_healthz(model_params):
    fl, _ = _make_fleet(*model_params, n=1)
    fl.submit([2, 3, 4, 5], max_tokens=4, tenant="alice")
    fl.submit([6, 7, 8, 9], max_tokens=4, tenant="bob")
    fl.step()
    ld = fl.replicas[0].engine.load()
    assert set(ld["tenants"]) == {"alice", "bob"}
    live = sum(c["running"] + c["queued"] for c in ld["tenants"].values())
    assert live == 2
    running = [t for t, c in ld["tenants"].items() if c["running"]]
    for t in running:
        assert ld["tenants"][t]["pages_in_use"] > 0
    hz = fl.healthz()
    assert set(hz["tenants"]) == {"alice", "bob"}
    assert hz["admission_ledger"]["alice"]["admitted"] == 1
    _drain_all(fl)


def test_tenant_labels_quoted_in_prometheus_exposition(model_params):
    model, params = model_params
    reg = MetricsRegistry()
    fl, _ = _make_fleet(model, params, n=1, registry=reg)
    fl.submit([2, 3, 4, 5], max_tokens=3, tenant="team-a")
    fl.submit([2, 3, 4, 5], max_tokens=3, tenant="team-b",
              deadline_s=0.0)                     # times out immediately
    _drain_all(fl)
    text = fl.metrics_text()
    assert 'fleet_tokens_total{tenant="team-a"}' in text
    assert 'serving_deadline_miss_total{' in text
    assert 'tenant="team-b"' in text
    assert 'serving_queue_wait_ms{' in text
    # snapshot (unquoted keys) and to_text (quoted) agree on the value
    snap = reg.snapshot()
    assert snap["fleet_tokens_total{tenant=team-a}"] > 0


# ---------------------------------------------------------------------------
# drain/join interplay with roles; autoscaler
# ---------------------------------------------------------------------------


def test_draining_last_prefill_replica_is_refused(model_params):
    model, params = model_params
    fl, _ = _make_fleet(model, params, n=2, roles=["prefill", "decode"])
    with pytest.raises(EnforceError, match="last prefill-capable"):
        fl.drain_replica(0)
    assert fl.replicas[0].state is ReplicaState.READY   # untouched
    # a second prefill-capable replica lifts the refusal
    idx = fl.add_replica(role="prefill")
    fl.step()
    assert fl.replica_state(idx) is ReplicaState.READY
    fl.drain_replica(0)
    assert fl.replicas[0].state is ReplicaState.DRAINING


def test_drain_refusal_never_blocks_unified_fleets(model_params):
    fl, _ = _make_fleet(*model_params, n=2)
    fl.drain_replica(0)                 # classic fleet: no role guard
    assert fl.replicas[0].state is ReplicaState.DRAINING


def test_autoscaler_grows_under_storm_and_shrinks_after(model_params):
    model, params = model_params
    reg = TenantRegistry()
    reg.register("storm", "batch")
    plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.02),
                          tenant_storm=("storm", 0, 6, 10))
    fl, _ = _make_fleet(
        model, params, n=1, plan=plan, tenants=reg, wfq=True,
        autoscale=AutoscalePolicy(min_replicas=1, max_replicas=3,
                                  buffered_hi=2, cooldown_ticks=2))
    rng = np.random.RandomState(0)
    tick = 0
    while tick < 6 or fl.has_work:
        if tick < 6 and tick % 2 == 0:
            for _ in range(plan.storm_factor(tick, "storm")):
                fl.submit(rng.randint(2, 50, size=6).tolist(),
                          max_tokens=3, tenant="storm")
        fl.step()
        tick += 1
        assert tick < 600, "fleet failed to drain"
    for _ in range(10):                 # idle tail: cold path + cooldowns
        fl.step()
    scaler = fl.autoscaler
    assert scaler.scale_ups >= 1
    assert scaler.scale_downs >= 1
    alive = [r for r in fl.replicas
             if r.state in (ReplicaState.READY, ReplicaState.JOINING)]
    assert 1 <= len(alive) <= 3
    check_control_conservation(fl)
    snap = fl.snapshot()
    # what scaling is for: fewer replica-ticks than a fleet pinned at the
    # ceiling would have burned over the same run
    assert 0 < snap["control_replica_ticks"] < 3 * fl._tick


def test_autoscaler_never_drains_last_prefill_replica(model_params):
    model, params = model_params
    fl, _ = _make_fleet(
        model, params, n=2, roles=["prefill", "decode"],
        autoscale=AutoscalePolicy(min_replicas=1, max_replicas=3,
                                  cooldown_ticks=0))
    for _ in range(8):                  # idle from the start: cold ticks
        fl.step()
    # the decode replica may drain; the lone prefill replica never does
    assert fl.replicas[0].role == "prefill"
    assert fl.replicas[0].state in (ReplicaState.READY, ReplicaState.JOINING)


def test_scale_up_under_kill_is_exactly_once(model_params):
    """Chaos pin: a replica joins (autoscale) while another dies
    mid-decode on the same trace — every stream exactly-once, ledger
    balanced, zero leaks on every replica including the killed one."""
    model, params = model_params
    reg = TenantRegistry()
    reg.register("a", "standard")
    reg.register("b", "standard")
    plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.02),
                          kill_at={4: 0},
                          tenant_storm=("b", 0, 6, 6))
    fl, _ = _make_fleet(
        model, params, n=2, plan=plan, tenants=reg, wfq=True,
        autoscale=AutoscalePolicy(min_replicas=2, max_replicas=4,
                                  buffered_hi=2, cooldown_ticks=2))
    rng = np.random.RandomState(0)
    streams = {}
    tick = 0
    while tick < 6 or fl.has_work:
        if tick < 6 and tick % 2 == 0:
            for tenant in ("a", "b"):
                for _ in range(plan.storm_factor(tick, tenant)):
                    toks = []
                    frid = fl.submit(rng.randint(2, 50, size=6).tolist(),
                                     max_tokens=3, tenant=tenant,
                                     on_token=toks.append)
                    streams[frid] = toks
        fl.step()
        tick += 1
        assert tick < 800, "fleet failed to drain"
    assert fl.metrics.replicas_dead >= 1
    assert fl.autoscaler.scale_ups >= 1
    assert fl.metrics.duplicate_completions == 0
    for frid, toks in streams.items():
        if fl.status(frid) is RequestStatus.COMPLETED:
            # the exactly-once fence: the callback stream IS the result
            assert toks == fl.result(frid)
    check_control_conservation(fl)
