"""Hierarchical KV cache (round 21): host-RAM spill tier with verified
swap-in, graceful degradation under memory pressure, and crash-warm
restart — all on injected clocks, no wall-clock sleeps.

The load-bearing invariants:

- a page swapped in from host memory produces TOKEN-IDENTICAL output to
  a cold re-prefill (the tier is a placement optimization, never a
  semantics change);
- a torn spill or a seeded bit-flip is ALWAYS caught by the per-page
  checksum at swap-in and degrades to a miss + ``HOSTTIER-CORRUPT`` —
  a corrupt page is never served;
- pages conserve across THREE states (device / host / dropped): the
  ``HOSTTIER-LEAK`` ledger balances at any tick, and rides every
  suite's ``assert_serving_drained`` via ``check_page_conservation``;
- the degradation ladder is ordered: device exhaustion spills harder,
  a full host tier LRU-drops its own pages, and only then does the
  engine shed/preempt;
- ``restart_replica`` re-adopts a dead replica's host tier (verified
  page by page) instead of starting cold, composed with the
  lease/fence/resubmit lifecycle and the exactly-once stream fence.

rid counters are GLOBAL (module-level), so cross-engine parity always
compares by submission order within one engine, never by rid.
"""

import jax
import numpy as np
import pytest

from paddle_tpu.platform.enforce import EnforceError
from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.serving.engine import DecoderLM, ServingEngine
from paddle_tpu.serving.faults import (FaultPlan, FleetFaultPlan,
                                       ManualClock, PageLeakError)
from paddle_tpu.serving.fleet import FleetRouter, ReplicaState
from paddle_tpu.serving.kv_cache import (_CHAIN_SEED, HostPageTier,
                                         page_checksum)

from conftest import assert_serving_drained as assert_drained  # noqa: E402

pytestmark = [pytest.mark.serving, pytest.mark.hosttier]

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
    base = dict(eos_id=EOS, page_size=PAGE, num_pages=16,
                max_pages_per_seq=8, max_slots=2, buckets=(8, 16),
                host_tier_bytes=1 << 20, swap_in_budget=4)
    base.update(kw)
    if "faults" not in base:
        base["faults"] = FaultPlan(seed=0, clock=ManualClock(tick_s=0.01))
    return ServingEngine(model, params, **base)


def _prompt(n=16, seed=0):
    return np.random.RandomState(seed).randint(2, 50, size=n).tolist()


def _payload(fill=1.0):
    """One synthetic page payload shaped like read_pages output."""
    k = np.full((1, 1, PAGE, 2, 8), fill, np.float32)
    v = np.full((1, 1, PAGE, 2, 8), fill + 0.5, np.float32)
    return k, v, None, None


# ---------------------------------------------------------------------------
# HostPageTier unit tests
# ---------------------------------------------------------------------------


class TestHostTierUnit:
    def test_depth_one_writer(self):
        """spill() stages; the NEXT spill (or pump/flush) commits — at
        most one write is ever in flight, exactly the checkpointer's
        pipelined-writer discipline."""
        tier = HostPageTier(1 << 20)
        tier.spill(1, _CHAIN_SEED, (1, 2, 3, 4), _payload())
        assert len(tier) == 0 and tier.spills == 1   # staged, not resident
        tier.spill(2, 1, (5, 6, 7, 8), _payload(2.0))
        assert len(tier) == 1                        # first committed
        assert tier.pump(tick=0) == 1
        assert len(tier) == 2 and tier.pump(tick=1) == 0
        tier.check()

    def test_checksum_roundtrip_and_verify(self):
        tier = HostPageTier(1 << 20)
        k, v, _, _ = _payload()
        tier.spill(7, _CHAIN_SEED, (9, 9, 9, 9), (k, v, None, None))
        tier.flush()
        rec = tier.take_verified(7, _CHAIN_SEED, (9, 9, 9, 9))
        assert rec is not None and tier.swap_ins == 1
        np.testing.assert_array_equal(rec.k, k)
        np.testing.assert_array_equal(rec.v, v)
        assert rec.checksum == page_checksum(rec.k, rec.v)
        tier.check()

    def test_tampered_bytes_degrade_to_miss(self):
        """Corruption after commit is caught at swap-in: the record is
        consumed as HOSTTIER-CORRUPT, never returned."""
        tier = HostPageTier(1 << 20)
        tier.spill(7, _CHAIN_SEED, (9, 9, 9, 9), _payload())
        tier.flush()
        rec = next(iter(tier._index.values()))
        rec.v.reshape(-1)[0] += 1.0          # bit rot
        assert tier.take_verified(7, _CHAIN_SEED, (9, 9, 9, 9)) is None
        assert tier.corrupt == 1 and tier.swap_ins == 0
        tier.check()

    def test_peek_is_pure(self):
        tier = HostPageTier(1 << 20)
        tier.spill(7, _CHAIN_SEED, (9, 9, 9, 9), _payload())
        tier.flush()
        assert tier.peek(7, _CHAIN_SEED, (9, 9, 9, 9)) is not None
        assert tier.peek(7, _CHAIN_SEED, (9, 9, 9, 8)) is None  # wrong toks
        assert tier.peek(7, 123, (9, 9, 9, 9)) is None          # wrong prev
        assert len(tier) == 1 and tier.swap_ins == 0
        tier.check()

    def test_lru_drop_at_capacity(self):
        """Host tier full -> the OLDEST host page drops (ladder rung 3);
        the ledger still balances."""
        one = sum(x.nbytes for x in _payload()[:2])
        tier = HostPageTier(2 * one)
        for i in range(4):
            tier.spill(10 + i, _CHAIN_SEED, (i,) * PAGE, _payload(float(i)))
        tier.flush()
        assert len(tier) == 2 and tier.dropped == 2
        assert tier.peek(10, _CHAIN_SEED, (0,) * PAGE) is None   # oldest out
        assert tier.peek(13, _CHAIN_SEED, (3,) * PAGE) is not None
        assert tier.resident_bytes <= tier.capacity_bytes
        tier.check()

    def test_forget_and_adopt(self):
        """forget() drops named keys; adopt() re-verifies a dead tier's
        pages into a fresh one, balancing BOTH ledgers (handed_off on
        the donor, adopted/restored on the successor)."""
        old = HostPageTier(1 << 20)
        for i in range(3):
            old.spill(20 + i, _CHAIN_SEED, (i,) * PAGE, _payload(float(i)))
        old.flush()
        old.forget([21])
        assert old.dropped == 1 and len(old) == 2
        # corrupt one survivor: adoption must catch it
        next(iter(old._index.values())).k.reshape(-1)[0] += 9.0
        new = HostPageTier(1 << 20)
        new.adopt(old)
        assert new.restored == 1 and new.corrupt == 1
        assert len(old) == 0 and old.handed_off == 2
        old.check()
        new.check()

    def test_ledger_violation_raises(self):
        tier = HostPageTier(1 << 20)
        tier.spill(1, _CHAIN_SEED, (1,) * PAGE, _payload())
        tier.flush()
        tier.spills += 1                      # cook the books
        with pytest.raises(PageLeakError, match="HOSTTIER-LEAK"):
            tier.check()


# ---------------------------------------------------------------------------
# engine-level: spill, verified swap-in, parity, degradation
# ---------------------------------------------------------------------------


def _roundtrip(eng, prompt, max_tokens=6):
    """cold serve -> flush (spill everything) -> warm serve on the SAME
    engine; returns (cold, warm) token lists."""
    r1 = eng.submit(list(prompt), max_tokens=max_tokens)
    eng.run()
    cold = eng.result(r1)
    eng.cache.flush()
    r2 = eng.submit(list(prompt), max_tokens=max_tokens)
    eng.run()
    return cold, eng.result(r2)


class TestEngineSwapIn:
    def test_swap_in_parity_vs_cold_prefill(self, model_params):
        """The tentpole parity pin: an evicted-then-spilled prefix served
        back through verified swap-in is token-identical to the cold
        serve, and the second serve barely re-prefills."""
        eng = _engine(*model_params)
        cold, warm = _roundtrip(eng, _prompt())
        assert warm == cold
        # the warm serve forwarded fewer prompt tokens than the cold one
        # (both are in the total: under twice the prompt in all)
        assert eng.metrics.prefill_tokens < 2 * len(_prompt())
        snap = eng.host_tier.snapshot()
        assert snap["host_swap_outs"] >= 4     # 4 full pages spilled
        assert snap["host_swap_ins"] >= 4      # ... and all came back
        assert snap["host_corrupt"] == 0
        assert eng._host_hits >= 1
        hz = eng.healthz()
        assert hz["host_swap_ins"] == snap["host_swap_ins"]
        assert_drained(eng)

    def test_swap_in_budget_bounds_per_tick(self, model_params):
        """swap_in_budget=1 swaps exactly ONE page ahead of admission —
        the rest of the prefix re-prefills normally (swap-in never
        delays admission to finish the chain) — and stays
        token-identical.  The unswapped host pages remain resident."""
        eng = _engine(*model_params, swap_in_budget=1)
        cold, warm = _roundtrip(eng, _prompt())
        assert warm == cold
        snap = eng.host_tier.snapshot()
        assert snap["host_swap_ins"] == 1
        assert snap["pages_host"] >= 2        # chain tail stayed on host
        assert_drained(eng)

    def test_torn_spill_degrades_to_miss(self, model_params):
        """Fault rung: the FIRST spill commits torn (tail half of V
        zeroed after the checksum was taken).  Swap-in must catch it —
        HOSTTIER-CORRUPT, a plain re-prefill, identical tokens."""
        eng = _engine(*model_params,
                      faults=FaultPlan(seed=0,
                                       clock=ManualClock(tick_s=0.01),
                                       torn_spill_at={0}))
        cold, warm = _roundtrip(eng, _prompt())
        assert warm == cold                    # never served corrupt KV
        assert eng.host_tier.corrupt >= 1
        assert_drained(eng)

    def test_bitflip_caught_never_hittable(self, model_params):
        """A seeded one-byte flip in K is caught by the checksum; the
        corrupt record is consumed (miss), never hittable again."""
        eng = _engine(*model_params,
                      faults=FaultPlan(seed=0,
                                       clock=ManualClock(tick_s=0.01),
                                       bitflip_spill_at={0}))
        cold, warm = _roundtrip(eng, _prompt())
        assert warm == cold
        assert eng.host_tier.corrupt >= 1
        # the corrupted chain head is gone for good: a third serve of the
        # same prompt cannot re-hit the corrupt record
        before = eng.host_tier.corrupt
        r3 = eng.submit(_prompt(), max_tokens=6)
        eng.run()
        assert eng.result(r3) == cold
        assert eng.host_tier.corrupt == before
        assert_drained(eng)

    def test_slow_host_io_stalls_writer_not_decode(self, model_params):
        """A slow-host-I/O window leaves the staged spill pending
        (spill_stall_ticks counts the wait) but decode keeps running and
        drain flushes it — nothing lost, nothing leaked."""
        eng = _engine(*model_params,
                      faults=FaultPlan(seed=0,
                                       clock=ManualClock(tick_s=0.01),
                                       slow_host_io=(0, 10_000)))
        cold, warm = _roundtrip(eng, _prompt())
        assert warm == cold
        assert eng.host_tier.spill_stall_ticks > 0
        assert_drained(eng)

    def test_int8_host_dtype_parity(self, model_params):
        """host_kv_dtype="int8" transcodes float pages on spill (~4x
        host capacity) and dequantizes on swap-in; greedy decode over a
        tiny model stays token-identical."""
        eng = _engine(*model_params, host_kv_dtype="int8")
        cold, warm = _roundtrip(eng, _prompt())
        assert warm == cold
        snap = eng.host_tier.snapshot()
        assert snap["host_swap_ins"] >= 1
        assert_drained(eng)

    def test_pressure_ladder_ordering(self, model_params):
        """Graceful degradation: a pool too small for the working set
        spills on eviction (rung 2), a host tier sized for ~2 pages
        LRU-drops its own oldest pages (rung 3) — and the engine never
        had to shed or preempt (rung 4 stays dry)."""
        one_page = 2 * (1 * 1 * PAGE * 2 * 8 * 4)     # k+v f32 bytes
        eng = _engine(*model_params, num_pages=12,
                      host_tier_bytes=2 * one_page + one_page // 2)
        outs = []
        for s in range(6):
            rid = eng.submit(_prompt(12, seed=s), max_tokens=4)
            eng.run()
            outs.append(eng.result(rid))
            eng.cache.flush()                 # force demotion pressure
        snap = eng.host_tier.snapshot()
        assert snap["host_swap_outs"] >= 6    # rung 2: spilling hard
        assert snap["host_dropped"] >= 1      # rung 3: host LRU-drop
        assert eng.metrics.shed == 0          # rung 4: never reached
        assert eng.metrics.preemptions == 0
        assert all(o is not None for o in outs)
        assert_drained(eng)

    def test_three_state_conservation_rides_drain_check(self, model_params):
        """check_page_conservation now covers the host ledger: cooking
        the tier's books makes the ENGINE check raise HOSTTIER-LEAK."""
        eng = _engine(*model_params)
        _roundtrip(eng, _prompt())
        eng.check_page_conservation()         # clean first
        eng.host_tier.spills += 3
        with pytest.raises(PageLeakError, match="HOSTTIER-LEAK"):
            eng.check_page_conservation()
        eng.host_tier.spills -= 3
        assert_drained(eng)

    def test_gauges_in_load_healthz_and_tenants(self, model_params):
        eng = _engine(*model_params)
        r1 = eng.submit(_prompt(), max_tokens=4, tenant="acme")
        eng.run()
        eng.cache.flush()
        eng.host_tier.flush()                 # commit the staged spill
        assert eng.load()["pages_host"] >= 4
        hz = eng.healthz()
        assert hz["pages_host"] >= 4
        assert hz["host_swap_outs"] >= 4
        assert eng.tenant_counts()["acme"]["pages_host"] >= 4
        r2 = eng.submit(_prompt(), max_tokens=4, tenant="acme")
        eng.run()
        assert eng.result(r2) == eng.result(r1)
        assert eng.healthz()["host_swap_ins"] >= 1
        assert_drained(eng)

    def test_tier_off_is_inert(self, model_params):
        """host_tier_bytes=0 (the default flag) keeps the classic
        engine: no tier object, zeroed gauges, identical behavior."""
        eng = _engine(*model_params, host_tier_bytes=0)
        assert eng.host_tier is None
        cold, warm = _roundtrip(eng, _prompt())
        assert warm == cold
        assert eng.healthz()["pages_host"] == 0
        assert_drained(eng)


# ---------------------------------------------------------------------------
# fleet-level: crash-warm restart, exactly-once, migration compose
# ---------------------------------------------------------------------------


def _mk_fleet(model, params, n=2, *, plan=None, tier=1 << 20, **kw):
    plan = plan or FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.01))

    def mk(i, time_fn):
        return ServingEngine(model, params, eos_id=EOS, page_size=PAGE,
                             num_pages=32, max_pages_per_seq=8, max_slots=4,
                             buckets=(8, 16), time_fn=time_fn,
                             host_tier_bytes=tier, swap_in_budget=4)

    return FleetRouter(mk, n, heartbeat_s=0.05, resubmit_budget=2,
                       faults=plan, **kw)


class TestFleetWarmRestart:
    def test_restart_replica_adopts_host_tier(self, model_params):
        """Kill a replica whose host tier holds spilled pages; the warm
        successor re-adopts them (verified) and serves the same prompt
        token-identically with real swap-ins — not a cold start."""
        fleet = _mk_fleet(*model_params)
        prompt = _prompt()
        f1 = fleet.submit(list(prompt), max_tokens=6)
        fleet.run(max_ticks=200)
        cold = fleet.result(f1)
        victim = next(r.idx for r in fleet.replicas
                      if r.engine.cache is not None and len(r.engine.cache))
        fleet.replicas[victim].engine.cache.flush()
        fleet.kill_replica(victim)
        new_idx = fleet.restart_replica(victim)
        assert fleet.metrics.warm_restarts == 1
        assert fleet.metrics.pages_restored >= 4
        fleet.drain_replica(1 - victim)       # force traffic to successor
        for _ in range(5):
            fleet.step()
        assert fleet.replica_state(new_idx) is ReplicaState.READY
        f2 = fleet.submit(list(prompt), max_tokens=6)
        fleet.run(max_ticks=200)
        assert fleet.result(f2) == cold
        succ = fleet.replicas[new_idx].engine
        assert succ.host_tier.snapshot()["host_swap_ins"] >= 1
        assert fleet.metrics.duplicate_completions == 0
        fleet.check_fleet_conservation()

    def test_restart_requires_dead(self, model_params):
        fleet = _mk_fleet(*model_params)
        with pytest.raises(EnforceError):
            fleet.restart_replica(0)

    def test_kill_mid_flight_exactly_once_with_restart(self, model_params):
        """A kill mid-decode resubmits to the survivor; the exactly-once
        fence dedups the replay; restart_replica afterwards neither
        duplicates completions nor corrupts the stream."""
        plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.01),
                              kill_at={3: 0})
        fleet = _mk_fleet(*model_params, plan=plan)
        streams = {}
        frids = []
        for s in range(4):
            p = _prompt(12, seed=s)
            streams[s] = []
            frids.append(fleet.submit(
                p, max_tokens=10,
                on_token=lambda t, s=s: streams[s].append(t)))
        fleet.run(max_ticks=400)
        # the injected kill fenced replica 0: restart it warm
        dead = [r.idx for r in fleet.replicas
                if r.state is ReplicaState.DEAD]
        assert dead
        fleet.restart_replica(dead[0])
        for _ in range(3):
            fleet.step()
        for s, frid in enumerate(frids):
            res = fleet.result(frid)
            if res is not None:               # completed (not shed)
                assert streams[s] == res      # exactly-once, in order
        assert fleet.metrics.duplicate_completions == 0
        fleet.check_fleet_conservation()

    def test_migrated_chain_source_host_pages_forgotten(self, model_params):
        """Spill + migration compose: when a chain hands off to a decode
        replica, any host copies the source spilled for that chain are
        forgotten — a later warm restart of the source cannot re-adopt
        pages the migration already moved (no double-adopt)."""
        model, params = model_params
        plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.01))

        def mk(i, time_fn):
            return ServingEngine(model, params, eos_id=EOS, page_size=PAGE,
                                 num_pages=32, max_pages_per_seq=8,
                                 max_slots=4, buckets=(8, 16),
                                 time_fn=time_fn, host_tier_bytes=1 << 20,
                                 swap_in_budget=4)

        fleet = FleetRouter(mk, 2, heartbeat_s=0.05, resubmit_budget=2,
                            faults=plan, roles=["prefill", "decode"],
                            migrate_budget=64)
        prompt = _prompt()
        src = fleet.replicas[0].engine
        frid = fleet.submit(list(prompt), max_tokens=6)
        # tick until the handoff is pending, then plant host copies of
        # the chain on the source BEFORE the pump applies it
        for _ in range(50):
            fleet.step()
            if frid in fleet._mig_pending:
                break
        assert frid in fleet._mig_pending
        keys = src.cache.chain_keys(prompt)
        for i, key in enumerate(keys):
            prev = _CHAIN_SEED if i == 0 else keys[i - 1]
            src.host_tier.spill(key, prev,
                                tuple(prompt[i * PAGE:(i + 1) * PAGE]),
                                _payload(float(i)))
        src.host_tier.flush()
        assert len(src.host_tier) == len(keys)
        fleet.run(max_ticks=200)
        assert fleet.metrics.migrations_applied >= 1
        # every chain key was forgotten at apply time
        for i, key in enumerate(keys):
            prev = _CHAIN_SEED if i == 0 else keys[i - 1]
            assert src.host_tier.peek(
                key, prev, tuple(prompt[i * PAGE:(i + 1) * PAGE])) is None
        assert src.host_tier.dropped >= len(keys)
        # ... so a warm restart of the source re-adopts NONE of them
        fleet.kill_replica(0)
        fleet.restart_replica(0)
        assert fleet.metrics.pages_restored == 0
        assert fleet.metrics.duplicate_completions == 0
        fleet.check_fleet_conservation()

    def test_fleet_healthz_reports_pages_host(self, model_params):
        fleet = _mk_fleet(*model_params)
        f1 = fleet.submit(_prompt(), max_tokens=4, tenant="acme")
        fleet.run(max_ticks=200)
        for rep in fleet.replicas:
            if rep.engine.cache is not None:
                rep.engine.cache.flush()
            if rep.engine.host_tier is not None:
                rep.engine.host_tier.flush()
        hz = fleet.healthz()
        assert sum(r["pages_host"] for r in hz["replicas"].values()) >= 4
        assert "pages_host" in hz["tenants"]["acme"]
        assert hz["tenants"]["acme"]["pages_host"] >= 4
        fleet.check_fleet_conservation()
