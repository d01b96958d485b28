"""Tensor-parallel serving: megatron-sharded decode/prefill over a
`model` mesh axis (round 13).

What is pinned here:

- **greedy parity**: tp in {1, 2, 4} engines produce token-identical
  outputs to the replicated engine AND the non-paged oracle on the
  virtual-8 mesh — sharding the heads/FFN columns changes the placement,
  never the trajectory;
- **per-chip byte accounting**: ``PagedKVConfig.bytes_per_page`` /
  ``pages_for_budget`` charge each chip 1/tp of every page (int8 scale
  arrays shard with their KV heads), asserted to the exact byte;
- **actionable validation**: every divisibility failure (query heads,
  KV heads, the GQA tp>KV-heads corner, FFN width) names the bad number
  and a fix, from BOTH ``ServingEngine(mesh=)`` and ``shard_plan()``;
- **cache semantics survive sharding**: COW fork + prefix-cache hits on
  a sharded pool, chaos/fault spot-run with tp=2, 0 page/ref leaks;
- **no new compile dimension**: a sealed TP steady state still compiles
  exactly once per (decode_bucket, prefill_bucket) pair;
- **reduce-not-gather, statically**: the sharding auditor over the real
  TP ``serving.step`` reports 0 ERRORs and a collective estimate equal
  to the closed-form megatron budget (2 row-parallel psums per layer,
  ``2*b*(N-1)/N`` each) — no implicit all-gather on the decode hot path;
- **one placement story**: ``shard_plan`` composes with ZeRO via
  ``plan_param_attrs`` (TP weights keep their layout, the replicated
  remainder still ZeRO-shards), and the fleet's replica unit becomes a
  mesh slice (``FleetRouter.over_mesh_slices``).
"""

import numpy as np
import jax
import pytest

from paddle_tpu.platform.enforce import EnforceError
from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.analysis.retrace import auditor
from paddle_tpu.parallel.mesh import make_mesh, mesh_slices
from paddle_tpu.serving import DecoderLM, FaultPlan, ServingEngine
from paddle_tpu.serving.engine import greedy_decode_reference, validate_tp
from paddle_tpu.serving.kv_cache import PagedKVConfig, pages_for_budget

from conftest import assert_serving_drained as assert_drained  # noqa: E402
from conftest import stored_pool  # noqa: E402

pytestmark = [pytest.mark.serving, pytest.mark.shard]

EOS = 1


def _model(num_heads=4, num_kv_heads=None, head_dim=8, layers=2):
    return DecoderLM(vocab_size=64, num_layers=layers,
                     num_heads=num_heads, num_kv_heads=num_kv_heads,
                     head_dim=head_dim, max_positions=128)


def _mesh(tp):
    return make_mesh((tp,), ("model",), jax.devices()[:tp])


def _engine(model, params, mesh=None, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_pages_per_seq", 12)
    kw.setdefault("max_slots", 4)
    kw.setdefault("buckets", (4, 8, 16))
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(model, params, eos_id=EOS, mesh=mesh, **kw)


# ---------------------------------------------------------------------------
# per-chip byte accounting (pool budget is PER CHIP under TP)
# ---------------------------------------------------------------------------


def test_bytes_per_page_exact_per_chip_f32_and_int8():
    base = dict(num_layers=2, num_heads=4, head_dim=16, page_size=16,
                num_pages=8, max_pages_per_seq=4, num_kv_heads=2)
    f32 = PagedKVConfig(dtype=np.float32, **base)
    # K+V, 2 layers, 16 tokens, 2 KV heads, 16 dims, 4 bytes
    assert f32.bytes_per_page() == 2 * 2 * 16 * 2 * 16 * 4 == 8192
    # tp=2: ONE KV head per chip — exactly half the bytes on each chip
    f32_tp = PagedKVConfig(dtype=np.float32, tp=2, **base)
    assert f32_tp.bytes_per_page() == 4096
    assert f32_tp.kv_bytes() == 8 * 4096
    # int8: values 1 byte + per-token f32 scales, scales shard with
    # their KV heads too
    i8 = PagedKVConfig(dtype=np.int8, tp=2, **base)
    assert i8.bytes_per_page() == \
        2 * (2 * 16 * 1 * 16 * 1 + 2 * 16 * 1 * 4) == 1280
    assert PagedKVConfig(dtype=np.int8, **base).bytes_per_page() == 2560


def test_pages_for_budget_is_per_chip_and_multiplies_with_tp():
    args = dict(num_layers=2, num_heads=4, head_dim=16, page_size=16,
                num_kv_heads=2)
    budget = 64 * 8192                    # 64 f32 pages at tp=1
    assert pages_for_budget(budget, dtype="float32", **args) == 64
    # the same PER-CHIP budget buys tp x the pages: each chip stores
    # only its 1/tp KV-head shard of every page
    assert pages_for_budget(budget, dtype="float32", tp=2, **args) == 128
    # and int8 compounds on top (4x values minus the f32 scale overhead)
    assert pages_for_budget(budget, dtype="int8", tp=2, **args) == \
        budget // 1280


def test_engine_pool_bytes_budget_accounts_tp(rng):
    model = _model()
    params = model.init_params(jax.random.PRNGKey(0))
    budget = 48 * PagedKVConfig(
        num_layers=model.num_layers, num_heads=model.num_heads,
        head_dim=model.head_dim, page_size=4, num_pages=2,
        max_pages_per_seq=1).bytes_per_page()
    rep = _engine(model, params, num_pages=None, pool_bytes=budget)
    tp2 = _engine(model, params, mesh=_mesh(2), num_pages=None,
                  pool_bytes=budget)
    assert rep.pool.num_usable == 47          # 48 minus the null page
    assert tp2.pool.num_usable == 95          # 2x pages, same chip bytes
    assert tp2.kv_cfg.kv_bytes() <= budget
    assert tp2.healthz()["tp"] == 2


# ---------------------------------------------------------------------------
# validation: actionable errors from both construction paths
# ---------------------------------------------------------------------------


def test_validation_num_heads_not_divisible():
    model = _model(num_heads=3, head_dim=8)
    with pytest.raises(EnforceError, match="num_heads .3.*divides 3"):
        validate_tp(model, 2)
    with pytest.raises(EnforceError, match="num_heads"):
        model.shard_plan(tp=2)
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(EnforceError, match="num_heads"):
        _engine(model, params, mesh=_mesh(2))


def test_validation_gqa_corner_tp_exceeds_kv_heads():
    model = _model(num_heads=4, num_kv_heads=2)
    with pytest.raises(EnforceError, match="GQA corner.*lower tp"):
        model.shard_plan(tp=4)
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(EnforceError, match="GQA corner"):
        _engine(model, params, mesh=_mesh(4))


def test_validation_kv_heads_not_divisible():
    # tp=2 <= kvh=3 passes the corner check but 3 % 2 != 0
    model = _model(num_heads=6, num_kv_heads=3)
    with pytest.raises(EnforceError, match="num_kv_heads .3."):
        validate_tp(model, 2)


def test_validation_ffn_width_not_divisible():
    model = _model(num_heads=4)
    model.ffn_dim = 6                       # force a bad width
    with pytest.raises(EnforceError, match="FFN width .6."):
        validate_tp(model, 4)


def test_validation_mesh_without_model_axis():
    model = _model()
    params = model.init_params(jax.random.PRNGKey(0))
    mesh = make_mesh((2,), ("data",), jax.devices()[:2])
    with pytest.raises(EnforceError, match="no 'model' axis"):
        _engine(model, params, mesh=mesh)


def test_kv_config_rejects_tp_not_dividing_kv_heads():
    with pytest.raises(EnforceError, match="shards whole KV heads"):
        PagedKVConfig(num_layers=1, num_heads=4, head_dim=8, page_size=4,
                      num_pages=8, max_pages_per_seq=2, num_kv_heads=2,
                      tp=4)


# ---------------------------------------------------------------------------
# greedy parity + cache semantics on the sharded pool
# ---------------------------------------------------------------------------


def _run_prompts(eng, prompts, max_tokens=8, max_ticks=500):
    rids = [eng.submit(p, max_tokens=max_tokens) for p in prompts]
    res = eng.run(max_ticks=max_ticks)
    assert_drained(eng)
    return [res[r] for r in rids]


def test_greedy_parity_tp_1_2_4_vs_replicated_oracle(rng):
    model = _model(num_heads=4, head_dim=8)
    params = model.init_params(jax.random.PRNGKey(0))
    prompts = [rng.randint(2, 64, size=rng.randint(4, 20)).tolist()
               for _ in range(5)]
    rep = _run_prompts(_engine(model, params), prompts)
    oracle = [greedy_decode_reference(model, params, p, 8, EOS)
              for p in prompts]
    assert rep == oracle
    for tp in (1, 2, 4):
        eng = _engine(model, params, mesh=_mesh(tp))
        assert eng.tp == tp
        assert _run_prompts(eng, prompts) == rep, f"tp={tp} diverged"


def test_cow_fork_and_prefix_hit_on_sharded_pool(rng):
    model = _model(num_heads=4, num_kv_heads=2)
    params = model.init_params(jax.random.PRNGKey(0))
    shared = rng.randint(2, 64, size=8).tolist()    # two FULL pages
    tail = rng.randint(2, 64, size=9).tolist()

    def run(mesh):
        eng = _engine(model, params, mesh=mesh)
        r1 = eng.submit(shared, max_tokens=6)
        eng.run(max_ticks=300)
        r2 = eng.submit(shared, max_tokens=6)       # full cover: COW
        eng.run(max_ticks=300)
        r3 = eng.submit(shared + tail, max_tokens=6)  # mid-prompt hit
        res = eng.run(max_ticks=400)
        assert_drained(eng)
        snap = eng.metrics.snapshot()
        assert snap["cow_forks"] >= 1
        assert snap["prefix_hit_rate"] > 0
        return [res[r] for r in (r1, r2, r3)]

    rep = run(None)
    assert rep[0] == rep[1]                         # cache parity
    assert run(_mesh(2)) == rep


def test_chaos_spot_run_tp2_conserves_pages_and_refs(rng):
    model = _model(num_heads=4, num_kv_heads=2)
    params = model.init_params(jax.random.PRNGKey(0))
    faults = FaultPlan(decode_errors={3: 1}, page_pressure=(2, 8, 12))
    eng = _engine(model, params, mesh=_mesh(2), kv_dtype="int8",
                  faults=faults)
    rids = [eng.submit(rng.randint(2, 64, size=rng.randint(4, 24)).tolist(),
                       max_tokens=8) for _ in range(6)]
    eng.step()
    faults.poison_nan(rids[2])                      # sharded FAILED scrub
    eng.run(max_ticks=800)
    assert_drained(eng)
    statuses = {r: str(eng.status(r)) for r in rids}
    assert statuses[rids[2]] == "failed"
    assert all(eng.status(r).terminal for r in rids)
    assert eng.metrics.retries >= 1                 # transient absorbed


# ---------------------------------------------------------------------------
# compile discipline: TP adds no compile dimension
# ---------------------------------------------------------------------------


def test_sealed_tp_steady_state_one_compile_per_pair(rng):
    old = FLAGS.jit_audit
    FLAGS.jit_audit = True
    auditor().reset()
    try:
        model = _model(num_heads=4, num_kv_heads=2)
        params = model.init_params(jax.random.PRNGKey(0))
        eng = _engine(model, params, mesh=_mesh(2))
        # warmup: decode-only + the pair buckets the replay will use
        eng.submit(rng.randint(2, 64, size=4).tolist(), max_tokens=8)
        eng.step()
        eng.submit(rng.randint(2, 64, size=20).tolist(), max_tokens=6)
        eng.run(max_ticks=400)
        compiles = auditor().compile_count("serving.step")
        assert compiles >= 2                  # >1 pair exercised
        auditor().seal()
        eng.submit(rng.randint(2, 64, size=4).tolist(), max_tokens=8)
        eng.step()
        eng.submit(rng.randint(2, 64, size=19).tolist(), max_tokens=6)
        eng.run(max_ticks=400)
        auditor().assert_no_retraces()        # sealed: zero new compiles
        auditor().assert_budget("serving.step", compiles)
    finally:
        FLAGS.jit_audit = old
        auditor().reset()


def test_tp_and_replicated_engines_share_site_without_false_retrace(rng):
    """Same geometry, same shapes, different shardings: jit legitimately
    compiles both, and the sharding-aware signature must keep them
    distinct instead of reporting a same-signature retrace."""
    old = FLAGS.jit_audit
    FLAGS.jit_audit = True
    auditor().reset()
    try:
        model = _model(num_heads=4)
        params = model.init_params(jax.random.PRNGKey(0))
        prompts = [rng.randint(2, 64, size=6).tolist()]
        _run_prompts(_engine(model, params), prompts, max_tokens=4)
        _run_prompts(_engine(model, params, mesh=_mesh(2)), prompts,
                     max_tokens=4)
        auditor().assert_no_retraces()
    finally:
        FLAGS.jit_audit = old
        auditor().reset()


# ---------------------------------------------------------------------------
# the sharding gate on the TP hot path: reduce-not-gather, closed form
# ---------------------------------------------------------------------------


def test_tp_step_audits_clean_comm_equals_closed_form():
    from paddle_tpu.analysis import sharding as S

    old = FLAGS.jit_audit
    FLAGS.jit_audit = True
    auditor().reset()
    try:
        eng = S.drive_serving_tp_steady_state(tp=2, kv_dtype="int8")
        assert eng is not None
        reps = S.audit_sharding_sites(
            sites=["serving.step", "serving.fork_page",
                   "serving.zero_pages"])
        for name, rep in reps.items():
            assert not rep.errors, (name, [d.message for d in rep.errors])
            assert not any("implicit-all-gather" in d.message
                           for d in rep.diagnostics), name
        # fork/zero stay collective-free even sharded
        assert reps["serving.fork_page"].comm_bytes == 0.0
        assert reps["serving.zero_pages"].comm_bytes == 0.0
        # the audited step estimate IS the closed-form megatron budget:
        # 2 row-parallel psums per layer, 2*b*(N-1)/N each over the
        # [rows, E] f32 activation — for every signature, take the max
        rec = auditor().sites["serving.step"]
        expected = 0.0
        for _sig, cap in rec.captured.items():
            # the packed buffer's length says the prefill bucket
            p_tokens = eng._tick_parts(
                np.empty(cap.args[2].shape, np.int32), eng._k1)[3]
            rows = eng._max_slots * eng._k1 + p_tokens.shape[0]
            expected = max(expected, eng.tp_step_comm_bytes(rows))
        assert expected > 0.0
        assert reps["serving.step"].comm_bytes == expected
    finally:
        FLAGS.jit_audit = old
        auditor().reset()


def test_replicated_contract_still_pins_zero_comm():
    """The mesh=None baseline contract did NOT silently loosen: specs
    all P(), comm budget 0."""
    model = _model()
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params)
    c = eng._step_contract
    assert c.in_specs == ((),) and c.out_specs == ((),)
    assert c.comm_bytes == 0.0 and c.mesh_axes == ()
    assert eng.tp_step_comm_bytes(100) == 0.0


# ---------------------------------------------------------------------------
# one placement story: ZeRO composition + fleet mesh-slice replicas
# ---------------------------------------------------------------------------


def test_shard_plan_composes_with_zero():
    from paddle_tpu.parallel.api import param_sharding
    from paddle_tpu.parallel.placement import plan_param_attrs
    from paddle_tpu.parallel.zero import build_zero_plan

    model = _model(num_heads=4, num_kv_heads=2)
    params = model.init_params(jax.random.PRNGKey(0))
    specs = plan_param_attrs(model.shard_plan(axis="model", tp=2))
    mesh = make_mesh((4, 2), ("data", "model"), jax.devices())
    ps = param_sharding(mesh, params, specs=specs)
    zp = build_zero_plan(mesh, params, specs=specs, axis="data")
    for l in range(model.num_layers):
        # TP weights keep their declared megatron layout (explicit
        # sharding wins) and are NOT re-sharded by ZeRO
        assert tuple(ps[f"l{l}.wq"].spec) == (None, "model")
        assert tuple(ps[f"l{l}.wo"].spec) == ("model", None)
        assert not zp.is_sharded(f"l{l}.wq")
        assert not zp.is_sharded(f"l{l}.wo")
    # the replicated remainder still gets its optimizer state sharded
    assert zp.is_sharded("emb") and zp.is_sharded("out")


def test_fleet_mesh_slice_replica_unit(rng):
    from paddle_tpu.serving.faults import FleetFaultPlan, ManualClock
    from paddle_tpu.serving.fleet import FleetRouter

    model = _model(num_heads=4, layers=1)
    params = model.init_params(jax.random.PRNGKey(0))
    plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.01),
                          kill_at={6: 0})

    def mk(i, time_fn, mesh):
        return ServingEngine(model, params, eos_id=EOS, page_size=4,
                             num_pages=32, max_pages_per_seq=8,
                             max_slots=4, buckets=(8, 16),
                             time_fn=time_fn, mesh=mesh)

    fleet = FleetRouter.over_mesh_slices(
        mk, tp=2, devices=jax.devices()[:6], heartbeat_s=0.05,
        resubmit_budget=2, faults=plan)
    assert len(fleet.replicas) == 3           # 6 devices / tp=2
    assert all(r.engine.tp == 2 for r in fleet.replicas)
    system = rng.randint(2, 64, size=8).tolist()
    frids = [fleet.submit(system + rng.randint(2, 64, size=4).tolist(),
                          max_tokens=6) for _ in range(9)]
    fleet.run(max_ticks=500)
    fleet.check_fleet_conservation()          # incl. the killed slice
    assert all(fleet.status(f).terminal for f in frids)
    snap = fleet.snapshot()
    assert snap["fleet_duplicate_completions"] == 0
    assert snap["fleet_completed"] >= 8


def test_mesh_slices_partition_and_cap():
    devs = jax.devices()
    slices = mesh_slices(2, devices=devs[:7])     # leftover chip unused
    assert len(slices) == 3
    assert all(s.axis_names == ("model",) for s in slices)
    used = [d for s in slices for d in s.devices.flat]
    assert len(set(used)) == 6                    # disjoint slices
    assert len(mesh_slices(2, devices=devs, max_slices=2)) == 2


# ---------------------------------------------------------------------------
# the kernel path under TP: shard_map over the model axis
# ---------------------------------------------------------------------------


def test_kernel_shard_map_matches_reference(rng):
    from paddle_tpu.serving.decode_attention import (
        BLOCK_ROWS, ragged_paged_attention_reference,
        ragged_paged_attention_tp)

    # block-uniform packing: one sequence per BLOCK_ROWS block (4 real
    # rows + 4 padding each), the contract the engine's packer owns
    h, kvh, d, pages, page = 4, 2, 8, 6, 8
    t = 2 * BLOCK_ROWS
    q = rng.randn(t, h, d).astype(np.float32)
    kp = rng.randn(pages, page, kvh, d).astype(np.float32)
    vp = rng.randn(pages, page, kvh, d).astype(np.float32)
    table = np.array([[1, 2, 3], [4, 5, 0]], np.int32)
    lens = np.array([20, 12], np.int32)
    row_seq = np.repeat(np.arange(2, dtype=np.int32), BLOCK_ROWS)
    qpos = np.full((t,), -1, np.int32)
    qpos[0:4] = np.arange(16, 20)
    qpos[BLOCK_ROWS:BLOCK_ROWS + 4] = np.arange(8, 12)
    want = ragged_paged_attention_reference(q, kp, vp, table, lens,
                                            row_seq, qpos)
    mesh = _mesh(2)
    pool = stored_pool(kp, vp)
    got = ragged_paged_attention_tp(mesh, "model", q, *pool, table,
                                    lens, row_seq, qpos, layer=0,
                                    use_kernel=True, interpret=True)
    real = qpos >= 0                       # padded rows are undefined
    np.testing.assert_allclose(np.asarray(got)[real],
                               np.asarray(want)[real],
                               rtol=2e-5, atol=2e-5)
    # the auto chooser on CPU routes to the reference fallback — same
    # semantics, no shard_map needed
    auto = ragged_paged_attention_tp(mesh, "model", q, *pool, table,
                                     lens, row_seq, qpos, layer=0)
    np.testing.assert_allclose(np.asarray(auto)[real],
                               np.asarray(want)[real],
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the stored pool under TP (PR 30): a chip's shard is its heads' lanes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype,use_kernel", [
    ("float32", False), ("float32", True), ("int8", False)],
    ids=["float32", "float32_kernel", "int8"])
def test_pool_shard_is_the_lanes_of_the_chips_own_heads(rng, kv_dtype,
                                                       use_kernel):
    """The pool is stored ``[L, pages, page, H_kv * D]`` and sharded on
    the merged dim: device ``i`` of a TP=2 mesh holds lanes
    ``[i * (H_kv/2) * D, (i+1) * (H_kv/2) * D)`` — its own KV heads, whole,
    and no other's (an int8 pool's scales: its heads' columns) — the
    kernel path (a ``shard_map`` handing each chip that shard and the
    layer index) and the reference path serve the replicated engine's
    tokens, and the shards hold what the replicated pool holds."""
    from paddle_tpu.serving.kv_cache import read_pages

    tp, kvh, d = 2, 4, 8
    model = _model(num_heads=4, num_kv_heads=kvh, head_dim=d, layers=3)
    params = model.init_params(jax.random.PRNGKey(0))
    prompts = [rng.randint(2, 64, size=n).tolist() for n in (5, 11, 17)]
    kw = dict(kv_dtype=kv_dtype, use_kernel=use_kernel, prefix_cache=False)
    rep = _engine(model, params, **kw)
    eng = _engine(model, params, mesh=_mesh(tp), **kw)
    want = _run_prompts(rep, prompts)
    if kv_dtype == "float32":
        # (int8 agreement across placements is a chip-level contract)
        assert _run_prompts(eng, prompts) == want
    else:
        _run_prompts(eng, prompts)
    lanes = kvh * d
    assert eng._kv.k.shape == (3, 64, 4, lanes)
    pages = list(range(1, 64))
    whole = read_pages(eng._kv, pages)       # published [L, n, page, H, D]
    for leaf, pub, width in ((eng._kv.k, whole[0], lanes),
                             (eng._kv.v, whole[1], lanes),
                             (eng._kv.k_scale, whole[2], kvh),
                             (eng._kv.v_scale, whole[3], kvh)):
        if leaf is None:
            assert pub is None
            continue
        shards = sorted(leaf.addressable_shards, key=lambda s: s.device.id)
        assert len(shards) == tp
        for i, sh in enumerate(shards):
            assert sh.index[3] == slice(i * width // tp,
                                        (i + 1) * width // tp)
            assert sh.data.shape == (3, 64, 4, width // tp)
            heads = pub[:, :, :, i * kvh // tp:(i + 1) * kvh // tp]
            np.testing.assert_array_equal(
                np.asarray(sh.data)[:, 1:],
                heads.reshape(heads.shape[:3] + (-1,)))
    if kv_dtype == "float32":
        # the same served traffic left the same K/V in both pools
        for a, b in zip(whole[:2], read_pages(rep._kv, pages)[:2]):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert any(np.asarray(whole[0]).any(axis=(0, 2, 3, 4)))
