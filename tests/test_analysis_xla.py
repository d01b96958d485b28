"""Jaxpr-level compiled-path auditor (paddle_tpu.analysis.xla): one
seeded-bad jaxpr per rule class — undonated big buffer, silent f32
upcast, callback-in-tick, const-captured weights, collective-in-decode,
busted budget — plus clean-run pins over the real sealed serving.step
and trainer sites, the retrace capture/donation-strip plumbing, the
obs-registry compile-count publish, and the extended host-sync lint.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.analysis import xla as X
from paddle_tpu.analysis.diagnostics import Severity
from paddle_tpu.analysis.lint import lint_source
from paddle_tpu.analysis.retrace import SiteContract, audit_jit, auditor
from paddle_tpu.platform.flags import FLAGS

pytestmark = [pytest.mark.xla, pytest.mark.analysis]


@pytest.fixture
def audit():
    old = FLAGS.jit_audit
    FLAGS.jit_audit = True
    auditor().reset()
    yield auditor()
    FLAGS.jit_audit = old
    auditor().reset()


def _report(site):
    reps = X.audit_sites(sites=[site])
    assert site in reps, f"site {site} captured nothing"
    return reps[site]


def _errors(rep):
    return [d for d in rep.diagnostics if d.severity is Severity.ERROR]


# ---------------------------------------------------------------------------
# capture plumbing
# ---------------------------------------------------------------------------


def test_site_captures_jaxpr_and_requested_kwargs(audit):
    f = audit_jit(lambda a: a * 2, site="t.cap", donate_argnums=(0,))
    f(jnp.ones((4, 4)))
    rec = audit.sites["t.cap"]
    # the REQUESTED kwargs survive even though CPU cannot donate
    assert rec.jit_kwargs == {"donate_argnums": (0,)}
    assert len(rec.captured) == 1
    cap = next(iter(rec.captured.values()))
    # each capture is self-contained (fn + kwargs + contract): two
    # engines sharing a site name replay through their OWN closures
    assert cap.jit_kwargs == {"donate_argnums": (0,)}
    closed = X.materialize_jaxpr(cap)
    assert [e.primitive.name for e in closed.jaxpr.eqns] == ["mul"]
    # captures hold ShapeDtypeStructs, never device buffers
    assert isinstance(cap.args[0], jax.ShapeDtypeStruct)
    # materialization never pollutes the compile count
    assert audit.compile_count("t.cap") == 1


def test_donation_declared_on_cpu_is_stripped_not_warned(audit):
    """The engine.py:372 gap, closed: sites declare the TPU donation
    contract unconditionally; audit_jit strips it before the CPU
    jax.jit so the run is warning-free, while the auditor checks the
    requested kwargs."""
    f = audit_jit(lambda a: a + 1, site="t.strip", donate_argnums=(0,))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out = f(jnp.ones((8,)))
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert audit.sites["t.strip"].jit_kwargs["donate_argnums"] == (0,)


def test_reset_clears_captures_in_place(audit):
    f = audit_jit(lambda a: a * 2, site="t.reset")
    f(jnp.ones((4,)))
    rec = audit.sites["t.reset"]
    assert rec.captured
    audit.reset()
    assert rec.captured == {}          # same record object, cleared
    # reset() is also the memory reclamation path: the fn references
    # (which can pin a whole engine via the step closure) are dropped
    assert rec.fn is None and rec.jit_kwargs == {}
    f(jnp.ones((4,)))                  # live wrapper keeps recording
    assert len(rec.captured) == 1
    # ...and its capture is self-contained, so the audit still works
    assert X.audit_sites(sites=["t.reset"])["t.reset"].signatures == 1


# ---------------------------------------------------------------------------
# seeded-bad jaxprs, one per rule class
# ---------------------------------------------------------------------------


def test_donation_contract_violation_flagged(audit):
    f = audit_jit(lambda kv, x: (kv + x, x), site="t.donbad",
                  xla_contract=SiteContract(donate=(0,)))
    f(jnp.ones((32, 32)), jnp.ones((32, 32)))
    errs = _errors(_report("t.donbad"))
    assert len(errs) == 1
    msg = errs[0].message
    assert "donation-contract" in msg and "t.donbad" in msg
    assert "arg 0" in msg


def test_donation_contract_satisfied_is_clean(audit):
    f = audit_jit(lambda kv, x: (kv + x, x), site="t.donok",
                  donate_argnums=(0,),
                  xla_contract=SiteContract(donate=(0,)))
    f(jnp.ones((32, 32)), jnp.ones((32, 32)))
    assert _errors(_report("t.donok")) == []


def test_undonated_big_buffer_reported_as_candidate(audit):
    big = jnp.ones((512, 512))                     # 1 MiB
    f = audit_jit(lambda a: a + 1.0, site="t.candidate")
    f(big)
    rep = _report("t.candidate")
    assert _errors(rep) == []                      # candidate = WARNING
    warns = [d for d in rep.diagnostics
             if d.severity is Severity.WARNING]
    assert len(warns) == 1 and "not donated" in warns[0].message


def test_silent_f32_upcast_flagged_and_allowlistable(audit):
    def fn(x, w):
        return x.astype(jnp.float32) @ w

    f = audit_jit(fn, site="t.upcast")
    f(jnp.ones((8, 8), jnp.bfloat16), jnp.ones((8, 8)))
    errs = _errors(_report("t.upcast"))
    assert len(errs) == 1
    assert "dtype-promotion-drift" in errs[0].message
    assert "dot_general" in errs[0].message        # names the eqn
    assert "t.upcast" in errs[0].message           # names the site

    g = audit_jit(fn, site="t.upcast_ok",
                  xla_contract=SiteContract(allow_upcast=("bfloat16",)))
    g(jnp.ones((8, 8), jnp.bfloat16), jnp.ones((8, 8)))
    assert _errors(_report("t.upcast_ok")) == []


def test_int8_dequant_chain_tracked_through_elementwise(audit):
    """The real drift shape: int8 pages -> convert -> scale-mul ->
    matmul.  The origin must survive the elementwise mul."""
    def fn(pages, scale, q):
        deq = pages.astype(jnp.float32) * scale
        return q @ deq

    f = audit_jit(fn, site="t.dequant")
    f(jnp.ones((8, 8), jnp.int8), jnp.ones((8, 8)), jnp.ones((4, 8)))
    errs = _errors(_report("t.dequant"))
    assert len(errs) == 1 and "int8" in errs[0].message


def test_drift_origin_survives_literal_operands_into_branches(audit):
    """cond-style eqns mix Literal and array operands; the origin map
    must align POSITIONALLY onto the branch jaxpr's invars (filtering
    literals first shifted every origin onto the wrong inner operand)."""
    def fn(pred, x, w):
        return jax.lax.cond(
            pred,
            lambda a, b, c: a.astype(jnp.float32) @ b + c,
            lambda a, b, c: jnp.zeros((8, 8)) + c,
            x, w, 1.0)

    f = audit_jit(fn, site="t.branchdrift")
    f(jnp.asarray(True), jnp.ones((8, 8), jnp.bfloat16),
      jnp.ones((8, 8)))
    errs = _errors(_report("t.branchdrift"))
    assert len(errs) == 1 and "bfloat16" in errs[0].message


def test_callback_in_per_tick_site_is_error(audit):
    def fn(x):
        return x + jax.pure_callback(
            lambda v: np.asarray(v),
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    f = audit_jit(fn, site="t.cb",
                  xla_contract=SiteContract(per_tick=True))
    f(jnp.ones((4,)))
    errs = _errors(_report("t.cb"))
    assert len(errs) == 1
    assert "host-transfer" in errs[0].message
    assert "pure_callback" in errs[0].message and "eqn" in errs[0].message

    # outside a per-tick site the same eqn is informational
    g = audit_jit(fn, site="t.cb_info")
    g(jnp.ones((4,)))
    rep = _report("t.cb_info")
    assert _errors(rep) == []
    assert any(d.severity is Severity.INFO and "host-transfer"
               in d.message for d in rep.diagnostics)


def test_const_captured_weights_flagged(audit):
    weights = jnp.ones((256, 256))                 # 256 KiB const
    f = audit_jit(lambda x: x @ weights, site="t.const")
    f(jnp.ones((4, 256)))
    errs = _errors(_report("t.const"))
    assert len(errs) == 1
    msg = errs[0].message
    assert "const-capture" in msg and "(256, 256)" in msg

    # passed as an argument, the same math is clean
    g = audit_jit(lambda x, w: x @ w, site="t.const_ok")
    g(jnp.ones((4, 256)), weights)
    assert _errors(_report("t.const_ok")) == []


def test_collective_in_decode_site_is_error(audit):
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("i",))

    def fn(x):
        return shard_map(lambda v: jax.lax.psum(v, "i"), mesh=mesh,
                         in_specs=P("i"), out_specs=P())(x)

    f = audit_jit(fn, site="t.coll",
                  xla_contract=SiteContract(per_tick=True))
    f(jnp.ones((4,)))
    errs = _errors(_report("t.coll"))
    assert len(errs) == 1
    assert "collective-placement" in errs[0].message
    assert "psum" in errs[0].message

    # where collectives are the point (ZeRO), the same eqn is INFO
    g = audit_jit(fn, site="t.coll_ok",
                  xla_contract=SiteContract(allow_collectives=True))
    g(jnp.ones((4,)))
    rep = _report("t.coll_ok")
    assert _errors(rep) == []
    assert any("collective-placement" in d.message
               for d in rep.diagnostics)


def test_busted_budget_flagged(audit):
    f = audit_jit(lambda x: x @ x, site="t.budget",
                  xla_contract=SiteContract(peak_bytes=64, flops=10.0))
    f(jnp.ones((8, 8)))
    errs = _errors(_report("t.budget"))
    assert len(errs) == 2                      # bytes AND flops busted
    assert all("budget" in d.message for d in errs)

    g = audit_jit(lambda x: x @ x, site="t.budget_ok",
                  xla_contract=SiteContract(peak_bytes=1 << 20,
                                            flops=1e9))
    g(jnp.ones((8, 8)))
    assert _errors(_report("t.budget_ok")) == []


def test_estimator_pins_exact_numbers(audit):
    f = audit_jit(lambda a, b: a @ b, site="t.est")
    f(jnp.ones((8, 8)), jnp.ones((8, 8)))
    rec = audit.sites["t.est"]
    closed = X.materialize_jaxpr(next(iter(rec.captured.values())))
    peak, flops = X.estimate_jaxpr(closed)
    assert flops == 2 * 8 * 8 * 8              # 2*M*N*K
    assert peak == 3 * 8 * 8 * 4               # two operands + result


def test_diagnostics_carry_the_grepable_tag(audit):
    f = audit_jit(lambda kv: kv + 1, site="t.tag",
                  xla_contract=SiteContract(donate=(0,)))
    f(jnp.ones((4,)))
    errs = _errors(_report("t.tag"))
    assert errs and all("XLA-AUDIT" in str(d) for d in errs)


# ---------------------------------------------------------------------------
# clean-run pins over the REAL sites
# ---------------------------------------------------------------------------


@pytest.mark.serving
def test_sealed_serving_steady_state_audits_clean(audit):
    """The acceptance pin: a sealed mixed steady-state run (int8 KV,
    prefix cache on) audits with zero ERROR diagnostics at every
    serving site, the donation contract is REQUESTED on CPU, and the
    sealed replay produced no RETRACE diagnostics."""
    old_bf16 = FLAGS.use_bf16
    FLAGS.use_bf16 = False
    try:
        X.drive_serving_steady_state(kv_dtype="int8", seal=True)
    finally:
        FLAGS.use_bf16 = old_bf16
    reps = X.audit_sites()
    # ALL contract-bearing serving sites captured — incl. zero_pages,
    # whose scrub only runs on the poisoned-request fault path
    assert {"serving.step", "serving.fork_page",
            "serving.zero_pages"} <= set(reps)
    for name, rep in reps.items():
        assert _errors(rep) == [], \
            f"{name}: {[str(d) for d in _errors(rep)]}"
    # the step compiled one pair per prefill bucket seen (0, 4|8, 16)
    step = reps["serving.step"]
    assert step.signatures >= 2
    assert step.peak_bytes > 0 and step.flops > 0
    # donation is requested even though this run is on CPU
    assert 1 in audit.sites["serving.step"].jit_kwargs["donate_argnums"]
    assert audit.diagnostics == []             # sealed replay: 0 RETRACE


@pytest.mark.serving
def test_float32_pool_audits_clean_without_allowlist(audit):
    """An f32 pool needs no allow_upcast: the contract must not carry a
    stale int8 entry (the allowlist is derived from the actual pool
    dtype) and the audit stays clean."""
    eng = X.drive_serving_steady_state(kv_dtype="float32", seal=False)
    assert eng._step_contract.allow_upcast == ()
    reps = X.audit_sites(sites=["serving.step"])
    assert _errors(reps["serving.step"]) == []


def test_trainer_step_audits_clean(audit):
    """One real train pass: trainer.train_step audits clean, with the
    (0, 1, 2) donation contract requested and verified."""
    X.drive_trainer_step()
    rep = _report("trainer.train_step")
    assert _errors(rep) == [], [str(d) for d in _errors(rep)]
    rec = auditor().sites["trainer.train_step"]
    assert rec.jit_kwargs["donate_argnums"] == (0, 1, 2)
    assert rec.contract is not None and rec.contract.donate == (0, 1, 2)


def test_trainer_step_with_dropped_donation_is_caught(audit):
    """The failure the rule exists for: donation silently dropped from
    the jit kwargs while the contract still declares it."""
    X.drive_trainer_step(batches=1, batch_size=8)
    rec = auditor().sites["trainer.train_step"]
    for cap in rec.captured.values():          # simulate the drop
        cap.jit_kwargs = {}
    rep = X.audit_record("trainer.train_step", rec)
    errs = _errors(rep)
    assert len(errs) == 3                      # args 0, 1, 2
    assert all("donation-contract" in d.message for d in errs)


# ---------------------------------------------------------------------------
# obs satellite: compile counts on the scrape surface
# ---------------------------------------------------------------------------


@pytest.mark.serving
@pytest.mark.obs
def test_compile_counts_published_to_registry(audit):
    from paddle_tpu.serving import DecoderLM, ServingEngine

    model = DecoderLM(vocab_size=32, num_layers=1, num_heads=2,
                      head_dim=8, max_positions=64)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, eos_id=1, page_size=4,
                        num_pages=16, max_pages_per_seq=4, max_slots=2,
                        buckets=(4, 8), prefill_chunk=0)
    eng.submit([3, 4, 5], max_tokens=4)
    eng.run(max_ticks=50)
    snap = eng.healthz()["metrics"]
    key = "jit_compiles_total{site=serving.step}"
    assert key in snap and snap[key] >= 1
    assert snap["jit_calls_total{site=serving.step}"] >= snap[key]
    # Prometheus exposition carries the same series
    assert 'jit_compiles_total{site="serving.step"}' \
        in eng.registry.to_text()


# ---------------------------------------------------------------------------
# lint satellite: block_until_ready is a host sync
# ---------------------------------------------------------------------------


def test_lint_flags_block_until_ready_method_and_function():
    src = "def f(x):\n    x.block_until_ready()\n"
    for d in ("serving", "obs", "platform"):
        out = lint_source(src, f"paddle_tpu/{d}/bad.py",
                          rules=["host-sync"])
        assert len(out) == 1 and out[0].code == "host-sync", d
    fn_form = "import jax\n\ndef f(x):\n    jax.block_until_ready(x)\n"
    out = lint_source(fn_form, "paddle_tpu/platform/bad.py",
                      rules=["host-sync"])
    assert len(out) == 1
    # outside the covered layers the rule does not apply
    assert lint_source(src, "paddle_tpu/models/x.py",
                       rules=["host-sync"]) == []
    # ...and the escape hatch works (stats.py's timing sync)
    allowed = ("def f(x):\n"
               "    x.block_until_ready()  # lint: allow(host-sync)\n")
    assert lint_source(allowed, "paddle_tpu/platform/stats2.py",
                       rules=["host-sync"]) == []


def test_stats_timer_block_records_honest_window():
    from paddle_tpu.platform.stats import StatSet

    ss = StatSet()
    out = {}
    with ss.timer("step", block=lambda: out["y"]):
        out["y"] = jnp.ones((64, 64)) @ jnp.ones((64, 64))
    e = ss.get("step")
    assert e is not None and e.count == 1 and e.total > 0.0
    # direct-value form works too
    arr = jnp.ones((8,))
    with ss.timer("step", block=arr):
        arr = arr + 1
    assert ss.get("step").count == 2


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------


def test_cli_rejects_unknown_rule():
    from paddle_tpu.analysis.cli import main

    assert main(["xla", "--rule", "nope"]) == 2


def test_audit_sites_skips_uncaptured(audit):
    # a site wrapped but never called has nothing to audit
    audit_jit(lambda x: x, site="t.never")
    assert "t.never" not in X.audit_sites()


def test_two_wrappers_one_site_audit_through_own_closures(audit):
    """Two engines sharing a site name wrap DIFFERENT closures; each
    captured signature must replay through the closure that traced it
    (a site-level fn would shape-crash or silently cross-audit)."""
    n1, n2 = 4, 7

    f1 = audit_jit(lambda x: x[:n1] * 2, site="t.shared",
                   xla_contract=SiteContract(flops=1e6))
    f2 = audit_jit(lambda x: x[:n2] * 2, site="t.shared",
                   xla_contract=SiteContract(flops=0.5))
    f1(jnp.ones((n1,)))
    f2(jnp.ones((n2,)))
    rep = _report("t.shared")
    assert rep.signatures == 2              # both materialized fine
    errs = _errors(rep)
    # only the second wrap's busted budget fires — contracts are
    # per-capture, not last-wrap-wins
    assert len(errs) == 1 and "budget" in errs[0].message


@pytest.mark.serving
@pytest.mark.obs
def test_compile_counts_published_unlabeled(audit):
    """The auditor is process-global, so its gauges publish WITHOUT
    per-engine labels — a replica must not appear to own the whole
    fleet's compiles."""
    from paddle_tpu.serving import DecoderLM, ServingEngine

    model = DecoderLM(vocab_size=32, num_layers=1, num_heads=2,
                      head_dim=8, max_positions=64)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, eos_id=1, page_size=4,
                        num_pages=16, max_pages_per_seq=4, max_slots=2,
                        buckets=(4, 8), prefill_chunk=0)
    eng.set_registry(eng.registry, replica="3")
    eng.submit([3, 4, 5], max_tokens=4)
    eng.run(max_ticks=50)
    snap = eng.healthz()["metrics"]
    assert "jit_compiles_total{site=serving.step}" in snap
    # match the label SYNTAX, not the bare substring: the site name
    # "zero.replicate" (whose record legitimately persists across an
    # in-place auditor reset) must not trip the replica-label check
    assert not any("jit_compiles_total" in k and "replica=" in k
                   for k in snap)


def test_stats_timer_block_never_masks_the_real_error():
    """timer(block=) must not evaluate block() when the timed body
    raised — the result usually doesn't exist, and a KeyError from the
    finally clause would mask the real failure."""
    from paddle_tpu.platform.stats import StatSet

    ss = StatSet()
    out = {}
    with pytest.raises(RuntimeError, match="the real error"):
        with ss.timer("step", block=lambda: out["y"]):
            raise RuntimeError("the real error")
    assert ss.get("step").count == 1        # window still recorded
