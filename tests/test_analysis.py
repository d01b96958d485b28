"""paddle_tpu.analysis tests: the program verifier catches every
seeded diagnostic class on hand-built bad programs and stays silent on
real training programs and every ``paddle_tpu.models`` network; the
retrace auditor counts exactly one compile for a steady-state serving
decode loop (one per bucket for prefill) and flags an injected
shape-churn loop; the linter rules fire on synthetic snippets, honor
the ``# lint: allow(<rule>)`` escape hatch, and find nothing in the
repo itself.
"""

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.analysis.diagnostics import Severity
from paddle_tpu.analysis.lint import lint_source, run_lint
from paddle_tpu.analysis.program_check import (verify_program,
                                               verify_topology)
from paddle_tpu.analysis.retrace import (RetraceError, audit_jit, auditor)
from paddle_tpu.fluid import layers, optimizer
from paddle_tpu.platform.flags import FLAGS

pytestmark = pytest.mark.analysis


def codes(diags, severity=None):
    return sorted({d.code for d in diags
                   if severity is None or d.severity is severity})


def errors(diags):
    return [d for d in diags if d.severity is Severity.ERROR]


# ---------------------------------------------------------------------------
# program verifier: clean real programs
# ---------------------------------------------------------------------------


def _fit_a_line():
    prog = fluid.Program()
    with fluid.program_guard(prog):
        x = layers.data("x", [13])
        y = layers.data("y", [1])
        pred = layers.fc(x, size=1, bias_attr=True)
        loss = layers.mean(layers.square_error_cost(pred, y))
        optimizer.SGDOptimizer(learning_rate=0.05).minimize(loss)
    return prog, [loss.name], ["x", "y"]


def _mlp_with_metrics():
    prog = fluid.Program()
    with fluid.program_guard(prog):
        img = layers.data("img", [64])
        label = layers.data("label", [1], dtype="int64")
        h = layers.fc(img, size=32, act="relu")
        logits = layers.fc(h, size=4)
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, label))
        acc = layers.accuracy(logits, label)
        optimizer.AdamOptimizer(learning_rate=1e-3).minimize(loss)
    return prog, [loss.name, acc.name], ["img", "label"]


def _convnet():
    prog = fluid.Program()
    with fluid.program_guard(prog):
        img = layers.data("img", [1, 12, 12])
        label = layers.data("label", [1], dtype="int64")
        c = layers.conv2d(img, num_filters=4, filter_size=3, act="relu")
        c = layers.batch_norm(c)
        p = layers.pool2d(c, pool_size=2, pool_type="max")
        logits = layers.fc(p, size=4)
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, label))
        optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return prog, [loss.name], ["img", "label"]


def _static_rnn():
    prog = fluid.Program()
    with fluid.program_guard(prog):
        x = layers.data("x", [6, 4, 8], append_batch_size=False)
        rnn = layers.StaticRNN()
        with rnn.step():
            x_t = rnn.step_input(x)
            h_prev = rnn.memory(shape=(4, 16), init_value=0.0)
            h = layers.fc([x_t, h_prev], size=16, act="tanh",
                          bias_attr=False)
            rnn.update_memory(h_prev, h)
            rnn.step_output(h)
        out = rnn()
        loss = layers.mean(out)
        optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return prog, [loss.name], ["x"]


@pytest.mark.parametrize("build", [_fit_a_line, _mlp_with_metrics,
                                   _convnet, _static_rnn])
def test_verifier_silent_on_real_training_programs(build):
    prog, fetches, feeds = build()
    diags = verify_program(prog, fetch_names=fetches, feed_names=feeds)
    assert diags == [], [str(d) for d in diags]


def test_verified_program_still_trains():
    """strict mode on a GOOD program changes nothing — it compiles and
    converges exactly as before."""
    prog, fetches, feeds = _fit_a_line()
    old = FLAGS.fluid_verify
    FLAGS.fluid_verify = "strict"
    try:
        rng = np.random.RandomState(0)
        true_w = rng.randn(13, 1).astype(np.float32)
        exe = fluid.Executor()
        scope = fluid.Scope()
        losses = []
        for _ in range(40):
            xb = rng.randn(16, 13).astype(np.float32)
            (l,) = exe.run(prog, feed={"x": xb, "y": xb @ true_w},
                           fetch_list=fetches, scope=scope)
            losses.append(float(l))
        assert losses[-1] < 0.1 * losses[0]
    finally:
        FLAGS.fluid_verify = old


# ---------------------------------------------------------------------------
# program verifier: each seeded-bad-program class
# ---------------------------------------------------------------------------


def test_def_before_use():
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var("a", shape=(4,))
    b.create_var("b", shape=(4,))
    b.create_var("c", shape=(4,))
    # reads `b` before the op that defines it
    b.append_op("relu", inputs={"X": "b"}, outputs={"Out": "c"})
    b.append_op("tanh", inputs={"X": "a"}, outputs={"Out": "b"})
    diags = verify_program(prog, feed_names=["a"])
    assert "def-before-use" in codes(errors(diags))


def test_undefined_var():
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var("out", shape=(4,))
    b.append_op("relu", inputs={"X": "never_declared"},
                outputs={"Out": "out"})
    diags = verify_program(prog)
    assert "undefined-var" in codes(errors(diags))


def test_dangling_fetch_and_unknown_feed():
    prog, _, _ = _fit_a_line()
    diags = verify_program(prog, fetch_names=["no_such_var"],
                           feed_names=["x", "y", "typo"])
    cs = codes(errors(diags))
    assert "dangling-fetch" in cs and "unknown-feed" in cs


def test_dead_var_warning():
    prog = fluid.Program()
    with fluid.program_guard(prog):
        x = layers.data("x", [8])
        used = layers.relu(x)
        dead = layers.tanh(x)          # never fetched, never read
        out = layers.mean(used)
    diags = verify_program(prog, fetch_names=[out.name], feed_names=["x"])
    dead_diags = [d for d in diags if d.code == "dead-var"]
    assert dead_diags and dead_diags[0].severity is Severity.WARNING
    assert any(dead.name in d.vars for d in dead_diags)
    assert not errors(diags)


def test_duplicate_writer():
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var("x", shape=(4,))
    b.create_var("o", shape=(4,))
    b.append_op("relu", inputs={"X": "x"}, outputs={"Out": "o"})
    b.append_op("tanh", inputs={"X": "x"}, outputs={"Out": "o"})
    diags = verify_program(prog, feed_names=["x"])
    assert "duplicate-writer" in codes(errors(diags))


def test_gradient_fan_in_is_not_duplicate_writer():
    """@GRAD accumulation and stateful batch_norm outputs are the
    sanctioned multi-writer aliases — a program with parameter fan-out
    (two consumers of one fc output) must verify clean."""
    prog = fluid.Program()
    with fluid.program_guard(prog):
        x = layers.data("x", [8])
        h = layers.fc(x, size=8, act="relu")
        a = layers.fc(h, size=4)
        bvar = layers.fc(h, size=4)          # h fans out -> h@GRAD summed
        loss = layers.mean(a + bvar)
        optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    diags = verify_program(prog, fetch_names=[loss.name], feed_names=["x"])
    assert diags == [], [str(d) for d in diags]


def test_shape_mismatch_matmul_and_elementwise():
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var("a", shape=(-1, 4))
    b.create_parameter("w", shape=(7, 3))
    b.create_var("o", shape=(-1, 3))
    b.append_op("mul", inputs={"X": "a", "Y": "w"}, outputs={"Out": "o"})
    diags = verify_program(prog, feed_names=["a"])
    assert "shape-mismatch" in codes(errors(diags))

    prog2 = fluid.Program()
    b2 = prog2.global_block()
    b2.create_var("p", shape=(8, 4))
    b2.create_var("q", shape=(8, 5))
    b2.create_var("r", shape=(8, 4))
    b2.append_op("elementwise_add", inputs={"X": "p", "Y": "q"},
                 outputs={"Out": "r"})
    diags2 = verify_program(prog2, feed_names=["p", "q"])
    assert "shape-mismatch" in codes(errors(diags2))


def test_shape_mismatch_conv_channels_and_reshape():
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var("img", shape=(-1, 3, 8, 8))
    b.create_parameter("w", shape=(4, 5, 3, 3))     # expects 5 channels
    b.create_var("o", shape=())
    b.append_op("conv2d", inputs={"Input": "img", "Filter": "w"},
                outputs={"Output": "o"}, attrs={"strides": 1,
                                                "paddings": 0})
    diags = verify_program(prog, feed_names=["img"])
    assert "shape-mismatch" in codes(errors(diags))

    prog2 = fluid.Program()
    b2 = prog2.global_block()
    b2.create_var("x", shape=(6, 4))
    b2.create_var("y", shape=())
    b2.append_op("reshape", inputs={"X": "x"}, outputs={"Out": "y"},
                 attrs={"shape": [5, 5]})           # 24 -> 25 elements
    diags2 = verify_program(prog2, feed_names=["x"])
    assert "shape-mismatch" in codes(errors(diags2))


def test_unknown_batch_broadcast_stays_unknown():
    """Broadcasting an unknown (batch) dim against a literal 1 must NOT
    infer 1: [None,8] + [1,8] -> [None,8], so a later reshape that is
    valid at runtime (batch=4 here) raises no false conflict."""
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var("x", shape=(-1, 8))
    b.create_var("one", shape=(1, 8))
    b.create_var("s", shape=(-1, 8))
    b.create_var("r", shape=(4, 8))
    b.append_op("elementwise_add", inputs={"X": "x", "Y": "one"},
                outputs={"Out": "s"})
    b.append_op("reshape", inputs={"X": "s"}, outputs={"Out": "r"},
                attrs={"shape": [4, 8]})
    diags = verify_program(prog, feed_names=["x", "one"])
    assert errors(diags) == [], [str(d) for d in errors(diags)]


def test_dtype_mismatch():
    # float + int arithmetic without a cast
    prog = fluid.Program()
    b = prog.global_block()
    b.create_var("f", shape=(4,), dtype="float32")
    b.create_var("i", shape=(4,), dtype="int64")
    b.create_var("o", shape=(4,))
    b.append_op("elementwise_add", inputs={"X": "f", "Y": "i"},
                outputs={"Out": "o"})
    diags = verify_program(prog, feed_names=["f", "i"])
    assert "dtype-mismatch" in codes(errors(diags))

    # hard labels must be integers
    prog2 = fluid.Program()
    with fluid.program_guard(prog2):
        logits = layers.data("logits", [4])
        label = layers.data("label", [1], dtype="float32")
        layers.softmax_with_cross_entropy(logits, label)
    diags2 = verify_program(prog2, feed_names=["logits", "label"])
    assert "dtype-mismatch" in codes(errors(diags2))


def test_executor_strict_mode_raises_on_bad_program():
    from paddle_tpu.platform.enforce import EnforceError

    prog = fluid.Program()
    b = prog.global_block()
    b.create_var("a", shape=(-1, 4))
    b.create_parameter("w", shape=(7, 3))
    b.create_var("o", shape=(-1, 3))
    b.append_op("mul", inputs={"X": "a", "Y": "w"}, outputs={"Out": "o"})
    old = FLAGS.fluid_verify
    FLAGS.fluid_verify = "strict"
    try:
        exe = fluid.Executor()
        with pytest.raises(EnforceError, match="shape-mismatch"):
            exe.run(prog, feed={"a": np.zeros((2, 4), np.float32)},
                    fetch_list=["o"], scope=fluid.Scope())
    finally:
        FLAGS.fluid_verify = old


def test_executor_validates_feed_fetch_up_front():
    from paddle_tpu.platform.enforce import EnforceError

    prog, fetches, _ = _fit_a_line()
    exe = fluid.Executor()
    scope = fluid.Scope()
    feed = {"x": np.zeros((4, 13), np.float32),
            "y": np.zeros((4, 1), np.float32)}
    with pytest.raises(EnforceError, match="fetch 'nope'"):
        exe.run(prog, feed=feed, fetch_list=["nope"], scope=scope)
    with pytest.raises(EnforceError, match="feed 'typo'"):
        exe.run(prog, feed={**feed, "typo": np.zeros((4, 1), np.float32)},
                fetch_list=fetches, scope=scope)
    # both problems reported in ONE error, not the first encountered
    with pytest.raises(EnforceError,
                       match=r"(?s)(feed 'typo'.*fetch 'nope'"
                             r"|fetch 'nope'.*feed 'typo')"):
        exe.run(prog, feed={**feed, "typo": np.zeros((4, 1), np.float32)},
                fetch_list=["nope"], scope=scope)


def test_program_cli(tmp_path):
    from paddle_tpu.analysis.cli import main

    good = tmp_path / "good.py"
    good.write_text(
        "from paddle_tpu.fluid import layers\n"
        "x = layers.data('x', [4])\n"
        "loss = layers.mean(layers.relu(x))\n"
        "FETCH = loss.name\n")
    assert main(["program", str(good)]) == 0
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import paddle_tpu.fluid as fluid\n"
        "prog = fluid.Program()\n"
        "b = prog.global_block()\n"
        "b.create_var('a', shape=(-1, 4))\n"
        "b.create_parameter('w', shape=(7, 3))\n"
        "b.create_var('o', shape=(-1, 3))\n"
        "b.append_op('mul', inputs={'X': 'a', 'Y': 'w'},"
        " outputs={'Out': 'o'})\n")
    assert main(["program", str(bad)]) == 1
    assert main(["program", str(bad), "--fetch", "o", "--feed", "a"]) == 1
    # --fetch binds to the default program only: a module-level pruned
    # Program that does not produce the fetch target must not fail
    multi = tmp_path / "multi.py"
    multi.write_text(
        "import paddle_tpu.fluid as fluid\n"
        "from paddle_tpu.fluid import layers\n"
        "from paddle_tpu.fluid.framework import default_main_program\n"
        "b = default_main_program().global_block()\n"
        "b.create_var('x', shape=(-1, 4))\n"
        "b.create_var('y', shape=(-1, 4))\n"
        "b.append_op('relu', inputs={'X': 'x'}, outputs={'Out': 'y'})\n"
        "other = fluid.Program()\n"
        "with fluid.program_guard(other):\n"
        "    z = layers.data('z', [4])\n"
        "    layers.tanh(z)\n")
    # 'y' exists only in the DEFAULT program; binding --fetch to every
    # program would fabricate a dangling-fetch on `other` and exit 1
    assert main(["program", str(multi), "--fetch", "y", "--feed", "x"]) == 0


def test_inline_verify_skips_per_run_dead_var(caplog):
    """A per-run fetch list is not the program's sink set: running with
    a partial fetch under the default warn mode must not log dead-var
    for ops another run fetches."""
    import logging

    prog, _, _ = _mlp_with_metrics()
    loss_name = None
    for op in prog.global_block().ops:
        if op.type == "mean":
            loss_name = op.output("Out")[0]
    exe = fluid.Executor()
    feed = {"img": np.zeros((4, 64), np.float32),
            "label": np.zeros((4, 1), np.int64)}
    with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
        exe.run(prog, feed=feed, fetch_list=[loss_name],  # not accuracy
                scope=fluid.Scope())
    assert "dead-var" not in caplog.text


# ---------------------------------------------------------------------------
# program verifier: the models zoo
# ---------------------------------------------------------------------------


def _model_builders():
    import paddle_tpu.models as zoo

    out = []
    for name in ("lenet", "smallnet", "alexnet", "googlenet", "resnet",
                 "text_lstm", "deepfm", "gan", "vae", "sequence_tagging",
                 "srl", "quick_start", "traffic_prediction", "transformer",
                 "seq2seq"):
        mod = getattr(zoo, name)
        for fn_name in ("build", "build_train", "build_seq2seq"):
            fn = getattr(mod, fn_name, None)
            if fn is not None:
                out.append(pytest.param(fn, id=f"{name}.{fn_name}"))
    return out


@pytest.mark.parametrize("build", _model_builders())
def test_models_verify_with_zero_errors(build):
    from paddle_tpu.topology import LayerOutput

    result = build()
    nodes = [r for r in (result if isinstance(result, tuple) else (result,))
             if isinstance(r, LayerOutput)]
    assert nodes, "build returned no LayerOutputs"
    diags = verify_topology(nodes)
    assert errors(diags) == [], [str(d) for d in errors(diags)]


def test_topology_verifier_catches_duplicate_names_and_bad_params():
    from paddle_tpu.attr import ParamAttr
    from paddle_tpu.topology import LayerOutput, ParamSpec

    a = LayerOutput("dup", "fc", [], fn=lambda ctx, p, ins: ins[0])
    bad = LayerOutput("dup", "fc", [a], fn=lambda ctx, p, ins: ins[0])
    diags = verify_topology(bad)
    assert errors(diags)

    p = LayerOutput("p", "fc", [], fn=lambda ctx, p, ins: 0,
                    params={"w": ParamSpec(shape=(-1, 4),
                                           attr=ParamAttr())})
    diags2 = verify_topology(p)
    assert "shape-mismatch" in codes(errors(diags2))


# ---------------------------------------------------------------------------
# retrace auditor
# ---------------------------------------------------------------------------


@pytest.fixture
def audit():
    old = FLAGS.jit_audit
    FLAGS.jit_audit = True
    auditor().reset()
    yield auditor()
    FLAGS.jit_audit = old
    auditor().reset()


def test_audit_counts_compiles_exactly(audit):
    import jax.numpy as jnp

    f = audit_jit(lambda x: x * 2, site="t.basic")
    for _ in range(5):
        f(jnp.ones((4,)))
    assert audit.compile_count("t.basic") == 1
    assert audit.call_count("t.basic") == 5
    f(jnp.ones((8,)))                       # new shape: a real compile
    assert audit.compile_count("t.basic") == 2
    assert audit.diagnostics == []          # warmup: nothing flagged
    audit.assert_budget("t.basic", 2)
    with pytest.raises(RetraceError, match="RETRACE"):
        audit.assert_budget("t.basic", 1)


def test_audit_flags_shape_churn_after_seal(audit):
    import jax.numpy as jnp

    f = audit_jit(lambda x: x + 1, site="t.churn")
    f(jnp.ones((4,)))
    audit.seal("t.churn")
    for n in (5, 6, 7):                     # injected shape churn
        f(jnp.ones((n,)))
    retraces = [d for d in audit.diagnostics if d.code == "RETRACE"]
    assert len(retraces) == 3
    assert all(d.severity is Severity.ERROR for d in retraces)
    with pytest.raises(RetraceError, match="RETRACE"):
        audit.assert_no_retraces()


def test_zero_identity_jit_is_cached_per_sharding(audit):
    """The ZeRO placement identities must not re-wrap (and so re-trace)
    per call — one compile per (sharding, site)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.zero import _identity_jit

    _identity_jit.cache_clear()
    mesh = make_mesh((8,), ("data",))
    sh = NamedSharding(mesh, P())
    try:
        for _ in range(4):
            _identity_jit(sh, "zero.reshard")(jnp.ones((8, 4)))
        assert audit.compile_count("zero.reshard") == 1
        assert not any(d.code == "RETRACE" for d in audit.diagnostics)
    finally:
        _identity_jit.cache_clear()


def test_audit_flags_fresh_wrapper_for_same_signature(audit):
    import jax.numpy as jnp

    # the classic hidden retrace: re-wrapping the "same" computation in
    # a new jit callable recompiles for an identical signature
    audit_jit(lambda x: x - 1, site="t.rewrap")(jnp.ones((4,)))
    audit_jit(lambda x: x - 1, site="t.rewrap")(jnp.ones((4,)))
    assert audit.compile_count("t.rewrap") == 2
    assert any(d.code == "RETRACE" for d in audit.diagnostics)


def test_seal_covers_sites_created_after_seal(audit):
    """Lazily-built jits (per-bucket prefill/chunk wrappers) may first
    wrap AFTER warmup is declared over — a global seal() must cover
    them, or post-seal compiles at a fresh bucket escape detection."""
    import jax.numpy as jnp

    audit_jit(lambda x: x, site="t.warm")(jnp.ones((4,)))
    audit.seal()                             # global: warmup over
    late = audit_jit(lambda x: x * 2, site="t.late")   # born sealed
    late(jnp.ones((4,)))
    assert any(d.code == "RETRACE" and "t.late" in d.vars
               for d in audit.diagnostics)
    with pytest.raises(RetraceError, match="RETRACE"):
        audit.assert_no_retraces()


def test_reset_keeps_live_wrappers_counted(audit):
    """reset() must zero counters IN PLACE: wrappers built before the
    reset keep reporting, instead of incrementing orphaned records
    while every later assert reads 0."""
    import jax.numpy as jnp

    f = audit_jit(lambda x: x + 1, site="t.live")
    f(jnp.ones((4,)))
    audit.reset()                            # discard warmup counts
    f(jnp.ones((8,)))                        # steady state: a compile!
    assert audit.compile_count("t.live") == 1
    assert audit.call_count("t.live") == 1
    with pytest.raises(RetraceError):
        audit.assert_budget("t.live", 0)


def test_audit_off_is_plain_jit():
    assert not FLAGS.jit_audit
    before = dict(auditor().snapshot())
    f = audit_jit(lambda x: x * 3, site="t.off")
    f(np.ones((4,), np.float32))
    assert "t.off" not in auditor().snapshot()
    assert auditor().snapshot() == before


@pytest.mark.serving
def test_serving_step_compiles_once_per_bucket_pair_steady_state(audit, rng):
    """The unified step (round 12) compiles exactly once per
    (decode_bucket, prefill_bucket) pair — the decode bucket is the
    fixed max_slots row count, so the ladder is one jit per prefill
    bucket plus the decode-only pb=0 — and a SEALED mixed
    prefill+decode steady state never compiles again.  The v1
    serving.decode / serving.prefill / serving.chunk_prefill sites are
    retired; serving.step is their one successor."""
    from paddle_tpu.serving import DecoderLM, ServingEngine

    old_bf16 = FLAGS.use_bf16
    FLAGS.use_bf16 = False
    try:
        model = DecoderLM(vocab_size=50, num_layers=2, num_heads=2,
                          head_dim=8, max_positions=128)
        params = model.init_params(jax.random.PRNGKey(0))
        eng = ServingEngine(model, params, eos_id=1, page_size=4,
                            num_pages=40, max_pages_per_seq=10,
                            max_slots=4, buckets=(4, 8, 16),
                            prefill_chunk=8)
        # warm the pair ladder deterministically: a lone short prompt
        # (pb=4), then decode-only ticks (pb=0) to completion...
        eng.submit(rng.randint(2, 50, size=3).tolist(), max_tokens=8)
        eng.run(max_ticks=100)
        # ...then a MIXED steady state: a long prompt chunks (8-row
        # chunks -> pb=8) while short batchmates decode in the same
        # fused dispatch
        eng.submit(rng.randint(2, 50, size=4).tolist(), max_tokens=12)
        eng.step()
        eng.submit(rng.randint(2, 50, size=20).tolist(), max_tokens=8)
        eng.run(max_ticks=300)
        pairs = audit.compile_count("serving.step")
        assert pairs == len(eng._step_fns)    # exactly one compile per pair
        assert pairs == 3                     # pb in {0, 4, 8}
        assert audit.compile_count("serving.decode") == 0   # site retired
        assert audit.compile_count("serving.prefill") == 0
        assert audit.compile_count("serving.chunk_prefill") == 0
        # steady state: same pair shapes must not compile AGAIN (the
        # same arrival pattern, so the packer reproduces the same
        # buckets — a new pattern could legitimately mint a new pair)
        audit.seal()
        eng.submit(rng.randint(2, 50, size=2).tolist(), max_tokens=8)
        eng.run(max_ticks=100)
        eng.submit(rng.randint(2, 50, size=4).tolist(), max_tokens=12)
        eng.step()
        eng.submit(rng.randint(2, 50, size=17).tolist(), max_tokens=8)
        eng.run(max_ticks=300)
        audit.assert_budget("serving.step", pairs)
        audit.assert_no_retraces()
        snap = audit.snapshot()
        assert snap["serving.step"]["calls"] > \
            snap["serving.step"]["compiles"]
    finally:
        FLAGS.use_bf16 = old_bf16


# ---------------------------------------------------------------------------
# linter rules on synthetic snippets
# ---------------------------------------------------------------------------


def _codes_of(findings):
    return sorted({d.code for d in findings})


def test_lint_wall_clock_scoped_to_serving_and_master():
    src = "import time\n\ndef tick():\n    return time.monotonic()\n"
    assert _codes_of(lint_source(src, "paddle_tpu/serving/x.py")) \
        == ["wall-clock"]
    assert _codes_of(lint_source(src, "paddle_tpu/master/x.py")) \
        == ["wall-clock"]
    assert lint_source(src, "paddle_tpu/reader/x.py") == []
    # passing the clock as an injectable default is the sanctioned form
    ok = "import time\n\ndef f(time_fn=time.monotonic):\n    return time_fn()\n"
    assert lint_source(ok, "paddle_tpu/serving/x.py") == []
    # aliased imports cannot smuggle the call past the rule
    alias1 = "import time as t\n\ndef tick():\n    return t.monotonic()\n"
    assert _codes_of(lint_source(alias1, "paddle_tpu/serving/x.py")) \
        == ["wall-clock"]
    alias2 = ("from time import monotonic\n\ndef tick():\n"
              "    return monotonic()\n")
    assert _codes_of(lint_source(alias2, "paddle_tpu/serving/x.py")) \
        == ["wall-clock"]


def test_lint_allowlist_escape_hatch():
    src = ("import time\n\ndef tick():\n"
           "    return time.monotonic()  # lint: allow(wall-clock)\n")
    assert lint_source(src, "paddle_tpu/serving/x.py") == []
    # the line ABOVE also covers (comment-then-statement style)
    src2 = ("import time\n\ndef tick():\n"
            "    # lint: allow(wall-clock)\n"
            "    return time.monotonic()\n")
    assert lint_source(src2, "paddle_tpu/serving/x.py") == []
    # allowing a DIFFERENT rule does not suppress
    src3 = ("import time\n\ndef tick():\n"
            "    return time.monotonic()  # lint: allow(host-sync)\n")
    assert _codes_of(lint_source(src3, "paddle_tpu/serving/x.py")) \
        == ["wall-clock"]


def test_lint_scopes_rules_from_resolved_path(tmp_path, monkeypatch):
    """Dir-scoped rules must fire when a file is linted by bare
    filename from inside its directory — scoping resolves the path."""
    from paddle_tpu.analysis.lint import lint_file

    d = tmp_path / "serving"
    d.mkdir()
    f = d / "x.py"
    f.write_text("import time\n\ndef tick():\n    return time.monotonic()\n")
    monkeypatch.chdir(d)
    assert _codes_of(lint_file("x.py")) == ["wall-clock"]


def test_lint_unseeded_random():
    bad = "import numpy as np\n\ndef f():\n    return np.random.randn(3)\n"
    assert _codes_of(lint_source(bad, "paddle_tpu/utils.py")) \
        == ["unseeded-random"]
    ok = ("import numpy as np\n\ndef f(seed):\n"
          "    return np.random.RandomState(seed).randn(3)\n")
    assert lint_source(ok, "paddle_tpu/utils.py") == []


def test_lint_host_sync_in_serving_loops():
    bad = ("import numpy as np\n\ndef step(rows):\n"
           "    for r in rows:\n"
           "        v = np.asarray(r)\n"
           "        w = r.item()\n")
    found = lint_source(bad, "paddle_tpu/serving/x.py")
    assert _codes_of(found) == ["host-sync"] and len(found) == 2
    # same code outside a loop, or outside serving/: clean
    ok = "import numpy as np\n\ndef step(r):\n    return np.asarray(r)\n"
    assert lint_source(ok, "paddle_tpu/serving/x.py") == []
    assert lint_source(bad, "paddle_tpu/reader/x.py") == []
    # float() over a jax expression inside the loop
    bad2 = ("import jax.numpy as jnp\n\ndef step(rows):\n"
            "    out = []\n    for r in rows:\n"
            "        out.append(float(jnp.mean(r)))\n    return out\n")
    assert _codes_of(lint_source(bad2, "paddle_tpu/serving/x.py")) \
        == ["host-sync"]


def test_lint_mutable_default():
    bad = "def f(x, acc=[]):\n    acc.append(x)\n    return acc\n"
    assert _codes_of(lint_source(bad, "paddle_tpu/utils.py")) \
        == ["mutable-default"]
    ok = "def f(x, acc=None):\n    return (acc or []) + [x]\n"
    assert lint_source(ok, "paddle_tpu/utils.py") == []


def test_lint_import_time_flags():
    bad = ("from paddle_tpu.platform.flags import FLAGS\n"
           "PERIOD = FLAGS.log_period\n")
    assert _codes_of(lint_source(bad, "paddle_tpu/x.py")) \
        == ["import-time-flags"]
    bad2 = ("from paddle_tpu.platform.flags import FLAGS\n"
            "def f(period=FLAGS.log_period):\n    return period\n")
    assert _codes_of(lint_source(bad2, "paddle_tpu/x.py")) \
        == ["import-time-flags"]
    ok = ("from paddle_tpu.platform.flags import FLAGS\n"
          "FLAGS.define('x', 1, 'help')\n"
          "def f():\n    return FLAGS.log_period\n")
    assert lint_source(ok, "paddle_tpu/x.py") == []
    # a def nested in a module-level if/try runs at CALL time — its body
    # must not be treated as an import-time read...
    ok2 = ("from paddle_tpu.platform.flags import FLAGS\n"
           "try:\n"
           "    def f():\n        return FLAGS.log_period\n"
           "except ImportError:\n    pass\n"
           "if True:\n"
           "    def g():\n        return FLAGS.seed\n")
    assert lint_source(ok2, "paddle_tpu/x.py") == []
    # ...but a bare read inside a module-level `if` IS import time
    bad3 = ("from paddle_tpu.platform.flags import FLAGS\n"
            "if True:\n    PERIOD = FLAGS.log_period\n")
    assert _codes_of(lint_source(bad3, "paddle_tpu/x.py")) \
        == ["import-time-flags"]


def test_every_flag_is_read_outside_the_registry():
    """A flag that nothing reads is a name with no effect, and one that
    only shadows a keyword is that keyword's second spelling: every name
    ``platform/flags.py`` defines is read (``FLAGS.<name>`` or
    ``getattr(FLAGS, "<name>", ...)``) somewhere under ``paddle_tpu/``
    outside the registry itself."""
    import re
    from pathlib import Path

    import paddle_tpu

    pkg = Path(paddle_tpu.__file__).resolve().parent
    registry = pkg / "platform" / "flags.py"
    src = "\n".join(p.read_text() for p in sorted(pkg.rglob("*.py"))
                    if p != registry)
    unread = [n for n in sorted(FLAGS.to_dict())
              if not re.search(rf'FLAGS\.{n}\b|FLAGS, "{n}"', src)]
    assert unread == [], unread


def test_repo_lints_clean():
    """The acceptance bar: the linter lands clean on its own repo (real
    findings fixed, justified ones allowlisted inline)."""
    findings = run_lint()
    assert findings == [], [d.message for d in findings]
