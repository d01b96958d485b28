"""The train step names its parts (PR 39): every node's scope carries its
kind beside its name, the optimizer and the step's own glue have scopes of
their own, and ``SGD.train`` names its host phases.  Scopes are metadata of
the compiled program: what a step computes is the parent's to the last bit.
"""

import contextlib
import hashlib
import re

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer, trainer
from paddle_tpu.models import transformer

VOCAB = 61
FEEDING = {"tokens": 0, "pos": 1, "target": 2}


def _samples(seed=7, lens=(11, 7, 16)):
    rng = np.random.RandomState(seed)
    out = []
    for n in lens:
        toks = rng.randint(0, VOCAB, size=n)
        out.append((toks.tolist(), list(range(n)),
                    np.roll(toks, -1).tolist()))
    return out


def _trainer(remat=False, fused_head=False, guard=False, clip=0.0,
             tracer=None):
    paddle.topology.reset_name_scope()
    *_, cost = transformer.build(vocab_size=VOCAB, d_model=16, n_layers=2,
                                 n_heads=2, max_len=32, remat=remat,
                                 fused_head=fused_head)
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([cost]), seed=0)
    kw = {}
    if guard:
        from paddle_tpu.resilience.guard import BadStepGuard

        kw["guard"] = BadStepGuard()
    return trainer.SGD(cost=cost, parameters=params, tracer=tracer,
                       update_equation=optimizer.Adam(
                           learning_rate=1e-2,
                           gradient_clipping_threshold=clip), **kw)


def _op_names(sgd):
    """Every ``op_name`` of the compiled step: what a profiler trace keeps
    of each instruction in its HLO proto."""
    step = sgd._build_step()
    feeds = sgd._make_feeder(FEEDING).feed(_samples())
    args = [sgd.parameters.as_dict(), sgd.opt_state, sgd.model_state,
            jax.random.PRNGKey(0), feeds]
    if sgd._guard is not None:
        args.append(sgd._guard_init())
    text = step.lower(*args).compile().as_text()
    return sorted({n for n in re.findall(r'op_name="([^"]+)"', text)
                   if n.startswith("jit(")})


def _under(scope):
    # benchmarks/harness/op_scopes.py `under`, which the readers go by
    pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/)])")
    return lambda op_name: bool(pat.search(op_name))


@pytest.fixture(scope="module")
def plain_names():
    return _op_names(_trainer(clip=1.0))


@pytest.fixture(scope="module")
def remat_names():
    return _op_names(_trainer(remat=True, fused_head=True))


def test_a_node_scope_carries_kind_and_name_in_one_path(plain_names):
    kind, name = _under("layer_norm"), _under("blk0_ln1")
    both = [n for n in plain_names if kind(n) and name(n)]
    assert both, plain_names[:20]
    # the kind lies outside the name, forward and backward alike
    for n in both:
        assert n.index("layer_norm") < n.index("blk0_ln1"), n
    assert any("transpose(" in n for n in both)
    assert any("transpose(" not in n for n in both)
    # and no name of this node comes without its kind
    assert not [n for n in plain_names if name(n) and not kind(n)]


@pytest.mark.parametrize("scope", ["opt", "opt.clip", "opt.update",
                                   "step.loss", "attn.proj", "attn.core"])
def test_the_step_names_its_own_parts(plain_names, scope):
    inside = [n for n in plain_names if _under(scope)(n)]
    assert inside, scope
    if scope.startswith("opt."):
        assert all(_under("opt")(n) for n in inside)
    if scope.startswith("attn."):
        assert all(_under("multi_head_attention")(n) for n in inside)


def test_nothing_of_the_optimizer_is_left_bare(plain_names):
    """The parent's update read ``jit(step)/mul``, ``jit(step)/sqrt``: a
    path of two segments is an operation outside every scope."""
    bare = [n for n in plain_names if n.count("/") == 1]
    assert not bare, bare


def test_a_remat_scope_shows_the_recompute_wrapper(remat_names):
    norm = _under("layer_norm")
    again = [n for n in remat_names
             if "rematted_computation" in n and norm(n)]
    assert again and all(_under("blk0_ln1")(n) or _under("blk0_ln2")(n)
                         or _under("blk1_ln1")(n) or _under("blk1_ln2")(n)
                         for n in again)
    # the fused head holds its own product: two inner scopes
    for scope in ("head.logits", "head.xent"):
        inside = [n for n in remat_names if _under(scope)(n)]
        assert inside and all(_under("lm_head_cost")(n) for n in inside)


def test_no_kind_is_named_as_an_inner_scope():
    from paddle_tpu import layer

    inner = {"gdn", "gdn.proj", "gdn.conv", "gdn.scan", "gdn.out", "gattn",
             "mla", "moe.route", "moe.experts", "moe.shared", "attn.proj",
             "attn.core", "head.logits", "head.xent", "opt", "opt.clip",
             "opt.update", "opt.average", "opt.shard", "opt.gather",
             "step.loss", "step.guard", "step.stats"}
    src = open(layer.__file__).read()
    kinds = set(re.findall(r'layer_type="([^"]+)"', src))
    assert "layer_norm" in kinds and len(kinds) > 50
    assert not kinds & inner


def test_a_guarded_step_shows_its_guard():
    names = _op_names(_trainer(guard=True))
    assert [n for n in names if _under("step.guard")(n)]
    assert [n for n in names if _under("opt.update")(n)]


def test_param_stats_have_a_scope():
    from paddle_tpu.platform.flags import FLAGS

    old = FLAGS.show_parameter_stats_period
    FLAGS.show_parameter_stats_period = 1
    try:
        names = _op_names(_trainer())
    finally:
        FLAGS.show_parameter_stats_period = old
    assert [n for n in names if _under("step.stats")(n)]


# ---- SGD.train names its host phases ------------------------------------------

class _Phases:
    """A stub of the tracer ``SGD`` holds: ``phase`` alone is recorded."""

    enabled = False

    def __init__(self):
        self.seen = []

    def phase(self, name, **kw):
        self.seen.append(name)
        return contextlib.nullcontext()

    def instant(self, *a, **kw):
        pass

    def span(self, *a, **kw):
        return contextlib.nullcontext()


@pytest.mark.parametrize("prefetch", [0, 2])
def test_train_names_feed_dispatch_and_flush(prefetch):
    from paddle_tpu.platform.flags import FLAGS

    tracer = _Phases()
    sgd = _trainer(tracer=tracer)
    batches = [_samples(seed=s) for s in range(4)]
    old = FLAGS.log_period
    FLAGS.log_period = 2
    try:
        sgd.train(lambda: iter(batches), num_passes=1, feeding=FEEDING,
                  event_handler=lambda ev: None, prefetch=prefetch)
    finally:
        FLAGS.log_period = old
    count = {p: tracer.seen.count(p) for p in set(tracer.seen)}
    # once a step; a flush each log window of two steps and one at the
    # pass's end
    assert count == {"step.feed": 4, "step.dispatch": 4, "step.flush": 3}
    if not prefetch:
        assert tracer.seen[:2] == ["step.feed", "step.dispatch"]


def test_the_phases_reach_a_profiler_session(tmp_path):
    """With no ``obs`` tracer bound the phases are bare annotations, which
    an open profiler session keeps as ``pt:step.*`` on its own clock."""
    sgd = _trainer()
    batches = [_samples(seed=s) for s in range(2)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        sgd.train(lambda: iter(batches), num_passes=1, feeding=FEEDING,
                  event_handler=lambda ev: None)
    finally:
        jax.profiler.stop_trace()
    found = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert found
    data = jax.profiler.ProfileData.from_file(str(found[0]))
    names = [e.name for plane in data.planes for line in plane.lines
             for e in line.events if e.name.startswith("pt:step.")]
    assert names.count("pt:step.feed") == 2
    assert names.count("pt:step.dispatch") == 2
    assert names.count("pt:step.flush") >= 1


# ---- scopes are metadata: the parent's results, bit for bit -------------------

def _digest(*arrays):
    return hashlib.sha256(b"".join(np.ascontiguousarray(a).tobytes()
                                   for a in arrays)).hexdigest()[:16]


def _three_steps(**kw):
    sgd = _trainer(**kw)
    step = sgd._build_step()
    feeds = sgd._make_feeder(FEEDING).feed(_samples())
    p, o, m = sgd.parameters.as_dict(), sgd.opt_state, sgd.model_state
    extra = [sgd._guard_init()] if sgd._guard is not None else []
    if extra:
        extra[0]["inject"] = np.float32(0.0)
    losses = []
    for i in range(3):
        out = step(p, o, m, jax.random.PRNGKey(i), feeds, *extra)
        loss, p, o, m = out[:4]
        if extra:
            extra = [{"inject": extra[0]["inject"], **out[5]}]
        losses.append(np.asarray(loss))
    return _digest(*losses), _digest(*(np.asarray(p[k]) for k in sorted(p)))


# case: (losses, parameters) after three Adam steps, sha256[:16], as commit
# 8cc39c5 (no kinds, no `opt`, no `step.*`) gave them here on the CPU
_PARENT = {
    "plain": (dict(clip=1.0), "105717e1b4db137b", "064b5314262738a1"),
    "remat, fused head": (dict(remat=True, fused_head=True),
                          "8d1ece5092960224", "c9f0eded7862d7f1"),
    "guarded": (dict(guard=True), "ffbad80b074965fb", "f80e3d6c00404610"),
}


@pytest.mark.parametrize("case", sorted(_PARENT))
def test_a_step_computes_what_the_parents_did(case):
    kw, losses, params = _PARENT[case]
    assert _three_steps(**kw) == (losses, params)
