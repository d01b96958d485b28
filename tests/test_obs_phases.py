"""``Tracer.phase``: the serving tick names its own phases (``pt:tick.*``)
for the profiler and, with an enabled tracer, for the ring.

Marker ``obs``.  What is pinned: the obs-off path is the profiler's
annotation and nothing else; the ring holds the phases of a tick in order
under one tick number, the dispatch inside the upload; a profiler session
finds them in the host plane; a tick places one buffer, replicated over
the engine's mesh, and dispatches once; the transfer counters equal a hand
count; the compiled step carries the blocks' names and takes one integer
operand, which it takes apart without a collective.
"""

import glob
import itertools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.analysis.retrace import auditor
from paddle_tpu.obs import NULL_TRACER, Tracer, chrome_trace
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.serving import (DecoderLM, FaultPlan, ManualClock,
                                ServingEngine)
from paddle_tpu.serving import engine as engine_mod

pytestmark = pytest.mark.obs

# as the ring holds them: a span is recorded when it ends, so the
# dispatch comes before the upload that holds it
TICK_PHASES = ["pt:tick.schedule", "pt:tick.assemble", "pt:tick.dispatch",
               "pt:tick.upload", "pt:tick.wait", "pt:tick.sample"]
V, SLOTS, PAGES_PER_SEQ = 64, 4, 8
TP = 4


@pytest.fixture(scope="module")
def small_model():
    model = DecoderLM(vocab_size=V, num_layers=1, num_heads=2, head_dim=8,
                      max_positions=128)
    return model, model.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tp_model():
    """Two blocks of four heads: splits over the 4-device CPU mesh the
    tensor-parallel tests use."""
    model = DecoderLM(vocab_size=V, num_layers=2, num_heads=TP, head_dim=8,
                      max_positions=128)
    return model, model.init_params(jax.random.PRNGKey(1))


def tp_mesh():
    return make_mesh((TP,), ("model",), jax.devices()[:TP])


def make_engine(model, params, clock, **kw):
    return ServingEngine(model, params, eos_id=1, page_size=4,
                         num_pages=32, max_pages_per_seq=PAGES_PER_SEQ,
                         max_slots=SLOTS, buckets=(8, 16), time_fn=clock,
                         prefix_cache=False, **kw)


class Forbidden:
    """Stands where a module was: any use of it fails the test."""

    def __init__(self, what):
        self._what = what

    def __getattr__(self, name):
        raise AssertionError(f"the obs-off phase touched {self._what}.{name}")


def exploding_clock():
    raise AssertionError("a phase read a clock")


# ---------------------------------------------------------------------------
# off: one annotation entered and left, nothing else
# ---------------------------------------------------------------------------


def test_phase_off_is_the_profilers_annotation_and_nothing_else(
        monkeypatch, small_model):
    """``NULL_TRACER`` bound, no profiler session: a phase is a bare
    ``TraceAnnotation``; no clock, lock, ring or registry is touched
    (``time`` and ``threading`` are taken away from ``obs.trace`` while
    an engine ticks), and the sealed steady state keeps its one compile
    per bucket pair and its one dispatch and one readback a tick (of the
    step's int32 words, a call after its dispatch)."""
    # an enabled tracer stamps its own clock; the obs-off one must not
    with pytest.raises(AssertionError, match="read a clock"):
        with Tracer(time_fn=exploding_clock).phase("tick.schedule", tick=3):
            pass
    monkeypatch.setattr(obs_trace, "time", Forbidden("time"))
    monkeypatch.setattr(obs_trace, "threading", Forbidden("threading"))
    ann = NULL_TRACER.phase("tick.schedule", tick=3)
    assert type(ann) is jax.profiler.TraceAnnotation
    with ann:
        pass
    assert NULL_TRACER.registry is None

    model, params = small_model
    assert not FLAGS.obs_trace
    old = FLAGS.jit_audit
    FLAGS.jit_audit = True
    auditor().reset()
    try:
        eng = make_engine(model, params, ManualClock(tick_s=0.01))
        assert eng._tracer is NULL_TRACER
        rid = eng.submit([2, 3, 4, 5], max_tokens=4)
        eng.run()
        pairs = auditor().compile_count("serving.step")
        assert pairs == len(eng._step_fns)
        auditor().seal()
        m = eng.metrics
        before = (m.ticks, m.step_dispatches, m.d2h_bytes, m.steps_lagged)
        rid2 = eng.submit([2, 3, 4, 5], max_tokens=4)
        eng.run()
        auditor().assert_budget("serving.step", pairs)
        auditor().assert_no_retraces()
    finally:
        FLAGS.jit_audit = old
        auditor().reset()
    assert eng.result(rid) == eng.result(rid2)
    # every tick of the second request but the one that read the last
    # step's words dispatched a step; what is read of a step is its
    # words: a choice and a finite flag for each slot's decode row and
    # chunk-final row
    steps = m.ticks - before[0] - 1
    assert m.step_dispatches - before[1] == steps
    assert m.steps_lagged - before[3] == steps - 1
    assert m.d2h_bytes - before[2] == steps * 2 * 2 * SLOTS * 4
    assert NULL_TRACER.events == [] and len(NULL_TRACER.ring) == 0


# ---------------------------------------------------------------------------
# on: the ring holds the tick's phases, in order, under one tick number
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lands", ["a-call-later", "in-its-own-call"])
def test_ring_holds_a_ticks_phases_in_order(small_model, lands):
    """``wait`` and ``sample`` are the read of a step's words and their
    walk: in the call after the step's dispatch, behind that call's own
    dispatch, or (a fault plan bound) in the step's own call."""
    model, params = small_model
    clk = ManualClock(tick_s=0.01)
    # the tracer's own clock moves at every reading, so that "inside"
    # below is a statement about order and not about equal stamps
    reads = itertools.count()
    tracer = Tracer(time_fn=lambda: 1e-3 * next(reads))
    kw = dict(faults=FaultPlan(clock=clk)) if lands == "in-its-own-call" \
        else {}
    eng = make_engine(model, params, clk, tracer=tracer.scoped(replica=7),
                      **kw)
    rid = eng.submit([2, 3, 4, 5], max_tokens=3)
    eng.run()
    eng.step()                                      # an idle tick
    assert eng.result(rid) is not None
    by_tick, extent = {}, {}
    for e in tracer.ring:
        if e.name.startswith("pt:"):
            assert e.kind == "X" and e.replica == 7
            by_tick.setdefault(e.args["tick"], []).append(e.name)
            extent[e.args["tick"], e.name] = (e.ts, e.ts + e.dur)
    landed = [t for t, names in by_tick.items() if "pt:tick.wait" in names]
    busy = [t for t, names in by_tick.items() if "pt:tick.dispatch" in names]
    assert len(landed) == len(busy) == eng.metrics.step_dispatches >= 3
    # children first (a span is recorded when it ends), the closing
    # bookkeeping under the sample phase's name, the tick last
    whole = TICK_PHASES + ["pt:tick.sample", "pt:tick"]
    if lands == "in-its-own-call":
        assert landed == busy and eng.metrics.steps_lagged == 0
    else:
        # the first step had none before it to read; the last was read
        # by the call after it, which had nothing to dispatch
        assert landed == [t + 1 for t in busy]
        assert by_tick[busy[0]] == [n for n in whole if n != "pt:tick.wait"
                                    ][:4] + ["pt:tick.sample", "pt:tick"]
        assert by_tick[landed[-1]] == ["pt:tick.schedule", "pt:tick.wait",
                                       "pt:tick.sample", "pt:tick.sample",
                                       "pt:tick"]
        assert eng.metrics.steps_lagged == len(busy) - 1
    for t in busy:
        if t in landed:
            assert by_tick[t] == whole
        # the compiled step's call alone, strictly inside the upload:
        # the placement comes before it
        (d0, d1), (u0, u1) = (extent[t, "pt:tick." + n]
                              for n in ("dispatch", "upload"))
        assert u0 < d0 < d1 < u1
    idle = [t for t in by_tick if t not in busy and t not in landed]
    assert idle and all(by_tick[t] == ["pt:tick.schedule", "pt:tick.sample",
                                       "pt:tick"] for t in idle)
    # the historical span is still there, and the exporter takes phases
    assert any(e.name == "decode_tick" for e in tracer.events)
    names = {ev["name"] for ev in chrome_trace(tracer.events)["traceEvents"]}
    assert set(TICK_PHASES) | {"pt:tick"} <= names


def test_profiler_session_finds_the_ticks_phases(small_model, tmp_path):
    """An obs-off engine under ``jax.profiler.trace``: the host plane has
    ``pt:tick`` and the five phases, the children inside their tick."""
    model, params = small_model
    eng = make_engine(model, params, ManualClock(tick_s=0.01))
    eng.submit([2, 3, 4, 5], max_tokens=2)
    eng.run()                                       # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.submit([3, 4, 5, 6], max_tokens=3)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                    "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("pt:")]
    ticks = [s for s in spans if s[0] == "pt:tick"]
    assert len(ticks) >= 3
    def inside(name, outer):
        mine = [s for s in spans if s[0] == name]
        assert len(mine) >= 3
        assert all(any(t[1] <= s[1] and s[2] <= t[2] for t in outer)
                   for s in mine)

    for name in TICK_PHASES:
        inside(name, ticks)
    inside("pt:tick.dispatch", [s for s in spans
                                if s[0] == "pt:tick.upload"])


# ---------------------------------------------------------------------------
# the transfer counters against a hand count
# ---------------------------------------------------------------------------


class Counting:
    """Stands where ``jax.numpy`` was in the engine: counts the arrays
    its host code makes."""

    def __init__(self):
        self.made = 0

    def __getattr__(self, name):
        if name in ("asarray", "array"):
            self.made += 1
        return getattr(jnp, name)


@pytest.mark.parametrize("mesh,spec", [(False, 0), (False, 2), (True, 0),
                                       (True, 2)],
                         ids=["one-device", "one-device-k1=3", "mesh-of-4",
                              "mesh-of-4-k1=3"])
def test_a_tick_places_one_buffer_and_dispatches_once(
        monkeypatch, small_model, tp_model, mesh, spec):
    """With and without a prefill chunk, with and without drafted rows:
    one ``jax.device_put`` of one int32 buffer, no other array made by
    the engine's host code, one dispatch; and what the compiled step
    receives (the buffer, and the words of the step before it, which
    never left the device) is committed and fully replicated over the
    engine's mesh (on the one device without one), so the call re-lays
    nothing and no program is compiled a second time."""
    model, params = tp_model if mesh else small_model
    kw = dict(mesh=tp_mesh()) if mesh else {}
    if spec:
        kw.update(spec_mode="ngram", spec_k=spec)
    eng = make_engine(model, params, ManualClock(tick_s=0.01), **kw)
    assert eng._k1 == 1 + spec
    puts, got = [], []
    real_put, real_step_fn = jax.device_put, eng._step_fn

    def put(x, device=None, **kwargs):
        puts.append(x)
        return real_put(x, device, **kwargs)

    def step_fn(pb, k1=1):
        fn = real_step_fn(pb, k1)

        def call(*args):
            got.append((pb, args))
            return fn(*args)
        return call

    eng.submit([2, 3, 4, 5], max_tokens=4)
    eng.run()                                  # every program compiled
    eng.submit([3, 4, 5, 6, 7], max_tokens=4)
    counting = Counting()
    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(engine_mod, "jnp", counting)
    monkeypatch.setattr(eng, "_step_fn", step_fn)
    for bucket in (True, False, False):        # the prompt, then decoding
        eng.step()
        (packed,), ((pb, args),) = puts, got
        assert (pb > 0) == bucket
        assert counting.made == 0
        assert packed.dtype == "int32" and packed.ndim == 1
        assert packed.size == SLOTS * (3 * eng._k1 + 2 + PAGES_PER_SEQ) \
            + 3 * pb
        params_in, _kv_in, placed, last = args     # and nothing else
        assert params_in is eng.params
        assert last.dtype == "int32" and last.is_fully_replicated
        assert last.shape == (2 * (SLOTS * eng._k1 + SLOTS),)
        if mesh:
            assert last.committed and last.sharding.is_equivalent_to(
                placed.sharding, 1)
        # a plain engine's words are those of the step in the air
        assert (eng._flying is None) == bool(spec)
        assert placed.committed == mesh
        assert placed.is_fully_replicated
        if mesh:
            assert placed.sharding == NamedSharding(eng.mesh, P())
            assert len(placed.sharding.device_set) == TP
            assert placed.sharding.device_set == \
                eng._kv.k.sharding.device_set
        assert (placed == packed).all()
        puts.clear()
        got.clear()
    assert all(fn._cache_size() == 1 for fn in eng._step_fns.values())


def test_transfer_counters_equal_the_hand_count(small_model):
    model, params = small_model
    eng = make_engine(model, params, ManualClock(tick_s=0.01))
    eng.submit([2, 3, 4, 5], max_tokens=4)
    eng.run()
    m = eng.metrics
    # per dispatch, ONE int32 buffer: d_tokens, d_pos, d_valid [B, 1],
    # p_last, att_lens [B], table [B, pages]; a tick with prefill rows
    # adds p_tokens, p_qpos, p_seq [bucket of 8]
    decode_only = 4 * (3 * SLOTS + 2 * SLOTS + SLOTS * PAGES_PER_SEQ)
    with_prefill = decode_only + 3 * 4 * 8
    assert m.prefill_rows > 0 and m.step_dispatches == 4
    assert m.h2d_bytes == with_prefill + 3 * decode_only
    # down: the step's int32 words, every dispatch: the choice and the
    # finite flag of each slot's decode row and chunk-final row (the
    # [2 B, V] float32 logits stay on the device)
    assert m.d2h_bytes == 4 * 2 * 2 * SLOTS * 4
    snap = m.snapshot()
    assert snap["h2d_bytes"] == m.h2d_bytes
    assert snap["d2h_bytes"] == m.d2h_bytes


# ---------------------------------------------------------------------------
# names in the device trace
# ---------------------------------------------------------------------------


def test_serving_step_names_blocks_and_parts(small_model):
    """The compiled step carries ``l<N>/attn``, ``l<N>/ffn`` and ``head``
    scopes, which is what a device trace names its operations by."""
    model, params = small_model
    eng = make_engine(model, params, ManualClock(tick_s=0.01))
    packed = eng._assemble([], [], 0, {})
    text = eng._step_fn(0, 1).lower(eng.params, eng._kv, packed,
                                    eng._last_words()).as_text(
        debug_info=True)
    for scope in ("l0/attn", "l0/ffn", "head"):
        assert scope in text, scope


@pytest.mark.parametrize("pb", [0, 8], ids=["decode-only", "bucket-of-8"])
@pytest.mark.parametrize("spec", [0, 2], ids=["k1=1", "k1=3"])
def test_serving_step_takes_one_integer_operand_and_gathers_nothing(
        tp_model, pb, spec):
    """On the 4-device mesh: besides the parameters and the pool the
    step takes ONE operand from the host, the tick's packed int32
    buffer, replicated, and the int32 words of the step before it, which
    it left replicated on the device; the compiled program holds the
    closed form's all-reduces (two a block) and no other collective, so
    taking the buffer apart and choosing each row's token move nothing
    between chips, and the words it returns are replicated."""
    model, params = tp_model
    kw = dict(spec_mode="ngram", spec_k=spec) if spec else {}
    eng = make_engine(model, params, ManualClock(tick_s=0.01),
                      mesh=tp_mesh(), **kw)
    k1 = eng._k1
    words = SLOTS * (3 * k1 + 2 + PAGES_PER_SEQ) + 3 * pb
    lowered = eng._step_fn(pb, k1).lower(
        eng.params, eng._kv, jax.device_put(eng._empty_tick(pb, k1),
                                            eng._tick_sharding),
        eng._last_words())
    leaves = jax.tree.leaves(lowered.args_info)
    ints = [a for a in leaves if jnp.issubdtype(a.dtype, jnp.integer)]
    last = 2 * (SLOTS * k1 + SLOTS)
    assert [(a.shape, str(a.dtype)) for a in ints] == [
        ((words,), "int32"), ((last,), "int32")]
    n_pool = len(jax.tree.leaves(eng._kv))
    assert len(leaves) == len(params) + n_pool + 2
    compiled = lowered.compile()
    assert compiled.output_shardings[0].is_fully_replicated
    assert compiled.output_shardings[0].is_equivalent_to(
        eng._tick_sharding, 1)
    text = compiled.as_text()
    ops = re.findall(r"= \S+ (all-reduce|all-gather|all-to-all|"
                     r"reduce-scatter|collective-permute|"
                     r"collective-broadcast)(?:-start)?\(", text)
    assert ops == ["all-reduce"] * (2 * model.num_layers), ops
