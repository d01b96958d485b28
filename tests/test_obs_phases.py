"""``Tracer.phase``: the serving tick names its own phases (``pt:tick.*``)
for the profiler and, with an enabled tracer, for the ring.

Marker ``obs``.  What is pinned: the obs-off path is the profiler's
annotation and nothing else; the ring holds the phases of a tick in order
under one tick number; a profiler session finds them in the host plane;
the transfer counters equal a hand count; the compiled step carries the
blocks' names.
"""

import glob
import os

import jax
import pytest

from paddle_tpu.analysis.retrace import auditor
from paddle_tpu.obs import NULL_TRACER, Tracer, chrome_trace
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.serving import DecoderLM, ManualClock, ServingEngine

pytestmark = pytest.mark.obs

TICK_PHASES = ["pt:tick.schedule", "pt:tick.assemble", "pt:tick.upload",
               "pt:tick.wait", "pt:tick.sample"]
V, SLOTS, PAGES_PER_SEQ = 64, 4, 8


@pytest.fixture(scope="module")
def small_model():
    model = DecoderLM(vocab_size=V, num_layers=1, num_heads=2, head_dim=8,
                      max_positions=128)
    return model, model.init_params(jax.random.PRNGKey(0))


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
    per bucket pair and its one dispatch and one readback a tick."""
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
        before = (m.ticks, m.step_dispatches, m.d2h_bytes)
        rid2 = eng.submit([2, 3, 4, 5], max_tokens=4)
        eng.run()
        auditor().assert_budget("serving.step", pairs)
        auditor().assert_no_retraces()
    finally:
        FLAGS.jit_audit = old
        auditor().reset()
    assert eng.result(rid) == eng.result(rid2)
    # every tick of the second request was busy: one dispatch and one
    # readback (the two logits arrays) each
    ticks = m.ticks - before[0]
    assert m.step_dispatches - before[1] == ticks
    assert m.d2h_bytes - before[2] == ticks * 2 * SLOTS * V * 4
    assert NULL_TRACER.events == [] and len(NULL_TRACER.ring) == 0


# ---------------------------------------------------------------------------
# on: the ring holds the tick's phases, in order, under one tick number
# ---------------------------------------------------------------------------


def test_ring_holds_a_ticks_phases_in_order(small_model):
    model, params = small_model
    clk = ManualClock(tick_s=0.01)
    tracer = Tracer(time_fn=clk)
    eng = make_engine(model, params, clk, tracer=tracer.scoped(replica=7))
    rid = eng.submit([2, 3, 4, 5], max_tokens=3)
    eng.run()
    eng.step()                                      # an idle tick
    assert eng.result(rid) is not None
    by_tick = {}
    for e in tracer.ring:
        if e.name.startswith("pt:"):
            assert e.kind == "X" and e.replica == 7
            by_tick.setdefault(e.args["tick"], []).append(e.name)
    busy = [t for t, names in by_tick.items() if "pt:tick.wait" in names]
    assert len(busy) == eng.metrics.step_dispatches >= 3
    for t in busy:
        # children first (a span is recorded when it ends), the closing
        # bookkeeping under the sample phase's name, the tick last
        assert by_tick[t] == TICK_PHASES + ["pt:tick.sample", "pt:tick"]
    idle = [t for t in by_tick if t not in busy]
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
    for name in TICK_PHASES:
        mine = [s for s in spans if s[0] == name]
        assert len(mine) >= 3
        assert all(any(t[1] <= s[1] and s[2] <= t[2] for t in ticks)
                   for s in mine)


# ---------------------------------------------------------------------------
# the transfer counters against a hand count
# ---------------------------------------------------------------------------


def test_transfer_counters_equal_the_hand_count(small_model):
    model, params = small_model
    eng = make_engine(model, params, ManualClock(tick_s=0.01))
    eng.submit([2, 3, 4, 5], max_tokens=4)
    eng.run()
    m = eng.metrics
    # per dispatch, int32 unless said: d_tokens, d_pos [B, 1], d_valid
    # [B, 1] bool, p_last, att_lens [B], table [B, pages]; a tick with
    # prefill rows adds p_tokens, p_qpos, p_seq [bucket of 8]
    decode_only = 4 * (SLOTS + SLOTS + SLOTS + SLOTS) + SLOTS \
        + 4 * SLOTS * PAGES_PER_SEQ
    with_prefill = decode_only + 3 * 4 * 8
    assert m.prefill_rows > 0 and m.step_dispatches == 4
    assert m.h2d_bytes == with_prefill + 3 * decode_only
    # down: [B, 1, V] and [B, V] float32 logits, every dispatch
    assert m.d2h_bytes == 4 * 2 * SLOTS * V * 4
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
    host = eng._assemble([], [], 0, {})
    text = eng._step_fn(0, 1).lower(eng.params, eng._kv, *host).as_text(
        debug_info=True)
    for scope in ("l0/attn", "l0/ffn", "head"):
        assert scope in text, scope
