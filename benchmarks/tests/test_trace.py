"""The reduction from a trace to numbers: on made-up intervals with known
answers, and on one small trace recorded on a TPU v5e (``data/``)."""

import glob
import os

import pytest

from harness import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def op(name, text, start, end):
    return T.Op(name, f"{name} = {text}", start, end)


def toy():
    kernel = 'f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %a), ' \
        'custom_call_target="tpu_custom_call"'
    ops = [
        op("%fusion.1", "f32[4]{0} fusion(f32[4]{0} %p)", 1.0, 2.0),
        op("%fusion.2", "f32[4]{0} fusion(f32[4]{0} %p)", 1.5, 2.5),  # overlaps
        op("%attn.7", kernel, 3.0, 3.5),
        op("%all-reduce.3", "f32[8]{0} all-reduce(f32[8]{0} %x), "
           "replica_groups={}", 6.0, 6.25),
        op("%fusion.9", "f32[4]{0} fusion(f32[4]{0} %p)", 11.0, 12.0),  # outside
    ]
    spans = [("engine_step", 0.5, 4.0), ("submit", 4.0, 5.0),
             ("engine_step", 5.0, 7.0)]
    return T.Trace([T.Chip(0, ops, [])], spans, (0.0, 10.0))


def test_merge_and_clip():
    assert T.merge([(1, 2), (1.5, 2.5), (3, 3.5), (3.5, 3.5)]) == \
        [(1, 2.5), (3, 3.5)]
    assert T.clipped_seconds([(1, 2.5), (3, 3.5)], 2.0, 3.25) == 0.75


def test_busy_idle_and_kinds():
    tr = toy()
    # union inside the window: [1, 2.5] + [3, 3.5] + [6, 6.25]
    assert T.busy_seconds(tr) == pytest.approx(2.25)
    assert T.op_seconds(tr, T.is_kernel) == (pytest.approx(0.5), 1)
    assert T.op_seconds(tr, T.is_collective) == (pytest.approx(0.25), 1)
    top = T.top_ops(tr)
    assert top[0] == ["fusion f32[4]", pytest.approx(2.0)]
    assert ["attn f32[8,128]", pytest.approx(0.5)] in top


def test_idle_gaps_are_labelled_by_the_host_span():
    gaps = dict(T.idle_gaps(toy()))
    # idle: [0,1] mid .5 -> engine_step (starts at .5); [2.5,3] engine_step;
    # [3.5,6] mid 4.75 -> submit; [6.25,10] mid 8.1 -> between
    assert gaps["engine_step"] == pytest.approx(1.0 + 0.5)
    assert gaps["submit"] == pytest.approx(2.5)
    assert gaps["between"] == pytest.approx(3.75)
    assert sum(gaps.values()) == pytest.approx(10.0 - 2.25)


def test_split_by_span():
    rows = T.per_span(toy(), "engine_step")
    assert rows == [(pytest.approx(3.5), pytest.approx(2.0)),
                    (pytest.approx(2.0), pytest.approx(0.25))]


def test_opcode_and_label():
    o = op("%slice-done.22", "f32[128,512]{1,0:T(8,128)S(1)} async-done((("
           "f32[512,512]{1,0:T(8,128)}), f32[128,512]{1,0}) %slice-start.2)",
           0, 1)
    assert o.opcode == "async-done"
    assert o.label == "slice-done f32[128,512]"
    k = op("%jvp_blk0_attn_.1", "(bf16[1,4,1024,128]{3,2,1,0:T(8,128)(2,1)"
           "S(1)}, f32[1,4,1024,1]{3,2,1,0}) custom-call(bf16[1,4,1024,128]"
           '{3,2,1,0} %b), custom_call_target="tpu_custom_call"', 0, 1)
    assert k.opcode == "custom-call" and T.is_kernel(k)
    assert k.label == "jvp_blk0_attn_ bf16[1,4,1024,128]"


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


@pytest.mark.skipif(not RECORDED, reason="no recorded trace in data/")
def test_recorded_tpu_trace():
    """A few training steps of the tiny preset, traced on one TPU v5e by
    the harness itself (``--trace 1``): what the reduction must find."""
    tr = T.load(RECORDED[0])
    assert len(tr.chips) == 1 and tr.window_s > 0
    busy = T.busy_seconds(tr)
    assert 0 < busy < tr.window_s
    steps = T.spans_named(tr, "dispatch")
    assert len(steps) >= 2
    seconds, calls = T.op_seconds(tr, T.is_kernel)
    assert calls > 0 and 0 < seconds < busy        # the flash kernels
    assert calls % 3 == 0                          # forward, dKV, dQ
    gaps = T.idle_gaps(tr)
    assert sum(s for _, s in gaps) == pytest.approx(tr.window_s - busy,
                                                    rel=1e-6)
    assert len(T.top_ops(tr)) == 10
    assert len(tr.chips[0].modules) >= 2           # one event a program run
