"""The reader of ``pool_copy_ms_per_tick.serve`` on a small synthetic
trace: a bare ``slice``, ``reshape`` and async ``copy`` are counted; a
fusion (also one named for the copy it fuses), the kernel and an
``all-reduce`` are not; outside the window nothing is; no trace, no
ticks or a train run read ``None`` and do not raise."""

import json
import os

import pytest

from harness import cells
from harness import trace as T

NAME = "pool_copy_ms_per_tick.serve"


@pytest.fixture(scope="module")
def metric():
    return cells.load_module(os.path.join(cells.ROOT, "layer_metrics",
                                          NAME + ".py"))


def op(name, text, start, end):
    return T.Op(name, f"{name} = {text}", start, end)


def toy(spans=(("engine_step", 0.0, 4.0), ("engine_step", 5.0, 9.0))):
    pool = "f32[1,128,128,8,128]{4,3,2,1,0:T(8,128)}"
    kernel = 'f32[8,64,8,128]{3,2,1,0} custom-call(f32[8,64,8,128]{3,2,1,0}' \
        ' %q), custom_call_target="tpu_custom_call"'
    ops = [
        # counted: 0.5 + 0.25 + 0.125 + 0.0625 s
        op("%slice.5", f"{pool} slice(f32[32,128,128,8,128]{{4,3,2,1,0}} "
           "%kv), slice={[3:4], [0:128], [0:128], [0:8], [0:128]}", 0.0, 0.5),
        op("%reshape.12", "f32[128,128,1024]{2,1,0:T(8,128)} reshape("
           f"{pool} %slice.5)", 0.5, 0.75),
        op("%copy-done.2", "f32[128,128,1024]{2,1,0} async-done((("
           "f32[128,128,1024]{2,1,0}), f32[128,128,1024]{2,1,0}) "
           "%copy-start.2)", 1.0, 1.125),
        op("%dynamic-slice.3", "f32[1,8]{1,0} dynamic-slice(f32[4,8]{1,0} "
           "%t, s32[] %i, s32[] %z), dynamic_slice_sizes={1,8}", 5.0, 5.0625),
        # not counted
        op("%fusion.83", "f32[32,128,128,8,128]{4,3,2,1,0} fusion("
           "f32[32,128,128,8,128]{4,3,2,1,0} %kv), kind=kLoop", 1.5, 2.0),
        op("%copy_fusion.1", "f32[288,1024]{1,0} fusion(f32[288,8,128]"
           "{2,1,0} %k), kind=kLoop", 2.0, 2.25),
        op("%ragged_paged_attention.7", kernel, 2.5, 3.5),
        op("%all-reduce.3", "f32[288,4096]{1,0} all-reduce(f32[288,4096]"
           "{1,0} %x), replica_groups={}", 6.0, 6.5),
        # a bare copy outside the window
        op("%copy.40", "f32[128,128,1024]{2,1,0} copy(f32[128,128,1024]"
           "{2,1,0} %y)", 11.0, 12.0),
    ]
    return T.Trace([T.Chip(0, ops, [])], list(spans), (0.0, 10.0))


def test_counts_bare_moves_and_nothing_else(metric):
    tr = toy()
    assert [o.name for o in tr.chips[0].ops if metric.is_move(o)] == \
        ["%slice.5", "%reshape.12", "%copy-done.2", "%dynamic-slice.3",
         "%copy.40"]
    run = {"kind": "serve", "trace": tr, "counters": {}}
    # 0.9375 s of moves inside the window over 2 ticks
    assert metric.read(run) == pytest.approx(1e3 * 0.9375 / 2)


def test_reads_zero_where_no_copy_is_left(metric):
    tr = toy()
    tr.chips[0].ops = [o for o in tr.chips[0].ops if not metric.is_move(o)]
    assert metric.read({"kind": "serve", "trace": tr, "counters": {}}) == 0.0


@pytest.mark.parametrize("run", [
    {"kind": "serve", "counters": {}},
    {"kind": "serve", "trace": None, "counters": {}},
    {"kind": "serve", "trace": T.Trace([], [], (0.0, 1.0)), "counters": {}},
    {"kind": "serve", "trace": toy(spans=()), "counters": {}},
    {"kind": "train", "trace": toy(), "counters": {}},
], ids=["untraced", "no_trace", "no_chips", "no_ticks", "train"])
def test_says_none_without_a_trace_or_its_ticks(metric, run):
    assert metric.read(run) is None


def test_the_manifest_lists_it_last_for_the_pool_layer():
    with open(os.path.join(cells.REPO, "BENCHMARK.json")) as f:
        m = json.load(f)["per_layer"][-1]
    assert m == {"name": NAME, "unit": "ms", "better": "lower",
                 "source": "device_trace", "layer": "scheduler and KV pool",
                 "moves": "itl_p95_ms", "workloads": ["serve-6.7b-tp4-chat"]}
