"""The two readers of the ragged kernel's grid counters
(``ServingMetrics.attn_kernel_calls`` / ``attn_grid_cells`` /
``attn_live_cells``): right numbers on a record that has the counters,
``None`` and no raise on one that has not (the parent's program, the
reference path, a train run)."""

import json
import os

import pytest

from harness import cells

NAMES = ["ragged_cells_per_call.serve", "ragged_live_cell_share.serve"]


def reader(name):
    return cells.load_module(os.path.join(cells.ROOT, "layer_metrics",
                                          name + ".py")).read


def test_readers_on_a_record_with_the_counters():
    # 32 layers x 3 ticks: two decode-only ticks of 32 x 1 x 10 steps a
    # call and one with a 256-row bucket (64 x 1 x 10), 30% of them live
    calls = 32 * 3
    grid = 32 * (320 + 320 + 640)
    run = {"kind": "serve", "counters": {
        "ticks": 3, "attn_kernel_calls": calls, "attn_grid_cells": grid,
        "attn_live_cells": grid * 3 // 10}}
    assert reader(NAMES[0])(run) == pytest.approx(1280 / 3)
    assert reader(NAMES[1])(run) == pytest.approx(30.0)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("record", [
    {"kind": "serve", "counters": {"ticks": 4, "prefill_rows": 0}},
    {"kind": "serve", "counters": {}},
    # the reference path: the counters are there and stay at zero
    {"kind": "serve", "counters": {"ticks": 4, "attn_kernel_calls": 0,
                                   "attn_grid_cells": 0,
                                   "attn_live_cells": 0}},
    {"kind": "train", "counters": {"attn_kernel_calls": 2,
                                   "attn_grid_cells": 8,
                                   "attn_live_cells": 4}},
], ids=["parent", "empty", "reference_path", "train"])
def test_readers_say_none_without_the_counters(name, record):
    assert reader(name)(record) is None


def test_the_manifest_lists_both_for_the_serving_kernel():
    with open(os.path.join(cells.REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NAMES:
        m = per_layer[name]
        assert (m["source"], m["layer"], m["moves"]) == \
            ("program_counter", "serving kernel", "itl_p95_ms")
        assert m["workloads"] == per_layer["ragged_roofline.serve"][
            "workloads"]
