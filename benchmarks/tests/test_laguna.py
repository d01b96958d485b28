"""The ``laguna`` family (``families/``, ``references/``, one counting
file, ``harness/program_ops.py`` and the seven readers it brings) at a tiny
size on the CPU: the harness end to end (``rehearsal_laguna.json``), the
control, the configuration's arithmetic and its published keys, the counts
by hand."""

import argparse
import json
import os
import time

import jax
import pytest

from harness import cells, measure

import run as bench_run

TESTS = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(TESTS, "rehearsal_laguna.json")
CELL = "rehearse-laguna-serve"
REAL = "serve-lagunaxs2-ep2-longmix"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cell_of():
    return cells.Cell(cells.load_json(MANIFEST), TESTS, CELL)


def real_cell():
    manifest, base, _ = cells.load_manifest(None)
    return cells.Cell(manifest, base, REAL)


def test_the_family_serves_to_correct_and_its_counters_reach_the_readers(
        capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "3000041901",
                         "--seconds", "1.5", "--trace", "1", "--manifest",
                         MANIFEST])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["compared"]["answers_of_wrong_length"] == [0.0, 0.0]
    assert line["compared"]["page_accounting_faults"] == [0.0, 0.0]
    m = line["metrics"]
    assert 0.0 < m["window_held_share.serve"]["value"] < 100.0
    # 8 of 16 experts held under a random router
    assert 35.0 < m["moe_held_share.serve"]["value"] < 65.0
    assert 0.0 < m["moe_live_tile_share.serve"]["value"] <= 100.0
    # no device trace on the CPU: the device's numbers are left out, and
    # no reader raises for the want of one
    for name in ("attn_full_ms_per_tick.serve",
                 "attn_window_ms_per_tick.serve",
                 "ragged_full_roofline.serve",
                 "ragged_window_roofline.serve",
                 "moe_gmm_held_roofline.serve", "moe_ms_per_tick.serve"):
        assert name not in m


@pytest.mark.parametrize("seed", [11, 12])
def test_the_control_is_not_correct(seed):
    cell = cell_of()
    args = argparse.Namespace(seed=seed, seconds=1.0, trace=0)
    out = cell.driver().control(cell, args, jax.devices()[:1],
                                time.perf_counter(), measure.CompileWatch())
    assert out["correct"] is False, out["rows"]
    lim = cell.limits
    assert all(out["program_rows"][k] <= lim[k] for k in lim)


def test_the_configuration_keeps_every_published_key_but_the_reduced():
    cfg = real_cell().config
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers"]
    # the floors: the dense layer and one whole period, 8 experts or more
    n = cfg["num_hidden_layers"]
    assert cfg["mlp_layer_types"][:n] == ["dense"] + ["sparse"] * 4
    assert cfg["layer_types"][1:n] == ["sliding_attention"] * 3 + \
        ["full_attention"]
    assert cfg["num_experts"] >= 8 and cfg["serve"]["n_layer"] == n


def test_the_arithmetic_of_the_cut():
    cell = real_cell()
    fam = cell.family()
    leaves = fam.leaves(cell.config, "serve")
    total = 0
    for shape, _kind in leaves.values():
        size = 1
        for s in shape:
            size *= s
        total += size
    assert total == 2_259_246_080                 # 9.04 GB at 4 B
    prog = fam.serve_program(cell.config, [None])
    assert set(prog["names"].values()) == set(leaves)
    shapes = prog["model"].param_shapes()
    assert {prog["names"][k]: v for k, v in shapes.items()} == \
        {k: v[0] for k, v in leaves.items()}
    # both head counts and the window reach the model as published
    assert prog["model"].layer_heads == (48, 64, 64, 64, 48)
    assert prog["model"].layer_windows == (None, 512, 512, 512, None)
    assert prog["model"].held == (0, 128)
    assert prog["model"].num_experts == 256


def test_the_kernels_counts_by_hand():
    k = cells.kernel("ragged_paged_attention_window")
    c = k.counts(1000.0, 0.0, heads=48, kv_heads=8, head_dim=128)
    assert c["bytes"] == 2 * 1000 * 8 * 128 * 4
    assert c["flops"] == 4 * 1000 * 48 * 128
    c = k.counts(0.0, 8.0, heads=64, kv_heads=8, head_dim=128)
    assert c["flops"] == 4 * 36 * 64 * 128 and c["bytes"] == 0
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert k.least_seconds(150000.0, 0.0, 48, 8, 128, peaks)["bound"] == \
        "memory"
