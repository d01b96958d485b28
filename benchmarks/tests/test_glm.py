"""The ``glm_moe_lite`` family (``families/``, ``references/``, the two
counting files and the four readers it brings) at a tiny size on the CPU:
the harness end to end (``rehearsal_glm.json``), the control, the counts by
hand, and the readers on a made-up run."""

import argparse
import json
import os
import time
import types

import pytest

from harness import cells, trace as T

import run as bench_run

TESTS = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(TESTS, "rehearsal_glm.json")
CELL = "rehearse-glm-train"


def cell_of():
    return cells.Cell(cells.load_json(MANIFEST), TESTS, CELL)


def test_the_family_trains_to_correct_and_its_counters_reach_the_readers(
        capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "2147492901",
                         "--seconds", "1", "--trace", "1", "--manifest",
                         MANIFEST])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert set(line["compared"]) >= {
        "loss_step2_rel", "first_grad_norm_worst_leaf",
        "first_grad_sketch_worst_leaf", "param_change_norm_worst_leaf"}
    # experts 2 and 3 of 8 are held, two a token: a quarter of the pairs,
    # give or take what 256 tokens a step leave to chance
    assert 15.0 < line["metrics"]["moe_held_share.train"]["value"] < 35.0
    assert 1.0 <= line["metrics"]["moe_imbalance.train"]["value"] <= 2.0
    # no device trace on the CPU: the two roofline shares are left out
    assert "moe_gmm_roofline.train" not in line["metrics"]
    assert "mla_flash_roofline.train" not in line["metrics"]


def test_frozen_leaves_stay_and_are_not_among_the_gradients():
    cell = cell_of()
    fam, drv = cell.family(), cell.driver()
    leaves = fam.leaves(cell.config, "train")
    frozen = fam.frozen(cell.config)
    assert sorted(frozen) == ["blocks.1.moe.bias", "mtp.block.moe.bias"]
    assert leaves["blocks.1.moe.experts.w_gate"][0] == (2, 32, 16)
    args = argparse.Namespace(seed=12, seconds=1.0, trace=0)
    want = drv.reference_readings(cell, args, "f32")
    # (the seeded leaf is made again to be subtracted: equal to rounding)
    assert all(want["change"][k] < 1e-7 for k in frozen)
    assert not set(frozen) & set(want["first_grad"]["norm"])
    assert min(v for k, v in want["change"].items()
               if k not in frozen) > 1e-4


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_control_is_not_correct(seed):
    cell = cell_of()
    drv = cell.driver()
    args = argparse.Namespace(seed=seed, seconds=1.0, trace=0)
    out = drv.control(cell, args, None, time.perf_counter(), None)
    assert out["correct"] is False, out["rows"]
    stated = drv.reference_readings(cell, args, "bf16")
    rows = drv.compare(stated, drv.reference_readings(cell, args, "f32"),
                       cell.limits)
    assert all(v <= lim for v, lim in rows.values()), rows


# ---- the counts, by hand -------------------------------------------------------

def test_grouped_matmul_counts_by_hand():
    k = cells.kernel("moe_grouped_matmul")
    c = k.counts(rows=100, hidden=8, width=4, held=2)
    assert len(c) == 9
    assert all(v["flops"] == 2 * 100 * 8 * 4 for v in c.values())
    # gate forward: rows in (bf16), both experts' matrices (bf16), result f32
    assert c["gate"]["bytes"] == 100 * 8 * 2 + 2 * 8 * 4 * 2 + 100 * 4 * 4
    # rows' gradient of the down product: [100, 8] x [8, 4]^T -> bf16
    assert c["down_drows"]["bytes"] == 100 * 8 * 2 + 64 * 2 + 100 * 4 * 2
    assert c["up_dmatrix"]["bytes"] == 100 * (8 + 4) * 2 + 64 * 4
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e9}
    least = k.least_seconds(100, 8, 4, 2, peaks)
    assert least["seconds"] == pytest.approx(9 * 6400 / 1e3)
    assert set(least["bound"].values()) == {"compute"}


def test_model_counts_by_hand():
    k = cells.kernel("glm_moe_lite_model")
    s = {"hidden": 8, "heads": 2, "q_rank": 4, "kv_rank": 2, "nope": 2,
         "rope": 2, "v": 4, "dense_width": 16, "expert_width": 4,
         "n_routed": 8, "held": 2, "top_k": 2, "shared": 1, "vocab": 10,
         "dense_layers": 1, "moe_layers": 2, "mtp": 1}
    attn = 8 * 4 + 4 * 2 * 4 + 8 * 4 + 2 * 2 * 6 + 2 * 4 * 8      # 184
    assert k.attention_params(s) == attn
    ffn = 3 * 8 * 4
    block = attn + 8 * 8 + ffn + (2 * 2 / 8) * ffn
    assert k.expert_block_params(s) == block
    active = (attn + 3 * 8 * 16) + 2 * block + 80 + (2 * 64 + block + 80)
    assert k.active_params(s) == active
    docs = [3, 2]
    pairs = 6 + 3
    attention = 3 * 2 * 2 * (2 + 2 + 4) * pairs * 4
    assert k.train_step_flops(docs, s) == 6 * active * 5 + attention


def test_the_cells_step_is_what_the_issue_reckoned():
    """353M matrix parameters a token and about 23.5 TFLOP a step of 2 x
    4096 tokens, from the configuration's file alone."""
    cfg = cells.load_json(os.path.join(cells.ROOT, "configs",
                                       "glm-4.7-flash.json"))
    fam = cells.load_module(os.path.join(cells.ROOT, "families",
                                         "glm_moe_lite.py"))
    k = cells.kernel("glm_moe_lite_model")
    assert k.active_params(fam.shapes(cfg)) / 1e6 == pytest.approx(352.8,
                                                                   abs=0.5)
    assert fam.train_step_flops(cfg, [4096, 4096]) / 1e12 == pytest.approx(
        23.5, abs=0.2)
    n = sum(int(__import__("numpy").prod(shape))
            for shape, _ in fam.leaves(cfg, "train").values())
    assert n / 1e6 == pytest.approx(706.5, abs=0.5)


# ---- the readers, on a made-up run ----------------------------------------------

def kernel_op(name, t0, seconds):
    text = (f"%{name} = bf16[8,8] custom-call(), "
            'custom_call_target="tpu_custom_call"')
    return T.Op(f"%{name}", text, t0, t0 + seconds)


def made_up_run():
    cell = cell_of()
    # one step of 256 tokens, two a token: 512 pairs, 128 on experts 2, 3
    counters = {"steps": 1}
    for name in ("blk1_moe", "mtp_moe"):
        counters[f"moe_rows_total{{layer={name}}}"] = 512.0
        counters[f"moe_rows_held_total{{layer={name}}}"] = 128.0
        counters[f"moe_max_expert_rows{{layer={name}}}"] = 80.0
    ops = []
    for blk in range(3):                 # remat: forward twice, dKV, dQ
        for i, n in enumerate(("flash_fwd", "flash_fwd", "flash_bwd_dkv",
                               "flash_bwd_dq")):
            ops.append(kernel_op(f"{n}.{blk}{i}", 0.1 * len(ops), 1e-3))
    for lay in range(2):                 # remat: 6 + 3 moe_gmm, 3 moe_tgmm
        for i in range(12):
            n = "moe_tgmm" if i >= 9 else "moe_gmm"
            ops.append(kernel_op(f"{n}.{lay}{i}", 0.1 * len(ops), 1e-3))
    ops.append(T.Op("%fusion.1", "%fusion.1 = f32[8] fusion()", 9.0, 9.5))
    tr = T.Trace([T.Chip(0, ops, [])], [], (0.0, 10.0))
    return {"kind": "train", "peaks": {"bf16_flops_per_s": 1e9,
                                       "hbm_bytes_per_s": 1e15},
            "chips": 1, "cell": cell, "layouts": [[64, 64, 64, 64]],
            "layers_run": 3, "counters": counters, "trace": tr,
            "tracing": types.SimpleNamespace(ended=0.0)}


def test_the_counter_readers_on_a_made_up_run():
    run = made_up_run()
    cell = run["cell"]
    assert cell.layer_metric("moe_held_share.train").read(run) == 25.0
    # fullest 80 rows against a mean of 128 / 2 held experts
    assert cell.layer_metric("moe_imbalance.train").read(run) == 1.25
    run["counters"] = {"steps": 1}
    assert cell.layer_metric("moe_held_share.train").read(run) is None
    assert cell.layer_metric("moe_imbalance.train").read(run) is None


def test_the_roofline_readers_on_a_made_up_run():
    run = made_up_run()
    cell, cfg = run["cell"], run["cell"].config
    # 24 grouped kernels of 1 ms = one step of two layers; compute-bound
    flops = 9 * 2.0 * 128 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    assert cell.layer_metric("moe_gmm_roofline.train").read(run) == \
        pytest.approx(100.0 * 2 * (flops / 1e9) / 24e-3)
    # 12 flash kernels of 1 ms = one step of three blocks of width 2 x 16
    pairs = 4 * 64 * 65 // 2
    least = 3 * (2 + 4 + 3) * 2.0 * pairs * 32 / 1e9
    assert cell.layer_metric("mla_flash_roofline.train").read(run) == \
        pytest.approx(100.0 * least / 12e-3)
    run["trace"] = T.Trace([T.Chip(0, [], [])], [], (0.0, 10.0))
    assert cell.layer_metric("moe_gmm_roofline.train").read(run) is None
    assert cell.layer_metric("mla_flash_roofline.train").read(run) is None
