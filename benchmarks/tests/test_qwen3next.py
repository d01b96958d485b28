"""The ``qwen3_next`` family (``families/``, ``references/``, the two
counting files, ``harness/op_scopes.py`` and the three readers it brings)
at a tiny size on the CPU: the harness end to end
(``rehearsal_qwen3next.json``), the control, the counts by hand, and the
readers on a made-up run with a made-up trace file."""

import argparse
import json
import os
import time
import types

import pytest

from harness import cells, op_scopes, trace as T

import run as bench_run

TESTS = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(TESTS, "rehearsal_qwen3next.json")
CELL = "rehearse-qwen3next-train"


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
    # experts 4-7 of 16 are held, three a token: a quarter of the pairs,
    # give or take what 256 tokens a step leave to chance
    assert 15.0 < line["metrics"]["moe_held_share.train"]["value"] < 35.0
    assert 1.0 <= line["metrics"]["moe_imbalance.train"]["value"] <= 2.0
    # no device trace on the CPU: the device's numbers are left out
    for name in ("moe_gmm_roofline.train", "gdn_scan_roofline.train",
                 "gdn_ms_per_step.train", "gqa_flash_roofline.train"):
        assert name not in line["metrics"]


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

def test_delta_rule_counts_by_hand():
    k = cells.kernel("gated_delta_rule")
    # one key head, two value heads, dk 4, dv 2, chunk 8
    per_chunk = 1 * 2 * (2 * 64 * 4) \
        + 2 * (2 * 512 / 3 + 2 * 64 * (4 + 2 * 2) + 6 * 8 * 4 * 2)
    assert k.chunk_flops(1, 2, 4, 2, chunk=8) == pytest.approx(per_chunk)
    # documents of 8 and 9 tokens: 1 + 2 chunks
    c = k.counts([8, 9], 1, 2, 4, 2, chunk=8)
    assert c["forward"]["flops"] == pytest.approx(3 * per_chunk)
    assert c["backward"]["flops"] == pytest.approx(6 * per_chunk)
    inputs = 17 * ((2 * 4 + 2 * 2) * 2 + 2 * 2 * 4)
    assert c["forward"]["bytes"] == inputs + 17 * 2 * 2 * 2
    assert c["backward"]["bytes"] == 2 * inputs + 17 * 2 * 2 * 2
    # with as many key heads as value heads: the issue's formula and the
    # solve
    c, dk, dv = 64.0, 128, 128
    assert k.chunk_flops(1, 1, dk, dv) == pytest.approx(
        2 * c * c * (3 * dk + 2 * dv) + 6 * c * dk * dv + 2 * c ** 3 / 3)
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e15}
    least = k.least_seconds([64], 1, 1, dk, dv, peaks)
    assert least["seconds"] == pytest.approx(
        3 * k.chunk_flops(1, 1, dk, dv) / 1e3)
    assert set(least["bound"].values()) == {"compute"}


def test_model_counts_by_hand():
    k = cells.kernel("qwen3_next_model")
    s = {"hidden": 8, "heads": 4, "kv_heads": 2, "head_dim": 4, "k_heads": 1,
         "v_heads": 2, "dk": 4, "dv": 2, "taps": 4, "expert_width": 4,
         "shared_width": 6, "n_routed": 16, "held": 4, "top_k": 2,
         "vocab": 10, "layers": 4, "interval": 4}
    assert k.block_kinds(s) == (3, 1)
    delta = 8 * (2 * 4 + 2 * 4 + 2 * 2) + 4 * 8
    assert k.delta_params(s) == delta
    attn = 8 * (2 * 16 + 2 * 8) + 16 * 8
    assert k.attention_params(s) == attn
    moe = 8 * 16 + 3 * 8 * 6 + 8 + (2 * 4 / 16) * 3 * 8 * 4
    assert k.expert_layer_params(s) == moe
    active = 3 * delta + attn + 4 * moe + 80
    assert k.active_params(s) == active
    docs = [3, 2]
    attention = 3 * (2.0 * 4 * 2 * 4) * (6 + 3) * 1
    scan = cells.kernel("gated_delta_rule").counts(docs, 1, 2, 4, 2)
    assert k.train_step_flops(docs, s) == pytest.approx(
        6 * active * 5 + attention
        + 3 * (scan["forward"]["flops"] + scan["backward"]["flops"]))


def test_the_cells_step_is_what_the_issue_reckoned():
    """625.7M parameters held; a delta-rule layer's projections 67.4M
    operations a token forward, an attention layer's 54.5M, an expert
    layer 12.3M, the scan 5.3M (the issue's 5.8M counts K K^T and Q K^T
    once a value head; they are made once a key head); about 11.3 TFLOP a
    step of 8192 tokens."""
    cfg = cells.load_json(os.path.join(cells.ROOT, "configs",
                                       "qwen3-next-80b-a3b.json"))
    fam = cells.load_module(os.path.join(cells.ROOT, "families",
                                         "qwen3_next.py"))
    k, s = cells.kernel("qwen3_next_model"), fam.shapes(cfg)
    assert 2 * k.delta_params(s) / 1e6 == pytest.approx(67.4, abs=0.1)
    assert 2 * k.attention_params(s) / 1e6 == pytest.approx(54.5, abs=0.1)
    assert 2 * k.expert_layer_params(s) / 1e6 == pytest.approx(12.3, abs=0.1)
    scan = cells.kernel("gated_delta_rule").counts([8192], 16, 32, 128, 128)
    assert scan["forward"]["flops"] / 8192 / 1e6 == pytest.approx(5.3,
                                                                  abs=0.1)
    assert fam.train_step_flops(cfg, [8192]) / 1e12 == pytest.approx(
        11.3, abs=0.2)
    n = sum(int(__import__("numpy").prod(shape))
            for shape, _ in fam.leaves(cfg, "train").values())
    assert n / 1e6 == pytest.approx(625.7, abs=0.5)


# ---- scopes from a trace file ---------------------------------------------------

def varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def field(number, payload):
    """One length-delimited field of a protobuf message."""
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def trace_file(tmp_path, programs, name="made_up"):
    """An ``.xplane.pb`` that holds nothing but the HLO of ``programs``
    ([{instruction: op_name}]) in its metadata plane, as a TPU's does."""
    entries = b""
    for i, ops in enumerate(programs):
        instructions = b"".join(
            field(2, field(1, name.encode())
                  + field(7, field(2, op.encode())))
            for name, op in ops.items())
        module = field(1, b"jit_step") + field(3, field(1, b"main")
                                               + instructions)
        stat = varint(1 << 3) + varint(1) + field(6, field(1, module))
        meta = varint(1 << 3) + varint(i + 1) + field(5, stat)
        entries += field(4, varint(1 << 3) + varint(i + 1) + field(2, meta))
    planes = field(1, field(2, b"/device:TPU:0")) \
        + field(1, field(2, b"/host:metadata") + entries)
    path = tmp_path / f"{name}.xplane.pb"
    path.write_bytes(planes)
    return str(path)


STEP = {"fusion.1": "jit(step)/jvp(remat_blk0_mix)/blk0_gdn/gdn/gdn.proj/dot",
        "while.2": "jit(step)/jvp(remat_blk0_mix)/blk0_gdn/gdn/gdn.scan/"
                   "while",
        "fusion.3": "jit(step)/transpose(jvp(remat_blk0_mix))/checkpoint/"
                    "rematted_computation/blk0_gdn/gdn/gdn.scan/while/body/"
                    "dot_general",
        "fusion.4": "jit(step)/jvp(remat_blk0_moe)/blk0_moe/moe.route/top_k",
        "fusion.5": "jit(step)/jvp(remat_blk3_mix)/blk3_attn/gattn/mul"}


def test_scopes_are_read_from_the_largest_program_of_a_trace_file(tmp_path):
    path = trace_file(tmp_path, [{"fusion.1": "jit(stack)/concatenate"},
                                 STEP])
    names = op_scopes.op_names(path)
    assert names == {"%" + k: v for k, v in STEP.items()}
    scan, whole = op_scopes.under("gdn.scan"), op_scopes.under("gdn")
    assert [k for k, v in names.items() if scan(v)] == ["%while.2",
                                                        "%fusion.3"]
    assert [k for k, v in names.items() if whole(v)] == [
        "%fusion.1", "%while.2", "%fusion.3"]
    assert not whole("jit(step)/blk0_gdn/mul")       # the node's name
    assert op_scopes.under("moe.route")(names["%fusion.4"])
    empty = tmp_path / "no_hlo.xplane.pb"
    empty.write_bytes(field(1, field(2, b"/device:TPU:0")))
    assert op_scopes.op_names(str(empty)) == {}


# ---- the readers, on a made-up run ----------------------------------------------

def op(name, t0, seconds, kernel=False):
    text = f"%{name} = f32[8,8] fusion()"
    if kernel:
        text = (f"%{name} = bf16[8,8] custom-call(), "
                'custom_call_target="tpu_custom_call"')
    return T.Op(f"%{name}", text, t0, t0 + seconds)


def made_up_run(tmp_path):
    cell = cell_of()
    ops = [op("fusion.1", 0.0, 0.5),                    # gdn.proj
           op("while.2", 1.0, 2.0),                     # gdn.scan, 1..3
           op("fusion.3", 1.5, 1.0),                    # its body, inside
           op("fusion.3", 3.5, 0.5),                    # gdn.scan again
           op("fusion.4", 5.0, 1.0),                    # moe.route
           op("fusion.5", 6.0, 1.0)]                    # gattn
    # one step, one attention block, remat: forward twice, dKV, dQ
    for i, n in enumerate(("flash_fwd", "flash_fwd", "flash_bwd_dkv",
                           "flash_bwd_dq")):
        ops.append(op(f"{n}.{i}", 7.0 + 0.1 * i, 1e-3, kernel=True))
    spans = [("dispatch", 0.1, 0.2), ("dispatch", 4.0, 4.1)]
    tr = T.Trace([T.Chip(0, ops, [])], spans, (0.0, 10.0))
    path = trace_file(tmp_path, [STEP])
    return {"kind": "train", "peaks": {"bf16_flops_per_s": 1e9,
                                       "hbm_bytes_per_s": 1e15},
            "chips": 1, "cell": cell, "layouts": [[64, 64, 64, 64]],
            "layers_run": 4, "counters": {"steps": 2}, "trace": tr,
            "tracing": types.SimpleNamespace(ended=0.0, file=lambda: path)}


def test_the_scope_readers_on_a_made_up_run(tmp_path):
    run = made_up_run(tmp_path)
    cell, cfg = run["cell"], run["cell"].config
    # under gdn: 0.5 + the while's 2.0 (its body lies inside it) + 0.5,
    # over two dispatched steps
    assert cell.layer_metric("gdn_ms_per_step.train").read(run) == \
        pytest.approx(1e3 * 3.0 / 2)
    # under gdn.scan: 2.5 s in two steps; three delta-rule layers of four
    # documents of 64 tokens, compute-bound at these peaks
    scan = cells.kernel("gated_delta_rule").counts(
        [64] * 4, cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])
    least = 3 * (scan["forward"]["flops"] + scan["backward"]["flops"]) / 1e9
    assert cell.layer_metric("gdn_scan_roofline.train").read(run) == \
        pytest.approx(100.0 * least * 2 / 2.5)
    # 4 flash kernels of 1 ms = one step of one block of width 4 x 16
    pairs = 4 * 64 * 65 // 2
    least = (2 + 4 + 3) * 2.0 * pairs * 64 / 1e9
    assert cell.layer_metric("gqa_flash_roofline.train").read(run) == \
        pytest.approx(100.0 * least / 4e-3)
    # a program without the scopes (the parent's): nothing, and no raise
    run["tracing"].file = lambda: trace_file(
        tmp_path, [{"fusion.1": "jit(step)/mla/dot"}], name="parent")
    run["trace"] = T.Trace([T.Chip(0, [op("fusion.1", 0.0, 0.5)], [])],
                           [("dispatch", 0.1, 0.2)], (0.0, 10.0))
    for name in ("gdn_ms_per_step.train", "gdn_scan_roofline.train",
                 "gqa_flash_roofline.train"):
        assert cell.layer_metric(name).read(run) is None
