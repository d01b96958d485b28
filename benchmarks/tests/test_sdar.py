"""The ``sdar_moe`` family (``families/``, ``references/``, the generator
``chat_closed_blocks``, one counting file and the six readers it brings) at
a tiny size on the CPU: the harness end to end (``rehearsal_sdar.json``),
the control, the configuration's arithmetic, the counts by hand, and the
readers on a made-up run with a made-up trace file."""

import argparse
import json
import os
import time
import types

import jax
import numpy as np
import pytest

from harness import cells, measure, trace as T

import run as bench_run
from test_qwen3next import field, varint

TESTS = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(TESTS, "rehearsal_sdar.json")
CELL = "rehearse-sdar-serve"
REAL = "serve-sdar30b-blockgen256"


def cell_of():
    return cells.Cell(cells.load_json(MANIFEST), TESTS, CELL)


def real_cell():
    manifest, base, _ = cells.load_manifest(None)
    return cells.Cell(manifest, base, REAL)


def test_the_family_serves_to_correct_and_its_counters_reach_the_readers(
        capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "2147492901",
                         "--seconds", "1.5", "--trace", "1", "--manifest",
                         MANIFEST])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["compared"]["answers_of_wrong_length"] == [0.0, 0.0]
    assert line["compared"]["page_accounting_faults"] == [0.0, 0.0]
    m = line["metrics"]
    # S + 1 = 3 passes over a block of 4, less what the window's two ends
    # cut (a pass inside it whose block's other passes are not)
    assert 2.5 < m["block_rows_per_token.serve"]["value"] <= 3.2
    assert 1.2 < m["tokens_per_slot_tick.serve"]["value"] < 1.6
    assert 0.0 < m["moe_live_tile_share.serve"]["value"] <= 100.0
    # no device trace on the CPU: the device's numbers are left out
    for name in ("moe_ms_per_tick.serve", "moe_gmm_roofline.serve",
                 "ragged_gqa_roofline.serve"):
        assert name not in m


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_control_is_not_correct(seed):
    cell = cell_of()
    args = argparse.Namespace(seed=seed, seconds=1.0, trace=0)
    out = cell.driver().control(cell, args, jax.devices()[:1],
                                time.perf_counter(), measure.CompileWatch())
    assert out["correct"] is False, out["rows"]
    lim = cell.limits
    assert all(out["program_rows"][k] <= lim[k] for k in lim)


def test_a_broken_token_is_not_correct():
    """A served token that is not the best of its own state's logits (the
    next-best of a neighbouring state, say) shows in the gap."""
    cell = cell_of()
    args = argparse.Namespace(seed=5, seconds=1.0, trace=0)
    rec = cell.driver().run(cell, args, jax.devices()[:1],
                            time.perf_counter(), measure.CompileWatch(),
                            broken=lambda tok, req: (tok + 1) % 200
                            if len(req["tokens"]) == 1 else tok)
    assert rec["check"]["correct"] is False
    assert rec["check"]["rows"]["served_token_gap_max"] > \
        cell.limits["served_token_gap_max"]


# ---- the traffic ------------------------------------------------------------

def test_prompts_are_rounded_up_to_whole_blocks_and_answers_are_fixed():
    cell = real_cell()
    gen = cell.generator()
    reqs = gen.make(cell.traffic, cell.config, 3000000019)
    base = gen.BASE.make_pool(cell.traffic)
    assert (reqs.pool[:, 0] % 4 == 0).all()
    assert ((reqs.pool[:, 0] - base[:, 0]) >= 0).all() and \
        ((reqs.pool[:, 0] - base[:, 0]) < 4).all()
    assert reqs.pool[:, 0].min() >= 16 and reqs.pool[:, 0].max() <= 1024
    assert (reqs.pool[:, 1] == 256).all()
    it = iter(reqs)
    first = [next(it) for _ in range(40)]
    assert [n for _, n in first[:32]] == [8 * (i + 1) for i in range(32)]
    assert all(n == 256 for _, n in first[32:])
    assert all(len(p) % 4 == 0 and p.max() < cell.config["vocab_size"]
               for p, _ in first)
    again = iter(gen.make(cell.traffic, cell.config, 3000000019))
    assert all((next(again)[0] == p).all() for p, _ in first[:4])
    with pytest.raises(ValueError, match="multiple_of"):
        gen.make({**cell.traffic, "multiple_of": 7}, cell.config, 1)


# ---- the configuration ------------------------------------------------------

def test_the_configuration_is_the_catalogs_but_for_its_depth():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    cfg = real_cell().config
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "SDAR-30B-A3B-Chat")
    assert cfg["source"] == entry["source_url"]
    differs = [k for k, v in entry["config"].items() if cfg.get(k, "no") != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == \
        entry["config"]["num_hidden_layers"]


def test_the_cells_bytes_are_what_the_issue_reckoned():
    """A layer 623.1M parameters, embedding and head 622.3M, 4 layers and
    both 3.115e9 = 12.46 GB in float32; 16 KB of K and V a token."""
    cell = real_cell()
    cfg, leaves = cell.config, cell.family().leaves(cell.config, "serve")
    size = {k: int(np.prod(s)) for k, (s, _) in leaves.items()}
    layer = sum(v for k, v in size.items() if k.startswith("blocks.0.")
                and not k.endswith("_g"))
    assert layer == 18_874_368 + 262_144 + 128 * 4_718_592
    assert size["wte"] + size["head"] == 2 * 151_936 * 2048
    total = sum(size.values())
    assert total / 1e9 == pytest.approx(3.115, abs=0.001)
    assert 4 * total / 1e9 == pytest.approx(12.46, abs=0.01)
    dep = cfg["serve"]
    token = dep["n_layer"] * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * 4
    assert token == 16384 and dep["pool_bytes"] // token // \
        dep["page_size"] == 512
    prog = cell.family().serve_program(cfg, [None])
    assert set(prog["names"].values()) == set(leaves)
    model = prog["model"]
    assert (model.num_experts, model.experts_per_token, model.num_heads,
            model.num_kv_heads, model.head_dim, model.block_length,
            model.denoise_steps) == (128, 8, 32, 4, 128, 4, 2)


# ---- the counts, by hand ----------------------------------------------------

def test_gqa_attention_counts_by_hand():
    k = cells.kernel("ragged_paged_attention_gqa")
    # 100 live tokens, 8 prefill rows, 4 query heads over 2 KV heads of 16,
    # 3 query rows a slot
    c = k.counts(100, 8, 4, 2, 16, rows_per_slot=3)
    assert c["flops"] == 4.0 * (3 * 100 + 36) * 4 * 16
    assert c["bytes"] == 2.0 * 108 * 2 * 16 * 4
    peaks = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e3}
    least = k.least_seconds(100, 8, 4, 2, 16, peaks, rows_per_slot=3)
    assert least == {"seconds": c["bytes"] / 1e3, "bound": "memory"}
    # one query row a slot and as many KV heads as query heads: the count
    # of ``ragged_paged_attention.py`` at that hidden width
    old = cells.kernel("ragged_paged_attention").counts(100, 8, 64)
    assert k.counts(100, 8, 4, 4, 16) == old


# ---- the readers, on a made-up run ------------------------------------------

def trace_file(tmp_path, programs, name="made_up"):
    """An ``.xplane.pb`` that holds nothing but the HLO of ``programs``
    ({program as its runs are named: {instruction: op_name}}) in its
    metadata plane, as a TPU's does."""
    entries = b""
    for i, (program, ops) in enumerate(programs.items()):
        instructions = b"".join(
            field(2, field(1, ins.encode())
                  + field(7, field(2, op.encode())))
            for ins, op in ops.items())
        module = field(1, b"jit_raw") + field(3, field(1, b"main")
                                              + instructions)
        stat = varint(1 << 3) + varint(1) + field(6, field(1, module))
        meta = varint(1 << 3) + varint(i + 1) \
            + field(2, program.encode()) + field(5, stat)
        entries += field(4, varint(1 << 3) + varint(i + 1) + field(2, meta))
    planes = field(1, field(2, b"/device:TPU:0")) \
        + field(1, field(2, b"/host:metadata") + entries)
    path = tmp_path / f"{name}.xplane.pb"
    path.write_bytes(planes)
    return str(path)


def op(name, t0, seconds, kernel=False):
    text = f"%{name} = f32[8,8] fusion()"
    if kernel:
        text = (f"%{name} = f32[8,8] custom-call(), "
                'custom_call_target="tpu_custom_call"')
    return T.Op(f"%{name}", text, t0, t0 + seconds)


# two step programs whose instructions share names: ``fusion.1`` is the
# router in one and the head in the other
DECODE = {"fusion.1": "jit(raw)/l0/ffn/moe.route/top_k",
          "moe_gmm.2": "jit(raw)/l0/ffn/moe.experts/pallas_call",
          "fusion.3": "jit(raw)/l0/ffn/moe.experts/mul",
          "fusion.4": "jit(raw)/head/dot_general"}
MIXED = {"fusion.1": "jit(raw)/head/dot_general",
         "moe_gmm.2": "jit(raw)/l0/ffn/moe.experts/pallas_call",
         "fusion.4": "jit(raw)/l0/ffn/moe.route/top_k"}


def made_up_run(tmp_path):
    cell = real_cell()
    ops = [op("fusion.1", 0.1, 0.2),                      # route (decode)
           op("moe_gmm.2", 0.4, 1.0, kernel=True),        # experts
           op("fusion.3", 1.5, 0.1),                      # experts
           op("fusion.4", 1.7, 0.2),                      # head: not counted
           op("ragged_paged_attention.5", 1.9, 0.05, kernel=True),
           op("fusion.1", 2.1, 0.3),                      # head (mixed)
           op("moe_gmm.2", 2.5, 0.5, kernel=True),        # experts
           op("fusion.4", 3.1, 0.4)]                      # route (mixed)
    modules = [T.Op("jit_raw(1)", "jit_raw(1)", 0.0, 2.0),
               T.Op("jit_raw(2)", "jit_raw(2)", 2.0, 4.0)]
    spans = [("engine_step", 0.0, 2.0), ("engine_step", 2.0, 4.0)]
    tr = T.Trace([T.Chip(0, ops, modules)], spans, (0.0, 10.0))
    path = trace_file(tmp_path, {"jit_raw(1)": DECODE, "jit_raw(2)": MIXED})
    ticks = [{"t0": 0.0, "t1": 2.0, "live_kv_tokens": 1000,
              "prefill_rows": 0},
             {"t0": 2.0, "t1": 4.0, "live_kv_tokens": 3000,
              "prefill_rows": 0}]
    counters = {"ticks": 10, "step_dispatches": 10, "decode_slots": 300,
                "block_rows": 1200, "tokens_fixed": 400,
                "moe_rows_total": 10 * 4 * 1024,
                "moe_live_experts": 10 * 4 * 100,
                "moe_live_tiles": 10 * 4 * 110,
                "moe_grid_tiles": 10 * 4 * 160}
    return {"kind": "serve", "chips": 1, "cell": cell, "layers_run": 4,
            "peaks": {"bf16_flops_per_s": 1e18, "hbm_bytes_per_s": 1e9},
            "ticks": ticks, "counters": counters, "trace": tr,
            "tracing": types.SimpleNamespace(t0=0.0, t1=4.0,
                                             file=lambda: path)}


def test_the_readers_on_a_made_up_run(tmp_path):
    run = made_up_run(tmp_path)
    cell, cfg = run["cell"], run["cell"].config
    read = lambda name: cell.layer_metric(name).read(run)  # noqa: E731
    # route 0.2 + 0.4, experts 1.0 + 0.1 + 0.5, each looked up in the
    # program it ran in, over two ticks
    assert read("moe_ms_per_tick.serve") == pytest.approx(1e3 * 2.2 / 2)
    # two moe_gmm calls of 1.5 s together stand for 2/3 of a layer and
    # tick; a layer's least: 100 experts' three matrices and 1024 rows'
    # operands, memory-bound at these peaks
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    least = (3 * 100 * e * f + 1024 * (3 * e + 3 * f)) * 4 / 1e9
    assert read("moe_gmm_roofline.serve") == pytest.approx(
        100.0 * least * (2 / 3) / 1.5)
    assert read("moe_live_tile_share.serve") == pytest.approx(100 * 110 / 160)
    assert read("block_rows_per_token.serve") == 3.0
    assert read("tokens_per_slot_tick.serve") == pytest.approx(4 / 3)
    # one attention call of 50 ms; a layer and tick reads 2000 live tokens
    # (the two ticks' mean) of K and V at 4 x 128 lanes
    assert read("ragged_gqa_roofline.serve") == pytest.approx(
        100.0 * (2.0 * 2000 * 512 * 4 / 1e9) / 0.05)


def test_the_readers_find_nothing_in_a_program_without_the_family(tmp_path):
    """The parent's program under this PR's benchmark files: no such
    counters, scopes or kernels, and a dense configuration; every new
    reader returns None and none raises."""
    run = made_up_run(tmp_path)
    manifest, base, _ = cells.load_manifest(None)
    dense = cells.Cell(manifest, base, "serve-6.7b-tp4-chat")
    run["counters"] = {"ticks": 10, "step_dispatches": 10,
                       "decode_slots": 300}
    run["trace"] = T.Trace(
        [T.Chip(0, [op("fusion.1", 0.1, 0.2),
                    op("ragged_paged_attention.5", 1.9, 0.05, kernel=True)],
                [T.Op("jit_raw(1)", "jit_raw(1)", 0.0, 2.0)])],
        [("engine_step", 0.0, 2.0)], (0.0, 10.0))
    path = trace_file(tmp_path, {"jit_raw(1)": {"fusion.1": "jit(raw)/ffn"}},
                      name="parent")
    run["tracing"].file = lambda: path
    new = ("moe_ms_per_tick.serve", "moe_gmm_roofline.serve",
           "moe_live_tile_share.serve", "block_rows_per_token.serve",
           "tokens_per_slot_tick.serve")
    for name in new:
        assert run["cell"].layer_metric(name).read(run) is None
    run["cell"] = dense
    for name in new + ("ragged_gqa_roofline.serve",):
        assert dense.layer_metric(name).read(run) is None
    # without a trace, or in a training run
    run["trace"] = None
    assert all(dense.layer_metric(n).read(run) is None for n in new)
    run["kind"] = "train"
    assert all(dense.layer_metric(n).read(run) is None for n in new)


def test_the_manifest_lists_the_cell_where_its_readers_find_something():
    manifest, _, _ = cells.load_manifest(None)
    listed = {m["name"] for m in manifest["per_layer"]
              if REAL in m.get("workloads", [])}
    assert {"moe_ms_per_tick.serve", "moe_gmm_roofline.serve",
            "moe_live_tile_share.serve", "ragged_gqa_roofline.serve",
            "block_rows_per_token.serve", "tokens_per_slot_tick.serve",
            "device_idle.serve", "readback_ms_per_tick.serve"} <= listed
    # its K/V width is not n_embd and it has no second chip
    assert not {"ragged_roofline.serve", "collective_ms_per_tick.serve"} \
        & listed
    cell = real_cell()
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"}
    entry = cell.entry
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
