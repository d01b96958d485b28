"""The ``falcon_h1`` family (``families/``, ``references/``, one counting
file and the three readers it brings) at a tiny size on the CPU: the
harness end to end (``rehearsal_falconh1.json``), the control, the
reference's modes, the configuration's arithmetic and its published keys,
the count by hand, and the readers on a made-up run with a made-up trace
file."""

import argparse
import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import cells, measure, trace as T, weights

import run as bench_run
from test_sdar import op, trace_file

TESTS = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(TESTS, "rehearsal_falconh1.json")
CELL = "rehearse-falconh1-serve"
REAL = "serve-falconh1-34b-chat64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("ssm_ms_per_tick.serve", "ssm_scan_roofline.serve",
       "state_held_share.serve")


def cell_of():
    return cells.Cell(cells.load_json(MANIFEST), TESTS, CELL)


def real_cell():
    manifest, base, _ = cells.load_manifest(None)
    return cells.Cell(manifest, base, REAL)


def test_the_family_serves_to_correct_and_its_counters_reach_the_readers(
        capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "3000043901",
                         "--seconds", "1.5", "--trace", "1", "--manifest",
                         MANIFEST])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["compared"]["answers_of_wrong_length"] == [0.0, 0.0]
    assert line["compared"]["page_accounting_faults"] == [0.0, 0.0]
    m = line["metrics"]
    # a slot's state of 2 x (4 x 8 x 16 + 3 x 96) floats against some tens
    # of tokens of 2 x 2 x 16 x 2 floats
    assert 5.0 < m["state_held_share.serve"]["value"] < 95.0
    # no device trace on the CPU: the device's numbers are left out, and
    # no reader raises for the want of one
    for name in ("ssm_ms_per_tick.serve", "ssm_scan_roofline.serve",
                 "ragged_gqa_roofline.serve"):
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


def test_the_references_modes():
    cell = cell_of()
    fam, ref, cfg = cell.family(), cell.reference(), cell.config
    tree = weights.unflatten(weights.make(fam.leaves(cfg, "serve"), 5))
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, cfg["vocab_size"], 24), jnp.int32)
    pos = jnp.arange(24, dtype=jnp.int32)
    rows = {mode: np.asarray(fam.reference_logits(
        ref, cfg, tree, toks, pos, jnp.zeros((24,), jnp.int32), mode=mode,
        block_rows=12)[0:24]) for mode in ref.MODES}
    assert rows["f32"].shape == (24, cfg["vocab_size"])
    near = np.abs(rows["bf16"] - rows["f32"]).max()
    far = np.abs(rows["fp8"] - rows["f32"]).max()
    assert 0 < near < far
    with pytest.raises(ValueError, match="unknown mode"):
        ref.matmul(rows["f32"], rows["f32"].T, "int4")
    # a later row does not move an earlier one (causal in both branches)
    moved = toks.at[20].set((toks[20] + 1) % cfg["vocab_size"])
    again = np.asarray(fam.reference_logits(
        ref, cfg, tree, moved, pos, jnp.zeros((24,), jnp.int32), mode="f32",
        block_rows=12)[0:24])
    assert np.array_equal(again[:20], rows["f32"][:20])
    assert not np.array_equal(again[20:], rows["f32"][20:])


def test_the_configuration_keeps_every_published_key_but_the_reduced():
    cfg = real_cell().config
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert sorted(cfg["reduced"]) == ["num_hidden_layers", "vocab_size"]
    # the floors: four blocks, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == cfg["serve"]["n_layer"] == 4
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_positions"] == cfg["max_position_embeddings"]


def test_the_arithmetic_of_the_cut():
    cell = real_cell()
    fam, cfg = cell.family(), cell.config
    leaves = fam.leaves(cfg, "serve")
    size = lambda name: int(np.prod(leaves[name][0]))  # noqa: E731
    assert size("blocks.0.in_proj") == 47_349_760
    assert size("blocks.0.out_proj") == 20_971_520
    block = sum(size(n) for n in leaves if n.startswith("blocks.0."))
    assert block == 430_120_032
    assert sum(size(n) for n in leaves) == 2_054_718_848      # 8.22 GB
    prog = fam.serve_program(cfg, [None])
    assert set(prog["names"].values()) == set(leaves)
    model = prog["model"]
    assert {prog["names"][k]: v for k, v in model.param_shapes().items()} \
        == {k: v[0] for k, v in leaves.items()}
    # the published multipliers reach the model, and a slot's state is
    # what the configuration says it is
    assert model.mult["ssm_multipliers"] == tuple(cfg["ssm_multipliers"])
    assert model.mult["key_multiplier"] == cfg["key_multiplier"]
    state = model.layer_state(0)
    per_slot = sum(int(np.prod(shape)) * 4 for shape, _ in state.values())
    assert per_slot == 4_194_304 + 61_440 == 4_255_744
    dep = cfg["serve"]
    states = dep["max_slots"] * dep["n_layer"] * per_slot
    assert states == 1_089_470_464
    token = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 4 * 4
    assert (dep["pool_bytes"] - states) // (token * dep["page_size"]) == 1272
    with pytest.raises(cells.CellError, match="one chip"):
        fam.serve_program(cfg, [None, None])


def test_the_scans_count_by_hand():
    k = cells.kernel("ssd_scan")
    s = dict(heads=32, lanes=128, state=256, groups=2, chunk=128)
    assert k.state_bytes(32, 128, 256) == 4_194_304
    # 64 decoding slots: each state read and written, each row's operands
    c = k.counts(64, 0, 0, 0, **s)
    rows = 64 * (2 * 4096 + 2 * 512 + 32) * 4
    assert c == {"flops": 0.0, "bytes": 64 * 2 * 4_194_304 + rows}
    # over the cell's 4 blocks: the 2.18 GB of state a tick (the
    # convolution's carry, 31 MB of it, is another scope's)
    assert 4 * 64 * 2 * 4_194_304 == 2_147_483_648
    # chunks: one that begins writes its state, one that goes on reads it
    # too; a row's chunked products
    c = k.counts(0, 1024, 1, 1, **s)
    per_row = 4 * 4096 * 256 + 2 * 2 * 128 * 256 + 2 * 32 * 128 * 128
    assert c["flops"] == 1024 * per_row
    assert c["bytes"] == 3 * 4_194_304 + 1024 * (2 * 4096 + 2 * 512 + 32) * 4
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert k.least_seconds(64, 0, 0, 0, peaks, **s)["bound"] == "memory"
    assert k.least_seconds(0, 1024, 1, 1, {**peaks, "hbm_bytes_per_s": 1e15},
                           **s)["bound"] == "compute"


# ---- the readers, on a made-up run ------------------------------------------

# two step programs whose instructions share names
DECODE = {"fusion.1": "jit(raw)/l0/ssm/ssm.scan/ssd_step/mul",
          "fusion.2": "jit(raw)/l0/ssm/ssm.proj/dot_general",
          "fusion.3": "jit(raw)/l0/attn/dot_general"}
MIXED = {"fusion.1": "jit(raw)/l0/attn/dot_general",
         "while.2": "jit(raw)/l0/ssm/ssm.scan/ssd_chunks/while",
         "fusion.3": "jit(raw)/l0/ssm/ssm.scan/ssd_chunks/while/body/exp",
         "fusion.4": "jit(raw)/l0/ssm/ssm.conv/mul"}


def made_up_run(tmp_path):
    cell = real_cell()
    ops = [op("fusion.1", 0.1, 0.4),          # the step's scan (decode)
           op("fusion.2", 0.6, 0.2),          # the projection
           op("fusion.3", 0.9, 0.5),          # attention: not counted
           op("fusion.1", 2.1, 0.3),          # attention (mixed)
           op("while.2", 2.5, 1.0),           # the chunks' loop ...
           op("fusion.3", 2.6, 0.2),          # ... and its body, inside it
           op("fusion.4", 3.6, 0.1)]          # the convolution
    modules = [T.Op("jit_raw(1)", "jit_raw(1)", 0.0, 2.0),
               T.Op("jit_raw(2)", "jit_raw(2)", 2.0, 4.0)]
    spans = [("engine_step", 0.0, 2.0), ("engine_step", 2.0, 4.0)]
    tr = T.Trace([T.Chip(0, ops, modules)], spans, (0.0, 10.0))
    path = trace_file(tmp_path, {"jit_raw(1)": DECODE, "jit_raw(2)": MIXED})
    counters = {"ticks": 10, "step_dispatches": 10,
                "ssm_rows_decode": 10 * 4 * 50, "ssm_rows_prefill": 10 * 4 * 200,
                "ssm_segments_started": 10 * 4 // 2,
                "ssm_segments_continued": 10 * 4 // 2,
                "state_bytes_live": 3 * 10**9,
                "full_kv_tokens_held": 10**6}
    return {"kind": "serve", "chips": 1, "cell": cell, "layers_run": 4,
            "peaks": {"bf16_flops_per_s": 1e18, "hbm_bytes_per_s": 1e9},
            "ticks": [], "counters": counters, "trace": tr,
            "tracing": types.SimpleNamespace(t0=0.0, t1=4.0,
                                             file=lambda: path)}


def test_the_readers_on_a_made_up_run(tmp_path):
    run = made_up_run(tmp_path)
    read = lambda name: run["cell"].layer_metric(name).read(run)  # noqa: E731
    # under ssm: 0.4 + 0.2 in the first program, the loop's 1.0 (its body
    # lies inside it) + 0.1 in the second; over two ticks
    assert read("ssm_ms_per_tick.serve") == pytest.approx(1e3 * 1.7 / 2)
    # under ssm.scan: 0.4 + 1.0 over two ticks; an average tick and layer
    # has 50 decode rows, 200 chunk rows, half a chunk that begins and
    # half one that goes on: memory-bound at these peaks
    moved = (2 * 50 + 2 * 0.5 + 0.5) * 4_194_304 \
        + 250 * (2 * 4096 + 2 * 512 + 32) * 4
    assert read("ssm_scan_roofline.serve") == pytest.approx(
        100.0 * 4 * (moved / 1e9) * 2 / 1.4)
    # 3 GB of states beside 1e6 tokens of 4 blocks x 4 KV heads x 128 x K
    # and V x 4 B
    kv = 10**6 * 4 * 2 * 4 * 128 * 4
    assert read("state_held_share.serve") == pytest.approx(
        100.0 * 3e9 / (3e9 + kv))


def test_the_readers_find_nothing_in_a_program_without_the_family(tmp_path):
    """The parent's program under this PR's benchmark files: no such
    counters or scopes; every new reader returns None and none raises."""
    run = made_up_run(tmp_path)
    manifest, base, _ = cells.load_manifest(None)
    dense = cells.Cell(manifest, base, "serve-6.7b-tp4-chat")
    run["counters"] = {"ticks": 10, "step_dispatches": 10,
                       "decode_slots": 300}
    path = trace_file(tmp_path, {"jit_raw(1)": {"fusion.1": "jit(raw)/ffn"},
                                 "jit_raw(2)": {}}, name="parent")
    run["tracing"].file = lambda: path
    for cell in (run["cell"], dense):
        run["cell"] = cell
        assert all(cell.layer_metric(n).read(run) is None for n in NEW)
    # counters without the scopes (a trace that kept no HLO)
    run["counters"] = made_up_run(tmp_path)["counters"]
    assert dense.layer_metric("ssm_scan_roofline.serve").read(run) is None
    assert dense.layer_metric("ssm_ms_per_tick.serve").read(run) is None
    # without a trace, off the chip, or in a training run
    run["trace"] = None
    assert dense.layer_metric("ssm_scan_roofline.serve").read(run) is None
    run["peaks"] = None
    assert dense.layer_metric("ssm_scan_roofline.serve").read(run) is None
    run["kind"] = "train"
    assert all(dense.layer_metric(n).read(run) is None for n in NEW)


def test_the_manifest_lists_the_cell_where_its_readers_find_something():
    manifest, _, _ = cells.load_manifest(None)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [REAL]
    assert by_name["ragged_gqa_roofline.serve"]["workloads"][-1] == REAL
    for m in manifest["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"):
            assert m["workloads"][-1] == REAL
    cell = real_cell()
    assert cell.chips == 1 and cell.traffic["clients"] == 64
    assert cell.config["serve"]["max_slots"] == 64
    assert {m["name"] for m in cell.per_layer()} >= set(NEW)
