"""The ``ouro`` family (``families/``, ``references/``, one counting file
and the four readers it brings) at a tiny size on the CPU: the harness end
to end (``rehearsal_ouro.json``), the control, the reference's modes and
passes, the configuration's arithmetic and its published keys, the count
by hand, and the readers on a made-up run with a made-up trace file."""

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
MANIFEST = os.path.join(TESTS, "rehearsal_ouro.json")
CELL = "rehearse-ouro-serve"
REAL = "serve-ouro2.6b-reason32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("loop_attn_ms_per_tick.serve", "loop_dense_ms_per_tick.serve",
       "loop_weights_roofline.serve", "expected_exit_step.serve")


def cell_of():
    return cells.Cell(cells.load_json(MANIFEST), TESTS, CELL)


def real_cell():
    manifest, base, _ = cells.load_manifest(None)
    return cells.Cell(manifest, base, REAL)


def test_the_family_serves_to_correct_and_its_counters_reach_the_readers(
        capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "3000047901",
                         "--seconds", "1.5", "--trace", "1", "--manifest",
                         MANIFEST])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert line["compared"]["answers_of_wrong_length"] == [0.0, 0.0]
    assert line["compared"]["page_accounting_faults"] == [0.0, 0.0]
    m = line["metrics"]
    # the gate under seeded weights: between the first pass and the last
    assert 1.0 < m["expected_exit_step.serve"]["value"] < 3.0
    # no device trace on the CPU: the device's numbers are left out, and
    # no reader raises for the want of one
    for name in NEW[:3] + ("ragged_gqa_roofline.serve",):
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


def test_the_references_modes_and_passes():
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
    # a later row does not move an earlier one (causal in every pass)
    moved = toks.at[20].set((toks[20] + 1) % cfg["vocab_size"])
    again = np.asarray(fam.reference_logits(
        ref, cfg, tree, moved, pos, jnp.zeros((24,), jnp.int32), mode="f32",
        block_rows=12)[0:24])
    assert np.array_equal(again[:20], rows["f32"][:20])
    assert not np.array_equal(again[20:], rows["f32"][20:])
    # every pass's logits and the exit distribution beside the last's
    out = ref.forward(tree, toks, pos, mode="f32", block_rows=12,
                      **fam.arch(cfg))
    steps = cfg["total_ut_steps"]
    assert len(out["pass_logits"]) == steps
    assert np.array_equal(np.asarray(out["logits"][0:24]), rows["f32"])
    assert np.array_equal(np.asarray(out["pass_logits"][-1][0:24]),
                          rows["f32"])
    assert not np.allclose(np.asarray(out["pass_logits"][-2][0:24]),
                           rows["f32"], atol=1e-3)
    p = np.asarray(out["exit_p"])
    assert p.shape == (steps, 24) and np.all(p > 0)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    assert np.all(np.asarray(ref.exit_step(out["exit_p"], 1.0)) == steps)
    assert np.all(np.asarray(ref.exit_step(out["exit_p"], 0.0)) == 1)


def test_the_configuration_keeps_every_published_key_but_the_reduced():
    cfg = real_cell().config
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    # the floor: four layers behind no leading dense one; all four passes
    assert cfg["num_hidden_layers"] == 8 and cfg["total_ut_steps"] == 4
    assert cfg["n_positions"] == cfg["max_position_embeddings"]
    assert "train" not in cfg


def test_the_arithmetic_of_the_cut():
    cell = real_cell()
    fam, cfg = cell.family(), cell.config
    leaves = fam.leaves(cfg, "serve")
    size = lambda name: int(np.prod(leaves[name][0]))  # noqa: E731
    layer = sum(size(n) for n in leaves if n.startswith("blocks.0."))
    assert layer == 51_388_416
    assert sum(size(n) for n in leaves) == 612_438_017         # 2.45 GB
    assert 48 * layer + 2 * 49_152 * 2048 + 4_097 == 2_667_974_657
    prog = fam.serve_program(cfg, [None])
    # each leaf ONCE whatever the number of passes
    assert set(prog["names"].values()) == set(leaves)
    assert len(prog["names"]) == len(leaves) == 5 + 11 * 8
    model = prog["model"]
    assert {prog["names"][k]: v for k, v in model.param_shapes().items()} \
        == {k: v[0] for k, v in leaves.items()}
    assert model.loops == 4 and model.num_layers == 8 == prog["layers"]
    dep = cfg["serve"]
    token = model.loops * model.num_layers * 2 * 16 * 128 * 4
    assert token == 512 * 1024
    assert dep["pool_bytes"] // (token * dep["page_size"]) == 160
    t = cell.traffic
    longest = t["prompt"]["max"] + t["answer"]["max"]
    assert -(-longest // dep["page_size"]) == 10
    with pytest.raises(cells.CellError, match="one chip"):
        fam.serve_program(cfg, [None, None])
    with pytest.raises(cells.CellError, match="every token runs every pass"):
        fam.serve_program({**cfg, "early_exit_threshold": 0.9}, [None])


def test_the_parameter_bytes_count_by_hand():
    k = cells.kernel("looped_weights")
    cfg = real_cell().config
    sizes = {key: cfg[key] for key in k.KEYS}
    assert k.layer_bytes(2048, 16, 128, 5632) == 205_520_896
    c = k.counts(**sizes)
    assert c["passes_bytes"] == 4 * 8 * 205_520_896 == 6_576_668_672
    assert c["head_bytes"] == 2048 * 49_152 * 4 == 402_653_184
    assert c["bytes"] == 6_979_321_856
    least = k.least_seconds({"hbm_bytes_per_s": 819e9}, **sizes)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(8.52e-3, rel=1e-3)
    # one pass: a plain decoder's tick
    assert k.counts(**{**sizes, "total_ut_steps": 1})["bytes"] == \
        8 * 205_520_896 + 402_653_184


# ---- the readers, on a made-up run ------------------------------------------

# two step programs whose instructions share names
DECODE = {"fusion.1": "jit(raw)/pass0/l0/attn/proj/dot_general",
          "ragged_paged_attention.2":
              "jit(raw)/pass0/l0/attn/jit(_ragged_call)/pallas_call",
          "fusion.3": "jit(raw)/pass1/l0/ffn/dot_general",
          "fusion.4": "jit(raw)/pass1/close/mul",
          "fusion.5": "jit(raw)/head/dot_general"}
MIXED = {"fusion.1": "jit(raw)/head/dot_general",
         "ragged_paged_attention.2":
             "jit(raw)/pass1/l0/attn/jit(_ragged_call)/pallas_call",
         "fusion.3": "jit(raw)/pass0/l0/attn/scatter",
         "fusion.4": "jit(raw)/pass0/l0/attn/proj/dot_general",
         "fusion.5": "jit(raw)/embed/gather"}


def made_up_run(tmp_path):
    cell = real_cell()
    ops = [op("fusion.1", 0.1, 0.2),                       # q, k, v
           op("ragged_paged_attention.2", 0.4, 0.3, kernel=True),
           op("fusion.3", 0.8, 0.4),                       # SwiGLU
           op("fusion.4", 1.3, 0.1),                       # the final norm
           op("fusion.5", 1.5, 0.2),                       # head
           op("fusion.1", 2.1, 0.3),                       # head (mixed)
           op("ragged_paged_attention.2", 2.5, 0.5, kernel=True),
           op("fusion.3", 3.1, 0.1),                       # the K/V scatter
           op("fusion.4", 3.3, 0.4),                       # q, k, v (mixed)
           op("fusion.5", 3.8, 0.1),                       # embedding: nobody's
           # a matrix brought on chip beside the kernel, 0.45 .. 0.75, and
           # a copy of the pool, which is nobody's parameter
           T.Op("%slice-start.7", "%slice-start.7 = ((f32[8,8]), f32[4,8], "
                "s32[]) async-start(f32[8,8] %params__l0_wv__.1), "
                "calls=%async_computation.7", 0.45, 0.46),
           T.Op("%slice-done.7", "%slice-done.7 = f32[4,8] async-done(("
                "(f32[8,8]), f32[4,8], s32[]) %slice-start.7)", 0.74, 0.75),
           T.Op("%copy-start.2", "%copy-start.2 = (f32[8,8], f32[8,8], "
                "u32[]) copy-start(f32[8,8] %kv_k.1)", 3.0, 3.01),
           T.Op("%copy-done.2", "%copy-done.2 = f32[8,8] copy-done((f32[8,8],"
                " f32[8,8], u32[]) %copy-start.2)", 3.95, 3.99)]
    modules = [T.Op("jit_raw(1)", "jit_raw(1)", 0.0, 2.0),
               T.Op("jit_raw(2)", "jit_raw(2)", 2.0, 4.0)]
    spans = [("engine_step", 0.0, 2.0), ("engine_step", 2.0, 4.0)]
    tr = T.Trace([T.Chip(0, ops, modules)], spans, (0.0, 10.0))
    path = trace_file(tmp_path, {"jit_raw(1)": DECODE, "jit_raw(2)": MIXED})
    counters = {"ticks": 10, "step_dispatches": 10, "loop_passes": 40,
                "exit_rows": 300, "exit_step_milli": 300 * 2250}
    return {"kind": "serve", "chips": 1, "cell": cell, "layers_run": 8,
            "peaks": {"bf16_flops_per_s": 1e18, "hbm_bytes_per_s": 1e10},
            "ticks": [], "counters": counters, "trace": tr,
            "tracing": types.SimpleNamespace(t0=0.0, t1=4.0,
                                             file=lambda: path)}


def test_the_readers_on_a_made_up_run(tmp_path):
    run = made_up_run(tmp_path)
    read = lambda name: run["cell"].layer_metric(name).read(run)  # noqa: E731
    # under attn and not under proj: the kernel's 0.3 in the first
    # program, its 0.5 and the scatter's 0.1 in the second; two ticks
    assert read("loop_attn_ms_per_tick.serve") == pytest.approx(
        1e3 * 0.9 / 2)
    # proj 0.2, ffn 0.4, close 0.1; proj 0.4
    assert read("loop_dense_ms_per_tick.serve") == pytest.approx(
        1e3 * 1.1 / 2)
    # those, the head's 0.2 and 0.3 and the prefetch of a parameter from
    # 0.45 to 0.75 (beside the kernel, between two products: + 0.3),
    # against 6,979,321,856 B a tick; the copy of the pool is not a
    # parameter's
    assert read("loop_weights_roofline.serve") == pytest.approx(
        100.0 * (6_979_321_856 / 1e10) * 2 / 1.9)
    assert read("expected_exit_step.serve") == pytest.approx(2.25)


def test_the_readers_find_nothing_in_a_program_without_passes(tmp_path):
    """The parent's program under this PR's benchmark files: no such
    counters or scopes; every new reader returns None and none raises."""
    run = made_up_run(tmp_path)
    manifest, base, _ = cells.load_manifest(None)
    dense = cells.Cell(manifest, base, "serve-falconh1-34b-chat64")
    run["counters"] = {"ticks": 10, "step_dispatches": 10,
                       "decode_slots": 300}
    path = trace_file(tmp_path, {
        "jit_raw(1)": {"fusion.1": "jit(raw)/l0/attn/dot_general",
                       "fusion.3": "jit(raw)/l0/ffn/dot_general"},
        "jit_raw(2)": {}}, name="parent")
    run["tracing"].file = lambda: path
    for cell in (run["cell"], dense):
        run["cell"] = cell
        assert all(cell.layer_metric(n).read(run) is None for n in NEW)
    # counters without the scopes (a trace that kept no HLO)
    run["counters"] = made_up_run(tmp_path)["counters"]
    for name in NEW[:3]:
        assert run["cell"].layer_metric(name).read(run) is None
    # without a trace, off the chip, or in a training run
    run["trace"] = None
    assert all(dense.layer_metric(n).read(run) is None for n in NEW[:3])
    run["peaks"] = None
    assert dense.layer_metric("loop_weights_roofline.serve").read(run) is None
    run["kind"] = "train"
    assert all(dense.layer_metric(n).read(run) is None for n in NEW)


def test_the_manifest_lists_the_cell_where_its_readers_find_something():
    manifest, _, _ = cells.load_manifest(None)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [REAL]
        assert by_name[name]["layer"] == "looped stack"
        assert by_name[name]["moves"] == "itl_p95_ms"
    assert REAL in by_name["ragged_gqa_roofline.serve"]["workloads"]
    assert REAL not in by_name["ragged_roofline.serve"]["workloads"]
    for m in manifest["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"):
            assert REAL in m["workloads"]
    cell = real_cell()
    assert cell.chips == 1 and cell.traffic["clients"] == 32
    assert cell.config["serve"]["max_slots"] == 32
    assert cell.traffic["generator"] == "chat_closed"
    assert cell.traffic["prompt"] == {"median": 128, "sigma": 0.8,
                                      "min": 32, "max": 512}
    assert cell.traffic["answer"] == {"median": 256, "sigma": 0.6,
                                      "min": 64, "max": 768}
    assert {m["name"] for m in cell.per_layer()} >= set(NEW)
