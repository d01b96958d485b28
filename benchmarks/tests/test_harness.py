"""The harness end to end on the CPU through the rehearsal manifest: tiny
cells run with ``correct`` true, real cells are refused off the chip, a
rehearsal may not name a real cell, files dropped in are found by name,
and a broken timed path or a lower precision comes out as not correct."""

import glob
import json
import os
import re
import shutil

import pytest

from harness import cells, trace as T

import run as bench_run

TESTS = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(TESTS, "rehearsal.json")
# the five cells' `check` rows at one seed, as the harness printed them
# before the family moved out of the drivers into families/gpt2_family.py
GOLDEN = cells.load_json(os.path.join(TESTS, "golden", "tiny-gpt.json"))


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def go(workload, capsys, seed=5, trace=0, manifest=MANIFEST, broken=None):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", "1", "--trace", str(trace),
                         "--manifest", manifest], broken=broken)
    return rc, capsys


@pytest.mark.parametrize("workload,e2e", [
    ("rehearse-train-seq", "train_tokens_per_s"),
    ("rehearse-train-packed", "train_tokens_per_s"),
    ("rehearse-serve-chat", "serve_tokens_per_s"),
    ("rehearse-serve-tp4", "serve_tokens_per_s"),
    ("rehearse-train-small", "train_tokens_per_s"),
])
def test_rehearsal_cells_run_and_are_correct(workload, e2e, capsys):
    rc, _ = go(workload, capsys, seed=GOLDEN["seed"])
    line, out = last_line(capsys)
    assert rc == 0
    printed = dict(re.findall(r"^\[bench\] check (\w+): (\S+) \(limit",
                              "\n".join(out), re.M))
    assert printed == GOLDEN["rows"][workload]
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == set(printed)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["metrics"][e2e]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert any(l.startswith("[bench] check ") and "(limit" in l for l in out)


def test_traced_rehearsal_reports_per_layer_metrics(capsys):
    rc, _ = go("rehearse-serve-chat", capsys, trace=1)
    line, _ = last_line(capsys)
    assert rc == 0 and "breakdown" in line
    assert "slot_occupancy.serve" in line["metrics"]
    assert "serve_tokens_per_s" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    # a share of a TPU's peak is never computed from a CPU run
    assert "ragged_roofline.serve" not in line["metrics"]
    # the trace keeps the program's phases beside the benchmark's spans,
    # under names that no reader of a `bench:` span selects
    tr = T.load(sorted(glob.glob(os.path.join(
        cells.REPO, ".bench_traces", "rehearse-serve-chat", "plugins",
        "profile", "*", "*.xplane.pb")))[-1])
    names = {name for name, _, _ in tr.spans}
    assert {"engine_step", "submit", "pt:tick", "pt:tick.wait"} <= names
    assert len(T.spans_named(tr, "engine_step")) == \
        len(T.spans_named(tr, "pt:tick"))


def test_a_real_cell_needs_the_chip(capsys):
    rc = bench_run.main(["--workload", "train-1.3b-seq2048", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc != 0 and '"correct"' not in out


def test_a_rehearsal_may_not_name_a_real_cell(tmp_path, capsys):
    m = cells.load_json(MANIFEST)
    m["workloads"][0]["name"] = "train-1.3b-seq2048"
    p = tmp_path / "m.json"
    p.write_text(json.dumps(m))
    rc = bench_run.main(["--workload", "train-1.3b-seq2048", "--seed", "1",
                         "--seconds", "1", "--trace", "0", "--manifest",
                         str(p)])
    assert rc != 0 and '"correct"' not in capsys.readouterr().out


@pytest.fixture
def dropped_in(tmp_path):
    """A second configuration, traffic mix and per-layer metric, added as
    files and manifest entries only."""
    base = tmp_path
    for d in ("traffic", "cells", "configs"):
        (base / d).mkdir()
    cfg = cells.load_json(os.path.join(TESTS, "configs", "tiny-gpt.json"))
    cfg.update(name="tiny-wide", n_embd=128, n_inner=512, n_head=4)
    cfg_file = os.path.join(TESTS, "configs", "tiny-wide.json")
    with open(cfg_file, "w") as f:
        json.dump(cfg, f)
    mix = cells.load_json(os.path.join(TESTS, "traffic", "tiny-seq.json"))
    mix.update(name="tiny-seq2", sequences_per_step=2, tokens_per_step=128)
    (base / "traffic" / "tiny-seq2.json").write_text(json.dumps(mix))
    shutil.copy(os.path.join(TESTS, "cells", "rehearse-train-seq.json"),
                base / "cells" / "dropped-cell.json")
    metric = os.path.join(cells.ROOT, "layer_metrics", "zz_dropped.train.py")
    with open(metric, "w") as f:
        f.write('"""steps in the window."""\n\n\ndef read(run):\n'
                '    return float(len(run["steps"]))\n')
    m = cells.load_json(MANIFEST)
    m["configs"].append({"name": "tiny-wide", "source": "none", "reduced": [],
                         "file": "benchmarks/tests/configs/tiny-wide.json",
                         "why": "test"})
    m["workloads"] = [{"name": "dropped-cell", "config": "tiny-wide",
                       "traffic": "tiny-seq2", "chips": 1, "why": "test"}]
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            e["workloads"] = ["dropped-cell"] if "train" in e["name"] else []
    m["per_layer"].append({
        "name": "zz_dropped.train", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "train_tokens_per_s", "workloads": ["dropped-cell"]})
    (base / "m.json").write_text(json.dumps(m))
    yield str(base / "m.json")
    os.remove(cfg_file)
    os.remove(metric)


def test_dropped_in_files_are_found_by_name(dropped_in, capsys):
    rc, _ = go("dropped-cell", capsys, trace=1, manifest=dropped_in)
    line, _ = last_line(capsys)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["zz_dropped.train"]["value"] > 0


# ---- the timed path broken underneath: `correct` has to come out false ----

def unchanged_state(sgd):
    """The compiled step returns its state as it got it."""
    built = sgd._build_step

    def build():
        step = built()

        def stuck(params, opt_state, mstate, key, feeds):
            loss, _, _, _, metrics = step(
                dict(params), dict(opt_state), mstate, key, feeds)
            return loss, params, opt_state, mstate, metrics
        return stuck

    sgd._build_step = build


def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys):
    import jax

    # the stuck step reads its arguments again, which donation forbids
    # where it is honoured; the CPU backend strips it
    assert jax.default_backend() == "cpu"
    rc, _ = go("rehearse-train-seq", capsys, broken=unchanged_state)
    line, out = last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert any("param_change_norm_worst_leaf" in l and "<-- over" in l
               for l in out)


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys):
    def alter(tok, req):
        return (tok + 1) % 211 if len(req["tokens"]) == 3 else tok

    rc, _ = go("rehearse-serve-chat", capsys, broken=alter)
    line, out = last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert any("served_token_gap_max" in l and "<-- over" in l for l in out)
