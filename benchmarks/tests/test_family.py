"""A model's family is one file, found by the configuration's ``family``:
the family that is there reads as it did on the parent (seeded leaves bit
for bit), and a second one arrives as files alone (``families/``,
``references/``, ``layer_metrics/`` under this directory and
``rehearsal_family.json``) and runs to ``correct`` through both drivers."""

import hashlib
import json
import os
import types

import numpy as np
import pytest

from harness import cells, weights

import run as bench_run

TESTS = os.path.dirname(os.path.abspath(__file__))
GOLDEN = cells.load_json(os.path.join(TESTS, "golden", "tiny-gpt.json"))
FAMILY = os.path.join(TESTS, "rehearsal_family.json")


def cell_of(name, manifest):
    return cells.Cell(cells.load_json(manifest), TESTS, name)


@pytest.mark.parametrize("group,workload", [
    ("train", "rehearse-train-seq"), ("serve", "rehearse-serve-chat")])
def test_seeded_leaves_are_the_parents_bit_for_bit(group, workload):
    """Digests recorded from the harness before the family moved out of
    the drivers: the same names, shapes and values."""
    cell = cell_of(workload, os.path.join(TESTS, "rehearsal.json"))
    made = weights.make(cell.family().leaves(cell.config, group),
                        GOLDEN["seed"])
    got = {k: [list(v.shape),
               hashlib.sha256(np.asarray(v).tobytes()).hexdigest()]
           for k, v in made.items()}
    assert got == GOLDEN["leaves"][group]


def test_names_of_any_depth_and_leaves_of_any_rank():
    import jax
    import jax.numpy as jnp

    flat = {"emb": 1, "mix.w": 2, "blocks.0.wq": 3, "blocks.0.ffn.up": 4,
            "blocks.1.wq": 5, "blocks.1.ffn.up": 6}
    tree = weights.unflatten(flat)
    assert tree["blocks"][1]["ffn"]["up"] == 6 and tree["mix"]["w"] == 2
    assert weights.flatten(tree) == flat
    # a stack [2, 3, 5] reads as the matrix [6, 5] in the same place
    g = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5), jnp.float32)
    key = weights.sketch_key(7)
    stack = weights.grad_readings({"w": ((2, 3, 5), "matrix")})({"w": g}, key)
    matrix = weights.grad_readings({"w": ((6, 5), "matrix")})(
        {"w": g.reshape(6, 5)}, key)
    assert np.array_equal(stack["sketch"]["w"], matrix["sketch"]["w"])
    assert float(stack["norm"]["w"]) == float(matrix["norm"]["w"])


def counting_steps(sgd):
    """Publishes a counter from the train loop, as a family's step will:
    one count for every call of the compiled step."""
    from paddle_tpu.obs import default_registry

    built = sgd._build_step
    calls = default_registry().counter("zz_family_steps_total")

    def build():
        step = built()

        def counted(*args):
            calls.inc()
            return step(*args)
        return counted

    sgd._build_step = build


def result(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_family_dropped_in_as_files_trains(capsys):
    rc = bench_run.main(["--workload", "rehearse-gated-train", "--seed", "11",
                         "--seconds", "1", "--trace", "1", "--manifest",
                         FAMILY], broken=counting_steps)
    line = result(capsys)
    assert rc == 0 and line["correct"] is True
    assert set(line["compared"]) >= {"first_grad_sketch_worst_leaf",
                                     "param_change_norm_worst_leaf"}
    # the counter went registry -> run["counters"] -> the dropped-in reader
    assert 0 < line["metrics"]["zz_family_steps.train"]["value"] \
        <= line["attempted"] + 1


def test_a_family_dropped_in_as_files_serves(capsys):
    rc = bench_run.main(["--workload", "rehearse-gated-serve", "--seed", "11",
                         "--seconds", "1", "--trace", "0", "--manifest",
                         FAMILY])
    line = result(capsys)
    assert rc == 0 and line["correct"] is True and line["attempted"] > 0
    assert line["compared"]["served_token_gap_max"][0] <= 0.005


def test_mfu_counts_with_the_familys_own_file():
    """``mfu.train`` asks the family for a step's operations; this
    configuration has none of the keys the GPT-2 family's count reads."""
    cell = cell_of("rehearse-gated-train", FAMILY)
    layout = [64, 64, 64, 64]
    run = {"kind": "train", "peaks": {"bf16_flops_per_s": 1e12}, "chips": 1,
           "cell": cell, "layouts": [layout],
           "tracing": types.SimpleNamespace(ended=0.0),
           "steps": [{"done": 1.0 + 0.5 * i} for i in range(5)]}
    want = cell.family().train_step_flops(cell.config, layout)
    assert want > 0
    assert cell.layer_metric("mfu.train").read(run) == pytest.approx(
        100.0 * want * 2.0 / 1e12)
