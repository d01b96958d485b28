"""The control of ``correct`` at a size a test run can hold: the reference
computed with float8 operands, in the program's place, is NOT correct, and
the same reference at float32 or with bfloat16 operands is."""

import argparse
import os
import time

import pytest

from harness import cells, measure

TESTS = os.path.dirname(os.path.abspath(__file__))


def cell_of(name, manifest="rehearsal.json"):
    m = cells.load_json(os.path.join(TESTS, manifest))
    return cells.Cell(m, TESTS, name)


@pytest.mark.parametrize("seed,workload,manifest", [
    (11, "rehearse-train-seq", "rehearsal.json"),
    (12, "rehearse-train-seq", "rehearsal.json"),
    (13, "rehearse-train-seq", "rehearsal.json"),
    # the control goes through the family's file too
    (12, "rehearse-gated-train", "rehearsal_family.json"),
])
def test_train_control_is_not_correct(seed, workload, manifest):
    cell = cell_of(workload, manifest)
    drv = cell.driver()
    args = argparse.Namespace(seed=seed, seconds=1.0, trace=0)
    out = drv.control(cell, args, None, time.perf_counter(), None)
    assert out["correct"] is False, out["rows"]
    want = drv.reference_readings(cell, args, "f32")
    stated = drv.reference_readings(cell, args, "bf16")
    rows = drv.compare(stated, want, cell.limits)
    assert all(v <= lim for v, lim in rows.values()), rows


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_serve_control_is_not_correct(seed):
    import jax

    cell = cell_of("rehearse-serve-chat")
    args = argparse.Namespace(seed=seed, seconds=1.0, trace=0)
    out = cell.driver().control(cell, args, jax.devices()[:1],
                                time.perf_counter(), measure.CompileWatch())
    assert out["program_rows"]["served_token_gap_max"] <= \
        cell.limits["served_token_gap_max"]
    assert out["correct"] is False, out["rows"]
