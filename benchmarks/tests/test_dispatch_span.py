"""The reader of ``dispatch_ms_per_tick.serve``: on made-up ticks with a
``pt:tick.dispatch`` inside each ``pt:tick.upload``; ``None``, without
raising, for a program that names its ticks but no dispatch (the commit
before PR 32), for an untraced run and for a train run; end to end
through a rehearsal manifest on the CPU, where the span lies inside the
upload; and its entry in ``BENCHMARK.json``."""

import json
import os

import pytest

from harness import cells, trace as T

import run as bench_run

NAME = "dispatch_ms_per_tick.serve"
TESTS = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(TESTS, "rehearsal_dispatch.json")


def reader(name):
    return cells.load_module(os.path.join(cells.ROOT, "layer_metrics",
                                          name + ".py")).read


def tick(t, dispatch_ms=None):
    """One made-up tick at ``t`` seconds: assemble 2 ms, upload 3 (the
    dispatch its last ``dispatch_ms``, where it has one), wait 60."""
    ms = 1e-3
    rows = [("tick", t, t + 65 * ms), ("tick.assemble", t, t + 2 * ms),
            ("tick.upload", t + 2 * ms, t + 5 * ms),
            ("tick.wait", t + 5 * ms, t + 65 * ms)]
    if dispatch_ms is not None:
        rows.append(("tick.dispatch", t + (5 - dispatch_ms) * ms, t + 5 * ms))
    return rows


def serve_run(spans, kind="serve"):
    """A record as ``run.py`` hands it to a reader, spans already read."""
    return {"kind": kind, "trace": T.Trace([], [], (0.0, 10.0)),
            "program_spans": sorted(spans, key=lambda s: s[1]),
            "counters": {}}


def test_reads_the_median_dispatch_of_the_whole_ticks():
    spans = tick(1.0, 1.0) + tick(2.0, 1.5) + tick(3.0, 2.5)
    # a dispatch outside any whole tick is not counted
    spans.append(("tick.dispatch", 5.0, 5.5))
    run = serve_run(spans)
    assert reader(NAME)(run) == pytest.approx(1.5)
    # the upload keeps its extent: placement and dispatch together
    assert reader("upload_ms_per_tick.serve")(run) == pytest.approx(3.0)


@pytest.mark.parametrize("run", [
    serve_run(tick(1.0) + tick(2.0)),
    {"kind": "serve", "counters": {}},
    {"kind": "serve", "trace": None, "counters": {}},
    serve_run(tick(1.0, 1.0), kind="train"),
], ids=["ticks_without_a_dispatch", "untraced", "no_trace", "train"])
def test_says_none_where_the_program_writes_no_such_span(run):
    assert reader(NAME)(run) is None


def test_traced_serve_rehearsal_reports_the_dispatch_inside_the_upload(
        capsys):
    rc = bench_run.main(["--workload", "rehearse-serve-chat", "--seed",
                         str(2**31 + 11), "--seconds", "1", "--trace", "1",
                         "--manifest", MANIFEST])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m[NAME] < m["upload_ms_per_tick.serve"]
    # a span inside a span: the tick's uncovered time does not count it
    assert 0 <= m["tick_uncovered_ms.serve"] < m["upload_ms_per_tick.serve"]


def test_the_manifest_lists_it_for_the_tick_assembly_layer():
    with open(os.path.join(cells.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    [m] = [e for e in manifest["per_layer"] if e["name"] == NAME]
    assert m == {"name": NAME, "unit": "ms", "better": "lower",
                 "source": "program_span",
                 "layer": "tick assembly and host sampling",
                 "moves": "itl_p95_ms", "workloads": ["serve-6.7b-tp4-chat"]}
    assert any(e["name"] == "upload_ms_per_tick.serve" and
               e["layer"] == m["layer"] for e in manifest["per_layer"])
