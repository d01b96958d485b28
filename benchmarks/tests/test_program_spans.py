"""The readers of the program's own phases (``pt:tick.*``)
and of the counters beside them: on made-up intervals with known answers,
on a recorded trace of a program that wrote no such spans (every reader
says ``None``), and end to end through a rehearsal manifest on the CPU."""

import glob
import json
import os

import pytest

from harness import cells, program_spans as P, trace as T

import run as bench_run

TESTS = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(TESTS, "rehearsal_phases.json")
RECORDED = sorted(glob.glob(os.path.join(TESTS, "data", "*.xplane.pb")))

SPAN_READERS = ["schedule_ms_per_tick.serve", "assemble_ms_per_tick.serve",
                "upload_ms_per_tick.serve", "readback_ms_per_tick.serve",
                "sample_ms_per_tick.serve", "tick_uncovered_ms.serve"]


def reader(name):
    return cells.load_module(os.path.join(cells.ROOT, "layer_metrics",
                                          name + ".py")).read


def tick(t, gap=0.0):
    """One made-up tick starting at ``t`` (seconds): schedule 1 ms,
    assemble 2, upload 3, wait 60, sample 4 and the closing bookkeeping
    0.5 under the same name, ``gap`` ms in no phase before the wait."""
    ms = 1e-3
    rows, at = [], t
    for name, length in (("tick.schedule", 1), ("tick.assemble", 2),
                         ("tick.upload", 3), (None, gap), ("tick.wait", 60),
                         ("tick.sample", 4), ("tick.sample", 0.5)):
        if name is not None:
            rows.append((name, at, at + length * ms))
        at += length * ms
    return [("tick", t, at)] + rows


def serve_run(spans, busy=(), chips=2):
    """A record as ``run.py`` hands it to a reader, spans already read."""
    ops = [T.Op("%f", "%f = f32[4]{0} fusion(f32[4]{0} %p)", a, b)
           for a, b in busy]
    trace = T.Trace([T.Chip(i, list(ops), []) for i in range(chips)], [],
                    (0.0, 10.0))
    return {"kind": "serve", "trace": trace, "program_spans": sorted(
        spans, key=lambda s: s[1]), "counters": {}}


def test_phase_readers_on_made_up_ticks():
    spans = tick(1.0) + tick(2.0) + tick(3.0, gap=0.25)
    run = serve_run(spans)
    assert reader("schedule_ms_per_tick.serve")(run) == pytest.approx(1.0)
    assert reader("assemble_ms_per_tick.serve")(run) == pytest.approx(2.0)
    assert reader("upload_ms_per_tick.serve")(run) == pytest.approx(3.0)
    # the two spans of a tick under one name are summed
    assert reader("sample_ms_per_tick.serve")(run) == pytest.approx(4.5)
    # a phase outside any whole tick is not counted
    run = serve_run(spans + [("tick.schedule", 5.0, 5.5)])
    assert reader("schedule_ms_per_tick.serve")(run) == pytest.approx(1.0)


def test_tick_uncovered_at_zero_and_above():
    read = reader("tick_uncovered_ms.serve")
    assert read(serve_run(tick(1.0) + tick(2.0))) == pytest.approx(0.0,
                                                                   abs=1e-9)
    spans = tick(1.0, gap=0.25) + tick(2.0, gap=0.25) + tick(3.0)
    assert read(serve_run(spans)) == pytest.approx(0.25)


def test_readback_takes_the_busy_time_out_of_the_wait():
    # the wait of the tick at 1.0 runs from 1.006 to 1.066; the device
    # (both chips alike) is busy from 1.004 to 1.056: 50 ms inside it
    run = serve_run(tick(1.0), busy=[(1.004, 1.056)])
    assert reader("readback_ms_per_tick.serve")(run) == pytest.approx(10.0)
    # chips are averaged: one of two idle leaves half the busy time
    run["trace"].chips[1].ops.clear()
    assert reader("readback_ms_per_tick.serve")(run) == pytest.approx(35.0)
    # no device in the trace (a CPU rehearsal): no number
    assert reader("readback_ms_per_tick.serve")(
        serve_run(tick(1.0), chips=0)) is None


def test_counter_readers():
    run = {"kind": "serve", "counters": {
        "ticks": 4, "h2d_bytes": 3000, "d2h_bytes": 13000,
        "prefill_pad_rows": 64, "prefill_tokens": 192, "prefill_rows": 200}}
    assert reader("transfer_kb_per_tick.serve")(run) == pytest.approx(4.0)
    assert reader("prefill_pad_share.serve")(run) == pytest.approx(25.0)
    run["counters"].update(prefill_pad_rows=0, prefill_tokens=0)
    assert reader("prefill_pad_share.serve")(run) is None
    # a program from before the transfer counters: nothing to read
    old = {"kind": "serve", "counters": {"ticks": 4, "prefill_rows": 0}}
    assert reader("transfer_kb_per_tick.serve")(old) is None
    assert reader("prefill_pad_share.serve")(old) is None


class Recorded:
    """Stands for ``measure.Tracing`` after a traced window."""

    def __init__(self, path):
        self.path = path

    def file(self):
        return self.path


@pytest.mark.skipif(not RECORDED, reason="no recorded trace in data/")
@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_say_none_without_program_spans(name):
    """The recorded v5e trace is of a program that had no ``pt:`` spans
    (PR 24): every reader finds nothing and does not raise, for the train
    run it is and for a serve run alike."""
    tr = T.load(RECORDED[0])
    for kind in ("train", "serve"):
        run = {"kind": kind, "trace": tr, "tracing": Recorded(RECORDED[0]),
               "counters": {}}
        assert reader(name)(run) is None
        assert P.of_run(run) == []


def go(workload, capsys):
    rc = bench_run.main(["--workload", workload, "--seed", str(2**31 + 9),
                         "--seconds", "1", "--trace", "1",
                         "--manifest", MANIFEST])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_traced_serve_rehearsal_reports_the_ticks_phases(capsys):
    rc, line = go("rehearse-serve-chat", capsys)
    assert rc == 0 and line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    phases = [m[n] for n in ("schedule_ms_per_tick.serve",
                             "assemble_ms_per_tick.serve",
                             "upload_ms_per_tick.serve",
                             "sample_ms_per_tick.serve")]
    assert all(v > 0 for v in phases)
    assert 0 <= m["tick_uncovered_ms.serve"] < sum(phases)
    assert m["transfer_kb_per_tick.serve"] > 0
    assert 0 <= m["prefill_pad_share.serve"] <= 100
    # busy time needs a device plane, which a CPU trace has not
    assert "readback_ms_per_tick.serve" not in m
