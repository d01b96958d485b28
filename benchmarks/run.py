#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one touch of JAX.  Exits non-zero and prints no result line
when JAX finds no TPU, fewer chips than the cell asks for, or a device kind
that ``peaks.json`` does not know.  ``--manifest <file>`` runs a cell of a
rehearsal manifest instead (tiny configurations on the CPU, for developing
the harness; its result line says ``"platform": "cpu"``); such a manifest
may not name a cell of ``BENCHMARK.json``.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (a profiler trace covers a few seconds of the window),
``device.busy_s``/``window_s`` and ``breakdown``.  The last line of
standard output is the result; everything else is on earlier lines.  The
numbers that decided ``correct`` stand beside their limits in the result
(``compared``, its last key) and on the last lines of standard error.
"""

import time

STARTED = time.perf_counter()      # before anything heavy is imported

import argparse
import math
import os
import sys
from typing import Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                     # harness, drivers, ...
sys.path.insert(1, os.path.dirname(HERE))    # the program: paddle_tpu

from harness import cells, measure, trace as trace_mod  # noqa: E402
from harness.measure import say  # noqa: E402

# A compilation inside the window is an error of the warm-up.  One shorter
# than this (a helper of a few scalars, such as the trainer's stacking of
# the costs it logs) is reported and let pass: it is a thousandth of a
# window, and a step or tick program takes seconds.
SMALL_COMPILE_S = 0.25


def main(argv: Optional[Sequence[str]] = None, broken=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None,
                    help="a rehearsal manifest (development only)")
    args = ap.parse_args(argv)
    try:
        manifest, base, rehearsal = cells.load_manifest(args.manifest)
        cell = cells.Cell(manifest, base, args.workload)
    except cells.CellError as e:
        print(f"[bench] error: {e}", file=sys.stderr)
        return 2

    cache_dir = measure.start_program()
    try:
        devs = measure.require_devices(cell.chips, rehearsal)
        kind = devs[0].device_kind
        # a rehearsal off the chip has no peaks: the shares of a peak
        # are then left out, never computed against another device's
        peaks = None if rehearsal and devs[0].platform != "tpu" \
            else measure.peaks_for(kind)
    except measure.NoDevice as e:
        print(f"[bench] error: {e}", file=sys.stderr)
        return 3
    watch = measure.CompileWatch()
    say(f"cell {cell.name}: config {cell.entry['config']}, traffic "
        f"{cell.entry['traffic']}, {cell.chips} chip(s) of {kind!r}, seed "
        f"{args.seed}, {args.seconds} s, trace {args.trace}, compile cache "
        f"{cache_dir}")

    record = cell.driver().run(cell, args, devs, STARTED, watch,
                               broken=broken)
    inside = record["compiles_in_window"]
    say(f"compile cache: {watch.hits} hits, {watch.misses} misses; "
        f"{len(watch.compiles)} compilations, {len(inside)} inside the "
        f"window {[round(x, 3) for x in inside]}")
    if any(took >= SMALL_COMPILE_S for took in inside):
        print("[bench] error: a program compiled inside the measured "
              "window; the warm-up does not cover the window's shapes",
              file=sys.stderr)
        return 4
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    breakdown = None
    if args.trace:
        path = record["tracing"].file()
        if path is None:
            print("[bench] error: the profiler wrote no trace",
                  file=sys.stderr)
            return 5
        tr = trace_mod.load(path)
        record.update(trace=tr, cell=cell, peaks=peaks, chips=len(devs))
        device["busy_s"] = trace_mod.busy_seconds(tr)
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": trace_mod.top_ops(tr) if tr.chips else [],
                     "idle_gaps": trace_mod.idle_gaps(tr) if tr.chips else []}
        metrics = {}
        for m in cell.per_layer():
            value = cell.layer_metric(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": record["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end()}
    for name, m in metrics.items():
        say(f"metric {name}: {m['value']} {m['unit']}")
    check = record["check"]
    # JSON has no NaN: a number that is not finite goes as its name
    compared = {k: [v if math.isfinite(v) else str(v), check["limits"][k]]
                for k, v in check["rows"].items()}
    print(measure.result_line(check["correct"], record["attempted"],
                              record["failed"], metrics, device, breakdown,
                              compared), flush=True)
    # what decided `correct`, once more, as the last lines on standard
    # error: where a run is not correct little else of it is kept
    for name, (value, limit) in compared.items():
        print(f"[bench] compared {name}: {value} (limit {limit})",
              file=sys.stderr)
    print(f"[bench] correct: {check['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
