#!/usr/bin/env python3
"""The control of ``correct``, at a cell's own size, on several seeds.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 [--seconds 12]

Not part of a benchmark run.  For each seed the reference computed in the
precision below the one the configuration states stands in the program's
place (see the drivers' ``control``); every number is printed beside its
limit, and the control has to come out as NOT correct.  Exits 0 when it
failed `correct` on every seed, 1 when it passed on any.
"""

import time

STARTED = time.perf_counter()

import argparse
import gc
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

from harness import cells, measure  # noqa: E402
from harness.measure import say  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--manifest", default=None)
    args = ap.parse_args(argv)
    manifest, base, rehearsal = cells.load_manifest(args.manifest)
    cell = cells.Cell(manifest, base, args.workload)

    measure.start_program()
    devs = measure.require_devices(cell.chips, rehearsal)
    watch = measure.CompileWatch()
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        run_args = argparse.Namespace(seed=seed, seconds=args.seconds,
                                      trace=0)
        out = cell.driver().control(cell, run_args, devs,
                                    time.perf_counter(), watch)
        for name, value in out["rows"].items():
            limit = cell.limits.get(name)
            if limit is None and name.startswith("loss_step"):
                limit = cell.limits["loss_rel"]
            say(f"control seed {seed} {name}: {value:.6g} (limit {limit})")
        say(f"control seed {seed}: correct={out['correct']} "
            f"{'(it must be False)' if out['correct'] else ''}")
        passed += bool(out["correct"])
        gc.collect()
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
