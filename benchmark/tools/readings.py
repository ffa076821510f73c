"""The readings that the limits of ``correct`` are set from.

    python -m benchmark.tools.readings --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 10

One process: for every seed a run of the cell (a short window at the
cell's own load; training's readings need none) and the numbers that
``correct`` compares; for the control seeds also the control (the
reference one precision lower, put in the program's place) and, for a
training cell, the reference with half of the batch left out, each judged
by ``harness.correct`` against the cell's limits as a run is.  One JSON
line a seed; the lower reading of a number is the largest over the seeds,
the upper the smallest that the control gives."""

import argparse
import importlib
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--root", default=os.getcwd())
    args = ap.parse_args(argv)
    from benchmark import harness
    cell, devices, row = harness.open_cell(args.root, args.workload)
    runner = importlib.import_module(
        f"benchmark.runners.{cell.workload['runner']}")
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        out = runner.run(cell, seed=seed, seconds=args.seconds,
                         tracer=harness.Tracer(args.root, cell.name, False),
                         t0=time.perf_counter(),
                         devices=devices, peaks=row,
                         control=seed in control)
        line = {"seed": seed, "correct": harness.correct(out.compared),
                "program": {k: v["value"] for k, v in out.compared.items()}}
        for name, got in out.facts.get("control", {}).items():
            line[name] = {"correct": harness.correct(got), **{
                k: v["value"] for k, v in got.items()}}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
