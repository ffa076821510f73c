"""One cell, once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses any device that is not a TPU of the peak table, builds the system
from the seed, warms up the cell's own shapes, measures for ``--seconds``,
compares what the timed path produced with the float32 reference, and
prints as its last line of standard output the one JSON object of the
contract."""

import time
T0 = time.perf_counter()      # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace_on: bool, *, need_chip: bool = True, t0: float = None):
    """Everything of a run but the printing.  ``need_chip=False`` is for
    the rehearsal tests on the CPU: it skips the look for a chip and
    nothing else."""
    from benchmark import harness
    cell, devices, row = harness.open_cell(root, workload, need_chip)
    harness.say(f"seed {seed} seconds {seconds} trace {int(trace_on)}")
    runner = importlib.import_module(
        f"benchmark.runners.{cell.workload['runner']}")
    tracer = harness.Tracer(root, workload, trace_on)
    out = runner.run(cell, seed=int(seed), seconds=float(seconds),
                     tracer=tracer, t0=T0 if t0 is None else t0,
                     devices=devices, peaks=row)
    device = out.facts.pop("device")
    line = harness.result_line(cell, out, device, trace_on, row)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=os.getcwd(),
                    help="where BENCHMARK.json and benchmark/ are")
    args = ap.parse_args(argv)
    from benchmark import harness
    line = run_cell(args.root, args.workload, args.seed, args.seconds,
                    bool(args.trace))
    sys.stdout.flush()
    harness.say_compared(line["compared"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
