"""One fault of the mechanism, read as a run is: the reference without
KDA's decay gate (``a_t = 1``) in the program's place.

    python -m benchmark.tools.kimi_linear_faults --workload <cell> --seeds 1,2,3

``benchmark.tools.readings`` gives the control (one precision lower) and the
half batch for any training cell; this fault is the family's own.  One JSON
line a seed: the four numbers of the faulted reference against the plain
one, judged by ``harness.correct`` against the cell's limits."""

import argparse
import json
import os
import sys

import jax


def fault_readings(cfg, opt, mix, seed, rows, batches, fault) -> dict:
    """Three steps of the reference with ``fault``, as
    ``runners.train.reference_readings`` reads them."""
    from benchmark.reference import kimi_linear as ref
    from benchmark.runners.train import CHECK_STEPS, step_key
    run = ref.Training(
        cfg, opt, jax.jit(lambda k: ref.init_weights(cfg, k))(
            ref.C.seed_key(seed)), rows=rows, fault=fault)
    losses, grad = [], None
    for i in range(CHECK_STEPS):
        loss, g = run.step(batches(mix, seed, i, cfg["vocab_size"]),
                           step_key(seed, i))
        losses.append(loss)
        if g is not None:
            grad = jax.device_get(g)
    return {"losses": losses, "grad": grad,
            "change": jax.device_get(run.change_norms())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", default="no_decay_gate")
    ap.add_argument("--root", default=os.getcwd())
    args = ap.parse_args(argv)
    from benchmark import harness
    from benchmark.runners import train
    cell, _, _ = harness.open_cell(args.root, args.workload)
    cfg, wl = cell.config, cell.workload
    batches = harness.resolve(wl["generator"])
    for seed in (int(s) for s in args.seeds.split(",")):
        read = lambda fault: fault_readings(
            cfg, wl["trainer"], wl["traffic"], seed,
            int(wl["reference_rows"]), batches, fault)
        got = train.compared(read(args.fault), read(None), wl["limits"])
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": harness.correct(got),
                          **{k: v["value"] for k, v in got.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
