"""The knee of an open-loop serving cell, found once by a sweep.

    python -m benchmark.tools.sweep --workload <cell> --rates 2,2.5,3 --seconds 30

One engine, one window a rate.  The knee is the highest offered rate at
which the backlog (requests due and not finished) at the window's end is no
larger than at its middle.  Prints one JSON line a rate."""

import argparse
import copy
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--root", default=os.getcwd())
    args = ap.parse_args(argv)
    from benchmark import harness
    from benchmark.runners import serve
    cell, devices, row = harness.open_cell(args.root, args.workload)
    tracer = harness.Tracer(args.root, args.workload, False)
    prepared = serve.set_up(cell, args.seed, tracer)
    for rate in (float(r) for r in args.rates.split(",")):
        one = copy.copy(cell)
        one.workload = copy.deepcopy(cell.workload)
        one.workload["traffic"]["rate_per_s"] = rate
        one.workload["drain_seconds"] = 60
        out = serve.run(one, seed=args.seed, seconds=args.seconds,
                        tracer=tracer, t0=time.perf_counter(),
                        devices=devices, peaks=row,
                        prepared=prepared, keep=True)
        while not prepared[0].engine.batcher.idle:
            time.sleep(0.05)
        e = out.end_to_end
        print(json.dumps({
            "rate_per_s": rate, "attempted": out.attempted,
            "failed": out.failed, "backlog_mid_end": out.facts["backlog"],
            "serve_tokens_per_s": e["serve_tokens_per_s"],
            "live_memory_share": out.facts["window"]["live_memory_share"],
            "ticks": len(out.facts["spans"]["tick"]),
            "ttft_p50_ms": e.get("ttft_p50_ms"),
            "ttft_p95_ms": e.get("ttft_p95_ms"),
            "itl_p50_ms": e.get("itl_p50_ms"),
            "itl_p95_ms": e.get("itl_p95_ms")}), flush=True)
    prepared[0].free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
