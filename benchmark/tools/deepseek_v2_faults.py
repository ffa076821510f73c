"""One fault of the mechanism, read as a run is: the reference without
rotary on the ``pe`` parts (what ``layers/mla.py`` computed before it had
one) in the program's place.

    python -m benchmark.tools.deepseek_v2_faults --workload <cell> --seeds 1,2,3

``benchmark.tools.readings`` gives the control (one precision lower) for any
serving cell; this fault is the family's own, and ``--control`` reads the
control the same way for a cell whose logits at the padded length do not fit
the chip twice.  No engine runs: a seed's sample is ``check_requests``
sequences of the cell's longest prompt and answer, drawn from the seed, and
at each served position the token judged is the one that the faulted
reference puts ``rank``-th, the last that a sampler of its best ``rank`` may
serve, held to the plain reference as ``runners.serve.served_gaps`` holds a
run's tokens.  One JSON line a seed, judged by ``harness.correct`` against
the cell's limit."""

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def fault_gaps(cfg, seed, prompts, n_new, *, rank, fault=None,
               control=None) -> list:
    """For each prompt: ``n_new`` tokens generated by the faulted (or
    lower-precision) reference, each its ``rank``-th best, and by how much
    each one's logit lies below the plain reference's ``rank``-th best at
    its position."""
    from benchmark.reference import deepseek_v2 as ref
    served = jax.jit(lambda k: ref.init_weights(cfg, k))(ref.C.seed_key(seed))
    w = jax.jit(ref.to_float32)(served)
    del served

    @jax.jit
    def judge(w, tok, pos):
        full = ref.logits_at(w, tok, pos, cfg=cfg)
        bad = ref.logits_at(w, tok, pos, cfg=cfg, fault=fault,
                            precision=control or "float32")
        judged = jax.lax.top_k(bad, rank)[1][:, -1]
        gap = jax.lax.top_k(full, rank)[0][:, -1] - jnp.take_along_axis(
            full, judged[:, None], axis=-1)[:, 0]
        return jnp.maximum(gap, 0.0)

    out = []
    for prompt in prompts:
        # the positions judged are the prompt's last n_new: every one has
        # the context a served token of a long request has
        tok = jnp.asarray(prompt, jnp.int32)
        pos = jnp.arange(len(prompt) - n_new, len(prompt), dtype=jnp.int32)
        out.append(np.asarray(judge(w, tok, pos)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", default="no_rope")
    ap.add_argument("--control", action="store_true",
                    help="the reference one precision lower, not the fault")
    ap.add_argument("--positions", type=int, default=256)
    ap.add_argument("--root", default=os.getcwd())
    args = ap.parse_args(argv)
    from benchmark import harness
    from benchmark.reference.common import LOWER
    from benchmark.runners.serve import judged_rank
    cell, _, _ = harness.open_cell(args.root, args.workload)
    cfg, wl = cell.config, cell.workload
    length = int(wl["traffic"]["prompt_tokens"]["max"])
    for seed in (int(s) for s in args.seeds.split(",")):
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xFA17])
        prompts = [rng.integers(0, cfg["vocab_size"], length)
                   for _ in range(int(wl["check_requests"]))]
        kw = ({"control": LOWER[cfg["dtype"]]} if args.control
              else {"fault": args.fault})
        gaps = fault_gaps(cfg, seed, prompts, args.positions,
                          rank=judged_rank(wl["engine"]), **kw)
        got = {"logit_gap_max": {"value": float(max(g.max() for g in gaps)),
                                 "limit": wl["limits"]["logit_gap_max"]}}
        print(json.dumps({"seed": seed, **kw,
                          "correct": harness.correct(got),
                          "logit_gap_max": got["logit_gap_max"]["value"],
                          "logit_gap_median": float(np.median(
                              np.concatenate(gaps)))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
