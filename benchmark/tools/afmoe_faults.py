"""Faults of the AFMoE (Trinity) mechanisms, read as a run is: the reference
with one mechanism left out or put where it does not belong, in the
program's place.

    python -m benchmark.tools.afmoe_faults --workload <cell> --seeds 1,2,3 \\
        [--fault no_window|rope_on_full|no_gate | --control]

As ``benchmark.tools.deepseek_v2_faults``, for this family's reference and
faults: no engine runs; a seed's sample
is ``check_requests`` sequences of the cell's longest prompt, drawn from
the seed; at each of the last ``--positions`` positions (1,536: all past
the window) the token judged is the one that the faulted reference, or with
``--control`` the reference one precision lower (or at ``--precision``),
puts ``rank``-th, held to
the plain reference as ``runners.serve.served_gaps`` holds a run's tokens:
where the configuration has a ``judged_router_margin`` the plain reference
blanks the positions at which a held expert's choice is not settled
(``reference.afmoe.logits_at``), here as in a run, and ``judged`` in the
line counts the positions that are left.
One JSON line a seed, judged by ``harness.correct`` against the cell's
limit.  ``--control`` is for a cell whose logits at the padded length do
not fit the chip twice (``benchmark.tools.readings --control-seeds`` asks
for them at ``max_seq_len`` positions)."""

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def fault_gaps(cfg, seed, prompts, n_new, *, rank, fault=None,
               control=None, count=None) -> list:
    """For each prompt: by how much the logit of the token that the faulted
    (or lower-precision) reference puts ``rank``-th lies below the plain
    reference's ``rank``-th best, at each of the prompt's last ``n_new``
    positions (nought where the plain reference judges nothing).  ``count``,
    a list, gains the number of positions judged."""
    from benchmark.reference import afmoe as ref
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
        # a blanked position (all logits nought) reads nought, not a number
        return jnp.where(jnp.any(full != 0.0, axis=-1),
                         jnp.maximum(gap, 0.0), jnp.nan)

    out = [np.asarray(judge(
        w, jnp.asarray(p, jnp.int32),
        jnp.arange(len(p) - n_new, len(p), dtype=jnp.int32)))
        for p in prompts]
    if count is not None:
        count.append(int(sum(np.isfinite(g).sum() for g in out)))
    return [np.nan_to_num(g) for g in out]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", default="no_window")
    ap.add_argument("--control", action="store_true",
                    help="the reference one precision lower, not the fault")
    ap.add_argument("--precision", default=None,
                    help="with --control: this precision and not the one "
                         "below the configuration's (its own: what "
                         "rounding alone does to the reference)")
    ap.add_argument("--positions", type=int, default=1536)
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
        kw = ({"control": args.precision or LOWER[cfg["dtype"]]}
              if args.control else {"fault": args.fault})
        judged = []
        gaps = fault_gaps(cfg, seed, prompts, args.positions,
                          rank=judged_rank(wl["engine"]), count=judged, **kw)
        got = {"logit_gap_max": {"value": float(max(g.max() for g in gaps)),
                                 "limit": wl["limits"]["logit_gap_max"]}}
        print(json.dumps({"seed": seed, **kw,
                          "correct": harness.correct(got),
                          "logit_gap_max": got["logit_gap_max"]["value"],
                          "judged": judged[0],
                          "positions": len(prompts) * args.positions,
                          "logit_gap_median": float(np.median(
                              np.concatenate(gaps)))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
