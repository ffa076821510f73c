"""The arithmetic of the comparisons that decide ``correct``."""

from __future__ import annotations

import numpy as np


def flatten_norms(norms: dict) -> dict:
    """name -> norm, one entry a leaf: a leaf stacked over layers gives
    ``name[i]``, one stacked over layers and parts ``name[i].j``."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, dtype=np.float64)
        if v.ndim == 0:
            out[name] = float(v)
        elif v.ndim == 1:
            for i, x in enumerate(v):
                out[f"{name}[{i}]"] = float(x)
        else:
            for i, row in enumerate(v):
                for j, x in enumerate(row):
                    out[f"{name}[{i}].{j}"] = float(x)
    return out


def worst_norm_gap(program: dict, reference: dict, leave_out=(),
                   log=None) -> tuple:
    """(gap, leaf) of the worst leaf: the gap between the program's norm
    and the reference's, against the reference's norm of that leaf or of
    the median leaf, whichever is larger."""
    prog, ref = flatten_norms(program), flatten_norms(reference)
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    median = float(np.median(list(ref.values())))
    gaps = []
    for name, r in ref.items():
        if name in leave_out:
            continue
        p = prog[name]
        gap = abs(p - r) / max(r, median) if np.isfinite(p) else np.inf
        gaps.append((gap, name, p, r))
    gaps.sort(reverse=True)
    if log is not None:
        log(f"  median leaf {median:.4g}; worst leaves (gap, leaf, program, "
            f"reference): " + "; ".join(
                f"{g:.4f} {n} {p:.4g} {r:.4g}" for g, n, p, r in gaps[:6]))
    return float(gaps[0][0]), gaps[0][1]


def near_zero_leaves(grad_norms: dict, share: float = 1e-3) -> set:
    """Leaves whose reference gradient is nought to rounding: under
    ``share`` of the median leaf's.  Under Adam they move by round-off
    alone, so their change is not compared."""
    ref = flatten_norms(grad_norms)
    median = float(np.median(list(ref.values())))
    return {n for n, r in ref.items() if r < share * median}


def loss_gap(program: list, reference: list) -> float:
    return float(max(abs(p - r) / abs(r) if np.isfinite(p) else np.inf
                     for p, r in zip(program, reference)))
