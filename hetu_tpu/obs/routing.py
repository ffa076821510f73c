"""Expert routing counters: where a step's (token, choice) pairs fell.

An expert layer that holds a share of the experts (``layers.moe.
HeldExpertsMoE``) reports, as device scalars in the step's metrics, the
pairs that fell on held experts, all pairs, the held experts that got a
row (summed over the expert layers), and the busiest held expert's rows
over the mean.  ``record_routing`` folds one step's numbers into the
registry.  It reads them, so call it on a step whose result is already on
the host or whose wait is wanted: not between dispatches."""

from __future__ import annotations

from hetu_tpu.obs import registry as _registry

__all__ = ["record_routing"]

KEYS = ("moe_held", "moe_assignments", "moe_experts_hit",
        "moe_load_max_over_mean")


def record_routing(metrics: dict):
    """One step's routing counts (the ``moe_*`` entries of a step's
    metrics) into ``hetu_moe_assignments_total{where="held"|"absent"}`` and
    the gauge ``hetu_moe_expert_load_max_over_mean``.  Returns the step's
    numbers as host values (those, and ``experts_hit``: the held experts
    that got a row), or None for a step that carries none."""
    if not all(k in metrics for k in KEYS):
        return None
    held, total = int(metrics["moe_held"]), int(metrics["moe_assignments"])
    load = float(metrics["moe_load_max_over_mean"])
    hit = int(metrics["moe_experts_hit"])
    if _registry.enabled():
        reg = _registry.get_registry()
        pairs = reg.counter(
            "hetu_moe_assignments_total",
            "(token, choice) pairs routed, by whether the chosen expert's "
            "weights are held here", ("where",))
        pairs.labels(where="held").inc(held)
        pairs.labels(where="absent").inc(total - held)
        reg.gauge(
            "hetu_moe_expert_load_max_over_mean",
            "rows of the busiest held expert over the mean of the held "
            "experts, worst expert layer of the last recorded step"
        ).set(load)
    return {"held": held, "assignments": total, "experts_hit": hit,
            "load_max_over_mean": load}
