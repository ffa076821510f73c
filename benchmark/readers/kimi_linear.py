"""Readers of the Kimi-Linear family's own metrics.

The training runner counts a dense decoder (``benchmark.counts.dims`` reads
``hidden_size``, ``intermediate_size`` and ``num_hidden_layers`` and knows
no experts, latents or linear attention), so what this family needs counted
is counted here, from its own counts (``benchmark.counts_kimi_linear``) and
from what the adapter's system ran (``benchmark.adapters.kimi_linear.SEEN``:
the configuration it was built from, the shape of its batches, each
recorded step's routing counts).  A metric's file names no configuration
and no traffic, so a second cell of the family needs no file copied.  Steps
are counted from the runner's spans: the window's from
``facts["spans"]["train_step"]``, the traced ones from the
``bench.train_step`` host spans of the trace.  The routing counts are cut
by the time of dispatch: the window's steps are those dispatched inside the
window's spans, the traced ones those dispatched after its last span.  A
reader that finds nothing to read returns ``None``."""

import re

import numpy as np

from benchmark import counts as base_counts
from benchmark import counts_kimi_linear as counts
from benchmark import trace
from benchmark.harness import say
from benchmark.readers import mfu as mfu_reader
from benchmark.readers import roofline as roofline_reader
from benchmark.reference.kimi_linear import dims, layer_kinds


def _seen():
    from benchmark.adapters import kimi_linear
    return kimi_linear.SEEN


def _window(facts: dict) -> tuple:
    spans = facts.get("spans", {}).get("train_step") or []
    return (spans[0][0], spans[-1][1], len(spans)) if spans else (0, 0, 0)


def _routing(facts: dict, traced: bool) -> list:
    """The routing counts of the window's steps, or of those after it."""
    first, last, _ = _window(facts)
    return [c for at, c in _seen().routing
            if (at > last if traced else first <= at <= last)]


def mfu(facts, reduced, params, peaks):
    """The whole step's share of the peak by the family's own count."""
    _, _, steps = _window(facts)
    seen = _seen()
    if not steps or seen is None or not seen.batch:
        return None
    rows, seq = seen.batch
    per_token = counts.train_flops_per_token(seen.cfg, {"seq": seq})
    return mfu_reader.read(
        dict(facts, model_flops=steps * rows * seq * per_token), reduced,
        params, peaks)


def held_seconds(reduced, all_of, any_of, enclosing) -> tuple:
    """(seconds, events) of the device events that ``all_of`` and
    ``any_of`` select as ``trace.Reduced.kernel_seconds`` does, together
    with every event matching ``enclosing`` that holds one of them in time
    (a loop whose body runs the kernel): the union of their intervals, so
    that a loop and what runs inside it count once; averaged over the
    chips."""
    any_re = [re.compile(p) for p in any_of]
    outer_re = re.compile(enclosing)
    total, count = 0.0, 0
    for d in reduced.devices:
        inner = np.array([all(s in n for s in all_of) and (
            not any_re or any(r.search(n) for r in any_re))
            for n in d.names], dtype=bool)[d.name_id]
        if not inner.any():
            continue
        outer = np.flatnonzero(np.array(
            [bool(outer_re.search(n)) for n in d.names],
            dtype=bool)[d.name_id])
        # events of one line nest, so a loop holds a kernel if the first
        # kernel to start at or after the loop's start starts before its end
        starts = np.sort(d.start[inner])
        nxt = np.searchsorted(starts, d.start[outer], side="left")
        holds = (nxt < len(starts)) & (
            starts[np.minimum(nxt, len(starts) - 1)] < d.end[outer])
        sel = inner.copy()
        sel[outer[holds]] = True
        total += trace.union_seconds(d.start[sel], d.end[sel])
        count += int(sel.sum())
    return total / max(len(reduced.devices), 1) / 1e9, count


def roofline(facts, reduced, params, peaks):
    """A kernel's share of its roofline over the traced steps: the work of
    every layer's forward and backward once a step (what the program
    recomputes is in the events' time), the events by ``all_of`` and
    ``any_of`` as ``benchmark.readers.roofline`` reads them; with
    ``enclosing``, also the loops that hold them (``held_seconds``)."""
    seen = _seen()
    if reduced is None or seen is None or not seen.batch:
        return None
    steps = len(reduced.host_spans.get("bench.train_step", ()))
    m, kinds = dims(seen.cfg), layer_kinds(seen.cfg)
    n_kda = sum(mix == "kda" for mix, _ in kinds)
    n_moe = sum(ffn == "moe" for _, ffn in kinds)
    b, s = seen.batch
    if params["work"] in ("kda_chunked", "kda_scan"):
        call = (counts.kda_chunked_call if params["work"] == "kda_chunked"
                else counts.kda_scan_call)
        calls, kw = steps * n_kda, dict(
            batch=b, heads=m["kh"], seq=s, dk=m["kd"], dv=m["kd"])
    elif params["work"] == "mla_attention":
        calls, call, kw = steps * (len(kinds) - n_kda), \
            counts.attention_call, dict(
                batch=b, heads=m["h"], seq=s, qk_dim=m["nope"] + m["rope"],
                v_dim=m["vd"], causal=True)
    else:
        # the pairs the program's counter saw on held experts and the
        # experts that got any, over the traced steps: a call on average
        routed = _routing(facts, traced=True)
        calls = steps * n_moe
        call, kw = counts.grouped_experts_call, dict(
            rows=sum(c["held"] for c in routed) / max(calls, 1),
            experts_hit=sum(c["experts_hit"] for c in routed)
            / max(calls, 1), hidden=m["d"], width=m["width"])
    fwd, bwd = call(backward=False, **kw), call(backward=True, **kw)
    work = (calls * (fwd[0] + bwd[0]), calls * (fwd[1] + bwd[1]))
    if "enclosing" not in params:
        return roofline_reader.read(
            dict(facts, kernel_work={params["work"]: work}), reduced,
            params, peaks)
    seconds, events = held_seconds(
        reduced, params.get("all_of", ()), params.get("any_of", ()),
        params["enclosing"])
    if not work[0] or events == 0 or seconds <= 0:
        return None
    least, bound = base_counts.roofline_seconds(work[0], work[1], peaks)
    say(f"roofline {params['work']}: {events} events and the loops that "
        f"hold them, {seconds:.6f} s on the device, least {least:.6f} s, "
        f"{bound}-bound")
    return 100.0 * least / seconds


def routing(facts, reduced, params, peaks):
    """``held_share``: pairs on held experts over all pairs, in percent,
    over the window's steps and expert layers; ``load_max_over_mean``: the
    busiest held expert's rows over the mean, worst layer and step."""
    if _seen() is None:
        return None
    routed = _routing(facts, traced=False)
    if not routed:
        return None
    if params["number"] == "load_max_over_mean":
        return max(c["load_max_over_mean"] for c in routed)
    return 100.0 * sum(c["held"] for c in routed) / sum(
        c["assignments"] for c in routed)
