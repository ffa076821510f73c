"""Readers of the AFMoE (Trinity) family's own metrics.

As for DeepSeek-V2 (``benchmark.readers.deepseek_v2``, whose window and
traced stretch these share): the serving runner counts a dense decoder with
one head count and one cache layout, so what this family needs counted is
counted here, from its own counts (``benchmark.counts_afmoe``) and from what
the adapter's system ran (``benchmark.adapters.afmoe.SEEN``: the
configuration, the cache by group as the engine built it, a timestamped
record of every device program the engine collected and of the pages held
in each group).  A program counts where it was collected.  A reader that
finds nothing to read (a program without the model, as the parent's) returns
``None``."""

from benchmark import counts_afmoe as counts
from benchmark.readers import mfu as mfu_reader
from benchmark.readers import roofline as roofline_reader
from benchmark.readers.deepseek_v2 import _traced, _window


def _seen():
    try:
        from benchmark.adapters import afmoe
    except ImportError:         # a program that has no such model
        return None
    return afmoe.SEEN


def _programs(stretch) -> list:
    seen = _seen()
    if seen is None or stretch is None:
        return []
    return [(kind, info) for at, kind, info in seen.programs
            if stretch[0] <= at <= stretch[1]]


def _held_pairs(ran, slots: int) -> float:
    """(token, held expert) pairs of the programs' own tokens: a program's
    counters also count its padding (a prompt's bucket, a step's idle
    slots), routed like any token, so they are scaled to the tokens that
    were asked for."""
    total = 0.0
    for kind, info in ran:
        held = (info.get("routing") or {}).get("held", 0)
        share = (info["prompt_len"] / info["bucket"] if kind == "prefill"
                 else info["rows"] / slots)
        total += held * share
    return total


def mfu(facts, reduced, params, peaks):
    """The whole step's share of the peak by the family's own count: the
    prefills and decode steps collected in the window."""
    seen, ran = _seen(), _programs(_window(facts))
    if not ran:
        return None
    flops = counts.serve_flops(
        seen.cfg,
        [info["prompt_len"] for kind, info in ran if kind == "prefill"],
        [c for kind, info in ran if kind == "decode"
         for c in info.get("contexts", ())],
        _held_pairs(ran, int(seen.engine["num_slots"])))
    return mfu_reader.read(dict(facts, model_flops=flops), reduced, params,
                           peaks)


def roofline(facts, reduced, params, peaks):
    """A kernel's share of its roofline over the traced stretch.
    ``gqa_paged_decode``: every layer's call of every decode step collected
    there, window and full layers together, over what each row sees.
    ``window_flash``: every layer's flash call of every prefill collected
    there, over the prompts' own lengths.  ``moe_experts``: the three
    grouped products of every expert layer of every program collected
    there, prefill and decode, rows and experts hit from the program's own
    counters (as ``readers.deepseek_v2`` reads the same kernel)."""
    seen = _seen()
    if reduced is None or seen is None:
        return None
    ran = _programs(_traced(facts))
    m = counts.dims(seen.cfg)
    ns, nf = counts.layers_by_kind(seen.cfg)
    shape = dict(heads=m["h"], kv_heads=m["kh"], head_dim=m["hd"])
    if params["work"] == "gqa_paged_decode":
        ctx = [c for kind, info in ran if kind == "decode"
               for c in info.get("contexts", ())]
        work = counts.gqa_paged_decode_call(
            seen_tokens=ns * sum(min(c, m["window"]) for c in ctx)
            + nf * sum(ctx), **shape)
    elif params["work"] == "moe_experts":
        routed = [info["routing"] for _, info in ran if info.get("routing")]
        # a program's counts are sums over its expert layers; operations
        # and bytes are linear in both, so the sums go in as one call
        work = counts.grouped_experts_call(
            rows=sum(c["held"] for c in routed),
            experts_hit=sum(c["experts_hit"] for c in routed),
            hidden=m["d"], width=m["width"], backward=False)
    else:
        work = [0.0, 0.0]
        for kind, info in ran:
            if kind != "prefill":
                continue
            for layers, window in ((ns, m["window"]), (nf, None)):
                f, b = counts.window_flash_call(
                    tokens=info["prompt_len"], window=window, **shape)
                work = [work[0] + layers * f, work[1] + layers * b]
    return roofline_reader.read(
        dict(facts, kernel_work={params["work"]: tuple(work)}), reduced,
        params, peaks)


def grouped_memory_share(facts, reduced, params, peaks):
    """Weights as served plus the pages held in every group of layers,
    averaged over the decode steps collected in the window, over the chip's
    memory."""
    from benchmark.reference import afmoe as ref
    seen, window = _seen(), _window(facts)
    if seen is None or window is None:
        return None
    held = [pages for at, pages in seen.held if window[0] <= at <= window[1]]
    if not held:
        return None
    page_bytes = {name: g["layers"] * g["page_size"]
                  * g["bytes_per_token_per_layer"]
                  for name, g in seen.pool["groups"].items()}
    live = sum(sum(h[name] * page_bytes[name] for name in page_bytes)
               for h in held) / len(held)
    return 100.0 * (ref.weight_bytes(seen.cfg) + live) / peaks["hbm_bytes"]
