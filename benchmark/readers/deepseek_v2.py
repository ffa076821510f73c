"""Readers of the DeepSeek-V2 family's own metrics.

The serving runner counts a dense GPT block (``benchmark.counts.dims`` reads
this configuration's keys without complaint), so its ``model_flops``, its
bytes a cached token and its ``kernel_work`` are not this family's.  What
the family needs counted is counted here, from its own counts
(``benchmark.counts_deepseek_v2``) and from what the adapter's system ran
(``benchmark.adapters.deepseek_v2.SEEN``: the configuration, the engine's
shapes, and a timestamped record of every device program the engine
collected, on ``time.perf_counter``, the runner's clock).  The window is
that of ``facts["spans"]["tick"]``, first tick's start to last tick's end;
the traced stretch is that of the program's own ``serve.tick`` spans, which
exist only while the profiler's session is live
(``benchmark.readers.program_span``).  A program counts where it was
collected.  A reader that finds nothing to read returns ``None``."""

from benchmark import counts as base_counts
from benchmark import counts_deepseek_v2 as counts
from benchmark.readers import mfu as mfu_reader
from benchmark.readers import program_span
from benchmark.readers import roofline as roofline_reader
from benchmark.reference import deepseek_v2 as ref


def _seen():
    from benchmark.adapters import deepseek_v2
    return deepseek_v2.SEEN


def _window(facts: dict) -> tuple:
    spans = facts.get("spans", {}).get("tick") or []
    return (spans[0][0], spans[-1][1]) if spans else None


def _traced(facts: dict) -> tuple:
    ticks = [s for s in program_span.traced_spans(facts)
             if s.name == "serve.tick"]
    return (min(s.start for s in ticks),
            max(s.end_time for s in ticks)) if ticks else None


def _programs(stretch) -> list:
    seen = _seen()
    if seen is None or stretch is None:
        return []
    return [(kind, info) for at, kind, info in seen.programs
            if stretch[0] <= at <= stretch[1]]


def mfu(facts, reduced, params, peaks):
    """The whole step's share of the peak by the family's own count: the
    prefills and decode steps collected in the window."""
    ran = _programs(_window(facts))
    if not ran:
        return None
    steps = [info for kind, info in ran if kind == "decode"]
    flops = counts.serve_flops(
        _seen().cfg,
        [info["prompt_len"] for kind, info in ran if kind == "prefill"],
        decoded=sum(s["rows"] for s in steps),
        decoded_context=sum(s["context_tokens"] for s in steps))
    return mfu_reader.read(dict(facts, model_flops=flops), reduced, params,
                           peaks)


def roofline(facts, reduced, params, peaks):
    """A kernel's share of its roofline over the traced stretch.
    ``mla_decode``: every layer's call of every decode step collected
    there, over the cached tokens its rows attended.  ``moe_experts``: the
    three grouped products of every expert layer of every program
    collected there, prefill and decode, rows and experts hit from the
    program's own counters."""
    seen = _seen()
    if reduced is None or seen is None:
        return None
    ran = _programs(_traced(facts))
    m = ref.dims(seen.cfg)
    if params["work"] == "mla_decode":
        ctx = sum(info["context_tokens"] for kind, info in ran
                  if kind == "decode")
        flops, nbytes = counts.mla_decode_call(
            context_tokens=ctx, heads=m["h"], width=m["r"] + m["rope"],
            value_width=m["r"])
        work = (m["layers"] * flops, m["layers"] * nbytes)
    else:
        routed = [info["routing"] for _, info in ran if info.get("routing")]
        # a program's counts are sums over its expert layers; bytes and
        # operations are linear in both, so the sums go in as one call
        work = counts.grouped_experts_call(
            rows=sum(c["held"] for c in routed),
            experts_hit=sum(c["experts_hit"] for c in routed),
            hidden=m["d"], width=m["width"], backward=False)
    return roofline_reader.read(
        dict(facts, kernel_work={params["work"]: work}), reduced, params,
        peaks)


def latent_memory_share(facts, reduced, params, peaks):
    """Weights as served plus the live latents in whole pages, averaged
    over the window, over the chip's memory.  The runner's
    ``live_memory_share`` is the same sum with ``counts.kv_bytes_per_token``
    a token, the bytes of a dense decoder's keys and values; the live
    tokens are taken back out of it and weighed as latents."""
    seen = _seen()
    share = facts.get("window", {}).get("live_memory_share")
    if seen is None or share is None:
        return None
    weights = ref.weight_bytes(seen.cfg)
    live_tokens = (share / 100.0 * peaks["hbm_bytes"] - weights) \
        / base_counts.kv_bytes_per_token(seen.cfg)
    return 100.0 * (weights + live_tokens * counts.kv_bytes_per_token(
        seen.cfg)) / peaks["hbm_bytes"]
