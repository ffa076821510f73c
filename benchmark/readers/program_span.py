"""A statistic of the program's own spans over the traced stretch.

The program (``hetu_tpu.obs.tracing``) records its spans while a profiler
session is live, on ``time.perf_counter``, the runners' clock.  Both runners
open the session after the window, so this run's spans are those that begin
after the window's last span of ``facts["spans"]`` ended, and after every
span that an earlier run of this process was given (a window may hold no
span at a tiny size).  A span counts only with its whole chain of parents up
to a root: a tick cut by either end of the session does not.

``params``: ``span`` names the spans read; each is worth its length, less
the lengths of its descendants whose name ends in ``minus`` if given.  With
``per`` they are summed by their ancestor of that name, one value for every
such ancestor, those without any too.  ``stat`` is ``mean`` or ``max`` of
the values, given in milliseconds.  A program that records no such span, as
one older than the spans does not, reads ``None``."""

import math
import statistics

from hetu_tpu.obs import tracing

_given_until = -math.inf       # the end of the last span given to a run


def traced_spans(facts: dict) -> list:
    """This run's spans with a complete chain of parents, found once a run
    and kept in ``facts``."""
    global _given_until
    if "program_spans" not in facts:
        window_end = max((b for spans in facts.get("spans", {}).values()
                          for _, b in spans), default=-math.inf)
        spans = since(tracing.get_tracer().spans,
                      max(window_end, _given_until))
        _given_until = max([_given_until] + [s.end_time for s in spans])
        facts["program_spans"] = spans
    return facts["program_spans"]


def since(spans, after: float) -> list:
    """The spans that begin at or after ``after`` and whose parents, up to
    a root, do too."""
    kept = {s.span_id: s for s in spans if s.start >= after}
    whole = {}

    def rooted(s) -> bool:
        if s.span_id not in whole:
            whole[s.span_id] = s.parent_id is None or (
                s.parent_id in kept and rooted(kept[s.parent_id]))
        return whole[s.span_id]

    return [s for s in kept.values() if rooted(s)]


def values(spans, params: dict) -> list:
    """One value, in seconds, for each span read or, with ``per``, for each
    ancestor of that name."""
    by_id = {s.span_id: s for s in spans}

    def ancestors(s):
        while s.parent_id is not None:
            s = by_id[s.parent_id]
            yield s

    worth = {s.span_id: s.end_time - s.start for s in spans
             if s.name == params["span"]}
    minus, per = params.get("minus"), params.get("per")
    for s in spans if minus else ():
        if s.name.endswith(minus):
            for a in ancestors(s):
                if a.span_id in worth:
                    worth[a.span_id] -= s.end_time - s.start
    if not worth or not per:
        return list(worth.values())
    summed = {s.span_id: 0.0 for s in spans if s.name == per}
    for sid, w in worth.items():
        for a in ancestors(by_id[sid]):
            if a.span_id in summed:
                summed[a.span_id] += w
                break
    return list(summed.values())


def read(facts, reduced, params, peaks):
    got = values(traced_spans(facts), params)
    if not got:
        return None
    return 1e3 * {"mean": statistics.fmean, "max": max}[params["stat"]](got)
