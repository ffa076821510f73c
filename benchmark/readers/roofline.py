"""A kernel's share of its roofline over the traced stretch.

``params``: ``all_of`` / ``any_of`` say which device events are the
kernel's (strings that the event's text holds, regexes one of which it
matches); ``work`` names the runner's count of the operations and bytes
that the kernel's calls of that stretch need (``benchmark.counts``).  The
least time for that work, over the summed time of the events."""

from benchmark import counts
from benchmark.harness import say


def read(facts, reduced, params, peaks):
    work = facts.get("kernel_work", {}).get(params["work"])
    if reduced is None or not work or not work[0]:
        return None
    seconds, events = reduced.kernel_seconds(
        all_of=params.get("all_of", ()), any_of=params.get("any_of", ()))
    if events == 0 or seconds <= 0:
        return None
    least, bound = counts.roofline_seconds(work[0], work[1], peaks)
    say(f"roofline {params['work']}: {events} events, {seconds:.6f} s on "
        f"the device, least {least:.6f} s, {bound}-bound")
    return 100.0 * least / seconds
