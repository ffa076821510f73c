"""One stage's share of the window's stage totals (the program's own
host-clock totals of queue, prefill, decode and emit, differenced over the
window)."""


def read(facts, reduced, params, peaks):
    totals = facts.get("stage_totals")
    if not totals or sum(totals.values()) <= 0:
        return None
    return 100.0 * totals[params["stage"]] / sum(totals.values())
