"""The device's idle share of the traced stretch: 1 - busy union / window."""


def read(facts, reduced, params, peaks):
    if reduced is None or reduced.idle_share is None:
        return None
    return 100.0 * reduced.idle_share
