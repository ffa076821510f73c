"""Mean length in milliseconds of the benchmark's own spans of one name:
all their time over all of them."""


def read(facts, reduced, params, peaks):
    spans = facts.get("spans", {}).get(params["span"])
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
