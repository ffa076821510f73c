"""A number that the runner took over the whole measured window and that
is no end-to-end metric of the cell: a steadier statistic kept beside the
tail that is bounded, or what the traffic holds on the chip."""


def read(facts, reduced, params, peaks):
    value = facts.get("window", {}).get(params["number"])
    return None if value is None else float(value)
