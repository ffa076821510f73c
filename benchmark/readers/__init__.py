"""Readers of per-layer metrics: ``read(facts, reduced, params, peaks)``.

``facts`` is what the runner counted and timed itself, ``reduced`` the
reduction of the traced stretch (``benchmark.trace.Reduced``) or ``None``.
A reader that finds nothing to read returns ``None`` and the metric is
left out of the line; it never returns 0 for a share of a peak."""
