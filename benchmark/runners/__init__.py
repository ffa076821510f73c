"""Runners: ``run(cell, seed, seconds, tracer, t0, devices, peaks)``.

A cell's file names its runner.  A runner builds the system from the seed,
warms it up, measures the window through the entry a user calls, frees the
system, runs the reference and hands back a ``harness.Outcome``."""
