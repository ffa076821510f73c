"""Shared by the benchmark's tests: where the tiny cells live, and one run
of each of them a session."""

import os
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data")


def run_tiny(workload, seed=5, seconds=0.5, trace=False, root=TINY):
    from benchmark import run
    return run.run_cell(root, workload, seed, seconds, trace,
                        need_chip=False, t0=time.perf_counter())


@pytest.fixture(scope="session")
def tiny_lines():
    """workload -> (untraced line, traced line), each run once."""
    cache = {}

    def get(workload):
        if workload not in cache:
            cache[workload] = (run_tiny(workload),
                               run_tiny(workload, seed=2**31 + 7,
                                        trace=True))
        return cache[workload]
    return get
