"""CI gate for the 16-device dryrun tier (configs F / F2).

The driver only ever calls ``dryrun_multichip(8)``, so the pp=4×tp=2×dp
composition and the planner-searching-at-16 path (``__graft_entry__.py``
config F/F2) could silently rot between rounds.  This slow-tier test
subprocess-runs the real entry point at n=16 — the same command a human
would use (``python __graft_entry__.py 16``) — and asserts every config
through F2 reports a finite loss.

Reference scale story: SURVEY §2.4 (the reference validates multi-worker
compositions only on live clusters; here the virtual CPU mesh is the
only multi-chip gate, so it must be exercised by CI, not by hand).
"""

from __future__ import annotations

import os
import re
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_16_device_tier_runs_all_configs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=16")
    out = subprocess.run(
        [sys.executable, "__graft_entry__.py", "16"], env=env,
        capture_output=True, text=True, timeout=1500, cwd=_REPO)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])

    # every tier config must have printed, with a finite loss (the entry
    # itself asserts finiteness before printing; nan/inf would rc!=0 —
    # this re-checks the printed value so a silent format drift fails too)
    losses = dict(re.findall(r"dryrun (\w+) .*loss=(\S+)", out.stdout))
    for config in ("A", "B", "C", "D", "E", "G", "F", "F2"):
        assert config in losses, (config, out.stdout)
        v = float(losses[config])
        assert v == v and abs(v) < 1e6, (config, losses[config])
