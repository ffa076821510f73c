"""Measure the PS priority channel: gradient-push latency under bulk
prefetch load, two-channel vs single shared connection.

The reference ships a priority-scheduled van (ps-lite p3_van.h:12) so
gradient pushes are not starved by bulk transfers.  The TCP client's
portable equivalent is a second independently-locked connection for
pushes/control (native/embed/ps_net.cpp Client).  This benchmark drives one
worker-shaped load: a background thread hammers big prefetch pulls while
the foreground times small gradient pushes — the contention pattern of the
CTR hybrid path (prefetch overlap + per-step SparsePush).

    python examples/bench_ps_priority.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, ".")


def run_mode(single_channel: bool) -> dict:
    """Run the mixed-load probe in a fresh process (the channel mode is
    fixed at connect time)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               HETU_PS_SINGLE_CHANNEL="1" if single_channel else "0")
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300)
    if out.returncode != 0:
        raise RuntimeError(out.stdout + out.stderr)
    line = next(l for l in out.stdout.splitlines() if l.startswith("{"))
    return json.loads(line)


_PROBE = """
import json, sys, threading, time
import numpy as np
sys.path.insert(0, ".")
from hetu_tpu.embed.net import EmbeddingServer, RemoteEmbeddingTable

ROWS, DIM = 8192, 256          # 8 MB of bulk payload per prefetch pull
PUSH_N, PUSHES = 32, 300

with EmbeddingServer() as srv:
    t = RemoteEmbeddingTable(f"127.0.0.1:{srv.port}", 1, ROWS, DIM,
                             optimizer="sgd", lr=0.1)
    stop = threading.Event()
    all_rows = np.arange(ROWS)

    def bulk_load():                      # prefetch-shaped background load
        while not stop.is_set():
            t.pull(all_rows)

    th = threading.Thread(target=bulk_load)
    th.start()
    time.sleep(0.2)                       # load in steady state
    ids = np.arange(PUSH_N)
    g = np.ones((PUSH_N, DIM), np.float32)
    lat = []
    for _ in range(PUSHES):
        t0 = time.perf_counter()
        t.push(ids, g)                    # gradient push under load
        lat.append(time.perf_counter() - t0)
    stop.set()
    th.join()
    lat = np.asarray(lat) * 1e3
    print(json.dumps({
        "push_ms_p50": round(float(np.percentile(lat, 50)), 3),
        "push_ms_p99": round(float(np.percentile(lat, 99)), 3),
        "push_ms_max": round(float(lat.max()), 3),
    }))
"""


def main():
    two = run_mode(single_channel=False)
    one = run_mode(single_channel=True)
    print(f"{'':24s}{'two-channel':>14s}{'single-channel':>16s}")
    for k in ("push_ms_p50", "push_ms_p99", "push_ms_max"):
        print(f"{k:24s}{two[k]:>14.3f}{one[k]:>16.3f}")
    # the starvation effect lives in the tail: most pushes land between
    # pulls (p50 unchanged), but without the split a push occasionally
    # queues behind a full bulk response
    speedup = one["push_ms_p99"] / max(two["push_ms_p99"], 1e-9)
    print(f"\npriority channel p99 push speedup under bulk load: "
          f"{speedup:.1f}x")
    print(json.dumps({"metric": "ps_push_p99_speedup_under_load",
                      "value": round(speedup, 2), "unit": "x",
                      "two_channel": two, "single_channel": one}))


if __name__ == "__main__":
    main()
