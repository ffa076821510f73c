"""The shared persistent autotune database (ops/pallas/autotune.py):
cross-kernel entries, cross-process round-trip, concurrent writers
merging without loss (the locked atomic save), heuristic override in each
consumer kernel, and the ``hetu_tune_*`` observability family.
"""

import json
import multiprocessing

import pytest

from hetu_tpu import obs
from hetu_tpu.ops.pallas import autotune as at

pytestmark = pytest.mark.pallas


@pytest.fixture
def tune_db(tmp_path, monkeypatch):
    path = tmp_path / "tune_db.json"
    monkeypatch.setenv(at._CACHE_ENV, str(path))
    at.clear_tune_cache()
    yield path
    at.clear_tune_cache()


def test_record_and_lookup_multi_kernel(tune_db):
    at.record_entry("lm_head", "N64|E32|V256", {"block_n": 32, "block_v": 128})
    at.record_entry("paged_decode", "h4|d64|p16", {"head_block": 2})
    at.record_entry("fused_ln", "T128|D256|s6", {"block_rows": 64})
    # all three kernels' entries live in ONE file, namespaced by kernel
    disk = json.loads(tune_db.read_text())
    assert {k.split("|")[0] for k in disk} == {"lm_head", "paged_decode",
                                              "fused_ln"}
    # a fresh process (memo cleared) sees them
    at.clear_tune_cache()
    assert at.tuned_entry("lm_head", "N64|E32|V256")["block_n"] == 32
    assert at.tuned_entry("paged_decode", "h4|d64|p16")["head_block"] == 2
    assert at.tuned_entry("fused_ln", "T128|D256|s6")["block_rows"] == 64
    assert at.tuned_entry("flash", "8x8|d4|c0") is None


def _writer(path, kernel, n, out_q):
    """Subprocess body: hammer n entries into the shared DB."""
    import os
    os.environ[at._CACHE_ENV] = path
    at.clear_tune_cache()
    for i in range(n):
        at.record_entry(kernel, f"sig{i}", {"i": i, "by": kernel})
    out_q.put("done")


def test_concurrent_writers_merge_without_loss(tune_db):
    """Acceptance: two processes recording entries concurrently into the
    same DB file — every entry from BOTH survives (exclusive-lock merge
    through the atomic writer; the old bare read-modify-write lost the
    race loser's whole batch)."""
    n = 25
    # spawn, not fork: the parent has initialized (multithreaded) jax
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    ps = [ctx.Process(target=_writer, args=(str(tune_db), kern, n, q))
          for kern in ("lm_head", "paged_decode")]
    for p in ps:
        p.start()
    for p in ps:
        assert q.get(timeout=60) == "done"
    for p in ps:
        p.join(30)
        assert p.exitcode == 0
    disk = json.loads(tune_db.read_text())
    for kern in ("lm_head", "paged_decode"):
        for i in range(n):
            key = f"{kern}|{at._device_kind()}|sig{i}"
            assert disk[key] == {"i": i, "by": kern}, key
    # the DB is valid JSON (no torn write) and the lock file is benign
    assert len(disk) == 2 * n


def test_consumers_pick_up_entries(tune_db):
    """Each kernel's block-selection helper prefers the DB: fused_ln row
    blocks, lm_head (via its None-default path), paged_decode head_block
    (exercised end to end: a tuned head_block of 1 still runs and matches
    — see test_paged_decode for the numeric invariance)."""
    from hetu_tpu.ops.pallas.fused_ln import _pick_block
    heur = _pick_block(128, 256, 6)
    tuned = 32 if heur != 32 else 16
    at.record_entry("fused_ln", "T128|D256|s6", {"block_rows": tuned})
    assert _pick_block(128, 256, 6) == tuned
    # an entry that no longer divides T falls back to the heuristic
    at.record_entry("fused_ln", "T120|D256|s6", {"block_rows": 32})
    assert _pick_block(120, 256, 6) != 32

    from hetu_tpu.ops.pallas.paged_decode import _head_block
    at.record_entry("paged_decode", "h4|d8|p4", {"head_block": 2})
    assert _head_block(4, 8, 4, None) == 2
    assert _head_block(4, 8, 4, 4) == 4  # explicit arg outranks the DB
    at.record_entry("paged_decode", "h6|d8|p4", {"head_block": 4})
    assert _head_block(6, 8, 4, None) == 6  # non-divisor entry ignored


def test_tune_metrics_exposed(tune_db):
    """hits/misses/retunes ride the hetu_tune_* counter family and appear
    in the Prometheus exposition."""
    reg = obs.get_registry()
    s0 = reg.snapshot()
    at.tuned_entry("lm_head", "Nx|missing")               # miss
    at.record_entry("lm_head", "Nx|missing", {"block_n": 8, "block_v": 128})
    at.tuned_entry("lm_head", "Nx|missing")               # hit
    at.record_entry("lm_head", "Nx|missing", {"block_n": 16,
                                              "block_v": 128})  # retune
    d = reg.delta(reg.snapshot(), s0)
    assert d['hetu_tune_misses_total{kernel="lm_head"}'] == 1
    assert d['hetu_tune_hits_total{kernel="lm_head"}'] == 1
    assert d['hetu_tune_retunes_total{kernel="lm_head"}'] == 1
    text = reg.render_prometheus()
    for name in ("hetu_tune_hits_total", "hetu_tune_misses_total",
                 "hetu_tune_retunes_total"):
        assert name in text
