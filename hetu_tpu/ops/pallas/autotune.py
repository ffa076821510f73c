"""Persistent kernel-autotune DATABASE for the Pallas kernels.

``_auto_blocks`` (flash.py) is a HEURISTIC table swept by hand on a v5e at
head_dim 64 (plus two d=128 points) — every other (seq, head_dim, device)
combination runs on extrapolation, and the fused-LN / LM-head / paged-decode
kernels each carried their own frozen block constants.  This module makes
the sweep a framework feature instead of a round-artifact: one on-disk JSON
database keyed by ``(kernel, device_kind, shape-sig)`` holds the measured
winners for every tunable kernel, and each kernel's block-selection helper
consults it at trace time (shapes are static under jit, so a lookup is a
plain dict hit).  Saves are **merge-on-save under an exclusive lock** —
the writer re-reads the disk copy, folds its new entries in, and publishes
through ``exec/checkpoint._atomic_write_bytes`` — so a fleet of gang
workers tuning concurrently can never torn-write or clobber each other's
entries (the previous bare ``read_text``/``write_text`` read-modify-write
lost the race loser's whole merge).

Covered kernels and their signatures:

=============  =======================  =============================
kernel         shape-sig                entry fields
=============  =======================  =============================
flash          ``{Sq}x{Sk}|d{D}|c{0/1}``  block_q, block_k
fused_ln       ``T{T}|D{D}|s{streams}``   block_rows
lm_head        ``N{N}|E{E}|V{V}``         block_n, block_v
paged_decode   ``h{H}|d{D}|p{page}``      head_block
=============  =======================  =============================

Every lookup and save is counted in the ``hetu_tune_*`` obs family
(hits/misses/retunes, labeled by kernel), so a fleet cold-start that is
silently re-tuning shows up in /metrics instead of as mystery latency.

Measurement uses the differenced-scan timer (time a scan of n1 and n2
chained iterations and divide the delta — the fixed per-dispatch host cost
cancels in the difference); see ``autotune_flash_blocks``.

Reference parity note: the reference has no Pallas kernels and no tuner;
the closest machinery is HetuSimulator's persistent op-time cache
(reference python/hetu/profiler.py:609-877), whose cache-keyed-by-device
design this follows (as does parallel/autoparallel/profiler.py).

Usage (explicit, outside jit — measurement never happens implicitly at
trace time):

    from hetu_tpu.ops.pallas import autotune_flash_blocks
    autotune_flash_blocks(512, 512, 128, causal=True)   # once per shape
    # ... flash_attention / flash_attn_fn now use the measured blocks

The DB location is ``HETU_TPU_TUNE_CACHE`` (default
``~/.cache/hetu_tpu_tune_db.json``).  It lives outside the checkout, so it
is empty on a fresh machine and every kernel must be correct, and the smoke
must pass, on its heuristic blocks alone.
"""

from __future__ import annotations

import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.core.runtime import pallas_interpret

__all__ = ["autotune_flash_blocks", "autotune_lm_head_blocks",
           "autotune_paged_decode", "autotune_fused_ln_rows",
           "tuned_blocks", "tuned_entry", "record_entry",
           "clear_tune_cache"]

_CACHE_ENV = "HETU_TPU_TUNE_CACHE"
_DEFAULT_CACHE = pathlib.Path.home() / ".cache" / "hetu_tpu_tune_db.json"
_mem_cache: dict | None = None
# entries recorded with save=False: an overlay re-applied after every
# disk reload, so an ephemeral tune survives a later saving tune's cache
# invalidation for the life of the process
_unsaved: dict = {}
_tune_metrics = None


def _tune_m():
    """Lazily-registered ``hetu_tune_*`` counter family (kernel-labeled):
    cache hits/misses at trace-time lookups and retunes (an existing entry
    re-measured and overwritten).  All no-ops when obs is disabled."""
    global _tune_metrics
    if _tune_metrics is None:
        from hetu_tpu.obs import registry as _obs
        reg = _obs.get_registry()
        _tune_metrics = {
            "hits": reg.counter(
                "hetu_tune_hits_total",
                "autotune DB lookups served from a measured entry",
                ("kernel",)),
            "misses": reg.counter(
                "hetu_tune_misses_total",
                "autotune DB lookups that fell through to the heuristic "
                "(cold-start retuning territory)", ("kernel",)),
            "retunes": reg.counter(
                "hetu_tune_retunes_total",
                "saves that overwrote an existing measured entry",
                ("kernel",)),
        }
    return _tune_metrics


def _cache_path() -> pathlib.Path:
    return pathlib.Path(os.environ.get(_CACHE_ENV) or _DEFAULT_CACHE)


def _device_kind() -> str:
    return str(getattr(jax.devices()[0], "device_kind", "cpu"))


def _full_key(kernel: str, sig: str, kind: str | None = None) -> str:
    return f"{kernel}|{kind or _device_kind()}|{sig}"


def _key(Sq: int, Sk: int, D: int, causal: bool, kind: str | None) -> str:
    """Flash entry key (kept for the flash tuner and its tests)."""
    return _full_key("flash", f"{Sq}x{Sk}|d{D}|c{int(bool(causal))}", kind)


def _load() -> dict:
    global _mem_cache
    if _mem_cache is None:
        try:
            _mem_cache = json.loads(_cache_path().read_text())
        except (OSError, ValueError):
            _mem_cache = {}
        _mem_cache.update(_unsaved)
    return _mem_cache


def clear_tune_cache() -> None:
    """Drop the whole in-memory cache, unsaved entries included (tests;
    a changed cache file re-loads)."""
    global _mem_cache
    _mem_cache = None
    _unsaved.clear()


def _invalidate_memo() -> None:
    """Force the next _load() to re-read disk, KEEPING the save=False
    overlay (the saving path's invalidation must not evict ephemeral
    tunes)."""
    global _mem_cache
    _mem_cache = None


def _locked_merge_save(updates: dict) -> None:
    """Publish ``updates`` into the on-disk DB: take an exclusive lock on
    a sibling ``.lock`` file, re-read the disk copy (another process — or
    an earlier tune in this one — may have written entries since our
    ``_load`` memoized), fold the updates in, and atomically replace via
    the checkpoint writer's tmp-write+fsync+rename.  Concurrent tuners
    serialize on the lock, so no merge is ever lost and no reader ever
    sees a torn file."""
    from hetu_tpu.exec.checkpoint import _atomic_write_bytes
    path = _cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    lock = path.with_name(path.name + ".lock")
    lf = open(lock, "a+b")
    try:
        try:
            import fcntl
            fcntl.flock(lf, fcntl.LOCK_EX)
            locked = True
        except ImportError:  # non-POSIX: no advisory lock exists
            locked = False
        try:
            cache = json.loads(path.read_text())
        except (OSError, ValueError):
            cache = {}
        cache.update(updates)
        payload = json.dumps(cache, indent=1, sort_keys=True).encode()
        if locked:
            _atomic_write_bytes(str(path), payload)
        else:
            # unlocked writers may interleave their read-modify-writes
            # (last merge wins), but a per-PID tmp keeps every published
            # file untorn — a SHARED tmp name would let two writers
            # truncate each other mid-write and publish garbage
            tmp = path.with_name(path.name + f".tmp.{os.getpid()}")
            tmp.write_bytes(payload)
            tmp.replace(path)
    finally:
        lf.close()
    _invalidate_memo()


def tuned_entry(kernel: str, sig: str, *, kind: str | None = None,
                count: bool = True) -> dict | None:
    """The measured entry for ``(kernel, device kind, sig)``, or None.
    Consulted by each kernel's block-selection helper at trace time."""
    hit = _load().get(_full_key(kernel, sig, kind))
    if count:
        m = _tune_m()
        (m["hits"] if hit else m["misses"]).labels(kernel=kernel).inc()
    return hit


def record_entry(kernel: str, sig: str, entry: dict, *,
                 kind: str | None = None, save: bool = True) -> None:
    """Adopt a measured ``entry`` for ``(kernel, device kind, sig)`` —
    into the in-memory cache immediately and (``save=True``) into the
    on-disk DB under the exclusive-lock merge."""
    full = _full_key(kernel, sig, kind)
    if _load().get(full) is not None:
        _tune_m()["retunes"].labels(kernel=kernel).inc()
    if save:
        # a newer saved entry supersedes any ephemeral one for this key
        _unsaved.pop(full, None)
        _locked_merge_save({full: entry})
    else:
        _unsaved[full] = entry
    _load()[full] = entry
    # calibration seam: a tuned entry is a measured kernel timing — fold
    # it into the installed profile store (one global load + branch when
    # none is; the sentinel then catches a retune landing >15% slower
    # than the stored baseline)
    from hetu_tpu.obs.calibration import note_tune
    note_tune(kernel, sig, entry, device_kind=kind or _device_kind())


def tuned_blocks(Sq: int, Sk: int, D: int,
                 causal: bool = False) -> tuple[int, int] | None:
    """The measured (block_q, block_k) for this shape on this device kind,
    or None if never autotuned.  Consulted by flash._block_sizes at trace
    time (shapes are static under jit, so this is a plain dict lookup).
    Falls back to the causal-complement entry: the block-size optimum
    tracks the (seq, head_dim) footprint, not the mask.

    A complement fallback is *tagged*: a copy lands under the exact-mask
    key in the in-memory cache with ``complement_fallback: True``, so
    cache dumps show which masks are running on borrowed measurements —
    and since the tag only lives in memory (the save path merges from
    disk and drops the memo), a later exact-mask ``autotune_flash_blocks``
    supersedes it."""
    cache = _load()
    m = _tune_m()
    hit = cache.get(_key(Sq, Sk, D, causal, None))
    if hit:
        m["hits"].labels(kernel="flash").inc()
        return int(hit["block_q"]), int(hit["block_k"])
    comp = cache.get(_key(Sq, Sk, D, not causal, None))
    if comp:
        cache[_key(Sq, Sk, D, causal, None)] = {
            "block_q": int(comp["block_q"]),
            "block_k": int(comp["block_k"]),
            "complement_fallback": True}
        m["hits"].labels(kernel="flash").inc()
        return int(comp["block_q"]), int(comp["block_k"])
    m["misses"].labels(kernel="flash").inc()
    return None


# ---------------------------------------------------------------------------
# measurement machinery
# ---------------------------------------------------------------------------

def _diff_time(step_fn, carry, n1: int, n2: int) -> float:
    """Per-iteration seconds of ``carry = step_fn(carry)`` via a
    differenced scan: time a jitted scan of n1 and n2 chained iterations
    and divide the delta — the fixed dispatch cost cancels.  The carry
    must keep every output of interest live so XLA cannot dead-code-
    eliminate the measured work."""
    def chain(n):
        def body(c, _):
            return step_fn(c), ()
        return jax.jit(lambda c: jax.lax.scan(body, c, None, length=n)[0])

    run1, run2 = chain(n1), chain(n2)

    def t(run):
        t0 = time.perf_counter()
        jax.block_until_ready(run(carry))
        return time.perf_counter() - t0

    t(run1), t(run2)  # compile both
    t(run1), t(run2)  # throwaway pair (first post-compile run skews)
    d = [(t(run2) - t(run1)) / (n2 - n1) for _ in range(3)]
    med = float(np.median(d))
    if med <= 0:
        # a latency spike on the short-chain side can make the difference
        # negative; persisting that would let a garbage candidate win the
        # grid and poison every later trace of this shape
        raise RuntimeError(f"nonpositive differenced timing {d} (noise)")
    return med


def _sweep(candidates, measure, *, budget_s: float | None,
           verbose: bool, tag: str) -> dict:
    """Measure each candidate (skipping the rest once ``budget_s`` is
    exceeded, keeping best-so-far); returns the {candidate_str: seconds |
    'failed: ...' | 'skipped: budget'} table."""
    table = {}
    t_start = time.perf_counter()
    for cand in candidates:
        name = "x".join(str(c) for c in cand) if isinstance(
            cand, tuple) else str(cand)
        if (budget_s is not None and table
                and time.perf_counter() - t_start > budget_s):
            table[name] = "skipped: budget"
            continue
        try:
            table[name] = measure(cand)
        except Exception as e:  # candidate rejected by Mosaic/VMEM
            table[name] = f"failed: {str(e)[:120]}"
        if verbose:
            print(f"autotune[{tag}]: {name} -> {table[name]}")
    # the sweep's wall cost is lost training time: journal it (kind
    # "retune", duration_s) — a no-op when no journal is installed.  The
    # goodput "retune" bucket is billed from this event alone, via
    # GoodputMeter.ingest, exactly like checkpoint_saved: one billing
    # path, so a driver that polls the journal into its meter never
    # double-counts a sweep.  Each measured candidate compiled two
    # differenced-scan programs (_measure_differenced's run1/run2); the
    # retune record reports that under `compiles` and the count lands in
    # hetu_compile_total{site="tune.<kernel>"} — NOT as per-compile
    # journal events, whose duration_s would double-bill the goodput
    # compile bucket on top of retune.
    dt = time.perf_counter() - t_start
    kernel = tag.split()[0]
    measured = sum(1 for v in table.values() if isinstance(v, float))
    from hetu_tpu.obs import journal as _journal
    from hetu_tpu.obs import registry as _registry
    _journal.record("retune", kernel=kernel, candidates=len(table),
                    compiles=2 * measured, duration_s=round(dt, 6))
    if measured and _registry.enabled():
        from hetu_tpu.obs import compile as _ocompile
        _ocompile._compile_m()["compiles"].labels(
            site=f"tune.{kernel}").inc(2 * measured)
    return table


def _best(table: dict, what: str):
    timed = {k: v for k, v in table.items() if isinstance(v, float)}
    if not timed:
        raise RuntimeError(f"no {what} candidate ran: {table}")
    return min(timed, key=timed.get)


# ---------------------------------------------------------------------------
# flash
# ---------------------------------------------------------------------------

def _candidate_grid(Sq: int, Sk: int, D: int, interpret: bool):
    """128-aligned divisors of the (padded) sequence, VMEM-capped — the
    same constraints _block_sizes enforces.  Interpreter mode (CPU tests)
    lifts the 128-alignment rule like the kernel itself does."""
    def divisors(S, cands):
        return [c for c in cands if c <= S and S % c == 0]

    if interpret:
        qs = divisors(Sq, [max(1, Sq // 2), Sq]) or [Sq]
        ks = divisors(Sk, [max(1, Sk // 2), Sk]) or [Sk]
    else:
        vmem_cap = max(128, (65536 // max(D, 1)) // 128 * 128)
        qs = divisors(Sq, [128, 256, 512])
        ks = [b for b in divisors(Sk, [128, 256, 512, 1024])
              if b <= vmem_cap]
    return [(bq, bk) for bq in qs for bk in ks]


def _time_fwd_bwd(bq: int, bk: int, q, k, v, causal: bool, interpret: bool,
                  n1: int, n2: int) -> float:
    """Per-iteration seconds of flash fwd+bwd at (bq, bk) via the
    differenced scan.  ALL of dq/dk/dv stay live (folded into the carry)
    so XLA cannot dead-code-eliminate any backward matmul."""
    from hetu_tpu.ops.pallas.flash import flash_attention_bhsd

    def loss(q, k, v):
        return flash_attention_bhsd(
            q, k, v, causal=causal, block_q=bq, block_k=bk,
            interpret=interpret).astype(jnp.float32).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2))

    def step(c):
        q, k, v = c
        dq, dk, dv = grad(q, k, v)
        eps = jnp.asarray(1e-6, q.dtype)
        return (q + eps * dq.astype(q.dtype),
                k + eps * dk.astype(k.dtype),
                v + eps * dv.astype(v.dtype))

    return _diff_time(step, (q, k, v), n1, n2)


def autotune_flash_blocks(Sq: int, Sk: int, D: int, *, causal: bool = False,
                          batch: int = 4, heads: int = 8,
                          dtype=jnp.bfloat16, interpret: bool | None = None,
                          n1: int = 4, n2: int = 12, save: bool = True,
                          budget_s: float | None = None,
                          verbose: bool = False) -> dict:
    """Measure the candidate (block_q, block_k) grid for this shape on the
    live device and persist the winner.  Returns
    {"block_q", "block_k", "table": {"bqxbk": seconds, ...}}.

    Run OUTSIDE jit; costs one compile per candidate (a handful — the
    grid is the 128-aligned divisors under the VMEM cap).  ``budget_s``
    stops measuring further candidates once exceeded (keeps the
    best-so-far; un-measured candidates are marked "skipped: budget").
    """
    if interpret is None:
        interpret = pallas_interpret()
    if not interpret and (Sq < 128 or Sk < 128 or Sq % 128 or Sk % 128):
        # fail NOW with the constraint named, not after the whole grid
        # comes back empty as 'no flash block candidate ran: {}'
        raise ValueError(
            f"autotune_flash_blocks: Sq={Sq}, Sk={Sk} must be multiples "
            f"of 128 (and >= 128) on TPU — the Pallas flash kernel's "
            f"block grid is 128-lane aligned, so no candidate block size "
            f"can divide this shape; pad the sequence to a 128 multiple "
            f"or pass interpret=True for a CPU-interpreter sweep")
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((batch, heads, Sq, D)) * 0.1, dtype)
    q = mk()
    k, v = (jnp.asarray(rng.standard_normal((batch, heads, Sk, D)) * 0.1,
                        dtype) for _ in range(2))

    table = _sweep(
        _candidate_grid(Sq, Sk, D, interpret),
        lambda c: _time_fwd_bwd(c[0], c[1], q, k, v, causal, interpret,
                                n1, n2),
        budget_s=budget_s, verbose=verbose, tag=f"flash {Sq}x{Sk} d{D}")
    best = _best(table, "flash block")
    bq, bk = (int(x) for x in best.split("x"))
    entry = {"block_q": bq, "block_k": bk, "table": table,
             "measured_at": {"batch": batch, "heads": heads,
                             "dtype": str(jnp.dtype(dtype))}}
    record_entry("flash", f"{Sq}x{Sk}|d{D}|c{int(bool(causal))}", entry,
                 save=save)
    return entry


# ---------------------------------------------------------------------------
# lm_head
# ---------------------------------------------------------------------------

def autotune_lm_head_blocks(N: int, E: int, V: int, *, dtype=jnp.bfloat16,
                            interpret: bool | None = None,
                            n1: int = 2, n2: int = 6, save: bool = True,
                            budget_s: float | None = None,
                            verbose: bool = False) -> dict:
    """Measure (block_n, block_v) for the fused LM-head CE kernel fwd+bwd
    at this (tokens, embed, vocab) shape and persist the winner."""
    from hetu_tpu.ops.pallas.lm_head import lm_head_cross_entropy_pallas
    if interpret is None:
        interpret = pallas_interpret()
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((N, E)) * 0.1, dtype)
    w = jnp.asarray(rng.standard_normal((E, V)) * 0.1, dtype)
    y = jnp.asarray(rng.integers(0, V, (N,)), jnp.int32)

    if interpret:
        cands = [(max(8, N // 2), max(128, V // 2)), (N, V)]
    else:
        cands = [(bn, bv) for bn in (256, 512, 1024) if N % bn == 0
                 for bv in (512, 1024, 2048) if V % bv == 0] or [(512, 1024)]

    def measure(c):
        bn, bv = c

        def loss(h, w):
            return lm_head_cross_entropy_pallas(
                h, w, y, block_n=bn, block_v=bv, interpret=interpret).sum()

        grad = jax.grad(loss, argnums=(0, 1))

        def step(carry):
            h, w = carry
            dh, dw = grad(h, w)
            eps = jnp.asarray(1e-6, h.dtype)
            return h + eps * dh.astype(h.dtype), w + eps * dw.astype(w.dtype)

        return _diff_time(step, (h, w), n1, n2)

    table = _sweep(cands, measure, budget_s=budget_s, verbose=verbose,
                   tag=f"lm_head N{N} V{V}")
    best = _best(table, "lm_head block")
    bn, bv = (int(x) for x in best.split("x"))
    entry = {"block_n": bn, "block_v": bv, "table": table}
    record_entry("lm_head", f"N{N}|E{E}|V{V}", entry, save=save)
    return entry


# ---------------------------------------------------------------------------
# paged_decode
# ---------------------------------------------------------------------------

def autotune_paged_decode(H: int, D: int, page_size: int, *,
                          batch: int = 8, pages_per_seq: int = 32,
                          dtype=jnp.bfloat16,
                          interpret: bool | None = None,
                          n1: int = 4, n2: int = 12, save: bool = True,
                          budget_s: float | None = None,
                          verbose: bool = False) -> dict:
    """Measure the head-block size for the paged-decode attention kernel
    (how many heads each grid step loads per page: VMEM footprint vs grid
    parallelism) and persist the winner."""
    from hetu_tpu.ops.pallas.paged_decode import paged_decode_attention
    if interpret is None:
        interpret = pallas_interpret()
    rng = np.random.default_rng(0)
    P = 1 + batch * pages_per_seq
    q = jnp.asarray(rng.standard_normal((batch, H, D)) * 0.1, dtype)
    k = jnp.asarray(rng.standard_normal(
        (P, page_size, H, D)) * 0.1, dtype)
    v = jnp.asarray(rng.standard_normal(
        (P, page_size, H, D)) * 0.1, dtype)
    tables = jnp.asarray(
        1 + np.arange(batch * pages_per_seq).reshape(batch, pages_per_seq),
        jnp.int32)
    lengths = jnp.full((batch,), pages_per_seq * page_size, jnp.int32)
    cands = [hb for hb in (1, 2, 4, 8, 16) if hb <= H and H % hb == 0]

    def measure(hb):
        def step(q):
            return paged_decode_attention(
                q, k, v, tables, lengths, head_block=hb,
                interpret=interpret).astype(q.dtype)

        return _diff_time(step, q, n1, n2)

    table = _sweep(cands, measure, budget_s=budget_s, verbose=verbose,
                   tag=f"paged_decode h{H} d{D}")
    hb = int(_best(table, "paged_decode head-block"))
    entry = {"head_block": hb, "table": table,
             "measured_at": {"batch": batch, "pages_per_seq": pages_per_seq,
                             "dtype": str(jnp.dtype(dtype))}}
    record_entry("paged_decode", f"h{H}|d{D}|p{page_size}", entry, save=save)
    return entry


# ---------------------------------------------------------------------------
# fused_ln
# ---------------------------------------------------------------------------

def autotune_fused_ln_rows(T: int, D: int, *, dtype=jnp.bfloat16,
                           interpret: bool | None = None,
                           n1: int = 4, n2: int = 12, save: bool = True,
                           budget_s: float | None = None,
                           verbose: bool = False) -> dict:
    """Measure the rows-per-block for the fused residual+dropout+LN kernel
    fwd+bwd at this (tokens, hidden) shape and persist the winner.  The
    entry is recorded per backward stream count (the tighter budget), so
    one measurement covers both directions."""
    from hetu_tpu.ops.pallas.fused_ln import fused_residual_dropout_ln
    if interpret is None:
        interpret = pallas_interpret()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, D)) * 0.1, dtype)
    y = jnp.asarray(rng.standard_normal((T, D)) * 0.1, dtype)
    scale = jnp.ones((D,), jnp.float32)
    bias = jnp.zeros((D,), jnp.float32)
    cands = [bt for bt in (8, 16, 32, 64, 128, 256, 512)
             if bt <= T and T % bt == 0]

    def measure(bt):
        for n in (4, 6):  # candidate-under-test visible to _pick_block:
            # poke the memo directly — record_entry would tick the
            # retunes counter once per candidate swap
            _load()[_full_key("fused_ln", f"T{T}|D{D}|s{n}")] = {
                "block_rows": int(bt)}

        def loss(x, y):
            return fused_residual_dropout_ln(
                x, y, scale, bias, interpret=interpret
            ).astype(jnp.float32).sum()

        grad = jax.grad(loss, argnums=(0, 1))

        def step(carry):
            x, y = carry
            dx, dy = grad(x, y)
            eps = jnp.asarray(1e-6, x.dtype)
            return x + eps * dx.astype(x.dtype), y + eps * dy.astype(y.dtype)

        return _diff_time(step, (x, y), n1, n2)

    try:
        table = _sweep(cands, measure, budget_s=budget_s, verbose=verbose,
                       tag=f"fused_ln T{T} D{D}")
        bt = int(_best(table, "fused_ln row-block"))
    finally:
        # drop the sweep's in-memory candidate entries whatever happened
        # — a failed sweep must not leave the LAST candidate silently
        # steering every later _pick_block in this process (memo-only
        # invalidation: unrelated save=False entries survive)
        _invalidate_memo()
    entry = {"block_rows": bt, "table": table}
    for n in (4, 6):  # forward streams 4 row blocks, backward 6
        record_entry("fused_ln", f"T{T}|D{D}|s{n}", dict(entry), save=save)
    return entry
