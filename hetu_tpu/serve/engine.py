"""``ServingEngine``: the online-inference driver.

Marries the decode seams (``models/gpt.py`` ``kv_cache=``/``cache_index=``)
to the paged pool and the continuous batcher, and carries the two serving
workloads the stack trains:

- **Generation** — seeded greedy/top-k sampling over a GPT.  Prefill
  runs the bucketed gather step at ``(1, bucket_len)`` (one compile per
  prompt bucket, once per request).  Decode — the per-token hot path —
  defaults to the PAGED step at the fixed ``(num_slots, 1)`` shape: the
  Pallas paged-decode kernel (ops/pallas/paged_decode.py) attends in
  place over the pool's page tables, so the per-step contiguous
  ``(L, batch, max_len, H, D)`` gather/scatter of the whole KV history —
  the dominant decode HBM traffic at long context — never happens
  (``kv_cache.gather_view_count`` proves the decode program traces zero
  views).  Sampling fuses into the LM head
  (ops/pallas/lm_head.py ``lm_head_sample_pallas``): the decode logits
  never materialize in HBM for greedy/top-k (temperature mode streams
  its bitwise-exact gumbel field instead — a wash, not a win).
  ``paged_decode=False`` restores the gather path.  Sampling keys derive from ``(seed, request.id,
  position)`` in both paths, so a request's token stream is a pure
  function of the seed and its own prompt — independent of which
  neighbors shared its batch.  Two same-seed runs of the same schedule
  produce bitwise-identical streams; the acceptance test asserts it.

- **CTR inference** — :meth:`infer_ctr` pulls embedding rows READ-ONLY
  through the model's existing HET stores (``CacheTable`` /
  ``RemoteEmbeddingTable``): stage-then-forward, never a gradient push.
  Local ``CacheTable`` stores are flipped to ``read_only`` at engine
  construction so a miswired training step raises instead of silently
  updating the table.  Remote pulls keep riding ``embed.net._rpc`` — the
  ``exec/faults.py`` PS seams stay injectable, so a socket kill under
  load must surface as a counted redial, not a wrong answer.

Telemetry (lazily registered, all no-ops when obs is disabled): queue
depth and active-slot gauges, TTFT and per-token latency histograms,
token/request counters by outcome, tokens/s gauge; admission rejections
are journaled (``serve_reject``) and deadline expiries are counted by
stage (``hetu_serve_deadline_expired_total{stage=queued|running}``) and
journaled (``request_expired``).  **Request-scope observability**: every
request carries a :class:`~hetu_tpu.obs.reqtrace.RequestTimeline` —
spans for queue wait, admission, prefill, each decode iteration (batch
composition in the attrs), and emit, with the stage decomposition
summing to wall time exactly — finished timelines land in
``self.trace_buffer`` (ring + slowest-N exemplars, ``/trace/<id>``) and
are graded by ``self.slo`` (:class:`~hetu_tpu.obs.slo.SLOEngine`:
TTFT/TPOT/queue-age targets, burn rates, shed pressure on ``/slo``).
The three jitted step functions are compile-counting seams
(:func:`obs.compile.instrument`): ``serve.prefill_step`` /
``serve.paged_decode`` / ``serve.sample`` dispatch through jit's own C++
cache and key only the calls it misses, so ``hetu_compile_total`` is
exact and a recompile storm is a gauge.  The step programs take the
model as its flat leaves, flattened once when the engine gets it (a
tuple of arrays, which jit flattens in C++; a ``Module``'s flatten is
Python run for each of its nodes on every call).  The
clock is injectable — the deterministic tests drive a virtual clock,
production defaults to ``time.monotonic``.

**Fleet tier** (serve/fleet): ``prefix_sharing=True`` attaches a
per-engine :class:`~hetu_tpu.serve.fleet.prefix.PrefixSharer` — prompt
prefixes alias shared refcounted KV pages and prefill computes only the
unshared suffix; ``draft_model=`` swaps the decode step for
propose-and-verify speculation
(:class:`~hetu_tpu.serve.fleet.spec.SpeculativeDecoder`, paged path
only) with accepted streams bitwise identical to the non-speculative
run; a :class:`~hetu_tpu.serve.fleet.router.FleetRouter` places
requests across N engines by trie affinity and shed pressure
(``RequestHandle.shed_reason`` marks re-routable rejections).

**Disaggregated serving** (serve/fleet/disagg.py): ``role=`` splits the
fleet into prefill workers (compute-bound: prefill, sample the first
token, then MIGRATE the KV pages to a decode worker and recycle the
slot immediately) and decode workers (memory-bound: ingest verified
migration records — or re-prefill on a corrupt one — and decode without
ever being stalled by a long-prompt burst); ``colocated`` (the default)
is the classic timeslicing engine.  Because sampling keys derive from
``(seed, request id, position)`` and migration preserves
``cache_index``/lengths exactly, a migrated stream is bitwise identical
to its colocated same-seed twin — the PR 13 guarantee carried across a
worker boundary.  ``prefill_tick_cost`` enables the virtual-time
timeslice model the deterministic A/B tests drive
(``HETU_TPU_DISAGG_ROLE`` / ``HETU_TPU_DISAGG_PREFILL_COST`` back the
kwargs).

**Look-ahead**: every device program of a tick has a dispatch half and a
collect half (the fetch of its tokens and what follows from them).  The
engine that runs its own loop (:meth:`ServingEngine.start`) dispatches all
a tick has before it fetches anything, and builds the next decode step by
count while the last one's tokens are still on the device (the decode
program feeds them from there), so the device always has the next program
queued and a tick costs max(device, host), not their sum.  ``step()``
called by anyone else runs the same halves back to back and returns that
call's tokens.  Which of the two is not an option: it follows from who
drives.  Streams are bitwise the same either way; an EOS is found one
step late and that step's token for the slot is dropped
(``hetu_serve_lookahead_discarded_total``).

Deadlines: ``deadline_s`` bounds a request's total age.  A request past
its deadline while still *queued* is dropped before admission (stage
``queued``); one that exceeds it while *running* is retired at the next
scheduler tick with the tokens generated so far (stage ``running``) —
serving it further would be serving it late.  Both resolve the handle
with status ``expired`` and a human-readable ``error``.
"""

from __future__ import annotations

import math
import os
import threading
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.exec import controller as _controller
from hetu_tpu.exec import faults as _faults
from hetu_tpu.obs import compile as _compile
from hetu_tpu.obs import journal as _journal
from hetu_tpu.obs import numerics as _numerics
from hetu_tpu.obs import registry as _obs
from hetu_tpu.obs import tracing as _tracing
from hetu_tpu.obs.reqtrace import ReqTraceBuffer, RequestTimeline
from hetu_tpu.obs.routing import record_routing
from hetu_tpu.obs.slo import SLOEngine
from hetu_tpu.ops.pallas.lm_head import lm_head_sample_pallas
from hetu_tpu.ops.pallas.paged_decode import walked_steps
from hetu_tpu.ops.random import (greedy_sample, temperature_sample,
                                 top_k_sample)
from hetu_tpu.serve.batcher import (AdmissionQueueFull, AdmissionShed,
                                    ContinuousBatcher, Request,
                                    TenantQuotaExceeded)
from hetu_tpu.serve.tenant import (DEFAULT_TENANT, TenantMeter,
                                   TenantPolicy, _tenant_m)
from hetu_tpu.serve import kv_cache as _kv
from hetu_tpu.serve.kv_cache import OutOfPages

__all__ = ["ServingEngine", "RequestHandle"]

_serve_metrics = None


def _serve_m() -> dict:
    global _serve_metrics
    if _serve_metrics is None:
        reg = _obs.get_registry()
        _serve_metrics = {
            "requests": reg.counter(
                "hetu_serve_requests_total",
                "serving requests by outcome (admitted at slot placement; "
                "every submitted request ends completed, rejected, "
                "expired, or — under an overcommitted pool — evicted)",
                ("outcome",)),
            "tokens": reg.counter(
                "hetu_serve_tokens_total", "generated tokens"),
            "queue": reg.gauge(
                "hetu_serve_queue_depth", "requests waiting for a slot"),
            "slots": reg.gauge(
                "hetu_serve_active_slots", "slots currently decoding"),
            "ttft": reg.histogram(
                "hetu_serve_ttft_seconds",
                "time to first token (arrival -> prefill sample)"),
            "tok_latency": reg.histogram(
                "hetu_serve_token_latency_seconds",
                "per-token decode latency (one batched step amortized "
                "over its active slots)"),
            "tps": reg.gauge(
                "hetu_serve_tokens_per_second",
                "decode throughput over the last step"),
            "ctr": reg.counter(
                "hetu_serve_ctr_requests_total", "CTR inference batches"),
            "deadline": reg.counter(
                "hetu_serve_deadline_expired_total",
                "requests dropped at their deadline, by the stage they "
                "were in (queued: expired waiting for a slot; running: "
                "cut off mid-decode, keeping the tokens generated)",
                ("stage",)),
            "shed": reg.counter(
                "hetu_serve_shed_total",
                "admission rejections that were load shedding, by cause "
                "(controller: the runtime controller's sustained-SLO-"
                "burn latch — global or tenant-scoped; queue_full: the "
                "per-tenant depth limit; bucket_freeze: prompt-bucket "
                "growth frozen during a compile storm; quota: the "
                "tenant's token bucket) and by submitting tenant "
                "(single-tenant deployments only ever emit "
                "tenant=\"default\")", ("reason", "tenant")),
            "decode_steps": reg.counter(
                "hetu_serve_decode_steps_total",
                "decode steps by how they were dispatched (ahead: while "
                "the step before was still in flight, its tokens fed on "
                "the device; in_turn: with every fed token on the host)",
                ("dispatch",)),
            "cache_token_bytes": reg.gauge(
                "hetu_serve_cache_token_bytes",
                "bytes one cached token holds over all layers, by the "
                "served model's cache spec"),
            "cache_pool_bytes": reg.gauge(
                "hetu_serve_cache_pool_bytes",
                "bytes of the page pool's device arrays"),
            "cache_pages": reg.gauge(
                "hetu_serve_cache_pages",
                "pages of the cache by group of layers (\"all\" where the "
                "model states one) and state: held by a sequence, or free",
                ("group", "state")),
            "cache_overwritten": reg.counter(
                "hetu_serve_cache_pages_overwritten_total",
                "ring slots of a window group that a sequence outgrowing "
                "the window took again for a later page", ("group",)),
            "paged_steps": reg.counter(
                "hetu_serve_paged_decode_steps_total",
                "grid steps of the paged decode kernel, a head block of "
                "one layer's call, by group of layers: walked (a step that "
                "holds a page its row sees, or an empty row's one step) "
                "and skipped (the rest of the steps the page tables hold)",
                ("group", "kind")),
            "discarded": reg.counter(
                "hetu_serve_lookahead_discarded_total",
                "tokens of a step in flight that were dropped at collect "
                "because their request had ended meanwhile (an EOS found "
                "one step late, a deadline, an eviction)"),
        }
    return _serve_metrics


class _PendingDecode(NamedTuple):
    """A decode step that was dispatched and not yet collected."""
    active: list        # the (slot, request) pairs it covers
    toks: jax.Array     # (num_slots,) sampled tokens, on the device
    t0: float           # the clock when its build began
    aux: dict = {}      # what the model's program counted (routing), on
    # the device; empty for a model that counts nothing
    contexts: tuple = ()    # cached tokens each of its rows attends over


class _PendingPrefill(NamedTuple):
    """A prefill that was dispatched and not yet collected."""
    req: Request
    tok: jax.Array      # (1,) the first token, on the device
    bucket: int
    shared_len: int
    aux: dict = {}      # as _PendingDecode.aux


class RequestHandle:
    """Caller-side future for one generation request."""

    def __init__(self, request_id: int):
        self.request_id = request_id
        self.trace_id = f"req-{request_id}"   # reqtrace derivation: the
        # handle can name its /trace/<id> timeline before resolving
        self._done = threading.Event()
        # completed | rejected | expired | evicted (overcommitted pool
        # only) | failed (the scheduler thread died on this error)
        self.status: Optional[str] = None
        self.tokens: list = []
        self.ttft_s: Optional[float] = None
        self.latency_s: Optional[float] = None
        self.error: Optional[str] = None   # human-readable failure reason
        # set on LOAD-SHEDDING rejections only ("controller" |
        # "queue_full" | "bucket_freeze" | "quota"): the fleet router
        # re-routes the first three to another replica; validation
        # rejections (None) would fail identically everywhere and quota
        # rejections are the tenant's own contract (re-routing would be
        # quota evasion) — both are returned as-is
        self.shed_reason: Optional[str] = None
        # multi-tenant front door: the resolved submitting tenant's id,
        # and — on shed/quota rejections — the deterministic backoff
        # hint /infer surfaces as retry_after_s
        self.tenant: Optional[str] = None
        self.retry_after_s: Optional[float] = None
        # deterministic uint32 fingerprint of the token stream
        # (obs.numerics.host_fingerprint_ints): two same-seed runs of the
        # same schedule must agree — a mismatch in prod IS sampler
        # nondeterminism, detectable from the /infer response alone
        self.stream_fingerprint: Optional[int] = None

    def _finish(self, status: str, tokens=(), ttft_s=None, latency_s=None,
                error=None, stream_fingerprint=None):
        self.status = status
        self.tokens = list(tokens)
        self.ttft_s = ttft_s
        self.latency_s = latency_s
        self.error = error
        self.stream_fingerprint = stream_fingerprint
        self._done.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    @property
    def done(self) -> bool:
        return self._done.is_set()


class ServingEngine:
    """Continuous-batching inference over one decoder (and optionally one
    CTR model sharing the process' HET stores).

    **What a served model provides** (``models.GPT``, ``models.DeepseekV2``
    and ``models.Afmoe`` do; the engine asks nothing else of it and
    never looks at its type): ``config`` with ``max_seq_len`` and
    ``vocab_size``; ``cache_spec()``, the :class:`~hetu_tpu.serve.kv_cache.
    CacheSpec` the pool is built from (its page layout and the bytes a
    token holds come from the model, not from the engine), or a
    ``GroupedCacheSpec`` of one a group of layers (window and full
    attention in one model), for which the pool holds pages and one table
    a sequence by group, ``cache`` below is the groups' arrays one after
    another and ``page_idx`` / ``page_tables`` a tuple of one matrix a
    group;
    ``prefill(cache, page_idx, cache_index, tokens, seq_lengths) ->
    (logits, cache, aux)``, the new tokens of each row written into the
    row's pages and the logits at its last valid position;
    ``decode(cache, page_tables, lengths, tokens) -> (hidden, cache,
    aux)``, one token a row over the pages read in place; ``head() ->
    (weight, vocab_axis)``, the vocabulary projection as the model stores
    it and the axis its vocabulary lies on: ``(hidden, vocab)`` and 1, or
    a tied embedding table ``(vocab, hidden)`` and 0.  The fused sampler
    streams it from there, so that no decode step copies, transposes or
    pads an array of the head's size.
    ``cache`` is the tuple of the pool's arrays, donated to every program
    and handed back updated; ``aux`` is a dict of what the program counted
    on the device (an expert layer's ``moe_*`` routing counts, which go to
    ``obs.record_routing`` once the step's tokens are on the host), empty
    for a model that counts nothing."""

    def __init__(self, model, *, num_slots: Optional[int] = None,
                 page_size: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 num_pages: Optional[int] = None, queue_depth: int = 64,
                 prompt_buckets=None,
                 sampling: str = "greedy", top_k: int = 5,
                 temperature: float = 1.0, eos_id: Optional[int] = None,
                 seed: int = 0, clock=time.monotonic,
                 defrag_every: int = 0, ctr_model=None,
                 paged_decode: bool = True,
                 fused_sampling: Optional[bool] = None,
                 slo_targets=None, trace_capacity: int = 256,
                 trace_slow_n: int = 8, trace_window: int = 128,
                 controller=None, prefix_sharing: Optional[bool] = None,
                 draft_model=None, spec_k: Optional[int] = None,
                 role: Optional[str] = None,
                 prefill_tick_cost: Optional[float] = None,
                 ctr_follower=None, tenants: Optional[TenantPolicy] = None,
                 plan=None):
        cfg = model.config
        self.model = model
        # what the step programs are fed: flattened here, once, so no call
        # walks the model's modules (jit flattens a tuple in C++)
        leaves, self._model_def = jax.tree_util.tree_flatten(model)
        self._model_leaves = tuple(leaves)
        self.eos_id = eos_id
        _tracing.watch_collector()
        # Plan-bearing construction (hetu_tpu/plan): the plan's serving
        # axes fill every knob the caller left unset — explicit kwargs
        # always win, so a plan composes with manual overrides.  spec_k
        # applies only when a draft model exists to speculate with.
        self.plan = plan
        if plan is not None:
            if num_slots is None:
                num_slots = plan.slots_per_replica
            if page_size is None and plan.page_size > 0:
                page_size = plan.page_size
            if prompt_buckets is None and plan.bucket_ladder:
                prompt_buckets = plan.bucket_ladder
            if num_pages is None and plan.kv_pool_pages > 0:
                num_pages = plan.kv_pool_pages
            if spec_k is None and plan.spec_k > 0 \
                    and draft_model is not None:
                spec_k = plan.spec_k
        # the historical defaults, applied after the plan merge
        num_slots = 8 if num_slots is None else int(num_slots)
        page_size = 16 if page_size is None else int(page_size)
        if prompt_buckets is None:
            prompt_buckets = (8, 16, 32, 64, 128)
        # disaggregated serving (serve/fleet/disagg.py): the worker ROLE.
        # "colocated" (default) timeslices prefill and decode on this
        # engine; "prefill" hands every freshly prefilled request's KV
        # pages to a decode worker through the router-installed
        # ``migrate_out`` hook; "decode" only ever decodes (migrated
        # requests arrive via accept_migration; re-prefill is the
        # verify-failure fallback).  HETU_TPU_DISAGG_ROLE backs the kwarg
        # — one env block configures every worker, the fleet convention.
        if role is None:
            role = os.environ.get("HETU_TPU_DISAGG_ROLE", "colocated")
        if role not in ("prefill", "decode", "colocated"):
            raise ValueError(f"unknown role {role!r}; one of 'prefill', "
                             f"'decode', 'colocated'")
        self.role = role
        # virtual-time cost model for the deterministic fleet ticks: a
        # prefill of bucket B makes this engine BUSY for
        # ceil(B * prefill_tick_cost) scheduler ticks (admission and
        # decode both skip — the chip is crunching the prefill), so the
        # simulation reproduces the timeslice stall a colocated chip
        # pays and a disaggregated decode worker never does.  0 (the
        # default) disables the model entirely: production engines on a
        # real clock measure real compute instead.
        if prefill_tick_cost is None:
            prefill_tick_cost = float(os.environ.get(
                "HETU_TPU_DISAGG_PREFILL_COST", "0") or 0)
        self.prefill_tick_cost = float(prefill_tick_cost)
        self._busy_ticks = 0
        self._tick_prefill_charge = 0
        # router-installed migration hook (role "prefill" only):
        # called as migrate_out(engine, request, record) -> bool
        self.migrate_out = None
        # migration settle callbacks (export-hold acks against the
        # SOURCE pool) deferred to run outside this engine's lock — a
        # decode worker settling while a prefill worker migrates to it
        # must not deadlock on crossed engine locks
        self._pending_settles: list = []
        self._migrations = {"out": 0, "in": 0, "reprefill": 0}
        if sampling not in ("greedy", "top_k", "temperature"):
            raise ValueError(f"unknown sampling mode {sampling!r}; one of "
                             f"'greedy', 'top_k', 'temperature'")
        if sampling == "top_k" and top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        self.sampling = sampling
        self.top_k = top_k
        self.temperature = temperature
        self.clock = clock
        self.defrag_every = defrag_every
        self.max_seq_len = min(max_seq_len or cfg.max_seq_len,
                               cfg.max_seq_len)
        if self.max_seq_len % page_size:
            self.max_seq_len -= self.max_seq_len % page_size
        # the page layout, the groups of layers and the bytes a token
        # holds are the model's; num_pages is a number, or a mapping from
        # the groups' names where the model states several (None: every
        # slot's whole allocation, a group)
        spec = model.cache_spec()
        self.pool = _kv.make_pool(
            spec, num_slots=num_slots, num_pages=num_pages,
            page_size=page_size, max_seq_len=self.max_seq_len)
        if not spec.plain_kv:
            # what reads one pool of keys and values of whole sequences is
            # refused here, by name, rather than built and wrong (the
            # failover monitor needs no refusal: a page export that raises
            # re-homes the request by re-prefill) (prefix sharing is
            # refused by PrefixSharer itself)
            for on, what in ((not paged_decode,
                              "paged_decode=False (the gather path)"),
                             (draft_model is not None,
                              "speculative decoding"),
                             (role != "colocated",
                              f"role {role!r} (page migration)")):
                if on:
                    raise _kv.UnsupportedCacheLayout(
                        f"{what} is written for one pool of keys and "
                        f"values of whole sequences in token-major pages; "
                        f"this model caches {spec.describe()}")
        buckets = tuple(b for b in sorted(prompt_buckets)
                        if b <= self.max_seq_len) or (self.max_seq_len,)
        # multi-tenant front door: the tenant policy (priority classes,
        # WFQ weights, quota buckets) feeds the batcher's weighted-fair
        # admission; share ONE TenantPolicy across a fleet's replicas
        # and the token buckets become fleet-wide quotas.  None = every
        # caller is the default tenant (the exact pre-tenant FIFO).
        self.batcher = ContinuousBatcher(num_slots, queue_depth=queue_depth,
                                         prompt_buckets=buckets,
                                         policy=tenants)
        # per-tenant usage metering (tokens, KV pages, compile-seconds,
        # outcomes) — the billing artifact behind /tenants
        self.tenant_meter = TenantMeter()
        # tenant ids whose queue-depth gauge has been published at least
        # once (so drained tenants can be zeroed on later steps)
        self._tenant_depth_published: set = set()
        self._base_key = jax.random.PRNGKey(seed)
        self._lock = threading.RLock()
        self._handles: dict = {}
        self._next_id = 0
        self._recycled = 0
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # why the scheduler thread died, if it did: every waiting handle
        # fails with it and later submits fail fast
        self._dead: Optional[str] = None
        # request-scope observability: one timeline per in-flight request,
        # finished timelines into the ring/exemplar buffer and the SLO
        # engine (both driven by the engine's own injectable clock, so
        # same-seed runs produce bitwise-identical timelines)
        self.trace_buffer = ReqTraceBuffer(capacity=trace_capacity,
                                           slow_n=trace_slow_n,
                                           window=trace_window)
        self.slo = SLOEngine(slo_targets, clock=clock)
        self._timelines: dict = {}
        # the jit seams are compile-counting (obs.compile: jit's C++
        # cache serves the calls, the seam keys only those it misses, so
        # hetu_compile_total is exact and a recompile storm is a gauge,
        # not a bench round).  Both step programs take the pool's arrays
        # (k and v, arguments 1 and 2; one latent array, argument 1)
        # donated and write them in place; the model's flat leaves are
        # argument 0, fed again every tick and never donated.  They are
        # called through pool.step alone, which adopts the arrays they
        # return.
        donated = tuple(range(1, 1 + len(self.pool.arrays)))
        self._step_fn = _compile.instrument(
            jax.jit(self._step_impl, donate_argnums=donated),
            site="serve.prefill_step")
        self._sample_fn = _compile.instrument(jax.jit(self._sample_impl),
                                              site="serve.sample")
        self.paged_decode = bool(paged_decode)
        if fused_sampling is None:
            # the fused sampler's streamed top-k holds at most 128
            # candidates in its lane-wide scratch; wider top-k falls back
            # to XLA logits + the row sampler (still paged attention)
            fused_sampling = (sampling != "top_k"
                              or min(top_k, cfg.vocab_size) <= 128)
        self._fused_sampling = bool(fused_sampling)
        self._paged_step_fn = _compile.instrument(
            jax.jit(self._paged_decode_impl, donate_argnums=donated),
            site="serve.paged_decode")
        self.ctr_model = ctr_model
        if ctr_model is not None:
            _mark_stores_read_only(ctr_model)
        # streaming freshness (embed.stream): a SnapshotFollower over the
        # CTR model's stores — infer_ctr gates on it, so training pushes
        # reach this read-only replica within the staleness bound without
        # the stores ever training in place
        if ctr_follower is not None and ctr_model is None:
            raise ValueError("ctr_follower needs a ctr_model to install "
                             "snapshots into")
        self.ctr_follower = ctr_follower
        # closed-loop remediation (exec.controller): the attached (or
        # process-wide installed) RuntimeController runs once per
        # scheduler tick — shed latch on sustained SLO burn, bucket
        # freeze under a compile storm.  With neither, the tick seam is
        # one attribute + one global load and a branch.
        self.controller = controller
        # while frozen, prompts needing a prefill bucket that has not
        # compiled yet are rejected instead of feeding the storm
        self.freeze_bucket_growth = False
        self._prefill_buckets: set = set()
        self._tick = 0
        # look-ahead: the decode step that the scheduler's own loop left
        # in flight across step() calls (dispatched, tokens not fetched).
        # Only start()'s thread leaves one; every other caller of step()
        # runs dispatch and collect back to back and finds None here.
        self._pending: Optional[_PendingDecode] = None
        self._decode_steps = {"ahead": 0, "in_turn": 0}
        # group -> the paged kernel's grid steps walked and skipped, over
        # the groups of keys and values it reads (not a latent cache's)
        self._paged_steps = {
            name: {"walked": 0, "skipped": 0}
            for name, g in self.pool.by_group().items()
            if self.paged_decode and g.spec.holds_kv}
        # group -> (free pages, ring overwrites) as last published
        self._cache_published: dict = {}
        self._lookahead_discarded = 0
        # what an in-turn step feeds where a step ahead feeds the last
        # step's tokens: one program signature for both
        self._no_prev = jnp.zeros((num_slots,), jnp.int32)
        # serving fault tolerance (serve/fleet/failover.py): the
        # heartbeat the monitor leases against, and the injected failure
        # modes.  _beat advances once per HEALTHY scheduler tick (a
        # crashed or hung engine's beat freezes — that IS the failure
        # signal); crash() is permanent, hang(n) is silence for n ticks.
        self._beat = 0
        self._crashed = False
        self._hang_ticks = 0
        # router-installed ledger hooks: on_token(rid, tok) after every
        # emitted token, on_finish(rid) when a handle resolves — both
        # called under this engine's lock, so they must stay tiny
        self.on_token = None
        self.on_finish = None
        # on_program(kind, info) once a device program's results are on
        # the host: "prefill" with request_id, prompt_len, bucket, or
        # "decode" with rows, contexts (the cached tokens each row attended
        # over, which a layer with a window sees the last of) and
        # context_tokens, their sum; both with routing, the program's
        # expert-routing counts as host numbers or None.  Same rules as the
        # two above
        self.on_program = None
        m = _serve_m()
        m["cache_token_bytes"].set(spec.token_bytes)
        m["cache_pool_bytes"].set(self.pool.nbytes)
        # fleet tier (serve/fleet): copy-on-write prefix sharing maps
        # identical prompt prefixes to shared refcounted KV pages, and a
        # draft model turns decode into propose-and-verify (bitwise
        # identical streams).  Lazy imports: serve.fleet imports this
        # module's types back.
        # HETU_TPU_FLEET_* env knobs back the explicit kwargs (the fleet
        # deployment story: one env block configures every replica)
        if prefix_sharing is None:
            prefix_sharing = os.environ.get(
                "HETU_TPU_FLEET_PREFIX_SHARE", "0") not in ("0", "", "false")
        if spec_k is None:
            spec_k = int(os.environ.get("HETU_TPU_FLEET_SPEC_K", "4"))
        self.sharer = None
        if prefix_sharing:
            from hetu_tpu.serve.fleet.prefix import PrefixSharer
            self.sharer = PrefixSharer(self.pool)
        self.spec = None
        if draft_model is not None:
            if not self.paged_decode:
                raise ValueError(
                    "speculative decoding requires paged_decode=True: "
                    "chained verify rows share one page table, which "
                    "only element-scattered paged K/V writes compose "
                    "(the gather path scatters whole per-row page "
                    "copies back — chained rows would clobber each "
                    "other)")
            from hetu_tpu.serve.fleet.spec import SpeculativeDecoder
            self.spec = SpeculativeDecoder(
                draft_model, spec_k, num_slots=num_slots,
                max_len=self.max_seq_len)

    # -- jitted compute -----------------------------------------------------

    def _served(self, model):
        """The model a step program was handed, inside its trace: the
        engine feeds its flat leaves, rebuilt here with the stored
        structure; a model handed whole has the same leaves."""
        return jax.tree_util.tree_unflatten(
            self._model_def, jax.tree_util.tree_leaves(model))

    @jax.named_scope("serve.prefill_step")
    def _step_impl(self, model, *args):
        """One serving step at any bucket shape, ``(model, *cache,
        page_idx, cache_index, tokens, seq_lengths)`` with ``model`` the
        model's flat leaves: the model's ``prefill`` writes the new
        tokens into the rows' pages (for keys and values: gather the
        paged views, run the incremental path, scatter the updated KV
        back).  Prefill and the gather path's decode differ only in the
        shapes they call this at."""
        n = len(self.pool.arrays)
        logits, cache, aux = self._served(model).prefill(args[:n],
                                                         *args[n:])
        return ((logits, aux), *cache)

    def _paged_decode_impl(self, model, *args):
        """The paged decode step, ``(model, *cache, page_tables, lengths,
        tokens, request_ids, positions[, prev])`` with ``model`` the
        model's flat leaves: the model's ``decode``
        attends over the pages IN PLACE via the page tables (a Pallas
        paged-decode kernel), each layer's new entry lands with one
        small scatter, and sampling fuses into the
        LM-head kernel — neither the contiguous KV views nor the (slots,
        vocab) logits ever materialize.  Same key derivation as
        :meth:`_sample_impl`, so streams stay bitwise-reproducible.

        ``prev`` (the engine's decode, never the speculative verify) is
        the last step's sampled tokens, still on the device: a row whose
        ``tokens`` entry is negative feeds its ``prev`` entry, so a step
        dispatched ahead needs no token the host has not seen."""
        n = len(self.pool.arrays)
        page_tables, lengths, tokens, request_ids, positions, *prev = args[n:]
        if prev:
            tokens = jnp.where(tokens >= 0, tokens, prev[0][:, None])
        model = self._served(model)
        last, cache, aux = model.decode(args[:n], page_tables, lengths,
                                        tokens)
        head, vocab_axis = model.head()
        head = head.astype(last.dtype)
        if self._fused_sampling:
            keys = None
            if self.sampling != "greedy":
                keys = jax.vmap(lambda r, p: jax.random.fold_in(
                    jax.random.fold_in(self._base_key, r), p))(
                    request_ids, positions)
            toks = lm_head_sample_pallas(
                last, head, vocab_axis=vocab_axis, mode=self.sampling,
                top_k=self.top_k, temperature=self.temperature, keys=keys)
        else:
            toks = self._sample_impl(
                last @ (head if vocab_axis else head.T), request_ids,
                positions)
        return ((toks, aux), *cache)

    def _sample_impl(self, logits, request_ids, positions):
        """Per-row seeded sampling (vmapped: one dispatch per step).  Keys
        derive INSIDE the jitted program from ``(seed, request id, token
        position)``, so batch composition cannot perturb any request's
        stream and the host loop ships two int32 vectors, not keys."""
        if self.sampling == "greedy":
            return greedy_sample(logits)

        def row(lg, rid, pos):
            key = jax.random.fold_in(
                jax.random.fold_in(self._base_key, rid), pos)
            if self.sampling == "temperature":
                return temperature_sample(lg, self.temperature, key=key)
            return top_k_sample(lg, self.top_k, self.temperature, key=key)

        return jax.vmap(row)(logits, request_ids, positions)

    # -- submission ---------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 16, *,
               deadline_s: Optional[float] = None,
               request_id: Optional[int] = None,
               tenant=None) -> RequestHandle:
        """Queue one generation request; never waits for the request, only
        for the engine's lock, which :meth:`step` holds through a tick (the
        ``serve.submit.wait`` span).  Returns a handle
        that resolves when the request completes, is rejected (queue
        depth / quota / too long), or expires at its deadline.

        ``tenant`` names the submitting tenant (an id string or a
        :class:`~hetu_tpu.serve.tenant.Tenant`; ``None`` = the default
        tenant, the exact pre-tenant path): admission runs weighted-fair
        over per-tenant sub-queues, quota buckets gate the front door
        (:class:`TenantQuotaExceeded` -> status ``rejected`` with
        ``shed_reason="quota"`` and a ``retry_after_s`` backoff hint),
        and the controller's scoped shed latch can close ONE tenant's
        door.

        ``request_id`` pins the id instead of drawing from this engine's
        counter — the disaggregated router's seam: token streams are a
        pure function of ``(seed, request id, prompt)``, so a router that
        assigns GLOBAL ids in submission order makes a migrated stream
        bitwise comparable to its colocated same-seed twin."""
        prompt = [int(t) for t in np.asarray(prompt).ravel()]
        # the wait for the lock that step() holds through a whole tick is
        # a span of its own, on the caller's thread
        with _tracing.span("serve.submit.wait"):
            self._lock.acquire()
        try:
            if self._dead is not None:
                handle = RequestHandle(self._next_id)
                handle._finish("failed", error=self._dead)
                return handle
            if request_id is None:
                rid = self._next_id
            else:
                rid = int(request_id)
                if rid in self._handles:
                    raise ValueError(f"request id {rid} is already in "
                                     f"flight on this engine")
            self._next_id = max(self._next_id, rid + 1)
            handle = RequestHandle(rid)
            ten = self.batcher.policy.resolve(tenant)
            handle.tenant = ten.id
            is_default = ten.id == DEFAULT_TENANT.id
            req = Request(id=rid, prompt=prompt,
                          max_new_tokens=int(max_new_tokens),
                          arrival=self.clock(), deadline_s=deadline_s,
                          tenant=None if is_default else ten.id)
            # tenant attrs only on non-default traffic, so a pre-tenant
            # deployment's timelines/spans stay bit-identical
            tattrs = {} if is_default else {"tenant": ten.id,
                                            "tenant_class": ten.klass}
            tl = RequestTimeline(rid, req.arrival, prompt_len=len(prompt),
                                 max_new_tokens=req.max_new_tokens,
                                 **tattrs)
            reason = None
            shed_reason = None  # set when the rejection is LOAD SHEDDING
            retry_after = None  # the /infer backoff hint, shed only
            max_bucket = self.batcher.prompt_buckets[-1]
            if not prompt:
                reason = "empty prompt"
            elif req.max_new_tokens < 1:
                reason = (f"max_new_tokens must be >= 1, got "
                          f"{req.max_new_tokens}")
            elif req.total_budget > self.max_seq_len:
                reason = (f"prompt+budget {req.total_budget} exceeds "
                          f"max_seq_len {self.max_seq_len}")
            elif len(prompt) > max_bucket:
                reason = (f"prompt of {len(prompt)} tokens exceeds the "
                          f"largest prefill bucket {max_bucket}")
            elif self.freeze_bucket_growth and \
                    self.batcher.bucket_for(len(prompt)) \
                    not in self._prefill_buckets:
                reason = (
                    f"prompt bucket "
                    f"{self.batcher.bucket_for(len(prompt))} not yet "
                    f"compiled and bucket growth is frozen (compile "
                    f"storm); warm buckets: "
                    f"{sorted(self._prefill_buckets)}")
                shed_reason = "bucket_freeze"
            if reason is None:
                try:
                    self.batcher.submit(req)
                except TenantQuotaExceeded as e:
                    # before AdmissionShed/QueueFull: it subclasses them
                    reason, shed_reason = str(e), "quota"
                    retry_after = round(e.retry_after_s, 6)
                except AdmissionShed as e:
                    reason, shed_reason = str(e), "controller"
                except AdmissionQueueFull as e:
                    reason, shed_reason = str(e), "queue_full"
            if reason is not None:
                _serve_m()["requests"].labels(outcome="rejected").inc()
                self.tenant_meter.note_outcome(ten.id, "rejected")
                if shed_reason is not None:
                    if retry_after is None:
                        retry_after = self._retry_hint(shed_reason)
                    self.tenant_meter.note_shed(ten.id, shed_reason)
                    _serve_m()["shed"].labels(reason=shed_reason,
                                              tenant=ten.id).inc()
                    _journal.record("shed", request_id=rid,
                                    reason=shed_reason,
                                    queue_depth=self.batcher.queue_len,
                                    **({} if is_default
                                       else {"tenant": ten.id}))
                    if shed_reason == "quota":
                        _journal.record("tenant_quota", request_id=rid,
                                        tenant=ten.id,
                                        retry_after_s=retry_after)
                _journal.record("serve_reject", request_id=rid,
                                reason=reason,
                                queue_depth=self.batcher.queue_len,
                                **({} if is_default
                                   else {"tenant": ten.id}))
                # a zero-length timeline still lands in the trace buffer
                # (a rejection is queryable forensics too), but it is NOT
                # graded: it never entered the serving pipeline, so it
                # must not consume SLO error budget
                tl.close("rejected", req.arrival, reason=reason)
                self._finalize_timeline(tl, grade=False)
                handle.shed_reason = shed_reason
                handle.retry_after_s = retry_after
                handle._finish("rejected", error=reason)
                return handle
            self._handles[rid] = handle
            self._timelines[rid] = tl
            _serve_m()["queue"].set(self.batcher.queue_len)
        finally:
            self._lock.release()
        return handle

    def _retry_hint(self, shed_reason: str) -> float:
        """The deterministic ``retry_after_s`` backoff hint for non-quota
        sheds (quota rejections carry the bucket's exact refill time
        instead).  ``controller``: scale with how far past the engage
        threshold the burn is (pressure 1.0 -> back off a long window's
        worth of tenths); ``queue_full``: one scheduler wave per queued
        batch ahead; ``bucket_freeze``: the storm detector's cool-down
        order of magnitude.  All pure functions of current deterministic
        state — same trace, same hints."""
        if shed_reason == "controller":
            return round(0.1 + self.slo.shed_pressure() *
                         self.slo.short_window_s / 10.0, 6)
        if shed_reason == "queue_full":
            waves = -(-self.batcher.queue_len
                      // max(self.batcher.num_slots, 1))
            return round(0.05 * max(waves, 1), 6)
        return 1.0  # bucket_freeze: wait out the compile storm

    # -- the scheduler loop -------------------------------------------------

    def step(self) -> int:
        """One scheduler tick: expire, admit+prefill (or ingest a
        migrated request's KV pages), one decode step.  Returns the
        number of tokens produced (0 when idle, or while the virtual
        prefill-cost model holds the engine busy)."""
        # who drives decides the order: the scheduler's own loop may leave
        # a decode step in flight across calls; anyone else stepping by hand
        # (tests, virtual clocks, the fleet simulations) gets each step's
        # tokens back from the call that dispatched it
        ahead = threading.current_thread() is self._thread
        with self._lock:
            produced = self._step_locked(ahead)
        # settle migration export holds OUTSIDE this engine's lock: the
        # settle acquires the SOURCE engine's lock, and a prefill worker
        # migrating into this engine holds its own lock while taking
        # ours — nesting the other direction too would deadlock
        while True:
            try:
                settle = self._pending_settles.pop(0)
            except IndexError:
                break
            settle()
        return produced

    def _step_locked(self, ahead: bool = False) -> int:
        self._tick += 1
        if self._crashed:
            # a crashed replica does nothing and — critically — does not
            # beat: the failover monitor reads the frozen heartbeat and
            # declares it lost after its lease expires
            return 0
        if self._hang_ticks > 0:
            # a hung replica is silent (no beat, no work) for the
            # injected span, then recovers on its own — the flap the
            # controller's quarantine hysteresis exists for
            self._hang_ticks -= 1
            return 0
        self._beat += 1
        plan = _faults.active_plan()
        if plan is not None:
            # chaos seam: a scheduled compile_storm fault notes `arg`
            # synthetic distinct-shape compiles (default: enough to
            # cross the threshold) into the process storm detector —
            # the deterministic stand-in for an unbucketed-shape
            # flood.  Only this kind is consumed here; the training
            # harnesses keep their own conventions.
            f = plan.take("compile_storm", late_ok=True, now=self._tick)
            if f is not None:
                storm = _compile.get_storm()
                for _ in range(int(f.arg or storm.threshold + 1)):
                    storm.note("fault_injection")
        _controller.maybe_serve_tick(self)
        m = _serve_m()
        if self._busy_ticks > 0:
            # the virtual prefill-cost model: the chip is still crunching
            # an earlier prefill — no admission, no decode this tick.
            # This is the timeslice stall a colocated worker pays under a
            # long-prompt burst and a disaggregated decode worker never
            # sees (its role never prefills).
            self._busy_ticks -= 1
            return self._collect_pending()
        with _tracing.span("serve.tick", tick=self._tick) as sp:
            produced, admitted = self._tick_phases(
                m, ahead and self.spec is None)
            if sp is not None:
                sp.set(active=self.batcher.active_slots, admitted=admitted,
                       produced=produced)
        return produced

    def _tick_phases(self, m, ahead: bool = False) -> tuple:
        """The work of one healthy tick, each phase a child span of
        ``serve.tick`` (none is per token), so that the tick's self time
        is the loop's own overhead.  Returns (tokens produced, requests
        admitted).

        Every device program has a dispatch half and a collect half (the
        fetch of its tokens, then what follows from them).  In turn, each
        collect follows its dispatch.  With ``ahead`` the tick dispatches
        all it has before it fetches anything: the admitted prefills,
        then the next decode step, built by count while the last one's
        tokens are still on the device; then it collects the last step,
        then the prefills, and leaves the new step in flight."""
        produced = 0 if ahead else self._collect_pending()
        with _tracing.span("serve.tick.schedule"):
            now = self.clock()
            # reserving gate: poll admits several requests before any of
            # them allocates, so the budget must be decremented as each
            # one passes — gating on live pool state alone would overcommit
            # (a group of layers: a request is admitted when every group
            # has the pages its prompt needs)
            budget = self.pool.free_by_group()

            def gate(r):
                need = self.pool.needed_by_group(len(r.prompt))
                if need[0] > budget[0] and self.sharer is not None:
                    # cached prefixes are a loan: evict trie-only pages
                    # (least-recently-matched first) to admit real work
                    # (sharing is one group's: PrefixSharer refuses more)
                    budget[0] += self.sharer.reclaim(need[0] - budget[0])
                if any(n > b for n, b in zip(need, budget)):
                    return False
                for g, n in enumerate(need):
                    budget[g] -= n
                return True

            tick = self.batcher.poll(now, can_admit=gate)
            for req in tick.expired:
                waited = now - req.arrival
                if req.migration is not None:
                    # a migrated request expired waiting for a decode slot:
                    # its KV never imported — settle the source's export hold
                    self._pending_settles.append(req.migration.settle)
                _journal.record("request_expired", request_id=req.id,
                                stage="queued", waited_s=round(waited, 6))
                m["requests"].labels(outcome="expired").inc()
                m["deadline"].labels(stage="queued").inc()
                self.tenant_meter.note_outcome(req.tenant_id, "expired")
                tl = self._timelines.pop(req.id)
                tl.close("expired", now, stage="queued")
                self._finalize_timeline(tl)
                self._handles.pop(req.id)._finish(
                    "expired",
                    error=f"deadline of {req.deadline_s}s expired after "
                          f"{waited:.6g}s in the admission queue")
                if self.on_finish is not None:
                    self.on_finish(req.id)
        # a prefill worker hands each request on as soon as it has its
        # first token: nothing of it is left for a later half to collect
        migrates = self.role == "prefill" and self.migrate_out is not None
        prefills = []
        for req in tick.admitted:
            if req.migration is not None:
                # a migrated request enters a decode slot: import its KV
                # (or re-prefill on a corrupt record) — it was already
                # counted admitted by the prefill worker
                with _tracing.span("serve.tick.ingest", request_id=req.id):
                    self._ingest_migration(req, now)
                continue
            with _tracing.span("serve.tick.prefill", request_id=req.id,
                               prompt_len=len(req.prompt)) as sp:
                m["requests"].labels(outcome="admitted").inc()
                self.tenant_meter.note_outcome(req.tenant_id, "admitted")
                self._timelines[req.id].admit(
                    now, slot=req.slot, queue_depth=self.batcher.queue_len)
                pending = self._prefill_dispatch(req, sp)
                if ahead and not migrates:
                    prefills.append(pending)
                    continue
                self._prefill_collect(pending)
                if migrates and req.id in self._handles:
                    self._migrate_after_prefill(req)
        # a running request past its deadline is cut off here, with
        # the tokens it has — serving it further is serving it late
        for _slot, req in self.batcher.active():
            if req.expired(now):
                self._retire(req, "expired", now)
        charge = self._tick_prefill_charge
        self._tick_prefill_charge = 0
        last, self._pending = self._pending, None
        step = None
        if charge > 0:
            # this tick was spent prefilling (the first busy tick);
            # decode resumes when the remaining charge drains
            self._busy_ticks += charge - 1
        elif self.spec is not None:
            # propose-and-verify (serve/fleet/spec.py): up to
            # ``spec_k + 1`` tokens per slot per tick, bitwise the same
            # streams; never ahead, its chains need every token on the host
            with _tracing.span("serve.tick.decode.device"):
                produced += self.spec.decode_step(self)
        else:
            step = self._decode_dispatch(last)
        if last is not None:
            produced += self._decode_collect(last)
        for pending in prefills:
            self._prefill_collect(pending)
        if ahead:
            self._pending = step
        elif step is not None:
            produced += self._decode_collect(step)
        with _tracing.span("serve.tick.publish"):
            m["queue"].set(self.batcher.queue_len)
            m["slots"].set(self.batcher.active_slots)
            for name, g in self.pool.by_group().items():
                now = (g.free_pages, g.pages_overwritten)
                free, over = self._cache_published.get(name, (None, 0))
                if now == (free, over):
                    continue        # most ticks move no page
                self._cache_published[name] = now
                m["cache_pages"].labels(group=name, state="free").set(now[0])
                m["cache_pages"].labels(group=name, state="held").set(
                    g.num_pages - 1 - now[0])
                if now[1] > over:
                    m["cache_overwritten"].labels(group=name).inc(
                        now[1] - over)
            # per-tenant depth gauges only once real multi-tenant traffic
            # exists (a pre-tenant deployment's metric surface is unchanged);
            # drained tenants are zeroed, not dropped, so dashboards see the
            # flood subside rather than a vanishing series
            lens = self.batcher.queue_lens()
            if any(tid != DEFAULT_TENANT.id for tid in lens) \
                    or self._tenant_depth_published:
                tq = _tenant_m()["queue"]
                for tid in self._tenant_depth_published - set(lens):
                    tq.labels(tenant=tid).set(0)
                for tid, n in lens.items():
                    tq.labels(tenant=tid).set(n)
                self._tenant_depth_published |= set(lens)
        return produced, len(tick.admitted)

    def run_until_idle(self, max_steps: int = 100000) -> None:
        for _ in range(max_steps):
            self.step()
            if self.batcher.idle:
                return
        raise RuntimeError(f"not idle after {max_steps} scheduler steps")

    def start(self, poll_interval: float = 0.001) -> "ServingEngine":
        """Run the scheduler on a daemon thread (the HTTP-serving mode)."""
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                with self._lock:
                    idle = self.batcher.idle and self._pending is None
                if idle:
                    time.sleep(poll_interval)
                    continue
                try:
                    self.step()
                except Exception as e:
                    # a step that raises (a kernel the compiler refuses,
                    # an OOM) must not leave callers waiting on a thread
                    # that no longer exists
                    self._fail_all(e)
                    raise

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="hetu-serve-engine")
        self._thread.start()
        return self

    def _fail_all(self, error: BaseException) -> None:
        """Resolve every in-flight handle as ``failed`` with the exception
        that killed the scheduler."""
        with self._lock:
            self._dead = (f"scheduler thread died: "
                          f"{type(error).__name__}: {error}")
            # a step in flight dies with the scheduler: its handles fail
            # below, and its tokens may sit behind the program that raised
            self._pending = None
            for rid, handle in list(self._handles.items()):
                handle._finish("failed", error=self._dead)
                if self.on_finish is not None:
                    self.on_finish(rid)
            self._handles.clear()
            self._timelines.clear()

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join(10)
            self._thread = None
            with self._lock:
                self._collect_pending()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- phases -------------------------------------------------------------

    def _prefill_dispatch(self, req: Request, span=None) -> _PendingPrefill:
        """The dispatch half of a prefill: right-pad the prompt (or, under
        prefix sharing, just its unshared suffix) to its bucket, run one
        (1, bucket) step at ``cache_index = shared_tokens``, sample the
        first token at the prompt's true last position, and fetch nothing.

        With a trie hit, the table's leading entries alias the shared
        pages — their K/V is already written, so the step computes and
        writes ONLY the suffix pages (the ``pages_written`` seam counts
        them: an identical-prefix request writes zero duplicate prefix
        pages).  The sampled position and its key are the same either
        way, shared or not.  ``span`` is the caller's ``serve.tick.prefill``
        span when one is recording: it takes the bucket and the share."""
        plen = len(req.prompt)
        shared_pages, shared_len = (), 0
        if self.sharer is not None:
            # trim the share so shared + suffix-bucket FITS the serving
            # window: the gathered view is max_seq_len tokens, and a
            # ragged write past it would be clamp-shifted back INTO the
            # shared prefix pages (dynamic_update_slice clamps), then
            # scattered back — corrupting the cached K/V for every alias
            m = self.sharer.match_tokens(req.prompt)
            while m and m + self.batcher.bucket_for(plen - m) \
                    > self.max_seq_len:
                m -= self.pool.page_size
            # under a compile-storm freeze, a COLD suffix bucket must not
            # slip past the admission gate (which checked the full-prompt
            # bucket): drop sharing, the full-prompt bucket is warm
            if m and self.freeze_bucket_growth and \
                    self.batcher.bucket_for(plen - m) \
                    not in self._prefill_buckets:
                m = 0
            shared_pages, shared_len = self.sharer.lookup(req.prompt, m)
        suffix = req.prompt[shared_len:]
        bucket = self.batcher.bucket_for(len(suffix))
        self._prefill_buckets.add(bucket)  # warm: survives a freeze
        if span is not None:
            span.set(bucket=bucket, shared_tokens=shared_len)
        # compile-seconds metering: whatever XLA compiles during THIS
        # prefill's dispatch (a cold bucket, typically) is billed to the
        # tenant whose request warmed it — measured wall time, billing
        # data only, never part of the replay surfaces
        compile_before = self._compile_seconds()
        if self.prefill_tick_cost > 0:
            # virtual-time cost model: this prefill occupies the chip for
            # ceil(bucket * cost) scheduler ticks (consumed in step())
            self._tick_prefill_charge += max(
                1, math.ceil(bucket * self.prefill_tick_cost))
        self.pool.alloc(req.id, plen, shared_pages=shared_pages,
                        owner=req.tenant_id)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(suffix)] = suffix
        # the dispatch calls alone: the device has this prefill queued
        # when they return
        with _tracing.span("serve.tick.prefill.device"):
            logits, aux = self.pool.step(
                self._step_fn, self._model_leaves,
                self.pool.gather_indices([req.id]),
                jnp.asarray([shared_len], jnp.int32), jnp.asarray(tokens),
                jnp.asarray([len(suffix)], jnp.int32))
            # the bucket's pad positions wrote garbage K/V beyond plen; the
            # table's length stays plen, so decode overwrites them in turn
            self.pool.table(req.id).length = plen
            _kv.note_pages_written(
                self.pool.pages_needed(plen) - len(shared_pages))
            if self.sharer is not None:
                if shared_len:
                    _journal.record("prefix_share", request_id=req.id,
                                    shared_tokens=shared_len,
                                    prompt_len=plen)
                self.sharer.publish(req.prompt, self.pool.table(req.id))
            tok = self._sample_fn(
                logits, jnp.asarray([req.id], jnp.int32),
                jnp.asarray([plen], jnp.int32))
        self.tenant_meter.note_compile(
            req.tenant_id, self._compile_seconds() - compile_before)
        return _PendingPrefill(req, tok, bucket, shared_len, aux)

    def _prefill_collect(self, pending: _PendingPrefill) -> None:
        """The collect half of a prefill: the first token on the host,
        ``prefill_at``, the timeline, the token's accounting."""
        req, tok, bucket, shared_len, aux = pending
        with _tracing.span("serve.tick.collect.device"):
            # fetched whole and indexed here: ``tok[0]`` on the device is
            # one more program, queued behind the decode step dispatched
            # since, and the first token would wait for that step
            tok = int(np.asarray(tok)[0])
        self._ran("prefill", aux, request_id=req.id,
                  prompt_len=len(req.prompt), bucket=bucket)
        if req.slot is None:
            # retired between its halves (a deadline): the handle is closed
            self._discard(1)
            return
        # re-read the clock so the prefill stage absorbs the prefill
        # compute on the real clock (the virtual test clock returns the
        # same instant, keeping the decomposition deterministic) — the
        # same convention _decode_collect uses for its timestamp
        done_at = self.clock()
        req.prefill_at = done_at
        plen = len(req.prompt)
        self.tenant_meter.note_tokens(req.tenant_id, prompt=plen)
        tl = self._timelines[req.id]
        tl.prefill(tl.admitted_at, done_at, bucket=bucket, prompt_len=plen,
                   **({"shared_tokens": shared_len} if shared_len else {}))
        self._append_token(req, tok, done_at, ttft=done_at - req.arrival,
                           batch=1)

    def _compile_seconds(self) -> float:
        """Total XLA compile wall seconds across the three instrumented
        step caches — the before/after delta attributes a prefill's cold
        compiles to its tenant."""
        return sum(p.compile_s
                   for fn in (self._step_fn, self._paged_step_fn,
                              self._sample_fn)
                   for p in fn.programs.values())

    # -- KV-page migration (disaggregated serving) --------------------------

    def _migrate_after_prefill(self, req: Request) -> None:
        """Role ``prefill``: hand the freshly prefilled request's KV
        pages to a decode worker through the router-installed
        ``migrate_out`` hook.  The export places a HOLD on the pages (the
        export/free race fix in kv_cache.py); a successful handoff
        recycles this engine's slot and pages immediately — prefill
        workers hold KV only for the duration of one prefill, which is
        what keeps their admission capacity high under a burst.  A failed
        placement (every decode worker shed) cancels the export and the
        request simply decodes here — degraded, never dropped."""
        self._collect_pending()  # no step in flight while pages move
        record = self.pool.export_pages(req.id)
        placed = False
        try:
            placed = bool(self.migrate_out(self, req, record))
        finally:
            if not placed:
                self.pool.cancel_export(req.id)
        if placed:
            self._migrations["out"] += 1
            self.batcher.finish(req.slot)
            self.pool.free(req.id)
            self._recycled += 1
            if self.defrag_every and self._recycled % self.defrag_every == 0:
                self.pool.defrag()
            self._handles.pop(req.id)
            self._timelines.pop(req.id)

    def accept_migration(self, req: Request, record, ticket, handle,
                         timeline) -> Optional[str]:
        """Decode-side intake: queue a migrated request for a decode
        slot.  The KV import is DEFERRED to slot admission (so the
        ordinary page-budget admission gate covers it); the handle and
        timeline transfer so the request resolves here exactly as it
        would have colocated.  Returns ``None`` on acceptance, or the
        shed reason (``controller`` | ``queue_full``) so the router can
        re-route to the next-ranked decode worker."""
        if self.role == "prefill":
            raise ValueError("a prefill-role engine cannot accept "
                             "migrations")
        with self._lock:
            if req.id in self._handles:
                # a direct submission on this engine drew the same id
                # (mixing router-pinned and engine-local ids): refuse so
                # the router re-routes instead of stranding the in-flight
                # request by overwriting its handle
                return "id_collision"
            mreq = Request(
                id=req.id, prompt=list(req.prompt),
                max_new_tokens=req.max_new_tokens, arrival=req.arrival,
                deadline_s=req.deadline_s, tenant=req.tenant,
                tokens=list(req.tokens),
                prefill_at=req.prefill_at, migration=ticket)
            try:
                self.batcher.submit(mreq)
            except AdmissionShed:
                return "controller"
            except AdmissionQueueFull:
                return "queue_full"
            self._handles[req.id] = handle
            self._timelines[req.id] = timeline
            self._next_id = max(self._next_id, req.id + 1)
            _serve_m()["queue"].set(self.batcher.queue_len)
            return None

    def _ingest_migration(self, req: Request, now: float) -> None:
        """A migrated request enters a decode slot: verify + import its
        KV pages.  A torn or tampered record is journaled by named
        reason (``migrate_verify_failed``) and the request falls back to
        a local re-prefill — corrupt KV is never served, and the stream
        stays bitwise what the colocated engine would have produced
        because sampling keys derive from ``(seed, request id,
        position)`` alone."""
        from hetu_tpu.serve.fleet.migrate import (MigrationIntegrityError,
                                                  migrate_metrics)
        ticket = req.migration
        tl = self._timelines[req.id]
        verified = True
        try:
            self.pool.import_pages(ticket.record, seq_id=req.id,
                                   owner=req.tenant_id)
            self._migrations["in"] += 1
        except MigrationIntegrityError as e:
            verified = False
            migrate_metrics()["failures"].labels(reason=e.reason).inc()
            _journal.record("migrate_verify_failed", request_id=req.id,
                            reason=e.reason)
            self._reprefill(req)
            self._migrations["reprefill"] += 1
        finally:
            # settle the source pool's export hold outside our lock
            self._pending_settles.append(ticket.settle)
        tl.span("serve.migrate", now, self.clock(), slot=req.slot,
                pages=ticket.record.num_pages, verified=verified)

    def _reprefill(self, req: Request) -> None:
        """Recompute a migrated request's prompt KV locally (the
        corrupt-record fallback): one bucketed prefill step, no sharing.
        The first token was already sampled by the prefill worker from
        the same ``(seed, request id, position)`` key — recomputing it
        here must agree bitwise, and the locally recomputed draw is the
        one trusted (a record corrupt enough to fail verification is a
        record whose producer's outputs are not to be taken on faith)."""
        plen = len(req.prompt)
        bucket = self.batcher.bucket_for(plen)
        self._prefill_buckets.add(bucket)
        if self.prefill_tick_cost > 0:
            self._tick_prefill_charge += max(
                1, math.ceil(bucket * self.prefill_tick_cost))
        self.pool.alloc(req.id, plen, owner=req.tenant_id)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :plen] = req.prompt
        logits, aux = self.pool.step(
            self._step_fn, self._model_leaves,
            self.pool.gather_indices([req.id]),
            jnp.asarray([0], jnp.int32), jnp.asarray(tokens),
            jnp.asarray([plen], jnp.int32))
        self._ran("prefill", aux, request_id=req.id, prompt_len=plen,
                  bucket=bucket)
        self.pool.table(req.id).length = plen
        _kv.note_pages_written(self.pool.pages_needed(plen))
        tok = int(self._sample_fn(
            logits, jnp.asarray([req.id], jnp.int32),
            jnp.asarray([plen], jnp.int32))[0])
        # only prompt KV was recomputed: any tokens beyond the first have
        # no K/V here, so the stream restarts from the re-drawn first
        # token — decode regenerates the rest from the same (seed, rid,
        # position) keys, bitwise what the lost engine would have emitted
        req.tokens[:] = [tok]

    # -- failure & failover (serve/fleet/failover.py drives these) ----------

    @property
    def crashed(self) -> bool:
        return self._crashed

    def crash(self) -> None:
        """Inject a permanent replica death: the engine stops beating and
        stops doing work; its KV pages are treated as unexportable (a
        dead chip's HBM is gone), so every in-flight request re-homes by
        re-prefill."""
        with self._lock:
            self._collect_pending()  # tokens the device had made are kept
            self._crashed = True

    def hang(self, ticks: int) -> None:
        """Inject a silent hang: no heartbeat and no work for ``ticks``
        scheduler ticks, then the engine resumes on its own.  A hang
        longer than the monitor's lease triggers failover (the pages are
        still intact, so KV salvage applies); a recovered replica is
        restored to serving — and a flapping one is quarantined by the
        controller."""
        with self._lock:
            self._collect_pending()
            self._hang_ticks = max(self._hang_ticks, int(ticks))

    def evacuate(self) -> list:
        """Drain every in-flight request off this (failed) engine:
        returns ``[(request, record_or_None, handle, timeline)]`` in
        deterministic admission order and leaves the batcher empty and
        the pool holding nothing but export HOLDs.

        Active requests' KV pages are EXPORTED when the engine is merely
        hung (``record`` carries them; the monitor verifies and either
        salvages them on a survivor or cancels the hold) and ``None``
        when it crashed — a dead chip's HBM is not salvageable.  Queued
        requests never had pages; a queued MIGRATED request's inbound
        ticket is settled here (the source's export hold must not leak
        just because the destination died).  Pages are freed either way:
        the exporter's hold keeps exported bytes alive until the monitor
        settles or cancels, so the pool's alloc/free balance survives
        the failure."""
        with self._lock:
            self._collect_pending()
            active_ids = {r.id for _slot, r in self.batcher.active()}
            out = []
            for req in self.batcher.evacuate():
                handle = self._handles.pop(req.id, None)
                tl = self._timelines.pop(req.id, None)
                record = None
                if req.id in active_ids:
                    if not self._crashed:
                        try:
                            record = self.pool.export_pages(req.id)
                        except ValueError:
                            # an outstanding export already holds these
                            # pages (e.g. a prefill worker mid-migration):
                            # that ticket owns the hold; re-prefill here
                            record = None
                    self.pool.free(req.id)
                if req.migration is not None:
                    # inbound migrated request that never imported: the
                    # settle runs outside engine locks via step()'s drain
                    self._pending_settles.append(req.migration.settle)
                    req.migration = None
                if handle is not None:
                    out.append((req, record, handle, tl))
            return out

    def accept_failover(self, req: Request, handle, timeline,
                        ticket=None) -> Optional[str]:
        """Survivor-side intake for one request re-homed off a failed
        replica.  With a ``ticket`` (a verified KV salvage), the request
        keeps its emitted tokens and its pages import at slot admission
        — decode continues exactly where the lost engine stopped.
        Without one, the request re-enters EMPTY (no tokens): prefill
        re-samples the first token and decode regenerates the stream,
        bitwise identical because sampling keys derive from ``(seed,
        request id, position)`` alone.  Either way the handle and
        timeline transfer, so the request resolves here as if nothing
        happened.  Returns ``None`` on acceptance or a shed reason the
        monitor uses to try the next survivor; admission bypasses shed
        latches and quota (``requeue``) — the request already passed the
        fleet's front door once."""
        if self.role == "prefill":
            raise ValueError("a prefill-role engine cannot accept "
                             "failover re-homes")
        with self._lock:
            self._collect_pending()
            if req.id in self._handles:
                return "id_collision"
            if ticket is not None:
                mreq = Request(
                    id=req.id, prompt=list(req.prompt),
                    max_new_tokens=req.max_new_tokens,
                    arrival=req.arrival, deadline_s=req.deadline_s,
                    tenant=req.tenant, tokens=list(req.tokens),
                    prefill_at=req.prefill_at, migration=ticket)
            else:
                mreq = Request(
                    id=req.id, prompt=list(req.prompt),
                    max_new_tokens=req.max_new_tokens,
                    arrival=req.arrival, deadline_s=req.deadline_s,
                    tenant=req.tenant)
            try:
                self.batcher.submit(mreq, requeue=True)
            except AdmissionQueueFull:
                return "queue_full"
            self._handles[req.id] = handle
            self._timelines[req.id] = timeline
            self._next_id = max(self._next_id, req.id + 1)
            _serve_m()["queue"].set(self.batcher.queue_len)
            return None

    def _ensure_pages(self, req_id: int, n_tokens: int) -> None:
        """Grow a sequence's allocation, evicting trie-only cached
        prefixes first when the free list is short — cached prefixes are
        a loan, never a reason to evict live work.  Raises
        :exc:`OutOfPages` only when the pool is genuinely full."""
        need = self.pool.pages_needed(n_tokens) - \
            len(self.pool.table(req_id).pages)
        if need > self.pool.free_pages and self.sharer is not None:
            self.sharer.reclaim(need - self.pool.free_pages)
        self.pool.ensure(req_id, n_tokens)

    def _decode_dispatch(self, last: Optional[_PendingDecode] = None
                         ) -> Optional[_PendingDecode]:
        """The dispatch half of one fixed-shape (num_slots, 1) decode step
        over every active slot; idle slots ride along masked into the
        scratch page.  Returns the step in flight, or ``None`` when no slot
        has a token to feed.

        ``last`` is the step before, dispatched and not yet collected:
        the host has not seen its tokens, but it knows each request's
        length by count (tokens appended, plus the one in flight), and
        from the count follow the write index, the sampled position, the
        page growth and whether ``last`` is the request's final step by
        ``max_new_tokens`` or ``max_seq_len``.  Such a request is simply
        not in this step; the others feed their token from ``last.toks``
        on the device.  An EOS cannot be counted: it is found when
        ``last`` is collected, and this step's token for that slot is
        dropped when this step is."""
        # host preparation, up to the dispatch
        with _tracing.span("serve.tick.decode.build"):
            active = self.batcher.active()
            if not active:
                return None
            t0 = self.clock()
            in_flight = {} if last is None else dict(last.active)
            seq_ids = [None] * self.batcher.num_slots
            # a token the host knows, or -1: the last step's, on the device
            tokens = np.zeros((self.batcher.num_slots, 1), np.int32)
            index = np.zeros(self.batcher.num_slots, np.int32)
            rids = np.zeros(self.batcher.num_slots, np.int32)
            positions = np.zeros(self.batcher.num_slots, np.int32)
            stepped, evicted = [], []
            for slot, req in active:
                flying = in_flight.get(slot) is req
                # the fed token's K/V lands at index ``length``; its
                # successor is sampled at global position ``length + 1``
                length = self.pool.table(req.id).length + flying
                if flying and (len(req.tokens) + 1 >= req.max_new_tokens
                               or length >= self.max_seq_len):
                    continue  # ends, by count, with the step in flight
                if not req.tokens and not flying:
                    continue  # its prefill is still in flight
                try:
                    self._ensure_pages(req.id, length + 1)
                    if self.sharer is not None:
                        # copy-on-write guard: never write into a page
                        # another table or the trie also references
                        # (sharing keeps the write target private by
                        # construction; this enforces the invariant rather
                        # than expecting it)
                        self.pool.copy_on_write(req.id, length)
                except OutOfPages:
                    # only reachable under an explicitly overcommitted pool
                    # (custom num_pages below full per-slot capacity);
                    # growth takes ANY free page, so a full pool is really
                    # full — retire the request with the tokens it has
                    # rather than wedging the scheduler loop.  Not on a
                    # count the host has not confirmed: ahead, the slot
                    # sits this step out and the next build decides
                    if not flying:
                        evicted.append((slot, req))
                    continue
                seq_ids[slot] = req.id
                tokens[slot, 0] = -1 if flying else req.tokens[-1]
                index[slot] = length
                rids[slot] = req.id
                positions[slot] = length + 1
                stepped.append((slot, req))
            for slot, req in evicted:
                self._retire(req, "evicted", self.clock())
            if not stepped:
                return None
            if self._paged_steps:
                self._count_paged_steps(index + 1)
            fed = (self.pool.gather_indices(seq_ids), jnp.asarray(index))
            keyed = (jnp.asarray(rids), jnp.asarray(positions))
            prev = self._no_prev if last is None else last.toks
        # the dispatch calls alone: the device has this step queued when
        # they return
        with _tracing.span("serve.tick.decode.device"):
            if self.paged_decode:
                toks, aux = self.pool.step(
                    self._paged_step_fn, self._model_leaves, *fed,
                    jnp.asarray(tokens), *keyed, prev)
            else:
                tokens = jnp.asarray(tokens)
                if last is not None:
                    tokens = jnp.where(tokens >= 0, tokens, prev[:, None])
                logits, aux = self.pool.step(self._step_fn,
                                             self._model_leaves, *fed,
                                             tokens, None)
                toks = self._sample_fn(logits, *keyed)
        how = "in_turn" if last is None else "ahead"
        self._decode_steps[how] += 1
        _serve_m()["decode_steps"].labels(dispatch=how).inc()
        return _PendingDecode(stepped, toks, t0, aux, tuple(
            int(index[slot]) + 1 for slot, _ in stepped))

    def _count_paged_steps(self, lengths) -> None:
        """What the paged decode kernel walks in this step, from the rows'
        lengths as the kernel gets them (an idle slot's one token of the
        scratch page included), by group of layers."""
        m = _serve_m()["paged_steps"]
        for name, steps in self._paged_steps.items():
            g = self.pool.by_group()[name]
            walked, held = walked_steps(lengths, g.pages_per_seq,
                                        g.page_size, g.spec.window)
            layers = g.spec.num_layers
            for kind, n in (("walked", walked), ("skipped", held - walked)):
                steps[kind] += n * layers
                m.labels(group=name, kind=kind).inc(n * layers)

    def _decode_collect(self, step: _PendingDecode) -> int:
        """The collect half of a decode step: its tokens on the host, then
        one emitted token for every request it covers that is still
        running.  A request that ended while the step was in flight (an
        EOS in the step before, a deadline, an eviction) has its token
        dropped and counted, never appended to a closed handle.  Returns
        the number of tokens emitted."""
        with _tracing.span("serve.tick.collect.device"):
            toks = np.asarray(step.toks)
        self._ran("decode", step.aux, rows=len(step.active),
                  context_tokens=sum(step.contexts), contexts=step.contexts)
        running = [(slot, req) for slot, req in step.active
                   if req.slot == slot]
        self._discard(len(step.active) - len(running))
        nactive = len(step.active)
        with _tracing.span("serve.tick.emit", tokens=len(running)):
            now = self.clock()
            for slot, req in running:
                self.pool.table(req.id).length += 1  # fed token's K/V written
                self._append_token(req, int(toks[slot]), now, batch=nactive)
            # the injected clock times the step (production: time.monotonic
            # measures the real compute; the virtual test clock keeps the
            # latency histogram deterministic — the prefill's convention)
            if running:
                dt = now - step.t0
                m = _serve_m()
                m["tok_latency"].observe(dt / len(running))
                m["tps"].set(len(running) / dt if dt > 0 else 0.0)
        return len(running)

    def _ran(self, kind: str, aux: dict, **info) -> None:
        """A device program's results are on the host: what it counted
        goes to the counters (its tokens were fetched, so reading ``aux``
        waits for nothing), and ``on_program`` hears of it."""
        routing = record_routing(jax.device_get(aux)) if aux else None
        if self.on_program is not None:
            self.on_program(kind, dict(info, routing=routing))

    def _collect_pending(self) -> int:
        """Collect the decode step left in flight, if there is one: what
        everything that reads or moves a request does first.  Returns the
        tokens emitted."""
        last, self._pending = self._pending, None
        return 0 if last is None else self._decode_collect(last)

    def _discard(self, n: int) -> None:
        if n:
            self._lookahead_discarded += n
            _serve_m()["discarded"].inc(n)

    def _append_token(self, req: Request, tok: int, now: float,
                      ttft: Optional[float] = None, batch: int = 1) -> None:
        """Account one generated token (its own K/V is written by the NEXT
        decode step, at index ``pool.table(id).length``); retire the
        request on EOS, budget exhaustion, or context exhaustion.
        ``batch`` is the decode step's batch composition (active slots),
        recorded on the token's ``serve.decode`` span — one span per
        generated token, the prefill-sampled first token included."""
        pt = self.pool.table(req.id)
        req.tokens.append(tok)
        if self.on_token is not None:
            # the router's in-flight ledger tracks tokens-emitted-so-far
            # (the failover monitor journals them at re-home time)
            self.on_token(req.id, tok)
        self._timelines[req.id].decode(now, batch=batch, slot=req.slot)
        m = _serve_m()
        m["tokens"].inc()
        if ttft is not None:
            m["ttft"].observe(max(ttft, 0.0))
        done = (tok == self.eos_id if self.eos_id is not None else False) \
            or len(req.tokens) >= req.max_new_tokens \
            or pt.length >= self.max_seq_len
        if done:
            self._retire(req, "completed", now)

    def _retire(self, req: Request, outcome: str, now: float) -> None:
        """Recycle the slot and pages, close the handle and timeline.
        ``outcome`` is ``completed``, ``expired`` (running deadline cut),
        or — only under an overcommitted pool — ``evicted``; the last two
        keep the tokens generated so far."""
        self.batcher.finish(req.slot)
        pages_held = len(self.pool.table(req.id).pages)
        self.pool.free(req.id)
        self._recycled += 1
        if self.defrag_every and self._recycled % self.defrag_every == 0:
            self.pool.defrag()
        m = _serve_m()
        error = None
        if outcome == "evicted":
            _journal.record("serve_evict", request_id=req.id,
                            tokens_generated=len(req.tokens))
            error = "evicted: KV pool exhausted (overcommitted num_pages)"
        elif outcome == "expired":
            age = now - req.arrival
            _journal.record("request_expired", request_id=req.id,
                            stage="running", age_s=round(age, 6),
                            tokens_generated=len(req.tokens))
            m["deadline"].labels(stage="running").inc()
            error = (f"deadline of {req.deadline_s}s expired after "
                     f"{age:.6g}s while decoding "
                     f"({len(req.tokens)} tokens generated)")
        m["requests"].labels(outcome=outcome).inc()
        self.tenant_meter.note_outcome(req.tenant_id, outcome)
        self.tenant_meter.note_tokens(req.tenant_id,
                                      generated=len(req.tokens))
        self.tenant_meter.note_pages(req.tenant_id, pages_held)
        # per-request token-stream fingerprint: O(tokens) host numpy, so
        # sampler nondeterminism is a field comparison in prod, not a
        # token-by-token diff (rides the handle, the /infer response, and
        # the request timeline)
        sfp = (_numerics.host_fingerprint_ints(req.tokens)
               if req.tokens else None)
        tl = self._timelines.pop(req.id)
        tl.close(outcome, now, tokens=len(req.tokens),
                 **({"stream_fp": sfp} if sfp is not None else {}))
        self._finalize_timeline(tl)
        self._handles.pop(req.id)._finish(
            outcome, req.tokens,
            ttft_s=(None if req.prefill_at is None
                    else req.prefill_at - req.arrival),
            latency_s=now - req.arrival, error=error,
            stream_fingerprint=sfp)
        if self.on_finish is not None:
            self.on_finish(req.id)  # prune the router's in-flight ledger

    def _finalize_timeline(self, tl: RequestTimeline,
                           grade: bool = True) -> None:
        """Resolved timeline -> trace buffer (+ SLO grading, + the process
        tracer when it is recording, so request traces stitch into the
        fleet timeline like any runtime span)."""
        self.trace_buffer.add(tl)
        if grade:
            self.slo.observe(tl)
        tracer = _tracing.get_tracer()
        if tracer.recording:
            tracer.record_external(tl.spans)

    # -- CTR inference ------------------------------------------------------

    def infer_ctr(self, dense, sparse) -> np.ndarray:
        """Read-only CTR scoring: stage the batch's embedding rows (host/
        remote pull through the HET caches — the fault-injectable PS path)
        and run the dense forward.  No gradients exist, so nothing can
        push; the stores are additionally flipped read-only at engine
        construction."""
        if self.ctr_model is None:
            raise RuntimeError("engine was built without a ctr_model")
        dense = jnp.asarray(np.asarray(dense, np.float32))
        sparse_np = np.asarray(sparse, np.int64)
        # stage-then-forward mutates the shared modules' staged rows, and
        # the HTTP front end is one-thread-per-request: serialize against
        # both concurrent CTR calls and the generation scheduler
        with self._lock:
            if self.ctr_follower is not None:
                # bounded staleness: install pending snapshot versions
                # BEFORE staging, so this batch never serves older than
                # the bound
                self.ctr_follower.gate()
            for mod in _staged_modules(self.ctr_model):
                mod.stage(sparse_np)
            logits = self.ctr_model.logits(dense, jnp.asarray(sparse_np))
        _serve_m()["ctr"].inc()
        return np.asarray(jax.nn.sigmoid(logits))

    # -- introspection ------------------------------------------------------

    def _embedding_stats(self) -> dict:
        """Embedding hit rates for ``/stats`` — tier stats for tiered
        layers, HBM hit stats otherwise, aggregated shard-cache stats as
        the fallback — beside the snapshot follower's freshness, so the
        CTR replica's cache efficiency scrapes next to the prefix-cache
        rates.  Reading the stats also refreshes the registry mirror
        (publish_cache_stats / the hetu_embed_* families), so
        ``/fleet/metrics`` carries the same numbers."""
        tables = []
        for mod in _staged_modules(self.ctr_model):
            fn = None
            for attr in ("tier_stats", "hit_stats", "stats"):
                fn = getattr(mod, attr, None)
                if fn is not None:
                    break
            if fn is None:
                # plain staged layer: the stats live on its HET cache
                fn = getattr(getattr(mod, "store", None), "stats", None)
            if fn is not None:
                tables.append(fn())
        return {"tables": tables,
                "snapshot": (None if self.ctr_follower is None
                             else self.ctr_follower.stats())}

    def stats(self) -> dict:
        """The ``/stats`` payload: scheduler + pool occupancy, the
        serving counters' current values, and an SLO quantile summary
        (TTFT / per-token latency p50+p99 from the serving histograms,
        via ``Histogram.quantile``, the one quantile implementation in
        the tree)."""
        with self._lock:
            reg = _obs.get_registry()
            snap = {k: v for k, v in reg.snapshot().items()
                    if k.startswith("hetu_serve_") and "_bucket" not in k}
            m = _serve_m()
            slo = {}
            for short, hist in (("ttft", m["ttft"]),
                                ("token_latency", m["tok_latency"])):
                h = hist.labels()
                for q, tag in ((0.5, "p50"), (0.99, "p99")):
                    v = h.quantile(q)
                    # empty histogram -> nan (deterministic); JSON has no
                    # NaN, so the payload carries null
                    slo[f"{short}_{tag}_s"] = (None if v is None or v != v
                                               else round(v, 6))
            return {
                "slo": slo,
                "shed_pressure": self.slo.shed_pressure(),
                "controller": {
                    "shedding": self.batcher.shed_reason,
                    "tenant_shedding": self.batcher.tenant_sheds,
                    "freeze_bucket_growth": self.freeze_bucket_growth,
                    "warm_buckets": sorted(self._prefill_buckets),
                },
                "tenants": {
                    "policy": self.batcher.policy.stats(),
                    "meter": self.tenant_meter.summary(),
                    "queue_lens": self.batcher.queue_lens(),
                },
                "queue_len": self.batcher.queue_len,
                "active_slots": self.batcher.active_slots,
                "num_slots": self.batcher.num_slots,
                "role": self.role,
                "migrations": dict(self._migrations),
                "prefix": (None if self.sharer is None
                           else self.sharer.stats()),
                "embedding": (None if self.ctr_model is None
                              else self._embedding_stats()),
                "speculative": (None if self.spec is None
                                else self.spec.stats()),
                "lookahead": {
                    "steps": dict(self._decode_steps),
                    "ahead_share": (
                        self._decode_steps["ahead"]
                        / max(sum(self._decode_steps.values()), 1)),
                    "discarded": self._lookahead_discarded,
                },
                "pool": self.pool.utilization(),
                "cache": self.pool.cache_stats(),
                "max_seq_len": self.max_seq_len,
                "sampling": self.sampling,
                "paged_decode": {
                    "enabled": self.paged_decode,
                    "steps": {name: dict(steps) for name, steps
                              in self._paged_steps.items()}},
                "fused_sampling": self._fused_sampling,
                "compile": _compile.compile_report(
                    self._step_fn, self._paged_step_fn, self._sample_fn),
                "metrics": snap,
            }


def _staged_modules(model) -> list:
    """Every staged host-embedding submodule of ``model`` (the Trainer's
    own discovery rule, reused)."""
    from hetu_tpu.exec.executor import _find_staged
    return _find_staged(model)


def _mark_stores_read_only(model) -> None:
    """Flip every local ``CacheTable`` store under ``model`` to read-only
    (serving must not train; see embed/engine.py).  A model that trained
    before being handed to the engine may hold buffered gradient pushes
    (``push_bound > 0``) and queued async pushes — drain them FIRST, so
    flipping the flag freezes the table instead of silently dropping the
    tail of training."""
    for mod in _staged_modules(model):
        flush_pushes = getattr(mod, "flush_pushes", None)
        if flush_pushes is not None:
            flush_pushes()
        stores = getattr(mod, "stores", None) or [getattr(mod, "store", None)]
        for st in stores:
            # engine CacheTable or PythonCacheTable (int8 tables) — the
            # shared is_het_cache duck tag
            if getattr(st, "is_het_cache", False) \
                    and hasattr(st, "read_only"):
                st.flush()  # apply buffered grads before freezing
                st.read_only = True
