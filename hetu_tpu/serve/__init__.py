"""Online inference subsystem: continuous batching over a paged KV cache.

The ROADMAP's north star is a system that *serves* heavy traffic, and the
paper's headline capability — the cache-enabled parameter server for huge
embedding tables (HET, VLDB'22) — is as much a serving story as a training
one.  This package is the inference path the training stack feeds:

- :mod:`~hetu_tpu.serve.kv_cache` — block-allocated KV-cache pool with
  per-sequence page tables (alloc/grow/free/defrag), one pool and one
  table a group of layers where a model's layers are of several kinds
  (window and full attention), behind fixed padded shapes, so XLA compiles one decode program and one prefill program per
  prompt bucket;
- :mod:`~hetu_tpu.serve.batcher` — Orca-style continuous batching
  (OSDI'22): admission queue with depth limit and per-request deadlines,
  prefill/decode interleave, slot recycling the moment a sequence
  finishes;
- :mod:`~hetu_tpu.serve.engine` — ``ServingEngine`` driving seeded GPT
  generation through the decode seams in ``layers/attention.py`` /
  ``models/gpt.py``, plus a CTR inference path that pulls embeddings
  READ-ONLY through the HET caches (no gradient push; PS faults from
  ``exec/faults.py`` remain injectable);
- :mod:`~hetu_tpu.serve.server` — stdlib-HTTP ``/infer`` + ``/stats``
  endpoint registered on the ``obs.server`` route table, sharing a port
  with ``/metrics``;
- :mod:`~hetu_tpu.serve.tenant` — the multi-tenant front door: priority
  classes (``latency`` / ``batch``), deterministic token-bucket quotas,
  and the per-tenant metering artifact the ``/tenants`` endpoint serves;
  the batcher schedules admission weighted-fair across tenants and the
  controller sheds one tenant without touching the others;
- :mod:`~hetu_tpu.serve.loadgen` — seeded deterministic load generator
  (the acceptance tests replay identical request schedules), including
  template-heavy shared-prefix traces and adversarial multi-tenant
  mixes;
- :mod:`~hetu_tpu.serve.fleet` — the multi-replica tier: copy-on-write
  prefix sharing over the paged pool, speculative decoding with a draft
  GPT (accepted streams bitwise identical to non-speculative runs), and
  :class:`~hetu_tpu.serve.fleet.FleetRouter` placing requests across N
  replicas by prefix-cache affinity and shed pressure, and the
  disaggregated prefill/decode tier
  (:class:`~hetu_tpu.serve.fleet.DisaggRouter`): finished prefills
  migrate their KV pages to decode workers as verified records, streams
  staying bitwise identical to colocated same-seed runs.

Everything is deterministic under a fixed seed: same schedule, same
tokens, bit-for-bit — the serving counterpart of the training stack's
chaos-lineage guarantee.
"""

from hetu_tpu.serve.batcher import (AdmissionQueueFull, AdmissionShed,
                                    ContinuousBatcher, Request,
                                    TenantQuotaExceeded)
from hetu_tpu.serve.engine import RequestHandle, ServingEngine
from hetu_tpu.serve.kv_cache import (DoubleFree, GroupedKVCachePool,
                                     KVCachePool, OutOfPages, PageTable,
                                     UnsupportedCacheLayout)
from hetu_tpu.serve.loadgen import (LoadItem, generate_diurnal_load,
                                    generate_load,
                                    generate_multitenant_load,
                                    generate_prefill_burst_load,
                                    generate_shared_prefix_load)
from hetu_tpu.serve.server import (FleetServingServer, ServingServer,
                                   serve_engine, serve_fleet_router)
from hetu_tpu.serve.tenant import (DEFAULT_TENANT, Tenant, TenantPolicy,
                                   TokenBucket)
from hetu_tpu.serve.fleet import (DisaggRouter, FleetRouter,
                                  MigrationFileFabric,
                                  MigrationIntegrityError, MigrationRecord,
                                  PrefixSharer, PrefixTrie,
                                  SpeculativeDecoder)

__all__ = [
    "KVCachePool", "GroupedKVCachePool", "PageTable", "OutOfPages",
    "DoubleFree", "UnsupportedCacheLayout",
    "ContinuousBatcher", "Request", "AdmissionQueueFull", "AdmissionShed",
    "TenantQuotaExceeded",
    "Tenant", "TenantPolicy", "TokenBucket", "DEFAULT_TENANT",
    "ServingEngine", "RequestHandle",
    "ServingServer", "serve_engine",
    "FleetServingServer", "serve_fleet_router",
    "generate_load", "generate_shared_prefix_load",
    "generate_prefill_burst_load", "generate_multitenant_load",
    "generate_diurnal_load", "LoadItem",
    "PrefixTrie", "PrefixSharer", "SpeculativeDecoder", "FleetRouter",
    "DisaggRouter", "MigrationRecord", "MigrationIntegrityError",
    "MigrationFileFabric",
]
