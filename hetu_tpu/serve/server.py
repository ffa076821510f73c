"""Serving endpoint: ``/infer`` + ``/stats`` on the obs route table.

The satellite payoff of the ``obs/server.py`` refactor: this module
registers handlers on a :class:`~hetu_tpu.obs.server.Routes` table and
inherits every line of HTTP plumbing — plus the full telemetry surface
(``/metrics``, ``/metrics.json``, ``/healthz``, ``/journal``), so one
ephemeral port scrapes the serving SLO metrics next to the endpoints
they describe.

- ``POST /infer`` with ``{"prompt": [ids...], "max_new_tokens": n,
  "deadline_s": s?, "timeout_s": s?, "tenant": "id"?}`` blocks until
  the request resolves and returns ``{"request_id", "trace_id",
  "status", "tokens", "ttft_s", "latency_s"}`` — 200 on completion, 429
  on admission rejection, 504 on deadline expiry, 500 (with the step's
  exception in ``error``) when the engine's scheduler thread has died.
  ``tenant`` names the
  submitting tenant (omitted = the default tenant): admission is
  weighted-fair across tenants, quota buckets gate the front door, and
  the controller can shed one tenant without the others.  A malformed
  body is a **400 with a named diagnosis** (``"diagnosis": "bad_json" |
  "missing_field" | "too_large"`` plus a human-readable ``error``),
  never a traceback.  Non-completed
  responses carry a human-readable ``error`` naming what happened
  (rejection reason; deadline stage and age), and ``trace_id`` keys the
  request's full timeline at ``/trace/<request_id>``.  Rejections that
  are *load shedding* additionally carry a machine-readable ``reason``
  (``controller`` | ``queue_full`` | ``bucket_freeze`` | ``quota``) and
  a deterministic ``retry_after_s`` backoff hint (the token bucket's
  exact refill time on quota; pressure/queue-derived otherwise) — each
  also counted under ``hetu_serve_shed_total{reason=,tenant=}`` and
  journaled (kind ``shed``; quota rejections add ``tenant_quota``).
- ``GET /tenants`` returns the per-tenant metering artifact: the tenant
  policy (class, weight, quota bucket state), usage accumulators
  (requests by outcome, prompt/generated tokens, KV pages held,
  compile-seconds), per-tenant queue depths, and live scoped-shed
  latches — the billing surface.
- ``GET /controller`` (via the telemetry routes) reports the installed
  runtime controller's policy, latches, and decision list — README
  "Self-driving runtime".
- ``POST /infer`` with ``{"dense": [[...]], "sparse": [[...]]}`` runs
  the read-only CTR path and returns ``{"pred": [...]}``.
- ``GET /stats`` returns the engine's scheduler/pool/counter snapshot.
- ``GET /slo`` returns the SLO engine's summary: targets, per-stage
  request-seconds, burn rates (short+long window per target), and the
  shed-pressure gauge.
- ``GET /trace`` lists the trace buffer (ring ids + exemplar ids);
  ``GET /trace/<request_id>`` returns one request's timeline — outcome,
  exact stage decomposition, span list (Chrome-stitchable schema).
"""

from __future__ import annotations

import json

from hetu_tpu.obs.server import Routes, RoutedHTTPServer, telemetry_routes

__all__ = ["ServingServer", "serve_engine", "FleetServingServer",
           "serve_fleet_router"]

# an /infer body past this is refused up front (diagnosis "too_large"):
# the serving front door must never json-parse an unbounded upload on a
# handler thread
MAX_INFER_BODY_BYTES = 1 << 20

# handle status -> /infer response code ("failed": the engine's scheduler
# thread died on the error the body carries)
_HTTP_STATUS = {"completed": 200, "rejected": 429, "expired": 504,
                "evicted": 503, "failed": 500}


def _infer_400(diagnosis: str, detail: str):
    """One named /infer diagnosis: machine-readable ``diagnosis``
    (``bad_json`` | ``missing_field`` | ``too_large``) + human-readable
    ``error`` — the malformed-request counterpart of the shed
    ``reason`` contract."""
    return (json.dumps({"diagnosis": diagnosis, "error": detail}
                       ).encode(), "application/json", 400)


def _parse_infer(body):
    """Validate one /infer body.  Returns ``(request_dict, None)`` or
    ``(None, <400 response triple>)`` — the handler returns the triple
    verbatim, so a malformed body can never reach ``submit`` (or a
    traceback reach the client)."""
    if body is not None and len(body) > MAX_INFER_BODY_BYTES:
        return None, _infer_400(
            "too_large",
            f"request body is {len(body)} bytes; /infer accepts at "
            f"most {MAX_INFER_BODY_BYTES}")
    try:
        req = json.loads(body or b"{}")
    except (ValueError, UnicodeDecodeError) as e:
        return None, _infer_400(
            "bad_json", f"request body is not valid JSON: {e}")
    if not isinstance(req, dict):
        return None, _infer_400(
            "bad_json", f"request body must be a JSON object, got "
            f"{type(req).__name__}")
    return req, None


def _handle_body(handle) -> dict:
    """The shared /infer response body for a resolved handle."""
    body = {
        "request_id": handle.request_id,
        "trace_id": handle.trace_id,
        "status": handle.status,
        "tokens": handle.tokens,
        # deterministic token-stream fingerprint: same seed + same
        # prompt must return the same value however the batch was
        # composed — compare across replicas/replays to catch
        # sampler nondeterminism in prod (null until a token lands)
        "stream_fingerprint": handle.stream_fingerprint,
        "ttft_s": handle.ttft_s,
        "latency_s": handle.latency_s,
    }
    if handle.error is not None:
        # the distinguishable-error contract: a shed/expired request
        # says WHY, not just a status code
        body["error"] = handle.error
    if handle.shed_reason is not None:
        # machine-readable backoff contract: WHICH door closed
        # (controller | queue_full | bucket_freeze | quota) and how
        # long to back off — the quota hint is the token bucket's
        # exact refill arithmetic
        body["reason"] = handle.shed_reason
        if handle.retry_after_s is not None:
            body["retry_after_s"] = handle.retry_after_s
    if getattr(handle, "tenant", None) not in (None, "default"):
        body["tenant"] = handle.tenant
    return body


def serving_routes(engine) -> Routes:
    """Telemetry routes + the serving endpoints over ``engine``.  Always
    scrapes the process-wide registry — that is where the engine's
    ``hetu_serve_*`` metrics live, so accepting a custom registry here
    would serve a /metrics with none of the serving SLO series."""
    routes = telemetry_routes()

    def infer(query, body):
        req, err = _parse_infer(body)
        if err is not None:
            return err
        if "dense" in req or "sparse" in req:
            if "dense" not in req or "sparse" not in req:
                return _infer_400(
                    "missing_field", "the CTR path needs BOTH 'dense' "
                    "and 'sparse' feature arrays")
            pred = engine.infer_ctr(req["dense"], req["sparse"])
            return json.dumps({"pred": [float(p) for p in pred]}).encode()
        if "prompt" not in req:
            return _infer_400(
                "missing_field", "/infer requires a 'prompt' field (a "
                "list of token ids) — or 'dense'+'sparse' for the CTR "
                "path")
        handle = engine.submit(
            req["prompt"], int(req.get("max_new_tokens", 16)),
            deadline_s=req.get("deadline_s"),
            tenant=req.get("tenant"))
        # `or`: a JSON null (or 0) timeout_s must not disable the timeout
        # and hang this handler thread forever
        if not handle.wait(timeout=float(req.get("timeout_s") or 60.0)):
            return (json.dumps({"request_id": handle.request_id,
                                "trace_id": handle.trace_id,
                                "status": "pending"}).encode(),
                    "application/json", 504)
        status = _HTTP_STATUS[handle.status]
        return (json.dumps(_handle_body(handle)).encode(),
                "application/json", status)

    def tenants(query, body):
        return json.dumps({
            "policy": engine.batcher.policy.stats(),
            "meter": engine.tenant_meter.summary(),
            "queue_lens": engine.batcher.queue_lens(),
            "shedding": engine.batcher.tenant_sheds,
        }).encode()

    def trace_index(query, body):
        buf = engine.trace_buffer
        return json.dumps({
            "completed": buf.completed,
            "ring": buf.request_ids(),
            "exemplars": [t.request_id for t in buf.exemplars()],
        }).encode()

    def trace_one(rest, query, body):
        try:
            rid = int(rest)
        except ValueError:
            return (json.dumps({"error": f"bad request id {rest!r}"}
                               ).encode(), "application/json", 400)
        tl = engine.trace_buffer.get(rid)
        if tl is None:
            return (json.dumps({"error": f"no timeline for request {rid} "
                                "(evicted from the ring and not an "
                                "exemplar, or never submitted)"}).encode(),
                    "application/json", 404)
        return json.dumps(tl.summary()).encode()

    routes.add("POST", "/infer", infer)
    routes.add("GET", "/tenants", tenants)
    routes.add("GET", "/stats",
               lambda q, b: json.dumps(engine.stats()).encode())
    routes.add("GET", "/slo",
               lambda q, b: json.dumps(engine.slo.summary()).encode())
    routes.add("GET", "/trace", trace_index)
    routes.add_prefix("GET", "/trace/", trace_one)
    return routes


class ServingServer(RoutedHTTPServer):
    """HTTP front end over a :class:`~hetu_tpu.serve.engine.ServingEngine`
    (which should be :meth:`~hetu_tpu.serve.engine.ServingEngine.start`-ed
    so its scheduler loop drains the queue)."""

    def __init__(self, engine, port: int = 0, host: str = "127.0.0.1"):
        super().__init__(serving_routes(engine), port, host,
                         thread_name="hetu-serve-http")
        self.engine = engine


def serve_engine(engine, port: int = 0,
                 host: str = "127.0.0.1") -> ServingServer:
    """Start the engine's scheduler thread and an HTTP front end for it;
    returns the started server (``.port`` has the bound port; ``stop()``
    stops the HTTP thread — stop the engine separately)."""
    engine.start()
    srv = ServingServer(engine, port, host)
    srv.start()
    return srv


def fleet_serving_routes(router) -> Routes:
    """Telemetry routes + the FLEET serving endpoints: ``POST /infer``
    places each request through the router's affinity policy (same
    request/response contract as the single-engine handler — callers
    cannot tell one replica from N, which is the point), and ``GET
    /fleet/serve`` reports the router's aggregated stats (per-replica
    occupancy/pressure/cache state, placement tally by reason).  A
    :class:`~hetu_tpu.serve.fleet.DisaggRouter` adds role columns
    (``role`` + per-replica ``migrations``/``pages_export_held``) and
    the fleet-wide migration tally to the same payload — the
    disaggregated tier serves through this front end unchanged."""
    routes = telemetry_routes()

    def infer(query, body):
        req, err = _parse_infer(body)
        if err is not None:
            return err
        if "prompt" not in req:
            return _infer_400(
                "missing_field", "/infer requires a 'prompt' field (a "
                "list of token ids)")
        kwargs = {"deadline_s": req.get("deadline_s"),
                  "tenant": req.get("tenant")}
        if req.get("request_id") is not None:
            # the idempotent-resubmit contract: a client retrying after
            # a dropped connection names its request id — an id still in
            # flight re-attaches to the LIVE handle (surviving failover,
            # since re-homes keep the id), never double-submits
            kwargs["request_id"] = int(req["request_id"])
        handle = router.submit(
            req["prompt"], int(req.get("max_new_tokens", 16)), **kwargs)
        if not handle.wait(timeout=float(req.get("timeout_s") or 60.0)):
            return (json.dumps({"request_id": handle.request_id,
                                "trace_id": handle.trace_id,
                                "status": "pending"}).encode(),
                    "application/json", 504)
        status = _HTTP_STATUS[handle.status]
        return (json.dumps(_handle_body(handle)).encode(),
                "application/json", status)

    def tenants(query, body):
        return json.dumps({
            "replicas": [{
                "replica": i,
                "meter": e.tenant_meter.summary(),
                "queue_lens": e.batcher.queue_lens(),
                "shedding": e.batcher.tenant_sheds,
            } for i, e in enumerate(router.engines)],
            # replicas may share one TenantPolicy (fleet-wide quotas);
            # report the first engine's view as the fleet policy
            "policy": router.engines[0].batcher.policy.stats(),
        }).encode()

    routes.add("POST", "/infer", infer)
    routes.add("GET", "/tenants", tenants)
    routes.add("GET", "/fleet/serve",
               lambda q, b: json.dumps(router.stats()).encode())
    routes.add("GET", "/fleet/failover",
               lambda q, b: json.dumps(
                   {"installed": False} if router.monitor is None
                   else router.monitor.summary()).encode())
    return routes


class FleetServingServer(RoutedHTTPServer):
    """HTTP front end over a :class:`~hetu_tpu.serve.fleet.FleetRouter`
    (whose replicas should be ``start()``-ed so their scheduler loops
    drain the queues)."""

    def __init__(self, router, port: int = 0, host: str = "127.0.0.1"):
        super().__init__(fleet_serving_routes(router), port, host,
                         thread_name="hetu-fleet-http")
        self.router = router


def serve_fleet_router(router, port: int = 0,
                       host: str = "127.0.0.1") -> FleetServingServer:
    """Start every replica's scheduler thread and one fleet HTTP front
    end; returns the started server.  Accepts a ``FleetRouter`` or a
    role-aware ``DisaggRouter`` — the endpoint contract is identical."""
    router.start()
    srv = FleetServingServer(router, port, host)
    srv.start()
    return srv
