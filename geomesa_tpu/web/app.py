"""REST endpoints over a datastore — stdlib WSGI, no framework.

Role parity: ``geomesa-web`` (SURVEY.md §2.19) — the reference exposes
Scalatra servlets for stats (``GeoMesaStatsEndpoint.scala``), query audit
(``QueryAuditEndpoint``), datastore management (``DataStoreServlet``) and a
GeoJSON REST API (``geomesa-geojson-rest``). Routes:

    GET    /api/version
    GET    /api/schemas                          list type names
    POST   /api/schemas                          {"name": ..., "spec": ...}
    POST   /api/sql                              {"q": "SELECT ..."} (caller
                                                 auths scope every row read)
    GET    /api/schemas/{name}                   spec + row count
    PATCH  /api/schemas/{name}                   {"add"|"keywords"|"rename_to"}
    DELETE /api/schemas/{name}
    POST   /api/schemas/{name}/features          GeoJSON FeatureCollection in
    PUT    /api/schemas/{name}/features          replace-by-id (WFS-T Update)
    DELETE /api/schemas/{name}/features?fids=a,b (WFS-T Delete)
    GET    /api/schemas/{name}/query?cql=&limit=&startIndex=&format=geojson|arrow|bin|avro|gml|csv|leaflet
    POST   /api/schemas/{name}/count-many        batched loose counts
    POST   /api/schemas/{name}/select-many       batched row retrieval (whole
                                                 batch in two device dispatches,
                                                 per-query Arrow IPC back)
    POST   /api/schemas/{name}/density-many      batched shared-viewport heatmaps
    POST   /api/schemas/{name}/aggregate         batched grouped aggregation
    GET    /api/schemas/{name}/stats?stats=Count();MinMax(a)   sketch stats
    GET    /api/schemas/{name}/stats/count?cql=&exact=
    GET    /api/schemas/{name}/stats/bounds?attr=
    GET    /api/schemas/{name}/stats/topk?attr=&k=
    GET    /api/schemas/{name}/density?cql=&bbox=&width=&height=
    GET    /api/audit?typeName=                  query audit records
    GET    /api/obs/flight?limit=&tenant=&type=&anomalies=1
                                                 query-audit flight recorder
    GET    /api/obs/costs?limit=&member=         per-plan-shape cost profiles
                                                 (+ per-member aggregates)
    GET    /api/obs/tenants?limit=               per-tenant usage accounting
    GET    /api/obs/audit?limit=                 continuous correctness auditor
    GET    /api/obs/lens?limit=&window=&type=    retained per-plan-signature
                                                 latency history + exemplars
                                                 (+ regression sentinel state)
    GET    /api/obs/lens?trace=<id>              resolve one exemplar trace_id
                                                 to its stitched span tree
    GET    /api/obs/stream?limit=&window=&topic= standing-query scale report:
                                                 subscriptions ranked by cost
                                                 share + delivery p99, capacity
                                                 section, backlog sentinel
    GET    /api/obs/stream?trace=<id>            resolve one delivery exemplar
                                                 to its stitched span tree
    GET    /api/obs/fusion?limit=                host-roundtrip fusion report
                                                 (signatures ranked by host-
                                                 choreography share)
    GET    /api/obs/ledger?format=json           raw roundtrip-ledger rollup
                                                 in the stable reconcile-
                                                 export schema (tpusync
                                                 --reconcile input)
    GET    /api/obs/shards                       shard map + live migration
                                                 states + migration counters
    GET    /api/metrics                          metrics snapshot (+ device
                                                 HBM residency section)
    GET    /api/metrics?format=prometheus       Prometheus text exposition
    GET    /wfs?service=WFS&request=...          OGC WFS 2.0 KVP binding
    GET    /wms?service=WMS&request=...          OGC WMS 1.3.0 (GetMap tiles)
    POST   /api/lease/{acquire|renew|release}    cross-host expiring leases
                                                 (ZK DistributedLocking role)
    POST   /api/journal/{topic}/publish          cross-host stream transport
    GET    /api/journal/{topic}/{poll|tpoll|end} (Kafka-broker role; tpoll
                                                 supports ?cursor= byte tail)
    POST   /subjects/{s}/versions                Confluent schema registry
    GET    /subjects/{s}/versions, /schemas/ids/{id}   (service half)
"""

from __future__ import annotations

import json
import re
from urllib.parse import parse_qs

import numpy as np

from geomesa_tpu import obs
from geomesa_tpu.obs import trace as _obstrace
from geomesa_tpu.obs import usage as _usage
from geomesa_tpu.planning.planner import Query
from geomesa_tpu.utils.timeouts import QueryTimeout as _QueryTimeout

__all__ = ["GeoMesaApp", "serve"]


class _HttpError(Exception):
    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status
        self.message = message


_STATUS = {
    200: "200 OK",
    201: "201 Created",
    204: "204 No Content",
    400: "400 Bad Request",
    403: "403 Forbidden",
    404: "404 Not Found",
    405: "405 Method Not Allowed",
    429: "429 Too Many Requests",
    500: "500 Internal Server Error",
    504: "504 Gateway Timeout",
}


def _jsonable(v):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return v


_ADMISSION_ROUTES = frozenset({
    # query-serving work subject to admission control: everything that
    # can reach a scan / device dispatch. Ops surfaces (metrics, obs,
    # audit, version), schema CRUD, writes, and the coordination planes
    # (lease/journal/registry) are exempt — shedding a metrics scrape
    # would blind the operator to the shed itself.
    "_query", "_sql", "_count_many", "_select_many", "_density_many",
    "_aggregate", "_stats", "_stats_count", "_stats_bounds",
    "_stats_topk", "_density", "_wfs", "_wms",
    # trajectory plane (docs/trajectory.md): corridor scans, track
    # aggregation, and interlink joins are all scan-class work
    "_tube_select", "_track_stats", "_link",
})


class GeoMesaApp:
    """WSGI application over one :class:`DataStore` (or merged view).

    ``admission``: a :class:`geomesa_tpu.serving.admission.
    AdmissionController` gating the query-serving routes (None = admit
    everything, the classic behavior); shed requests answer 429 +
    ``Retry-After``. ``coalesce_ms``: the request-coalescing batch
    window (None = ``GEOMESA_TPU_COALESCE_MS``, default 2 ms; <= 0
    disables) — concurrent compatible ``/query`` requests share one
    batched device dispatch (docs/serving.md).
    """

    def __init__(self, store, auth_provider=None, journal=None,
                 schema_registry=None, admission=None,
                 coalesce_ms: float | None = None):
        # auth_provider: security.auth.AuthorizationsProvider — derives the
        # caller's visibility auths from the request (None = unrestricted,
        # the single-tenant default)
        # journal: a stream.journal.JournalBus to expose over /api/journal
        # (cross-host stream transport — the Kafka-broker role; None hides
        # the routes)
        # schema_registry: a stream.confluent.SchemaRegistry to expose on
        # the Confluent REST paths (/subjects, /schemas/ids) so remote
        # producers/consumers share schema ids; None hides the routes
        from geomesa_tpu.utils.locks import LeaseService

        self.store = store
        self.auth_provider = auth_provider
        self.journal = journal
        self.schema_registry = schema_registry
        self.leases = LeaseService()
        self.admission = admission
        from geomesa_tpu.serving.coalesce import Coalescer, env_window_s

        window_s = (env_window_s() if coalesce_ms is None
                    else max(float(coalesce_ms), 0.0) / 1000.0)
        self.coalescer = (
            Coalescer(store, window_s=window_s,
                      metrics=getattr(store, "metrics", None))
            if window_s > 0 else None
        )
        self.routes = [
            # Confluent Schema Registry wire protocol (the
            # geomesa-kafka-confluent service half)
            ("POST", r"^/subjects/([^/]+)/versions$", self._registry_register),
            ("GET", r"^/subjects/([^/]+)/versions$", self._registry_versions),
            ("GET", r"^/schemas/ids/(\d+)$", self._registry_by_id),
            # cross-host coordination: named expiring leases (the ZK
            # DistributedLocking role for hosts with no shared mount)
            ("POST", r"^/api/lease/(acquire|renew|release)$", self._lease),
            # cross-host stream transport over the journal (Kafka-broker
            # role): publish + offset-addressed poll
            ("POST", r"^/api/journal/([^/]+)/publish$", self._journal_publish),
            ("GET", r"^/api/journal/([^/]+)/poll$", self._journal_poll),
            ("GET", r"^/api/journal/([^/]+)/tpoll$", self._journal_tpoll),
            ("GET", r"^/api/journal/([^/]+)/end$", self._journal_end),
            ("GET", r"^/api/version$", self._version),
            ("GET", r"^/api/schemas$", self._list_schemas),
            ("POST", r"^/api/schemas$", self._create_schema),
            ("POST", r"^/api/sql$", self._sql),
            ("GET", r"^/api/schemas/([^/]+)$", self._get_schema),
            ("PATCH", r"^/api/schemas/([^/]+)$", self._update_schema),
            ("DELETE", r"^/api/schemas/([^/]+)$", self._delete_schema),
            ("POST", r"^/api/schemas/([^/]+)/features$", self._add_features),
            ("PUT", r"^/api/schemas/([^/]+)/features$", self._update_features),
            ("DELETE", r"^/api/schemas/([^/]+)/features$", self._delete_features),
            ("GET", r"^/api/schemas/([^/]+)/query$", self._query),
            ("POST", r"^/api/schemas/([^/]+)/count-many$", self._count_many),
            ("POST", r"^/api/schemas/([^/]+)/select-many$", self._select_many),
            ("POST", r"^/api/schemas/([^/]+)/density-many$", self._density_many),
            ("POST", r"^/api/schemas/([^/]+)/aggregate$", self._aggregate),
            # trajectory plane: corridor scans + batched track aggregation
            # + two-store interlink (docs/trajectory.md § HTTP surface)
            ("POST", r"^/api/schemas/([^/]+)/tube-select$", self._tube_select),
            ("POST", r"^/api/schemas/([^/]+)/track-stats$", self._track_stats),
            ("POST", r"^/api/link$", self._link),
            ("GET", r"^/api/schemas/([^/]+)/stats$", self._stats),
            ("GET", r"^/api/schemas/([^/]+)/stats/count$", self._stats_count),
            ("GET", r"^/api/schemas/([^/]+)/stats/bounds$", self._stats_bounds),
            ("GET", r"^/api/schemas/([^/]+)/stats/topk$", self._stats_topk),
            ("GET", r"^/api/schemas/([^/]+)/density$", self._density),
            ("GET", r"^/api/audit$", self._audit),
            ("GET", r"^/api/obs/flight$", self._obs_flight),
            ("GET", r"^/api/obs/costs$", self._obs_costs),
            ("GET", r"^/api/obs/tenants$", self._obs_tenants),
            ("GET", r"^/api/obs/audit$", self._obs_audit),
            # profiling plane: retained latency history + trace exemplars,
            # the host-roundtrip fusion report (docs/observability.md
            # § Query lens & host-roundtrip ledger)
            ("GET", r"^/api/obs/lens$", self._obs_lens),
            # stream lens: per-subscription delivery histograms + the
            # standing-query scale report (docs/streaming.md § Stream lens)
            ("GET", r"^/api/obs/stream$", self._obs_stream),
            ("GET", r"^/api/obs/fusion$", self._obs_fusion),
            ("GET", r"^/api/obs/ledger$", self._obs_ledger),
            # elasticity plane: shard map + live migration states +
            # process-wide migration counters (docs/operations.md)
            ("GET", r"^/api/obs/shards$", self._obs_shards),
            ("GET", r"^/api/metrics$", self._metrics),
            # OGC WFS 2.0 KVP binding (GeoServer-plugin role, web/wfs.py)
            ("GET", r"^/wfs/?$", self._wfs),
            # OGC WMS 1.3.0 KVP binding: GetCapabilities + GetMap tiles
            ("GET", r"^/wms/?$", self._wms),
        ]

    # -- WSGI ----------------------------------------------------------------
    def __call__(self, environ, start_response):
        method = environ["REQUEST_METHOD"]
        path = environ.get("PATH_INFO", "/")
        params = {
            k: v[0] for k, v in parse_qs(environ.get("QUERY_STRING", "")).items()
        }
        # reserved keys: only the server may set them — never the client
        params.pop("__auths__", None)
        params.pop("__deadline__", None)
        params.pop("__tenant__", None)
        if self.auth_provider is not None:
            params["__auths__"] = self.auth_provider.auths(environ)
        # tenant attribution (obs.usage / docs/observability.md § Usage
        # metering): the caller's X-Geomesa-Tenant assertion (same proxy-
        # trust posture as X-Geomesa-Auths — the fronting proxy must own
        # this header), bound to the request context below so every store
        # audit record and outbound federated RPC attributes to it;
        # absent header = the default (anonymous) tenant
        tenant = (environ.get("HTTP_X_GEOMESA_TENANT") or "").strip()
        if tenant:
            params["__tenant__"] = tenant
        # deadline propagation (X-Geomesa-Deadline-Ms): the caller's
        # REMAINING budget in ms, re-anchored on this host's monotonic
        # clock — see geomesa_tpu.resilience.http / docs/resilience.md
        hdr = environ.get("HTTP_X_GEOMESA_DEADLINE_MS")
        if hdr is not None:
            from geomesa_tpu.utils.timeouts import Deadline

            try:
                params["__deadline__"] = Deadline.after_ms(float(hdr))
            except ValueError:
                return self._respond(
                    start_response, 400,
                    {"error": f"bad X-Geomesa-Deadline-Ms header: {hdr!r}"},
                    "application/json",
                )
        # trace propagation (X-Geomesa-Trace): join the remote caller's
        # trace; a sampled context force-records this request's tree and
        # returns it serialized so the caller grafts it under its RPC span
        # (the stitched federated tree — docs/observability.md)
        ctx = _obstrace.extract(environ.get("HTTP_X_GEOMESA_TRACE"))
        # per-request metrics (the servlet AggregatedMetricsFilter role):
        # counter per route pattern + total, into the store's registry so
        # /api/metrics reports request rates alongside store counters
        metrics = getattr(self.store, "metrics", None)
        if metrics is not None:
            metrics.counter("web.requests").inc()
        try:
            body = None
            if method in ("POST", "PUT", "PATCH", "DELETE"):
                length = int(environ.get("CONTENT_LENGTH") or 0)
                raw = environ["wsgi.input"].read(length) if length else b""
                body = json.loads(raw) if raw else None
            matched_path = False
            for m, pattern, handler in self.routes:
                match = re.match(pattern, path)
                if match:
                    matched_path = True
                    if m == method:
                        if (
                            self.admission is not None
                            and handler.__name__ in _ADMISSION_ROUTES
                        ):
                            # the serving plane's front gate: per-tenant
                            # token bucket + priority class; a shed
                            # answers 429 + Retry-After BEFORE any scan
                            # or device work (docs/serving.md)
                            import math

                            decision = self.admission.admit(
                                tenant or None,
                                environ.get("HTTP_X_GEOMESA_PRIORITY")
                                or "normal",
                            )
                            if not decision.admitted:
                                if metrics is not None:
                                    metrics.counter("web.shed").inc()
                                return self._respond(
                                    start_response, 429,
                                    {
                                        "error": "admission shed: tenant "
                                                 "over rate/budget",
                                        "retry_after_s": round(
                                            decision.retry_after_s, 3),
                                    },
                                    "application/json",
                                    extra_headers=[(
                                        "Retry-After",
                                        str(max(1, math.ceil(
                                            decision.retry_after_s))),
                                    )],
                                )
                        # one trace root per request: each server thread's
                        # ContextVar starts empty, so concurrent requests
                        # build disjoint span trees; the handler's store
                        # queries/serialization nest underneath
                        route = handler.__name__.lstrip("_")
                        if ctx is not None and ctx.sampled:
                            span_cm = _obstrace.propagated(
                                "http", ctx, method=method, path=path,
                                route=route)
                        else:
                            span_cm = obs.span(
                                "http", method=method, path=path, route=route)
                        from contextlib import nullcontext

                        # an unsampled incoming context must stay unsampled
                        # on OUR outbound hops too (fan-out to members):
                        # honoring the flag end to end, not just locally
                        join_cm = (
                            _obstrace.unsampled_join()
                            if ctx is not None and not ctx.sampled
                            else nullcontext()
                        )
                        with span_cm as sp, join_cm, \
                                _usage.tenant_context(tenant):
                            if (
                                ctx is not None and not ctx.sampled
                                and isinstance(sp, _obstrace.Span)
                            ):
                                # unsampled context + local tracing on: the
                                # ids still join the caller's trace (honoring
                                # the flag means not FORCING a record)
                                sp.trace_id = ctx.trace_id
                                sp.parent_id = ctx.parent_span_id
                            if metrics is not None:
                                metrics.counter(
                                    f"web.requests.{route}"
                                ).inc()
                                with metrics.timer("web.request_ms").time():
                                    status, payload, ctype = self._run_handler(
                                        handler, match.groups(), params, body
                                    )
                            else:
                                status, payload, ctype = self._run_handler(
                                    handler, match.groups(), params, body
                                )
                        extra = None
                        if ctx is not None and ctx.sampled:
                            extra = [(
                                _obstrace.TRACE_RETURN_HEADER,
                                _obstrace.serialize_subtree(sp),
                            )]
                        out = self._respond(
                            start_response, status, payload, ctype,
                            extra_headers=extra)
                        if out and out[0]:
                            # response-payload bytes attribute to the
                            # tenant (the store can't see serialization);
                            # headerless traffic accrues under the
                            # default (anonymous) tenant — egress
                            # attribution must not undercount the bulk
                            # of an unlabeled deployment's load
                            _usage.get().note_bytes_out(
                                tenant or None, len(out[0]))
                        return out
            raise _HttpError(405 if matched_path else 404,
                             "method not allowed" if matched_path else "not found")
        except _HttpError as e:
            return self._respond(
                start_response, e.status, {"error": e.message}, "application/json"
            )
        except _QueryTimeout as e:
            # a spent/blown deadline — shed before work or expired during
            # it — answers 504 so the caller's client maps it back to its
            # own QueryTimeout (the end-to-end timeout contract)
            return self._respond(
                start_response, 504, {"error": str(e)}, "application/json"
            )
        except KeyError as e:
            return self._respond(
                start_response, 404, {"error": str(e)}, "application/json"
            )
        except PermissionError as e:
            return self._respond(
                start_response, 403, {"error": str(e)}, "application/json"
            )
        except (ValueError, TypeError) as e:
            return self._respond(
                start_response, 400, {"error": str(e)}, "application/json"
            )

    def _run_handler(self, handler, groups, params, body):
        """Dispatch one matched route under the request's deadline.

        No deadline: a plain call (zero overhead). With one: work whose
        budget is already spent is shed with 504 BEFORE the handler runs
        (no scan, no device work); otherwise the handler runs under
        :func:`run_with_timeout` registered with the store's Watchdog, so
        a blown budget abandons the worker thread, counts it, and still
        answers 504 — the ThreadManagement posture applied per hop."""
        deadline = params.get("__deadline__")
        if deadline is None:
            return handler(*groups, params=params, body=body)
        from geomesa_tpu.utils.timeouts import run_with_timeout

        metrics = getattr(self.store, "metrics", None)
        rem_s = deadline.remaining_s()
        if rem_s <= 0:
            if metrics is not None:
                metrics.counter("web.deadline.shed").inc()
            raise _QueryTimeout("deadline spent before processing began")
        wd = getattr(self.store, "watchdog", None)
        token = None
        if wd is not None:
            token = wd.register(
                f"http {handler.__name__.lstrip('_')} "
                f"(deadline {rem_s * 1000:.0f}ms)")
        abandoned = False
        try:
            return run_with_timeout(
                handler, rem_s, *groups, params=params, body=body)
        except _QueryTimeout as e:
            # only count THIS request abandoned when OUR worker is the
            # one still running — a store scan that already shed/expired
            # (and counted itself) re-raises with the marker cleared
            abandoned = getattr(e, "worker_abandoned", True)
            if metrics is not None:
                metrics.counter("web.deadline.expired").inc()
            raise
        finally:
            # finally: a handler error (404/400/403) must release the
            # registration too, not leak it in the active set forever
            if token is not None:
                wd.complete(token, timed_out=abandoned)

    def _respond(self, start_response, status, payload, ctype,
                 extra_headers=None):
        if isinstance(payload, (dict, list)):
            data = json.dumps(_jsonable(payload)).encode()
        elif payload is None:
            data = b""
        else:
            data = payload
        headers = [("Content-Type", ctype), ("Content-Length", str(len(data)))]
        if extra_headers:
            headers.extend(extra_headers)
        start_response(_STATUS[status], headers)
        return [data]

    # -- handlers ------------------------------------------------------------
    def _version(self, params, body):
        import geomesa_tpu

        return 200, {"name": "geomesa-tpu", "version": geomesa_tpu.__version__}, "application/json"

    # -- cross-host coordination (no-shared-mount deployments) ---------------
    def _lease(self, op, params, body):
        """Named expiring leases (``utils.locks.LeaseService``): the
        coordinator half of ``http_lease_lock``. Always 200 — contention
        is a normal outcome (``ok: false``), not an HTTP error."""
        b = body or {}
        name = b.get("name")
        if not name or not isinstance(name, str):
            raise _HttpError(400, "body must include a lease 'name'")
        ttl = float(b.get("ttl_s", 60.0))
        if op == "acquire":
            out = self.leases.acquire(name, str(b.get("holder", "?")), ttl)
        elif op == "renew":
            out = self.leases.renew(name, str(b.get("token", "")), ttl)
        else:
            out = self.leases.release(name, str(b.get("token", "")))
        return 200, out, "application/json"

    def _require_journal(self):
        if self.journal is None:
            raise _HttpError(404, "no journal attached to this server")
        return self.journal

    # NB: WSGI servers deliver PATH_INFO already percent-decoded (PEP
    # 3333), so the matched topic/subject group is the literal name — do
    # NOT unquote again. Names containing '/' are not addressable over
    # these path routes (journal topics are `geomesa-<type>`, so this
    # never arises in practice).
    def _journal_publish(self, topic, params, body):
        import base64

        bus = self._require_journal()
        b = body or {}
        if "data_b64" not in b:
            raise _HttpError(400, "body must include 'data_b64'")
        bus.publish(
            topic, str(b.get("key", "")),
            base64.b64decode(b["data_b64"]),
            barrier=bool(b.get("barrier", False)),
        )
        return 200, {"ok": True}, "application/json"

    def _journal_poll(self, topic, params, body):
        import base64

        bus = self._require_journal()
        partition = self._int_param(params, "partition") or 0
        offset = self._int_param(params, "offset") or 0
        max_n = self._int_param(params, "max_n") or 256
        msgs = bus.poll(topic, partition, offset, max_n)
        return 200, {
            "payloads": [base64.b64encode(p).decode() for p in msgs],
            "end": bus.end_offset(topic, partition),
        }, "application/json"

    def _journal_tpoll(self, topic, params, body):
        import base64

        bus = self._require_journal()
        if "cursor" in params:
            # byte-cursor tail: O(new data) per call — the long-lived
            # remote-subscriber path
            msgs, nxt = bus.total_poll_bytes(
                topic, self._int_param(params, "cursor") or 0)
            return 200, {
                "payloads": [base64.b64encode(p).decode() for p in msgs],
                "cursor": nxt,
            }, "application/json"
        offset = self._int_param(params, "offset") or 0
        max_n = self._int_param(params, "max_n") or 256
        msgs = bus.total_poll(topic, offset, max_n)
        return 200, {
            "payloads": [base64.b64encode(p).decode() for p in msgs],
            "size": bus.topic_size(topic),
        }, "application/json"

    def _journal_end(self, topic, params, body):
        bus = self._require_journal()
        partition = self._int_param(params, "partition") or 0
        return 200, {
            "end": bus.end_offset(topic, partition),
            "partitions": bus.partitions,
            "size": bus.topic_size(topic),
        }, "application/json"

    # -- Confluent Schema Registry protocol ----------------------------------
    def _require_registry(self):
        if self.schema_registry is None:
            raise _HttpError(404, "no schema registry attached to this server")
        return self.schema_registry

    def _registry_register(self, subject, params, body):
        reg = self._require_registry()
        b = body or {}
        if "schema" not in b:
            raise _HttpError(400, 'body must be {"schema": "<avro json>"}')
        # Confluent wire format carries the schema as a STRING of JSON
        schema = (json.loads(b["schema"]) if isinstance(b["schema"], str)
                  else b["schema"])
        sid = reg.register(subject, schema)
        return 200, {"id": sid}, "application/json"

    def _registry_versions(self, subject, params, body):
        reg = self._require_registry()
        return 200, reg.versions(subject), "application/json"

    def _registry_by_id(self, sid, params, body):
        reg = self._require_registry()
        try:
            schema = reg.schema_by_id(int(sid))
        except KeyError:
            raise _HttpError(404, f"schema id {sid} not found")
        return 200, {"schema": json.dumps(schema)}, "application/json"

    def _list_schemas(self, params, body):
        return 200, {"schemas": self.store.list_schemas()}, "application/json"

    def _sql(self, params, body):
        if not isinstance(body, dict) or not body.get("q"):
            raise _HttpError(400, "body must be {\"q\": \"SELECT ...\"}")
        from geomesa_tpu.geometry.types import Geometry
        from geomesa_tpu.geometry.wkt import to_wkt
        from geomesa_tpu.sql.engine import SqlError, sql as _run_sql

        try:
            # caller auths thread into EVERY internal store query; paths
            # that cannot apply row visibility (mesh aggregation, device
            # join gather) decline automatically inside sql()
            res = _run_sql(self.store, str(body["q"]),
                           auths=params.get("__auths__"))
        except SqlError as e:
            raise _HttpError(400, f"sql error: {e}")

        def _cell(v):
            # geometry-typed projections serialize as WKT (the engine's own
            # convention for geometry-valued UDF results)
            return to_wkt(v) if isinstance(v, Geometry) else _jsonable(v)

        return 200, {
            "columns": list(res.columns),
            "rows": [[_cell(v) for v in row] for row in res.rows()],
        }, "application/json"

    def _create_schema(self, params, body):
        if not body or "name" not in body or "spec" not in body:
            raise _HttpError(400, "body must be {\"name\": ..., \"spec\": ...}")
        self.store.create_schema(body["name"], body["spec"])
        return 201, {"created": body["name"]}, "application/json"

    def _get_schema(self, name, params, body):
        sft = self.store.get_schema(name)
        if self._restricted_auths(name, params) is not None:
            # same leak class as the stats endpoints: the store-wide count
            # reveals restricted rows — report only the caller-visible count
            count = self._visible_stat(name, params, "Count()").count
        else:
            count = self.store.stats_count(name)
        return 200, {
            "name": sft.name,
            "spec": sft.to_spec(),
            "attributes": [
                {"name": a.name, "type": a.type.value} for a in sft.attributes
            ],
            "count": count,
        }, "application/json"

    def _update_schema(self, name, params, body):
        """Schema evolution (updateSchema role): body keys ``add`` (spec
        string or list of specs), ``keywords`` (list of strings),
        ``rename_to`` (string)."""
        if not isinstance(body, dict) or not ({"add", "keywords", "rename_to"} & set(body)):
            raise _HttpError(400, "expected {add|keywords|rename_to} body")
        add = body.get("add")
        if add is not None and not (
            isinstance(add, str)
            or (isinstance(add, list) and all(isinstance(s, str) for s in add))
        ):
            raise _HttpError(400, "'add' must be a spec string or list of them")
        keywords = body.get("keywords")
        if keywords is not None and not (
            isinstance(keywords, list)
            and all(isinstance(k, str) for k in keywords)
        ):
            raise _HttpError(400, "'keywords' must be a list of strings")
        rename_to = body.get("rename_to")
        if rename_to is not None and not isinstance(rename_to, str):
            raise _HttpError(400, "'rename_to' must be a string")
        # store ValueErrors map to JSON 400 in __call__
        sft = self.store.update_schema(
            name, add=add, keywords=keywords, rename_to=rename_to
        )
        return 200, {"name": sft.name, "spec": sft.to_spec()}, "application/json"

    def _delete_schema(self, name, params, body):
        self.store.delete_schema(name)
        return 204, None, "application/json"

    def _geojson_records(self, name, body, require_id: bool):
        """GeoJSON FeatureCollection (or bare Feature) body → (records,
        fids). ``require_id``: modify semantics address features by id."""
        if not isinstance(body, dict):
            raise _HttpError(400, "expected a GeoJSON FeatureCollection body")
        feats = body.get("features", [body] if body.get("type") == "Feature" else None)
        if feats is None:
            raise _HttpError(400, "expected a GeoJSON FeatureCollection body")
        from geomesa_tpu.convert.json_converter import geojson_geometry

        sft = self.store.get_schema(name)
        recs, fids = [], []
        for i, f in enumerate(feats):
            if require_id and "id" not in f:
                raise _HttpError(400, f"feature {i}: updates require an id")
            props = dict(f.get("properties") or {})
            if sft.geom_field:
                g = geojson_geometry(f.get("geometry"))
                if g is None:
                    raise _HttpError(400, f"feature {i}: missing/invalid geometry")
                props[sft.geom_field] = g
            recs.append({a.name: props.get(a.name) for a in sft.attributes})
            fids.append(str(f["id"]) if "id" in f else None)
        return recs, fids

    def _add_features(self, name, params, body):
        recs, fids = self._geojson_records(name, body, require_id=False)
        if any(f is None for f in fids):
            fids = None  # auto-generated z3-uuid fids
        elif fids and self._restricted_auths(name, params) is not None:
            # explicit ids from a restricted caller could shadow hidden rows,
            # and any existence check would itself be an oracle — restricted
            # writers get auto-generated ids only
            raise _HttpError(
                403, "explicit feature ids require unrestricted access"
            )
        n = self.store.write(name, recs, fids=fids)
        return 201, {"written": n}, "application/json"

    def _assert_fids_mutable(self, name, params, fids):
        """Visibility guard for mutations: a restricted caller may only
        address ids it can SEE. Any id NOT in the caller-visible set — hidden
        or nonexistent alike, so the response can't be used as an existence
        oracle — is a uniform 403. Returns the caller's auths (for the
        store-level enforcement that re-checks under the mutation lock,
        closing the check-then-act race), or None when unrestricted."""
        auths = self._restricted_auths(name, params)
        if auths is None:
            return None
        from geomesa_tpu.filter import ast as _ast

        visible = set(
            self.store.query(
                name, Query(filter=_ast.FidIn(tuple(fids)), auths=auths)
            ).table.fids.tolist()
        )
        if set(fids) - visible:
            raise _HttpError(403, "forbidden: target features not visible")
        return auths

    def _update_features(self, name, params, body):
        """WFS-T Update analog: replace features by id (modify writer);
        store-side ValueError maps to 400 via the dispatch handler."""
        recs, fids = self._geojson_records(name, body, require_id=True)
        auths = self._assert_fids_mutable(name, params, fids)
        n = self.store.update_features(name, recs, fids, visible_to=auths)
        return 200, {"updated": n}, "application/json"

    def _delete_features(self, name, params, body):
        """WFS-T Delete analog: ``?fids=a,b,c`` (or body {"fids": [...]})."""
        fids = [f for f in params.get("fids", "").split(",") if f]
        if not fids and isinstance(body, dict):
            fids = body.get("fids")
        if not (
            isinstance(fids, list)
            and fids
            and all(isinstance(f, str) for f in fids)
        ):
            raise _HttpError(400, 'expected ?fids=a,b,c or {"fids": [...]}')
        auths = self._assert_fids_mutable(name, params, fids)
        n = self.store.delete_features(name, fids, visible_to=auths)
        return 200, {"deleted": n}, "application/json"

    def _int_param(self, params, key):
        if key not in params:
            return None
        try:
            v = int(params[key])
        except ValueError:
            raise _HttpError(400, f"{key} must be an integer: {params[key]!r}")
        if v < 0:
            raise _HttpError(400, f"{key} must be >= 0: {v}")
        return v

    def _parse_query(self, params) -> Query:
        hints = {}
        if params.get("__tenant__"):
            # tenant rides the query object too (the audit record's
            # primary source; the context var covers paths that build
            # their own Query instances)
            hints["tenant"] = params["__tenant__"]
        if params.get("__deadline__") is not None:
            # the store's own scan honors the remaining budget too: it
            # sheds before device work when the budget is gone and caps
            # its watchdog timeout at the remaining time
            hints["deadline"] = params["__deadline__"]
        limit = self._int_param(params, "limit")
        props = params["properties"].split(",") if params.get("properties") else None
        sort_by = None
        if params.get("sortBy"):
            fld = params["sortBy"]
            desc = fld.startswith("-")
            sort_by = (fld.lstrip("-"), desc)
        return Query(
            filter=params.get("cql") or None,
            limit=limit,
            # OGC startIndex paging (use with sortBy for stable pages)
            start_index=self._int_param(params, "startIndex"),
            properties=props,
            sort_by=sort_by,
            hints=hints,
            auths=params.get("__auths__"),
        )

    def _query(self, name, params, body):
        q = self._parse_query(params)
        fmt = params.get("format", "geojson")
        if self.coalescer is not None:
            # request coalescing (docs/serving.md): concurrent /query
            # requests for the same type share ONE select_many device
            # dispatch; per-query auths/hints/deadlines are preserved,
            # and a deadline too tight for the window bypasses it. A
            # store without select_many executes singly (no window).
            r = self.coalescer.submit(name, "select", q)
        else:
            r = self.store.query(name, q)
        from geomesa_tpu.web.formats import UnknownFormat, format_table

        try:
            # the pipeline's last stage: payload encoding, timed apart from
            # the store scan it follows
            with obs.span("serialize", format=fmt, rows=r.count):
                payload, ctype = format_table(r.table, fmt)
        except UnknownFormat:
            raise _HttpError(400, f"unknown format {fmt!r}") from None
        return 200, payload, ctype

    def _tube_select(self, name, params, body):
        """POST {"track": [[x, y, epoch_ms], ...], "buffer_deg": f,
        "time_buffer_ms": n, "filter"?: cql, "format"?: fmt} → matching
        features through the batched device corridor engine."""
        if not body or "track" not in body:
            raise _HttpError(400, 'body must include "track"')
        try:
            track = [(float(x), float(y), int(t)) for x, y, t in body["track"]]
            buf = float(body.get("buffer_deg", 0.0))
            tb = int(body.get("time_buffer_ms", 0))
        except (TypeError, ValueError) as e:
            raise _HttpError(400, f"bad tube-select body: {e}") from None
        from geomesa_tpu.trajectory.corridor import tube_select_device
        from geomesa_tpu.web.formats import UnknownFormat, format_table

        try:
            table = tube_select_device(
                self.store, name, track, buf, tb,
                filter=body.get("filter"), auths=params.get("__auths__"))
        except ValueError as e:
            raise _HttpError(400, str(e)) from None
        except KeyError as e:
            raise _HttpError(404, str(e)) from None
        fmt = body.get("format", params.get("format", "geojson"))
        try:
            with obs.span("serialize", format=fmt, rows=len(table)):
                payload, ctype = format_table(table, fmt)
        except UnknownFormat:
            raise _HttpError(400, f"unknown format {fmt!r}") from None
        return 200, payload, ctype

    def _track_stats(self, name, params, body):
        """POST {"track_field": str, "filter"?: cql, "dwell_eps_deg"?: f}
        → per-entity track aggregates (one fused device pass)."""
        if not body or "track_field" not in body:
            raise _HttpError(400, 'body must include "track_field"')
        from geomesa_tpu.trajectory.state import (
            DEFAULT_DWELL_EPS_DEG, track_stats)

        try:
            stats = track_stats(
                self.store, name, str(body["track_field"]),
                filter=body.get("filter"),
                dwell_eps_deg=float(
                    body.get("dwell_eps_deg", DEFAULT_DWELL_EPS_DEG)),
                auths=params.get("__auths__"))
        except (TypeError, ValueError) as e:
            raise _HttpError(400, f"bad track-stats request: {e}") from None
        except KeyError as e:
            raise _HttpError(404, str(e)) from None
        n = len(stats["track"])
        return 200, {
            "entities": n,
            "columns": {k: [_jsonable(x) if isinstance(x, (np.generic,))
                            else (x if isinstance(x, (int, float, str))
                                  else str(x))
                            for x in v.tolist()]
                        for k, v in stats.items()},
        }, "application/json"

    def _link(self, params, body):
        """POST {"left": type, "right": type, "pred"?: "intersects"|
        "dwithin", "distance"?: f, "time_buffer_ms"?: n, "left_filter"?,
        "right_filter"?} → exact interlink pair set (2D / XZ3 legs)."""
        if not body or "left" not in body or "right" not in body:
            raise _HttpError(400, 'body must include "left" and "right"')
        from geomesa_tpu.trajectory.interlink import interlink

        tb = body.get("time_buffer_ms")
        try:
            pairs = interlink(
                self.store, str(body["left"]), self.store,
                str(body["right"]), pred=body.get("pred", "intersects"),
                distance=float(body.get("distance", 0.0)),
                time_buffer_ms=(None if tb is None else int(tb)),
                lfilter=body.get("left_filter"),
                rfilter=body.get("right_filter"),
                auths=params.get("__auths__"))
        except ValueError as e:
            raise _HttpError(400, str(e)) from None
        except KeyError as e:
            raise _HttpError(404, str(e)) from None
        return 200, {
            "count": len(pairs),
            "pairs": [[lf, rf] for lf, rf in pairs],
        }, "application/json"

    def _restricted_auths(self, name, params):
        """The caller's auths when visibility enforcement applies, else None.

        Stats/count endpoints normally read pre-computed store-wide sketches;
        when an auth provider is configured AND the schema labels features
        (``geomesa.vis.field``), those sketches would leak restricted rows —
        such requests must recompute over the caller-visible subset."""
        auths = params.get("__auths__")
        if auths is None:
            return None
        try:
            sft = self.store.get_schema(name)
        except KeyError:
            return None  # handler will 404 on its own store call
        if not (sft.user_data or {}).get("geomesa.vis.field"):
            return None
        return auths

    def _count_many(self, name, params, body):
        """POST {"queries": [cql, ...], "loose": bool} → batched counts in
        one device pass (DataStore.count_many)."""
        if not body or "queries" not in body:
            raise _HttpError(400, 'body must be {"queries": [...]}')
        if not hasattr(self.store, "count_many"):
            raise _HttpError(400, "store does not support batched counts")
        auths = self._restricted_auths(name, params)
        queries = body["queries"]
        if auths is not None:
            # visibility-filtered counts can't use the loose batched path
            queries = [Query(filter=c, auths=auths) for c in queries]
        counts = self.store.count_many(
            name, queries, loose=bool(body.get("loose", True))
        )
        return 200, {"counts": counts}, "application/json"

    def _select_many(self, name, params, body):
        """POST {"queries": [cql|null, ...]} → {"results": [{"count": n,
        "arrow_b64": ...}, ...]}: batched row retrieval — the whole
        batch's device work in two dispatches (DataStore.select_many),
        per-query Arrow IPC back. The federation surface of the batched
        read path; caller visibility applies per query through the shared
        reduce pipeline."""
        import base64

        from geomesa_tpu.io.arrow import to_ipc_bytes

        if not body or "queries" not in body:
            raise _HttpError(400, 'body must be {"queries": [...]}')
        sm = getattr(self.store, "select_many", None)
        if sm is None:
            raise _HttpError(400, "store does not support batched selects")
        auths = self._restricted_auths(name, params)
        queries = [Query(filter=c, auths=auths) for c in body["queries"]]
        out = [
            {
                "count": int(r.count),
                "arrow_b64": base64.b64encode(
                    to_ipc_bytes(r.table)).decode(),
            }
            for r in sm(name, queries)
        ]
        return 200, {"results": out}, "application/json"

    def _aggregate(self, name, params, body):
        """POST {"queries": [cql, ...], "group_by": [cols], "value_cols":
        [cols]} → per query: null (that query cannot ride the mesh — the
        caller runs its own fold) or {"groups": [[key, ...], ...], "count":
        [...], "cols": {col: {"count"/"sum"/"min"/"max": [...]}}} with NaN
        extrema as null. The fused grouped segment-reduce over HTTP — the
        federation analog of count-many/density-many."""
        if not body or "queries" not in body:
            raise _HttpError(400, 'body must be {"queries": [...]}')
        agg = getattr(self.store, "aggregate_many", None)
        if agg is None:
            raise _HttpError(400, "store does not support batched aggregation")
        auths = self._restricted_auths(name, params)
        queries = body["queries"]
        if auths is not None:
            # visibility-filtered rows can't ride the batched device fold
            queries = [Query(filter=c, auths=auths) for c in queries]
        now_ms = body.get("now_ms")
        out = agg(
            name, queries,
            group_by=body.get("group_by"),
            value_cols=body.get("value_cols", []),
            now_ms=None if now_ms is None else int(now_ms),
        )

        def _key(v):
            return v.item() if isinstance(v, np.generic) else v

        def _f(v: float):
            return None if np.isnan(v) else float(v)

        results = []
        for r in out:
            if r is None:
                results.append(None)
                continue
            results.append({
                "groups": [[_key(k) for k in key] for key in r["groups"]],
                "count": [int(c) for c in r["count"]],
                "cols": {
                    c: {
                        "count": [int(v) for v in d["count"]],
                        "sum": [float(v) for v in d["sum"]],
                        "min": [_f(v) for v in d["min"]],
                        "max": [_f(v) for v in d["max"]],
                    }
                    for c, d in r["cols"].items()
                },
            })
        return 200, {"results": results}, "application/json"

    def _density_many(self, name, params, body):
        """POST {"queries": [cql, ...], "bbox": [x1,y1,x2,y2], "width", "height",
        "loose"} → one shared-viewport heatmap per query in one device pass
        (DataStore.density_many)."""
        if not body or "queries" not in body or "bbox" not in body:
            raise _HttpError(400, 'body must be {"queries": [...], "bbox": [...]}')
        if not hasattr(self.store, "density_many"):
            raise _HttpError(400, "store does not support batched density")
        bbox = body["bbox"]
        if not (isinstance(bbox, list) and len(bbox) == 4):
            raise _HttpError(400, "bbox must be [xmin, ymin, xmax, ymax]")
        width = int(body.get("width", 256))
        height = int(body.get("height", 256))
        # clamp client-controlled grid dims: a huge grid is a huge
        # allocation AND a forever-cached compiled kernel per distinct shape
        if not (1 <= width <= 4096 and 1 <= height <= 4096):
            raise _HttpError(400, "width/height must be in [1, 4096]")
        auths = self._restricted_auths(name, params)
        queries = body["queries"]
        if auths is not None:
            # visibility-filtered grids can't use the loose batched path
            queries = [Query(filter=c, auths=auths) for c in queries]
        grids = self.store.density_many(
            name, queries, tuple(float(v) for v in bbox),
            width=width, height=height,
            loose=bool(body.get("loose", True)),
        )
        return 200, {
            "width": width,
            "height": height,
            "grids": [g.tolist() for g in grids],
        }, "application/json"

    def _stats(self, name, params, body):
        spec = params.get("stats")
        if not spec:
            raise _HttpError(400, "missing ?stats= spec")
        r = self.store.query(
            name,
            Query(filter=params.get("cql"), hints={"stats": spec},
                  auths=params.get("__auths__")),
        )

        def sketch_dict(s):
            from geomesa_tpu.stats.sketches import Stat

            d = {}
            for k, v in vars(s).items():
                if k.startswith("_") or callable(v):
                    continue
                if isinstance(v, Stat):
                    v = sketch_dict(v)
                elif isinstance(v, dict):
                    v = {
                        str(gk): sketch_dict(gv) if isinstance(gv, Stat) else gv
                        for gk, gv in v.items()
                    }
                d[k] = v
            return _jsonable(d)

        out = {label: sketch_dict(s) for label, s in (r.stats or {}).items()}
        return 200, out, "application/json"

    def _visible_stat(self, name, params, spec: str):
        """One sketch computed over the caller-visible rows only."""
        r = self.store.query(
            name,
            Query(filter=params.get("cql"), hints={"stats": spec},
                  auths=params.get("__auths__")),
        )
        return r.stats[spec]

    def _stats_count(self, name, params, body):
        if self._restricted_auths(name, params) is not None:
            c = self._visible_stat(name, params, "Count()").count
        else:
            exact = params.get("exact", "false").lower() in ("1", "true", "yes")
            c = self.store.stats_count(name, params.get("cql"), exact=exact)
        return 200, {"count": c}, "application/json"

    def _stats_bounds(self, name, params, body):
        attr = params.get("attr")
        if not attr:
            raise _HttpError(400, "missing ?attr=")
        if self._restricted_auths(name, params) is not None:
            mm = self._visible_stat(name, params, f"MinMax({attr})")
            lo, hi = mm.min, mm.max
        else:
            lo, hi = self.store.stats_bounds(name, attr)
        return 200, {"attr": attr, "min": lo, "max": hi}, "application/json"

    def _stats_topk(self, name, params, body):
        attr = params.get("attr")
        if not attr:
            raise _HttpError(400, "missing ?attr=")
        k = int(params.get("k", 10))
        if self._restricted_auths(name, params) is not None:
            top = self._visible_stat(name, params, f"TopK({attr}, {k})").top(k)
        else:
            top = self.store.stats_top_k(name, attr, k)
        return 200, {"attr": attr, "topk": [[v, int(c)] for v, c in top]}, "application/json"

    def _density(self, name, params, body):
        opts = {
            "width": int(params.get("width", 256)),
            "height": int(params.get("height", 256)),
        }
        if params.get("bbox"):
            opts["bbox"] = tuple(float(v) for v in params["bbox"].split(","))
        r = self.store.query(
            name,
            Query(filter=params.get("cql"), hints={"density": opts},
                  auths=params.get("__auths__")),
        )
        return 200, {"width": opts["width"], "height": opts["height"],
                     "grid": r.density}, "application/json"

    def _audit(self, params, body):
        w = getattr(self.store, "audit_writer", None)
        events = []
        if w is not None and hasattr(w, "query_events"):
            events = [json.loads(e.to_json()) for e in w.query_events(params.get("typeName"))]
        return 200, {"events": events}, "application/json"

    def _obs_flight(self, params, body):
        """The query-audit flight recorder (``geomesa-tpu obs flight``
        pulls this): newest records, dump state, recorder config.
        Server-side filters: ``?tenant=``, ``?type=``, ``?anomalies=1``
        (applied before the limit)."""
        from geomesa_tpu.obs import flight

        limit = self._int_param(params, "limit")
        anomalies = params.get("anomalies", "").lower() in ("1", "true",
                                                            "yes")
        return 200, flight.get().snapshot(
            limit=limit or 64,
            tenant=params.get("tenant") or None,
            type_name=params.get("type") or None,
            anomalies_only=anomalies,
        ), "application/json"

    def _obs_tenants(self, params, body):
        """Per-tenant usage accounting (``geomesa-tpu obs tenants`` pulls
        this): rolling-window + lifetime counters per tenant, the
        (tenant, type, plan-signature) heavy-hitter table, and per-tenant
        SLO burn — docs/observability.md § Usage metering & workload
        replay."""
        limit = self._int_param(params, "limit")
        return 200, _usage.get().snapshot(limit=limit), "application/json"

    def _obs_costs(self, params, body):
        """The per-(type, plan-signature) observed-cost table
        (``geomesa-tpu obs costs`` pulls this): p50/p95 device-ms and
        wall-ms, rows, bytes scanned — the adaptive planner's training
        signal — plus the cost model's ``calibration`` report
        (predicted-vs-actual drift per plan shape: mean absolute relative
        error, signed bias, sample counts), so a model that has gone
        stale is visible before it costs latency (docs/planning.md)."""
        from geomesa_tpu.obs import devmon

        limit = self._int_param(params, "limit")
        tbl = devmon.costs()
        out = tbl.snapshot(limit=limit or 256)
        out["calibration"] = tbl.calibration_report()
        # per-member observed-cost aggregates (merged/sharded views):
        # ?member=N filters to one member's rows
        member_costs = getattr(self.store, "member_costs_snapshot", None)
        if member_costs is not None:
            out["members"] = member_costs(
                member=self._int_param(params, "member"))
        return 200, out, "application/json"

    def _obs_audit(self, params, body):
        """The continuous correctness auditor (``geomesa-tpu obs audit``
        pulls this): per-kind checked/passed/diverged/abstained
        counters, queue health, recent divergence reports (with repro-
        bundle paths), and the latest invariant-sweep results —
        docs/observability.md § Continuous correctness auditing."""
        from geomesa_tpu.obs import audit as _obsaudit

        limit = self._int_param(params, "limit")
        return 200, _obsaudit.get().snapshot(limit=limit or 32), \
            "application/json"

    def _obs_lens(self, params, body):
        """The retained profiling plane (``geomesa-tpu obs lens`` pulls
        this): per-(type, plan-signature) time-bucketed latency history,
        live-window quantiles, trace exemplars (each resolvable to a
        stitched span tree), plus the regression sentinel's alarm state —
        docs/observability.md § Query lens & host-roundtrip ledger."""
        from geomesa_tpu.obs import lens as _lensmod
        from geomesa_tpu.obs import plantable
        from geomesa_tpu.obs import trace as _obstrace

        trace_id = params.get("trace")
        if trace_id:
            # exemplar resolution: bucket → trace_id → stitched span tree,
            # straight off the completed-roots ring (404 once it ages out)
            root = _obstrace.find_trace(trace_id)
            if root is None:
                return 404, {"error": f"trace not found: {trace_id!r}"}, \
                    "application/json"
            return 200, _obstrace.span_doc(root), "application/json"

        limit = self._int_param(params, "limit")
        try:
            window_s = float(params.get("window") or 300.0)
        except ValueError:
            return 400, {"error": f"bad window: {params['window']!r}"}, \
                "application/json"
        out = plantable.table().lens_snapshot(
            limit=limit or 50, window_s=window_s,
            type_name=params.get("type") or None)
        out["sentinel"] = _lensmod.sentinel().snapshot()
        return 200, out, "application/json"

    def _obs_stream(self, params, body):
        """The standing-query scale report (``geomesa-tpu obs
        stream-report`` pulls this): per topic, subscriptions ranked by
        scan-cost share with delivery-latency quantiles / stage
        decomposition / on-time-late accounting / chunk-trace exemplars,
        the capacity section (occupancy, churn, predicted next
        bucket-crossing recompile, HBM-per-subscription ×1M), and the
        backlog sentinel's alarm state — docs/streaming.md § Stream lens
        & delivery SLOs. ``?trace=`` resolves a delivery exemplar exactly
        like ``/api/obs/lens?trace=``."""
        from geomesa_tpu.obs import streamlens as _slmod
        from geomesa_tpu.obs import trace as _obstrace

        trace_id = params.get("trace")
        if trace_id:
            root = _obstrace.find_trace(trace_id)
            if root is None:
                return 404, {"error": f"trace not found: {trace_id!r}"}, \
                    "application/json"
            return 200, _obstrace.span_doc(root), "application/json"

        limit = self._int_param(params, "limit")
        try:
            window_s = float(params.get("window") or 300.0)
        except ValueError:
            return 400, {"error": f"bad window: {params['window']!r}"}, \
                "application/json"
        out = _slmod.get().report(
            window_s=window_s, limit=limit or 50,
            topic=params.get("topic") or None)
        out["sentinel"] = _slmod.sentinel().snapshot()
        return 200, out, "application/json"

    def _obs_fusion(self, params, body):
        """The host-roundtrip fusion-opportunity report (``geomesa-tpu
        obs fusion-report`` pulls this): plan signatures ranked by
        host-choreography share — dispatches/syncs per query, inter-stage
        host gaps, transfer bytes. The work list for whole-plan device
        compilation (ROADMAP item 1)."""
        from geomesa_tpu.obs import ledger as _rtledger

        limit = self._int_param(params, "limit")
        return 200, {
            "entries": _rtledger.table().fusion_report(limit=limit or 50),
        }, "application/json"

    def _obs_ledger(self, params, body):
        """The raw roundtrip-ledger rollup in the stable reconcile-export
        schema (``kind`` + ``schema_version`` + per-(type, signature)
        counter entries) — what ``geomesa-tpu obs ledger-export`` writes
        and ``python -m geomesa_tpu.analysis --sync --reconcile`` reads.
        ``?format=json`` is accepted (and is the only format) so callers
        can pin the content negotiation they mean."""
        from geomesa_tpu.obs import ledger as _rtledger

        fmt = params.get("format")
        if fmt not in (None, "json"):
            return 400, {"error": f"unsupported format: {fmt!r}"}, \
                "application/json"
        return 200, _rtledger.table().export(), "application/json"

    def _obs_shards(self, params, body):
        """The sharded federation's routing state (``geomesa-tpu obs
        shards`` pulls this): current generation, members, per-shard
        ownership, LIVE migration records (state / rows shipped+replayed
        / dual-ledger size), coverage violations, and the process-wide
        migration state counters. Stores without a shard router answer
        with just the counters — the caller learns this serves a single
        member, not an error."""
        from geomesa_tpu.serving import elastic as _elastic

        out = {"migration_counters": _elastic.migration_metrics()}
        snap = getattr(self.store, "shards_snapshot", None)
        if snap is not None:
            out.update(snap())
        else:
            out["sharded"] = False
        return 200, out, "application/json"

    def _metrics(self, params, body):
        m = getattr(self.store, "metrics", None)
        # the store's SLO engine (DataStore and MergedDataStoreView both
        # carry one): burn rates / budgets ride the same scrape
        slo_engine = getattr(self.store, "slo", None)
        if params.get("format") == "prometheus":
            # text exposition for a Prometheus scrape: the store registry
            # plus the process-wide jax telemetry registry (compile times,
            # per-step dispatch, recompile counts) when it exists
            from geomesa_tpu.obs import devmon, jaxmon
            from geomesa_tpu.obs.export import (
                PROMETHEUS_CONTENT_TYPE,
                prometheus_text,
            )

            text = prometheus_text(m, jaxmon.GLOBAL)
            if slo_engine is not None:
                text += slo_engine.prometheus_text()
            # device telemetry: labeled HBM residency/budget/spill gauges
            text += devmon.prometheus_text()
            # buffer pool + GeoBlocks query cache: geomesa_cache_{hits,
            # misses,evictions}, geomesa_pool_* and pyramid-bytes gauges
            cache_lines = getattr(self.store, "cache_prometheus_lines", None)
            if cache_lines is not None:
                text += "\n".join(cache_lines()) + "\n"
            # streaming tier: per-topic lag / poll-rate / scanner pipeline
            # gauges (geomesa_stream_lag{topic} is the backpressure signal)
            from geomesa_tpu.stream import telemetry as stream_telemetry

            text += stream_telemetry.prometheus_text()
            # tenant usage: geomesa_tenant_* counters with BOUNDED label
            # cardinality (top-K tenants + an "other" rollup) plus the
            # per-tenant SLO burn gauges
            text += _usage.get().prometheus_text()
            # admission control: geomesa_admission_* admitted/shed
            # series (per-priority + bounded per-tenant shed counters)
            if self.admission is not None:
                text += self.admission.prometheus_text()
            # correctness auditor: geomesa_audit_* per-kind checked/
            # passed/diverged/abstained counters
            from geomesa_tpu.obs import audit as _obsaudit

            text += _obsaudit.get().prometheus_text()
            # durability plane: geomesa_wal_* append/flush/trim counters +
            # geomesa_recovery_* replay counters (store/wal.py)
            from geomesa_tpu.store import wal as _walmod

            text += _walmod.prometheus_text()
            # query lens: TRUE histogram families (geomesa_lens_latency_ms
            # _bucket/_sum/_count with le labels) per (type, signature),
            # plus the regression sentinel's gauge + counter
            from geomesa_tpu.obs import lens as _lensmod
            from geomesa_tpu.obs import plantable

            text += plantable.table().prometheus_text()
            text += _lensmod.sentinel().prometheus_text()
            # stream lens: geomesa_stream_delivery_* histogram families
            # per (topic, subscription) — top-K-by-cost + `other` rollup —
            # plus the stream.delivery SLO gauges and the backlog sentinel
            from geomesa_tpu.obs import streamlens as _slmod

            text += _slmod.get().prometheus_text()
            text += _slmod.sentinel().prometheus_text()
            # elastic plane: geomesa_shard_migrations_total{state},
            # geomesa_tier_bytes{tier,type}, geomesa_autoscaler_* totals
            from geomesa_tpu.serving import elastic as _elastic

            text += _elastic.prometheus_text()
            return 200, text.encode(), PROMETHEUS_CONTENT_TYPE
        out = m.snapshot() if m is not None else {}
        # device section: per-(type, index, group) resident bytes, budget
        # headroom, spill report, process transfer totals (obs.devmon)
        from geomesa_tpu.obs import devmon

        out["device"] = devmon.device_report()
        # buffer-pool / query-cache / pyramid gauge block
        cache_report = getattr(self.store, "cache_report", None)
        if cache_report is not None:
            out["cache"] = cache_report()
        if slo_engine is not None:
            slo_snap = slo_engine.snapshot()
            if slo_snap:
                out["slo"] = slo_snap
        # federated stores surface their per-member health scoreboard
        # (rolling success rate, p95, breaker state) alongside the metrics
        health = getattr(self.store, "member_health", None)
        if health is not None:
            out["federation_members"] = health()
        # streaming tier: per-topic lag/poll/scan gauges (empty dict when
        # no stream threads have reported)
        from geomesa_tpu.stream import telemetry as stream_telemetry

        stream_report = stream_telemetry.report()
        if stream_report:
            out["stream"] = stream_report
        # tenant usage accounting (full detail at GET /api/obs/tenants)
        meter = _usage.get()
        if meter.observe_count:
            out["tenants"] = meter.snapshot(limit=16)
        # correctness auditor (full detail at GET /api/obs/audit)
        from geomesa_tpu.obs import audit as _obsaudit

        aud = _obsaudit.get()
        if aud.checked or _obsaudit.ENABLED:
            out["audit"] = aud.snapshot(limit=8)
        # durability plane: WAL append/ack/trim + recovery replay counters
        # (only once a WAL has written — plain stores skip the section)
        from geomesa_tpu.store import wal as _walmod

        wal_m = _walmod.wal_metrics()
        if any(wal_m.values()):
            out["wal"] = wal_m
        # query lens summary (full detail at GET /api/obs/lens): only once
        # something has been observed — plain scrapes skip the section
        from geomesa_tpu.obs import lens as _lensmod
        from geomesa_tpu.obs import plantable

        plans = plantable.table()
        if plans.observe_count:
            out["lens"] = plans.lens_snapshot(limit=8)
            out["lens"]["sentinel"] = _lensmod.sentinel().snapshot()
        # stream lens summary (full detail at GET /api/obs/stream)
        from geomesa_tpu.obs import streamlens as _slmod

        stream_lens = _slmod.get()
        if stream_lens.observe_count:
            out["stream_lens"] = stream_lens.report(limit=8)
            out["stream_lens"]["sentinel"] = _slmod.sentinel().snapshot()
        # serving plane: admission decisions + coalesce effectiveness
        if self.admission is not None:
            out["admission"] = self.admission.snapshot(limit=16)
        if self.coalescer is not None and self.coalescer.dispatch_count:
            c = self.coalescer
            out["coalesce"] = {
                "window_ms": c.window_s * 1000.0,
                "dispatches": c.dispatch_count,
                "queries": c.query_count,
                "max_width": c.max_width,
            }
        return 200, out, "application/json"

    def _ogc(self, handler, error_cls, params):
        """Shared OGC KVP dispatch: route to the protocol handler, render
        its error class as the protocol's XML exception report, and apply
        visibility auths exactly as on the native query endpoints."""
        try:
            status, body_out, ctype = handler(
                self.store, params, auths=params.get("__auths__")
            )
        except error_cls as e:
            return 400, e.to_xml().encode(), "text/xml"
        if isinstance(body_out, str):
            body_out = body_out.encode()
        return status, body_out, ctype

    def _wfs(self, params, body):
        """OGC WFS 2.0 KVP binding (GetCapabilities / DescribeFeatureType /
        GetFeature)."""
        from geomesa_tpu.web.wfs import WfsError, handle_wfs

        return self._ogc(handle_wfs, WfsError, params)

    def _wms(self, params, body):
        """OGC WMS 1.3.0 KVP binding (GetCapabilities / GetMap): density
        heatmap or point tiles over the fused device density path."""
        from geomesa_tpu.web.wms import WmsError, handle_wms

        return self._ogc(handle_wms, WmsError, params)


def serve(store, host: str = "127.0.0.1", port: int = 8080, threads: bool = True,
          auth_provider=None, journal=None, schema_registry=None,
          admission=None, coalesce_ms: float | None = None):
    """Run the API on wsgiref's simple server (dev/ops tool, not a prod WSGI
    container — same posture as the reference's embedded servlets).

    ``threads=True`` (default) handles requests concurrently — the store's
    per-type snapshot/mutator locking makes parallel queries + background
    compactions safe; pass False for single-threaded debugging.
    ``auth_provider``: see :class:`geomesa_tpu.security.auth.AuthorizationsProvider`.
    ``journal``/``schema_registry``: attach the cross-host stream transport
    (``/api/journal``) and Confluent-protocol registry (``/subjects``) —
    see GeoMesaApp; the lease endpoint (``/api/lease``) is always on.
    """
    import socketserver
    from wsgiref.simple_server import WSGIServer, make_server

    cls = WSGIServer
    if threads:

        class _ThreadingWSGIServer(socketserver.ThreadingMixIn, WSGIServer):
            daemon_threads = True

        cls = _ThreadingWSGIServer
    httpd = make_server(
        host, port,
        GeoMesaApp(store, auth_provider=auth_provider, journal=journal,
                   schema_registry=schema_registry, admission=admission,
                   coalesce_ms=coalesce_ms),
        server_class=cls,
    )
    print(f"geomesa-tpu REST on http://{host}:{port}/api")
    httpd.serve_forever()
