"""Federated read-only view over multiple stores.

Role parity: ``geomesa-index-api/.../index/view/MergedDataStoreView.scala:31``
+ ``MergedQueryRunner.scala`` (SURVEY.md §2.3): N underlying stores (each
optionally scoped by a per-store filter) presented as one read-only store;
queries fan out, per-store results merge, sort/limit/aggregations apply at the
view level. Mergeable aggregates merge exactly (density grids sum, stat
sketches are monoids — the reference's reducer pattern, P6/P10 in §2.20).
"""

from __future__ import annotations

import time
from dataclasses import replace

import numpy as np

from geomesa_tpu import obs
from geomesa_tpu.filter import ast
from geomesa_tpu.obs import flight as _flight
from geomesa_tpu.planning.planner import Query
from geomesa_tpu.resilience import MEMBER_FAILURE_TYPES, CircuitOpenError
from geomesa_tpu.schema.columnar import FeatureTable
from geomesa_tpu.schema.sft import FeatureType
from geomesa_tpu.store.datastore import QueryResult
from geomesa_tpu.utils.timeouts import QueryTimeout

__all__ = ["MergedDataStoreView", "intersection_schema", "intersection_schemas"]


def intersection_schema(stores, name: str) -> FeatureType:
    """The shared multi-store schema contract (the reference's
    ``MergedDataStoreSchemas`` trait): a type must exist on every member
    with the same attribute layout. Used by the merged AND routed views —
    schema-compat rules must not drift between them."""
    sft = stores[0].get_schema(name)
    for s in stores[1:]:
        other = s.get_schema(name)
        if [a.name for a in other.attributes] != [
            a.name for a in sft.attributes
        ]:
            raise ValueError(f"schema mismatch across stores for {name!r}")
    return sft


def intersection_schemas(stores) -> list[str]:
    names = set(stores[0].list_schemas())
    for s in stores[1:]:
        names &= set(s.list_schemas())
    return sorted(names)


class MergedDataStoreView:
    """Read-only fan-out over ``[(store, scope_filter_or_None), ...]``.

    ``on_member_error`` (docs/resilience.md) picks the federation's
    failure posture:

    - ``"fail"`` (default, the historical behavior): any member error
      fails the whole query — strict, every answer is complete.
    - ``"partial"``: a member failing with a MEMBER failure (transport
      error, 5xx after retries, open circuit breaker, blown deadline,
      corrupt payload — :data:`geomesa_tpu.resilience.MEMBER_FAILURE_TYPES`)
      is skipped; the merged result carries the surviving members' rows,
      marked ``degraded=True`` with per-member error details, the way
      query-cache systems serve cached partials under failure (GeoBlocks,
      arXiv:1908.07753). Semantic errors (missing schema, bad filter —
      KeyError/ValueError/PermissionError) still fail: they are the
      caller's bug on every member alike. All members failing fails the
      query in either mode.

    Degradations are observable: ``metrics`` counters
    (``federation.member_errors[.i]``, ``federation.degraded_queries``)
    and an :func:`obs.event` span marker per skipped member.
    """

    def __init__(self, stores, on_member_error: str = "fail", metrics=None,
                 slo=None, slo_target: float = 0.999):
        if not stores:
            raise ValueError("merged view needs at least one store")
        if on_member_error not in ("fail", "partial"):
            raise ValueError(
                f"on_member_error must be 'fail' or 'partial', "
                f"got {on_member_error!r}")
        from geomesa_tpu.filter.cql import parse

        self.on_member_error = on_member_error
        if metrics is None:
            from geomesa_tpu.utils.metrics import MetricsRegistry

            metrics = MetricsRegistry()
        self.metrics = metrics
        # SLO engine (docs/observability.md § SLOs): every member fan-out
        # leg is one availability observation against
        # ``federation.member`` keyed by member index — the burn-rate /
        # error-budget surface behind GET /api/metrics?format=prometheus
        # and the member_health() scoreboard
        if slo is None:
            from geomesa_tpu.obs.slo import SloEngine

            slo = SloEngine()
        self.slo = slo
        self.slo.objective("federation.member", target=slo_target)
        # scope filters parsed once here, not per query
        self.stores = []
        for s in stores:
            store, scope = s if isinstance(s, tuple) else (s, None)
            if scope is not None and not isinstance(scope, ast.Filter):
                scope = parse(scope)
            self.stores.append((store, scope))
        # per-member observed-cost aggregates (ROADMAP item-3 leftover:
        # per-shard cost asymmetry): every fan-out leg records its wall
        # ms under (member, type, op) — served as the `members` section
        # of GET /api/obs/costs (?member= filter), the member column of
        # `geomesa-tpu obs costs`, and the explain scoreboard, so one
        # slow shard is visible as a COST asymmetry, not just an SLO one
        import threading

        self._member_cost_lock = threading.Lock()  # leaf: the table only
        self._member_costs: dict = {}

    def _member_run(self, i: int, fn, errors: list, outcomes: list | None = None,
                    cost: tuple | None = None):
        """One member's fan-out leg: ``(ok, result)``. In ``partial``
        mode a member failure is recorded (metrics + SLO + span event +
        the errors list) and skipped; in ``fail`` mode it propagates.
        ``outcomes`` (when passed) collects the flight-recorder member
        summary: ``(i, "ok" | "error:<Type>", ms)``. ``cost`` —
        ``(type_name, op)`` — records the leg's wall into the per-member
        observed-cost table (successful legs only: a fail-fast breaker
        leg's near-zero wall is not the member's cost of doing the work)."""
        t0 = time.perf_counter()
        try:
            out = fn()
        except MEMBER_FAILURE_TYPES as e:
            ms = (time.perf_counter() - t0) * 1000.0
            self.slo.observe("federation.member", ok=False,
                             latency_ms=ms, key=str(i))
            if outcomes is not None:
                outcomes.append((i, f"error:{type(e).__name__}", ms))
            if self.on_member_error != "partial":
                raise
            errors.append((i, e))
            self.metrics.counter("federation.member_errors").inc()
            self.metrics.counter(f"federation.member_errors.{i}").inc()
            obs.event("member_error", member=i, error=type(e).__name__)
            return False, None
        ms = (time.perf_counter() - t0) * 1000.0
        self.slo.observe("federation.member", ok=True,
                         latency_ms=ms, key=str(i))
        if cost is not None:
            self._note_member_cost(i, cost[0], cost[1], ms)
        if outcomes is not None:
            outcomes.append((i, "ok", ms))
        return True, out

    def _note_member_cost(self, i: int, type_name: str, op: str,
                          ms: float) -> None:
        from geomesa_tpu.obs.plantable import _Quantiles

        key = (i, type_name, op)
        with self._member_cost_lock:
            ent = self._member_costs.get(key)
            if ent is None:
                ent = self._member_costs[key] = [0, _Quantiles()]
                # bounded: (members × types × ops) is small by
                # construction, but a type-churning workload must not
                # grow it forever
                while len(self._member_costs) > 512:
                    self._member_costs.pop(next(iter(self._member_costs)))
            ent[0] += 1
            ent[1].update(ms)

    def member_costs_snapshot(self, member: int | None = None) -> list:
        """Per-(member, type, op) observed wall-ms aggregates — the
        `members` section of ``GET /api/obs/costs`` (``?member=``
        filters), rendered by ``geomesa-tpu obs costs`` and the merged
        ``explain`` scoreboard."""
        with self._member_cost_lock:
            items = list(self._member_costs.items())
        out = []
        for (i, type_name, op), (n, qs) in items:
            if member is not None and i != member:
                continue
            out.append({
                "member": i,
                "store": getattr(self.stores[i][0], "base_url",
                                 type(self.stores[i][0]).__name__)
                if i < len(self.stores) else "?",
                "type": type_name,
                "op": op,
                "count": n,
                "wall_ms_p50": round(qs.quantile(0.5), 3),
                "wall_ms_p95": round(qs.quantile(0.95), 3),
            })
        out.sort(key=lambda r: (r["member"], r["type"], r["op"]))
        return out

    @staticmethod
    def _anomalies(errors: list) -> tuple:
        """Flight-recorder anomaly classification of a fan-out's member
        failures (degraded/slow are detected by the recorder itself)."""
        out: list[str] = []
        for _, e in errors:
            kind = None
            if isinstance(e, CircuitOpenError):
                kind = _flight.A_BREAKER
            elif isinstance(e, QueryTimeout):
                kind = _flight.A_DEADLINE
            if kind is not None and kind not in out:
                out.append(kind)
        return tuple(out)

    def member_health(self) -> list:
        """The per-member health scoreboard (docs/observability.md):
        rolling 5-minute success rate, latency quantiles from the SLO
        tracker's reservoir, breaker state where the member exposes one,
        and the cumulative error count — what ``/api/metrics`` and
        ``explain`` surface for operators."""
        out = []
        for i, (store, _) in enumerate(self.stores):
            tk = self.slo.tracker("federation.member", key=str(i))
            win = min(tk.objective.windows)
            p50, p95, p99 = tk.latency_quantiles()
            breaker = getattr(store, "breaker", None)
            errs = self.metrics.counters.get(f"federation.member_errors.{i}")
            out.append({
                "member": i,
                "store": getattr(store, "base_url", type(store).__name__),
                "success_rate": 1.0 - tk.burn_rate(win) * (
                    1.0 - tk.objective.target),
                "budget_remaining": tk.budget_remaining(win),
                "window": int(win),
                "p50_ms": p50,
                "p95_ms": p95,
                "p99_ms": p99,
                "breaker": breaker.state if breaker is not None else None,
                "errors": errs.count if errs is not None else 0,
            })
        return out

    def explain(self, type_name: str, q=None) -> str:
        """Federated EXPLAIN: each member's own plan explain (where the
        member supports it) plus the health scoreboard, so a degraded
        member is visible right where the operator is reading plans."""
        if isinstance(q, (str, ast.Filter)) or q is None:
            q = Query(filter=q)
        lines = [f"Federated plan over {len(self.stores)} members "
                 f"(on_member_error={self.on_member_error}):"]
        base_f = q.resolved_filter()
        for i, (store, scope) in enumerate(self.stores):
            f = base_f if scope is None else ast.And((base_f, scope))
            sub = replace(q, filter=f, sort_by=None, limit=None,
                          start_index=None)
            ex = getattr(store, "explain", None)
            lines.append(f"-- member {i}: "
                         f"{getattr(store, 'base_url', type(store).__name__)}")
            if ex is None:
                lines.append("   (no explain surface)")
                continue
            try:
                lines.append("   " + str(ex(type_name, sub)).replace(
                    "\n", "\n   "))
            except MEMBER_FAILURE_TYPES as e:
                lines.append(f"   (unavailable: {type(e).__name__}: {e})")
        lines.append("Member health:")
        for h in self.member_health():
            lines.append(
                f"  member {h['member']} [{h['store']}]: "
                f"success={h['success_rate']:.3f} "
                f"budget={h['budget_remaining']:.2f} "
                f"p95={h['p95_ms']:.1f}ms "
                f"breaker={h['breaker'] or '-'} errors={h['errors']}")
        costs = self.member_costs_snapshot()
        rows = [c for c in costs if c["type"] == type_name]
        if rows:
            lines.append("Member cost asymmetry (observed wall ms):")
            for c in rows:
                lines.append(
                    f"  member {c['member']} {c['op']:<12s} "
                    f"n={c['count']:<5d} p50={c['wall_ms_p50']:.2f} "
                    f"p95={c['wall_ms_p95']:.2f}")
        return "\n".join(lines)

    @staticmethod
    def _error_details(errors: list) -> list:
        return [(i, type(e).__name__, str(e)) for i, e in errors]

    def _member_subset(self, type_name: str, f) -> list | None:
        """Member indices a query with this filter must fan out to:
        ``None`` = all (the merged view's default), ``[]`` = none (a
        provably disjoint filter). The sharded federation
        (:class:`geomesa_tpu.serving.shards.ShardedDataStoreView`)
        overrides this to narrow fan-out to the members whose Z-prefix
        shards the plan's ranges intersect — member indices stay the
        DECLARED positions, so SLO keys, metrics counters and the
        health scoreboard attribute stably across differing subsets."""
        return None

    def _member_subset_rows(self, type_name: str, f) -> list | None:
        """Row-read variant of :meth:`_member_subset`: the sharded view
        widens this to the UNION of old and new owners during a live
        shard migration's dual-apply window (row results dedup by fid
        at the merge), while additive reads — counts, stats sketches,
        density grids, grouped aggregations, everything that SUMS
        across members — keep the authoritative subset (a union fan
        would double-count every dual-applied row). The merged default:
        the two fans are identical."""
        return self._member_subset(type_name, f)

    def _fan_targets(self, type_name: str, f, rows: bool = False) -> list:
        """``[(member_index, (store, scope)), ...]`` for one fan-out.
        ``rows=True`` marks a row-returning read (union fan allowed)."""
        subset = (self._member_subset_rows(type_name, f) if rows
                  else self._member_subset(type_name, f))
        if subset is None:
            return list(enumerate(self.stores))
        return [(i, self.stores[i]) for i in subset]

    def _merge_member_tables(self, tables: list) -> FeatureTable:
        """Merge seam for per-member row results: the sharded view
        overrides this to dedup dual-applied rows by fid while a live
        shard migration union-fans reads."""
        return FeatureTable.concat(tables) if len(tables) > 1 else tables[0]

    def _note_degraded(self, errors: list, op: str) -> None:
        self.metrics.counter("federation.degraded_queries").inc()
        obs.event("degraded", op=op, members_failed=len(errors))

    def get_schema(self, name: str) -> FeatureType:
        stores = [s for s, _ in self.stores]
        if self.on_member_error != "partial":
            return intersection_schema(stores, name)
        # partial mode: the schema contract holds over the ANSWERING
        # members — a dead member must not take down the view's whole
        # schema surface (its data absence is recorded per query by the
        # fan-outs). Layout mismatches are semantic and still raise.
        sft = None
        last: Exception | None = None
        for s in stores:
            try:
                other = s.get_schema(name)
            except MEMBER_FAILURE_TYPES as e:
                last = e
                continue
            if sft is None:
                sft = other
            elif [a.name for a in other.attributes] != [
                a.name for a in sft.attributes
            ]:
                raise ValueError(f"schema mismatch across stores for {name!r}")
        if sft is None:
            raise last if last is not None else KeyError(name)
        return sft

    def list_schemas(self) -> list[str]:
        stores = [s for s, _ in self.stores]
        if self.on_member_error != "partial":
            return intersection_schemas(stores)
        names: set | None = None
        last: Exception | None = None
        for s in stores:
            try:
                ns = set(s.list_schemas())
            except MEMBER_FAILURE_TYPES as e:
                last = e
                continue
            names = ns if names is None else names & ns
        if names is None:
            raise last if last is not None else ValueError("no members")
        return sorted(names)

    def query(self, type_name: str, q: "Query | str | ast.Filter | None" = None, **kwargs) -> QueryResult:
        if isinstance(q, (str, ast.Filter)) or q is None:
            q = Query(filter=q, **kwargs)
        t_start = time.perf_counter()
        outcomes: list = []
        # one federation span per query: member RPC spans (and their
        # grafted remote subtrees) nest under it, member-error/degraded
        # events attach to it — the stitched tree's local frame
        with obs.span("federation.query", type=type_name,
                      members=len(self.stores)):
            filt = q.filter if isinstance(q.filter, str) else str(
                q.filter or "INCLUDE")
            # federation-level tenant attribution: the frontend's request
            # context (member stores attribute their own legs via the
            # propagated X-Geomesa-Tenant header — resilience/http.py)
            from geomesa_tpu.obs import usage as _usage
            from geomesa_tpu.obs import workload as _workload

            tenant = q.hints.get("tenant") or _usage.current_tenant()
            try:
                res, errors = self._query_fanout(type_name, q, outcomes)
            except MEMBER_FAILURE_TYPES as e:
                # whole-query failure (all members down, or fail mode):
                # the always-on record must not miss the worst outcomes
                ms = (time.perf_counter() - t_start) * 1000.0
                _flight.record(
                    op="query", type_name=type_name, source="federation",
                    plan=filt, latency_ms=ms,
                    rows=0, degraded=True, members=outcomes,
                    anomalies=self._anomalies([(None, e)]),
                    tenant=tenant or "", auths=q.auths,
                )
                _usage.observe(tenant, type_name, "federation", rows=0,
                               wall_ms=ms, ok=False)
                raise
            # always-on audit record; anomalies (degraded result, open
            # breaker, blown member deadline) trigger the flight dump
            ms = (time.perf_counter() - t_start) * 1000.0
            _flight.record(
                op="query", type_name=type_name, source="federation",
                plan=filt, latency_ms=ms,
                rows=res.count, degraded=res.degraded, members=outcomes,
                anomalies=self._anomalies(errors),
                tenant=tenant or "", auths=q.auths,
            )
            # view-level metering under the "federation" pseudo-signature:
            # in-process member stores meter their own legs per plan shape,
            # so the device-ms attribution stays with the store tier
            _usage.observe(tenant, type_name, "federation", rows=res.count,
                           wall_ms=ms, ok=not res.degraded)
            if _workload.ENABLED:
                _workload.record(
                    ts=time.time(), op="query", type_name=type_name,
                    source="federation", filter_text=filt, hints=q.hints,
                    tenant=tenant or "", auths=q.auths,
                    plan_signature="federation", predicted_ms=None,
                    latency_ms=ms, rows=res.count, degraded=res.degraded,
                )
        return res

    def _query_fanout(self, type_name: str, q: Query, outcomes: list):
        sft = self.get_schema(type_name)

        # sub-queries: scope filter ANDed in; view-level reduce steps stripped
        # (sort/limit re-applied on the merged stream, reference
        # MergedQueryRunner behavior)
        tables: list[FeatureTable] = []
        density = None
        stats = None
        bin_parts: list[bytes] = []
        errors: list = []
        base_f = q.resolved_filter()
        row_read = not any(k in q.hints for k in ("density", "stats", "bin"))
        targets = self._fan_targets(type_name, base_f, rows=row_read)
        if not targets:
            # provably disjoint under the shard map: no member can hold
            # a matching row. Aggregation-hinted queries (density /
            # stats / bin) still fan to ONE member so the zero answer
            # keeps its channel shape (a zero grid, empty sketches) —
            # a disjoint filter matches nothing on ANY member, so one
            # member's answer IS the global answer. Plain row queries
            # answer empty without any fan-out.
            if any(k in q.hints for k in ("density", "stats", "bin")):
                targets = [(0, self.stores[0])]
            else:
                empty = FeatureTable.from_records(sft, [])
                return QueryResult(empty, np.empty(0, dtype=np.int64)), []
        for i, (store, scope) in targets:
            f = base_f if scope is None else ast.And((base_f, scope))
            sub = replace(q, filter=f, sort_by=None, limit=None, start_index=None)
            ok, res = self._member_run(
                i, lambda s=store, t=sub: s.query(type_name, t), errors,
                outcomes, cost=(type_name, "query"))
            if not ok:
                continue
            if res.density is not None:
                density = res.density if density is None else density + res.density
            if res.stats is not None:
                if stats is None:
                    stats = dict(res.stats)
                else:
                    stats = {k: stats[k].merge(v) for k, v in res.stats.items()}
            if res.bin_data is not None:
                bin_parts.append(res.bin_data)
            if res.density is None and res.stats is None and res.bin_data is None:
                tables.append(res.table)

        if errors and len(errors) == len(targets):
            # zero ATTEMPTED members answered: no partial to serve
            raise errors[-1][1]
        degraded = bool(errors)
        if degraded:
            self._note_degraded(errors, "query")

        if density is not None or stats is not None or bin_parts:
            bin_data = None
            if bin_parts:
                bin_opts = q.hints.get("bin") or {}
                if bin_opts.get("sort"):
                    # per-store chunks are each time-sorted; a plain join
                    # would interleave — merge-sort them (BinSorter role)
                    from geomesa_tpu.utils.bin_format import merge_sorted

                    bin_data = merge_sorted(
                        bin_parts, labeled=bool(bin_opts.get("label"))
                    )
                else:
                    bin_data = b"".join(bin_parts)
            empty = FeatureTable.from_records(sft, [])
            return QueryResult(
                empty,
                np.empty(0, dtype=np.int64),
                density=density,
                stats=stats,
                bin_data=bin_data,
                degraded=degraded,
                member_errors=self._error_details(errors) if errors else None,
            ), errors

        table = self._merge_member_tables(tables)
        rows = np.arange(len(table), dtype=np.int64)
        from geomesa_tpu.store.reduce import sort_limit

        table, rows = sort_limit(table, rows, q.sort_by, q.limit, q.start_index)
        return QueryResult(
            table, rows, degraded=degraded,
            member_errors=self._error_details(errors) if errors else None,
        ), errors

    def stats_count(self, type_name: str, cql=None, exact: bool = False):
        """Count across stores, honoring each store's scope filter. In
        ``partial`` mode a failed member contributes zero (undercount —
        recorded via metrics/span event; the return type stays a bare
        number)."""
        from geomesa_tpu.filter.cql import parse

        f = parse(cql) if isinstance(cql, str) else cql
        total = 0
        errors: list = []
        targets = self._fan_targets(type_name, f)
        for i, (s, scope) in targets:
            sub = f if scope is None else (scope if f is None else ast.And((f, scope)))
            ok, n = self._member_run(
                i, lambda s=s, t=sub: s.stats_count(type_name, t, exact),
                errors, cost=(type_name, "stats_count"))
            if ok:
                total += n
        if errors:
            if len(errors) == len(targets):
                raise errors[-1][1]
            self._note_degraded(errors, "stats_count")
        return total

    def aggregate_many(self, type_name: str, queries, group_by=None,
                       value_cols=(), now_ms: int | None = None):
        """Federated grouped aggregation: push the fold to every member
        (each runs its own fused mesh pass — or its owner's, over HTTP via
        RemoteDataStore) and merge the per-group partials at the view level:
        counts/sums add, extrema min/max, group order is first occurrence
        across members in member order (the same order the view's merged
        host fold would produce). A query any member declines is declined
        (None) for the whole view — the caller's host fold keeps exact
        semantics rather than mixing engines per slice."""
        qs = [
            Query(filter=q) if isinstance(q, (str, ast.Filter)) or q is None
            else q
            for q in queries
        ]
        # capability check BEFORE any fan-out: one member without the fold
        # declines the whole view, and earlier members must not burn device
        # passes / remote round-trips whose results would be discarded
        if any(
            getattr(store, "aggregate_many", None) is None
            for store, _ in self.stores
        ):
            return [None] * len(qs)
        # fan only to the members ANY query of the batch can touch (the
        # sharded view's subset hook; None = all, the merged default)
        subset_u: set | None = set()
        for q in qs:
            s = self._member_subset(type_name, q.resolved_filter())
            if s is None:
                subset_u = None
                break
            subset_u.update(s)
        targets = (list(enumerate(self.stores)) if subset_u is None
                   else [(i, self.stores[i]) for i in sorted(subset_u)])
        per_member = []
        errors: list = []
        for i, (store, scope) in targets:
            agg = store.aggregate_many
            subs = []
            for q in qs:
                f = q.resolved_filter()
                if scope is not None:
                    f = ast.And((f, scope))
                subs.append(replace(q, filter=f))
            ok, partials = self._member_run(
                i, lambda a=agg, s=subs: a(type_name, s, group_by=group_by,
                                           value_cols=value_cols,
                                           now_ms=now_ms),
                errors, cost=(type_name, "aggregate"))
            if ok:
                per_member.append(partials)
        if errors:
            if not per_member:
                raise errors[-1][1]
            # partial federation fold: surviving members' partials merge;
            # each result dict below carries the degraded marker
            self._note_degraded(errors, "aggregate_many")
        degraded = bool(errors)
        out: list = []
        vcols = list(value_cols)
        for qi in range(len(qs)):
            parts = [m[qi] for m in per_member]
            if any(p is None for p in parts):
                out.append(None)
                continue
            keys: list = []
            pos: dict = {}
            cnt: list[int] = []
            acc = {c: {"count": [], "sum": [], "min": [], "max": []}
                   for c in vcols}
            for p in parts:
                for gi, key in enumerate(p["groups"]):
                    g = pos.get(key)
                    if g is None:
                        g = pos[key] = len(keys)
                        keys.append(key)
                        cnt.append(0)
                        for c in vcols:
                            acc[c]["count"].append(0)
                            acc[c]["sum"].append(0.0)
                            acc[c]["min"].append(np.nan)
                            acc[c]["max"].append(np.nan)
                    cnt[g] += int(p["count"][gi])
                    for c in vcols:
                        d = p["cols"][c]
                        acc[c]["count"][g] += int(d["count"][gi])
                        acc[c]["sum"][g] += float(d["sum"][gi])
                        for k, fold in (("min", min), ("max", max)):
                            v = float(d[k][gi])
                            if np.isnan(v):
                                continue
                            cur = acc[c][k][g]
                            acc[c][k][g] = v if np.isnan(cur) else fold(cur, v)
            # no-GROUP-BY single groups merge into one row; grouped results
            # keep only non-empty groups (every member already filters, but
            # scope-disjoint members contribute zero-count groups never)
            rec = {
                "groups": keys,
                "count": np.asarray(cnt, dtype=np.int64),
                "cols": {
                    c: {k: np.asarray(v, dtype=np.float64)
                        if k != "count"
                        else np.asarray(v, dtype=np.int64)
                        for k, v in acc[c].items()}
                    for c in vcols
                },
            }
            if degraded:
                rec["degraded"] = True
                rec["member_errors"] = self._error_details(errors)
            out.append(rec)
        return out
