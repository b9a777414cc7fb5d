"""Serving-plane tests (ISSUE 12): per-tenant admission control,
request coalescing into batched device dispatch, and consistent-hash
sharded federation. See docs/serving.md."""

import io
import json
import threading
import urllib.error

import numpy as np
import pytest

from geomesa_tpu.geometry.types import Point
from geomesa_tpu.obs import usage
from geomesa_tpu.planning.planner import Query
from geomesa_tpu.serving.admission import AdmissionController
from geomesa_tpu.serving.coalesce import Coalescer
from geomesa_tpu.serving.shards import ShardedDataStoreView, ShardRouter
from geomesa_tpu.store.datastore import DataStore
from geomesa_tpu.utils.metrics import MetricsRegistry
from geomesa_tpu.web import GeoMesaApp

T0 = 1_500_000_000_000
SPEC = "name:String,dtg:Date,*geom:Point"


def call(app, method, path, query="", body=None, headers=None):
    raw = json.dumps(body).encode() if body is not None else b""
    environ = {
        "REQUEST_METHOD": method,
        "PATH_INFO": path,
        "QUERY_STRING": query,
        "CONTENT_LENGTH": str(len(raw)),
        "wsgi.input": io.BytesIO(raw),
        **(headers or {}),
    }
    out = {}

    def start_response(status, headers_):
        out["status"] = int(status.split()[0])
        out["headers"] = dict(headers_)

    chunks = app(environ, start_response)
    return out["status"], out["headers"], b"".join(chunks)


def make_store(n=200, seed=5, compacted=True):
    ds = DataStore(backend="tpu")
    ds.create_schema("pts", SPEC)
    rng = np.random.default_rng(seed)
    ds.write("pts", [
        {"name": f"n{i % 3}", "dtg": T0 + i * 1000,
         "geom": Point(float(rng.uniform(-170, 170)),
                       float(rng.uniform(-60, 60)))}
        for i in range(n)
    ], fids=[f"f{i}" for i in range(n)])
    if compacted:
        ds.compact("pts")
    return ds


@pytest.fixture()
def meter():
    """A fresh process usage meter (restored afterwards) so admission /
    metering assertions see only this test's traffic."""
    m = usage.UsageMeter(k=8)
    prev = usage.install(m)
    yield m
    usage.install(prev)


# -- admission control --------------------------------------------------------

class TestAdmission:
    def _controller(self, meter, **kw):
        kw.setdefault("rate_qps", 10.0)
        kw.setdefault("burst", 4.0)
        kw.setdefault("min_rate_qps", 0.5)
        kw.setdefault("meter", meter)
        kw.setdefault("metrics", MetricsRegistry())
        return AdmissionController(**kw)

    def test_token_bucket_refill_deterministic(self, meter):
        """Clock-free deterministic time injection: refill is exactly
        rate * dt, Retry-After is the time to re-cross the reserve."""
        t = [0.0]
        ac = self._controller(meter, clock=lambda: t[0])
        # burst 4, high reserve 0: 4 admits drain to zero, the 5th sheds
        admits = [ac.admit("a", "high").admitted for _ in range(5)]
        assert admits == [True, True, True, True, False]
        d = ac.admit("a", "high")
        assert not d.admitted and d.reason == "rate"
        # need 1 token at 10/s => 0.1 s
        assert d.retry_after_s == pytest.approx(0.1, rel=1e-6)
        t[0] += 0.05  # half a token back: still shed
        assert not ac.admit("a", "high").admitted
        t[0] += 0.1  # now > 1 token
        assert ac.admit("a", "high").admitted

    def test_priority_shed_order_no_inversion(self, meter):
        """Low sheds first; a high-priority request is NEVER shed while
        low-priority traffic is still being admitted."""
        t = [0.0]
        ac = self._controller(meter, rate_qps=10.0, burst=10.0,
                              clock=lambda: t[0])
        order = []
        # alternate low/high until both classes shed
        for i in range(40):
            pri = "low" if i % 2 == 0 else "high"
            d = ac.admit("a", pri)
            order.append((pri, d.admitted))
        first_high_shed = next(
            (i for i, (p, a) in enumerate(order)
             if p == "high" and not a), None)
        last_low_admit = max(
            (i for i, (p, a) in enumerate(order) if p == "low" and a),
            default=-1)
        assert first_high_shed is not None  # bucket fully drained
        assert last_low_admit < first_high_shed
        # and low started shedding strictly before high did
        first_low_shed = next(
            i for i, (p, a) in enumerate(order) if p == "low" and not a)
        assert first_low_shed < first_high_shed

    def test_slo_budget_scales_refill(self, meter):
        """Refill rate is tied to the tenant's live tenant.query error
        budget: a burned tenant refills at the floor, others at full
        rate — the ISSUE 11 substrate consumed as designed."""
        for _ in range(50):
            meter.observe("hog", "pts", "sig", wall_ms=5.0, ok=False)
        for _ in range(50):
            meter.observe("polite", "pts", "sig", wall_ms=5.0, ok=True)
        ac = self._controller(meter)
        assert ac.budget_remaining("hog") == 0.0
        assert ac.budget_remaining("polite") == 1.0
        assert ac.effective_rate("hog") == pytest.approx(0.5)  # the floor
        assert ac.effective_rate("polite") == pytest.approx(10.0)

    def test_shed_lands_in_counters_flight_and_usage(self, meter):
        from geomesa_tpu.obs import flight as _flight

        t = [0.0]
        ac = self._controller(meter, burst=2.0, clock=lambda: t[0])
        before = _flight.get().record_count
        budget_before = meter.slo.tracker(
            "tenant.query", "a").budget_remaining(300.0)
        for _ in range(5):
            ac.admit("a", "normal")
        assert ac.shed_count > 0
        m = ac.metrics
        assert m.counters["serving.admission.shed"].count == ac.shed_count
        assert m.counters["serving.admission.admitted"].count == \
            ac.admitted_count
        # flight records with the shed anomaly, attributed to the tenant
        recs = [r for r in _flight.get().records()
                if r.op == "admission" and r.tenant == "a"]
        assert recs and _flight.A_SHED in recs[-1].anomalies
        assert _flight.get().record_count > before
        # usage counters carry the shed under its own signature...
        snap = meter.snapshot()
        assert any(h["signature"] == "admission.shed"
                   for h in snap["heavy_hitters"])
        # ...WITHOUT burning the tenant's SLO (no lock-out feedback loop)
        assert meter.slo.tracker("tenant.query", "a").budget_remaining(
            300.0) == budget_before
        # prometheus series present with bounded labels
        text = ac.prometheus_text()
        assert "geomesa_admission_shed_total" in text
        assert 'geomesa_admission_shed_tenant_total{tenant="a"}' in text

    def test_web_429_with_retry_after(self, meter):
        ds = make_store(n=20)
        t = [0.0]
        ac = self._controller(meter, rate_qps=2.0, burst=2.0,
                              metrics=ds.metrics, clock=lambda: t[0])
        app = GeoMesaApp(ds, admission=ac, coalesce_ms=0)
        # drain, then shed
        statuses = []
        for _ in range(5):
            s, h, _b = call(app, "GET", "/api/schemas/pts/query",
                            headers={"HTTP_X_GEOMESA_TENANT": "a"})
            statuses.append((s, h))
        assert statuses[0][0] == 200
        shed = [(s, h) for s, h in statuses if s == 429]
        assert shed
        ra = shed[0][1].get("Retry-After")
        assert ra is not None and int(ra) >= 1
        # ops surfaces are exempt: the operator can still see the shed
        s, _h, b = call(app, "GET", "/api/metrics",
                        headers={"HTTP_X_GEOMESA_TENANT": "a"})
        assert s == 200
        assert json.loads(b)["admission"]["shed"] >= 1

    def test_remote_429_typed_and_never_retried(self, meter):
        """Satellite: 429 surfaces as RateLimitedError carrying the
        server's Retry-After, classified NON-retryable — a shedding
        member costs exactly ONE round trip (no retry storm)."""
        import wsgiref.simple_server
        from wsgiref.simple_server import make_server

        from geomesa_tpu.resilience.policy import (
            RateLimitedError,
            RetryPolicy,
            retryable,
        )
        from geomesa_tpu.store.remote import RemoteDataStore

        class Quiet(wsgiref.simple_server.WSGIRequestHandler):
            def log_message(self, *a):
                pass

        ds = make_store(n=10)
        t = [0.0]
        ac = self._controller(meter, rate_qps=1.0, burst=1.0,
                              clock=lambda: t[0])
        app = GeoMesaApp(ds, admission=ac, coalesce_ms=0)
        hits = [0]

        def counting(environ, sr):
            if "/query" in environ.get("PATH_INFO", ""):
                hits[0] += 1
            return app(environ, sr)

        httpd = make_server("127.0.0.1", 0, counting,
                            handler_class=Quiet)
        port = httpd.server_address[1]
        th = threading.Thread(target=httpd.serve_forever, daemon=True)
        th.start()
        try:
            rs = RemoteDataStore(
                f"http://127.0.0.1:{port}",
                retry=RetryPolicy(max_attempts=4, base_delay_s=0.001))
            ac.admit("x")  # drain the 1-token anonymous... (own bucket)
            # anonymous bucket: burst 1, normal reserve 0.1 — sheds
            with pytest.raises(RateLimitedError) as ei:
                rs.query("pts", "BBOX(geom,0,0,1,1)")
            assert ei.value.retry_after_s >= 1.0
            assert hits[0] == 1  # ONE attempt: classified non-retryable
        finally:
            httpd.shutdown()
        # the classification contract, pinned directly
        err = urllib.error.HTTPError("http://x", 429, "shed", None, None)
        assert not retryable(err, idempotent=True)
        assert not retryable(err, idempotent=False)
        assert not retryable(
            RateLimitedError("http://x", 2.0), idempotent=True)

    def test_priority_header_unknown_is_normal(self, meter):
        ac = self._controller(meter)
        d = ac.admit("a", "super-extra-vip")
        assert d.priority == "normal"


# -- request coalescing -------------------------------------------------------

def _concurrent(app, reqs, window_warm_s=0.0):
    """Fire reqs = [(path, query, headers)] concurrently after a
    barrier; returns results in request order."""
    results = [None] * len(reqs)
    barrier = threading.Barrier(len(reqs))

    def go(i, path, query, headers):
        barrier.wait()
        results[i] = call(app, "GET", path, query=query, headers=headers)

    threads = [
        threading.Thread(target=go, args=(i, p, q, h))
        for i, (p, q, h) in enumerate(reqs)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


CQLS = ["BBOX(geom,-50,-50,50,50)", "name='n1'", "BBOX(geom,0,0,90,60)",
        None]


def _qs(cql):
    return "" if cql is None else "cql=" + cql.replace(" ", "%20")


class TestCoalesce:
    def test_concurrent_requests_share_one_dispatch_byte_identical(self):
        ds = make_store()
        serial_app = GeoMesaApp(ds, coalesce_ms=0)
        serial = {}
        for cql in CQLS:
            s, _h, b = call(serial_app, "GET", "/api/schemas/pts/query",
                            query=_qs(cql))
            assert s == 200
            serial[cql] = b
        app = GeoMesaApp(ds, coalesce_ms=250.0)
        reqs = [("/api/schemas/pts/query", _qs(CQLS[i % len(CQLS)]), None)
                for i in range(8)]
        results = _concurrent(app, reqs)
        for i, (s, _h, b) in enumerate(results):
            assert s == 200
            assert b == serial[CQLS[i % len(CQLS)]]  # byte-identical
        c = app.coalescer
        assert c.query_count == 8
        assert c.dispatch_count < c.query_count  # FEWER dispatches
        assert c.max_width > 1  # coalescing observed

    def test_two_tenant_coalesce_meters_each_tenant(self, meter):
        """Satellite: a coalesced dispatch meters rows/wall per member
        query against ITS tenant — not the batch leader's."""
        import time as _time

        ds = make_store()
        # expected per-tenant row counts from uncoalesced execution
        expected = {
            "acme": ds.query("pts", CQLS[0]).count,
            "globex": ds.query("pts", CQLS[1]).count,
        }

        class SlowFirst:
            """First dispatch stalls so the two tenant requests gather
            into ONE batch behind it (backpressure batching made
            deterministic)."""

            def __init__(self, inner):
                self._inner = inner
                self.n = 0

            def query(self, *a, **k):
                self.n += 1
                if self.n == 1:
                    _time.sleep(0.25)
                return self._inner.query(*a, **k)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        slow = SlowFirst(ds)
        app = GeoMesaApp(slow, coalesce_ms=500.0)
        # occupy the key with an in-flight (slow) dispatch...
        opener = threading.Thread(
            target=call, args=(app, "GET", "/api/schemas/pts/query"),
            kwargs={"query": _qs(CQLS[2])})
        opener.start()
        _time.sleep(0.05)
        # ...so both tenants land in the gathering batch behind it
        reqs = [
            ("/api/schemas/pts/query", _qs(CQLS[0]),
             {"HTTP_X_GEOMESA_TENANT": "acme"}),
            ("/api/schemas/pts/query", _qs(CQLS[1]),
             {"HTTP_X_GEOMESA_TENANT": "globex"}),
        ]
        results = _concurrent(app, reqs)
        opener.join()
        assert all(s == 200 for s, _h, _b in results)
        assert app.coalescer.max_width == 2  # ONE dispatch served both
        snap = meter.snapshot()
        rows = {t["tenant"]: t["lifetime"]["rows"]
                for t in snap["tenants"]}
        assert rows.get("acme") == expected["acme"]
        assert rows.get("globex") == expected["globex"]
        queries = {t["tenant"]: t["lifetime"]["queries"]
                   for t in snap["tenants"]}
        assert queries.get("acme") == 1 and queries.get("globex") == 1

    def test_deadline_too_tight_bypasses_window(self):
        from geomesa_tpu.utils.timeouts import Deadline

        ds = make_store(n=30)
        co = Coalescer(ds, window_s=0.2)
        q = Query(filter=None, hints={"deadline": Deadline.after_ms(50)})
        r = co.submit("pts", "select", q)
        assert r.count == 30
        assert co.dispatch_count == 0  # never entered a batch
        assert co.metrics.counters[
            "serving.coalesce.bypass_deadline"].count == 1

    def test_width_one_keeps_individual_plan_audit(self):
        """A width-1 'batch' must run the ordinary query path so the
        adaptive planner's cost table keeps training on web traffic."""
        from geomesa_tpu.obs import devmon

        prev = devmon.install(devmon.ResidencyLedger())
        try:
            ds = make_store()
            app = GeoMesaApp(ds, coalesce_ms=20.0)
            s, _h, _b = call(app, "GET", "/api/schemas/pts/query",
                             query=_qs(CQLS[0]))
            assert s == 200
            assert devmon.costs().snapshot()["entry_count"] >= 1
        finally:
            devmon.install(prev)

    def test_count_and_aggregate_ops_parity(self):
        ds = make_store()
        co = Coalescer(ds, window_s=0.0)  # window off: direct singles
        assert co.window_s == 0.0
        co = Coalescer(ds, window_s=0.001)
        got = co.submit("pts", "count", Query(filter=CQLS[0]), loose=False)
        assert got == ds.count_many("pts", [CQLS[0]], loose=False)[0]
        got = co.submit("pts", "aggregate", Query(filter=None),
                        group_by=["name"])
        ref = ds.aggregate_many("pts", [None], group_by=["name"])[0]
        assert got is not None and ref is not None
        assert sorted(got["groups"]) == sorted(ref["groups"])
        assert got["count"].sum() == ref["count"].sum()

    def test_leader_error_propagates_to_every_waiter(self):
        ds = make_store(n=10)
        app = GeoMesaApp(ds, coalesce_ms=200.0)
        reqs = [("/api/schemas/nope/query", "", None) for _ in range(3)]
        results = _concurrent(app, reqs)
        assert all(s == 404 for s, _h, _b in results)

    def test_store_without_batched_surface_executes_singly(self):
        from geomesa_tpu.store.merged import MergedDataStoreView

        ds = make_store(n=40)
        view = MergedDataStoreView([ds])
        app = GeoMesaApp(view, coalesce_ms=20.0)
        s, _h, b = call(app, "GET", "/api/schemas/pts/query",
                        query=_qs(CQLS[0]))
        assert s == 200
        assert app.coalescer.dispatch_count == 0


# -- shard router + sharded federation ---------------------------------------

def _sft():
    from geomesa_tpu.schema.sft import parse_spec

    return parse_spec("pts", SPEC)


class TestShardRouter:
    def test_partition_total_and_deterministic(self):
        r1 = ShardRouter([0, 1, 2], n_shards=12)
        r2 = ShardRouter([0, 1, 2], n_shards=12)
        assert r1.shard_member == r2.shard_member  # no hash randomization
        rng = np.random.default_rng(3)
        keys = r1.keys_for(rng.uniform(-180, 180, 500),
                           rng.uniform(-90, 90, 500))
        shards = r1.shards_of_keys(keys)
        assert shards.min() >= 0 and shards.max() < 12
        # every shard owned by exactly one member
        assert len(r1.shard_member) == 12
        assert set(r1.shard_member) <= {0, 1, 2}

    def test_members_dedupe_fixes_double_count(self):
        """Red/green (satellite 1): several Z-prefix shard ranges map to
        the SAME member — the fan-out must hit that member ONCE. A
        per-shard fan-out would double-count every row it holds."""
        from geomesa_tpu.filter.cql import parse

        r = ShardRouter([0, 1], n_shards=16)
        sft = _sft()
        # a box wide enough to intersect many shards on both members
        members = r.members_for_filter(
            parse("BBOX(geom,-170,-80,170,80)"), sft)
        shards = r.shards_for_boxes([(-170.0, -80.0, 170.0, 80.0)])
        assert len(shards) > 2  # several shards intersected...
        assert members is not None
        assert len(members) == len(set(members)) <= 2  # ...members deduped
        # and end-to-end: a whole-domain count equals the true row count
        stores = [make_store(n=0, compacted=False) for _ in range(2)]
        view = ShardedDataStoreView(stores, n_shards=16)
        rng = np.random.default_rng(9)
        view.write("pts", [
            {"name": "n", "dtg": T0,
             "geom": Point(float(rng.uniform(-170, 170)),
                           float(rng.uniform(-60, 60)))}
            for i in range(120)
        ], fids=[f"d{i}" for i in range(120)])
        assert view.query(
            "pts", "BBOX(geom,-170,-80,170,80)").count == 120
        assert view.stats_count("pts") == 120

    def test_consistent_hash_minimal_movement(self):
        r = ShardRouter(["a", "b", "c"], n_shards=64)
        r2 = r.with_members(["a", "b"])  # c departs
        moved = [
            s for s in range(64)
            if r.shard_member[s] != r2.shard_member[s]
        ]
        # only c's shards move; a/b keep everything they owned
        assert all(r.shard_member[s] == "c" for s in moved)
        assert {r.shard_member[s] for s in range(64)} == {"a", "b", "c"}

    def test_fid_and_attr_filters_fan_everywhere_disjoint_nowhere(self):
        from geomesa_tpu.filter import ast
        from geomesa_tpu.filter.cql import parse

        r = ShardRouter([0, 1, 2], n_shards=12)
        sft = _sft()
        assert r.members_for_filter(
            ast.FidIn(("f1",)), sft) is None  # fid: all members
        assert r.members_for_filter(parse("name='x'"), sft) is None
        assert r.members_for_filter(None, sft) is None
        disjoint = parse(
            "BBOX(geom,10,10,20,20) AND BBOX(geom,30,30,40,40)")
        assert r.members_for_filter(disjoint, sft) == []

    def test_routed_view_deterministic_under_shard_router(self):
        """Satellite 1 (route fallback audit): with a shard router
        configured, fid filters still route to the id store and
        attribute-only filters to their attribute route — repeatably —
        while single-owner spatial filters route to the owner member."""
        from geomesa_tpu.filter import ast
        from geomesa_tpu.filter.cql import parse
        from geomesa_tpu.store.routed import RoutedDataStoreView

        id_store = make_store(n=5, seed=1, compacted=False)
        attr_store = make_store(n=5, seed=2, compacted=False)
        geo_store = make_store(n=5, seed=3, compacted=False)
        router = ShardRouter([0, 1, 2], n_shards=12)
        view = RoutedDataStoreView(
            [(id_store, ["id"]), (attr_store, [["name"]]),
             (geo_store, [[]])],
            shard_router=router,
        )
        for _ in range(3):  # deterministic: identical every time
            assert view.route(
                ast.FidIn(("f1",)), "pts") is id_store
            assert view.route(parse("name='n1'"), "pts") is attr_store
            # unconstrained: include store
            assert view.route(None, "pts") is geo_store
        f = parse("BBOX(geom,10,10,11,11)")
        owner = router.members_for_filter(f, _sft())
        assert owner is not None and len(owner) == 1
        for _ in range(3):
            assert view.route(f, "pts") is view.stores[owner[0]]


class _CountingStore:
    """Delegating wrapper counting query fan-outs."""

    def __init__(self, ds):
        self._ds = ds
        self.queries = 0

    def query(self, *a, **k):
        self.queries += 1
        return self._ds.query(*a, **k)

    def __getattr__(self, name):
        return getattr(self._ds, name)


class TestShardedView:
    def _mk(self, n=300, members=3, n_shards=12, **kw):
        stores = [DataStore(backend="tpu") for _ in range(members)]
        view = ShardedDataStoreView(stores, n_shards=n_shards, **kw)
        view.create_schema("pts", SPEC)
        rng = np.random.default_rng(5)
        recs = [
            {"name": f"n{i % 3}", "dtg": T0 + i * 1000,
             "geom": Point(float(rng.uniform(-170, 170)),
                           float(rng.uniform(-60, 60)))}
            for i in range(n)
        ]
        view.write("pts", recs, fids=[f"f{i}" for i in range(n)])
        view.compact("pts")
        return view, stores, recs

    def test_write_partitions_each_row_exactly_once(self):
        view, stores, recs = self._mk()
        per = [int(s.stats_count("pts")) for s in stores]
        assert sum(per) == 300
        assert all(p > 0 for p in per)  # every member carries load
        fid_sets = [set(s.query("pts").table.fids.tolist())
                    for s in stores]
        for i in range(len(fid_sets)):
            for j in range(i + 1, len(fid_sets)):
                assert not (fid_sets[i] & fid_sets[j])  # disjoint

    def test_read_parity_with_unsharded_reference(self):
        view, stores, recs = self._mk()
        ref = DataStore(backend="tpu")
        ref.create_schema("pts", SPEC)
        ref.write("pts", recs, fids=[f"f{i}" for i in range(300)])
        ref.compact("pts")
        for cql in CQLS:
            got = view.query("pts", cql)
            want = ref.query("pts", cql)
            assert got.count == want.count
            assert (sorted(got.table.fids.tolist())
                    == sorted(want.table.fids.tolist()))
        # batched surfaces
        got_many = view.select_many("pts", CQLS)
        want_many = [ref.query("pts", c) for c in CQLS]
        for g, w in zip(got_many, want_many):
            assert sorted(g.table.fids.tolist()) == sorted(
                w.table.fids.tolist())
        assert view.count_many("pts", CQLS, loose=False) == [
            w.count for w in want_many]
        ga = view.aggregate_many("pts", [None], group_by=["name"])[0]
        wa = ref.aggregate_many("pts", [None], group_by=["name"])[0]
        assert ga is not None and wa is not None
        assert dict(zip([g[0] for g in ga["groups"]],
                        ga["count"].tolist())) == \
            dict(zip([g[0] for g in wa["groups"]],
                     wa["count"].tolist()))
        # sort/limit re-applied at the view level
        page = view.query("pts", Query(filter=None, sort_by=("dtg", False),
                                       limit=10, start_index=5))
        rpage = ref.query("pts", Query(filter=None, sort_by=("dtg", False),
                                       limit=10, start_index=5))
        assert page.table.fids.tolist() == rpage.table.fids.tolist()

    def test_fanout_prunes_to_intersecting_members(self):
        stores = [_CountingStore(DataStore(backend="tpu"))
                  for _ in range(3)]
        view = ShardedDataStoreView(stores, n_shards=12)
        view.create_schema("pts", SPEC)
        rng = np.random.default_rng(5)
        view.write("pts", [
            {"name": "n", "dtg": T0,
             "geom": Point(float(rng.uniform(-170, 170)),
                           float(rng.uniform(-60, 60)))}
            for i in range(100)
        ], fids=[f"f{i}" for i in range(100)])
        for s in stores:
            s.queries = 0
        # a tiny box: strictly fewer members than the full set
        sub = view._member_subset(
            "pts", Query(filter="BBOX(geom,10,10,10.5,10.5)")
            .resolved_filter())
        assert sub is not None and 1 <= len(sub) < 3
        view.query("pts", "BBOX(geom,10,10,10.5,10.5)")
        assert sum(s.queries for s in stores) == len(sub)
        # attribute-only: all members (rows could be anywhere)
        for s in stores:
            s.queries = 0
        view.query("pts", "name='n'")
        assert sum(s.queries for s in stores) == 3
        # provably disjoint: NO fan-out at all
        for s in stores:
            s.queries = 0
        r = view.query(
            "pts", "BBOX(geom,10,10,20,20) AND BBOX(geom,30,30,40,40)")
        assert r.count == 0
        assert sum(s.queries for s in stores) == 0

    def test_wkt_geometries_place_by_coordinates(self):
        """Red/green: WKT strings are accepted anywhere a geometry is
        (the columnar tier's convention) — the shard writer must place
        them by their coordinates, not the fid hash, or pruned spatial
        reads can never reach the row."""
        stores = [DataStore(backend="tpu") for _ in range(3)]
        view = ShardedDataStoreView(stores, n_shards=12)
        view.create_schema("pts", SPEC)
        view.write("pts", [
            {"name": "w", "dtg": T0, "geom": "POINT (10 10)"},
            {"name": "w", "dtg": T0, "geom": "POINT (-120 40)"},
        ], fids=["wa", "wb"])
        # the narrow box prunes fan-out to the coordinate's shard owner
        # — the row must be there
        assert view.query("pts", "BBOX(geom,9,9,11,11)").count == 1
        assert view.query("pts", "BBOX(geom,-121,39,-119,41)").count == 1

    def test_extended_geometries_fan_everywhere(self):
        """Red/green: rows partition by envelope-CENTER key, so a query
        box can intersect a polygon whose center shard lies far outside
        the box's Z-ranges — non-point types must fan out to ALL
        members or matching rows silently vanish."""
        from geomesa_tpu.geometry.types import Polygon
        from geomesa_tpu.schema.sft import parse_spec

        spec = "name:String,*geom:Polygon;geomesa.xz.precision='10'"
        stores = [DataStore(backend="tpu") for _ in range(3)]
        view = ShardedDataStoreView(stores, n_shards=12)
        view.create_schema("poly", spec)
        # a wide polygon: center x=50, but it reaches x=0
        view.write("poly", [{
            "name": "wide",
            "geom": Polygon(np.array(
                [[0.0, -10.0], [100.0, -10.0], [100.0, 10.0],
                 [0.0, 10.0]])),
        }], fids=["w1"])
        sft = parse_spec("poly", spec)
        router = view.router
        f = Query(filter="BBOX(geom,0,-10,5,10)").resolved_filter()
        # the fix: non-point schemas never prune the fan-out...
        assert router.members_for_filter(f, sft) is None
        # ...so a query box far from the center still finds the row
        assert view.query("poly", "BBOX(geom,0,-10,5,10)").count == 1
        # disjoint filters still fan nowhere
        assert router.members_for_filter(
            Query(filter="BBOX(geom,10,10,20,20) AND "
                         "BBOX(geom,30,30,40,40)").resolved_filter(),
            sft) == []

    def test_disjoint_density_keeps_grid_shape(self):
        """A provably-disjoint filter must still answer a density query
        with a ZERO GRID (the channel's shape), not a table-shaped
        empty result with density=None."""
        view, stores, recs = self._mk(n=50)
        r = view.query("pts", Query(
            filter="BBOX(geom,10,10,20,20) AND BBOX(geom,30,30,40,40)",
            hints={"density": {"width": 8, "height": 8}}))
        assert r.density is not None
        assert np.asarray(r.density).shape == (8, 8)
        assert float(np.asarray(r.density).sum()) == 0.0

    def test_partial_mode_degrades_on_member_failure(self):
        view, stores, recs = self._mk(members=3,
                                      on_member_error="partial")

        class Boom:
            def __getattr__(self, name):
                if name in ("query", "select_many", "count_many",
                            "stats_count"):
                    def _fail(*a, **k):
                        raise ConnectionError("member down")
                    return _fail
                return getattr(stores[0], name)

        total = view.query("pts").count
        dead_rows = stores[0].stats_count("pts")
        view.stores[0] = (Boom(), None)
        r = view.query("pts")
        assert r.degraded and r.member_errors
        assert r.count == total - dead_rows
        # batched surfaces degrade the same way
        out = view.select_many("pts", [None])[0]
        assert out.degraded and out.count == total - dead_rows
        assert view.count_many("pts", [None], loose=False)[0] == \
            total - dead_rows
        # fail mode: the same failure raises
        view.on_member_error = "fail"
        with pytest.raises(ConnectionError):
            view.query("pts")


# -- the end-to-end serving pin ----------------------------------------------

class TestEndToEndServing:
    def test_coalesce_shed_and_usage_reconcile(self, meter):
        """The acceptance pin: concurrent HTTP queries from 3 tenants
        coalesce into fewer device dispatches than queries, results are
        byte-identical to uncoalesced serial execution, per-tenant usage
        totals reconcile, and with one tenant driven past its SLO budget
        ONLY that tenant's requests shed (429)."""
        ds = make_store()
        serial_app = GeoMesaApp(ds, coalesce_ms=0)
        serial = {}
        for cql in CQLS[:3]:
            s, _h, b = call(serial_app, "GET", "/api/schemas/pts/query",
                            query=_qs(cql))
            serial[cql] = b
        expected_rows = {cql: ds.query("pts", cql).count
                         for cql in CQLS[:3]}

        t = [0.0]
        ac = AdmissionController(
            rate_qps=100.0, burst=100.0, min_rate_qps=0.25,
            meter=meter, metrics=ds.metrics, clock=lambda: t[0])
        app = GeoMesaApp(ds, admission=ac, coalesce_ms=250.0)
        tenants = ["t-a", "t-b", "t-c"]
        base_queries = meter.snapshot()["observe_count"]
        reqs = [
            ("/api/schemas/pts/query", _qs(CQLS[i % 3]),
             {"HTTP_X_GEOMESA_TENANT": tenants[i % 3]})
            for i in range(9)
        ]
        results = _concurrent(app, reqs)
        # every query answered, byte-identical to serial execution
        for i, (s, _h, b) in enumerate(results):
            assert s == 200
            assert b == serial[CQLS[i % 3]]
        c = app.coalescer
        assert c.query_count == 9 and c.dispatch_count < 9
        assert c.max_width > 1
        # per-tenant usage totals reconcile exactly
        snap = meter.snapshot()
        per = {x["tenant"]: x["lifetime"] for x in snap["tenants"]}
        for i, tn in enumerate(tenants):
            want = sum(expected_rows[CQLS[j % 3]]
                       for j in range(9) if j % 3 == i)
            assert per[tn]["rows"] == want
            assert per[tn]["queries"] == 3
            assert per[tn]["bytes_out"] > 0  # web egress attribution
        # drive t-c past its SLO budget: its refill collapses to the
        # floor and its burst is gone after the next few requests
        for _ in range(100):
            meter.observe("t-c", "pts", "sig", wall_ms=5.0, ok=False)
        with ac._lock:
            ac._buckets["t-c"].tokens = 0.0  # burst already spent
        codes = {}
        for tn in tenants:
            s, _h, _b = call(app, "GET", "/api/schemas/pts/query",
                             query=_qs(CQLS[0]),
                             headers={"HTTP_X_GEOMESA_TENANT": tn})
            codes[tn] = s
        assert codes["t-c"] == 429  # only the over-budget tenant sheds
        assert codes["t-a"] == 200 and codes["t-b"] == 200
        assert meter.snapshot()["observe_count"] > base_queries
