"""REST endpoint tests via in-process WSGI calls (reference: geomesa-web
servlets — SURVEY.md §2.19)."""

import io
import json

import numpy as np
import pytest

from geomesa_tpu.store.datastore import DataStore
from geomesa_tpu.utils.audit import InMemoryAuditWriter
from geomesa_tpu.web import GeoMesaApp


def call(app, method, path, query="", body=None, headers=None):
    """Minimal WSGI client: returns (status_code, headers, bytes)."""
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

    def start_response(status, headers):
        out["status"] = int(status.split()[0])
        out["headers"] = dict(headers)

    chunks = app(environ, start_response)
    return out["status"], out["headers"], b"".join(chunks)


def jcall(app, method, path, query="", body=None, headers=None):
    status, _, data = call(app, method, path, query, body, headers)
    return status, json.loads(data) if data else None


@pytest.fixture()
def app():
    ds = DataStore(backend="tpu", audit_writer=InMemoryAuditWriter())
    return GeoMesaApp(ds)


def _ingest(app, n=50):
    jcall(app, "POST", "/api/schemas", body={"name": "pts", "spec": "name:String,dtg:Date,*geom:Point"})
    rng = np.random.default_rng(9)
    feats = [
        {
            "type": "Feature",
            "id": f"p{i}",
            "geometry": {"type": "Point",
                         "coordinates": [float(rng.uniform(-50, 50)), float(rng.uniform(-50, 50))]},
            "properties": {"name": f"n{i % 4}", "dtg": 1_498_867_200_000 + i * 1000},
        }
        for i in range(n)
    ]
    status, out = jcall(app, "POST", "/api/schemas/pts/features",
                        body={"type": "FeatureCollection", "features": feats})
    assert status == 201 and out["written"] == n


class TestSchemaCrud:
    def test_version(self, app):
        status, out = jcall(app, "GET", "/api/version")
        assert status == 200 and out["name"] == "geomesa-tpu"

    def test_create_list_get_delete(self, app):
        status, out = jcall(app, "POST", "/api/schemas",
                            body={"name": "t1", "spec": "a:Integer,*geom:Point"})
        assert status == 201
        _, out = jcall(app, "GET", "/api/schemas")
        assert "t1" in out["schemas"]
        status, out = jcall(app, "GET", "/api/schemas/t1")
        assert status == 200 and out["count"] == 0
        assert any(a["name"] == "geom" for a in out["attributes"])
        status, _ = jcall(app, "DELETE", "/api/schemas/t1")
        assert status == 204
        status, _ = jcall(app, "GET", "/api/schemas/t1")
        assert status == 404

    def test_bad_requests(self, app):
        status, out = jcall(app, "POST", "/api/schemas", body={"name": "x"})
        assert status == 400
        status, _ = jcall(app, "GET", "/api/nope")
        assert status == 404
        status, _ = jcall(app, "DELETE", "/api/schemas")
        assert status == 405


class TestQueryAndStats:
    def test_geojson_query(self, app):
        _ingest(app)
        status, out = jcall(app, "GET", "/api/schemas/pts/query",
                            query="cql=BBOX(geom,-50,-50,0,50)&limit=10")
        assert status == 200
        assert out["type"] == "FeatureCollection"
        assert 0 < len(out["features"]) <= 10
        f = out["features"][0]
        assert f["geometry"]["type"] == "Point" and "name" in f["properties"]

    def test_arrow_query(self, app):
        import pyarrow as pa

        _ingest(app)
        status, headers, data = call(app, "GET", "/api/schemas/pts/query", "format=arrow")
        assert status == 200
        assert headers["Content-Type"] == "application/vnd.apache.arrow.stream"
        at = pa.ipc.open_stream(data).read_all()
        assert at.num_rows == 50

    def test_avro_gml_leaflet_query(self, app):
        import io as _io

        from geomesa_tpu.io.avro import read_avro

        _ingest(app)
        status, headers, data = call(app, "GET", "/api/schemas/pts/query", "format=avro")
        assert status == 200 and headers["Content-Type"] == "application/avro"
        records, fids, _ = read_avro(_io.BytesIO(data))
        assert len(records) == 50

        status, headers, data = call(app, "GET", "/api/schemas/pts/query", "format=gml")
        assert status == 200 and headers["Content-Type"] == "application/gml+xml"
        assert data.count(b"<gml:featureMember>") == 50

        status, headers, data = call(app, "GET", "/api/schemas/pts/query", "format=leaflet")
        assert status == 200 and headers["Content-Type"].startswith("text/html")
        assert b"L.map(" in data

    def test_stats_endpoints(self, app):
        _ingest(app)
        status, out = jcall(app, "GET", "/api/schemas/pts/stats", "stats=Count()")
        assert status == 200 and out["Count()"]["count"] == 50
        status, out = jcall(app, "GET", "/api/schemas/pts/stats/count", "exact=true")
        assert out["count"] == 50
        status, out = jcall(app, "GET", "/api/schemas/pts/stats/bounds", "attr=dtg")
        assert out["min"] == 1_498_867_200_000
        status, out = jcall(app, "GET", "/api/schemas/pts/stats/topk", "attr=name&k=2")
        assert len(out["topk"]) == 2

    def test_density(self, app):
        _ingest(app)
        status, out = jcall(app, "GET", "/api/schemas/pts/density",
                            "bbox=-50,-50,50,50&width=16&height=16")
        assert status == 200
        grid = np.asarray(out["grid"])
        assert grid.shape == (16, 16) and grid.sum() == 50

    def test_audit_and_metrics(self, app):
        _ingest(app)
        jcall(app, "GET", "/api/schemas/pts/query", "cql=BBOX(geom,0,0,10,10)")
        status, out = jcall(app, "GET", "/api/audit", "typeName=pts")
        assert status == 200 and len(out["events"]) >= 1
        status, out = jcall(app, "GET", "/api/metrics")
        assert status == 200 and out["store.queries"]["count"] >= 1

    def test_metrics_prometheus_exposition(self, app):
        _ingest(app)
        jcall(app, "GET", "/api/schemas/pts/query", "cql=BBOX(geom,0,0,10,10)")
        status, headers, data = call(
            app, "GET", "/api/metrics", "format=prometheus"
        )
        assert status == 200
        assert headers["Content-Type"] == "text/plain; version=0.0.4; charset=utf-8"
        text = data.decode()
        assert "# TYPE geomesa_store_queries_total counter" in text
        assert "geomesa_store_queries_total" in text
        # timers export as summaries with quantile labels
        assert 'geomesa_web_request_ms_seconds{quantile="0.5"}' in text
        # the JSON snapshot stays the default
        status, out = jcall(app, "GET", "/api/metrics")
        assert status == 200 and isinstance(out, dict)

    def test_metrics_device_section(self, app):
        """The device-telemetry satellite: /api/metrics carries a
        ``device`` section (resident bytes by type/index/group, budget
        headroom, transfer totals) in JSON and labeled residency gauges
        in the Prometheus exposition."""
        from geomesa_tpu.obs import devmon

        prev = devmon.install(devmon.ResidencyLedger())
        try:
            _ingest(app, n=1500)  # enough rows to go device-resident
            status, out = jcall(app, "GET", "/api/metrics")
            assert status == 200
            dev = out["device"]
            assert dev["total_bytes"] > 0
            assert "pts" in dev["resident"]
            groups = dev["resident"]["pts"]["z3"]
            assert groups.get("spatial", 0) > 0
            assert dev["transfers"]["h2d_bytes"] >= 0
            assert "headroom_bytes" in dev and "spilled" in dev
            status, _, data = call(
                app, "GET", "/api/metrics", "format=prometheus")
            text = data.decode()
            assert ("geomesa_device_resident_bytes"
                    '{type="pts",index="z3",group="spatial"}') in text
            assert "geomesa_device_resident_bytes_total" in text
        finally:
            devmon.install(prev)

    def test_metrics_cache_gauges(self, app):
        """ISSUE 7 satellite: geomesa_cache_{hits,misses,evictions},
        pool gauges, and pyramid-bytes ride the Prometheus scrape, and
        the JSON snapshot carries the cache report block."""
        _ingest(app, n=1500)
        # drive one grouped aggregate so the cache/pyramid have traffic
        app.store.aggregate_many(
            "pts", ["BBOX(geom, 0, 0, 40, 40)"], group_by=None,
            value_cols=[])
        app.store.aggregate_many(
            "pts", ["BBOX(geom, 0, 0, 40, 40)"], group_by=None,
            value_cols=[])
        status, _, data = call(
            app, "GET", "/api/metrics", "format=prometheus")
        assert status == 200
        text = data.decode()
        for name in ("geomesa_cache_hits", "geomesa_cache_misses",
                     "geomesa_cache_evictions", "geomesa_pool_hits",
                     "geomesa_pool_resident_bytes"):
            assert name in text
        status, out = jcall(app, "GET", "/api/metrics")
        assert status == 200
        cache = out["cache"]
        assert cache["agg_cache"]["hits"] >= 1
        assert "pyramid_bytes" in cache and "pool" in cache

    def test_obs_costs_endpoint(self, app):
        from geomesa_tpu.obs import devmon

        prev = devmon.install(devmon.ResidencyLedger())
        try:
            _ingest(app, n=1500)
            jcall(app, "GET", "/api/schemas/pts/query",
                  "cql=BBOX(geom,0,0,10,10)")
            status, out = jcall(app, "GET", "/api/obs/costs")
            assert status == 200
            assert out["entry_count"] >= 1
            e = next(r for r in out["entries"] if r["type"] == "pts")
            assert e["count"] >= 1 and e["wall_ms_p50"] > 0
            assert {"signature", "device_ms_p50", "rows_p50",
                    "bytes_scanned_p50"} <= set(e)
            # the adaptive cost model's calibration report rides along
            cal = out["calibration"]
            assert {"entries", "entry_count", "samples"} <= set(cal)
        finally:
            devmon.install(prev)

    def test_count_many(self, app):
        _ingest(app)
        status, out = jcall(
            app, "POST", "/api/schemas/pts/count-many",
            body={"queries": ["BBOX(geom, -50, -50, 0, 50)", "INCLUDE"]},
        )
        assert status == 200
        assert out["counts"][1] == 50
        assert 0 < out["counts"][0] <= 50

    def test_sql_endpoint(self, app):
        _ingest(app)
        status, out = jcall(app, "POST", "/api/sql", body={
            "q": "SELECT name, COUNT(*) AS n FROM pts GROUP BY name"})
        assert status == 200
        assert out["columns"] == ["name", "n"]
        assert sorted(r[0] for r in out["rows"]) == ["n0", "n1", "n2", "n3"]
        assert sum(r[1] for r in out["rows"]) == 50
        status, out = jcall(app, "POST", "/api/sql", body={"q": "SELEC x"})
        assert status == 400 and "sql error" in out["error"]
        status, _ = jcall(app, "POST", "/api/sql", body={})
        assert status == 400

    def test_sql_endpoint_scopes_rows_to_caller_auths(self):
        # caller auths thread into every internal query: a restricted
        # caller's SQL sees ONLY their visible rows (never over-served)
        from geomesa_tpu.schema.columnar import FeatureTable
        from geomesa_tpu.schema.sft import parse_spec
        from geomesa_tpu.security.auth import HeaderAuthorizationsProvider
        from geomesa_tpu.web import GeoMesaApp

        sft = parse_spec(
            "tracks", "dtg:Date,*geom:Point,vis:String;geomesa.vis.field='vis'"
        )
        ds = DataStore(backend="oracle")
        ds.create_schema(sft)
        from geomesa_tpu.geometry import Point as _P

        recs = [
            {"dtg": 1_500_000_000_000 + i, "geom": _P(i, i), "vis": v}
            for i, v in enumerate(["admin", "", "user|admin", "secret", ""])
        ]
        ds.write("tracks", FeatureTable.from_records(
            sft, recs, [f"f{i}" for i in range(5)]))
        app2 = GeoMesaApp(ds, auth_provider=HeaderAuthorizationsProvider())

        def q(headers):
            return jcall(app2, "POST", "/api/sql",
                         body={"q": "SELECT COUNT(*) AS n FROM tracks"},
                         headers=headers)

        s, o = q({"HTTP_X_GEOMESA_AUTHS": "admin"})
        assert s == 200 and o["rows"][0][0] == 4  # admin, '', user|admin, ''
        s, o = q({})  # no header = NO auths: only unrestricted rows
        assert s == 200 and o["rows"][0][0] == 2
        s, o = q({"HTTP_X_GEOMESA_AUTHS": "secret"})
        assert s == 200 and o["rows"][0][0] == 3

    def test_query_invalid_cql(self, app):
        _ingest(app)
        status, out = jcall(app, "GET", "/api/schemas/pts/query", "cql=NOT%20VALID(")
        assert status == 400

    def test_leaflet_script_injection_escaped(self, app):
        # a hostile property value must not break out of the <script> block
        status, _ = jcall(app, "POST", "/api/schemas", body={
            "name": "evil", "spec": "name:String,*geom:Point"})
        assert status == 201
        status, _ = jcall(app, "POST", "/api/schemas/evil/features", body={
            "type": "FeatureCollection",
            "features": [{
                "type": "Feature", "id": "e1",
                "geometry": {"type": "Point", "coordinates": [1.0, 2.0]},
                "properties": {
                    "name": "</script><script>alert(1)</script>"},
            }],
        })
        assert status == 201
        status, headers, data = call(
            app, "GET", "/api/schemas/evil/query", "format=leaflet")
        assert status == 200
        assert b"</script><script>alert" not in data
        assert b"\\u003c/script\\u003e" in data

    def test_update_schema_endpoint(self, app):
        _ingest(app)
        status, out = jcall(app, "PATCH", "/api/schemas/pts",
                            body={"add": "severity:Integer",
                                  "keywords": ["a", "b"]})
        assert status == 200
        assert "severity:Integer" in out["spec"]
        status, out = jcall(app, "GET", "/api/schemas/pts")
        assert any(a["name"] == "severity" for a in out["attributes"])
        # invalid evolution -> clean 400
        status, out = jcall(app, "PATCH", "/api/schemas/pts",
                            body={"add": "g2:Point"})
        assert status == 400

    def test_update_schema_validation(self, app):
        _ingest(app)
        # non-dict body -> clean 400, not a 500 traceback
        status, out = jcall(app, "PATCH", "/api/schemas/pts",
                            body="severity:Integer")
        assert status == 400
        # unknown keys only -> 400, not a silent no-op
        status, out = jcall(app, "PATCH", "/api/schemas/pts",
                            body={"adds": "severity:Integer"})
        assert status == 400
        # wrong types -> 400
        for bad in ({"rename_to": 5}, {"keywords": "gdelt"},
                    {"add": [1, 2]}):
            status, out = jcall(app, "PATCH", "/api/schemas/pts", body=bad)
            assert status == 400, bad
        # store still listable and unchanged
        status, out = jcall(app, "GET", "/api/schemas")
        assert status == 200 and "pts" in out["schemas"]


class TestFeatureModification:
    """WFS-T Update/Delete analog endpoints."""

    def _app(self):
        from geomesa_tpu.geometry import Point
        from geomesa_tpu.schema.sft import parse_spec
        from geomesa_tpu.store.datastore import DataStore
        from geomesa_tpu.web.app import GeoMesaApp

        ds = DataStore(backend="oracle")
        ds.create_schema(parse_spec("t", "name:String,*geom:Point"))
        ds.write("t", [{"name": f"v{i}", "geom": Point(float(i), 0.0)}
                       for i in range(5)], fids=[f"f{i}" for i in range(5)])
        return GeoMesaApp(ds), ds

    def test_put_updates_by_id(self):
        app, ds = self._app()
        body = {"type": "FeatureCollection", "features": [
            {"type": "Feature", "id": "f2",
             "geometry": {"type": "Point", "coordinates": [50.0, 5.0]},
             "properties": {"name": "replaced"}},
        ]}
        status, out, _ = app._update_features("t", {}, body)
        assert status == 200 and out["updated"] == 1
        r = ds.query("t", "BBOX(geom, 49, 4, 51, 6)")
        assert r.table.fids.tolist() == ["f2"]
        assert ds.query("t").count == 5

    def test_put_missing_fid_404(self):
        # no silent upsert: the store raises KeyError, dispatch maps to 404
        import pytest

        app, ds = self._app()
        body = {"type": "FeatureCollection", "features": [
            {"type": "Feature", "id": "ghost",
             "geometry": {"type": "Point", "coordinates": [1.0, 1.0]},
             "properties": {"name": "x"}},
        ]}
        with pytest.raises(KeyError):
            app._update_features("t", {}, body)
        assert ds.query("t").count == 5

    def test_put_requires_ids(self):
        from geomesa_tpu.web.app import _HttpError

        app, _ = self._app()
        body = {"type": "FeatureCollection", "features": [
            {"type": "Feature",
             "geometry": {"type": "Point", "coordinates": [0.0, 0.0]},
             "properties": {"name": "x"}},
        ]}
        import pytest

        with pytest.raises(_HttpError) as e:
            app._update_features("t", {}, body)
        assert e.value.status == 400

    def test_delete_by_fids_param(self):
        app, ds = self._app()
        status, out, _ = app._delete_features("t", {"fids": "f1,f3"}, None)
        assert status == 200 and out["deleted"] == 2
        assert ds.query("t").count == 3
        # body form too
        status, out, _ = app._delete_features("t", {}, {"fids": ["f0"]})
        assert out["deleted"] == 1

    def test_delete_requires_fids(self):
        import pytest

        from geomesa_tpu.web.app import _HttpError

        app, _ = self._app()
        with pytest.raises(_HttpError):
            app._delete_features("t", {}, None)


class TestDeleteBodyOverHttp:
    def test_delete_body_form_reaches_handler(self):
        """The WSGI dispatcher must parse DELETE bodies (regression: the
        documented {"fids": [...]} form was unreachable over real HTTP)."""
        import io as _io
        import json as _json

        from geomesa_tpu.geometry import Point
        from geomesa_tpu.schema.sft import parse_spec
        from geomesa_tpu.store.datastore import DataStore
        from geomesa_tpu.web.app import GeoMesaApp

        ds = DataStore(backend="oracle")
        ds.create_schema(parse_spec("t", "name:String,*geom:Point"))
        ds.write("t", [{"name": "a", "geom": Point(0, 0)},
                       {"name": "b", "geom": Point(1, 1)}], fids=["x", "y"])
        app = GeoMesaApp(ds)
        raw = _json.dumps({"fids": ["x"]}).encode()
        environ = {
            "REQUEST_METHOD": "DELETE",
            "PATH_INFO": "/api/schemas/t/features",
            "QUERY_STRING": "",
            "CONTENT_LENGTH": str(len(raw)),
            "wsgi.input": _io.BytesIO(raw),
        }
        out = {}
        app(environ, lambda status, headers: out.update(status=status))
        assert out["status"].startswith("200")
        assert ds.query("t").count == 1
