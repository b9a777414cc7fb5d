"""The usage & workload plane (ISSUE 11, docs/observability.md § Usage
metering & workload replay): tenant-attributed metering accuracy, the
SpaceSaving heavy-hitter error bound and prometheus label-cardinality
cap, workload capture → deterministic replay round-trips with row-count
parity, tenant propagation across a 2-member federated view, cost-model
persistence, and the <2% always-on overhead bound with capture AND
metering enabled on the cached-jit select path.

Doubles as the CI usage/workload gate in scripts/lint.sh; also rides the
lock-order sanitizer subset (the usage meter and workload journal locks
are documented leaves — docs/concurrency.md).
"""

import json
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from geomesa_tpu import obs
from geomesa_tpu.geometry.types import Point
from geomesa_tpu.obs import flight as obs_flight
from geomesa_tpu.obs import replay as obs_replay
from geomesa_tpu.obs import usage as obs_usage
from geomesa_tpu.obs import workload as obs_workload
from geomesa_tpu.obs.flight import FlightRecorder
from geomesa_tpu.obs.slo import SloEngine
from geomesa_tpu.planning.planner import Query
from geomesa_tpu.resilience.policy import RetryPolicy
from geomesa_tpu.store.datastore import DataStore
from geomesa_tpu.store.merged import MergedDataStoreView
from geomesa_tpu.store.remote import RemoteDataStore
from geomesa_tpu.web.app import GeoMesaApp

T0 = 1_500_000_000_000
CQL = "BBOX(geom,-50,-40,50,40)"


@pytest.fixture(autouse=True)
def _iso():
    """Fresh meter, disabled journal, fresh flight recorder per test —
    the usage/workload singletons are process-global accumulators."""
    prev_meter = obs_usage.install(obs_usage.UsageMeter(k=8))
    prev_journal = obs_workload.install(None)
    prev_rec = obs_flight.install(
        FlightRecorder(dump_dir=None, min_dump_interval_s=0.0))
    yield
    obs_usage.install(prev_meter)
    obs_workload.install(prev_journal)
    obs_flight.install(prev_rec)


def _filled_store(seed=1, n=200, name="pts"):
    rng = np.random.default_rng(seed)
    ds = DataStore(backend="tpu")
    ds.create_schema(name, "name:String,dtg:Date,*geom:Point")
    ds.write(name, [
        {"name": f"n{i % 5}", "dtg": T0 + i * 1000,
         "geom": Point(float(rng.uniform(-170, 170)),
                       float(rng.uniform(-40, 40)))}
        for i in range(n)
    ], fids=[f"{seed}-{i}" for i in range(n)])
    ds.compact(name)
    return ds


# ---------------------------------------------------------------------------
# SpaceSaving sketch: error bound + monitoring guarantee
# ---------------------------------------------------------------------------

class TestSpaceSaving:
    def test_heavy_hitters_monitored_and_error_bounded(self):
        """The classic SpaceSaving guarantees on a skewed stream: every
        key with true weight > W/K is monitored, and each reported count
        lies in [true, true + error] with error <= W/K."""
        rng = np.random.default_rng(3)
        k = 8
        s = obs_usage.SpaceSaving(k)
        true: dict = {}
        # 4 heavy keys + a long tail of 200 singletons
        stream = (["h0"] * 400 + ["h1"] * 300 + ["h2"] * 200 + ["h3"] * 150
                  + [f"t{i}" for i in range(200)])
        rng.shuffle(stream)
        for key in stream:
            s.offer(key, 1.0)
            true[key] = true.get(key, 0) + 1
        W = s.total
        assert W == len(stream)
        top = {key: (c, err) for key, c, err in s.top()}
        for hk in ("h0", "h1", "h2", "h3"):
            assert hk in top, f"heavy hitter {hk} not monitored"
            c, err = top[hk]
            assert err <= W / k + 1e-9
            assert true[hk] <= c <= true[hk] + err + 1e-9
        # capacity is exact
        assert len(s.top()) == k

    def test_weighted_offers(self):
        s = obs_usage.SpaceSaving(4)
        s.offer(("acme", "pts", "z3:rows"), 120.0)
        s.offer(("globex", "pts", "z3:rows"), 5.0)
        s.offer(("acme", "pts", "z3:rows"), 80.0)
        top = s.top(1)
        assert top[0][0] == ("acme", "pts", "z3:rows")
        assert top[0][1] == pytest.approx(200.0)


# ---------------------------------------------------------------------------
# metering accuracy + bounded label cardinality
# ---------------------------------------------------------------------------

class TestMeterAccuracy:
    def test_hand_counted_totals(self):
        """Lifetime and window counters match exactly what was fed."""
        clk = [1000.0]
        m = obs_usage.UsageMeter(k=4, clock=lambda: clk[0])
        expect: dict = {}
        rng = np.random.default_rng(5)
        for i in range(60):
            t = f"t{i % 3}"
            rows = int(rng.integers(0, 50))
            wall = float(rng.uniform(0.5, 9.0))
            m.observe(t, "pts", "z3:rows", rows=rows, wall_ms=wall,
                      device_ms=wall / 2)
            e = expect.setdefault(t, [0, 0, 0, 0.0, 0.0])
            e[0] += 1
            e[1] += rows
            e[3] += wall
            e[4] += wall / 2
            clk[0] += 1.0
        m.note_bytes_out("t0", 12345)
        expect["t0"][2] += 12345
        snap = m.snapshot()
        assert snap["tenant_count"] == 3
        by_tenant = {t["tenant"]: t for t in snap["tenants"]}
        for t, e in expect.items():
            life = by_tenant[t]["lifetime"]
            assert life["queries"] == e[0]
            assert life["rows"] == e[1]
            assert life["bytes_out"] == e[2]
            assert life["wall_ms"] == pytest.approx(e[3])
            assert life["device_ms"] == pytest.approx(e[4])
            # everything happened within the 1h window
            w1h = by_tenant[t]["windows"]["1h"]
            assert w1h["queries"] == e[0]
            assert w1h["wall_ms"] == pytest.approx(e[3])

    def test_default_tenant_for_anonymous(self):
        m = obs_usage.UsageMeter(k=4)
        m.observe(None, "pts", "z3:rows", rows=1, wall_ms=1.0)
        snap = m.snapshot()
        assert snap["tenants"][0]["tenant"] == obs_usage.DEFAULT_TENANT

    def test_prometheus_cardinality_capped_and_reconciles(self):
        """More tenants than K: the scrape holds exactly K+1 label values
        per tenant metric, and the sum across all labels (top-K + other)
        equals the true total — nothing is lost in the rollup."""
        m = obs_usage.UsageMeter(k=4)
        for i in range(20):
            m.observe(f"t{i:02d}", "pts", "z3:rows", rows=2,
                      wall_ms=float(i + 1))
        lines = m.prometheus_lines()
        qlines = [ln for ln in lines
                  if ln.startswith("geomesa_tenant_queries_total{")]
        assert len(qlines) == m.k + 1
        assert sum(1 for ln in qlines if 'tenant="other"' in ln) == 1
        total = sum(float(ln.rsplit(" ", 1)[1]) for ln in qlines)
        assert total == 20
        # rows reconcile too
        rlines = [ln for ln in lines
                  if ln.startswith("geomesa_tenant_rows_total{")]
        assert sum(float(ln.rsplit(" ", 1)[1]) for ln in rlines) == 40

    def test_tenant_table_bounded_eviction_folds_into_other(self):
        m = obs_usage.UsageMeter(k=2, max_tenants=4)
        for i in range(10):
            m.observe(f"t{i}", "pts", "sig", rows=1, wall_ms=1.0)
        snap = m.snapshot()
        assert snap["tenant_count"] <= 4
        # nothing lost: tracked lifetimes + other rollup = 10 queries
        tracked = sum(t["lifetime"]["queries"] for t in snap["tenants"])
        assert tracked + snap["other_lifetime"]["queries"] == 10
        # the SLO engine is bounded by the same cap: evicted tenants
        # drop their trackers, so an unbounded tenant-id stream cannot
        # grow the engine or its exposition
        assert len(m.slo.trackers()) <= 4

    def test_slo_lines_use_distinct_metric_names(self):
        """The meter's per-tenant SLO gauges ride the scrape under their
        OWN names (geomesa_tenant_slo_*) — a second # TYPE header for
        geomesa_slo_burn_rate (the store engine's name) would make
        strict text-format consumers reject the whole payload."""
        m = obs_usage.UsageMeter(k=4)
        m.observe("acme", "pts", "sig", rows=1, wall_ms=1.0)
        lines = m.prometheus_lines()
        assert any(
            ln.startswith("geomesa_tenant_slo_burn_rate") for ln in lines)
        assert not any(
            ln.startswith(("geomesa_slo_burn_rate",
                           "# TYPE geomesa_slo_burn_rate"))
            for ln in lines)

    def test_client_controlled_tenant_escaped_in_exposition(self):
        """A tenant id with quotes/backslashes/newlines (the header is
        client-controlled) must not malform the scrape — every emitted
        line still parses as name{labels} value."""
        evil = 'evil"} 1\nback\\slash'
        m = obs_usage.UsageMeter(k=4)
        m.observe(evil, "pts", "sig", rows=1, wall_ms=1.0)

        def label_value(ln):
            """Escape-aware scan of the first label value (the
            exposition-spec parse a real consumer does)."""
            i = ln.index('="') + 2
            out = []
            while True:
                c = ln[i]
                if c == "\\":
                    out.append({"\\": "\\", '"': '"', "n": "\n"}[ln[i + 1]])
                    i += 2
                    continue
                if c == '"':
                    return "".join(out), ln[i + 1:]
                out.append(c)
                i += 1

        lines = [ln for ln in m.prometheus_lines()
                 if not ln.startswith("#")]
        assert lines
        for ln in lines:
            assert "\n" not in ln  # a raw newline would split the line
            value, rest = label_value(ln)
            # round-trip: the consumer recovers the exact tenant id, and
            # the remainder is a well-formed close + sample value
            assert value in (evil, obs_usage.UsageMeter.OTHER)
            assert rest.startswith("}") or rest.startswith(',window="')

    def test_tenant_slo_series_bounded_by_k(self):
        """The K+1 cardinality bound covers the geomesa_tenant_slo_*
        gauges too, not just the counters."""
        m = obs_usage.UsageMeter(k=4)
        for i in range(30):
            m.observe(f"t{i:02d}", "pts", "sig", rows=1,
                      wall_ms=float(i + 1))
        lines = m.prometheus_lines()
        tenants = set()
        for ln in lines:
            if ln.startswith("#"):
                continue
            start = ln.index('tenant="') + len('tenant="')
            tenants.add(ln[start:ln.index('"', start)])
        assert len(tenants) <= m.k + 1
        slo_lines = [ln for ln in lines
                     if ln.startswith("geomesa_tenant_slo_burn_rate{")]
        assert 0 < len(slo_lines) <= m.k * 2  # K tenants x 2 windows

    def test_timed_out_queries_meter_against_tenant(self):
        """A deadline-shed query never reaches _audit but must still
        burn the tenant's accounting — the heaviest tenants are exactly
        the ones that time out."""
        from geomesa_tpu.utils.timeouts import Deadline, QueryTimeout

        ds = _filled_store()
        with pytest.raises(QueryTimeout):
            ds.query("pts", Query(
                filter=CQL,
                hints={"tenant": "hog", "deadline": Deadline.after_ms(-1)}))
        snap = obs_usage.get().snapshot()
        by_tenant = {t["tenant"]: t for t in snap["tenants"]}
        assert by_tenant["hog"]["lifetime"]["queries"] == 1
        tk = obs_usage.get().slo.tracker("tenant.query", "hog")
        assert tk.burn_rate(300.0) > 0  # ok=False burned the budget

    def test_store_query_meters_tenant_rows(self):
        """End to end through DataStore._audit: per-tenant query and row
        totals match the hand-counted query results."""
        ds = _filled_store()
        counts = {}
        for i, tenant in enumerate(["acme", "globex", "acme"]):
            q = Query(filter=CQL, hints={"tenant": tenant})
            r = ds.query("pts", q)
            counts[tenant] = counts.get(tenant, 0) + r.count
        snap = obs_usage.get().snapshot()
        by_tenant = {t["tenant"]: t for t in snap["tenants"]}
        assert by_tenant["acme"]["lifetime"]["queries"] == 2
        assert by_tenant["globex"]["lifetime"]["queries"] == 1
        assert by_tenant["acme"]["lifetime"]["rows"] == counts["acme"]
        assert by_tenant["globex"]["lifetime"]["rows"] == counts["globex"]
        # the flight record carries the same tenant + a plan signature
        recs = obs_flight.get().records()
        assert recs[-1].tenant in ("acme", "globex")
        assert recs[-1].plan_signature
        # heavy hitters key by (tenant, type, signature)
        hh = snap["heavy_hitters"]
        assert any(h["tenant"] == "acme" and h["type"] == "pts"
                   for h in hh)

    def test_tenant_context_fallback(self):
        """No hint: the request-scoped context attributes the query (the
        web layer's binding); outside any context the default applies."""
        ds = _filled_store()
        with obs_usage.tenant_context("ctx-tenant"):
            ds.query("pts", CQL)
        ds.query("pts", CQL)
        snap = obs_usage.get().snapshot()
        by_tenant = {t["tenant"]: t for t in snap["tenants"]}
        assert by_tenant["ctx-tenant"]["lifetime"]["queries"] == 1
        assert by_tenant[obs_usage.DEFAULT_TENANT]["lifetime"]["queries"] == 1


# ---------------------------------------------------------------------------
# workload capture → deterministic replay
# ---------------------------------------------------------------------------

class TestCaptureReplay:
    def test_capture_replay_round_trip_row_parity(self, tmp_path):
        """The acceptance pin: a captured workload replayed closed-loop
        reproduces byte-identical row counts per query and emits a
        recorded-vs-replayed p50/p95 report per plan signature."""
        obs_workload.install(obs_workload.WorkloadJournal(str(tmp_path)))
        ds = _filled_store()
        filters = [CQL, "BBOX(geom,-170,-40,0,40)", "name = 'n1'", None]
        recorded_rows = []
        for i in range(12):
            q = Query(filter=filters[i % 4],
                      hints={"tenant": f"t{i % 2}"})
            recorded_rows.append(ds.query("pts", q).count)
        obs_workload.flush()

        events = obs_replay.load_events(str(tmp_path))
        assert len(events) == 12
        # deterministic order: seq strictly increasing, arrival sorted
        seqs = [e["seq"] for e in events]
        assert seqs == sorted(seqs) and len(set(seqs)) == 12
        arrivals = [e["ts_arrival"] for e in events]
        assert arrivals == sorted(arrivals)
        assert [e["rows"] for e in events] == recorded_rows
        assert all(e["plan_signature"] for e in events)
        assert all(e["tenant"] in ("t0", "t1") for e in events)

        doc = obs_replay.run(ds, str(tmp_path))
        assert doc["parity_ok"], doc["row_mismatches"]
        assert doc["events"] == 12
        assert doc["errors"] == 0
        for sig, s in doc["signatures"].items():
            assert s["parity"]
            assert s["recorded_ms"]["p50"] >= 0
            assert s["replayed_ms"]["p50"] > 0
            assert s["recorded_ms"]["p95"] >= s["recorded_ms"]["p50"]
        # the report loads as a bench --regress baseline shape
        assert all(
            "value" in c and c["unit"] == "ms/query"
            for c in doc["configs"].values()
        )

    def test_replay_tenant_filter_and_attribution(self, tmp_path):
        """--tenant replays one tenant's slice, and replayed queries
        re-attribute to the recorded tenant (metering + flight)."""
        obs_workload.install(obs_workload.WorkloadJournal(str(tmp_path)))
        ds = _filled_store()
        for i in range(8):
            ds.query("pts", Query(filter=CQL,
                                  hints={"tenant": f"t{i % 2}"}))
        obs_workload.flush()
        obs_usage.install(obs_usage.UsageMeter(k=8))  # reset the meter
        events = obs_replay.load_events(str(tmp_path), tenant="t1")
        assert len(events) == 4
        outcomes = obs_replay.replay(ds, events)
        assert all(o["parity"] for o in outcomes)
        snap = obs_usage.get().snapshot()
        by_tenant = {t["tenant"]: t for t in snap["tenants"]}
        assert by_tenant["t1"]["lifetime"]["queries"] == 4
        assert "t0" not in by_tenant

    def test_open_loop_pacing_honors_speed(self, tmp_path):
        """Open-loop replay sleeps recorded inter-arrival / speed."""
        events = [
            {"seq": 1, "ts_arrival": 100.0, "op": "query", "type": "pts",
             "filter": None, "latency_ms": 1.0, "rows": 0},
            {"seq": 2, "ts_arrival": 101.0, "op": "query", "type": "pts",
             "filter": None, "latency_ms": 1.0, "rows": 0},
            {"seq": 3, "ts_arrival": 103.0, "op": "query", "type": "pts",
             "filter": None, "latency_ms": 1.0, "rows": 0},
        ]

        class _Store:
            def query(self, name, q):
                class R:
                    count = 0
                return R()

        sleeps = []
        clock = [0.0]

        def fake_sleep(s):
            sleeps.append(s)
            clock[0] += s

        obs_replay.replay(_Store(), events, speed=2.0,
                          _sleep=fake_sleep, _clock=lambda: clock[0])
        # inter-arrivals 1s and 2s at speed 2 → due at 0.5s and 1.5s
        assert sleeps == [pytest.approx(0.5), pytest.approx(1.0)]

    def test_rotation_bounded_and_readable(self, tmp_path):
        j = obs_workload.WorkloadJournal(str(tmp_path), max_bytes=4096,
                                         max_files=3, flush_every=8)
        for i in range(600):
            j.append({"ts_arrival": float(i), "op": "query",
                      "type": "pts", "pad": "x" * 64})
        j.flush()
        files = j.files()
        assert 1 <= len(files) <= 3
        import os

        for f in files:
            assert os.path.getsize(f) <= 4096 + 100 * 8  # cap + one batch
        events = obs_workload.read_events(str(tmp_path))
        assert events, "rotation lost everything"
        seqs = [e["seq"] for e in events]
        assert seqs == sorted(seqs)
        # the newest events survive rotation
        assert seqs[-1] == 600

    def test_replay_does_not_recapture_into_the_journal(self, tmp_path):
        """Replaying while capture is enabled (the documented runbook
        state) must not append the replayed queries back onto the
        recording being read."""
        obs_workload.install(obs_workload.WorkloadJournal(str(tmp_path)))
        ds = _filled_store()
        for _ in range(3):
            ds.query("pts", Query(filter=CQL, hints={"tenant": "t0"}))
        obs_workload.flush()
        assert len(obs_workload.read_events(str(tmp_path))) == 3
        doc = obs_replay.run(ds, str(tmp_path))
        assert doc["parity_ok"]
        obs_workload.flush()
        assert len(obs_workload.read_events(str(tmp_path))) == 3
        # capture resumes after the replay (the journal is restored)
        ds.query("pts", Query(filter=CQL, hints={"tenant": "t0"}))
        obs_workload.flush()
        assert len(obs_workload.read_events(str(tmp_path))) == 4

    def test_aggregation_hinted_events_abstain_from_parity(self):
        """A density audit records grid mass, not row count — replaying
        it compares latency but must not manufacture a parity failure."""

        class _Store:
            def query(self, name, q):
                class R:
                    count = 0  # density results carry no row table
                return R()

        events = [{"seq": 1, "op": "query", "type": "pts", "filter": None,
                   "hints": {"density": {"width": 8, "height": 8}},
                   "latency_ms": 1.0, "rows": 57}]
        outcomes = obs_replay.replay(_Store(), events)
        assert outcomes[0]["parity"] is None
        doc = obs_replay.report(events, outcomes)
        assert doc["parity_ok"] is True
        assert not doc["row_mismatches"]

    def test_read_events_ignores_reader_side_rotation_config(self, tmp_path):
        """Reading globs EVERY rotated file on disk — a capture written
        with a larger max_files than the reader's env must not silently
        lose its oldest rotations."""
        j = obs_workload.WorkloadJournal(str(tmp_path), max_bytes=4096,
                                         max_files=8, flush_every=4)
        for i in range(400):
            j.append({"ts_arrival": float(i), "op": "query",
                      "type": "pts", "pad": "x" * 64})
        j.flush()
        assert len(j.files()) > 4  # writer really rotated past 4 files
        # reader with DEFAULT (smaller) config still sees everything
        events = obs_workload.read_events(str(tmp_path))
        assert {e["seq"] for e in events} == {
            e["seq"]
            for p in j.files()
            for e in obs_workload.read_events(p)
        }
        assert len(events) > 100

    def test_empty_replay_never_reads_as_pass(self):
        doc = obs_replay.report([], [], mode="closed-loop")
        assert doc["events"] == 0
        assert doc["parity_ok"] is False

    def test_remote_replay_skips_unforwardable_events(self):
        """--url mode: events carrying hints (beyond tenant) or auths
        can't round-trip over the RemoteDataStore surface — they skip
        with a reason instead of manufacturing parity failures."""

        class _Store:
            def query(self, name, q):
                class R:
                    count = 3
                return R()

        events = [
            {"seq": 1, "op": "query", "type": "pts", "filter": None,
             "hints": {"density": {"width": 8}}, "latency_ms": 1.0,
             "rows": 5},
            {"seq": 2, "op": "query", "type": "pts", "filter": None,
             "hints": None, "auths": ["s"], "latency_ms": 1.0, "rows": 5},
            {"seq": 3, "op": "query", "type": "pts", "filter": None,
             "hints": {"tenant": "acme"}, "latency_ms": 1.0, "rows": 3},
        ]
        outcomes = obs_replay.replay(_Store(), events, remote=True)
        assert "skipped" in outcomes[0] and "density" in outcomes[0]["skipped"]
        assert "skipped" in outcomes[1] and "auths" in outcomes[1]["skipped"]
        assert outcomes[2].get("parity") is True
        doc = obs_replay.report(events, outcomes)
        assert doc["events"] == 1 and doc["skipped"] == 2
        assert doc["parity_ok"] is True

    def test_unreplayable_hints_dropped(self, tmp_path):
        from geomesa_tpu.utils.timeouts import Deadline

        obs_workload.install(obs_workload.WorkloadJournal(str(tmp_path)))
        ds = _filled_store()
        q = Query(filter=CQL, hints={"tenant": "t0", "loose_bbox": True,
                                     "deadline": Deadline.after_ms(60000)})
        ds.query("pts", q)
        obs_workload.flush()
        (e,) = obs_replay.load_events(str(tmp_path))
        assert "deadline" not in (e["hints"] or {})
        assert e["hints"]["loose_bbox"] is True


# ---------------------------------------------------------------------------
# tenant propagation across a federated view (2 live HTTP members)
# ---------------------------------------------------------------------------

def _serve(app):
    from wsgiref.simple_server import WSGIRequestHandler, make_server

    class _Quiet(WSGIRequestHandler):
        def log_message(self, *a):
            pass

    httpd = make_server("127.0.0.1", 0, app, handler_class=_Quiet)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    return httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


class TestFederatedTenantPropagation:
    def test_tenant_propagates_to_member_flight_records(self):
        """A federated query under a tenant context: the outbound RPCs
        carry X-Geomesa-Tenant (resilience/http.py choke point), the
        member web layer re-binds it, and the member-side store audit
        records attribute to the ORIGINAL tenant."""
        servers = []
        try:
            members = []
            for seed in (1, 2):
                store = _filled_store(seed=seed)
                httpd, url = _serve(GeoMesaApp(store))
                servers.append(httpd)
                members.append(RemoteDataStore(
                    url, retry=RetryPolicy(max_attempts=1)))
            view = MergedDataStoreView(members)
            with obs_usage.tenant_context("fed-tenant"):
                res = view.query("pts", CQL)
            assert res.count > 0
            recs = obs_flight.get().records()
            store_recs = [r for r in recs if r.source == "store"]
            fed_recs = [r for r in recs if r.source == "federation"]
            # both member stores audited with the propagated tenant
            assert len(store_recs) >= 2
            assert all(r.tenant == "fed-tenant" for r in store_recs)
            assert len(fed_recs) == 1 and fed_recs[0].tenant == "fed-tenant"
            # metering: member legs + the view-level record all attribute
            snap = obs_usage.get().snapshot()
            by_tenant = {t["tenant"]: t for t in snap["tenants"]}
            assert by_tenant["fed-tenant"]["lifetime"]["queries"] >= 3
        finally:
            for s in servers:
                s.shutdown()

    def test_web_endpoints_tenant_header_and_obs_tenants(self):
        """X-Geomesa-Tenant on a plain query attributes server-side; the
        /api/obs/tenants and filtered /api/obs/flight surfaces serve it."""
        store = _filled_store()
        httpd, url = _serve(GeoMesaApp(store))
        try:
            req = urllib.request.Request(
                url + "/api/schemas/pts/query?cql="
                + urllib.parse.quote(CQL),
                headers={"X-Geomesa-Tenant": "hdr-tenant"})
            with urllib.request.urlopen(req, timeout=10) as r:
                json.load(r)
            # headerless traffic accrues bytes under the default tenant
            with urllib.request.urlopen(
                    url + "/api/schemas/pts/query?cql="
                    + urllib.parse.quote(CQL), timeout=10) as r:
                json.load(r)
            anon = obs_usage.get().snapshot()
            anon_row = {t["tenant"]: t for t in anon["tenants"]}[
                obs_usage.DEFAULT_TENANT]
            assert anon_row["lifetime"]["bytes_out"] > 0
            with urllib.request.urlopen(
                    url + "/api/obs/tenants", timeout=10) as r:
                doc = json.load(r)
            names = [t["tenant"] for t in doc["tenants"]]
            assert "hdr-tenant" in names
            by = {t["tenant"]: t for t in doc["tenants"]}
            assert by["hdr-tenant"]["lifetime"]["queries"] == 1
            assert by["hdr-tenant"]["lifetime"]["bytes_out"] > 0
            # flight filter: only this tenant's records come back
            with urllib.request.urlopen(
                    url + "/api/obs/flight?tenant=hdr-tenant",
                    timeout=10) as r:
                fl = json.load(r)
            assert fl["records"]
            assert all(rec["tenant"] == "hdr-tenant"
                       for rec in fl["records"])
            # prometheus: geomesa_tenant_* series present, K+1 bound holds
            with urllib.request.urlopen(
                    url + "/api/metrics?format=prometheus",
                    timeout=10) as r:
                text = r.read().decode()
            qlines = [ln for ln in text.splitlines()
                      if ln.startswith("geomesa_tenant_queries_total{")]
            assert any('tenant="hdr-tenant"' in ln for ln in qlines)
            assert len(qlines) <= obs_usage.get().k + 1
            # one # TYPE header per metric name across the WHOLE payload
            # (strict text-format consumers reject duplicates wholesale)
            type_lines = [ln for ln in text.splitlines()
                          if ln.startswith("# TYPE ")]
            names = [ln.split()[2] for ln in type_lines]
            assert len(names) == len(set(names)), sorted(
                n for n in names if names.count(n) > 1)
        finally:
            httpd.shutdown()


# ---------------------------------------------------------------------------
# cost-model persistence (sidecar under GEOMESA_TPU_WORKLOAD_DIR)
# ---------------------------------------------------------------------------

class TestCostPersistence:
    def test_snapshot_load_round_trip(self, tmp_path):
        from geomesa_tpu.obs import devmon, plantable

        path = str(tmp_path / "costs.json")
        ct = devmon.costs()
        for i in range(20):
            ct.observe_cost("pts", "z3:iv4:rows", wall_ms=2.0 + (i % 3),
                            rows=10)
        ct.tick("pts", "select_route")
        # one planned query: predicted-vs-actual lands in the calibration
        ct.record_query("pts", "z3:iv4:rows", 3.0, rows=10, planned=True)
        before = ct.predict("pts", "z3:iv4:rows")
        assert devmon.save_cost_snapshot(path) == path

        # "restart": a fresh table
        plantable.install()
        assert devmon.costs().predict("pts", "z3:iv4:rows") is None
        assert devmon.load_cost_snapshot(path)
        after = devmon.costs().predict("pts", "z3:iv4:rows")
        assert after is not None
        assert after["wall_ms_p50"] == pytest.approx(
            before["wall_ms_p50"])
        assert after["observations"] == before["observations"]
        # probe cadence survives: the tick counter continues, not restarts
        assert devmon.costs().tick("pts", "select_route") == 2
        cal = devmon.costs().calibration_report()
        assert cal["entry_count"] == 1
        assert cal["entries"][0]["last_actual_ms"] == pytest.approx(3.0)

    def test_load_never_regresses_a_richer_live_entry(self, tmp_path):
        """Merge by richness: a live table that learned PAST the
        snapshot keeps its entries on load (a second store open must not
        roll the planner back to stale p50s)."""
        from geomesa_tpu.obs import devmon

        path = str(tmp_path / "costs.json")
        ct = devmon.costs()
        for _ in range(5):
            ct.observe_cost("pts", "sig", wall_ms=100.0)
        devmon.save_cost_snapshot(path)
        # the live table learns on, past the snapshot, at a new level
        for _ in range(20):
            ct.observe_cost("pts", "sig", wall_ms=1.0)
        before = ct.predict("pts", "sig")
        assert devmon.load_cost_snapshot(path)
        after = ct.predict("pts", "sig")
        assert after["observations"] == before["observations"] == 25
        assert after["wall_ms_p50"] == before["wall_ms_p50"]

    def test_schema_delete_purges_persisted_entries(self, tmp_path,
                                                    monkeypatch):
        from geomesa_tpu.obs import devmon

        monkeypatch.setenv("GEOMESA_TPU_WORKLOAD_DIR", str(tmp_path))
        ds = _filled_store(name="doomed")
        ds.query("doomed", CQL)
        devmon.save_cost_snapshot()
        path = devmon.cost_sidecar_path()
        with open(path) as fh:
            doc = json.load(fh)
        assert any(e["type"] == "doomed" for e in doc["costs"]["entries"])
        ds.delete_schema("doomed")
        with open(path) as fh:
            doc = json.load(fh)
        assert not any(
            e["type"] == "doomed" for e in doc["costs"]["entries"])
        assert not any(t[0] == "doomed" for t in doc["costs"]["ticks"])

    def test_catalog_save_load_round_trips_costs(self, tmp_path,
                                                 monkeypatch):
        from geomesa_tpu.obs import devmon, plantable
        from geomesa_tpu.store import persistence

        monkeypatch.setenv("GEOMESA_TPU_WORKLOAD_DIR",
                           str(tmp_path / "wl"))
        ds = _filled_store()
        ds.query("pts", CQL)
        sig_rows = devmon.costs().snapshot()["entries"]
        assert sig_rows
        persistence.save(ds, str(tmp_path / "cat"))
        plantable.install()
        assert not devmon.costs().snapshot()["entries"]
        ds2 = persistence.load(str(tmp_path / "cat"))
        loaded = devmon.costs().snapshot()["entries"]
        assert {(r["type"], r["signature"]) for r in loaded} >= {
            (r["type"], r["signature"]) for r in sig_rows}
        assert ds2.stats_count("pts") == ds.stats_count("pts")


# ---------------------------------------------------------------------------
# overhead: the <2% bound with capture + metering ON
# ---------------------------------------------------------------------------

class TestOverhead:
    def test_capture_and_metering_under_2pct(self, tmp_path):
        """The lint.sh gate: flight record + SLO observation + usage
        metering + workload capture per query (everything _audit adds,
        untraced) must cost < 2% of the cached-jit select path's p50."""
        obs_workload.install(obs_workload.WorkloadJournal(str(tmp_path)))
        ds = _filled_store(n=400)
        sel = ("BBOX(geom,-50,-40,50,40) AND dtg DURING "
               "2017-07-14T02:40:00Z/2017-07-14T02:41:00Z")
        ds.query("pts", sel)  # compile + plan-cache warm
        lat = []
        for _ in range(15):
            t0 = time.perf_counter_ns()
            ds.query("pts", sel)
            lat.append(time.perf_counter_ns() - t0)
        p50_ns = float(np.percentile(lat, 50))

        eng = SloEngine()
        N = 5_000

        def per_call_ns():
            t0 = time.perf_counter_ns()
            for i in range(N):
                obs_flight.record(op="query", type_name="pts", plan=CQL,
                                  latency_ms=1.0, rows=10,
                                  breakdown={"plan": 0.1, "scan": 0.9},
                                  tenant="acme",
                                  plan_signature="z3:iv4:rows")
                eng.observe("store.query", ok=True, key="pts",
                            latency_ms=1.0)
                obs_usage.observe("acme", "pts", "z3:iv4:rows", rows=10,
                                  wall_ms=1.0)
                obs_workload.record(
                    ts=1.0, op="query", type_name="pts", source="store",
                    filter_text=CQL, hints=None, tenant="acme",
                    auths=None, plan_signature="z3:iv4:rows",
                    predicted_ms=None, latency_ms=1.0, rows=10)
            return (time.perf_counter_ns() - t0) / N

        cost = min(per_call_ns() for _ in range(3))
        assert cost < 0.02 * p50_ns, (
            f"capture+metering cost {cost:.0f} ns "
            f">= 2% of query p50 {p50_ns:.0f} ns")

    def test_steady_select_zero_recompiles_with_capture_on(self, tmp_path):
        """The acceptance pin's second half: capture + metering add no
        jit traffic — the steady cached-select path stays at zero new
        compile signatures and zero recompiles (jaxmon census)."""
        from geomesa_tpu.obs import jaxmon

        obs_workload.install(obs_workload.WorkloadJournal(str(tmp_path)))
        ds = _filled_store(n=400)
        sel = ("BBOX(geom,-50,-40,50,40) AND dtg DURING "
               "2017-07-14T02:40:00Z/2017-07-14T02:41:00Z")
        for _ in range(3):
            ds.query("pts", Query(filter=sel, hints={"tenant": "acme"}))
        before = jaxmon.jit_report()
        for _ in range(10):
            ds.query("pts", Query(filter=sel, hints={"tenant": "acme"}))
        after = jaxmon.jit_report()
        assert (after.get("recompiles", 0)
                - before.get("recompiles", 0)) == 0
        assert set(after["steps"]) == set(before["steps"])


# ---------------------------------------------------------------------------
# device-ms reconciliation (tenant series vs devmon attribution)
# ---------------------------------------------------------------------------

class TestDeviceMsReconciliation:
    def test_tenant_device_ms_matches_devprof_attribution(self):
        """Every query profiled (devprof hint): the meter's per-tenant
        device-ms total equals the sum of the flight records' device
        attributions — the two surfaces reconcile exactly when sampling
        is 100% (within sampling error otherwise)."""
        ds = _filled_store()
        for _ in range(4):
            ds.query("pts", Query(filter=CQL,
                                  hints={"tenant": "dev-t",
                                         "devprof": True}))
        recs = [r for r in obs_flight.get().records()
                if r.tenant == "dev-t"]
        dev_total = sum(
            r.device.get("device_compute", 0.0)
            + r.device.get("dispatch", 0.0)
            + r.device.get("compile", 0.0)
            for r in recs
        )
        snap = obs_usage.get().snapshot()
        by_tenant = {t["tenant"]: t for t in snap["tenants"]}
        metered = by_tenant["dev-t"]["lifetime"]["device_ms"]
        assert metered == pytest.approx(dev_total, rel=1e-6)
        assert metered > 0
