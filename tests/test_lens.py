"""query-lens tests (ISSUE 17): the retained per-(type, plan-signature)
profiling plane, the host-roundtrip ledger + fusion report, trace
exemplars, the recompile census, and the regression sentinel.

Acceptance pins (see docs/observability.md):

- staged select attributes >= 2 dispatches + >= 1 host sync per query,
  the cached fused path exactly 1 dispatch (the ROADMAP item-1 evidence);
- the p99 exemplar resolves end-to-end: bucket -> trace_id -> span tree;
- one batched coalesced dispatch charges ledger counts to EVERY member
  signature, exemplars resolve to disjoint submitter trees;
- sentinel red/green: a 2x latency shift raises A_REGRESSION within one
  evaluation window, steady traffic raises nothing across 10 windows;
- the always-on plan-table write stays < 2% of the cached-jit select p50
  (the scripts/lint.sh gate);
- Prometheus lens exposition is a TRUE histogram family (cumulative
  ``le`` buckets, ``+Inf`` == ``_count``) — checked by parsing, not eye.
"""

import io
import json
import math
import re
import threading
import time

import numpy as np
import pytest

from geomesa_tpu import obs
from geomesa_tpu.geometry.types import Point
from geomesa_tpu.obs import flight as obs_flight
from geomesa_tpu.obs import jaxmon
from geomesa_tpu.obs import ledger as ledger_mod
from geomesa_tpu.obs import lens as lens_mod
from geomesa_tpu.obs import plantable
from geomesa_tpu.obs import trace as obs_trace
from geomesa_tpu.obs.flight import A_RECOMPILE, A_REGRESSION, FlightRecorder
from geomesa_tpu.obs.lens import (
    BUCKET_EDGES_MS,
    EXEMPLARS_PER_BUCKET,
    RegressionSentinel,
    _quantile,
)
from geomesa_tpu.obs.ledger import QueryLedger
from geomesa_tpu.obs.plantable import PlanTable
from geomesa_tpu.planning.planner import Query
from geomesa_tpu.serving.coalesce import Coalescer
from geomesa_tpu.store import backends
from geomesa_tpu.store.datastore import DataStore
from geomesa_tpu.web.app import GeoMesaApp

T0 = 1_500_000_000_000  # 2017-07-14T02:40:00Z
SPEC = "name:String,dtg:Date,*geom:Point"
CQL = "BBOX(geom,-50,-40,50,40)"
# same z2 index group as CQL but a different interval-count bucket —
# a DISTINCT plan signature served by the SAME batched dispatch
CQL_SMALL = "BBOX(geom,-12,-9,13,11)"


@pytest.fixture(autouse=True)
def _iso():
    """Per-test isolation: tracing off + drained buffers, a fresh flight
    recorder (dumps off), fresh plan-table / sentinel singletons, and a
    reset recompile census."""
    obs.disable()
    obs.drain()
    prev_rec = obs_flight.install(
        FlightRecorder(dump_dir=None, min_dump_interval_s=0.0))
    prev_tbl = plantable.install()
    prev_sent = lens_mod.install_sentinel(RegressionSentinel())
    jaxmon._census_reset()
    listeners = list(obs_trace._root_listeners)
    yield
    obs_trace._root_listeners[:] = listeners
    lens_mod.sentinel().close()
    lens_mod.install_sentinel(prev_sent)
    plantable.install(prev_tbl)
    obs_flight.install(prev_rec)
    jaxmon._census_reset()
    obs.disable()
    obs.drain()


def _make_store(n=300, seed=5, name="pts", compacted=True):
    ds = DataStore(backend="tpu")
    ds.create_schema(name, SPEC)
    rng = np.random.default_rng(seed)
    ds.write(name, [
        {"name": f"n{i % 3}", "dtg": T0 + i * 1000,
         "geom": Point(float(rng.uniform(-170, 170)),
                       float(rng.uniform(-60, 60)))}
        for i in range(n)
    ], fids=[f"f{i}" for i in range(n)])
    if compacted:
        ds.compact(name)
    return ds


@pytest.fixture(scope="module")
def store():
    """Module-shared compacted store: the mesh steps compile once and
    every test below runs against the cached-jit path."""
    return _make_store()


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


def _serve(app):
    from wsgiref.simple_server import WSGIRequestHandler, make_server

    class _Quiet(WSGIRequestHandler):
        def log_message(self, *a):
            pass

    httpd = make_server("127.0.0.1", 0, app, handler_class=_Quiet)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    port = httpd.server_address[1]
    return httpd, f"http://127.0.0.1:{port}"


def _ledger(dispatches):
    """A query ledger holding ``dispatches`` back-to-back dispatches."""
    ql = QueryLedger()
    for i in range(dispatches):
        ql.note_dispatch(float(i), float(i) + 0.001)
    return ql


def _observe(tbl, type_name, sig, latency_ms, rows=0, dispatches=0,
             trace_id="", now=None):
    """One unplanned query's plan-table write: a latency point (plus a
    rollup charge when it dispatched)."""
    tbl.record_query(type_name, sig, latency_ms, rows=rows,
                     trace_id=trace_id, now=now,
                     ledger=_ledger(dispatches) if dispatches else None)


# ---------------------------------------------------------------------------
# Latency history core: buckets, quantiles, retention, exemplars
# ---------------------------------------------------------------------------

class TestLensCore:
    def test_window_quantiles_from_merged_bins(self):
        lens = PlanTable(bucket_s=10.0)
        t = 10_000.0
        for _ in range(80):
            _observe(lens, "pts", "z2:rows", latency_ms=3.0, rows=5,
                         dispatches=1, now=t)
        for _ in range(20):
            _observe(lens, "pts", "z2:rows", latency_ms=40.0, now=t)
        w = lens.window_stats("pts", "z2:rows", t - 60, t + 1)
        assert w["count"] == 100
        assert w["rows"] == 400
        assert w["dispatches"] == 80
        assert w["max_ms"] == 40.0
        # 3.0 ms lands in the (2, 5] bin, 40 ms in (25, 50]: the p50
        # interpolates inside (2, 5], the p95 inside (25, 50]
        assert 2.0 < w["p50_ms"] <= 5.0
        assert 25.0 < w["p95_ms"] <= 50.0
        assert w["p95_ms"] == pytest.approx(43.75)
        assert w["p99_ms"] == pytest.approx(48.75)
        assert w["mean_ms"] == pytest.approx((80 * 3.0 + 20 * 40.0) / 100)

    def test_ring_retention_is_bounded(self):
        lens = PlanTable(bucket_s=1.0, ring=5)
        for i in range(10):
            _observe(lens, "pts", "s", latency_ms=1.0, now=100.0 + i)
        w = lens.window_stats("pts", "s", 0.0, 1e9)
        assert w["count"] == 5  # only the newest 5 buckets survive
        # and they are the NEWEST five
        w_old = lens.window_stats("pts", "s", 100.0, 105.0)
        assert w_old["count"] == 0

    def test_exemplar_replace_min_keeps_the_tail(self):
        lens = PlanTable(bucket_s=10.0)
        t = 10_000.0
        for i in range(10):
            _observe(lens, "pts", "s", latency_ms=float(i + 1),
                         trace_id=f"tr{i + 1}", now=t)
        ex = lens.exemplars("pts", "s")
        assert len(ex) == EXEMPLARS_PER_BUCKET
        # the bucket keeps its slowest traced queries, slowest first
        assert [e["trace_id"] for e in ex] == ["tr10", "tr9", "tr8", "tr7"]
        assert ex[0]["latency_ms"] == 10.0

    def test_untraced_observations_take_no_exemplar_slot(self):
        lens = PlanTable()
        t = 10_000.0
        _observe(lens, "pts", "s", latency_ms=500.0, now=t)  # no trace
        _observe(lens, "pts", "s", latency_ms=1.0, trace_id="tr", now=t)
        ex = lens.exemplars("pts", "s")
        assert [e["trace_id"] for e in ex] == ["tr"]

    def test_snapshot_shape(self):
        lens = PlanTable(bucket_s=10.0, clock=lambda: 10_000.0)
        for i in range(5):
            _observe(lens, "pts", "s", latency_ms=2.0, trace_id=f"t{i}",
                         now=10_000.0)
        snap = lens.lens_snapshot(window_s=300.0)
        assert snap["series"] == 1
        assert snap["observe_count"] == 5
        (e,) = snap["entries"]
        assert e["type"] == "pts" and e["signature"] == "s"
        assert e["window"]["count"] == 5
        assert e["buckets"][0]["count"] == 5
        assert len(e["exemplars"]) == EXEMPLARS_PER_BUCKET


class TestQuantileMath:
    def test_empty_is_zero(self):
        assert _quantile([0] * (len(BUCKET_EDGES_MS) + 1), 0, 0.5) == 0.0

    def test_overflow_bin_reports_top_edge(self):
        lens = PlanTable()
        _observe(lens, "t", "s", latency_ms=99_999.0, now=1.0)
        w = lens.window_stats("t", "s", 0.0, 10.0)
        assert w["p50_ms"] == BUCKET_EDGES_MS[-1]
        assert w["max_ms"] == 99_999.0

    def test_edge_value_is_le_inclusive(self):
        # latency exactly on an edge counts in that edge's le bucket
        lens = PlanTable()
        for _ in range(10):
            _observe(lens, "t", "s", latency_ms=5.0, now=1.0)
        w = lens.window_stats("t", "s", 0.0, 10.0)
        assert 2.0 < w["p50_ms"] <= 5.0


# ---------------------------------------------------------------------------
# Prometheus histogram conformance — parsed, not eyeballed (satellite)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$')
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def _parse_prometheus(text):
    """Minimal text-exposition parser: family types + samples with label
    dicts. Raises on a malformed line — the conformance check."""
    types, samples = {}, []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _hash, _t, name, kind = line.split()
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed exposition line: {line!r}"
        name, raw_labels, raw_val = m.groups()
        labels = dict(_LABEL_RE.findall(raw_labels or ""))
        samples.append((name, labels, float(raw_val)))
    return types, samples


class TestPrometheusHistogram:
    def _lens_with_traffic(self):
        lens = PlanTable(bucket_s=10.0)
        t = 10_000.0
        for ms in [0.3, 0.9, 3.0, 3.0, 7.0, 40.0, 400.0]:
            _observe(lens, "pts", "z2:rows", latency_ms=ms, dispatches=1,
                         now=t)
        for ms in [1.5, 2.5]:
            _observe(lens, "pts", "scan:rows", latency_ms=ms, now=t + 20)
        return lens

    def test_true_histogram_family(self):
        lens = self._lens_with_traffic()
        types, samples = _parse_prometheus(lens.prometheus_text())
        assert types["geomesa_lens_latency_ms"] == "histogram"
        assert types["geomesa_lens_dispatches_total"] == "counter"
        # group by full series label set
        series = {}
        for name, labels, val in samples:
            key = (labels.get("type"), labels.get("signature"))
            series.setdefault(key, {})[
                (name, labels.get("le"))] = val
        for key in [("pts", "z2:rows"), ("pts", "scan:rows")]:
            s = series[key]
            buckets = [(float("inf") if le == "+Inf" else float(le), v)
                       for (name, le), v in s.items()
                       if name == "geomesa_lens_latency_ms_bucket"]
            buckets.sort()
            # every fixed edge + the +Inf bucket is present
            assert len(buckets) == len(BUCKET_EDGES_MS) + 1
            assert [b[0] for b in buckets][:-1] == list(BUCKET_EDGES_MS)
            assert math.isinf(buckets[-1][0])
            # CUMULATIVE and monotone non-decreasing
            vals = [v for _, v in buckets]
            assert vals == sorted(vals)
            # +Inf bucket == _count
            count = s[("geomesa_lens_latency_ms_count", None)]
            assert vals[-1] == count
            assert ("geomesa_lens_latency_ms_sum", None) in s
        z2 = series[("pts", "z2:rows")]
        assert z2[("geomesa_lens_latency_ms_count", None)] == 7
        assert z2[("geomesa_lens_latency_ms_sum", None)] == pytest.approx(
            0.3 + 0.9 + 3.0 + 3.0 + 7.0 + 40.0 + 400.0)
        assert z2[("geomesa_lens_dispatches_total", None)] == 7

    def test_le_labels_render_integral_edges_bare(self):
        lens = self._lens_with_traffic()
        text = lens.prometheus_text()
        assert 'le="1"' in text and 'le="0.25"' in text
        assert 'le="1.0"' not in text
        assert 'le="+Inf"' in text

    def test_empty_lens_emits_nothing(self):
        assert PlanTable().prometheus_text() == ""

    def test_sentinel_exposition_parses(self):
        s = RegressionSentinel()
        types, samples = _parse_prometheus(s.prometheus_text())
        assert types["geomesa_lens_regression"] == "gauge"
        assert types["geomesa_lens_regressions_total"] == "counter"
        assert ("geomesa_lens_regressions_total", {}, 0.0) in samples


# ---------------------------------------------------------------------------
# QueryLedger and the plan-table rollup: host-roundtrip accounting
# ---------------------------------------------------------------------------

class TestQueryLedger:
    def test_host_gap_between_device_activities(self):
        ql = QueryLedger()
        ql.note_dispatch(1.00, 1.01, compiled=True, h2d_bytes=100)
        # 20 ms of host choreography before the sync begins
        ql.note_sync(1.03, 1.04)
        # 10 ms more before the next dispatch
        ql.note_dispatch(1.05, 1.06, d2h_bytes=50)
        s = ql.snapshot()
        assert s["dispatches"] == 2 and s["compiles"] == 1
        assert s["syncs"] == 1
        assert s["dispatch_ms"] == pytest.approx(20.0, abs=1e-6)
        assert s["sync_ms"] == pytest.approx(10.0, abs=1e-6)
        assert s["host_gap_ms"] == pytest.approx(30.0, abs=1e-6)
        assert s["h2d_bytes"] == 100 and s["d2h_bytes"] == 50

    def test_first_activity_opens_no_gap(self):
        ql = QueryLedger()
        ql.note_dispatch(5.0, 5.01)
        assert ql.snapshot()["host_gap_ms"] == 0.0

    def test_roundtrip_nesting_gets_fresh_inner_ledger(self):
        with ledger_mod.roundtrip() as outer:
            ledger_mod.note_dispatch(1.0, 1.01)
            with ledger_mod.roundtrip() as inner:
                assert ledger_mod.current() is inner
                ledger_mod.note_dispatch(2.0, 2.01)
                ledger_mod.note_dispatch(3.0, 3.01)
            assert ledger_mod.current() is outer
        assert ledger_mod.current() is None
        assert outer.dispatches == 1  # not double-charged with the inner 2
        assert inner.dispatches == 2

    def test_materialize_counts_sync_on_path_only(self):
        out = ledger_mod.materialize([1, 2, 3])  # off path: bare asarray
        assert isinstance(out, np.ndarray)
        with ledger_mod.roundtrip() as ql:
            out = ledger_mod.materialize([4, 5])
            assert list(out) == [4, 5]
        assert ql.syncs == 1


class TestLedgerTable:
    def _ql(self, dispatches, gap_ms):
        ql = QueryLedger()
        t = 1.0
        for _ in range(dispatches):
            ql.note_dispatch(t, t + 0.001)
            t += 0.001 + gap_ms / 1000.0
        return ql

    def test_fusion_report_ranks_by_host_share(self):
        tbl = PlanTable()
        # staged shape: 3 dispatches with big host gaps between them
        tbl.record_query("pts", "staged", 13.0, ledger=self._ql(3, gap_ms=5.0))
        # fused shape: one dispatch, no choreography
        tbl.record_query("pts", "fused", 1.0, ledger=self._ql(1, gap_ms=0.0))
        rep = tbl.fusion_report()
        assert [r["signature"] for r in rep] == ["staged", "fused"]
        staged, fused = rep
        assert staged["host_share"] > fused["host_share"]
        assert staged["dispatches_per_query"] == 3.0
        assert staged["host_gap_ms"] == pytest.approx(10.0, abs=1e-6)
        assert fused["host_share"] == 0.0
        assert 0.0 <= staged["host_share"] <= 1.0

    def test_charges_accumulate_per_signature(self):
        tbl = PlanTable()
        for _ in range(4):
            tbl.record_query("pts", "s", 4.0, ledger=self._ql(2, gap_ms=1.0))
        (row,) = tbl.fusion_report()
        assert row["queries"] == 4
        assert row["dispatches_per_query"] == 2.0
        assert row["wall_ms"] == pytest.approx(16.0)


# ---------------------------------------------------------------------------
# Store integration: staged vs fused dispatch attribution (acceptance)
# ---------------------------------------------------------------------------

class TestStoreAttribution:
    def test_fused_path_charges_one_dispatch_per_query(self, store):
        store.query("pts", CQL)  # compile + plan-cache warm
        plantable.install()
        for _ in range(3):
            store.query("pts", CQL)
        (row,) = ledger_mod.table().fusion_report()
        assert row["queries"] == 3
        # the cached one-pass select is ONE device dispatch per query
        assert row["dispatches_per_query"] == 1.0
        assert row["syncs_per_query"] >= 1.0  # the result materialization
        assert row["compiles"] == 0  # warm: no compile charged
        assert row["d2h_bytes"] > 0
        snap = plantable.table().lens_snapshot()
        (e,) = snap["entries"]
        assert e["window"]["dispatches"] == 3

    def test_staged_path_charges_multi_dispatch(self, store, monkeypatch):
        # force the staged two-phase select (count pass -> host sizing ->
        # gather pass) by zeroing the one-pass slot budget
        monkeypatch.setattr(backends, "_ONE_PASS_MAX_SLOTS", 0)
        store.query("pts", CQL)  # compile the staged steps
        plantable.install()
        for _ in range(3):
            store.query("pts", CQL)
        (row,) = ledger_mod.table().fusion_report()
        assert row["queries"] == 3
        # the acceptance pin: staged execution is >= 2 dispatches with a
        # host sync point between them — the fusion opportunity the
        # report exists to surface
        assert row["dispatches_per_query"] >= 2.0
        assert row["syncs_per_query"] >= 1.0
        assert row["host_gap_ms"] > 0.0
        assert row["host_share"] > 0.0

    def test_purge_reaches_lens_and_ledger(self):
        ds = _make_store(n=120, seed=7, name="tmp")
        ds.query("tmp", "BBOX(geom,-90,-50,90,50)")
        assert any(k[0] == "tmp" for k in plantable.table().series_keys())
        assert any(r["type"] == "tmp"
                   for r in ledger_mod.table().fusion_report())
        ds.delete_schema("tmp")
        assert not any(k[0] == "tmp" for k in plantable.table().series_keys())
        assert not any(r["type"] == "tmp"
                       for r in ledger_mod.table().fusion_report())


# ---------------------------------------------------------------------------
# Trace exemplars end-to-end: bucket -> trace_id -> span tree (acceptance)
# ---------------------------------------------------------------------------

def _find_tree(roots, trace_id):
    return next((r for r in roots if r.trace_id == trace_id), None)


def _span_names(span, acc=None):
    acc = [] if acc is None else acc
    acc.append(span.name)
    for c in span.children:
        _span_names(c, acc)
    return acc


class TestExemplarResolution:
    def test_p99_exemplar_resolves_to_span_tree(self, store, monkeypatch):
        store.query("pts", CQL)  # warm
        plantable.install()
        obs.enable(jax_telemetry=False)
        try:
            # drive ONE deliberately slow query: the backend stalls
            # inside the timed scan window, so this query IS the tail
            orig = store.backend.select

            def slow_select(*a, **k):
                time.sleep(0.08)
                return orig(*a, **k)

            monkeypatch.setattr(store.backend, "select", slow_select)
            store.query("pts", CQL)
            monkeypatch.setattr(store.backend, "select", orig)
            for _ in range(8):
                store.query("pts", CQL)
        finally:
            obs.disable()
        (key,) = plantable.table().series_keys()
        ex = plantable.table().exemplars(*key)
        assert ex, "traced queries must leave exemplars"
        top = ex[0]  # slowest-first: the p99+ sample
        assert top["latency_ms"] >= 80.0
        assert top["latency_ms"] == max(e["latency_ms"] for e in ex)
        # ... and its trace_id resolves to the retained span tree
        tree = _find_tree(obs.recent(), top["trace_id"])
        assert tree is not None, "exemplar trace not in trace.recent()"
        names = _span_names(tree)
        assert "query" in names  # the store's per-query root stage
        # every exemplar resolves, not just the top one
        for e in ex:
            assert _find_tree(obs.recent(), e["trace_id"]) is not None


# ---------------------------------------------------------------------------
# Coalesced batch attribution (acceptance + satellite)
# ---------------------------------------------------------------------------

class TestCoalescedAttribution:
    def test_one_batched_dispatch_charges_every_signature(self, store):
        # warm both plan shapes + the batched steps so the coalesced
        # dispatch below runs cached
        store.query("pts", CQL)
        store.query("pts", CQL_SMALL)
        store.select_many("pts", [Query(filter=CQL),
                                  Query(filter=CQL_SMALL)])
        sig_a, sig_b = (r.plan_signature
                        for r in obs_flight.get().records()[-2:])
        assert sig_a != sig_b, "test needs two distinct plan signatures"

        plantable.install()
        obs.enable(jax_telemetry=False)

        class SlowFirst:
            """First dispatch stalls so the two submitters gather into
            ONE batch behind it (backpressure batching, deterministic —
            the test_serving idiom)."""

            def __init__(self, inner):
                self._inner = inner
                self.n = 0

            def query(self, *a, **k):
                self.n += 1
                if self.n == 1:
                    time.sleep(0.25)
                return self._inner.query(*a, **k)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        co = Coalescer(SlowFirst(store), window_s=0.5)
        roots = {}

        def submit(tag, cql):
            with obs.collect(tag) as root:
                co.submit("pts", "select", Query(filter=cql))
            roots[tag] = root

        try:
            opener = threading.Thread(
                target=co.submit,
                args=("pts", "select", Query(filter="BBOX(geom,-5,-5,5,5)")))
            opener.start()
            time.sleep(0.05)  # opener's slow dispatch now holds the key
            subs = [threading.Thread(target=submit, args=("a", CQL)),
                    threading.Thread(target=submit, args=("b", CQL_SMALL))]
            for t in subs:
                t.start()
            for t in subs:
                t.join()
            opener.join()
        finally:
            obs.disable()
        assert co.max_width == 2  # ONE batched dispatch served both

        rows = {r["signature"]: r for r in ledger_mod.table().fusion_report()}
        assert sig_a in rows and sig_b in rows
        # every member signature sees the SHARED batch ledger: identical
        # dispatch counts, >= 1 (the batch ran at least one device pass)
        assert rows[sig_a]["queries"] == 1 and rows[sig_b]["queries"] == 1
        assert rows[sig_a]["dispatches_per_query"] >= 1.0
        assert (rows[sig_a]["dispatches_per_query"]
                == rows[sig_b]["dispatches_per_query"])

        # exemplars resolve to DISJOINT submitter trees, not the batch
        # leader's: each signature's exemplar carries ITS submitter's
        # stamped trace_id
        (ex_a,) = plantable.table().exemplars("pts", sig_a)
        (ex_b,) = plantable.table().exemplars("pts", sig_b)
        assert ex_a["trace_id"] == roots["a"].trace_id
        assert ex_b["trace_id"] == roots["b"].trace_id
        assert ex_a["trace_id"] != ex_b["trace_id"]


# ---------------------------------------------------------------------------
# Regression sentinel red/green (acceptance)
# ---------------------------------------------------------------------------

def _feed(lens, sig, ms, t_from, t_to, n, type_name="pts"):
    for i in range(n):
        _observe(lens, type_name, sig, latency_ms=ms,
                     now=t_from + (t_to - t_from) * i / max(n - 1, 1))


class TestRegressionSentinel:
    def _pair(self, **kw):
        lens = PlanTable(bucket_s=10.0)
        kw.setdefault("live_window_s", 60.0)
        kw.setdefault("ref_window_s", 600.0)
        sent = RegressionSentinel(table=lens, **kw)
        return lens, sent

    def test_2x_shift_raises_within_one_window(self):
        lens, sent = self._pair()
        t = 100_000.0
        # reference: steady 4 ms; live: the regression — 2x+ slower
        _feed(lens, "z2:rows", 4.0, t - 650, t - 70, 40)
        _feed(lens, "z2:rows", 40.0, t - 55, t - 5, 20)
        raised = sent.evaluate_once(now=t)
        assert len(raised) == 1
        (a,) = raised
        assert a["cause"] == "p50_vs_ref"
        assert a["signature"] == "z2:rows"
        assert a["factor"] > 2.0
        # ... and the alarm reached the flight recorder as A_REGRESSION
        recs = [r for r in obs_flight.get().records()
                if A_REGRESSION in r.anomalies]
        assert len(recs) == 1
        assert recs[0].source == "sentinel"
        assert recs[0].plan_signature == "z2:rows"
        # the gauge latches
        assert "geomesa_lens_regression{" in sent.prometheus_text()

    def test_steady_traffic_raises_nothing_across_10_windows(self):
        lens, sent = self._pair(interval_s=30.0)
        t0 = 100_000.0
        _feed(lens, "z2:rows", 4.0, t0 - 650, t0, 200)
        for k in range(10):
            t = t0 + 30.0 * k
            _feed(lens, "z2:rows", 4.0, t - 25, t, 20)
            assert sent.evaluate_once(now=t) == []
        assert sent.snapshot()["alarms"] == []
        assert sent.eval_count == 10
        assert not [r for r in obs_flight.get().records()
                    if A_REGRESSION in r.anomalies]

    def test_alarm_latches_once_per_episode_then_recovers(self):
        lens, sent = self._pair()
        t = 100_000.0
        _feed(lens, "s", 4.0, t - 650, t - 70, 40)
        _feed(lens, "s", 40.0, t - 55, t - 5, 20)
        assert len(sent.evaluate_once(now=t)) == 1
        assert sent.evaluate_once(now=t) == []  # latched, no re-raise
        assert len(sent.snapshot()["alarms"]) == 1
        # recovery: fast live traffic again -> alarm clears
        t2 = t + 120.0
        _feed(lens, "s", 4.0, t2 - 55, t2 - 5, 20)
        assert sent.evaluate_once(now=t2) == []
        assert sent.snapshot()["alarms"] == []
        assert sent.regressions_total == 1

    def test_baseline_regression_without_reference_traffic(self):
        lens, sent = self._pair()
        assert sent.load_baselines({"pts:s": 4.0}) == 1
        t = 100_000.0
        _feed(lens, "s", 40.0, t - 55, t - 5, 20)  # no ref window traffic
        (a,) = sent.evaluate_once(now=t)
        assert a["cause"] == "p50_vs_baseline"

    def test_baselines_bench_sidecar_shape(self):
        _lens, sent = self._pair()
        n = sent.load_baselines({"entries": [
            {"type": "pts", "signature": "a", "p50_ms": 2.0},
            {"type": "pts", "signature": "b", "p50_ms": 3.0},
        ]})
        assert n == 2
        assert sent.snapshot()["baselines"] == 2

    def test_thin_traffic_holds_judgment(self):
        lens, sent = self._pair(min_live=16)
        t = 100_000.0
        _feed(lens, "s", 4.0, t - 650, t - 70, 40)
        _feed(lens, "s", 40.0, t - 55, t - 5, 8)  # below min_live
        assert sent.evaluate_once(now=t) == []

    def test_sustain_requires_consecutive_windows(self):
        lens, sent = self._pair(sustain=2)
        t = 100_000.0
        _feed(lens, "s", 4.0, t - 650, t - 70, 40)
        _feed(lens, "s", 40.0, t - 55, t - 5, 20)
        assert sent.evaluate_once(now=t) == []  # streak 1 of 2
        assert len(sent.evaluate_once(now=t)) == 1  # streak 2: fires

    def test_worker_runs_and_stops(self):
        lens, sent = self._pair(interval_s=0.01)
        sent.start()
        try:
            deadline = time.time() + 2.0
            while sent.eval_count == 0 and time.time() < deadline:
                time.sleep(0.01)
        finally:
            sent.close()
        assert sent.eval_count >= 1

    def test_evaluation_runs_in_audit_shadow(self):
        # sentinel reads must not feed the lens/cost planes: an observe
        # made DURING evaluation would be a feedback loop. Pin the shadow
        # flag is set inside the evaluation.
        from geomesa_tpu.obs import audit as obs_audit

        lens, sent = self._pair()
        seen = {}
        orig = lens.series_keys

        def probe():
            seen["shadow"] = obs_audit.in_shadow()
            return orig()

        lens.series_keys = probe
        sent.evaluate_once(now=100.0)
        assert seen["shadow"] is True


# ---------------------------------------------------------------------------
# Recompile census -> A_RECOMPILE (satellite)
# ---------------------------------------------------------------------------

class TestRecompileCensus:
    def test_storm_threshold_fires_once_per_window(self, monkeypatch):
        monkeypatch.setattr(jaxmon, "_RECOMPILE_STORM", 3)
        monkeypatch.setattr(jaxmon, "_RECOMPILE_WINDOW_S", 60.0)
        jaxmon._census_reset()
        for _ in range(2):
            jaxmon._note_recompile("step_a")
        assert not [r for r in obs_flight.get().records()
                    if A_RECOMPILE in r.anomalies]
        jaxmon._note_recompile("step_b")  # third in window: the storm
        recs = [r for r in obs_flight.get().records()
                if A_RECOMPILE in r.anomalies]
        assert len(recs) == 1
        assert recs[0].source == "jaxmon"
        # more recompiles inside the same window stay rate-limited
        for _ in range(5):
            jaxmon._note_recompile("step_c")
        recs = [r for r in obs_flight.get().records()
                if A_RECOMPILE in r.anomalies]
        assert len(recs) == 1
        census = jaxmon.recompile_census()
        assert census["storms"] == 1
        assert census["threshold"] == 3
        assert census["in_window"] == 8

    def test_observed_step_shape_churn_reaches_census(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.setattr(jaxmon, "_RECOMPILE_STORM", 2)
        jaxmon._census_reset()

        step = jaxmon.observed("lens_census_probe", jax.jit(lambda x: x * 2))
        # four abstract shapes through ONE warm step: three recompiles
        for n in range(1, 5):
            step(jnp.arange(n))
        assert jaxmon.recompile_census()["storms"] >= 1
        recs = [r for r in obs_flight.get().records()
                if A_RECOMPILE in r.anomalies]
        assert recs and recs[0].type_name == ""


# ---------------------------------------------------------------------------
# Always-on overhead: lens.observe + ledger charge < 2% of select p50
# ---------------------------------------------------------------------------

class TestOverhead:
    def test_lens_and_ledger_overhead_under_2pct(self, store):
        """The lint.sh gate: the one plan-table write _audit makes per
        planned query (cost profile, calibration, latency point and
        rollup charge, untraced) must cost < 2% of the cached-jit select
        path's own p50."""
        store.query("pts", CQL)  # compile + plan-cache warm
        lat = []
        for _ in range(15):
            t0 = time.perf_counter_ns()
            store.query("pts", CQL)
            lat.append(time.perf_counter_ns() - t0)
        p50_ns = float(np.percentile(lat, 50))

        tbl = PlanTable()
        ql = QueryLedger()
        ql.note_dispatch(1.0, 1.002)
        ql.note_sync(1.003, 1.004)
        N = 5_000

        def per_call_ns():
            t0 = time.perf_counter_ns()
            for _ in range(N):
                tbl.record_query("pts", "z2:iv32:rows", 2.0, rows=10,
                                 planned=True, ledger=ql)
            return (time.perf_counter_ns() - t0) / N

        cost = min(per_call_ns() for _ in range(3))
        assert cost < 0.02 * p50_ns, (
            f"plan-table always-on cost {cost:.0f} ns "
            f">= 2% of query p50 {p50_ns:.0f} ns")

    def test_off_path_dispatch_hook_is_cheap(self):
        # no roundtrip open: note_dispatch must be one ContextVar read
        N = 20_000
        t0 = time.perf_counter_ns()
        for _ in range(N):
            ledger_mod.note_dispatch(1.0, 1.001)
        per = (time.perf_counter_ns() - t0) / N
        assert per < 2_000  # ns — generous even for CI


# ---------------------------------------------------------------------------
# Web API + CLI surfaces
# ---------------------------------------------------------------------------

class TestWebApi:
    def test_obs_lens_endpoint(self, store):
        app = GeoMesaApp(store, coalesce_ms=0)
        for _ in range(2):
            store.query("pts", CQL)
        s, _h, b = call(app, "GET", "/api/obs/lens")
        assert s == 200
        doc = json.loads(b)
        assert doc["entries"], "lens traffic must surface"
        e = doc["entries"][0]
        assert e["type"] == "pts"
        assert {"count", "p50_ms", "p95_ms", "p99_ms"} <= set(e["window"])
        assert "sentinel" in doc
        assert doc["sentinel"]["alarms"] == []

    def test_obs_lens_trace_param_resolves_exemplar(self, store):
        # the one-click loop: drive a traced query, read its exemplar
        # trace_id back out of the lens, then resolve it to the span tree
        # through the SAME endpoint (?trace=) — bucket → trace_id → tree
        app = GeoMesaApp(store, coalesce_ms=0)
        with obs.collect("lens.web_exemplar"):
            store.query("pts", CQL)
        s, _h, b = call(app, "GET", "/api/obs/lens")
        exemplars = [x for e in json.loads(b)["entries"]
                     for x in e["exemplars"]]
        assert exemplars, "traced query must leave an exemplar"
        tid = exemplars[0]["trace_id"]
        s, _h, b = call(app, "GET", "/api/obs/lens", query=f"trace={tid}")
        assert s == 200
        doc = json.loads(b)
        assert doc["trace_id"] == tid
        names = set()

        def _walk(d):
            names.add(d["n"])
            for c in d.get("c", ()):
                _walk(c)

        _walk(doc)
        assert "query" in names

    def test_obs_lens_trace_param_unknown_is_404(self, store):
        app = GeoMesaApp(store, coalesce_ms=0)
        s, _h, _b = call(app, "GET", "/api/obs/lens",
                         query="trace=deadbeef-t99")
        assert s == 404

    def test_obs_lens_bad_window_is_400(self, store):
        app = GeoMesaApp(store, coalesce_ms=0)
        s, _h, _b = call(app, "GET", "/api/obs/lens", query="window=bogus")
        assert s == 400

    def test_obs_fusion_endpoint(self, store):
        app = GeoMesaApp(store, coalesce_ms=0)
        store.query("pts", CQL)
        s, _h, b = call(app, "GET", "/api/obs/fusion")
        assert s == 200
        doc = json.loads(b)
        assert doc["entries"]
        row = doc["entries"][0]
        assert {"host_share", "dispatches_per_query",
                "syncs_per_query"} <= set(row)

    def test_metrics_scrape_carries_lens_histogram(self, store):
        app = GeoMesaApp(store, coalesce_ms=0)
        store.query("pts", CQL)
        s, _h, b = call(app, "GET", "/api/metrics",
                        query="format=prometheus")
        assert s == 200
        text = b.decode()
        assert "# TYPE geomesa_lens_latency_ms histogram" in text
        assert "geomesa_lens_latency_ms_bucket" in text
        assert "geomesa_lens_regressions_total" in text
        types, _samples = _parse_prometheus(
            "\n".join(ln for ln in text.splitlines()
                      if "geomesa_lens" in ln))
        assert types["geomesa_lens_latency_ms"] == "histogram"

    def test_metrics_json_carries_lens_section(self, store):
        app = GeoMesaApp(store, coalesce_ms=0)
        store.query("pts", CQL)
        s, _h, b = call(app, "GET", "/api/metrics")
        assert s == 200
        doc = json.loads(b)
        assert "lens" in doc
        assert doc["lens"]["entries"]


class TestCli:
    def test_obs_lens_and_fusion_report(self, store, capsys):
        from geomesa_tpu.cli.__main__ import main

        for _ in range(2):
            store.query("pts", CQL)
        httpd, url = _serve(GeoMesaApp(store, coalesce_ms=0))
        try:
            main(["obs", "lens", "--url", url])
            out = capsys.readouterr().out
            assert "query lens:" in out
            assert "pts" in out and "p99" in out
            main(["obs", "fusion-report", "--url", url])
            out = capsys.readouterr().out
            assert "fusion report:" in out
            assert "host%" in out and "disp/q" in out
            main(["obs", "lens", "--url", url, "--json"])
            doc = json.loads(capsys.readouterr().out)
            assert doc["entries"]
        finally:
            httpd.shutdown()
