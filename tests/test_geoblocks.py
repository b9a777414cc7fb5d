"""GeoBlocks pyramid + query cache (ops/geoblocks.py, ISSUE 7): exact
parity of the interior-from-pyramid + boundary-refined-from-base answer
against the brute-force referee, epoch-based invalidation (a write can
never leave a stale cached answer servable — red/green), warm repeats
served from cache byte-identically, pool-attributed warm-up staging, and
the concurrent write+aggregate stress that rides the lock-order sanitizer
in CI (scripts/lint.sh)."""

import threading

import numpy as np
import pytest

from geomesa_tpu.geometry.types import Point
from geomesa_tpu.obs import devmon
from geomesa_tpu.ops.geoblocks import AggPyramid, QueryCache
from geomesa_tpu.store.datastore import DataStore

T0 = 1_600_000_000_000
SPEC = "name:String,val:Double,cnt:Integer,dtg:Date,*geom:Point"


@pytest.fixture(autouse=True)
def _fresh_costs():
    """The plan table (and its routing probe phase) is process-global:
    without isolation, earlier tests' consult ticks decide which test
    lands on the every-16th probe-the-loser route — order-fragile."""
    from geomesa_tpu.obs import plantable

    prev = plantable.install()
    yield
    plantable.install(prev)


def mk(backend="tpu", n=3000, seed=21, compact=True):
    rng = np.random.default_rng(seed)
    ds = DataStore(backend=backend)
    ds.create_schema("ev", SPEC)
    lon = rng.uniform(-60, 60, n)
    lat = rng.uniform(-45, 45, n)
    # rows exactly ON the query box edges: the boundary refinement path
    # must settle these against the f64 filter, not the int superset
    lon[:25] = 10.0
    lat[25:50] = -20.0
    t = T0 + rng.integers(0, 3 * 86_400_000, n)
    recs = [
        {
            "name": f"g{i % 7}",
            "val": None if i % 11 == 0 else float((i * 37) % 1000) / 10.0,
            "cnt": int(i % 13),
            "dtg": int(t[i]),
            "geom": Point(float(lon[i]), float(lat[i])),
        }
        for i in range(n)
    ]
    ds.write("ev", recs, fids=[f"e{i}" for i in range(n)])
    if compact:
        ds.compact("ev")
    return ds


QUERIES = [
    "BBOX(geom, -50, -40, 10, -20)",
    "BBOX(geom, -50, -40, 10, -20) AND dtg DURING "
    "2020-09-13T12:00:00Z/2020-09-15T00:00:00Z",
    "dtg DURING 2020-09-13T12:00:00Z/2020-09-14T00:00:00Z",
    "INCLUDE",
    "BBOX(geom, -0.5, -0.5, 0.5, 0.5)",  # tiny box: all-boundary cover
]


def _same(a, b, rtol=1e-9):
    if (a is None) != (b is None):
        return False
    if a is None:
        return True
    if a["groups"] != b["groups"]:
        return False
    if not np.array_equal(a["count"], b["count"]):
        return False
    for c in a["cols"]:
        for k in ("count", "min", "max"):
            x, y = a["cols"][c][k], b["cols"][c][k]
            if not np.allclose(x, y, rtol=rtol, equal_nan=True):
                return False
        if not np.allclose(a["cols"][c]["sum"], b["cols"][c]["sum"],
                           rtol=1e-6, equal_nan=True):
            return False
    return True


class TestPyramidParity:
    @pytest.mark.parametrize("q", QUERIES)
    def test_pyramid_equals_fused_scan(self, q, monkeypatch):
        tpu = mk("tpu")
        got = tpu.aggregate_many("ev", [q], group_by=["name"],
                                 value_cols=["val", "cnt"])
        assert got[0] is not None
        assert tpu.metrics.counter("store.agg.pyramid_served").count == 1
        # referee: the SAME query through the fused device scan
        monkeypatch.setenv("GEOMESA_TPU_PYRAMID", "0")
        ref_ds = mk("tpu", seed=21)
        ref = ref_ds.aggregate_many("ev", [q], group_by=["name"],
                                    value_cols=["val", "cnt"])
        assert ref[0] is not None
        assert ref_ds.metrics.counter("store.agg.pyramid_served").count == 0
        assert _same(got[0], ref[0])

    def test_no_group_by_and_delta_fold(self):
        tpu = mk("tpu")
        tpu.write("ev", [
            {"name": "fresh", "val": 5.0, "cnt": 1, "dtg": T0,
             "geom": Point(0.25, 0.25)},
        ], fids=["d1"])
        q = "BBOX(geom, -10, -10, 10, 10)"
        got = tpu.aggregate_many("ev", [q], group_by=["name"],
                                 value_cols=["val"])
        import os

        os.environ["GEOMESA_TPU_PYRAMID"] = "0"
        try:
            ref_ds = mk("tpu")
            ref_ds.write("ev", [
                {"name": "fresh", "val": 5.0, "cnt": 1, "dtg": T0,
                 "geom": Point(0.25, 0.25)},
            ], fids=["d1"])
            ref = ref_ds.aggregate_many("ev", [q], group_by=["name"],
                                        value_cols=["val"])
        finally:
            del os.environ["GEOMESA_TPU_PYRAMID"]
        assert _same(got[0], ref[0])
        assert any(k == ("fresh",) for k in got[0]["groups"])

    def test_global_aggregate_no_groups(self):
        tpu = mk("tpu")
        got = tpu.aggregate_many("ev", ["INCLUDE"], group_by=None,
                                 value_cols=["val"])
        assert got[0] is not None
        assert int(got[0]["count"].sum()) == 3000

    def test_byte_cap_falls_back_to_scan(self, monkeypatch):
        monkeypatch.setenv("GEOMESA_TPU_PYRAMID_BYTES", "64")
        tpu = mk("tpu")
        out = tpu.aggregate_many("ev", [QUERIES[0]], group_by=["name"],
                                 value_cols=["val"])
        assert out[0] is not None  # fused scan served it
        assert tpu.metrics.counter("store.agg.pyramid_served").count == 0


class TestEpochInvalidation:
    def test_write_red_green(self):
        """THE satellite red/green: a cached aggregate must never serve
        the pre-write answer after a write returns."""
        ds = mk("tpu")
        q = "BBOX(geom, -60, -45, 60, 45)"
        before = ds.aggregate_many("ev", [q], group_by=["name"],
                                   value_cols=["val"])
        n_before = int(before[0]["count"].sum())
        # prime the cache (warm hit)
        again = ds.aggregate_many("ev", [q], group_by=["name"],
                                  value_cols=["val"])
        assert ds.metrics.counter("store.agg.cache_hits").count == 1
        assert _same(before[0], again[0])
        ds.write("ev", [{"name": "g0", "val": 1.0, "cnt": 0, "dtg": T0,
                         "geom": Point(0.1, 0.1)}], fids=["w1"])
        after = ds.aggregate_many("ev", [q], group_by=["name"],
                                  value_cols=["val"])
        assert int(after[0]["count"].sum()) == n_before + 1
        # compaction re-sorts: cached first-occurrence order is stale too
        ds.compact("ev")
        post_compact = ds.aggregate_many("ev", [q], group_by=["name"],
                                         value_cols=["val"])
        assert int(post_compact[0]["count"].sum()) == n_before + 1
        # deletes invalidate as well
        ds.delete_features("ev", ["w1"])
        post_del = ds.aggregate_many("ev", [q], group_by=["name"],
                                     value_cols=["val"])
        assert int(post_del[0]["count"].sum()) == n_before

    def test_warm_repeat_is_cache_served_and_identical(self):
        ds = mk("tpu")
        q = "BBOX(geom, -50, -40, 10, -20)"
        cold = ds.aggregate_many("ev", [q], group_by=["name"],
                                 value_cols=["val"])
        served0 = ds.metrics.counter("store.agg.pyramid_served").count
        warm = ds.aggregate_many("ev", [q], group_by=["name"],
                                 value_cols=["val"])
        # the warm run recomputed NOTHING: no pyramid or scan execution
        assert ds.metrics.counter("store.agg.pyramid_served").count == served0
        assert ds.agg_cache.snapshot()["hits"] == 1
        assert _same(cold[0], warm[0], rtol=0.0)

    def test_concurrent_write_aggregate_stress(self):
        """Writers and aggregators race; every answer must be internally
        consistent and the final quiesced answer exact. Runs under the
        GEOMESA_TPU_SANITIZE lock-order sanitizer in scripts/lint.sh."""
        ds = mk("tpu", n=800)
        q = "BBOX(geom, -60, -45, 60, 45)"
        errs = []
        stop = threading.Event()

        def writer(tid):
            try:
                for i in range(20):
                    ds.write("ev", [{
                        "name": f"w{tid}", "val": 1.0, "cnt": 0,
                        "dtg": T0 + i, "geom": Point(0.5, 0.5),
                    }], fids=[f"w{tid}-{i}"])
            except Exception as e:  # noqa: BLE001
                errs.append(e)
            finally:
                stop.set()

        def aggregator():
            try:
                while not stop.is_set():
                    out = ds.aggregate_many(
                        "ev", [q], group_by=["name"], value_cols=["val"])
                    if out[0] is not None:
                        assert int(out[0]["count"].sum()) >= 800
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(2)]
        threads += [threading.Thread(target=aggregator) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errs
        final = ds.aggregate_many("ev", [q], group_by=["name"],
                                  value_cols=["val"])
        assert int(final[0]["count"].sum()) == 800 + 2 * 20


class TestSchemaLifecycleInvalidation:
    def test_delete_recreate_never_serves_dead_tables_answer(self):
        """The epoch tuple RECURS across delete_schema + create_schema of
        the same name — the cache must die with the schema, not outlive
        it and serve the dead table's aggregate as the new table's."""
        ds = mk("tpu", n=400)
        q = "BBOX(geom, -60, -45, 60, 45)"
        before = ds.aggregate_many("ev", [q], group_by=["name"],
                                   value_cols=[])[0]
        assert before is not None and len(before["groups"]) == 7
        ds.delete_schema("ev")
        ds.create_schema("ev", SPEC)
        recs = [{"name": "zz", "val": 1.0, "cnt": 0,
                 "dtg": T0 + i, "geom": Point(0.1, 0.1)}
                for i in range(50)]
        ds.write("ev", recs, fids=[f"n{i}" for i in range(50)])
        ds.compact("ev")
        after = ds.aggregate_many("ev", [q], group_by=["name"],
                                  value_cols=[])[0]
        assert after is not None
        assert after["groups"] == [("zz",)]
        assert int(after["count"].sum()) == 50

    def test_rename_drops_old_name_cache(self):
        ds = mk("tpu", n=300)
        q = "BBOX(geom, -60, -45, 60, 45)"
        ds.aggregate_many("ev", [q], group_by=["name"], value_cols=[])
        ds.update_schema("ev", rename_to="ev2")
        assert ds.agg_cache.snapshot()["entries"] == 0
        got = ds.aggregate_many("ev2", [q], group_by=["name"],
                                value_cols=[])[0]
        assert int(got["count"].sum()) == 300


class TestPoolAttribution:
    def test_pool_label_excluded_from_devprof(self):
        """Satellite red/green: pool warm-up staging bytes land on the
        pool's jaxmon counter, never in the triggering query's devprof
        h2d split; unlabeled (query-side) staging IS attributed."""
        from geomesa_tpu.obs import jaxmon

        with devmon.profiled() as prof:
            mine = np.zeros(128, dtype=np.int32)
            pool_bytes = np.zeros(256, dtype=np.int32)
            jaxmon.count_h2d(mine)
            jaxmon.count_h2d(pool_bytes, label="pool")
        assert prof.h2d_bytes == mine.nbytes  # pool bytes excluded
        snap = jaxmon.registry().snapshot()
        assert snap["jax.transfer.h2d_bytes.pool"]["count"] >= (
            pool_bytes.nbytes)

    def test_agg_residency_staging_is_pool_labelled(self, monkeypatch):
        """The fused path's value-column staging (a pool warm-up a query
        happens to trigger) must not inflate that query's h2d split."""
        from geomesa_tpu.obs import jaxmon

        monkeypatch.setenv("GEOMESA_TPU_PYRAMID", "0")  # force fused path
        ds = mk("tpu")
        pool0 = (jaxmon.registry()
                 .counter("jax.transfer.h2d_bytes.pool").count)
        with devmon.profiled() as prof:
            out = ds.aggregate_many("ev", [QUERIES[0]], group_by=["name"],
                                    value_cols=["val"])
        assert out[0] is not None
        pool_staged = (jaxmon.registry()
                       .counter("jax.transfer.h2d_bytes.pool").count
                       - pool0)
        assert pool_staged > 0  # the (V, N) value matrix warm-up
        # the profiled query's h2d excludes the pool warm-up bytes
        assert prof.h2d_bytes < pool_staged + 4096


class TestPyramidUnit:
    def test_boundary_rows_are_a_superset_of_edge_rows(self):
        rng = np.random.default_rng(5)
        n = 2000
        xi = rng.integers(0, 2**31, n)
        yi = rng.integers(0, 2**31, n)
        gid = rng.integers(0, 4, n)
        pyr = AggPyramid(xi, yi, np.zeros(n, np.int64), gid,
                         [(g,) for g in range(4)],
                         np.zeros((0, n)))
        box = (2**29, 2**30, 2**29, 2**30)
        cnt, first, _vc, _vs, _mn, _mx, rows = pyr.answer(box, None)
        inside = ((xi >= box[0]) & (xi <= box[1])
                  & (yi >= box[2]) & (yi <= box[3]))
        strict = ((xi > box[0]) & (xi < box[1])
                  & (yi > box[2]) & (yi < box[3]))
        # interior partials + boundary rows cover every int-domain match
        interior_total = int(cnt.sum())
        row_mask = np.zeros(n, dtype=bool)
        row_mask[rows] = True
        assert interior_total + int((inside & row_mask).sum()) >= int(
            inside.sum())
        # interior never includes a row ON the box edge
        assert interior_total <= int(strict.sum())

    def test_no_constraints_counts_everything(self):
        n = 500
        rng = np.random.default_rng(6)
        pyr = AggPyramid(
            rng.integers(0, 2**31, n), rng.integers(0, 2**31, n),
            rng.integers(0, 5, n), np.zeros(n, np.int64), [()],
            np.zeros((0, n)))
        cnt, first, *_rest, rows = pyr.answer(None, None)
        assert int(cnt.sum()) + 0 == n  # full grid interior, no window
        assert len(rows) == 0
        assert int(first[0]) == 0

    def test_byte_cap_raises(self):
        with pytest.raises(ValueError, match="byte cap"):
            AggPyramid(np.zeros(4, np.int64), np.zeros(4, np.int64),
                       np.zeros(4, np.int64), np.zeros(4, np.int64),
                       [()], np.zeros((0, 4)), byte_cap=16)


class TestQueryCacheUnit:
    def test_epoch_mismatch_misses_and_drops(self):
        qc = QueryCache()
        res = {"groups": [("a",)], "count": np.array([1]),
               "cols": {}}
        qc.put("t", "k", (1, 1), res)
        assert qc.get("t", "k", (1, 1)) is not None
        assert qc.get("t", "k", (1, 2)) is None  # stale: dropped
        assert qc.get("t", "k", (1, 1)) is None  # eager drop happened
        assert qc.snapshot()["misses"] == 2

    def test_deep_copy_isolation(self):
        qc = QueryCache()
        res = {"groups": [("a",)], "count": np.array([5]),
               "cols": {"v": {"sum": np.array([1.0])}}}
        qc.put("t", "k", 1, res)
        got = qc.get("t", "k", 1)
        got["count"][0] = 999
        got["cols"]["v"]["sum"][0] = -1.0
        clean = qc.get("t", "k", 1)
        assert clean["count"][0] == 5
        assert clean["cols"]["v"]["sum"][0] == 1.0

    def test_lru_eviction(self):
        qc = QueryCache(max_entries=2)
        r = {"groups": [], "count": np.zeros(0, np.int64), "cols": {}}
        qc.put("t", "a", 1, r)
        qc.put("t", "b", 1, r)
        qc.put("t", "c", 1, r)
        assert qc.get("t", "a", 1) is None
        assert qc.get("t", "c", 1) is not None
        assert qc.snapshot()["evictions"] == 1

    def test_choose_agg_path_consults_cost_table(self):
        from geomesa_tpu.obs.plantable import PlanTable
        from geomesa_tpu.planning.planner import choose_agg_path

        ct = PlanTable()
        assert choose_agg_path(ct, "t") == "pyramid"  # no data: default
        for _ in range(10):
            ct.observe_cost("t", "gagg:pyramid", wall_ms=10.0)
            ct.observe_cost("t", "gagg:scan", wall_ms=1.0)
        assert choose_agg_path(ct, "t") == "scan"
        ct2 = PlanTable()
        for _ in range(10):
            ct2.observe_cost("t", "gagg:pyramid", wall_ms=1.0)
            ct2.observe_cost("t", "gagg:scan", wall_ms=10.0)
        assert choose_agg_path(ct2, "t") == "pyramid"

    def test_agg_route_probes_the_loser(self):
        from geomesa_tpu.obs.plantable import PlanTable
        from geomesa_tpu.planning.planner import (AGG_PROBE_EVERY,
                                                  choose_agg_path)

        # scan wins — but the pyramid must still be probed periodically
        # so its profile stays fresh and the verdict can flip back
        ct = PlanTable()
        for _ in range(10):
            ct.observe_cost("t", "gagg:pyramid", wall_ms=10.0)
            ct.observe_cost("t", "gagg:scan", wall_ms=1.0)
        routes = [choose_agg_path(ct, "t")
                  for _ in range(2 * AGG_PROBE_EVERY)]
        assert routes.count("pyramid") == 2
        # symmetric: a pyramid-default workload (scan has NO observations
        # and could otherwise never qualify) still measures the scan
        ct2 = PlanTable()
        routes2 = [choose_agg_path(ct2, "t")
                   for _ in range(AGG_PROBE_EVERY)]
        assert routes2.count("scan") == 1
        # the schedule rides the consult counter, not observation counts:
        # consults that never observe still advance toward the next probe
        ct3 = PlanTable()
        for _ in range(10):
            ct3.observe_cost("t", "gagg:pyramid", wall_ms=10.0)
            ct3.observe_cost("t", "gagg:scan", wall_ms=1.0)
        seen = set()
        for _ in range(2 * AGG_PROBE_EVERY):
            seen.add(choose_agg_path(ct3, "t"))
        assert seen == {"scan", "pyramid"}


class TestLambdaWarmPath:
    def test_feature_cache_version_bumps(self):
        from geomesa_tpu.schema.sft import parse_spec
        from geomesa_tpu.stream.cache import FeatureCache

        fc = FeatureCache(parse_spec("ev", SPEC))
        v0 = fc.version
        fc.put("a", {"name": "x"}, ts=1)
        assert fc.version > v0
        v1 = fc.version
        fc.delete("a")
        assert fc.version > v1
        v2 = fc.version
        fc.clear()
        assert fc.version > v2

    def test_lambda_data_epoch_advances_on_both_tiers(self):
        from geomesa_tpu.stream.lambda_store import LambdaDataStore

        lds = LambdaDataStore(persist_interval_s=None)
        try:
            lds.create_schema("ev", SPEC)
            e0 = lds.data_epoch("ev")
            lds.write("ev", "f1", {
                "name": "a", "val": 1.0, "cnt": 1, "dtg": T0,
                "geom": Point(1.0, 1.0),
            })
            lds.stream.drain("ev")  # hot put applies on a consumer thread
            e1 = lds.data_epoch("ev")
            assert e1 != e0
            lds.cold.write("ev", [{
                "name": "b", "val": 2.0, "cnt": 2, "dtg": T0,
                "geom": Point(2.0, 2.0),
            }], fids=["c1"])
            assert lds.data_epoch("ev") != e1
        finally:
            lds.close()
