"""Compiled-kernel validation on REAL TPU hardware (opt-in).

The default suite runs Pallas kernels in interpret mode on the CPU mesh
(tests/conftest.py forces ``JAX_PLATFORMS=cpu``); this suite witnesses the
COMPILED Mosaic path on an actual chip — the round-1 verdict's "compiled
kernels unwitnessed" gap. Run explicitly:

    GEOMESA_TPU_DEVICE_TESTS=1 python -m pytest tests/tpu/ -q -p no:cacheprovider

It self-skips unless ``GEOMESA_TPU_DEVICE_TESTS=1`` AND a non-CPU jax
backend initializes; per-test results of the chip run are recorded in
``CHANGES.md``.
"""

import os

import numpy as np
import pytest

if os.environ.get("GEOMESA_TPU_DEVICE_TESTS") != "1":
    pytest.skip(
        "device suite is opt-in: set GEOMESA_TPU_DEVICE_TESTS=1",
        allow_module_level=True,
    )

import jax  # noqa: E402

if jax.default_backend() in ("cpu",):
    pytest.skip("no accelerator backend available", allow_module_level=True)

import jax.numpy as jnp  # noqa: E402

from geomesa_tpu.curve import zorder  # noqa: E402
from geomesa_tpu.ops.pallas_kernels import (  # noqa: E402
    batched_count,
    z2_encode,
    z3_encode,
)
from geomesa_tpu.ops.refine import pack_boxes, pack_times  # noqa: E402


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(11)


def _assemble(hi, lo) -> np.ndarray:
    return (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | np.asarray(
        lo
    ).astype(np.uint64)


class TestCompiledEncodeKernels:
    def test_z3_encode_matches_host(self, rng):
        n = 50_000
        xs = rng.integers(0, 2**21, n).astype(np.uint32)
        ys = rng.integers(0, 2**21, n).astype(np.uint32)
        ts = rng.integers(0, 2**21, n).astype(np.uint32)
        hi, lo = z3_encode(xs, ys, ts)  # compiled (interpret=False)
        np.testing.assert_array_equal(
            _assemble(hi, lo), zorder.encode3(xs, ys, ts)
        )

    def test_z2_encode_matches_host(self, rng):
        n = 50_000
        xs = rng.integers(0, 2**31, n).astype(np.uint32)
        ys = rng.integers(0, 2**31, n).astype(np.uint32)
        hi, lo = z2_encode(xs, ys)
        np.testing.assert_array_equal(
            _assemble(hi, lo), zorder.encode2(xs, ys)
        )


class TestCompiledScanKernel:
    def test_batched_count_matches_numpy(self, rng):
        n = 200_000
        x = np.sort(rng.integers(0, 2**31 - 1, n)).astype(np.int32)
        y = rng.integers(0, 2**31 - 1, n).astype(np.int32)
        bins = rng.integers(0, 50, n).astype(np.int32)
        offs = rng.integers(0, 10_000, n).astype(np.int32)
        q = 8
        boxes_np = []
        times_np = []
        for _ in range(q):
            x1, x2 = np.sort(rng.integers(0, 2**31 - 1, 2))
            y1, y2 = np.sort(rng.integers(0, 2**31 - 1, 2))
            b1, b2 = np.sort(rng.integers(0, 50, 2))
            o1, o2 = np.sort(rng.integers(0, 10_000, 2))
            boxes_np.append([x1, x2, y1, y2])
            times_np.append([b1, o1, b2, o2])
        boxes = np.stack(
            [pack_boxes(np.array([b], np.int32)) for b in boxes_np]
        )
        times = np.stack(
            [pack_times(np.array([t], np.int32)) for t in times_np]
        )
        counts = np.asarray(
            batched_count(
                jnp.asarray(x), jnp.asarray(y), jnp.asarray(bins),
                jnp.asarray(offs), 0, n,
                jnp.asarray(boxes), jnp.asarray(times),
            )
        )
        for i, ((x1, x2, y1, y2), (b1, o1, b2, o2)) in enumerate(
            zip(boxes_np, times_np)
        ):
            inside = (x >= x1) & (x <= x2) & (y >= y1) & (y <= y2)
            t_lo = (bins > b1) | ((bins == b1) & (offs >= o1))
            t_hi = (bins < b2) | ((bins == b2) & (offs <= o2))
            want = int((inside & t_lo & t_hi).sum())
            assert counts[i] == want, f"query {i}: {counts[i]} != {want}"


class TestCompiledMeshPath:
    def test_datastore_select_parity_on_device(self, rng):
        """Full store round-trip on the real chip vs the oracle."""
        from geomesa_tpu.geometry.types import Point
        from geomesa_tpu.schema.columnar import FeatureTable
        from geomesa_tpu.schema.sft import parse_spec
        from geomesa_tpu.store.datastore import DataStore

        sft = parse_spec(
            "evt", "name:String,dtg:Date,*geom:Point;geomesa.z3.interval='week'"
        )
        n = 100_000
        recs = [
            {
                "name": f"f{i}",
                "dtg": 1_600_000_000_000 + int(rng.integers(0, 6 * 86_400_000)),
                "geom": Point(
                    float(rng.uniform(-170, 170)), float(rng.uniform(-80, 80))
                ),
            }
            for i in range(n)
        ]
        fids = [f"f{i}" for i in range(n)]
        table = FeatureTable.from_records(sft, recs, fids)
        tpu = DataStore(backend="tpu")
        tpu.create_schema(sft)
        tpu.write("evt", table)
        oracle = DataStore(backend="oracle")
        oracle.create_schema(sft)
        oracle.write("evt", table)
        # witness BOTH select dispatch routes on hardware: the one-pass
        # gather (forced via a huge threshold) and the two-pass
        # count->gather (threshold 0) — both must match the oracle
        import geomesa_tpu.store.backends as _B

        saved_slots = _B._ONE_PASS_MAX_SLOTS
        try:
            for route_slots in (1 << 62, 0):
                _B._ONE_PASS_MAX_SLOTS = route_slots
                for q in (
                    "BBOX(geom, -60, -40, 60, 40)",
                    "BBOX(geom, 10, 10, 20, 20) AND dtg DURING "
                    "2020-09-13T12:00:00Z/2020-09-16T00:00:00Z",
                ):
                    got = set(tpu.query("evt", q).table.fids)
                    want = set(oracle.query("evt", q).table.fids)
                    assert got == want, \
                        f"{q} (slots={route_slots}): {len(got ^ want)} differ"
        finally:
            _B._ONE_PASS_MAX_SLOTS = saved_slots
        # no failover happened: the compiled path really served these
        assert tpu.metrics.counter("store.query.device_failovers").count == 0

        # batched loose counts (fused Pallas scan) agree with exact counts
        qs = ["BBOX(geom, -60, -40, 60, 40)", "BBOX(geom, 100, 20, 150, 60)"]
        loose = tpu.count_many("evt", qs, loose=True)
        exact = [oracle.query("evt", q).count for q in qs]
        assert loose == exact, (loose, exact)

        # density heatmap (MXU one-hot matmul): mass equals the exact count
        from geomesa_tpu.planning.planner import Query as _Q

        r = tpu.query("evt", _Q(
            filter="BBOX(geom, -60, -40, 60, 40)",
            hints={"density": {"bbox": (-60, -40, 60, 40),
                               "width": 64, "height": 64}},
        ))
        assert r.density is not None
        assert abs(float(r.density.sum()) - exact[0]) < 1e-3

        # batched select_many (round 5): the whole batch's rows in two
        # dispatches, per-query-identical to the oracle
        sel_qs = [
            "BBOX(geom, -60, -40, 60, 40)",
            "BBOX(geom, 10, 10, 20, 20)",
            "BBOX(geom, 100, 20, 150, 60)",
        ]
        batch = tpu.select_many("evt", sel_qs)
        for q, r_b in zip(sel_qs, batch):
            assert set(r_b.table.fids) == set(oracle.query("evt", q).table.fids)
        assert tpu.metrics.counter("store.query.device_failovers").count == 0

        # batched device KNN matches brute force
        from geomesa_tpu.process.knn import knn_many

        pts = [Point(10.0, 10.0), Point(-50.0, 20.0)]
        got_knn = knn_many(tpu, "evt", pts, k=5)
        g = tpu._state("evt").table.geom_column()
        for p, (t_k, d_k) in zip(pts, got_knn):
            d_all = np.sqrt(
                (g.x - p.x).astype(np.float32) ** 2
                + (g.y - p.y).astype(np.float32) ** 2
            )
            want_d = np.sort(d_all)[:5]
            np.testing.assert_allclose(
                np.sort(d_k), want_d, rtol=1e-3, atol=1e-4
            )
        assert tpu.metrics.counter("store.query.device_failovers").count == 0

    def test_track_store_bbox_select_on_device(self, rng):
        """Extended-geometry (XZ2) mesh retrieval on the real chip."""
        from geomesa_tpu.geometry.types import LineString
        from geomesa_tpu.schema.columnar import FeatureTable
        from geomesa_tpu.schema.sft import parse_spec
        from geomesa_tpu.store.datastore import DataStore

        spec = "name:String,*geom:LineString;geomesa.xz.precision='12'"
        sft = parse_spec("trk", spec)
        recs = []
        for i in range(20_000):
            cx = float(rng.uniform(-170, 170))
            cy = float(rng.uniform(-80, 80))
            pts = np.stack(
                [cx + np.linspace(0, 0.3, 5), cy + np.linspace(0, 0.2, 5)], 1
            )
            recs.append({"name": f"t{i}", "geom": LineString(pts)})
        table = FeatureTable.from_records(
            sft, recs, [f"t{i}" for i in range(20_000)]
        )
        tpu = DataStore(backend="tpu")
        tpu.create_schema(sft)
        tpu.write("trk", table)
        oracle = DataStore(backend="oracle")
        oracle.create_schema(parse_spec("trk", spec))
        oracle.write("trk", table)
        st = tpu._state("trk")
        kinds = {k: getattr(v, "kind", None)
                 for k, v in (st.backend_state or {}).items()}
        assert "bboxes" in kinds.values()
        q = "BBOX(geom, -20, -15, 10, 15)"
        assert set(tpu.query("trk", q).table.fids) == set(
            oracle.query("trk", q).table.fids
        )
        assert tpu.metrics.counter("store.query.device_failovers").count == 0


class TestRound3DevicePaths:
    """Round-3 device machinery witnessed on hardware: the sample sort the
    public compact path uses, the block-sparse join gather behind the SQL
    mesh JOIN, and the TTL-masked live-store KNN."""

    def test_device_sort_perm_on_hardware(self, rng):
        from geomesa_tpu.parallel.mesh import make_mesh
        from geomesa_tpu.store.device_ingest import device_sort_perm

        keys = rng.integers(0, 2**62, 200_000, dtype=np.uint64)
        perm = device_sort_perm(make_mesh(), keys)
        np.testing.assert_array_equal(keys[perm], np.sort(keys))
        # wide composite (bin, 63-bit key) with the low-bits tiebreak
        bins = rng.integers(0, 6, 100_000).astype(np.int32)
        z = rng.integers(0, 2**63 - 1, 100_000, dtype=np.uint64)
        route = (bins.astype(np.uint64) << np.uint64(48)) | (z >> np.uint64(15))
        tie = (z & np.uint64(0x7FFF)).astype(np.int32)
        perm2 = device_sort_perm(make_mesh(), route, tie)
        want = np.lexsort((z, bins))
        np.testing.assert_array_equal(bins[perm2], bins[want])
        np.testing.assert_array_equal(z[perm2], z[want])

    def test_sql_mesh_join_on_hardware(self, rng):
        from geomesa_tpu.geometry.types import Point, Polygon
        from geomesa_tpu.sql.engine import sql
        from geomesa_tpu.store.datastore import DataStore

        n = 500_000
        lon = rng.uniform(-60, 60, n)
        lat = rng.uniform(-60, 60, n)
        ds = DataStore(backend="tpu")
        ds.create_schema("pts", "name:String,*geom:Point")
        ds.write(
            "pts",
            [{"name": f"p{i}", "geom": Point(float(lon[i]), float(lat[i]))}
             for i in range(n)],
            fids=[f"p{i}" for i in range(n)],
        )
        ds.create_schema("zones", "zone:String,*geom:Polygon")
        polys = []
        for k in range(8):
            cx, cy = rng.uniform(-45, 45, 2)
            ang = np.sort(rng.uniform(0, 2 * np.pi, 10))
            rad = rng.uniform(3, 9, 10)
            polys.append({
                "zone": f"z{k}",
                "geom": Polygon(np.stack(
                    [cx + rad * np.cos(ang), cy + rad * np.sin(ang)], 1
                )),
            })
        ds.write("zones", polys, fids=[f"z{k}" for k in range(8)])
        import geomesa_tpu.process.join as pj

        # the spy must record a RETURN, not just a call: the engine
        # swallows device errors and falls back to the host join, which
        # would produce identical rows and fake a witnessed mesh path
        spy = {"returned": 0}
        real = pj.join_rows_device

        def spied(*a, **k):
            out = real(*a, **k)
            spy["returned"] += 1
            return out

        pj.join_rows_device = spied
        try:
            r = sql(ds, "SELECT a.name, b.zone FROM pts a JOIN zones b "
                        "ON ST_Within(a.geom, b.geom)")
        finally:
            pj.join_rows_device = real
        assert spy["returned"] == 1, "mesh join did not complete on hardware"
        assert ds.metrics.counter("store.query.device_failovers").count == 0
        from geomesa_tpu.geometry import predicates as P

        want = sum(
            int(P.points_within_geom(lon, lat, z["geom"]).sum())
            for z in polys
        )
        assert len(r) == want

    def test_ttl_knn_on_hardware(self, rng):
        from geomesa_tpu.geometry.types import Point
        from geomesa_tpu.process.knn import knn_many
        from geomesa_tpu.schema.sft import parse_spec
        from geomesa_tpu.store.datastore import DataStore

        t0 = 1_600_000_000_000
        sft = parse_spec("kt", "dtg:Date,*geom:Point")
        sft.user_data["geomesa.age.off"] = 3_600_000
        ds = DataStore(backend="tpu")
        ds.create_schema(sft)
        n = 200_000
        lon = rng.uniform(-100, 100, n)
        lat = rng.uniform(-50, 50, n)
        q = Point(10.0, 10.0)
        recs = []
        for i in range(n):
            fresh = i % 2 == 0
            g = (Point(float(lon[i]), float(lat[i])) if fresh
                 else Point(q.x + 1e-5 * (i + 1), q.y))
            recs.append({"dtg": t0 if fresh else t0 - 7_200_000, "geom": g})
        ds.write("kt", recs, fids=[str(i) for i in range(n)])
        ds.compact("kt")
        res = knn_many(ds, "kt", [q], k=8, now_ms=t0 + 60_000)
        got = set(res[0][0].fids.tolist())
        assert len(got) == 8  # full heap of FRESH neighbors, not empty
        assert not (got & {str(i) for i in range(n) if i % 2 == 1}), got
        # the device path must have served this (no silent host fallback)
        assert ds.metrics.counter("store.query.device_failovers").count == 0

    def test_public_compact_device_sort_2m(self, rng):
        """VERDICT r3 item 6: the PUBLIC ingest/compact path routes through
        the device sample sort at production scale (>= DEVICE_SORT_MIN_ROWS
        rows) on real hardware, and the sorted store then serves parity-
        correct device queries."""
        from geomesa_tpu.schema.columnar import (
            Column,
            FeatureTable,
            GeometryColumn,
        )
        from geomesa_tpu.schema.sft import AttributeType, parse_spec
        from geomesa_tpu.store.datastore import DataStore
        import geomesa_tpu.store.device_ingest as di

        n = 2_200_000  # above the 2M public-path device-sort threshold
        sft = parse_spec("big", "name:String,dtg:Date,*geom:Point")
        lon = rng.uniform(-170, 170, n)
        lat = rng.uniform(-80, 80, n)
        dtg = (1_600_000_000_000
               + rng.integers(0, 6 * 86_400_000, n)).astype(np.int64)
        names = np.array([f"n{i % 5}" for i in range(n)], dtype=object)
        table = FeatureTable.from_columns(sft, np.arange(n).astype(str), {
            "name": Column(AttributeType.STRING, names),
            "dtg": Column(AttributeType.DATE, dtg),
            "geom": GeometryColumn(
                AttributeType.POINT, None, None, x=lon, y=lat,
                bounds=np.stack([lon, lat, lon, lat], axis=1),
            ),
        })
        ds = DataStore(backend="tpu")
        ds.create_schema(sft)
        spy = {"returned": 0}
        real = di.device_sort_perm

        def spied(*a, **k):
            out = real(*a, **k)
            spy["returned"] += 1
            return out

        di.device_sort_perm = spied
        try:
            ds.write("big", table)
            ds.compact("big")
        finally:
            di.device_sort_perm = real
        assert spy["returned"] >= 1, "public compact skipped the device sort"
        q = "BBOX(geom, -30, -20, 40, 35)"
        got = ds.query("big", q).count
        want = int(((lon >= -30) & (lon <= 40)
                    & (lat >= -20) & (lat <= 35)).sum())
        assert got == want
        assert ds.metrics.counter("store.query.device_failovers").count == 0

    def test_mesh_grouped_aggregation_on_hardware(self, rng):
        """Round-4 surface: the fused grouped segment-reduce (SQL GROUP BY
        engine) completes on the real chip with numpy parity and no row
        materialization."""
        from geomesa_tpu.geometry.types import Point
        from geomesa_tpu.sql.engine import sql
        from geomesa_tpu.store.datastore import DataStore

        n = 300_000
        lon = rng.uniform(-60, 60, n)
        lat = rng.uniform(-45, 45, n)
        vals = rng.normal(50, 20, n)
        ds = DataStore(backend="tpu")
        ds.create_schema("ag", "name:String,val:Double,*geom:Point")
        from geomesa_tpu.schema.columnar import (
            Column,
            FeatureTable,
            GeometryColumn,
        )
        from geomesa_tpu.schema.sft import AttributeType

        names = np.array([f"g{i % 6}" for i in range(n)], dtype=object)
        table = FeatureTable.from_columns(
            ds.get_schema("ag"), np.arange(n).astype(str), {
                "name": Column(AttributeType.STRING, names),
                "val": Column(AttributeType.DOUBLE, vals),
                "geom": GeometryColumn(
                    AttributeType.POINT, None, None, x=lon, y=lat,
                    bounds=np.stack([lon, lat, lon, lat], axis=1),
                ),
            })
        ds.write("ag", table)
        ds.compact("ag")
        calls = {"q": 0}
        real_q = ds.query
        ds.query = lambda *a, **k: (
            calls.__setitem__("q", calls["q"] + 1), real_q(*a, **k)
        )[1]
        try:
            r = sql(ds, "SELECT name, COUNT(*) AS n, SUM(val) AS s, "
                        "MIN(val) AS lo, MAX(val) AS hi FROM ag "
                        "WHERE BBOX(geom, -40, -30, 35, 30) GROUP BY name")
        finally:
            ds.query = real_q
        assert calls["q"] == 0, "grouped aggregate materialized rows"
        assert ds.metrics.counter("store.query.device_failovers").count == 0
        inb = (lon >= -40) & (lon <= 35) & (lat >= -30) & (lat <= 30)
        namelist = list(r.columns["name"])
        assert len(namelist) == 6
        for g in range(6):
            m = inb & (names == f"g{g}")
            i = namelist.index(f"g{g}")
            assert int(r.columns["n"][i]) == int(m.sum())
            assert abs(float(r.columns["s"][i]) - vals[m].sum()) \
                < 1e-6 * max(1.0, abs(vals[m].sum()))
            assert float(r.columns["lo"][i]) == vals[m].min()
            assert float(r.columns["hi"][i]) == vals[m].max()

    def test_journal_ingest_query_on_hardware(self, rng, tmp_path):
        """Round-3 surface: durable journal -> streaming consumer ->
        device-resident store -> device query, end to end on hardware."""
        from geomesa_tpu.geometry.types import Point
        from geomesa_tpu.stream.datastore import StreamingDataStore
        from geomesa_tpu.stream.journal import JournalBus
        from geomesa_tpu.store.datastore import DataStore

        bus = JournalBus(str(tmp_path / "journal"))
        # async consumers make drain() an actual barrier over the journal's
        # tailer delivery (bare subscribe dispatch is asynchronous)
        sds = StreamingDataStore(bus=bus, async_consumers=2)
        sds.create_schema("live", "name:String,dtg:Date,*geom:Point")
        n = 20_000
        t0 = 1_600_000_000_000
        lon = rng.uniform(-100, 100, n)
        lat = rng.uniform(-50, 50, n)
        for i in range(n):
            sds.put("live", f"f{i}", {
                "name": f"n{i % 4}", "dtg": t0 + i,
                "geom": Point(float(lon[i]), float(lat[i])),
            })
        assert sds.drain("live", timeout_s=30.0)
        feats = sds.query("live")  # cached features from the journal
        assert len(feats.table) == n
        ds = DataStore(backend="tpu")
        ds.create_schema("live", "name:String,dtg:Date,*geom:Point")
        ds.write("live", feats.table)
        ds.compact("live")
        q = "BBOX(geom, -50, -25, 60, 40)"
        got = ds.query("live", q).count
        want = int(((lon >= -50) & (lon <= 60)
                    & (lat >= -25) & (lat <= 40)).sum())
        assert got == want
        assert ds.metrics.counter("store.query.device_failovers").count == 0

    def test_mxu_bincount_exactness_on_hardware(self, rng):
        """Round-4 surface: the MXU one-hot bincount (auto-selected on TPU
        for the grouped fold) must agree EXACTLY with the segment_sum
        implementation on the real chip — witnessing the bf16-one-hot +
        int32-carry exactness claim on actual Mosaic-compiled matmuls."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as _P

        from geomesa_tpu.parallel.mesh import make_mesh, shard_columns
        from geomesa_tpu.parallel.query import make_grouped_agg_step

        mesh = make_mesh()
        n = 500_000
        G = 512
        x = rng.integers(0, 1 << 20, n).astype(np.int32)
        y = rng.integers(0, 1 << 20, n).astype(np.int32)
        bins = rng.integers(0, 4, n).astype(np.int32)
        offs = rng.integers(0, 1000, n).astype(np.int32)
        gid = rng.integers(0, G, n).astype(np.int32)
        vals = rng.normal(size=(1, n))
        cols, padded, _ = shard_columns(mesh, {
            "x": x, "y": y, "bins": bins, "offs": offs, "gid": gid,
            "rowid": np.arange(n, dtype=np.int32),
        })
        pv = np.zeros((1, padded))
        pv[:, :n] = vals
        dvals = jax.device_put(
            pv, NamedSharding(mesh, _P(None, "data"))
        )
        q = 2
        boxes = np.broadcast_to(
            np.array([[0, 800_000, 0, 1 << 20]], np.int32), (q, 1, 4)
        ).copy()
        times = np.broadcast_to(
            np.array([[0, -1, 10, 10_000]], np.int32), (q, 1, 4)
        ).copy()
        args = (cols["x"], cols["y"], cols["bins"], cols["offs"],
                cols["gid"], cols["rowid"], dvals, jnp.int32(n),
                jnp.asarray(boxes), jnp.asarray(times))
        seg = make_grouped_agg_step(mesh, G, 1, 256, impl="segment")(*args)
        mxu = make_grouped_agg_step(mesh, G, 1, 256, impl="mxu")(*args)
        # numpy ground truth per impl: the fold counts the interior, and
        # rows ON a box edge (x == 0 / 800_000, y == 0 — about one per run
        # at this n) come back as gathered candidates for the host's
        # re-test, so truth is fold + candidates
        m = (x >= 0) & (x <= 800_000)
        want = np.bincount(gid[m], minlength=G)
        for name, r in (("segment", seg), ("mxu", mxu)):
            pos, hits = np.asarray(r[6])[0], np.asarray(r[7])[0]
            cand = np.concatenate(
                [pos[d, : hits[d]] for d in range(pos.shape[0])]
            ).astype(np.int64)
            edge = np.bincount(gid[cand], minlength=G)
            np.testing.assert_array_equal(
                np.asarray(r[0])[0] + edge, want, err_msg=f"{name} bincount")
        np.testing.assert_array_equal(np.asarray(seg[0]), np.asarray(mxu[0]))
        np.testing.assert_array_equal(np.asarray(seg[2]), np.asarray(mxu[2]))

    def test_planned_count_pruned_scan_on_hardware(self, rng):
        """Round-5 surface (VERDICT r4 item 3): the index-pruned resident
        count — candidate-block gather + per-pair compare, compiled on the
        real chip — must equal both the full-scan step and numpy. This is
        the kernel behind config 7's pruned headline."""
        import jax.numpy as jnp

        from geomesa_tpu.parallel.mesh import make_mesh, shard_columns
        from geomesa_tpu.parallel.query import (
            intervals_to_block_pairs,
            make_batched_count_step,
            make_planned_count_step,
            pad_block_pairs,
        )

        mesh = make_mesh()
        n = 500_000
        B = 1024
        x = np.sort(rng.integers(0, 1 << 30, n)).astype(np.int32)
        y = rng.integers(0, 1 << 30, n).astype(np.int32)
        bins = rng.integers(0, 8, n).astype(np.int32)
        offs = rng.integers(0, 10_000, n).astype(np.int32)
        cols, padded, rps = shard_columns(
            mesh, {"x": x, "y": y, "bins": bins, "offs": offs}, multiple=B)
        assert rps % B == 0
        q = 4
        boxes_np, times_np, ivs = [], [], []
        for i in range(q):
            x1, x2 = np.sort(rng.integers(0, 1 << 30, 2))
            y1, y2 = np.sort(rng.integers(0, 1 << 30, 2))
            boxes_np.append(np.array([[x1, x2, y1, y2]], np.int32))
            times_np.append(np.array([[0, 0, 8, 10_000]], np.int32))
            # x-sorted store: the exact x-span rows are the cover
            a = int(np.searchsorted(x, x1, "left"))
            e = int(np.searchsorted(x, x2, "right"))
            ivs.append(np.array([[a, e]], np.int64))
        from geomesa_tpu.ops.refine import pack_boxes, pack_times

        boxes = np.stack([pack_boxes(b) for b in boxes_np])
        times = np.stack([pack_times(t) for t in times_np])
        q_, b_ = intervals_to_block_pairs(ivs, B)
        budget = -(-len(q_) // 8) * 8
        pq, pb = pad_block_pairs(q_, b_, budget)
        pstep = make_planned_count_step(mesh, q, B, budget, chunk=8)
        pruned = np.asarray(pstep(
            cols["x"], cols["y"], cols["bins"], cols["offs"],
            jnp.int32(n), jnp.asarray(pq[None]), jnp.asarray(pb[None]),
            jnp.asarray(boxes[None]), jnp.asarray(times[None]),
        ))[0]
        full = np.asarray(make_batched_count_step(mesh)(
            cols["x"], cols["y"], cols["bins"], cols["offs"],
            jnp.int32(n), jnp.asarray(boxes), jnp.asarray(times),
        ))
        np.testing.assert_array_equal(pruned, full)
        for i, b in enumerate(boxes_np):
            x1, x2, y1, y2 = b[0]
            want = int(((x >= x1) & (x <= x2)
                        & (y >= y1) & (y <= y2)).sum())
            assert pruned[i] == want
        assert pruned.sum() > 0

    def test_wms_tile_on_hardware(self, rng):
        """A WMS GetMap heatmap tile served off the real chip: the density
        grid rides the fused device path and the tile's hot pixels match
        the exact numpy mask."""
        import io

        from PIL import Image

        from geomesa_tpu.geometry.types import Point
        from geomesa_tpu.store.datastore import DataStore
        from geomesa_tpu.web.wms import handle_wms

        n = 200_000
        lon = rng.uniform(-170, 170, n)
        lat = rng.uniform(-80, 80, n)
        ds = DataStore(backend="tpu")
        ds.create_schema("w", "name:String,*geom:Point")
        ds.write("w", [
            {"name": str(i), "geom": Point(float(lon[i]), float(lat[i]))}
            for i in range(n)
        ], fids=[str(i) for i in range(n)])
        ds.compact("w")
        status, body, ctype = handle_wms(ds, {
            "service": "WMS", "request": "GetMap", "layers": "w",
            "crs": "CRS:84", "bbox": "-60,-40,60,40",
            "width": "64", "height": "64",
        })
        assert status == 200 and ctype == "image/png"
        img = np.asarray(Image.open(io.BytesIO(body)).convert("RGBA"))
        grids = ds.density_many(
            "w", [None], (-60.0, -40.0, 60.0, 40.0),
            width=64, height=64, loose=False,
        )
        grid = np.asarray(grids[0])
        want = int(((lon >= -60) & (lon <= 60)
                    & (lat >= -40) & (lat <= 40)).sum())
        assert float(grid.sum()) == want  # exact mass on hardware
        assert ((img[..., 3] > 0) == (grid[::-1] > 0)).all()
        assert ds.metrics.counter("store.query.device_failovers").count == 0
