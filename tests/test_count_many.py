"""Batched multi-query counts (one device pass) — loose-bbox semantics and
fallbacks (reference: batched scanner fan-out + loose-bbox hint — SURVEY.md
§2.20 P4, QueryHints)."""

import numpy as np
import pytest

from geomesa_tpu.geometry.types import Point
from geomesa_tpu.planning.planner import Query
from geomesa_tpu.store.datastore import DataStore

T0 = 1_498_867_200_000


@pytest.fixture(scope="module")
def ds():
    rng = np.random.default_rng(13)
    n = 30_000
    recs = [
        {
            "name": f"n{i % 3}",
            "dtg": T0 + int(rng.integers(0, 14 * 86_400_000)),
            "geom": Point(float(rng.uniform(-170, 170)), float(rng.uniform(-85, 85))),
        }
        for i in range(n)
    ]
    store = DataStore(backend="tpu")
    store.create_schema("b", "name:String,dtg:Date,*geom:Point")
    store.write("b", recs)
    store.compact("b")
    return store


def _queries():
    rng = np.random.default_rng(3)
    out = []
    for _ in range(12):
        cx, cy = rng.uniform(-120, 120), rng.uniform(-60, 60)
        w, h = rng.uniform(5, 40), rng.uniform(5, 30)
        lo = T0 + int(rng.integers(0, 7 * 86_400_000))
        import datetime

        t1 = datetime.datetime.fromtimestamp(lo / 1000, datetime.timezone.utc)
        t2 = datetime.datetime.fromtimestamp((lo + 4 * 86_400_000) / 1000, datetime.timezone.utc)
        out.append(
            f"BBOX(geom, {cx - w/2:.4f}, {cy - h/2:.4f}, {cx + w/2:.4f}, {cy + h/2:.4f}) "
            f"AND dtg DURING {t1:%Y-%m-%dT%H:%M:%SZ}/{t2:%Y-%m-%dT%H:%M:%SZ}"
        )
    return out


class TestCountMany:
    def test_matches_exact_queries(self, ds):
        qs = _queries()
        batched = ds.count_many("b", qs)
        exact = [ds.query("b", q).count for q in qs]
        assert batched == exact  # random doubles never sit on cell edges
        assert sum(batched) > 0

    def test_mixed_filters_fall_back(self, ds):
        qs = ["name = 'n1'", "BBOX(geom, -50, -50, 50, 50)", "INCLUDE"]
        batched = ds.count_many("b", qs)
        exact = [ds.query("b", q).count for q in qs]
        assert batched == exact

    def test_exact_mode(self, ds):
        qs = _queries()[:4]
        assert ds.count_many("b", qs, loose=False) == [
            ds.query("b", q).count for q in qs
        ]

    def test_exact_mode_stays_batched(self, ds, monkeypatch):
        """loose=False on a point store must run ONE fused device pass +
        edge corrections, not Q per-query host executions."""
        calls = {"query": 0}
        real = ds.query

        def spy(*a, **k):
            calls["query"] += 1
            return real(*a, **k)

        qs = _queries()[:6]
        want = [ds.query("b", q).count for q in qs]
        monkeypatch.setattr(ds, "query", spy)
        got = ds.count_many("b", qs, loose=False)
        assert got == want
        # edge corrections touch main.take, never ds.query
        assert calls["query"] == 0, calls

    def test_exact_mode_extended_geometries(self, monkeypatch):
        """loose=False on an XZ (bbox-overlap) store stays batched and
        matches the per-query exact path, with track endpoints planted
        EXACTLY on the query box edges."""
        from geomesa_tpu.geometry.types import LineString

        rng = np.random.default_rng(55)
        n = 3_000
        store = DataStore(backend="tpu")
        store.create_schema("trk", "name:String,dtg:Date,*geom:LineString")
        boxes = [(-10.0, -10.0, 10.0, 10.0), (5.123, -30.0, 44.9, 7.7)]
        recs = []
        for i in range(n):
            x0 = float(rng.uniform(-160, 150))
            y0 = float(rng.uniform(-75, 70))
            if i < 40:  # endpoints exactly ON a query edge
                bx = boxes[i % 2]
                x0 = bx[0] if i % 4 < 2 else bx[2]
                y0 = bx[1] if i % 8 < 4 else bx[3]
            recs.append({
                "name": f"t{i}", "dtg": T0 + i,
                "geom": LineString([(x0, y0), (x0 + 1.5, y0 + 1.0)]),
            })
        store.write("trk", recs, fids=[str(i) for i in range(n)])
        store.compact("trk")
        qs = [f"BBOX(geom, {x1}, {y1}, {x2}, {y2})"
              for x1, y1, x2, y2 in boxes]
        want = [store.query("trk", q).count for q in qs]
        calls = {"q": 0}
        real = store.query
        monkeypatch.setattr(
            store, "query",
            lambda *a, **k: (calls.__setitem__("q", calls["q"] + 1),
                            real(*a, **k))[1],
        )
        got = store.count_many("trk", qs, loose=False)
        assert got == want, (got, want)
        assert calls["q"] == 0, "extended-geometry exact count fell back"

    def test_out_of_range_time_counts_zero(self, ds):
        """A temporal constraint that clamps entirely away (pre-epoch /
        beyond the indexable range) is UNSATISFIABLE — both modes must
        count 0, not substitute the full time window."""
        q = ("BBOX(geom, -170, -85, 170, 85) AND dtg DURING "
             "1960-01-01T00:00:00Z/1960-01-02T00:00:00Z")
        assert ds.query("b", q).count == 0
        assert ds.count_many("b", [q], loose=False) == [0]
        assert ds.count_many("b", [q], loose=True) == [0]

    def test_exact_mode_boundary_adversarial(self):
        """Rows planted EXACTLY on query box edges (f64) — where the int
        superset and f64 differ — must count identically to the exact
        path. This is the case loose counting gets wrong by design."""
        rng = np.random.default_rng(99)
        n = 8_000
        store = DataStore(backend="tpu")
        store.create_schema("edge", "name:String,dtg:Date,*geom:Point")
        boxes = [
            (-10.0, -10.0, 10.0, 10.0),
            (3.33333333, -20.0, 47.77777, 5.5),
            (-123.456789, 12.3456789, -100.0001, 44.4),
        ]
        recs = []
        lon = rng.uniform(-170, 170, n)
        lat = rng.uniform(-85, 85, n)
        k = 0
        for x1, y1, x2, y2 in boxes:
            for bx in (x1, x2):
                for by in (y1, y2):
                    for dx in (-1e-9, 0.0, 1e-9):
                        lon[k] = bx + dx
                        lat[k] = by + dx
                        k += 1
        for i in range(n):
            recs.append({
                "name": f"n{i}", "dtg": T0 + int(rng.integers(0, 86_400_000)),
                "geom": Point(float(lon[i]), float(lat[i])),
            })
        store.write("edge", recs, fids=[str(i) for i in range(n)])
        store.compact("edge")
        qs = [f"BBOX(geom, {x1}, {y1}, {x2}, {y2})" for x1, y1, x2, y2 in boxes]
        got = store.count_many("edge", qs, loose=False)
        want = []
        for x1, y1, x2, y2 in boxes:
            want.append(int(
                ((lon >= x1) & (lon <= x2) & (lat >= y1) & (lat <= y2)).sum()
            ))
        assert got == want, (got, want)
        # and oracle agreement (full AST semantics)
        assert got == [store.query("edge", q).count for q in qs]

    def test_hot_tier_falls_back(self, ds):
        ds.write("b", [{"name": "hot", "dtg": T0, "geom": Point(0.5, 0.5)}])
        try:
            got = ds.count_many("b", ["BBOX(geom, 0, 0, 1, 1)"])
            assert got == [ds.query("b", "BBOX(geom, 0, 0, 1, 1)").count]
        finally:
            ds.compact("b")

    def test_oracle_backend_loops(self):
        ds2 = DataStore(backend="oracle")
        ds2.create_schema("o", "dtg:Date,*geom:Point")
        ds2.write("o", [{"dtg": T0 + i, "geom": Point(i, i)} for i in range(10)])
        assert ds2.count_many("o", ["BBOX(geom, -1, -1, 4, 4)", "INCLUDE"]) == [5, 10]

    def test_non_default_field_predicates_fall_back(self):
        # TempOp on a NON-default Date attribute must not be loose-batched
        # (the extraction would silently drop it and count everything)
        ds2 = DataStore(backend="tpu")
        ds2.create_schema(
            "nd", "created:Date,dtg:Date,*geom:Point;geomesa.index.dtg='dtg'"
        )
        ds2.write("nd", [
            {"created": 86_400_000 * i, "dtg": T0 + i, "geom": Point(i, i)}
            for i in range(10)
        ])
        ds2.compact("nd")
        assert ds2.get_schema("nd").dtg_field == "dtg"
        q = "created AFTER 1970-01-05T00:00:00Z"  # created > 4 days
        exact = ds2.query("nd", q).count
        assert ds2.count_many("nd", [q]) == [exact]
        assert exact == 5

    def test_limit_falls_back(self, ds):
        q = Query(filter="BBOX(geom, -170, -85, 170, 85)", limit=5)
        assert ds.count_many("b", [q]) == [ds.query("b", q).count] == [5]

    def test_interceptors_apply(self, ds):
        from geomesa_tpu.filter import ast as A

        calls = []

        def scope(sft, q):
            from dataclasses import replace

            calls.append(1)
            return replace(
                q, filter=A.And([q.resolved_filter(),
                                 A.BBox("geom", 0.0, 0.0, 180.0, 90.0)])
            )

        ds.register_interceptor("b", scope)
        try:
            got = ds.count_many("b", ["INCLUDE"])
            exact = ds.query("b", "BBOX(geom, 0, 0, 180, 90)").count
            assert got == [exact]
            assert calls  # interceptor ran on the batched path
        finally:
            ds._interceptors.clear()

    def test_age_off_falls_back(self):
        ds2 = DataStore(backend="tpu")
        ds2.create_schema("ttl", "dtg:Date,*geom:Point;geomesa.age.off='1000'")
        now = 1_700_000_000_000
        ds2.write("ttl", [
            {"dtg": now - 10_000, "geom": Point(1, 1)},   # expired
            {"dtg": now - 100, "geom": Point(2, 2)},      # fresh
        ])
        ds2.compact("ttl")
        q = Query(filter="INCLUDE", hints={"now_ms": now})
        assert ds2.count_many("ttl", [q]) == [ds2.query("ttl", q).count] == [1]

    def test_batched_counts_audited(self):
        from geomesa_tpu.utils.audit import InMemoryAuditWriter

        ds2 = DataStore(backend="tpu", audit_writer=InMemoryAuditWriter())
        ds2.create_schema("a", "dtg:Date,*geom:Point")
        ds2.write("a", [{"dtg": T0, "geom": Point(1, 1)}])
        ds2.compact("a")
        ds2.count_many("a", ["BBOX(geom, 0, 0, 2, 2)", "INCLUDE"])
        assert len(ds2.audit_writer.query_events("a")) == 2


_DAY = "2017-07-0{}T00:00:00Z"
_SLOT_BATCHES = {
    # every CQL one box and one window: the scan tests 1 + 1 slots
    "one_slot": [
        f"BBOX(geom, {x}, {y}, {x + 30}, {y + 25}) AND dtg DURING "
        f"{_DAY.format(d)}/{_DAY.format(d + 3)}"
        for x, y, d in ((-120, -50, 1), (-40, -20, 2), (10, 0, 3), (60, 20, 4))
    ],
    # an antimeridian box (two slots), no time constraint, no box
    "mixed": [
        f"BBOX(geom, -60, -30, 0, 30) AND dtg DURING "
        f"{_DAY.format(1)}/{_DAY.format(5)}",
        f"BBOX(geom, 150, -40, -150, 40) AND dtg DURING "
        f"{_DAY.format(2)}/{_DAY.format(8)}",
        "BBOX(geom, 20, -10, 80, 60)",
        f"dtg DURING {_DAY.format(3)}/{_DAY.format(4)}",
    ],
    # the OR is not a pure bbox+time conjunction: it runs per query while
    # the rest of the batch stays batched
    "or_boxes": [
        "BBOX(geom, -100, -40, -60, 0) OR BBOX(geom, 40, 0, 90, 50)",
        f"BBOX(geom, -20, -20, 20, 20) AND dtg DURING "
        f"{_DAY.format(1)}/{_DAY.format(6)}",
        "BBOX(geom, 160, -60, -160, 60)",
    ],
}
# (live box+time slots, Q x (B' + T') scanned) of each batch's batched part
_SLOT_COUNTERS = {"one_slot": (8, 8), "mixed": (9, 12), "or_boxes": (5, 6)}


@pytest.fixture(scope="module")
def slot_stores():
    """A point store and an XZ (bbox-overlap) store of horizontal segments,
    whose bboxes ARE their geometries, so loose counts equal exact ones;
    instants sit half a second off every whole second, never on a
    window's quantized endpoint."""
    from geomesa_tpu.geometry.types import LineString

    rng = np.random.default_rng(21)
    n = 3_000
    lon = rng.uniform(-175, 170, n)
    lat = rng.uniform(-80, 80, n)
    dtg = T0 + rng.integers(0, 10 * 86_400, n) * 1000 + 500
    fids = [str(i) for i in range(n)]
    pts = DataStore(backend="tpu")
    pts.create_schema("p", "dtg:Date,*geom:Point")
    pts.write("p", [{"dtg": int(t), "geom": Point(float(x), float(y))}
                    for x, y, t in zip(lon, lat, dtg)], fids=fids)
    pts.compact("p")
    xz = DataStore(backend="tpu")
    xz.create_schema("s", "dtg:Date,*geom:LineString")
    xz.write("s", [
        {"dtg": int(t),
         "geom": LineString([(float(x), float(y)), (float(x) + 4.0, float(y))])}
        for x, y, t in zip(lon, lat, dtg)
    ], fids=fids)
    xz.compact("s")
    return {"point": (pts, "p"), "xz": (xz, "s")}


class TestCountManySlots:
    """count_many scans only the slot columns its batch uses: every mode
    and store layout answers as the per-query exact path and as the
    untrimmed full-width scan."""

    @pytest.mark.parametrize("batch", sorted(_SLOT_BATCHES))
    @pytest.mark.parametrize("loose", [True, False])
    @pytest.mark.parametrize("layout", ["point", "xz"])
    def test_trimmed_batches_match_exact(self, slot_stores, monkeypatch,
                                         layout, loose, batch):
        from geomesa_tpu.ops import refine

        store, t = slot_stores[layout]
        qs = _SLOT_BATCHES[batch]
        want = [store.query(t, q).count for q in qs]
        live = store.metrics.counter("store.count_many.slots_live")
        scanned = store.metrics.counter("store.count_many.slots_scanned")
        before = (live.count, scanned.count)
        got = store.count_many(t, qs, loose=loose)
        assert got == want, (got, want)
        assert sum(got) > 0
        assert (live.count - before[0], scanned.count - before[1]) \
            == _SLOT_COUNTERS[batch]
        monkeypatch.setattr(refine, "trim_slots",
                            lambda b, tt, overlap=False: (b, tt, 0))
        assert store.count_many(t, qs, loose=loose) == got


class TestDensityMany:
    def _stores(self, n=4000, seed=13):
        from geomesa_tpu.schema.sft import parse_spec

        rng = np.random.default_rng(seed)
        recs = [
            {"name": f"n{i % 5}",
             "dtg": T0 + int(rng.integers(0, 10 * 86_400_000)),
             "geom": Point(float(rng.uniform(-60, 60)), float(rng.uniform(-40, 40)))}
            for i in range(n)
        ]
        out = []
        for backend in ("tpu", "oracle"):
            ds = DataStore(backend=backend)
            ds.create_schema(parse_spec("evt", "name:String,dtg:Date,*geom:Point"))
            ds.write("evt", recs, fids=[str(i) for i in range(n)])
            ds.compact("evt")
            out.append(ds)
        return out

    def test_batched_matches_exact(self):
        from geomesa_tpu.planning.planner import Query as Q

        tpu, oracle = self._stores()
        bbox = (-60.0, -40.0, 60.0, 40.0)
        queries = [
            "BBOX(geom, -30, -20, 30, 20)",
            "BBOX(geom, 0, 0, 60, 40) AND dtg DURING "
            "2017-07-02T00:00:00Z/2017-07-06T00:00:00Z",
            "BBOX(geom, 100, 50, 120, 60)",  # disjoint from data
        ]
        grids = tpu.density_many("evt", queries, bbox, width=64, height=64)
        assert len(grids) == 3
        for q, g in zip(queries, grids):
            exact = oracle.query(
                "evt",
                Q(filter=q, hints={"density": {"bbox": bbox, "width": 64,
                                               "height": 64}}),
            ).density
            assert g.shape == (64, 64)
            assert float(g.sum()) == float(exact.sum()), q

    def test_residual_filters_fall_back_exact(self):
        tpu, oracle = self._stores(1500)
        bbox = (-60.0, -40.0, 60.0, 40.0)
        q = "BBOX(geom, -30, -20, 30, 20) AND name = 'n2'"
        (g,) = tpu.density_many("evt", [q], bbox, width=32, height=32)
        from geomesa_tpu.planning.planner import Query as Q

        exact = oracle.query(
            "evt", Q(filter=q, hints={"density": {"bbox": bbox, "width": 32,
                                                  "height": 32}})
        ).density
        assert float(g.sum()) == float(exact.sum())

    def test_cell_placement_and_full_grid(self):
        # known single-point placement: a transposed/flipped grid must fail
        from geomesa_tpu.schema.sft import parse_spec

        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("p", "dtg:Date,*geom:Point"))
        # viewport (0,0)-(40,20), 8x4 grid: cells are 5x5 degrees
        ds.write("p", [{"dtg": T0, "geom": Point(32.5, 3.0)}], fids=["a"])
        ds.compact("p")
        (g,) = ds.density_many("p", ["INCLUDE"], (0, 0, 40, 20),
                               width=8, height=4)
        assert g.shape == (4, 8)
        assert float(g.sum()) == 1.0
        iy, ix = np.nonzero(g)
        assert (int(ix[0]), int(iy[0])) == (6, 0)  # x=32.5→col 6, y=3→row 0

    def test_viewport_excludes_outside_rows(self):
        # rows outside the shared viewport must NOT be clamped into edge
        # cells by the batched path (review finding)
        from geomesa_tpu.planning.planner import Query as Q
        from geomesa_tpu.schema.sft import parse_spec

        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("p", "dtg:Date,*geom:Point"))
        # y off any cell edge: ON-edge rows are the documented loose-vs-
        # exact boundary epsilon, not what this test checks
        recs = [{"dtg": T0, "geom": Point(x, 0.4)}
                for x in (-50.0, -5.0, 5.0, 50.0)]  # 2 inside, 2 outside
        ds.write("p", recs, fids=list("abcd"))
        ds.compact("p")
        viewport = (-10.0, -10.0, 10.0, 10.0)
        for q in ("INCLUDE", "BBOX(geom, -60, -10, 60, 10)"):
            (g,) = ds.density_many("p", [q], viewport, width=16, height=16)
            exact = ds.query(
                "p", Q(filter=q, hints={"density": {"bbox": viewport,
                                                    "width": 16, "height": 16}})
            ).density
            assert float(g.sum()) == 2.0, q
            assert np.array_equal(g, exact), q

    def test_weight_by_hint_survives_fallback(self):
        from geomesa_tpu.planning.planner import Query as Q
        from geomesa_tpu.schema.sft import parse_spec

        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("p", "w:Double,dtg:Date,*geom:Point"))
        ds.write("p", [{"w": 3.0, "dtg": T0, "geom": Point(1.0, 1.0)}],
                 fids=["a"])
        ds.compact("p")
        (g,) = ds.density_many(
            "p",
            [Q(filter="INCLUDE", hints={"density": {"weight_by": "w"}})],
            (-10, -10, 10, 10), width=8, height=8,
        )
        assert float(g.sum()) == 3.0  # weighted, not dropped

    def test_rest_density_many(self):
        from geomesa_tpu.schema.sft import parse_spec
        from geomesa_tpu.web.app import GeoMesaApp, _HttpError

        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("p", "dtg:Date,*geom:Point"))
        ds.write("p", [{"dtg": T0, "geom": Point(2.0, 2.0)},
                       {"dtg": T0, "geom": Point(-3.0, -3.0)}], fids=["a", "b"])
        ds.compact("p")
        app = GeoMesaApp(ds)
        status, out, _ = app._density_many(
            "p", {},
            {"queries": ["INCLUDE", "BBOX(geom, 0, 0, 10, 10)"],
             "bbox": [-10, -10, 10, 10], "width": 8, "height": 8},
        )
        assert status == 200
        g0, g1 = np.array(out["grids"][0]), np.array(out["grids"][1])
        assert g0.shape == (8, 8)
        assert float(g0.sum()) == 2.0 and float(g1.sum()) == 1.0
        import pytest as _pytest

        with _pytest.raises(_HttpError):
            app._density_many("p", {}, {"queries": ["INCLUDE"]})  # no bbox
        with _pytest.raises(_HttpError):
            app._density_many("p", {}, {"queries": ["INCLUDE"],
                                        "bbox": [1, 2, 3]})

    def test_rest_density_many_dims_clamped(self):
        from geomesa_tpu.schema.sft import parse_spec
        from geomesa_tpu.web.app import GeoMesaApp, _HttpError

        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("p", "dtg:Date,*geom:Point"))
        app = GeoMesaApp(ds)
        import pytest as _pytest

        with _pytest.raises(_HttpError, match="4096"):
            app._density_many(
                "p", {}, {"queries": ["INCLUDE"], "bbox": [0, 0, 1, 1],
                          "width": 20000, "height": 64},
            )
        # float width coerces instead of crashing
        assert ds.density_many("p", ["INCLUDE"], (0, 0, 1, 1),
                               width=8.0, height=4)[0].shape == (4, 8)
