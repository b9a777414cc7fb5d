"""The z3 index keeps its rows in key order (``Z3Index.features``, ``rank``):
refine reads the residual's columns there and reduce gathers the result
rows there, and every answer is the one the arrival-ordered table gives —
the same fids in the same order, every column's values and ``valid``, the
same ``row_ids`` (GeoMesa's own layout: each feature stored under its
index key, SURVEY.md §2.3)."""

import time

import numpy as np
import pytest

from geomesa_tpu.geometry import Point
from geomesa_tpu.planning.planner import Query
from geomesa_tpu.schema.columnar import FeatureTable, GeometryColumn
from geomesa_tpu.schema.sft import parse_spec
from geomesa_tpu.store.datastore import DataStore
from geomesa_tpu.store.reduce import reduce_result

T0 = 1_498_867_200_000  # 2017-07-01
DAY = 86_400_000
# 30 years: no row of 2017 expires at query time, age_off drops by now_ms
TTL_MS = 30 * 365 * DAY
SPEC = ("name:String,actor:String,code:String,n:Integer,v:Double,"
        "dtg:Date,*geom:Point")

BOX = "BBOX(geom, -30, -20, 25, 20)"
DURING = "dtg DURING 2017-07-02T00:00:00Z/2017-07-07T00:00:00Z"
SELECTS = [
    f"{BOX} AND {DURING}",
    f"{BOX} AND {DURING} AND name = 'n3'",
    f"{BOX} AND {DURING} AND v > 40.5 AND actor IS NOT NULL",
    f"BBOX(geom, -50, -35, -10, 5) AND {DURING} AND code <> 'c2'",
]


def _records(rng, n, start):
    recs = []
    for i in range(start, start + n):
        recs.append({
            "name": f"n{i % 7}",
            # nulls give the string and number columns `valid` arrays
            "actor": None if i % 11 == 0 else f"actor{i % 97}",
            "code": f"c{i % 5}",
            "n": None if i % 13 == 0 else int(i % 1000),
            "v": float(rng.uniform(0, 100)),
            "dtg": T0 + int(rng.integers(0, 10 * DAY)),
            "geom": Point(float(rng.uniform(-60, 60)),
                          float(rng.uniform(-40, 40))),
        })
    return recs


def _store(n=6000, seed=5, spec=SPEC, ttl=False, backend="tpu"):
    rng = np.random.default_rng(seed)
    sft = parse_spec("ev", spec + (f";geomesa.age.off='{TTL_MS}'" if ttl
                                   else ""))
    ds = DataStore(backend=backend)
    ds.create_schema(sft)
    recs = _records(rng, n, 0)
    if "dtg" not in spec:
        for r in recs:
            del r["dtg"]
    ds.write("ev", recs, fids=[f"f{i}" for i in range(n)])
    ds.compact("ev")
    return ds


def _write_more(ds, n, start, seed):
    recs = _records(np.random.default_rng(seed), n, start)
    ds.write("ev", recs, fids=[f"f{i}" for i in range(start, start + n)])


def _query(q):
    return Query(filter=q) if isinstance(q, str) else q


def _arrival_answer(ds, q):
    """The answer the arrival-ordered table gives: the brute-force row set
    over (main ++ delta), taken from the main table itself."""
    st = ds._state("ev")
    main, _, _, _, delta = st.snapshot()
    q = _query(q)
    whole = main if delta is None else FeatureTable.concat([main, delta])
    rows = np.nonzero(q.resolved_filter().mask(whole))[0]
    n = len(main)
    parts = [t.take(sel) for t, sel in ((main, rows[rows < n]),
                                        (delta, rows[rows >= n] - n))
             if len(sel)]
    table = parts[0] if len(parts) == 1 else FeatureTable.concat(parts)
    return reduce_result(st.sft, table, rows, q)[:2]


def _assert_same_column(name, got, want):
    assert type(got) is type(want), name
    assert got.type == want.type, name
    if want.valid is None:
        assert got.valid is None, name
    else:
        np.testing.assert_array_equal(got.valid, want.valid, err_msg=name)
    if isinstance(want, GeometryColumn):
        for part in ("x", "y", "bounds"):
            np.testing.assert_array_equal(
                getattr(got, part), getattr(want, part), err_msg=name)
        assert list(got.geometries()) == list(want.geometries()), name
        return
    assert got.values.dtype == want.values.dtype, name
    assert list(got.values) == list(want.values), name


def _assert_same(result, want):
    table, rows = want
    assert len(result.table) > 0
    np.testing.assert_array_equal(result.row_ids, rows)
    assert list(result.table.fids) == list(table.fids)
    assert list(result.table.columns) == list(table.columns)
    for name, col in table.columns.items():
        _assert_same_column(name, result.table.columns[name], col)


def _assert_copy_coherent(ds):
    """After any rebuild, ``features`` row ``rank[r]`` is main row ``r``."""
    st = ds._state("ev")
    z3 = st.indices["z3"]
    main = st.table
    assert len(z3.features) == len(main) == len(z3.rank)
    np.testing.assert_array_equal(z3.features.fids[z3.rank], main.fids)
    np.testing.assert_array_equal(np.sort(z3.rank), np.arange(len(main)))
    np.testing.assert_array_equal(z3.rank[z3.perm], np.arange(len(main)))
    for name, col in main.columns.items():
        _assert_same_column(name, z3.features.columns[name].take(z3.rank),
                            col)


@pytest.fixture(scope="module")
def ds():
    return _store()


class TestKeyedRows:
    @pytest.mark.parametrize("cql", SELECTS)
    def test_device_select_matches_arrival_take(self, ds, cql):
        r = ds.query("ev", cql)
        assert r.plan_info.index_name == "z3"
        _assert_same(r, _arrival_answer(ds, cql))

    @pytest.mark.parametrize("cql", SELECTS)
    def test_host_select_matches_arrival_take(self, ds, cql):
        """Circuit open: the backend's host path expands the plan and
        refines on the host."""
        try:
            ds._device_down_until = time.monotonic() + 3600.0
            r = ds.query("ev", cql)
        finally:
            ds._device_down_until = 0.0
        assert r.plan_info.index_name == "z3"
        _assert_same(r, _arrival_answer(ds, cql))

    def test_fid_residual_reads_keyed_fids(self, ds):
        fids = ", ".join(f"'f{i}'" for i in range(0, 6000, 7))
        q = Query(filter=f"{BOX} AND {DURING} AND IN ({fids})",
                  hints={"index": "z3"})
        r = ds.query("ev", q)
        assert r.plan_info.index_name == "z3"
        _assert_same(r, _arrival_answer(ds, q))

    @pytest.mark.parametrize("sort_by, limit", [
        (("v", True), 40), (("name", False), 25), (("dtg", False), None)])
    def test_sort_and_limit(self, ds, sort_by, limit):
        q = Query(filter=SELECTS[0], sort_by=sort_by, limit=limit)
        r = ds.query("ev", q)
        want = _arrival_answer(ds, q)
        _assert_same(r, want)
        if limit is not None:
            assert len(r.table) == limit

    def test_select_many_matches_arrival_take(self, ds):
        results = ds.select_many("ev", SELECTS)
        for cql, r in zip(SELECTS, results):
            _assert_same(r, _arrival_answer(ds, cql))

    def test_copy_is_key_ordered(self, ds):
        _assert_copy_coherent(ds)
        z3 = ds._state("ev").indices["z3"]
        order = np.lexsort((z3.zs, z3.bins))
        np.testing.assert_array_equal(order, np.arange(len(order)))
        # the copy's rows carry the keys the index sorted them by
        x = z3.features.geom_column().x
        np.testing.assert_array_equal(x, ds._state("ev").table.geom_column().x[z3.perm])


class TestKeyedRowsRebuilds:
    def test_delta_rows(self):
        ds = _store(n=3000, seed=7)
        _write_more(ds, 400, 3000, seed=8)
        assert ds._state("ev").delta.rows == 400
        from_delta = 0
        for cql in SELECTS:
            r = ds.query("ev", cql)
            from_delta += int((r.row_ids >= 3000).sum())
            _assert_same(r, _arrival_answer(ds, cql))
        assert from_delta > 0

    def test_second_compaction_merge_build(self):
        ds = _store(n=3000, seed=9)
        z3_before = ds._state("ev").indices["z3"]
        _write_more(ds, 700, 3000, seed=10)
        ds.compact("ev")
        st = ds._state("ev")
        assert st.indices["z3"] is not z3_before
        assert len(st.table) == 3700
        _assert_copy_coherent(ds)
        for cql in SELECTS:
            _assert_same(ds.query("ev", cql), _arrival_answer(ds, cql))

    def test_age_off_rebuilds_copy(self):
        ds = _store(n=3000, seed=11, ttl=True)
        cutoff = T0 + 3 * DAY
        removed = ds.age_off("ev", now_ms=cutoff + TTL_MS)
        assert removed > 0
        assert (ds._state("ev").table.dtg_millis() >= cutoff).all()
        _assert_copy_coherent(ds)
        for cql in SELECTS:
            _assert_same(ds.query("ev", cql), _arrival_answer(ds, cql))

    def test_delete_rebuilds_copy(self):
        ds = _store(n=3000, seed=12)
        gone = [f"f{i}" for i in range(0, 3000, 3)]
        assert ds.delete_features("ev", gone) == len(gone)
        _assert_copy_coherent(ds)
        for cql in SELECTS:
            r = ds.query("ev", cql)
            assert not set(gone) & set(r.table.fids)
            _assert_same(r, _arrival_answer(ds, cql))

    def test_schema_evolution_rebuilds_copy(self):
        ds = _store(n=2000, seed=14)
        ds.update_schema("ev", add="severity:Integer")
        _assert_copy_coherent(ds)
        assert "severity" in ds._state("ev").indices["z3"].features.columns
        for cql in SELECTS:
            _assert_same(ds.query("ev", cql), _arrival_answer(ds, cql))

    def test_oracle_backend_takes_through_copy(self):
        ds = _store(n=2000, seed=15, backend="oracle")
        _assert_copy_coherent(ds)
        for cql in SELECTS:
            _assert_same(ds.query("ev", cql), _arrival_answer(ds, cql))

    def test_z2_only_store_takes_from_main(self):
        ds = _store(n=3000, seed=16, spec=SPEC.replace("dtg:Date,", ""))
        indices = ds._state("ev").indices
        assert "z3" not in indices
        assert all(ix.features is None for ix in indices.values())
        for cql in ("BBOX(geom, -30, -20, 25, 20)",
                    "BBOX(geom, -30, -20, 25, 20) AND name = 'n3'"):
            _assert_same(ds.query("ev", cql), _arrival_answer(ds, cql))


def _reduce_counts(ds):
    c = ds.metrics.counter
    return (c("store.reduce.rows").count, c("store.reduce.rows_keyed").count)


class TestReduceCounters:
    def test_every_z3_select_row_is_keyed(self, ds):
        rows0, keyed0 = _reduce_counts(ds)
        want = 0
        for cql in SELECTS:
            want += len(ds.query("ev", cql).row_ids)
        for r in ds.select_many("ev", SELECTS):
            want += len(r.row_ids)
        rows1, keyed1 = _reduce_counts(ds)
        assert rows1 - rows0 == want > 0
        assert (keyed1 - keyed0) / (rows1 - rows0) == 1.0

    def test_z2_only_store_keys_nothing(self):
        ds = _store(n=2000, seed=17, spec=SPEC.replace("dtg:Date,", ""))
        rows0, keyed0 = _reduce_counts(ds)
        n = len(ds.query("ev", "BBOX(geom, -30, -20, 25, 20)").row_ids)
        rows1, keyed1 = _reduce_counts(ds)
        assert rows1 - rows0 == n > 0
        assert (keyed1 - keyed0) / (rows1 - rows0) == 0.0

    def test_delta_rows_are_not_counted(self):
        ds = _store(n=2000, seed=18)
        _write_more(ds, 300, 2000, seed=19)
        r = ds.query("ev", SELECTS[0])
        n_main = int((r.row_ids < 2000).sum())
        assert n_main < len(r.row_ids)
        assert _reduce_counts(ds) == (n_main, n_main)
