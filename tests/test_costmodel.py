"""Adaptive cost-model planner (ISSUE 9, ROADMAP item 3).

Covers the decision engine (seeded ranking, learned override, bounded
probe cadence, SLO tie-breaking), the planner golden grid (strategy
choice across a selectivity × index-availability grid, the cost-model
override path, the cheap-select fast path), the residual-mask refine
parity, the select dispatch-route fast path (singleton select through
the batched planned steps — red/green pinned against the oracle), the
join route choice, and calibration reporting."""

import numpy as np
import pytest

from geomesa_tpu.filter import ast
from geomesa_tpu.filter.cql import parse as parse_cql
from geomesa_tpu.geometry import Point
from geomesa_tpu.obs import devmon, plantable
from geomesa_tpu.obs.devmon import ResidencyLedger
from geomesa_tpu.obs.plantable import PlanTable
from geomesa_tpu.planning import costmodel
from geomesa_tpu.planning.costmodel import Candidate, CostModel
from geomesa_tpu.planning.planner import (
    CHEAP_MAX_RANGES,
    CHEAP_SELECT_ROWS,
    Query,
    QueryPlanner,
    StrategyDecider,
    build_indices,
)
from geomesa_tpu.schema.sft import parse_spec
from geomesa_tpu.stats.store_stats import StoreStats
from geomesa_tpu.store.datastore import DataStore

T0 = 1_498_867_200_000
SPEC = "name:String,age:Integer:index=true,dtg:Date,*geom:Point"


@pytest.fixture()
def fresh():
    """Isolated residency ledger + plan table for the test; restored
    after."""
    prev = devmon.install(ResidencyLedger())
    prev_table = plantable.install()
    yield
    devmon.install(prev)
    plantable.install(prev_table)


def _fill(ds, n=3000, seed=7, type_name="evt"):
    rng = np.random.default_rng(seed)
    recs = [
        {
            "name": f"n{i % 40}",
            "age": int(rng.integers(0, 100)),
            "dtg": T0 + int(rng.integers(0, 10 * 86_400_000)),
            "geom": Point(float(rng.uniform(-180, 180)),
                          float(rng.uniform(-90, 90))),
        }
        for i in range(n)
    ]
    ds.write(type_name, recs, fids=[f"f{i}" for i in range(n)])
    ds.compact(type_name)


def _store(n=3000, backend="tpu"):
    ds = DataStore(backend=backend)
    ds.create_schema(parse_spec("evt", SPEC))
    _fill(ds, n)
    return ds


def _planner_fixture(n=3000, indices=None):
    """(sft, indices, stats) over a synthetic table — planner-only tests
    need no store."""
    ds = _store(n)
    st = ds._state("evt")
    idx = dict(st.indices)
    if indices is not None:
        idx = {k: v for k, v in idx.items() if k in indices}
    return st.sft, idx, st.stats


# ---------------------------------------------------------------------------
# decision engine
# ---------------------------------------------------------------------------

class TestCostModelChoose:
    def test_seeded_ranking_before_training(self, fresh):
        m = CostModel(table=PlanTable())
        win, ranked, source = m.choose("t", "d", [
            Candidate("a", "sig:a", seed_ms=2.0),
            Candidate("b", "sig:b", seed_ms=1.0),
        ])
        assert (win.name, source) == ("b", "stats")
        assert [c.name for c in ranked] == ["b", "a"]

    def test_learned_override_beats_seeds(self, fresh):
        ct = PlanTable()
        m = CostModel(table=ct)
        # seeds say "b"; measurements say "a" is 10x faster
        for _ in range(10):
            ct.observe_cost("t", "sig:a", wall_ms=1.0)
            ct.observe_cost("t", "sig:b", wall_ms=10.0)
        win, _, source = m.choose("t", "d", [
            Candidate("a", "sig:a", seed_ms=2.0),
            Candidate("b", "sig:b", seed_ms=1.0),
        ])
        assert (win.name, source) == ("a", "cost-model")

    def test_partial_training_stays_on_seeds(self, fresh):
        ct = PlanTable()
        m = CostModel(table=ct)
        for _ in range(10):
            ct.observe_cost("t", "sig:a", wall_ms=1.0)  # only one side trained
        win, _, source = m.choose("t", "d", [
            Candidate("a", "sig:a", seed_ms=2.0),
            Candidate("b", "sig:b", seed_ms=1.0),
        ], probe=False)
        assert (win.name, source) == ("b", "stats")

    def test_probe_cadence_remeasures_loser(self, fresh):
        ct = PlanTable()
        m = CostModel(table=ct)
        for _ in range(10):
            ct.observe_cost("t", "sig:a", wall_ms=1.0)
            ct.observe_cost("t", "sig:b", wall_ms=10.0)
        picks = [
            m.choose("t", "d", [
                Candidate("a", "sig:a", seed_ms=1.0),
                Candidate("b", "sig:b", seed_ms=2.0),
            ])[0].name
            for _ in range(2 * costmodel.PROBE_EVERY)
        ]
        assert picks.count("b") == 2  # exactly the two scheduled probes
        # the probe consults carry source "probe"
        srcs = [
            m.choose("t", "d2", [
                Candidate("a", "sig:a", seed_ms=1.0),
                Candidate("b", "sig:b", seed_ms=2.0),
            ])[2]
            for _ in range(costmodel.PROBE_EVERY)
        ]
        assert srcs.count("probe") == 1

    def test_probe_bounded_by_seed_ratio(self, fresh):
        """A candidate seeded catastrophically worse than the winner is
        never probed — bounded exploration."""
        m = CostModel(table=PlanTable())
        picks = [
            m.choose("t", "d", [
                Candidate("cheap", "sig:a", seed_ms=1.0),
                Candidate("scan", "sig:b",
                          seed_ms=costmodel.PROBE_MAX_RATIO * 100.0),
            ])[0].name
            for _ in range(2 * costmodel.PROBE_EVERY)
        ]
        assert picks.count("scan") == 0

    def test_slo_tie_break_prefers_low_variance(self, fresh):
        ct = PlanTable()
        m = CostModel(table=ct)
        # a: faster p50, fat tail; b: near-tied p50, tight tail
        for i in range(20):
            ct.observe_cost("t", "sig:a", wall_ms=40.0 if i == 0 else 10.0)
            ct.observe_cost("t", "sig:b", wall_ms=11.0)
        norm, _, _ = m.choose("t", "d1", [
            Candidate("a", "sig:a", seed_ms=1.0),
            Candidate("b", "sig:b", seed_ms=2.0),
        ], probe=False)
        burn, _, src = m.choose("t", "d2", [
            Candidate("a", "sig:a", seed_ms=1.0),
            Candidate("b", "sig:b", seed_ms=2.0),
        ], under_burn=True, probe=False)
        assert norm.name == "a"  # p50 wins un-burned
        assert (burn.name, src) == ("b", "cost-model/slo")

    def test_select_route_flips_with_observations(self, fresh):
        ct = PlanTable()
        m = CostModel(table=ct)
        assert m.choose_select_route("t") == "twopass"  # seeded default
        for _ in range(10):
            ct.observe_cost("t", "sel:twopass", wall_ms=20.0)
            ct.observe_cost("t", "sel:planned", wall_ms=2.0)
        assert m.choose_select_route("t") == "planned"

    def test_join_route_seeds_by_density(self, fresh):
        m = CostModel(table=PlanTable())
        assert m.choose_join_path("t", 0.01) == "block"
        assert m.choose_join_path("t2", 0.9) == "dense"


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------

class TestCalibration:
    def test_record_and_report(self, fresh):
        m = plantable.table()
        for _ in range(4):  # the prior: a 10 ms p50 worth calibrating
            m.observe_cost("t", "z3:iv8:rows", wall_ms=10.0)
        # predicted 10 vs actual 8, then predicted 10 vs actual 10
        m.record_query("t", "z3:iv8:rows", 8.0, planned=True)
        m.record_query("t", "z3:iv8:rows", 10.0, planned=True)
        rep = m.calibration_report()
        assert rep["entry_count"] == 1 and rep["samples"] == 2
        e = rep["entries"][0]
        assert e["count"] == 2
        assert e["mean_abs_rel_err"] == pytest.approx(0.125, abs=1e-3)
        assert e["mean_signed_rel_err"] == pytest.approx(0.125, abs=1e-3)
        assert rep["overall_mean_abs_rel_err"] == pytest.approx(
            0.125, abs=1e-3)

    def test_queries_feed_calibration(self, fresh):
        """The audit path records predicted-vs-actual once the plan shape
        has a usable prior."""
        ds = _store(2000)
        cql = "BBOX(geom, -60, -30, 60, 30)"
        for _ in range(8):
            ds.query("evt", cql)
        rep = plantable.table().calibration_report()
        assert rep["samples"] >= 1
        assert any(e["type"] == "evt" for e in rep["entries"])

    def test_explain_analyze_renders_calibration_and_alternatives(
            self, fresh):
        ds = _store(2000)
        cql = ("BBOX(geom, -60, -30, 60, 30) AND "
               "dtg AFTER 2017-07-02T00:00:00Z")
        for _ in range(5):
            ds.query("evt", cql)
        ea = ds.explain("evt", cql, analyze=True)
        assert ea.cost["calibration_error"] is not None
        assert ea.cost["strategy_source"]
        # the z3/z2 decision has at least one rejected alternative
        assert ea.cost["alternatives"]
        text = str(ea)
        assert "calibration error" in text
        assert "Rejected:" in text

    def test_schema_delete_purges_calibration(self, fresh):
        ds = _store(2000)
        for _ in range(6):
            ds.query("evt", "BBOX(geom, -60, -30, 60, 30)")
        ds.delete_schema("evt")
        assert not any(
            e["type"] == "evt"
            for e in plantable.table().calibration_report()["entries"]
        )


# ---------------------------------------------------------------------------
# planner golden grid
# ---------------------------------------------------------------------------

BOX_TIME = ("BBOX(geom, -60, -30, 60, 30) AND "
            "dtg DURING 2017-07-02T00:00:00Z/2017-07-05T00:00:00Z")
BOX_ONLY = "BBOX(geom, -60, -30, 60, 30)"
ATTR_EQ = "age = 17"


class TestPlannerGolden:
    """Strategy choice pinned across selectivity × index availability."""

    def _choose(self, cql, indices=None, stats=True, hints=None,
                cost_model=None, type_name="evt", under_burn=False):
        sft, idx, st_stats = _planner_fixture(indices=indices)
        f = parse_cql(cql)
        from geomesa_tpu.filter.bounds import coerce_attr_bounds, extract

        e = extract(f, sft.geom_field, sft.dtg_field,
                    attrs=tuple(n.split(":", 1)[1] for n in idx
                                if n.startswith("attr:")))
        e = coerce_attr_bounds(sft, e)
        dec = {}
        name, _ = StrategyDecider.choose(
            idx, e, f, hints or {}, st_stats if stats else None,
            type_name=type_name, cost_model=cost_model,
            under_burn=under_burn, decision=dec,
        )
        return name, dec

    def test_grid_spatiotemporal_prefers_z3(self, fresh):
        name, dec = self._choose(BOX_TIME)
        assert name == "z3" and dec["source"] == "stats"

    def test_grid_spatial_only_prefers_z2(self, fresh):
        name, _ = self._choose(BOX_ONLY)
        assert name == "z2"

    def test_grid_spatial_only_without_z2_takes_z3(self, fresh):
        name, _ = self._choose(BOX_ONLY, indices=["z3", "id"])
        assert name == "z3"

    def test_grid_selective_attr_equality_wins(self, fresh):
        # ~1% selectivity on the attr index vs a loose half-world box:
        # the penalized attr estimate still undercuts the spatial cover
        name, dec = self._choose(f"{ATTR_EQ} AND BBOX(geom,-179,-89,179,89)")
        assert name == "attr:age"
        assert dec["est_rows"] <= 3000 * 0.05

    def test_grid_no_stats_heuristic(self, fresh):
        name, dec = self._choose(BOX_TIME, stats=False)
        assert name == "z3" and dec["source"] == "heuristic"

    def test_grid_forced_hint_wins(self, fresh):
        name, dec = self._choose(BOX_TIME, hints={"index": "z2"})
        assert name == "z2" and dec["source"] == "forced"

    def test_cost_model_override_flips_choice(self, fresh):
        """Stats prefer z3 for bbox+time; inject measurements proving z2
        serves this type faster — the trained model overrides."""
        ct = PlanTable()
        model = CostModel(table=ct)
        for _ in range(10):
            ct.observe_cost("evt", "z3:iv64:rows", wall_ms=50.0)
            ct.observe_cost("evt", "z2:iv64:rows", wall_ms=1.0)
            ct.observe_cost("evt", "attr:age:rows", wall_ms=2.0)
        name, dec = self._choose(BOX_TIME, cost_model=model)
        assert name == "z2"
        assert dec["source"] == "cost-model"
        assert dec["predicted_ms"] is not None
        # rejected alternatives carry their estimates + observations
        alt_names = {a["name"] for a in dec["alternatives"]}
        assert "z3" in alt_names
        z3_alt = next(a for a in dec["alternatives"] if a["name"] == "z3")
        assert z3_alt["observed_ms_p50"] == pytest.approx(50.0, rel=0.2)

    def test_strategy_probe_cadence(self, fresh):
        """Every PROBE_EVERY-th strategy consult re-measures the losing
        index (bounded: seeds within PROBE_MAX_RATIO)."""
        ct = PlanTable()
        model = CostModel(table=ct)
        for _ in range(10):
            ct.observe_cost("evt", "z3:iv64:rows", wall_ms=1.0)
            ct.observe_cost("evt", "z2:iv64:rows", wall_ms=5.0)
        picks = [
            self._choose(BOX_TIME, cost_model=model)[0]
            for _ in range(2 * costmodel.PROBE_EVERY)
        ]
        assert picks.count("z3") > picks.count("z2") > 0

    def test_cheap_fast_path_reduces_range_budget(self, fresh):
        """High-selectivity selects skip the union search and decompose
        with the reduced budget; loose ones keep the full machinery."""
        sft, idx, stats = _planner_fixture()
        planner = QueryPlanner(sft, idx, stats, cost_model=False)
        # a tiny box: estimate ≪ CHEAP_SELECT_ROWS
        _, _, info = planner.plan(Query(filter=(
            "BBOX(geom, 10, 10, 10.5, 10.5) AND "
            "dtg DURING 2017-07-02T00:00:00Z/2017-07-02T06:00:00Z")))
        assert info.cheap
        assert info.n_intervals <= CHEAP_MAX_RANGES
        assert any("cheap fast path" in n for n in info.notes)
        # a loose half-world box: not cheap once the threshold sits
        # below its estimate (test stores are far smaller than the
        # production absolute threshold)
        import geomesa_tpu.planning.planner as planner_mod

        saved = planner_mod.CHEAP_SELECT_ROWS
        planner_mod.CHEAP_SELECT_ROWS = 100
        try:
            _, _, info2 = planner.plan(Query(filter=BOX_ONLY))
        finally:
            planner_mod.CHEAP_SELECT_ROWS = saved
        assert not info2.cheap
        assert info2.est_rows > 100

    def test_cheap_path_results_identical(self, fresh):
        """Red/green: the reduced range budget only widens the int-domain
        superset — result rows are identical to the oracle referee."""
        ds = _store(3000)
        ref = DataStore(backend="oracle")
        ref.create_schema(parse_spec("evt", SPEC))
        _fill(ref)
        cql = ("BBOX(geom, 10, 10, 14, 14) AND "
               "dtg DURING 2017-07-02T00:00:00Z/2017-07-03T00:00:00Z")
        r = ds.query("evt", cql)
        assert r.plan_info.cheap
        assert sorted(r.table.fids.tolist()) == sorted(
            ref.query("evt", cql).table.fids.tolist())

    def test_static_explain_renders_strategy_block(self, fresh):
        ds = _store(2000)
        text = ds.explain("evt", BOX_TIME)
        assert "Strategy:" in text
        assert "Rejected:" in text


# ---------------------------------------------------------------------------
# residual mask (the refine fast path)
# ---------------------------------------------------------------------------

class TestResidualMask:
    def _table(self, n=500):
        ds = _store(n)
        return ds._state("evt").table

    @pytest.mark.parametrize("cql", [
        "BBOX(geom, -60, -30, 60, 30)",
        "BBOX(geom, -60, -30, 60, 30) AND dtg AFTER 2017-07-04T00:00:00Z",
        "age BETWEEN 10 AND 40",
        "name = 'n3' OR age > 90",
        "NOT (age < 50)",
        "INTERSECTS(geom, POLYGON((-10 -10, 10 -10, 10 10, -10 10, -10 -10)))",
        "name LIKE 'n1%'",
        "age IS NULL",
        "IN ('f1', 'f7', 'f99')",
        "INCLUDE",
    ])
    def test_parity_with_full_take(self, cql):
        table = self._table()
        f = parse_cql(cql)
        rng = np.random.default_rng(3)
        rows = np.sort(rng.choice(len(table), size=200, replace=False))
        got = ast.residual_mask(f, table, rows)
        want = np.asarray(f.mask(table.take(rows)), dtype=bool)
        assert got.dtype == np.bool_
        assert (got == want).all()

    def test_opaque_node_falls_back(self):
        class Weird(ast.Filter):
            def mask(self, table):
                return np.arange(len(table)) % 2 == 0

        table = self._table(100)
        rows = np.arange(0, 100, 3)
        got = ast.residual_mask(Weird(), table, rows)
        want = Weird().mask(table.take(rows))
        assert (got == want).all()

    def test_column_refs(self):
        f = parse_cql("BBOX(geom,0,0,1,1) AND (age > 3 OR name = 'x')")
        props, fids, opaque = ast.column_refs(f)
        assert props == {"geom", "age", "name"}
        assert not fids and not opaque
        props, fids, _ = ast.column_refs(parse_cql("IN ('a','b')"))
        assert fids and not props


# ---------------------------------------------------------------------------
# select dispatch route (the bench-6 fast path)
# ---------------------------------------------------------------------------

class TestSelectRoute:
    def test_singleton_planned_route_red_green(self, fresh):
        """Red/green pin: force the planned route (the batched block-pair
        steps run with a singleton batch) and require byte-identical row
        sets vs the oracle referee — the fast path must never change
        results, only cost."""
        ds = _store(4000)
        ref = DataStore(backend="oracle")
        ref.create_schema(parse_spec("evt", SPEC))
        _fill(ref, 4000)
        cql = ("BBOX(geom, -90, -45, 90, 45) AND "
               "dtg DURING 2017-07-02T00:00:00Z/2017-07-06T00:00:00Z")
        ct = devmon.costs()
        # train the table so the planned route wins outright
        for _ in range(10):
            ct.observe_cost("evt", "sel:planned", wall_ms=1.0)
            ct.observe_cost("evt", "sel:twopass", wall_ms=50.0)
        assert costmodel.model().choose_select_route("evt") == "planned"
        got = ds.query("evt", cql)
        want = ref.query("evt", cql)
        assert sorted(got.table.fids.tolist()) == sorted(
            want.table.fids.tolist())
        # the dispatch observed its route (planned gains an observation
        # beyond the 10 injected)
        assert ct.predict("evt", "sel:planned")["observations"] >= 11

    def test_route_observations_accumulate(self, fresh):
        ds = _store(2000)
        for _ in range(4):
            ds.query("evt", "BBOX(geom, -60, -30, 60, 30)")
        p = devmon.costs().predict("evt", "sel:twopass")
        assert p is not None and p["observations"] >= 4

    def test_exec_cache_reused_on_cached_plans(self, fresh):
        """The plan-cache-hit path memoizes the dispatch payload: the
        second identical query reuses the staged split instead of
        re-deriving it (and results stay identical)."""
        ds = _store(2000)
        cql = "BBOX(geom, -60, -30, 60, 30)"
        r1 = ds.query("evt", cql)
        st = ds._state("evt")
        key = ds._plan_cache_key(Query(filter=cql))
        plan, _, _ = st.plan_cache[key]
        assert plan.exec_cache  # populated by the first dispatch
        memo_before = dict(plan.exec_cache)
        r2 = ds.query("evt", cql)
        assert plan.exec_cache == memo_before  # reused, not rebuilt
        assert sorted(r1.table.fids.tolist()) == sorted(
            r2.table.fids.tolist())


# ---------------------------------------------------------------------------
# stats selectivity API
# ---------------------------------------------------------------------------

class TestSelectivity:
    def test_fraction_and_rows_compose(self, fresh):
        ds = _store(3000)
        stats: StoreStats = ds._state("evt").stats
        assert stats.selectivity(parse_cql("INCLUDE")) == pytest.approx(
            1.0, abs=0.05)
        # a superset cover estimate: may overshoot the true 0.5 fraction
        half = stats.selectivity(parse_cql("BBOX(geom,-180,-90,0,90)"))
        assert 0.3 < half < 0.85
        tiny = stats.selectivity(parse_cql("BBOX(geom,10,10,10.2,10.2)"))
        assert tiny < 0.01
        # attribute bounds compose via min
        both = stats.selectivity(
            parse_cql("age = 17 AND BBOX(geom,-180,-90,0,90)"))
        assert both <= min(
            half, stats.selectivity(parse_cql("age = 17"))) + 1e-9

    def test_disjoint_is_zero(self, fresh):
        ds = _store(1000)
        stats = ds._state("evt").stats
        assert stats.estimate_filter_rows(parse_cql("age = 5 AND age = 9")) \
            == 0.0

    def test_stats_count_uses_shared_estimator(self, fresh):
        ds = _store(3000)
        est = ds.stats_count("evt", "BBOX(geom,-180,-90,0,90)")
        stats = ds._state("evt").stats
        assert est == pytest.approx(
            stats.estimate_filter_rows(parse_cql("BBOX(geom,-180,-90,0,90)")))


# ---------------------------------------------------------------------------
# join route
# ---------------------------------------------------------------------------

class TestJoinRoute:
    def _poly_store(self, n=3000):
        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("evt", SPEC))
        _fill(ds, n)
        from geomesa_tpu.geometry.types import Polygon

        ring = np.array([[-20.0, -20.0], [20.0, -20.0], [20.0, 20.0],
                         [-20.0, 20.0], [-20.0, -20.0]])
        return ds, [Polygon(ring)]

    def test_join_counts_auto_routes_and_records(self, fresh):
        from geomesa_tpu.process.join import (
            join_counts_auto,
            measured_pair_density,
        )

        ds, polys = self._poly_store()
        density = measured_pair_density(ds, "evt", polys)
        assert density is not None and 0.0 < density <= 1.0
        counts, route = join_counts_auto(ds, "evt", polys)
        assert route in ("block", "dense")
        # parity vs the exact host predicate
        from geomesa_tpu.geometry import predicates as P

        col = ds._state("evt").table.geom_column()
        want = int(P.points_within_geom(col.x, col.y, polys[0]).sum())
        assert int(counts[0]) == want
        # the run recorded its route signature
        assert devmon.costs().predict("evt", f"join:{route}") is not None

    def test_join_route_flips_with_observations(self, fresh):
        from geomesa_tpu.process.join import join_counts_auto

        ds, polys = self._poly_store()
        ct = devmon.costs()
        for _ in range(10):
            ct.observe_cost("evt", "join:block", wall_ms=50.0)
            ct.observe_cost("evt", "join:dense", wall_ms=1.0)
        counts, route = join_counts_auto(ds, "evt", polys)
        assert route == "dense"


# ---------------------------------------------------------------------------
# zero-recompile census (the steady select path)
# ---------------------------------------------------------------------------

class TestZeroRecompiles:
    def test_steady_select_path_zero_recompiles(self, fresh):
        """THE J003 contract for the adaptive select route: once BOTH
        dispatch routes are warm (one full probe cycle covers the planned
        singleton route too), further queries — including the scheduled
        probes — add no new compile signatures and never recompile
        (jaxmon census pin). The fast path must share the batched steps'
        jit cache, not grow its own."""
        from geomesa_tpu.obs import jaxmon

        ds = _store(2000)
        cql = ("BBOX(geom, -60, -30, 60, 30) AND "
               "dtg DURING 2017-07-02T00:00:00Z/2017-07-06T00:00:00Z")
        # warm: one full probe cycle exercises twopass AND the planned
        # probe consult, compiling every shape the steady path can touch
        for _ in range(costmodel.PROBE_EVERY + 2):
            ds.query("evt", cql)
        before = jaxmon.jit_report()
        for _ in range(costmodel.PROBE_EVERY + 2):
            ds.query("evt", cql)
        after = jaxmon.jit_report()
        assert (after.get("recompiles", 0) - before.get("recompiles", 0)) == 0
        assert set(after["steps"]) == set(before["steps"])


# ---------------------------------------------------------------------------
# review-pass regression pins
# ---------------------------------------------------------------------------

class TestReviewPins:
    def test_probe_plans_never_cached(self, fresh):
        """A probe-tick plan deliberately took the LOSING strategy; caching
        it would replay the loser for every later identical query. The
        plan store must skip it (and the next identical query caches a
        normal plan)."""
        ds = _store(1000)
        st = ds._state("evt")
        planner = QueryPlanner(st.sft, st.indices, st.stats,
                               cost_model=False)
        q = Query(filter="BBOX(geom, -10, -10, 10, 10)")
        plan, f, info = planner.plan(q)
        info.strategy_source = "probe"
        key = ds._plan_cache_key(q)
        ds._plan_store(st, st.indices, key, (plan, f, info))
        assert key not in st.plan_cache
        info.strategy_source = "cost-model"
        ds._plan_store(st, st.indices, key, (plan, f, info))
        assert key in st.plan_cache

    def test_zero_seed_floor_skips_probe(self, fresh):
        """A 0-row best estimate gives the PROBE_MAX_RATIO bound nothing
        to anchor on: the probe is skipped, never unbounded."""
        m = CostModel(table=PlanTable())
        picks = [
            m.choose("t", "d", [
                Candidate("tiny", "sig:a", est_rows=0.0),
                Candidate("scan", "sig:b", est_rows=1e7),
            ])[0].name
            for _ in range(2 * costmodel.PROBE_EVERY)
        ]
        assert picks.count("scan") == 0

    def test_wide_plan_payload_not_memoized(self, fresh):
        """Dispatch payloads above the slot cap re-derive per query
        instead of pinning unaccounted device arrays in the plan cache."""
        from geomesa_tpu.store import backends as B

        ds = _store(2000)
        saved = B._EXEC_MEMO_MAX_SLOTS
        B._EXEC_MEMO_MAX_SLOTS = 1  # force every payload over the cap
        try:
            cql = "BBOX(geom, -60, -30, 60, 30)"
            r1 = ds.query("evt", cql)
            st = ds._state("evt")
            plan, _, _ = st.plan_cache[ds._plan_cache_key(Query(filter=cql))]
            assert not plan.exec_cache  # over the cap: nothing pinned
            r2 = ds.query("evt", cql)  # still correct, just re-derived
            assert sorted(r1.table.fids.tolist()) == sorted(
                r2.table.fids.tolist())
        finally:
            B._EXEC_MEMO_MAX_SLOTS = saved
