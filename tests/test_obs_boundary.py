"""Spans at the host↔device boundary: the backend's stage / sync / unpack
children of ``dispatch``, the batched fan-outs' span skeleton, the
``telemetry`` span around the per-query audit, the profiler bridge that
puts every span on the device trace's clock, and the XLA module names of
the observed step programs."""

import glob
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from geomesa_tpu import obs
from geomesa_tpu.geometry import Point
from geomesa_tpu.obs import ledger, plantable
from geomesa_tpu.parallel.mesh import DATA_AXIS, QUERY_AXIS
from geomesa_tpu.schema.sft import parse_spec
from geomesa_tpu.store.datastore import DataStore

T0 = 1_498_867_200_000


def _cql(i: int) -> str:
    """A distinct bbox+time CQL per ``i`` (no plan-cache repeats)."""
    x = -45.0 + i
    return (f"BBOX(geom,{x},-40,{x + 30},40) AND dtg DURING "
            "2017-07-01T00:00:00Z/2017-07-01T00:05:00Z")


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable()
    obs.drain()
    yield
    obs.disable()
    obs.drain()


@pytest.fixture(scope="module")
def ds():
    rng = np.random.default_rng(11)
    store = DataStore(backend="tpu")
    store.create_schema(parse_spec("pts", "name:String,dtg:Date,*geom:Point"))
    store.write("pts", [
        {"name": f"n{i % 3}", "dtg": T0 + i * 700,
         "geom": Point(float(rng.uniform(-50, 50)),
                       float(rng.uniform(-50, 50)))}
        for i in range(600)
    ])
    store.compact("pts")
    return store


def _names(span) -> list:
    return [c.name for c in span.children]


# -- DataStore.query: dispatch children and telemetry ------------------------

@pytest.mark.parametrize("route", ["twopass", "planned"])
def test_query_dispatch_children(ds, monkeypatch, route):
    from geomesa_tpu.planning import costmodel

    monkeypatch.setattr(costmodel.model(), "choose_select_route",
                        lambda type_name: route)
    with obs.collect("probe") as root:
        res = ds.query("pts", _cql(1 if route == "twopass" else 2))
    assert res.count > 0
    (q,) = root.children
    assert q.name == "query"
    assert _names(q).count("telemetry") == 1
    (disp,) = q.find("dispatch")
    below = {s.name for s in disp.walk()} - {"dispatch"}
    assert {"stage", "jit", "sync", "unpack"} <= below
    # every stage of the backend's host work has its own label: the
    # dispatch span itself keeps no child-less stretch of note
    assert len(disp.find("stage")) == 1 and len(disp.find("unpack")) == 1


def test_telemetry_wraps_the_audit(ds, monkeypatch):
    seen = []
    real = DataStore._audit

    def spy(self, *a, **k):
        seen.append(obs.current().name)
        return real(self, *a, **k)

    monkeypatch.setattr(DataStore, "_audit", spy)
    with obs.collect("probe"):
        ds.query("pts", _cql(3))
        ds.select_many("pts", [_cql(4), _cql(5)])
    assert seen == ["telemetry"] * 3


# -- the batched fan-outs: one skeleton a call --------------------------------

def test_count_many_span_tree(ds):
    cqls = [_cql(10 + i) for i in range(6)]
    want = [ds.query("pts", q).count for q in cqls]
    with obs.collect("probe") as root:
        got = ds.count_many("pts", cqls)
    assert got == want
    (cm,) = root.children
    assert cm.name == "count_many" and cm.attrs["n_queries"] == 6
    # ONE of each per call, never one per CQL
    assert _names(cm) == ["plan", "stage", "jit", "sync", "telemetry"]
    assert cm.find("query") == []


def test_density_many_span_tree(ds):
    with obs.collect("probe") as root:
        grids = ds.density_many("pts", [_cql(20), _cql(21)],
                                bbox=(-50, -50, 50, 50), width=16, height=16)
    assert len(grids) == 2 and grids[0].sum() > 0
    (dm,) = root.children
    assert dm.name == "density_many"
    assert _names(dm) == ["plan", "stage", "jit", "sync", "telemetry"]


def test_aggregate_many_span_tree(ds, monkeypatch):
    from geomesa_tpu.ops import geoblocks

    # the fused device pass, not the pre-aggregation pyramid
    monkeypatch.setenv(geoblocks.PYRAMID_ENV, "0")
    with obs.collect("probe") as root:
        out = ds.aggregate_many("pts", [_cql(30)], group_by=["name"])
    assert out[0] is not None and out[0]["count"].sum() > 0
    (am,) = root.children
    assert am.name == "aggregate_many"
    names = _names(am)
    for stage in ("plan", "stage", "jit", "telemetry"):
        assert names.count(stage) == 1, names
    assert "sync" in names


def test_count_many_charges_the_ledger(ds):
    # compile the batch's shape outside the measured table
    ds.count_many("pts", [_cql(40 + i) for i in range(4)])
    prev = plantable.install()
    try:
        ds.count_many("pts", [_cql(44 + i) for i in range(4)])
        rows = [e for e in ledger.table().export()["entries"]
                if e["type"] == "pts"]
    finally:
        plantable.install(prev)
    # the batch's shared ledger is charged once per member query, as
    # select_many's is: each carries the one dispatch and the one sync
    assert sum(e["queries"] for e in rows) == 4
    assert sum(e["dispatches"] for e in rows) == 4
    assert sum(e["syncs"] for e in rows) == 4
    assert all(e["compiles"] == 0 for e in rows)


# -- the profiler bridge -------------------------------------------------------

def test_spans_in_a_profiler_capture(ds, tmp_path):
    ds.query("pts", _cql(50))  # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.request"), \
                    obs.collect("bench.request"):
                ds.query("pts", _cql(51 + i))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    events.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    # the caller's own annotation only: the bridged root is prefixed
    requests = sorted(events["bench.request"])
    assert len(requests) == 3
    assert len(events[obs.trace.BRIDGE_PREFIX + "bench.request"]) == 3
    for name in ("query", "plan", "dispatch", "telemetry"):
        spans = sorted(events[obs.trace.BRIDGE_PREFIX + name])
        assert len(spans) == 3, name
        for (s, e), (rs, re_) in zip(spans, requests):
            assert rs <= s <= e <= re_, name


def test_no_jax_spans_still_record():
    code = (
        "import sys\n"
        "from geomesa_tpu import obs\n"
        "with obs.collect('root') as root:\n"
        "    with obs.span('plan'):\n"
        "        with obs.span('stage'):\n"
        "            pass\n"
        "assert [s.name for s in root.walk()] == ['root', 'plan', 'stage']\n"
        "assert all(s.t1_ns >= s.t0_ns > 0 for s in root.walk())\n"
        "assert not any(m == 'jax' or m.startswith('jax.')"
        " for m in sys.modules), 'jax imported'\n"
        "print('ok')\n"
    )
    env = {**os.environ, "GEOMESA_TPU_NO_JAX": "1"}
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == "ok"


# -- step programs lower to modules named after their census ------------------

N, C, Q, BOX, TIM = 8192, 128, 4, 8, 16


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), (DATA_AXIS, QUERY_AXIS))


def _arg(mesh, shape, dtype=jnp.int32, spec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _step_cases():
    from geomesa_tpu.parallel import query as pq
    from geomesa_tpu.trajectory.state import cached_track_stats_step

    def cols(m, n):
        return [_arg(m, (N,), spec=P(DATA_AXIS)) for _ in range(n)]

    def select(m, n):
        return (*cols(m, n), _arg(m, (1, C), spec=P(DATA_AXIS, None)),
                _arg(m, (1,), spec=P(DATA_AXIS)),
                _arg(m, (BOX, 4)), _arg(m, (TIM, 4)))

    def batched(m, n, q=Q):
        return (*cols(m, n), _arg(m, ()), _arg(m, (q, BOX, 4)),
                _arg(m, (q, TIM, 4)))

    return {
        "select_count": (lambda m: pq.cached_select_count_step(m),
                         lambda m: select(m, 4)),
        "select_gather": (lambda m: pq.cached_select_gather_step(m, C),
                          lambda m: select(m, 4)),
        "select_count_bbox": (lambda m: pq.cached_select_count_step_bbox(m),
                              lambda m: select(m, 6)),
        "select_gather_bbox": (
            lambda m: pq.cached_select_gather_step_bbox(m, C),
            lambda m: select(m, 6)),
        "batched_count": (lambda m: pq.cached_batched_count_step(m),
                          lambda m: batched(m, 4)),
        "batched_edge_gather": (
            lambda m: pq.cached_batched_edge_gather_step(m, C),
            lambda m: batched(m, 4)),
        "batched_overlap": (
            lambda m: pq.cached_batched_overlap_step(m, with_time=True),
            lambda m: batched(m, 6)),
        "planned_count": (
            lambda m: pq.cached_planned_count_step(m, Q, 4096, 8),
            lambda m: (*cols(m, 4), _arg(m, ()), _arg(m, (1, 8)),
                       _arg(m, (1, 8)), _arg(m, (1, Q, BOX, 4)),
                       _arg(m, (1, Q, TIM, 4)))),
        "planned_gather": (
            lambda m: pq.cached_planned_gather_step(m, 4096, 8, C),
            lambda m: (*cols(m, 4), _arg(m, ()), _arg(m, (8,)),
                       _arg(m, (8,)), _arg(m, (Q, BOX, 4)),
                       _arg(m, (Q, TIM, 4)))),
        "batched_density": (
            lambda m: pq.cached_batched_density_step(m, 16, 16),
            lambda m: (*batched(m, 4), _arg(m, (Q, 4)))),
        "batched_knn": (
            lambda m: pq.cached_batched_knn_step(m, 4),
            lambda m: (*[_arg(m, (N,), spec=P(DATA_AXIS))] * 2, _arg(m, ()),
                       _arg(m, (Q,), jnp.float32, P(QUERY_AXIS)),
                       _arg(m, (Q,), jnp.float32, P(QUERY_AXIS)))),
        "matrix_scan_q8": (
            lambda m: pq.cached_matrix_scan_step(m, 4, 8),
            lambda m: (*cols(m, 4), _arg(m, ()), _arg(m, (8, 2, 4)),
                       _arg(m, (8, 2, 4)))),
        # the trajectory plane's unsharded step (trajectory/state.py)
        "track_stats_n64_e8": (
            lambda m: cached_track_stats_step(64, 8),
            lambda m: (*[jax.ShapeDtypeStruct((64,), jnp.float32)] * 3,
                       jax.ShapeDtypeStruct((64,), jnp.int32),
                       jax.ShapeDtypeStruct((64,), jnp.bool_),
                       jax.ShapeDtypeStruct((), jnp.float32))),
    }


@pytest.mark.parametrize("name", sorted(_step_cases()))
def test_step_lowers_to_named_module(name):
    factory, args = _step_cases()[name]
    mesh = _mesh()
    wrapper = factory(mesh)
    first = wrapper.__wrapped__.lower(*args(mesh)).as_text().splitlines()[0]
    assert first.startswith(f"module @jit_{name} "), first
