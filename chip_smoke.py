#!/usr/bin/env python3
"""Bring-up smoke: the DataStore main path, end to end, on the chip.

    python chip_smoke.py [--rows N] [--seed S] [--chips 4]

One process. It ingests GDELT-shaped points (bench.py's generator, from
``--seed``) through ``FeatureTable.from_columns`` → ``DataStore`` on an
explicit mesh → ``write`` → ``compact`` (the device sample sort at this
size), then answers bbox+time CQLs through every client entry point —
``query``, ``select_many``, ``count_many``, a density hint, a SQL
``GROUP BY``, the HTTP ``/query`` route — and a short standing-query
stream. Every answer is held to a numpy f64 brute-force referee over the
generated columns: row sets and exact counts equal, loose (int-domain)
counts within the coordinate quantization, f64 aggregates within
``ops.referee``'s tolerance (the chip keeps f64 emulated). Any mismatch,
any device failover/sort/load failure, an open device circuit, or a
query phase that made no device dispatch fails the run.

Progress goes to stdout as one JSON object per line; the LAST line is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed on a TPU. No TPU → exit 1 before any work. ``--chips 4`` runs only
the sharded path (ingest, compact, query, select_many, count_many and the
grouped aggregate on a 4-device mesh) and what it is compared with.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

SCHEMA = "EventRootCode:Integer,GoldsteinScale:Double,dtg:Date,*geom:Point"
TYPE = "gdelt"
QUERIES = 32  # bbox+time CQLs per phase (bench.py's make_queries mix)
_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}


class SmokeFailure(AssertionError):
    pass


def _emit(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _iso(ms: int) -> str:
    import datetime

    dt = datetime.datetime.fromtimestamp(ms / 1000, datetime.timezone.utc)
    return dt.strftime("%Y-%m-%dT%H:%M:%S.") + f"{int(ms) % 1000:03d}Z"


class _CompileClock:
    """Sums JAX's own compile-phase durations (trace, lowering, backend
    compile — a persistent-cache hit shows up as a short backend compile)
    and counts cache hits and misses."""

    def __init__(self):
        import jax.monitoring as jm

        self.secs = dict.fromkeys(_COMPILE_EVENTS.values(), 0.0)
        self.events = {"cache_hits": 0, "cache_misses": 0}
        jm.register_event_duration_secs_listener(self._on_duration)
        jm.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_kw):
        key = _COMPILE_EVENTS.get(name)
        if key:
            self.secs[key] += secs

    def _on_event(self, name, **_kw):
        tail = name.rsplit("/", 1)[-1]
        if tail in self.events:
            self.events[tail] += 1

    def report(self) -> dict:
        return {**{k: round(v, 3) for k, v in self.secs.items()},
                **self.events}


def _dispatches() -> int:
    """Device step calls so far, from the jaxmon per-step census."""
    from geomesa_tpu.obs import jaxmon

    snap = jaxmon.registry().snapshot()
    return sum(v.get("count", 0) for k, v in snap.items()
               if k.startswith("jax.jit.") and k.endswith(".calls"))


class _Phase:
    """Times one phase and counts the device dispatches it made."""

    def __init__(self, name: str, needs_device: bool = True, **info):
        self.name, self.needs_device, self.info = name, needs_device, info

    def __enter__(self):
        self.t0, self.d0 = time.perf_counter(), _dispatches()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        n = _dispatches() - self.d0
        _emit(phase=self.name, seconds=round(time.perf_counter() - self.t0, 3),
              device_dispatches=n, **self.info)
        _check(n > 0 or not self.needs_device,
               f"{self.name}: made no device dispatch")
        return False


def native_report() -> dict:
    """Whether each native library was built here from the committed
    ``.cpp`` (no ``.so`` beside it before this run) and loaded."""
    from geomesa_tpu import native

    out = {}
    for cpp in sorted(native._DIR.glob("*.cpp")):
        name = cpp.stem
        prebuilt = (native._DIR / f"lib{name}.so").exists()
        lib = native._load_lib(name)
        out[name] = {"prebuilt": prebuilt, "loaded": lib is not None}
    return out


def make_data(rows: int, seed: int):
    """GDELT-shaped columns: bench.py's clustered points + a root event
    code (1..20) and a Goldstein scale (-10..10, one decimal)."""
    import numpy as np

    from bench import synth_gdelt

    lon, lat, t = synth_gdelt(rows, seed=seed)
    rng = np.random.default_rng(seed + 1)
    code = rng.integers(1, 21, rows)
    gold = np.round(rng.uniform(-10.0, 10.0, rows), 1)
    return lon, lat, t, code, gold


def make_cqls(n: int, seed: int):
    from bench import make_queries

    boxes, windows = make_queries(n, seed=seed)
    cqls = [
        f"BBOX(geom, {x1}, {y1}, {x2}, {y2}) AND dtg DURING {_iso(lo)}/{_iso(hi)}"
        for (x1, y1, x2, y2), (lo, hi) in zip(boxes, windows)
    ]
    return boxes, windows, cqls


def referee_rows(lon, lat, t, boxes, windows) -> list:
    """Sorted row ids per query: the f64 brute-force mask (BBOX inclusive,
    DURING exclusive at both ends)."""
    import numpy as np

    out = []
    for (x1, y1, x2, y2), (lo, hi) in zip(boxes, windows):
        m = (t > lo) & (t < hi)
        m &= (lon >= x1) & (lon <= x2) & (lat >= y1) & (lat <= y2)
        out.append(np.flatnonzero(m))
    return out


def loose_bounds(lon, lat, t, boxes, windows) -> list:
    """(exact, widened) row counts per query: a loose (int-domain) answer
    must lie between them. The widened box/window adds one micro-degree
    and one second on every side — more than the 31-bit coordinate
    quantum (~1.7e-7 degrees) and the z3 week-bin offset unit (1 s)."""
    eps, eps_ms = 1e-6, 1000
    out = []
    for (x1, y1, x2, y2), (lo, hi) in zip(boxes, windows):
        exact = ((t > lo) & (t < hi) & (lon >= x1) & (lon <= x2)
                 & (lat >= y1) & (lat <= y2)).sum()
        wide = ((t > lo - eps_ms) & (t < hi + eps_ms)
                & (lon >= x1 - eps) & (lon <= x2 + eps)
                & (lat >= y1 - eps) & (lat <= y2 + eps)).sum()
        out.append((int(exact), int(wide)))
    return out


def _within(got, bounds, what: str) -> None:
    bad = [(i, g, b) for i, (g, b) in enumerate(zip(got, bounds))
           if not b[0] <= g <= b[1]]
    _check(not bad, f"{what}: (query, answer, (exact, widened)) {bad[:4]}")


def _fid_rows(table):
    import numpy as np

    return np.sort(np.asarray(table.fids).astype(np.int64))


def _same_rows(got, want, what: str) -> None:
    import numpy as np

    _check(np.array_equal(got, want),
           f"{what}: {len(got)} rows vs referee {len(want)}")


def build_store(chips: int, lon, lat, t, code, gold):
    import numpy as np

    from geomesa_tpu.parallel.mesh import make_mesh
    from geomesa_tpu.schema.columnar import Column, FeatureTable, point_column
    from geomesa_tpu.schema.sft import AttributeType, parse_spec
    from geomesa_tpu.store.backends import TpuBackend
    from geomesa_tpu.store.datastore import DataStore

    rows = len(lon)
    with _Phase("table", needs_device=False, rows=rows):
        sft = parse_spec(TYPE, SCHEMA)
        table = FeatureTable.from_columns(
            sft, np.arange(rows).astype(str).astype(object),
            {"EventRootCode": Column(AttributeType.INT, code.astype(np.int64)),
             "GoldsteinScale": Column(AttributeType.DOUBLE, gold),
             "dtg": Column(AttributeType.DATE, t.astype(np.int64)),
             "geom": point_column(lon, lat)},
        )
    # an explicit mesh: the run uses exactly `chips` devices even on a
    # host with more
    ds = DataStore(backend=TpuBackend(mesh=make_mesh(chips)))
    ds.create_schema(sft)
    # a bulk write past the compaction threshold compacts inside write();
    # the explicit compact is then a no-op, as it is for a user
    with _Phase("write_compact", needs_device=False, rows=rows,
                device_sort=ds._device_sorter(rows) is not None):
        ds.write(TYPE, table)
        ds.compact(TYPE)
    return ds


def residency(ds, chips: int) -> dict:
    """Device ids holding each resident index's columns."""
    state = ds._state(TYPE).backend_state or {}
    out = {}
    for name, dev in state.items():
        if dev is None:
            continue
        ids = sorted({d.id for c in dev.cols.values() for d in c.devices()})
        out[name] = ids
        _check(len(ids) == chips,
               f"index {name} resident on {ids}, want {chips} devices")
    _check(bool(out), "no index is device-resident after compact")
    return out


def phase_query(ds, cqls, ref) -> None:
    with _Phase("query", queries=len(cqls),
                rows=int(sum(len(r) for r in ref))):
        for i, c in enumerate(cqls):
            _same_rows(_fid_rows(ds.query(TYPE, c).table), ref[i],
                       f"query[{i}]")


def phase_select_many(ds, cqls, ref) -> None:
    with _Phase("select_many", queries=len(cqls)):
        res = ds.select_many(TYPE, cqls)
        for i, r in enumerate(res):
            _same_rows(_fid_rows(r.table), ref[i], f"select_many[{i}]")


def phase_count_many(ds, cqls, ref, bounds) -> None:
    """Exact counts (fused int count + edge-bucket gather, host re-test)
    must equal the referee; loose counts (the Pallas ``batched_count``
    scan, int-domain superset by contract) must lie within the
    quantization bounds."""
    with _Phase("count_many", queries=len(cqls)):
        got = [int(c) for c in ds.count_many(TYPE, cqls, loose=False)]
        want = [len(r) for r in ref]
        _check(got == want, f"count_many {got} vs referee {want}")
    with _Phase("count_many_loose", queries=len(cqls)):
        loose = [int(c) for c in ds.count_many(TYPE, cqls, loose=True)]
        _within(loose, bounds, "count_many loose")


def phase_density(ds, cqls, boxes, ref, bounds) -> None:
    """One density hint over the query's own box: every matched row lands
    in the grid, so the grid's mass is the exact count. Then the batched
    ``density_many`` pass (the MXU one-hot step) over a whole-world
    viewport, whose per-query mass is a loose count."""
    from geomesa_tpu.planning.planner import Query

    i = max(range(len(ref)), key=lambda k: len(ref[k]))
    with _Phase("density", query=i, rows=len(ref[i])):
        hint = {"bbox": tuple(boxes[i]), "width": 256, "height": 256}
        grid = ds.query(TYPE, Query(filter=cqls[i],
                                    hints={"density": hint})).density
        _check(grid.shape == (256, 256), f"density grid {grid.shape}")
        _check(float(grid.sum()) == float(len(ref[i])),
               f"density mass {float(grid.sum())} vs referee {len(ref[i])}")
    with _Phase("density_many", queries=len(cqls)):
        grids = ds.density_many(TYPE, cqls, (-180.0, -90.0, 180.0, 90.0))
        _within([int(round(float(g.sum()))) for g in grids], bounds,
                "density_many mass")


def phase_sql(ds, cqls, ref, code, gold, n_stmt: int = 3) -> None:
    """SQL GROUP BY through the fused device fold (segment-reduce per
    shard; psum, and all_gather for the extrema, across shards). The
    GeoBlocks pyramid, which answers repeated aggregations from host
    partials by design, is switched off for this phase with its operator
    knob so the device step is the one that answers."""
    import os

    from geomesa_tpu.ops.geoblocks import PYRAMID_ENV
    from geomesa_tpu.sql.engine import sql

    prev = os.environ.get(PYRAMID_ENV)
    os.environ[PYRAMID_ENV] = "0"
    try:
        _sql_checks(ds, sql, cqls, ref, code, gold, n_stmt)
    finally:
        if prev is None:
            os.environ.pop(PYRAMID_ENV, None)
        else:
            os.environ[PYRAMID_ENV] = prev


def _sql_checks(ds, sql, cqls, ref, code, gold, n_stmt) -> None:
    """Held to ``ops.referee.agg_equal``: exact counts, sums and extrema
    within its f64 tolerance. Whether the extrema came back bit-exact is
    reported alongside (the chip keeps f64 in an emulated form)."""
    import numpy as np

    from geomesa_tpu.ops.referee import agg_equal

    col = "GoldsteinScale"
    exact = total = 0
    worst = 0.0
    with _Phase("sql_group_by", statements=n_stmt) as ph:
        for i in range(n_stmt):
            res = sql(ds, (
                f"SELECT EventRootCode, COUNT(*) AS n, SUM({col}) AS s, "
                f"MIN({col}) AS lo, MAX({col}) AS hi "
                f"FROM {TYPE} WHERE {cqls[i]} GROUP BY EventRootCode"))
            live = {(int(k),): {"count": int(n), "cols": {
                col: [int(n), float(sm), float(lo), float(hi)]}}
                for k, n, sm, lo, hi in res.rows()}
            rows = ref[i]
            want = {}
            for k in np.unique(code[rows]):
                g = gold[rows[code[rows] == k]]
                want[(int(k),)] = {"count": len(g), "cols": {col: [
                    len(g), float(g.sum()), float(g.min()), float(g.max())]}}
            ok, why = agg_equal(live, want)
            _check(ok, f"sql[{i}]: {why}")
            for key, w in want.items():
                for a, b in zip(live[key]["cols"][col][2:],
                                w["cols"][col][2:]):
                    total += 1
                    exact += a == b
                    worst = max(worst, abs(a - b) / max(abs(b), 1e-300))
        ph.info.update(extrema=total, extrema_bit_exact=exact,
                       extrema_max_rel_diff=worst)


def phase_http(ds, cqls, ref, n_req: int = 3) -> None:
    """GET /api/schemas/<t>/query on an in-process threaded server."""
    import socketserver
    import threading
    import urllib.parse
    import urllib.request
    from wsgiref.simple_server import WSGIRequestHandler, WSGIServer, make_server

    import numpy as np

    from geomesa_tpu.web.app import GeoMesaApp

    class _Server(socketserver.ThreadingMixIn, WSGIServer):
        daemon_threads = True

    class _Quiet(WSGIRequestHandler):
        def log_message(self, *a):
            pass

    httpd = make_server("127.0.0.1", 0, GeoMesaApp(ds), server_class=_Server,
                        handler_class=_Quiet)
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    picks = sorted(range(len(ref)), key=lambda k: len(ref[k]))[:n_req]
    try:
        with _Phase("http", requests=n_req):
            base = f"http://127.0.0.1:{httpd.server_port}/api/schemas/{TYPE}/query"
            for i in picks:
                url = base + "?" + urllib.parse.urlencode({"cql": cqls[i]})
                with urllib.request.urlopen(url, timeout=300) as resp:
                    doc = json.loads(resp.read())
                got = np.sort(np.array([int(f["id"]) for f in doc["features"]],
                                       dtype=np.int64))
                _same_rows(got, ref[i], f"http[{i}]")
    finally:
        httpd.shutdown()
        httpd.server_close()
        th.join(timeout=10)


def phase_stream(seed: int, events: int = 20_000, n_subs: int = 4) -> None:
    """Standing bbox queries on a StreamingDataStore, one window of puts,
    drain: each subscription's delivered count equals the brute-force
    mask over the same events."""
    import numpy as np

    from bench import make_queries, synth_gdelt
    from geomesa_tpu.geometry.types import Point
    from geomesa_tpu.stream.datastore import StreamingDataStore

    lon, lat, t = synth_gdelt(events, seed=seed + 2)
    boxes, _ = make_queries(n_subs, seed=seed + 3)
    sds = StreamingDataStore()
    sds.create_schema("live", "dtg:Date,*geom:Point")
    counts = [0] * n_subs

    def on_hits(k):
        def cb(batch):
            counts[k] += int(batch.count)
        return cb

    with _Phase("stream", events=events, subscriptions=n_subs):
        for k, (x1, y1, x2, y2) in enumerate(boxes):
            sds.subscribe_query("live", f"BBOX(geom, {x1}, {y1}, {x2}, {y2})",
                                on_hits(k))
        for i in range(events):
            sds.put("live", str(i), {"dtg": int(t[i]),
                                     "geom": Point(float(lon[i]), float(lat[i]))},
                    ts=int(t[i]))
        _check(sds.drain("live", timeout_s=300), "stream drain timed out")
        sds.close()
        want = [int(((lon >= x1) & (lon <= x2) & (lat >= y1) & (lat <= y2)).sum())
                for x1, y1, x2, y2 in boxes]
        _check(counts == want, f"stream hits {counts} vs referee {want}")


def device_health(ds) -> dict:
    m = ds.metrics
    health = {
        "device_failovers": m.counter("store.query.device_failovers").count,
        "sort_failures": m.counter("store.device.sort_failures").count,
        "load_failures": m.counter("store.device.load_failures").count,
        "circuit_open": m.gauge("store.device.circuit_open").value,
    }
    _check(not any(health.values()), f"device path degraded: {health}")
    return health


def peak_hbm(devices) -> dict:
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        out[d.id] = stats.get("peak_bytes_in_use")
    return out


def run(rows: int, n_queries: int, seed: int, chips: int) -> dict:
    """Every phase for ``chips`` devices; raises SmokeFailure on a wrong
    answer or a degraded device path."""
    import jax

    lon, lat, t, code, gold = make_data(rows, seed)
    boxes, windows, cqls = make_cqls(n_queries, seed + 7)
    ref = referee_rows(lon, lat, t, boxes, windows)
    bounds = loose_bounds(lon, lat, t, boxes, windows)
    ds = build_store(chips, lon, lat, t, code, gold)
    _emit(phase="residency", devices=residency(ds, chips))
    phase_query(ds, cqls, ref)
    phase_select_many(ds, cqls, ref)
    phase_count_many(ds, cqls, ref, bounds)
    phase_sql(ds, cqls, ref, code, gold)
    if chips == 1:
        phase_density(ds, cqls, boxes, ref, bounds)
        phase_http(ds, cqls, ref)
        phase_stream(seed)
    health = device_health(ds)
    ds.close()
    return {"health": health,
            "peak_hbm_bytes": peak_hbm(jax.devices()[:chips])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=16_000_000)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 1

    from geomesa_tpu.utils.compile_cache import enable_compile_cache

    clock = _CompileClock()
    cache_dir = enable_compile_cache()
    _emit(phase="start", kind=dev.device_kind, chips=args.chips,
          rows=args.rows, queries=QUERIES, seed=args.seed,
          jax=jax.__version__, compile_cache=cache_dir,
          native=native_report())
    t0 = time.perf_counter()
    try:
        out = run(args.rows, QUERIES, args.seed, args.chips)
    except SmokeFailure as e:
        _emit(phase="failed", error=str(e))
        return 1
    _emit(phase="done", seconds=round(time.perf_counter() - t0, 3),
          compile=clock.report(), **out)
    # the device as JAX reports it: one chip by default, four with --chips 4
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
