"""Spatial join: points × polygons (the ``JoinProcess`` / batched ST_Within role).

Two paths (SURVEY.md §2.14 TPU mapping):

- :func:`join_within` — exact: per-polygon index-planned scan (z2 ranges) +
  f64 residual predicate. The oracle-parity path.
- :func:`join_within_device` — bulk: whole point store against all polygons
  via the f32 device kernel (:mod:`geomesa_tpu.ops.join`), returning counts;
  ~1e-5 deg edge tolerance (BASELINE config #4's throughput shape).
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.filter import ast
from geomesa_tpu.planning.planner import Query


def join_scan(ds, type_name: str, geoms, pred: str = "within", filter=None,
              auths=None):
    """Per-geometry index-planned scans: yields (geom_index, result table).

    The shared core of the exact join paths (JoinProcess and the SQL
    engine's spatial JOIN): each right-side geometry becomes ONE planned
    query of the left store — Z/XZ ranges + residual — never a cartesian
    pass. ``pred`` is the predicate applied to the LEFT geometry column
    (within/contains/intersects); ``None`` geometries yield empty results.
    ``auths`` scopes every planned query to the caller's row visibility.
    """
    sft = ds.get_schema(type_name)
    base = None
    if filter is not None:
        from geomesa_tpu.filter.cql import parse

        base = parse(filter) if isinstance(filter, str) else filter
    for i, g in enumerate(geoms):
        if g is None:
            yield i, None
            continue
        f = ast.SpatialOp(pred, sft.geom_field, g)
        if base is not None:
            f = ast.And([f, base])
        yield i, ds.query(type_name, Query(filter=f, auths=auths)).table


def join_within(ds, type_name: str, polygons, filter=None):
    """Exact join: returns list of (polygon_index, row fids ndarray)."""
    return [
        (i, t.fids if t is not None else np.empty(0, dtype=object))
        for i, t in join_scan(ds, type_name, polygons, "within", filter)
    ]


def join_rows_device(ds, type_name: str, geoms, pred: str = "within",
                     chunk_budget: int = 32_000_000):
    """Distributed EXACT spatial join returning row sets per right geometry.

    The mesh path of the SQL engine's spatial JOIN (``GeoMesaRelation.scala:
    94`` / ``SQLRules.scala`` role, VERDICT r2 item 6): the z2-sorted device
    layout is cut into fixed blocks; per geometry, only the blocks its bbox
    z-ranges touch are tested (host planning, ``polygon_block_plan``), an
    int-domain bbox gather compacts candidate rows on device (a SUPERSET —
    normalize is monotone), and the exact f64 predicate runs host-side on
    the few candidates. One device dispatch per chunk, not per geometry.

    Returns ``(snapshot_table, [(i, rows), ...])`` — the coherent snapshot
    table the row indices refer to (main tier, plus pending delta rows
    appended when the store is live; a racing compaction cannot skew them)
    and, per geometry ``i`` in order, the matching row indices. TTL-expired
    rows are filtered host-side on the candidates, and pending hot-tier
    rows are predicate-tested host-side and spliced in — live stores stay
    on the mesh path. Raises ValueError when the store/layout cannot take
    the device path (caller falls back to :func:`join_scan`); device
    errors propagate for the caller's circuit breaker.

    ``chunk_budget``: max int32 lanes per gather dispatch (bounds HBM).
    """
    import jax.numpy as jnp

    from geomesa_tpu.curve.normalize import lat as norm_lat, lon as norm_lon
    from geomesa_tpu.geometry import predicates as P
    from geomesa_tpu.ops.join import (
        make_block_bbox_count_step,
        make_block_bbox_gather_step,
        polygon_block_plan,
    )
    from geomesa_tpu.parallel.mesh import data_shards
    from geomesa_tpu.store.backends import JOIN_BLOCK, REFINE_PRECISION, TpuBackend

    if pred not in ("within", "intersects"):
        raise ValueError(f"device join: unsupported predicate {pred!r}")
    if not isinstance(ds.backend, TpuBackend) or not ds._device_available():
        raise ValueError("device join: TPU backend unavailable")
    import time as _time

    _t0 = _time.perf_counter()
    st = ds._state(type_name)
    main, indices, backend_state, _stats, delta = st.snapshot()
    dev = (backend_state or {}).get("z2")
    z2 = indices.get("z2")
    if dev is None or z2 is None or main is None or len(main) == 0:
        raise ValueError("device join: no z2 device residency")
    # age-off: expired rows still sit in the device layout; filter them
    # host-side on the (few) candidates so mesh and host paths agree
    ttl = ds._age_off_ttl_ms(st.sft)
    cutoff_ms = None
    main_dtg = None
    if ttl is not None:
        if st.sft.dtg_field is None:
            raise ValueError("device join: TTL without dtg field")
        import time as _time

        cutoff_ms = int(_time.time() * 1000) - ttl
        main_dtg = main.dtg_millis()
    block = JOIN_BLOCK
    if dev.rows_per_shard % block:
        raise ValueError("device join: layout not block-aligned")
    mesh = ds.backend._get_mesh()
    shards = data_shards(mesh)
    nlon = norm_lon(REFINE_PRECISION)
    nlat = norm_lat(REFINE_PRECISION)
    col = main.geom_column()
    perm = z2.perm

    # f64 bboxes for planning; int-domain bboxes for the device test
    k = len(geoms)
    bbox_deg = np.zeros((k, 4))
    ibox = np.zeros((k, 4), dtype=np.int32)
    empty = np.zeros(k, dtype=bool)
    for i, g in enumerate(geoms):
        if g is None:
            empty[i] = True
            continue
        x1, y1, x2, y2 = g.bbox
        bbox_deg[i] = (x1, y1, x2, y2)
        ibox[i] = (
            int(nlon.normalize(x1)), int(nlon.normalize(x2)),
            int(nlat.normalize(y1)), int(nlat.normalize(y2)),
        )

    count_step = make_block_bbox_count_step(mesh, block)
    true_n = jnp.int32(len(main))
    out: list[tuple[int, np.ndarray]] = []
    # chunk geometries so D × Kc × capacity stays inside the lane budget;
    # kc_limit persists across budget-overflow retries (halving a local kc
    # that is recomputed each iteration would loop forever)
    start = 0
    kc_limit = 1024
    while start < k:
        # plan a provisional chunk, then size capacity from real counts
        kc = min(k - start, kc_limit)
        sel = np.arange(start, start + kc)
        blk, nblk = polygon_block_plan(
            z2.zs, bbox_deg[sel], block, dev.rows_per_shard, shards
        )
        dev_blk = jnp.asarray(blk)
        dev_nblk = jnp.asarray(nblk)
        dev_ibox = jnp.asarray(ibox[sel])
        counts = np.asarray(
            # chunked by the lane budget on purpose: the geometry set can
            # exceed what one launch may materialize, and the overflow retry
            # (kc_limit halving) needs the per-chunk counts on host
            # tpusync: disable-next-line=S003
            count_step(dev.cols["x"], dev.cols["y"], true_n,
                       dev_blk, dev_nblk, dev_ibox)
        )  # (D, Kc)
        cap = max(int(counts.max()), 1)
        cap = 1 << (cap - 1).bit_length()  # pow2: bounded compile variants
        if shards * kc * cap > chunk_budget:
            # split the chunk instead of materializing an oversized buffer
            if kc == 1:
                # single huge geometry: exact host scan for just this one
                if empty[start]:
                    out.append((start, np.empty(0, dtype=np.int64)))
                    start += 1
                    continue
                g = geoms[start]
                m = (
                    P.points_within_geom(col.x, col.y, g)
                    if pred == "within"
                    else P.points_intersect_geom(col.x, col.y, g)
                )
                if main_dtg is not None:
                    m &= main_dtg >= cutoff_ms
                out.append((start, np.nonzero(m)[0]))
                start += 1
                continue
            kc_limit = max(1, kc // 2)
            continue
        gather = make_block_bbox_gather_step(mesh, block, cap)
        # second dispatch of the count+gather pair; same chunking rationale
        # tpusync: disable-next-line=S003
        pos, hits = gather(
            dev.cols["x"], dev.cols["y"], true_n, dev_blk, dev_nblk, dev_ibox
        )
        pos = np.asarray(pos)   # (D, Kc, cap) global sorted positions
        hits = np.asarray(hits)
        for j in range(kc):
            gi = start + j
            if empty[gi]:
                out.append((gi, np.empty(0, dtype=np.int64)))
                continue
            cand = np.concatenate(
                [pos[d, j, : hits[d, j]] for d in range(shards)]
            ).astype(np.int64)
            rows = perm[cand]  # sorted-order → original row indices
            g = geoms[gi]
            m = (
                P.points_within_geom(col.x[rows], col.y[rows], g)
                if pred == "within"
                else P.points_intersect_geom(col.x[rows], col.y[rows], g)
            )
            if main_dtg is not None:
                m &= main_dtg[rows] >= cutoff_ms
            out.append((gi, rows[m]))
        start += kc
        # regrow gradually after success: a hard reset to 1024 would re-pay
        # the whole halving descent (a plan + count dispatch per halving)
        # for every chunk under a tight budget
        kc_limit = min(1024, kc_limit * 2)

    _observe_join(ds, type_name, "block", _t0,
                  sum(len(r) for _, r in out))
    if delta is None or not len(delta):
        return main, out

    # pending hot-tier rows: few (bounded by the compaction threshold) —
    # evaluate the exact predicate host-side and splice them in, same as
    # the live-store KNN merge. Row indices >= len(main) address the delta
    # part of the returned combined snapshot table.
    from geomesa_tpu.schema.columnar import FeatureTable

    dcol = delta.geom_column()
    d_keep = np.ones(len(delta), dtype=bool)
    if dcol.valid is not None:
        d_keep &= dcol.valid
    if cutoff_ms is not None:
        d_keep &= delta.dtg_millis() >= cutoff_ms
    combined = FeatureTable.concat([main, delta])
    n_main = len(main)
    merged: list[tuple[int, np.ndarray]] = []
    for gi, rows in out:
        g = geoms[gi]
        if g is None or not d_keep.any():
            merged.append((gi, rows))
            continue
        dm = (
            P.points_within_geom(dcol.x, dcol.y, g)
            if pred == "within"
            else P.points_intersect_geom(dcol.x, dcol.y, g)
        ) & d_keep
        extra = n_main + np.nonzero(dm)[0]
        merged.append((gi, np.concatenate([rows, extra]) if len(extra) else rows))
    return combined, merged


def join_within_device(ds, type_name: str, polygons, max_vertices: int = 64):
    """Bulk join counts: (K,) ndarray of points-inside counts per polygon.

    Runs the f32 crossing-number kernel over the full point store on device.
    """
    import time as _time

    import jax.numpy as jnp

    from geomesa_tpu.ops.join import pack_polygons, points_in_polygons_count

    _t0 = _time.perf_counter()

    ds.compact(type_name)  # bulk path scans the main tier only
    st = ds._state(type_name)
    if st.table is None or len(st.table) == 0:
        return np.zeros(len(polygons), dtype=np.int32)
    col = st.table.geom_column()
    if col.x is None:
        raise ValueError("device join requires a point geometry store")
    verts, bbox, _ = pack_polygons(polygons, max_vertices)
    counts = points_in_polygons_count(
        jnp.asarray(col.x.astype(np.float32)),
        jnp.asarray(col.y.astype(np.float32)),
        jnp.asarray(verts),
        jnp.asarray(bbox),
    )
    counts = np.asarray(counts)
    _observe_join(ds, type_name, "dense", _t0, int(counts.sum()))
    return counts


def _observe_join(ds, type_name: str, route: str, t0: float,
                  rows: int) -> None:
    """Record one join execution under its plan signature
    (``join:block`` / ``join:dense``) — the cost model's training signal
    for :func:`join_counts_auto`'s route choice."""
    import time as _time

    from geomesa_tpu.obs import devmon

    devmon.costs().observe_cost(
        type_name, f"join:{route}",
        wall_ms=(_time.perf_counter() - t0) * 1000.0, rows=rows,
    )


def measured_pair_density(ds, type_name: str, geoms) -> float | None:
    """MEASURED candidate-pair density of a join: candidate rows a z2
    range plan admits (searchsorted over the HOST z2 keys — no block
    expansion, no device work) over the brute-force
    ``points x geometries`` pair count, clamped to [0, 1]. None when the
    store has no z2 device layout to plan against (the block route can't
    run at all)."""
    from geomesa_tpu.ops.join import planned_candidate_rows
    from geomesa_tpu.store.backends import TpuBackend

    if not isinstance(ds.backend, TpuBackend):
        return None
    st = ds._state(type_name)
    main, indices, backend_state, _stats, _delta = st.snapshot()
    dev = (backend_state or {}).get("z2")
    z2 = indices.get("z2")
    if dev is None or z2 is None or main is None or not len(main):
        return None
    k = sum(1 for g in geoms if g is not None)
    if k == 0:
        return 0.0
    bbox_deg = np.array(
        [g.bbox for g in geoms if g is not None], dtype=np.float64
    )
    # searchsorted row-count estimate — the block route (if chosen) does
    # its own full block planning exactly once, not twice
    cand = planned_candidate_rows(z2.zs, bbox_deg)
    return min(float(int(cand.sum())) / float(len(main) * k), 1.0)


def join_counts_auto(ds, type_name: str, polygons, max_vertices: int = 64):
    """Adaptive join counts: per-polygon points-inside counts via the
    route the cost model picks — ``"block"`` (the index-pruned
    block-sparse gather + exact f64 host refine,
    :func:`join_rows_device`) or ``"dense"`` (the full f32
    crossing-number pass, :func:`join_within_device`). Returns
    ``(counts (K,) int64, route)``.

    The seed comes from the MEASURED pair density (how many candidate
    rows the block plan would actually test): sparse joins — polygons
    touching few z2 blocks — seed the block route, dense ones the full
    pass. Observed wall per route lands under the ``join:block`` /
    ``join:dense`` plan signatures, so once both routes are trained the
    measured p50 decides, and the model's probe cadence re-measures the
    loser (docs/planning.md). Note the documented f32 tolerance of the
    dense kernel (~1e-5 deg at polygon edges); callers needing exact
    parity should call :func:`join_rows_device` directly."""
    from geomesa_tpu.planning import costmodel

    density = measured_pair_density(ds, type_name, polygons)
    route = "dense"
    if density is not None:
        route = costmodel.model().choose_join_path(type_name, density)
    if route == "block":
        try:
            _snap, pairs = join_rows_device(ds, type_name, polygons)
            counts = np.zeros(len(polygons), dtype=np.int64)
            for i, rows in pairs:
                counts[i] = len(rows)
            return counts, route
        except ValueError:
            route = "dense"  # layout can't take the block path after all
    return (
        np.asarray(
            join_within_device(ds, type_name, polygons, max_vertices),
            dtype=np.int64,
        ),
        route,
    )
