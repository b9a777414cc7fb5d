"""Batched point-in-polygon kernels for spatial joins (ST_Within / ST_Contains).

Reference: the Spark ST_* UDFs evaluate JTS predicates per row
(``geomesa-spark-jts/.../udf/SpatialRelationFunctions.scala`` — SURVEY.md
§2.14); the billion-row join plan (BASELINE config #4) maps each polygon over
the point set. TPU re-design: polygons are padded to a fixed vertex count and
``lax.map``-ped over a crossing-number kernel vectorized across all points —
K × V × N elementwise ops on the VPU, partial counts psum-merged when sharded.

Precision note: the device kernel computes in f32 (degrees). Points within
~1e-5 deg of a polygon edge can classify differently than the f64 oracle —
callers needing exact parity route candidates through the host refine
(:func:`geomesa_tpu.process.join.join_within`), which uses these counts only
as a prefilter.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from geomesa_tpu.geometry.types import MultiPolygon, Polygon


def pack_polygons(polygons, max_vertices: int = 64):
    """Polygon list → (verts (K, V, 2) f32, bbox (K, 4) f32, nverts (K,)).

    Shells only (holes are rare in join workloads; holed polygons should take
    the exact host path). Rings are closed; padding repeats the last vertex
    (zero-length edges never change crossing parity).
    """
    k = len(polygons)
    verts = np.zeros((k, max_vertices, 2), dtype=np.float32)
    bbox = np.zeros((k, 4), dtype=np.float32)
    nverts = np.zeros(k, dtype=np.int32)
    for i, p in enumerate(polygons):
        if isinstance(p, MultiPolygon):  # largest part; exact path for the rest
            p = max(p.parts, key=lambda q: len(q.shell))
        if not isinstance(p, Polygon):
            raise ValueError(f"expected polygon, got {p.geom_type}")
        ring = p.shell
        if len(ring) > max_vertices:
            raise ValueError(
                f"polygon {i} has {len(ring)} vertices > max {max_vertices}"
            )
        verts[i, : len(ring)] = ring
        verts[i, len(ring) :] = ring[-1]
        nverts[i] = len(ring)
        bbox[i] = p.bbox
    return verts, bbox, nverts


def _membership(x, y, ring, bb):
    """(N,) bool: points inside one closed ring ∩ its bbox (crossing number).

    The single shared kernel body — count and mask variants derive from it so
    precision/edge fixes can never diverge between them.
    """
    in_bb = (x >= bb[0]) & (x <= bb[2]) & (y >= bb[1]) & (y <= bb[3])
    x1 = ring[:-1, 0][:, None]  # (V-1, 1)
    y1 = ring[:-1, 1][:, None]
    x2 = ring[1:, 0][:, None]
    y2 = ring[1:, 1][:, None]
    straddle = (y1 > y[None, :]) != (y2 > y[None, :])
    dy = y2 - y1
    safe_dy = jnp.where(dy == 0, 1.0, dy)
    xint = x1 + (y[None, :] - y1) * (x2 - x1) / safe_dy
    crossing = straddle & (x[None, :] < xint)
    inside = (crossing.sum(axis=0) % 2).astype(bool)
    return inside & in_bb


@jax.jit
def points_in_polygons_count(x, y, verts, bbox):
    """Counts of points strictly inside each polygon (f32 crossing number).

    Args:
      x, y: (N,) f32 point coords (degrees).
      verts: (K, V, 2) f32 closed rings (padded).
      bbox: (K, 4) f32 [xmin, ymin, xmax, ymax].

    Returns (K,) int32 counts. jittable / shard_map-able (psum the counts).
    """
    return jax.lax.map(
        lambda poly: _membership(x, y, poly[0], poly[1]).sum(dtype=jnp.int32),
        (verts, bbox),
    )


@jax.jit
def points_in_polygons_mask(x, y, verts, bbox):
    """(K, N) bool membership masks — for small K where the full matrix fits."""
    return jax.lax.map(lambda poly: _membership(x, y, poly[0], poly[1]), (verts, bbox))


# ---------------------------------------------------------------------------
# Index-pruned block-sparse join (the 1B × 10K scale path, VERDICT r1 item 4)
# ---------------------------------------------------------------------------


def planned_candidate_rows(sorted_z2: np.ndarray, bbox_deg,
                           max_ranges: int = 16, sfc=None) -> np.ndarray:
    """Per-polygon candidate row counts a z2 range plan admits —
    searchsorted over the HOST sorted keys, no block expansion and no
    device work, so a route decision can measure pair density without
    paying the full :func:`polygon_block_plan` it may then skip. Counts
    are pre-block-rounding (a lower bound on what the block join tests);
    adequate as a density seed."""
    from geomesa_tpu.curve.sfc import Z2SFC

    sfc = sfc or Z2SFC()
    out = np.zeros(len(bbox_deg), dtype=np.int64)
    for p, (xmin, ymin, xmax, ymax) in enumerate(bbox_deg):
        zr = sfc.ranges(
            [(float(xmin), float(ymin), float(xmax), float(ymax))],
            max_ranges=max_ranges,
        )
        if len(zr) == 0:
            continue
        starts = np.searchsorted(sorted_z2, zr[:, 0], side="left")
        ends = np.searchsorted(sorted_z2, zr[:, 1], side="right")
        out[p] = int(np.maximum(ends - starts, 0).sum())
    return out


_BUCKETS = (16, 32, 64, 128, 256, 512)


def pack_polygons_bucketed(polygons, buckets=_BUCKETS):
    """Group polygons by vertex-count bucket (pow2 padding tiers).

    Returns a list of (ids (Kb,) int64, verts (Kb, V, 2) f32, bbox (Kb, 4)
    f32, nverts (Kb,) int32) — one entry per non-empty bucket. Removes the
    round-1 hard cap at 64 vertices: each tier compiles its own kernel shape.
    """
    groups: dict[int, list[int]] = {}
    shells = []
    for i, p in enumerate(polygons):
        if isinstance(p, MultiPolygon):
            p = max(p.parts, key=lambda q: len(q.shell))
        if not isinstance(p, Polygon):
            raise ValueError(f"expected polygon, got {p.geom_type}")
        shells.append(p)
        nv = len(p.shell)
        for b in buckets:
            if nv <= b:
                groups.setdefault(b, []).append(i)
                break
        else:
            raise ValueError(
                f"polygon {i} has {nv} vertices > max bucket {buckets[-1]}"
            )
    out = []
    for b in sorted(groups):
        ids = np.asarray(groups[b], dtype=np.int64)
        verts, bbox, nverts = pack_polygons(
            [shells[i] for i in ids], max_vertices=b
        )
        out.append((ids, verts, bbox, nverts))
    return out


def polygon_block_plan(
    sorted_z2: np.ndarray,
    bbox_deg: np.ndarray,
    block: int,
    rows_per_shard: int,
    n_shards: int,
    max_ranges: int = 16,
    sfc=None,
):
    """Host planning for the block-sparse join: per-polygon z2 ranges →
    per-shard LOCAL candidate block ids.

    The store is z2-sorted and cut into fixed blocks of ``block`` rows
    (``rows_per_shard`` must be a multiple of ``block``). A polygon's
    candidate set is every block its bbox z-ranges touch — the TPU analog of
    the reference planning ranges per query then batch-scanning them
    (SURVEY.md §2.20 P4): fewer, fatter ranges; block granularity keeps
    device shapes fixed.

    Returns (blk (D, K, MB) int32 local block ids, nblk (D, K) int32) with
    MB padded to a power of two; padding slots repeat block 0 and are masked
    by ``nblk``.
    """
    from geomesa_tpu.curve.sfc import Z2SFC

    if rows_per_shard % block:
        raise ValueError(f"rows_per_shard {rows_per_shard} % block {block} != 0")
    sfc = sfc or Z2SFC()
    k = len(bbox_deg)
    blocks_per_shard = rows_per_shard // block
    per_shard: list[list[np.ndarray]] = [[] for _ in range(n_shards)]
    max_blocks = 1
    for p in range(k):
        xmin, ymin, xmax, ymax = bbox_deg[p]
        # bboxes arrive f32-rounded (pack_polygons); widen by one f32 ulp so
        # points whose f64 coords sit just past a rounded-down edge (but whose
        # f32 rounding lands inside) are never pruned out of the cover
        xmin = float(np.nextafter(np.float32(xmin), np.float32(-np.inf)))
        ymin = float(np.nextafter(np.float32(ymin), np.float32(-np.inf)))
        xmax = float(np.nextafter(np.float32(xmax), np.float32(np.inf)))
        ymax = float(np.nextafter(np.float32(ymax), np.float32(np.inf)))
        zr = sfc.ranges([(xmin, ymin, xmax, ymax)], max_ranges=max_ranges)
        if len(zr) == 0:
            for d in range(n_shards):
                per_shard[d].append(np.empty(0, dtype=np.int64))
            continue
        starts = np.searchsorted(sorted_z2, zr[:, 0], side="left")
        ends = np.searchsorted(sorted_z2, zr[:, 1], side="right")
        keep = ends > starts
        b_lo = starts[keep] // block
        b_hi = (ends[keep] - 1) // block + 1
        # expand spans → unique global block ids (vectorized repeat-arange)
        lens = b_hi - b_lo
        tot = int(lens.sum())
        if tot == 0:
            gids = np.empty(0, dtype=np.int64)
        else:
            gids = np.unique(
                np.repeat(b_lo, lens)
                + (np.arange(tot) - np.repeat(np.cumsum(lens) - lens, lens))
            )
        owner = gids // blocks_per_shard
        for d in range(n_shards):
            local = gids[owner == d] - d * blocks_per_shard
            per_shard[d].append(local)
            if len(local) > max_blocks:
                max_blocks = len(local)
    mb = 1
    while mb < max_blocks:
        mb <<= 1
    blk = np.zeros((n_shards, k, mb), dtype=np.int32)
    nblk = np.zeros((n_shards, k), dtype=np.int32)
    for d in range(n_shards):
        for p in range(k):
            ids = per_shard[d][p]
            blk[d, p, : len(ids)] = ids
            nblk[d, p] = len(ids)
    return blk, nblk


@lru_cache(maxsize=None)
def make_block_bbox_count_step(mesh, block: int):
    """Pass 1 of the row-returning block join: per-shard counts of rows in
    each polygon's int-domain bbox, over the planned candidate blocks only.

    fn(x, y, true_n, blk (D, K, MB), nblk (D, K), ibox (K, 4) int32
    [xmin, xmax, ymin, ymax]) → (D, K) int32 per-shard counts. The int
    test is a SUPERSET of the f64 bbox (normalize is monotone), so a host
    residual on the gathered rows is exact — the same two-phase contract
    as distributed select (SURVEY.md §7)."""
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from geomesa_tpu.parallel.mesh import DATA_AXIS

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS), P(DATA_AXIS), P(),
            P(DATA_AXIS, None, None), P(DATA_AXIS, None), P(),
        ),
        out_specs=P(DATA_AXIS, None),
        check_vma=False,
    )
    def step(x, y, true_n, blk, nblk, ibox):
        n = x.shape[0]
        base = jax.lax.axis_index(DATA_AXIS) * n
        mb = blk.shape[2]

        def one(args):
            b_ids, nb, bb = args
            take = b_ids[:, None] * block + jnp.arange(block, dtype=jnp.int32)
            take = take.reshape(-1)
            live = (
                (jnp.arange(mb, dtype=jnp.int32) < nb)[:, None]
                .repeat(block, axis=1).reshape(-1)
            ) & ((base + take) < true_n)
            xs = x[take]
            ys = y[take]
            inside = (
                (xs >= bb[0]) & (xs <= bb[1]) & (ys >= bb[2]) & (ys <= bb[3])
            )
            return (inside & live).sum(dtype=jnp.int32)

        return jax.lax.map(one, (blk[0], nblk[0], ibox))[None, :]

    return step


@lru_cache(maxsize=None)
def make_block_bbox_gather_step(mesh, block: int, capacity: int):
    """Pass 2: compact each polygon's int-bbox-matching GLOBAL sorted-order
    row positions into ``capacity`` lanes per shard.

    fn(x, y, true_n, blk, nblk, ibox) → (positions (D, K, capacity) int32,
    hits (D, K) int32); positions[d, p, :hits[d, p]] are global positions
    on shard d matching polygon p (unused lanes hold -1)."""
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from geomesa_tpu.parallel.mesh import DATA_AXIS

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS), P(DATA_AXIS), P(),
            P(DATA_AXIS, None, None), P(DATA_AXIS, None), P(),
        ),
        out_specs=(P(DATA_AXIS, None, None), P(DATA_AXIS, None)),
        check_vma=False,
    )
    def step(x, y, true_n, blk, nblk, ibox):
        n = x.shape[0]
        base = jax.lax.axis_index(DATA_AXIS) * n
        mb = blk.shape[2]

        def one(args):
            b_ids, nb, bb = args
            take = b_ids[:, None] * block + jnp.arange(block, dtype=jnp.int32)
            take = take.reshape(-1)
            live = (
                (jnp.arange(mb, dtype=jnp.int32) < nb)[:, None]
                .repeat(block, axis=1).reshape(-1)
            ) & ((base + take) < true_n)
            xs = x[take]
            ys = y[take]
            mask = (
                (xs >= bb[0]) & (xs <= bb[1]) & (ys >= bb[2]) & (ys <= bb[3])
            ) & live
            dest = jnp.where(
                mask, jnp.cumsum(mask.astype(jnp.int32)) - 1, capacity
            )
            out = jnp.full((capacity,), -1, dtype=jnp.int32)
            out = out.at[dest].set(base + take, mode="drop")
            return out, mask.sum(dtype=jnp.int32)

        pos, hits = jax.lax.map(one, (blk[0], nblk[0], ibox))
        return pos[None], hits[None, :]

    return step


@lru_cache(maxsize=None)
def make_block_join_step(mesh, block: int):
    """Sharded block-sparse ST_Within count: every shard tests only its
    planned candidate blocks per polygon, counts psum-merged over the data
    axis.

    fn(x, y, true_n, blk (D, K, MB), nblk (D, K), verts (K, V, 2),
       bbox (K, 4)) → (K,) int32 counts.
    """
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from geomesa_tpu.parallel.mesh import DATA_AXIS

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS), P(DATA_AXIS), P(),
            P(DATA_AXIS, None, None), P(DATA_AXIS, None),
            P(), P(),
        ),
        out_specs=P(),
        check_vma=False,
    )
    def step(x, y, true_n, blk, nblk, verts, bbox):
        n = x.shape[0]
        base = jax.lax.axis_index(DATA_AXIS) * n
        mb = blk.shape[2]

        def one(args):
            b_ids, nb, ring, bb = args  # (MB,), (), (V, 2), (4,)
            take = b_ids[:, None] * block + jnp.arange(block, dtype=jnp.int32)
            take = take.reshape(-1)  # (MB·B,) local positions
            live = (
                (jnp.arange(mb, dtype=jnp.int32) < nb)[:, None]
                .repeat(block, axis=1).reshape(-1)
            ) & ((base + take) < true_n)
            xs = x[take]
            ys = y[take]
            inside = _membership(xs, ys, ring, bb)
            return (inside & live).sum(dtype=jnp.int32)

        counts = jax.lax.map(one, (blk[0], nblk[0], verts, bbox))
        return jax.lax.psum(counts, DATA_AXIS)

    return step
