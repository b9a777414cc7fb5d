"""SPMD query execution over a device mesh: shard_map + collectives.

The reference's scan fan-out is a BatchScanner RPC to tablet servers, each
running an iterator stack, with partials folded client-side (SURVEY.md §3.3,
§2.20 P4-P6). TPU-native: the sorted store is contiguously sharded over the
mesh ``data`` axis; every shard runs the same fused refine/aggregate kernel on
its slice; partial counts/grids are ``psum``-merged over ICI. Batched queries
ride the ``query`` mesh axis (DP): each query-column of the mesh scans the
whole (replicated-over-query) store for its slice of the query batch.

Two execution shapes:

- :func:`make_batched_count_step` / :func:`make_batched_density_step` —
  throughput path: Q queries × full-shard masked scan, no host planning.
- :func:`make_select_step` — latency path: host-planned candidate slots
  (z-range intervals → per-shard gather indices), device refine, psum count.
"""

from __future__ import annotations

import os
from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from geomesa_tpu.analysis.contracts import device_band
from geomesa_tpu.obs.jaxmon import observed as _observed
from geomesa_tpu.parallel.mesh import DATA_AXIS, QUERY_AXIS, data_shards


def split_intervals_by_shard(
    intervals: np.ndarray, rows_per_shard: int, n_shards: int, bucket: int
) -> tuple[np.ndarray, np.ndarray]:
    """Global [start, end) row intervals → per-shard local gather indices.

    Returns (idx (D, C) int32 local positions, counts (D,) int32). ``bucket``
    is the common padded slot count C (max per-shard candidates, rounded up by
    the caller).
    """
    idx = np.zeros((n_shards, bucket), dtype=np.int32)
    counts = np.zeros(n_shards, dtype=np.int32)
    for d in range(n_shards):
        lo = d * rows_per_shard
        hi = lo + rows_per_shard
        pos_list = []
        for s, e in intervals:
            s2, e2 = max(int(s), lo), min(int(e), hi)
            if e2 > s2:
                pos_list.append(np.arange(s2 - lo, e2 - lo, dtype=np.int32))
        if pos_list:
            pos = np.concatenate(pos_list)
            if len(pos) > bucket:
                raise ValueError(f"shard {d}: {len(pos)} candidates > bucket {bucket}")
            idx[d, : len(pos)] = pos
            counts[d] = len(pos)
    return idx, counts


def max_shard_candidates(intervals: np.ndarray, rows_per_shard: int, n_shards: int) -> int:
    best = 0
    for d in range(n_shards):
        lo, hi = d * rows_per_shard, (d + 1) * rows_per_shard
        tot = 0
        for s, e in intervals:
            tot += max(0, min(int(e), hi) - max(int(s), lo))
        best = max(best, tot)
    return best


@lru_cache(maxsize=None)
def make_select_step(mesh: Mesh):
    """Latency path: per-shard gather + refine; returns (mask (D,C), count)."""

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS, None),
            P(DATA_AXIS),
            P(),
            P(),
        ),
        out_specs=(P(DATA_AXIS, None), P()),
        check_vma=False,
    )
    def step(x, y, bins, offs, idx, count, boxes, times):
        from geomesa_tpu.ops.refine import refine_points

        mask = refine_points(x, y, bins, offs, idx[0], count[0], boxes, times)
        total = jax.lax.psum(mask.sum(dtype=jnp.int32), DATA_AXIS)
        # query axis replicates the work; collective over it is identity-safe
        return mask[None, :], total

    return step


def _refine_for(n_cols: int):
    """4 device columns (x/y/bins/offs) → point containment refine;
    6 (xmin/xmax/ymin/ymax/bins/offs) → extended-geometry bbox overlap."""
    from geomesa_tpu.ops.refine import refine_bboxes, refine_points

    return refine_points if n_cols == 4 else refine_bboxes


def _make_count_step(mesh: Mesh, n_cols: int):
    """Pass 1 of distributed row retrieval: per-shard refine → per-shard hit
    counts (D,) int32 on host. The counts size pass 2's capacity lanes
    (the overflow-safe two-phase gather of SURVEY.md §7 "variable-length
    results on fixed-shape hardware")."""

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            *(P(DATA_AXIS) for _ in range(n_cols)),
            P(DATA_AXIS, None), P(DATA_AXIS), P(), P(),
        ),
        out_specs=P(DATA_AXIS),
        check_vma=False,
    )
    def step(*args):
        cols, (idx, count, boxes, times) = args[:n_cols], args[n_cols:]
        mask = _refine_for(n_cols)(*cols, idx[0], count[0], boxes, times)
        return mask.sum(dtype=jnp.int32)[None]

    return step


def _make_gather_step(mesh: Mesh, n_cols: int, capacity: int, replicate: bool):
    """Pass 2: per-shard refine + on-device compaction of matching *global*
    row positions into ``capacity`` lanes per shard.

    Returns ``fn(*cols, idx, count, boxes, times) → (positions (D, capacity)
    int32, hits (D,) int32)`` — positions[d, :hits[d]] are global
    sorted-order row positions matching on shard d (lanes beyond the hit
    count hold -1). With ``replicate=True`` the per-shard buffers are
    ``all_gather``-merged over the data axis so every device holds the full
    hit list (the reference's client-side merge of BatchScanner partials,
    done on-fabric — ``AccumuloQueryPlan.scala:136`` role).

    The ArrowScan/QueryPlan.scan role (``ArrowScan.scala:37``,
    ``QueryPlan.scala:106``): a query that *returns rows*, executed
    shard-parallel with collectives instead of scan RPC.
    """

    out_pos = P(None, None) if replicate else P(DATA_AXIS, None)
    out_cnt = P(None) if replicate else P(DATA_AXIS)

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            *(P(DATA_AXIS) for _ in range(n_cols)),
            P(DATA_AXIS, None), P(DATA_AXIS), P(), P(),
        ),
        out_specs=(out_pos, out_cnt),
        check_vma=False,
    )
    def step(*args):
        cols, (idx, count, boxes, times) = args[:n_cols], args[n_cols:]
        mask = _refine_for(n_cols)(*cols, idx[0], count[0], boxes, times)
        localpos = idx[0]
        base = jax.lax.axis_index(DATA_AXIS) * cols[0].shape[0]
        # stable stream compaction: prefix-sum destinations, OOB lanes drop
        dest = jnp.where(mask, jnp.cumsum(mask.astype(jnp.int32)) - 1, capacity)
        out = jnp.full((capacity,), -1, dtype=jnp.int32)
        out = out.at[dest].set(base + localpos, mode="drop")
        hits = mask.sum(dtype=jnp.int32)
        out = out[None, :]
        hits = hits[None]
        if replicate:
            out = jax.lax.all_gather(out, DATA_AXIS, axis=0, tiled=True)
            hits = jax.lax.all_gather(hits, DATA_AXIS, axis=0, tiled=True)
        return out, hits

    return step


def make_select_count_step(mesh: Mesh):
    return _make_count_step(mesh, 4)


def make_select_gather_step(mesh: Mesh, capacity: int, replicate: bool = False):
    return _make_gather_step(mesh, 4, capacity, replicate)


def make_select_count_step_bbox(mesh: Mesh):
    """Pass-1 counts for EXTENDED-geometry stores: per-shard bbox-overlap
    refine over the feature-bbox SoA (xmin/xmax/ymin/ymax int32 columns) —
    the distributed row-retrieval path for XZ2/XZ3 indexes (linestrings,
    polygons), where the loose test is interval overlap, not containment.
    Column order: (xmin, xmax, ymin, ymax, bins, offs)."""
    return _make_count_step(mesh, 6)


def make_select_gather_step_bbox(mesh: Mesh, capacity: int):
    """Pass-2 gather for extended-geometry stores (see
    :func:`make_select_gather_step`; refine is bbox overlap)."""
    return _make_gather_step(mesh, 6, capacity, replicate=False)


@lru_cache(maxsize=None)
def cached_select_count_step(mesh: Mesh):
    """Memoized per-mesh count step — jit caches key on function identity,
    so sharing the closure across DataStore instances avoids recompiles.

    Every ``cached_*`` factory wraps its step with
    :func:`geomesa_tpu.obs.jaxmon.observed`: per-call dispatch timing,
    compile detection, and recompile counts keyed by abstract signature
    (the live J003), costing ~1-2 µs per millisecond-scale dispatch."""
    return _observed("select_count", make_select_count_step(mesh))


@lru_cache(maxsize=None)
def cached_select_gather_step(mesh: Mesh, capacity: int, replicate: bool = False):
    return _observed(
        "select_gather", make_select_gather_step(mesh, capacity, replicate)
    )


@lru_cache(maxsize=None)
def cached_select_count_step_bbox(mesh: Mesh):
    return _observed("select_count_bbox", make_select_count_step_bbox(mesh))


@lru_cache(maxsize=None)
def cached_select_gather_step_bbox(mesh: Mesh, capacity: int):
    return _observed(
        "select_gather_bbox", make_select_gather_step_bbox(mesh, capacity)
    )


@lru_cache(maxsize=None)
def cached_batched_count_step(mesh: Mesh, impl: str = "auto"):
    return _observed("batched_count", make_batched_count_step(mesh, impl))


@lru_cache(maxsize=None)
def cached_planned_count_step(mesh: Mesh, n_queries: int, block_rows: int,
                              n_pairs: int, chunk: int = 8,
                              overlap: bool = False):
    return _observed(
        "planned_count",
        make_planned_count_step(mesh, n_queries, block_rows, n_pairs,
                                chunk=chunk, overlap=overlap),
    )


@lru_cache(maxsize=None)
def cached_planned_gather_step(mesh: Mesh, block_rows: int, n_pairs: int,
                               capacity: int, chunk: int = 8,
                               overlap: bool = False):
    return _observed(
        "planned_gather",
        make_planned_gather_step(mesh, block_rows, n_pairs, capacity,
                                 chunk=chunk, overlap=overlap),
    )


def _batched_time_match(bins, offs, times):
    """(Q, Nl) bool: row instant inside any of the query's (bin, offset)
    windows — the ONE place the inclusive interval semantics live for the
    batched throughput steps (point containment and bbox overlap)."""
    bi = bins[None, None, :]
    oi = offs[None, None, :]
    after = (bi > times[:, :, 0, None]) | (
        (bi == times[:, :, 0, None]) & (oi >= times[:, :, 1, None])
    )
    before = (bi < times[:, :, 2, None]) | (
        (bi == times[:, :, 2, None]) & (oi <= times[:, :, 3, None])
    )
    return (after & before).any(axis=1)


def _batched_masks(x, y, bins, offs, base, true_n, boxes, times):
    """(Ql, Nl) bool: query q matches local row r (int-domain superset test)."""
    xi = x[None, None, :]  # (1, 1, Nl)
    yi = y[None, None, :]
    in_box = (
        (xi >= boxes[:, :, 0, None])
        & (xi <= boxes[:, :, 1, None])
        & (yi >= boxes[:, :, 2, None])
        & (yi <= boxes[:, :, 3, None])
    ).any(axis=1)
    in_time = _batched_time_match(bins, offs, times)
    rows_valid = (base + jnp.arange(x.shape[0], dtype=jnp.int32)) < true_n
    return in_box & in_time & rows_valid[None, :]


# Per-slot int predicates for the exact-count path. INVARIANT: these must
# agree bit-for-bit with the fused count kernels' semantics —
# `_batched_masks`/`batched_count` (point containment) and
# `make_batched_overlap_step`'s match expression (interval overlap) — or
# the superset-minus-correction arithmetic of the exact mode silently
# breaks. Any inclusivity/layout change there must land here too.

def _slot_point(x, y, b):
    """(inside, on_edge) for one containment box slot [xlo, xhi, ylo, yhi]."""
    inside = (x >= b[0]) & (x <= b[1]) & (y >= b[2]) & (y <= b[3])
    edge = (x == b[0]) | (x == b[1]) | (y == b[2]) | (y == b[3])
    return inside, inside & edge


def _slot_overlap(fxmin, fymin, fxmax, fymax, b):
    """(overlaps, on_edge) for one overlap box slot: strict int inequality
    on an axis implies the f64 inequality, so divergence needs equality
    with the opposing query edge bucket."""
    inside = (
        (fxmin <= b[1]) & (fxmax >= b[0])
        & (fymin <= b[3]) & (fymax >= b[2])
    )
    edge = (
        (fxmin == b[1]) | (fxmax == b[0])
        | (fymin == b[3]) | (fymax == b[2])
    )
    return inside, inside & edge


def _slot_time_edge(bins, offs, t):
    """Rows AT one window's quantized endpoints — where coarse offsets
    (seconds for week/month bins, minutes for year) can admit rows the
    exact-ms f64 predicate rejects. Pad slots (unsatisfiable windows) are
    gated out."""
    valid = (t[0] < t[2]) | ((t[0] == t[2]) & (t[1] <= t[3]))
    at_lo = (bins == t[0]) & (offs == t[1])
    at_hi = (bins == t[2]) & (offs == t[3])
    return valid & (at_lo | at_hi)


def make_batched_edge_gather_step(mesh: Mesh, capacity: int,
                                  overlap: bool = False):
    """ONE-pass fused count + boundary-candidate gather for EXACT batched
    counts.

    The int-domain count is a superset of the f64 predicate only at
    quantization boundaries: spatial — interior buckets of a closed f64 box
    are f64-certain (normalization is monotone), so spatial divergence sits
    in an EDGE bucket of some box slot; temporal — bin offsets are coarser
    than ms for week/month/year periods, so temporal divergence sits AT a
    window's quantized (bin, offset) endpoints. This step returns, per
    query, the full int-domain count (psum over shards) AND the compacted
    global positions of every edge-or-endpoint candidate — the (tiny) set
    the host re-tests against the f64 filter AST and subtracts
    (``count_many(loose=False)``; the counting-scan analog of the select
    path's superset-refine + exact-residual contract). One sweep serves
    both outputs, so exact mode costs the same device scan as loose mode.

    Point mode: fn(x, y, bins, offs, true_n, boxes, times).
    Overlap mode (``overlap=True``): fn(xmin, ymin, xmax, ymax, bins,
    offs, true_n, boxes, times). Either returns
        (counts (Q,) int32,
         positions (Q, D, capacity) int32 global positions (-1 pad),
         hits (Q, D) int32 TRUE per-shard candidate counts).
    ``hits > capacity`` on any shard means that query's lanes truncated —
    callers fall back to the exact per-query path for it.
    """

    n_cols = 6 if overlap else 4

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            *(P(DATA_AXIS) for _ in range(n_cols)),
            P(),
            P(QUERY_AXIS, None, None),
            P(QUERY_AXIS, None, None),
        ),
        out_specs=(
            P(QUERY_AXIS),
            P(QUERY_AXIS, DATA_AXIS, None),
            P(QUERY_AXIS, DATA_AXIS),
        ),
        check_vma=False,
    )
    def step(*args):
        cols, (true_n, boxes, times) = args[:n_cols], args[n_cols:]
        if overlap:
            fxmin, fymin, fxmax, fymax, bins, offs = cols
            n = fxmin.shape[0]
        else:
            x, y, bins, offs = cols
            n = x.shape[0]
        base = jax.lax.axis_index(DATA_AXIS) * n
        rows_valid = (base + jnp.arange(n, dtype=jnp.int32)) < true_n

        def one(args_q):
            boxes_q, times_q = args_q  # (B, 4), (T, 4)
            in_box = jnp.zeros((n,), dtype=jnp.bool_)
            on_edge = jnp.zeros((n,), dtype=jnp.bool_)
            for k in range(boxes_q.shape[0]):
                b = boxes_q[k]
                if overlap:
                    ins, edg = _slot_overlap(fxmin, fymin, fxmax, fymax, b)
                else:
                    ins, edg = _slot_point(x, y, b)
                in_box |= ins
                on_edge |= edg
            time_edge = jnp.zeros((n,), dtype=jnp.bool_)
            for k in range(times_q.shape[0]):
                time_edge |= _slot_time_edge(bins, offs, times_q[k])
            in_all = in_box & _batched_time_match(
                bins, offs, times_q[None]
            )[0] & rows_valid
            mask = in_all & (on_edge | time_edge)
            dest = jnp.where(
                mask, jnp.cumsum(mask.astype(jnp.int32)) - 1, capacity
            )
            out = jnp.full((capacity,), -1, dtype=jnp.int32)
            out = out.at[dest].set(
                base + jnp.arange(n, dtype=jnp.int32), mode="drop"
            )
            # TRUE count (may exceed capacity): hits > capacity flags the
            # truncated lanes so the host falls back for that query
            return in_all.sum(dtype=jnp.int32), out, mask.sum(dtype=jnp.int32)

        counts, pos, hits = jax.lax.map(one, (boxes, times))
        return (
            jax.lax.psum(counts, DATA_AXIS),
            pos[:, None, :],
            hits[:, None],
        )

    return step


@lru_cache(maxsize=None)
def cached_batched_edge_gather_step(mesh: Mesh, capacity: int,
                                    overlap: bool = False):
    return _observed(
        "batched_edge_gather",
        make_batched_edge_gather_step(mesh, capacity, overlap),
    )


def make_batched_count_step(mesh: Mesh, impl: str = "auto"):
    """Throughput path: Q queries full-scan counts, psum over data shards.

    fn(x, y, bins, offs, true_n, boxes (Q, B, 4), times (Q, T, 4)) → (Q,) int32.

    ``impl``: ``"pallas"`` uses the fused Pallas scan kernel
    (:func:`geomesa_tpu.ops.pallas_kernels.batched_count` — one HBM pass per
    query batch, VMEM-resident accumulator), ``"jnp"`` the XLA broadcast
    version, ``"auto"`` picks pallas on TPU backends (interpret-mode pallas on
    CPU is orders of magnitude slower than XLA, so auto never picks it there).
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    interpret = jax.default_backend() != "tpu"

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(),
            P(QUERY_AXIS, None, None),
            P(QUERY_AXIS, None, None),
        ),
        out_specs=P(QUERY_AXIS),
        check_vma=False,
    )
    def step(x, y, bins, offs, true_n, boxes, times):
        base = jax.lax.axis_index(DATA_AXIS) * x.shape[0]
        if impl == "pallas":
            from geomesa_tpu.ops.pallas_kernels import batched_count

            counts = batched_count(
                x, y, bins, offs, base, true_n, boxes, times,
                interpret=interpret,
            )
        else:
            m = _batched_masks(x, y, bins, offs, base, true_n, boxes, times)
            counts = m.sum(axis=1, dtype=jnp.int32)
        return jax.lax.psum(counts, DATA_AXIS)

    return step


def make_matrix_scan_step(mesh: Mesh, topk: int, impl: str = "auto"):
    """Subscription-matrix scan: Q standing queries over one streamed chunk
    in ONE fused pass — per-query match counts AND a newest-match position
    sample, psum/gathered over data shards.

    fn(x, y, bins, offs, true_n, boxes (Q, B, 4), times (Q, T, 4)) →
    (counts (Q,) int32, positions (Q, D, topk) int32 global chunk row
    positions, -1 padded).

    Counts are EXACT (bit-identical to :func:`make_batched_count_step` on
    the same payloads). Positions are a newest-match SAMPLE: each data
    shard keeps the most recent matched row per 128-row lane (the pallas
    scoreboard of :func:`geomesa_tpu.ops.pallas_kernels.batched_count_hits`;
    the jnp path computes the identical lane-max) and returns its top-k —
    at most one position per (shard, lane), every returned position a true
    match. ``impl`` as in :func:`make_batched_count_step`.
    """
    from geomesa_tpu.ops.pallas_kernels import LANES as _LANES

    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    interpret = jax.default_backend() != "tpu"
    k = min(topk, _LANES)

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(),
            P(QUERY_AXIS, None, None),
            P(QUERY_AXIS, None, None),
        ),
        out_specs=(P(QUERY_AXIS), P(QUERY_AXIS, DATA_AXIS, None)),
        check_vma=False,
    )
    def step(x, y, bins, offs, true_n, boxes, times):
        n = x.shape[0]
        if n % _LANES:
            raise ValueError(
                f"matrix scan needs per-shard rows % {_LANES} == 0, got {n}"
            )
        base = jax.lax.axis_index(DATA_AXIS) * n
        if impl == "pallas":
            from geomesa_tpu.ops.pallas_kernels import batched_count_hits

            counts, lane_pos = batched_count_hits(
                x, y, bins, offs, base, true_n, boxes, times,
                interpret=interpret,
            )
        else:
            m = _batched_masks(x, y, bins, offs, base, true_n, boxes, times)
            counts = m.sum(axis=1, dtype=jnp.int32)
            gpos = base + jnp.arange(n, dtype=jnp.int32)
            lane_pos = jnp.where(m, gpos[None, :], jnp.int32(-1)).reshape(
                m.shape[0], n // _LANES, _LANES
            ).max(axis=1)
        top, _ = jax.lax.top_k(lane_pos, k)
        if k < topk:
            top = jnp.pad(top, ((0, 0), (0, topk - k)), constant_values=-1)
        return jax.lax.psum(counts, DATA_AXIS), top[:, None, :]

    return step


@lru_cache(maxsize=None)
def cached_matrix_scan_step(mesh: Mesh, topk: int, q_cap: int,
                            impl: str = "auto"):
    """Memoized matrix-scan step, ONE observed identity per (mesh, topk,
    capacity bucket): growing the subscription matrix into the next
    power-of-two bucket compiles a NEW step (a first compile on a fresh
    identity, never a J003 recompile on a warm one), and the steady path —
    subscription add/remove inside a bucket — reuses the compiled
    executable with zero recompiles (pinned in tests/test_stream_matrix.py
    via the jaxmon census)."""
    return _observed(
        f"matrix_scan_q{q_cap}", make_matrix_scan_step(mesh, topk, impl)
    )


@lru_cache(maxsize=None)
def make_repeated_count_step(mesh: Mesh, impl: str = "auto"):
    """Like :func:`make_batched_count_step` but evaluates R independent query
    batches in ONE dispatch via ``lax.scan`` — boxes (R, Q, B, 4), times
    (R, Q, T, 4) → (R, Q) counts.

    Purpose: device-time isolation when dispatch round trips dominate. Each
    scan iteration is a full HBM pass with *different* queries (so XLA cannot
    hoist the body), making per-pass device time measurable as
    ``(t(R2) - t(R1)) / (R2 - R1)`` with the dispatch RTT cancelled — the
    memory-bound MFU analog (HBM bytes/s) falls straight out.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    interpret = jax.default_backend() != "tpu"

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(),
            P(None, QUERY_AXIS, None, None),
            P(None, QUERY_AXIS, None, None),
        ),
        out_specs=P(None, QUERY_AXIS),
        check_vma=False,
    )
    def step(x, y, bins, offs, true_n, boxes_r, times_r):
        base = jax.lax.axis_index(DATA_AXIS) * x.shape[0]

        def one(carry, bt):
            boxes, times = bt
            if impl == "pallas":
                from geomesa_tpu.ops.pallas_kernels import batched_count

                counts = batched_count(
                    x, y, bins, offs, base, true_n, boxes, times,
                    interpret=interpret,
                )
            else:
                m = _batched_masks(x, y, bins, offs, base, true_n, boxes, times)
                counts = m.sum(axis=1, dtype=jnp.int32)
            return carry, counts

        _, counts_r = jax.lax.scan(one, 0, (boxes_r, times_r))
        return jax.lax.psum(counts_r, DATA_AXIS)

    return step


def _batched_overlap_masks(fxmin, fymin, fxmax, fymax, bins, offs, base,
                           true_n, boxes, times):
    """(Q, Nl) bool overlap-mode analog of :func:`_batched_masks` (the
    XZ bbox layout): row bbox intersects any query box AND the time
    windows match. MUST agree bit-for-bit with
    :func:`make_batched_overlap_step`'s inline match and
    :func:`_slot_overlap` (the exact-mode edge contract)."""
    x1 = fxmin[None, None, :]
    y1 = fymin[None, None, :]
    x2 = fxmax[None, None, :]
    y2 = fymax[None, None, :]
    match = (
        (x1 <= boxes[:, :, 1, None])
        & (x2 >= boxes[:, :, 0, None])
        & (y1 <= boxes[:, :, 3, None])
        & (y2 >= boxes[:, :, 2, None])
    ).any(axis=1)
    match = match & _batched_time_match(bins, offs, times)
    rows_valid = (
        base + jnp.arange(fxmin.shape[0], dtype=jnp.int32)
    ) < true_n
    return match & rows_valid[None, :]


def _planned_block_mask(cols, base, true_n, boxes, times, si, qj,
                        block_rows: int, overlap: bool = False):
    """(block_rows,) bool: rows of the block at local offset ``si``
    matching query ``qj`` — a dynamic slice fed through
    :func:`_batched_masks` (point containment) or
    :func:`_batched_overlap_masks` (bbox overlap), so the pruned steps
    share the ONE home of the inclusive predicate semantics with the
    fused full-scan kernels (they must agree bit-for-bit: config 7's
    pruned headline and select_many's exact-capacity argument both rest
    on that parity)."""
    sl = [jax.lax.dynamic_slice(c, (si,), (block_rows,)) for c in cols]
    f = _batched_overlap_masks if overlap else _batched_masks
    return f(*sl, base + si, true_n, boxes[qj][None], times[qj][None])[0]


def intervals_to_block_pairs(intervals_per_query, block_rows: int):
    """Per-query row intervals → flat (query, block) work list.

    ``intervals_per_query``: list over queries of (k, 2) int64 arrays of
    half-open global row intervals (the planner's pruned candidate spans).
    Returns unpadded (pair_q, pair_blk) int32 arrays: blocks are global
    row-space tiles of ``block_rows``, deduped per query (many small
    z-ranges landing in one block collapse to one gather). Each (q, blk)
    pair is one unit of device work for :func:`make_planned_count_step`;
    pad to the step's compile-time budget with :func:`pad_block_pairs`."""
    qs, bs = [], []
    for q, iv in enumerate(intervals_per_query):
        iv = np.asarray(iv, dtype=np.int64).reshape(-1, 2)
        spans = [
            np.arange(a // block_rows, (b - 1) // block_rows + 1)
            for a, b in iv if b > a
        ]
        if not spans:
            continue
        blks = np.unique(np.concatenate(spans))
        qs.append(np.full(len(blks), q, dtype=np.int32))
        bs.append(blks.astype(np.int32))
    if not qs:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    return np.concatenate(qs), np.concatenate(bs)


def pad_block_pairs(pair_q, pair_blk, n_pairs: int):
    """Pad a (query, block) work list to the step's compile-time length;
    padded slots carry query -1 (skipped on device). Raises if the list
    exceeds the budget — truncating a cover would silently undercount."""
    total = len(pair_q)
    if total > n_pairs:
        raise ValueError(f"{total} block pairs exceed budget {n_pairs}")
    out_q = np.full(n_pairs, -1, dtype=np.int32)
    out_b = np.zeros(n_pairs, dtype=np.int32)
    out_q[:total] = pair_q
    out_b[:total] = pair_blk
    return out_q, out_b


def make_planned_count_step(mesh: Mesh, n_queries: int, block_rows: int,
                            n_pairs: int, chunk: int = 8,
                            overlap: bool = False):
    """Index-pruned resident count: exact batched counts touching ONLY the
    planner's candidate blocks (VERDICT r4 item 3 — the z-index route that
    lifts the 125M resident scan off the full-scan compute bound).

    The full-scan step does N × Q row-query compares per pass; here the
    host plans each query's z-range cover, converts it to (query, block)
    pairs (:func:`intervals_to_block_pairs`), and the device gathers each
    candidate block once FOR ITS ONE QUERY — total work is
    Σ_q cover_blocks(q) × block_rows, typically 10-100× less. Counts are
    EXACT w.r.t. the same int-domain predicate as
    :func:`make_batched_count_step` provided the pairs cover every
    matching row (the z-decomposition guarantee; callers widen the cover
    by one coarse-grid cell so 21-bit planning can never miss a row the
    31-bit predicate passes).

    fn(x, y, bins, offs, true_n, pair_q (R, P), pair_blk (R, P),
    boxes (R, Q, B, 4), times (R, Q, T, 4)) → (R, Q) counts. The leading
    R axis scans independent query batches in one dispatch (same
    RTT-cancelling differencing methodology as
    :func:`make_repeated_count_step`). Pairs and query payloads are
    replicated; every shard walks the full pair list and contributes only
    its owned blocks, merged with one psum.
    """
    assert n_pairs % chunk == 0, (n_pairs, chunk)
    n_spatial = 6 if overlap else 4

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            *(P(DATA_AXIS) for _ in range(n_spatial)),
            P(),
            P(None, None),              # pair_q (R, P) replicated
            P(None, None),              # pair_blk (R, P)
            P(None, QUERY_AXIS, None, None),
            P(None, QUERY_AXIS, None, None),
        ),
        out_specs=P(None, QUERY_AXIS),
        check_vma=False,
    )
    def step(*sargs):
        cols = sargs[:n_spatial]
        true_n, pair_q_r, pair_blk_r, boxes_r, times_r = sargs[n_spatial:]
        n = cols[0].shape[0]
        # a block straddling a shard boundary would be owned by NO shard —
        # a silent undercount; shard with shard_columns(multiple=block_rows)
        assert n % block_rows == 0, (
            f"per-shard rows {n} not a multiple of block_rows {block_rows}")
        base = jax.lax.axis_index(DATA_AXIS) * n
        ql = boxes_r.shape[1]  # local query count on this query-shard
        qbase = jax.lax.axis_index(QUERY_AXIS) * ql

        def one_batch(carry, rb):
            pair_q, pair_blk, boxes, times = rb

            def chunk_body(acc, pc):
                pq, pb = pc  # (chunk,)
                # global row positions are int32 BY CONTRACT (buf/pos lanes,
                # base = axis_index * n, device_sort_perm's >= 2**31 guard
                # all wrap/raise first) — i64 here bought an emulated TPU
                # op, never extra range
                start_g = pb.astype(jnp.int32) * block_rows
                local = (start_g - base).astype(jnp.int32)
                # query ids are global: this query-shard owns [qbase,
                # qbase+ql); non-owned or padded pairs contribute zero
                qloc = pq - qbase
                own = (
                    (pq >= 0) & (qloc >= 0) & (qloc < ql)
                    & (local >= 0) & (local + block_rows <= n)
                )
                s = jnp.where(own, local, 0)
                qi = jnp.clip(qloc, 0, ql - 1)

                def count_one(si, qj, ok):
                    # the block predicate IS the fused kernels' mask on
                    # the sliced rows — the single home of the inclusive
                    # semantics, so the pruned path can never drift from
                    # the scan it must match bit-for-bit
                    m = _planned_block_mask(
                        cols, base, true_n, boxes, times, si, qj,
                        block_rows, overlap=overlap)
                    return jnp.where(ok, m.sum(dtype=jnp.int32), 0)

                cnts = jax.vmap(count_one)(s, qi, own)  # (chunk,)
                return acc.at[qi].add(cnts), None

            acc0 = jnp.zeros(ql, dtype=jnp.int32)
            acc, _ = jax.lax.scan(
                chunk_body, acc0,
                (pair_q.reshape(-1, chunk), pair_blk.reshape(-1, chunk)),
            )
            return carry, acc

        _, counts_r = jax.lax.scan(
            one_batch, 0, (pair_q_r, pair_blk_r, boxes_r, times_r))
        return jax.lax.psum(counts_r, DATA_AXIS)

    return step


def make_planned_gather_step(mesh: Mesh, block_rows: int, n_pairs: int,
                             capacity: int, chunk: int = 8,
                             overlap: bool = False):
    """Batched multi-query row retrieval over planner candidate BLOCKS:
    ONE dispatch serves the whole query batch (the ``select_many`` path —
    dispatch RTTs amortize across queries like the fused count steps, and
    block ids ship host→device in KBs where per-row candidate slots would
    ship MBs over the host link).

    fn(x, y, bins, offs, true_n, pair_q (P,), pair_blk (P,),
    boxes (Q, B, 4), times (Q, T, 4)) →
        (buf (D, capacity) int32, pair_hits (P,) int32)

    Each (query, block) pair is evaluated on the ONE data shard that owns
    its block (global block grid; per-shard rows must divide block_rows —
    asserted); matching global positions append into the shard's ``buf``
    in pair-index order. The host reconstructs per-pair row sets from
    ``pair_hits`` alone: a pair's owner shard is ``blk * block_rows //
    rows_per_shard``, and within a shard the pairs' spans are consecutive
    in pair order. ``capacity`` must be ≥ the per-shard match total — the
    caller sizes it from :func:`make_planned_count_step`'s exact counts
    (same predicate, so overflow is impossible by construction).
    """
    assert n_pairs % chunk == 0, (n_pairs, chunk)
    n_spatial = 6 if overlap else 4

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            *(P(DATA_AXIS) for _ in range(n_spatial)),
            P(),
            P(None),
            P(None),
            P(None, None, None),
            P(None, None, None),
        ),
        out_specs=(P(DATA_AXIS, None), P(None)),
        check_vma=False,
    )
    def step(*sargs):
        cols = sargs[:n_spatial]
        true_n, pair_q, pair_blk, boxes, times = sargs[n_spatial:]
        n = cols[0].shape[0]
        assert n % block_rows == 0, (
            f"per-shard rows {n} not a multiple of block_rows {block_rows}")
        base = jax.lax.axis_index(DATA_AXIS) * n
        nq = boxes.shape[0]

        def chunk_body(carry, pc):
            buf, off = carry
            pq, pb = pc  # (chunk,)
            # global row positions are int32 BY CONTRACT (buf/pos lanes,
            # base = axis_index * n, device_sort_perm's >= 2**31 guard all
            # wrap/raise first) — i64 here bought an emulated TPU op,
            # never extra range
            start_g = pb.astype(jnp.int32) * block_rows
            local = (start_g - base).astype(jnp.int32)
            own = (pq >= 0) & (local >= 0) & (local + block_rows <= n)
            s = jnp.where(own, local, 0)
            qi = jnp.clip(pq, 0, nq - 1)

            def pair_mask(si, qj):
                # same single-home predicate as the planned count step
                return _planned_block_mask(
                    cols, base, true_n, boxes, times, si, qj,
                    block_rows, overlap=overlap)

            masks = jax.vmap(pair_mask)(s, qi)       # (chunk, block_rows)
            masks = masks & own[:, None]
            counts = masks.sum(axis=1, dtype=jnp.int32)
            starts = off + jnp.cumsum(counts) - counts
            within = jnp.cumsum(masks.astype(jnp.int32), axis=1) - 1
            dest = jnp.where(masks, starts[:, None] + within, capacity)
            pos = (base + s[:, None]
                   + jnp.arange(block_rows, dtype=jnp.int32)[None, :])
            buf = buf.at[dest.ravel()].set(pos.ravel(), mode="drop")
            return (buf, (off + counts.sum()).astype(jnp.int32)), counts

        buf0 = jnp.full((capacity,), -1, dtype=jnp.int32)
        (buf, _), hits = jax.lax.scan(
            chunk_body, (buf0, jnp.int32(0)),
            (pair_q.reshape(-1, chunk), pair_blk.reshape(-1, chunk)),
        )
        # each valid pair is owned by exactly one data shard: the psum is
        # owner-count + zeros. Identical across the query axis (all inputs
        # replicated), so no collective there.
        hits = jax.lax.psum(hits.reshape(-1), DATA_AXIS)
        return buf[None, :], hits

    return step


def make_batched_overlap_step(mesh: Mesh, with_time: bool = False):
    """Extended-geometry (XZ) throughput path: Q bbox-overlap counts over a
    store of per-feature bounding boxes, psum over data shards.

    fn(xmin, ymin, xmax, ymax, true_n, boxes (Q, B, 4)) → (Q,) int32, where
    ``boxes`` packs int-domain [qxlo, qxhi, qylo, qyhi] and a row matches
    when its bbox intersects any of the query's boxes — the XZ2 scan's
    overlap test (``XZ2SFC.scala`` ranges + per-row refine) as one fused
    vectorized pass (SURVEY.md §2.20 P4/P5). With ``with_time=True`` the
    signature gains (bins, offs) columns and a (Q, T, 4) times payload
    (the XZ3 shape; ``count_many``'s loose path for extended stores).
    """

    col_specs = (P(DATA_AXIS),) * (6 if with_time else 4)
    q_specs = (
        (P(QUERY_AXIS, None, None), P(QUERY_AXIS, None, None))
        if with_time
        else (P(QUERY_AXIS, None, None),)
    )

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(*col_specs, P(), *q_specs),
        out_specs=P(QUERY_AXIS),
        check_vma=False,
    )
    def step(*args):
        if with_time:
            xmin, ymin, xmax, ymax, bins, offs, true_n, boxes, times = args
        else:
            xmin, ymin, xmax, ymax, true_n, boxes = args
        base = jax.lax.axis_index(DATA_AXIS) * xmin.shape[0]
        x1 = xmin[None, None, :]
        y1 = ymin[None, None, :]
        x2 = xmax[None, None, :]
        y2 = ymax[None, None, :]
        match = (
            (x1 <= boxes[:, :, 1, None])
            & (x2 >= boxes[:, :, 0, None])
            & (y1 <= boxes[:, :, 3, None])
            & (y2 >= boxes[:, :, 2, None])
        ).any(axis=1)
        if with_time:
            match = match & _batched_time_match(bins, offs, times)
        rows_valid = (base + jnp.arange(xmin.shape[0], dtype=jnp.int32)) < true_n
        counts = (match & rows_valid[None, :]).sum(axis=1, dtype=jnp.int32)
        return jax.lax.psum(counts, DATA_AXIS)

    return step


_KNN_CHUNK = 1 << 18  # row-chunk per scan step: Q×chunk f32 ≈ 64 MB at Q=64


def _knn_valid_and_degrees(x, y, true_n, ttl):
    """Shared prologue: decode int32 coords to planar f32 degrees and
    build the validity mask (tail padding + optional TTL expiry)."""
    sx = np.float32(360.0 / 2**31)
    sy = np.float32(180.0 / 2**31)
    n = x.shape[0]
    base = jax.lax.axis_index(DATA_AXIS) * n
    valid = (base + jnp.arange(n, dtype=jnp.int32)) < true_n
    if ttl is not None:
        bins, offs, cut = ttl
        live = (bins > cut[0]) | ((bins == cut[0]) & (offs >= cut[1]))
        valid = valid & live
    xf = x.astype(jnp.float32) * sx - jnp.float32(180.0)
    yf = y.astype(jnp.float32) * sy - jnp.float32(90.0)
    return base, valid, xf, yf


def _local_knn_heaps(x, y, true_n, qx, qy, k, ttl=None, impl=None):
    """Per-shard candidate heaps shared by the gather and ring KNN steps.

    Three implementations (``GEOMESA_KNN_IMPL``): ``map`` top-ks each query
    over the full column sequentially (peak memory O(N), fast on host
    backends where top_k is a cheap selection); ``scan`` streams row
    chunks through a running per-query top-k so the shard is read ONCE
    for ALL queries (the HBM-bound accelerator shape — the map form
    re-reads the shard Q times); ``blocked`` replaces the single
    full-column top-k with per-block batched top-k + a survivor top-k
    (hierarchical, still exact — targets the accelerator where one
    10⁸-length ``lax.top_k`` is sort-shaped and serial). Default ``map``
    until a variant's accelerator win is hardware-measured (CPU mesh:
    map 0.7 s vs scan 2.1 s per 64-query batch at 4M rows — host top_k
    favors map).
    Selection: an explicit ``impl`` argument overrides the env knob;
    ``None`` defers to ``GEOMESA_KNN_IMPL``, read at TRACE time — set it
    before the first KNN call of the process (the ``cached_*`` step
    wrappers are memoized per (mesh, k, with_ttl, impl)).

    ``ttl``: optional (bins, offs, cut) — rows with (bin, off)
    lexicographically BELOW cut=(cut_bin, cut_off) are TTL-expired and
    masked to inf, so a live store's device sweep never surfaces aged-off
    candidates (the AgeOffIterator-at-scan role on the KNN path).

    Returns (dists² (Ql, k) ascending, global rows (Ql, k) int32)."""
    impl = impl or os.environ.get("GEOMESA_KNN_IMPL", "map")
    if impl not in ("map", "scan", "blocked"):
        # loud by design: the impls return identical results, so a typo'd
        # selection silently falling back to map could never be caught by
        # output checks — it would just benchmark the wrong kernel
        raise ValueError(f"unknown KNN impl {impl!r} "
                         "(expected 'map', 'scan', or 'blocked')")
    if impl == "scan":
        return _local_knn_heaps_scan(x, y, true_n, qx, qy, k, ttl)
    if impl == "blocked":
        return _local_knn_heaps_blocked(x, y, true_n, qx, qy, k, ttl)
    base, valid, xf, yf = _knn_valid_and_degrees(x, y, true_n, ttl)

    def one(qp):
        qxi, qyi = qp
        d2 = (xf - qxi) ** 2 + (yf - qyi) ** 2
        d2 = jnp.where(valid, d2, jnp.inf)
        nd, ni = jax.lax.top_k(-d2, k)
        return -nd, base + ni.astype(jnp.int32)

    return jax.lax.map(one, (qx, qy))  # (Ql, k) each


_KNN_BLOCK = 2048  # blocked-impl row-block width (lane-aligned, ≫ k)


def _pad_to_blocks(base, xf, yf, valid, n, width):
    """Pad the shard columns to a multiple of ``width`` and reshape to
    (rows/width, width), returning the matching per-lane GLOBAL row ids
    with padded-tail ids clamped INTO this shard's range: ``base + n ..``
    would alias the NEXT shard's real global ids, and a shard with < k
    live rows would then surface another shard's first rows as neighbors.
    Shared by the scan and blocked impls — the aliasing guard must stay
    identical in both."""
    nb = -(-n // width)
    pad = nb * width - n
    if pad:
        xf = jnp.pad(xf, (0, pad))
        yf = jnp.pad(yf, (0, pad))
        valid = jnp.pad(valid, (0, pad))
    loc = jnp.minimum(
        jnp.arange(nb * width, dtype=jnp.int32), jnp.int32(n - 1)
    )
    rows = (base + loc).reshape(nb, width)
    return (xf.reshape(nb, width), yf.reshape(nb, width),
            valid.reshape(nb, width), rows)


def _local_knn_heaps_blocked(x, y, true_n, qx, qy, k, ttl=None):
    """Hierarchical exact top-k: per-BLOCK top-k over a (n/B, B) view (a
    cheap batched sort of short rows), then a final top-k over the n/B·k
    survivors. Exact because every global top-k member is by definition
    within its own block's top-k. Motivation: a single ``lax.top_k`` over a
    10⁸-length vector is the dominant cost of the ``map`` impl on an
    accelerator (sort-shaped, serial in row length), while (nb, 2048)
    batched top-k tiles onto the VPU; the survivor set is ~k·n/B ≪ n."""
    base, valid, xf, yf = _knn_valid_and_degrees(x, y, true_n, ttl)
    n = x.shape[0]
    bw = int(min(_KNN_BLOCK, max(k, n)))
    xb, yb, vb, rb = _pad_to_blocks(base, xf, yf, valid, n, bw)
    kb = min(k, bw)

    def one(qp):
        qxi, qyi = qp
        d2 = (xb - qxi) ** 2 + (yb - qyi) ** 2
        d2 = jnp.where(vb, d2, jnp.inf)
        nd1, ni1 = jax.lax.top_k(-d2, kb)            # (nb, kb) per-block
        nd2, sel = jax.lax.top_k(nd1.reshape(-1), k)  # over survivors
        blk = sel // kb
        col = jnp.take(ni1.reshape(-1), sel)
        rows = jnp.take(rb.reshape(-1), blk * bw + col)  # pre-clamped ids
        return -nd2, rows.astype(jnp.int32)

    return jax.lax.map(one, (qx, qy))  # (Ql, k) each


def _local_knn_heaps_scan(x, y, true_n, qx, qy, k, ttl=None):
    """Streaming variant: row chunks through a running per-query top-k
    (one shard read for all queries; see :func:`_local_knn_heaps`)."""
    base, valid, xf, yf = _knn_valid_and_degrees(x, y, true_n, ttl)
    n = x.shape[0]
    q = qx.shape[0]

    chunk = int(min(n, _KNN_CHUNK))
    xc, yc, vc, rc = _pad_to_blocks(base, xf, yf, valid, n, chunk)

    def body(carry, inp):
        bd, bi = carry  # (Q, k) running best dists² / global rows
        cx, cy, cv, cr = inp
        d2 = (cx[None, :] - qx[:, None]) ** 2 + (cy[None, :] - qy[:, None]) ** 2
        d2 = jnp.where(cv[None, :], d2, jnp.inf)
        cat_d = jnp.concatenate([bd, d2], axis=1)  # carry first: on f32
        cat_i = jnp.concatenate(  # ties the EARLIER row wins, as before
            [bi, jnp.broadcast_to(cr[None, :], (q, chunk))], axis=1
        )
        nd, sel = jax.lax.top_k(-cat_d, k)
        return (-nd, jnp.take_along_axis(cat_i, sel, axis=1)), None

    init = (
        jnp.full((q, k), jnp.inf, dtype=jnp.float32),
        jnp.broadcast_to(base.astype(jnp.int32), (q, k)),
    )
    (bd, bi), _ = jax.lax.scan(body, init, (xc, yc, vc, rc))
    return bd, bi


def _check_knn_impl(impl):
    if impl not in (None, "map", "scan", "blocked"):
        raise ValueError(f"unknown KNN impl {impl!r} "
                         "(expected 'map', 'scan', or 'blocked')")


def make_batched_knn_step(mesh: Mesh, k: int, with_ttl: bool = False,
                          impl: str | None = None):
    """Batched multi-point KNN in ONE pass: per-shard distance scan +
    ``top_k``, candidates ``all_gather``-merged over the data axis and
    re-ranked — replacing the reference's per-point iterative-deepening
    window loop (``KNearestNeighborSearchProcess.scala:583``) with a single
    device-parallel sweep (VERDICT r1 item 7).

    fn(x, y, true_n, qx (Q,) f32 deg, qy (Q,) f32 deg) →
        (dists (Q, k) f32 degrees, rows (Q, k) int32 global sorted-order
        positions). Distances are planar f32 degrees (the CPU referee must
    use the same f32 math; int→f32 coordinate rounding is ~2e-5°).

    ``with_ttl``: signature becomes fn(x, y, bins, offs, true_n, qx, qy,
    cut (2,) int32) — rows lex-below cut are expired and masked on device
    (live-store KNN, VERDICT r2 item 5).

    ``impl``: per-shard sweep shape, overriding ``GEOMESA_KNN_IMPL``
    (``None`` = the env knob; see :func:`_local_knn_heaps`).
    """

    _check_knn_impl(impl)
    col_specs = (P(DATA_AXIS),) * (4 if with_ttl else 2)
    tail_specs = (P(QUERY_AXIS), P(QUERY_AXIS)) + ((P(),) if with_ttl else ())

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(*col_specs, P(), *tail_specs),
        out_specs=(P(QUERY_AXIS, None), P(QUERY_AXIS, None)),
        check_vma=False,
    )
    def step(*args):
        if with_ttl:
            x, y, bins, offs, true_n, qx, qy, cut = args
            ttl = (bins, offs, cut)
        else:
            x, y, true_n, qx, qy = args
            ttl = None
        dloc, iloc = _local_knn_heaps(x, y, true_n, qx, qy, k, ttl=ttl, impl=impl)
        # merge per-shard candidate heaps across the mesh
        ad = jax.lax.all_gather(dloc, DATA_AXIS, axis=0)  # (D, Ql, k)
        ai = jax.lax.all_gather(iloc, DATA_AXIS, axis=0)
        d_all = jnp.moveaxis(ad, 0, 1).reshape(dloc.shape[0], -1)  # (Ql, D*k)
        i_all = jnp.moveaxis(ai, 0, 1).reshape(iloc.shape[0], -1)
        nd, sel = jax.lax.top_k(-d_all, k)
        rows = jnp.take_along_axis(i_all, sel, axis=1)
        return jnp.sqrt(-nd), rows

    return step


@lru_cache(maxsize=None)
def cached_batched_knn_step(mesh: Mesh, k: int, with_ttl: bool = False,
                            impl: str | None = None):
    return _observed(
        "batched_knn", make_batched_knn_step(mesh, k, with_ttl, impl=impl)
    )


@lru_cache(maxsize=None)
def cached_batched_overlap_step(mesh: Mesh, with_time: bool = False):
    return _observed(
        "batched_overlap", make_batched_overlap_step(mesh, with_time)
    )


def make_batched_density_step(mesh: Mesh, width: int = 256, height: int = 256):
    """Q queries full-scan density grids: (Q, H, W) f32, psum over data shards.

    ``grid_bounds``: (Q, 4) int32 [xlo, xhi, ylo, yhi] per query.
    """

    use_mxu = jax.default_backend() == "tpu"
    chunk = 8192  # one-hot chunks: 2 × (chunk × 256) bf16 ≈ 8 MB VMEM

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(),
            P(QUERY_AXIS, None, None),
            P(QUERY_AXIS, None, None),
            P(QUERY_AXIS, None),
        ),
        out_specs=P(QUERY_AXIS, None, None),
        check_vma=False,
    )
    def step(x, y, bins, offs, true_n, boxes, times, grid_bounds):
        base = jax.lax.axis_index(DATA_AXIS) * x.shape[0]
        n = x.shape[0]
        rows_valid = (base + jnp.arange(n, dtype=jnp.int32)) < true_n

        # sequential over queries (lax.map): peak memory stays O(N), never
        # O(Q·N) — 100M-row shards with Q=16 would otherwise materialize
        # multi-GB (Q, N) temporaries and exhaust HBM
        def one(args):
            boxes_q, times_q, gb = args  # (B, 4), (T, 4), (4,)
            in_box = jnp.zeros((n,), dtype=jnp.bool_)
            for k in range(boxes_q.shape[0]):
                in_box |= (
                    (x >= boxes_q[k, 0]) & (x <= boxes_q[k, 1])
                    & (y >= boxes_q[k, 2]) & (y <= boxes_q[k, 3])
                )
            in_time = jnp.zeros((n,), dtype=jnp.bool_)
            for k in range(times_q.shape[0]):
                after = (bins > times_q[k, 0]) | (
                    (bins == times_q[k, 0]) & (offs >= times_q[k, 1])
                )
                before = (bins < times_q[k, 2]) | (
                    (bins == times_q[k, 2]) & (offs <= times_q[k, 3])
                )
                in_time |= after & before
            mask_q = in_box & in_time & rows_valid

            xi = x.astype(jnp.float32)
            yi = y.astype(jnp.float32)
            xlo = gb[0].astype(jnp.float32)
            xhi = gb[1].astype(jnp.float32)
            ylo = gb[2].astype(jnp.float32)
            yhi = gb[3].astype(jnp.float32)
            sx = width / (xhi - xlo + 1.0)
            sy = height / (yhi - ylo + 1.0)
            cx = jnp.clip(((xi - xlo) * sx).astype(jnp.int32), 0, width - 1)
            cy = jnp.clip(((yi - ylo) * sy).astype(jnp.int32), 0, height - 1)
            w = mask_q.astype(jnp.float32)
            if not use_mxu:
                flat = jnp.zeros(width * height, dtype=jnp.float32)
                flat = flat.at[cy * width + cx].add(w)
                return flat.reshape(height, width)

            # MXU path: grid = Σ_chunks one_hot(cy)ᵀ · (w ⊙ one_hot(cx)) —
            # the histogram as bf16 matmuls with f32 accumulation (exact for
            # counts < 2^24), which beats TPU scatter by an order of
            # magnitude. Masked-out rows get weight 0.
            k = -(-n // chunk)
            pad = k * chunk - n
            cxp = jnp.pad(cx, (0, pad)).reshape(k, chunk)
            cyp = jnp.pad(cy, (0, pad)).reshape(k, chunk)
            wp = jnp.pad(w, (0, pad)).reshape(k, chunk)

            def body(acc, args):
                cxc, cyc, wc = args
                rowsh = jax.nn.one_hot(cyc, height, dtype=jnp.bfloat16)
                colsh = jax.nn.one_hot(cxc, width, dtype=jnp.bfloat16)
                rowsh = rowsh * wc.astype(jnp.bfloat16)[:, None]
                part = jax.lax.dot_general(
                    rowsh, colsh,
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                return acc + part, None

            acc, _ = jax.lax.scan(
                body, jnp.zeros((height, width), jnp.float32), (cxp, cyp, wp)
            )
            return acc

        grids = jax.lax.map(one, (boxes, times, grid_bounds))  # (Ql, H, W)
        return jax.lax.psum(grids, DATA_AXIS)

    return step


def make_ring_knn_step(mesh: Mesh, k: int, with_ttl: bool = False,
                       impl: str | None = None):
    """Batched KNN with a RING top-k merge over the data axis (``ppermute``).

    Same contract as :func:`make_batched_knn_step`, different collective
    topology: instead of ``all_gather``-ing every shard's candidate heap
    (O(D·k) resident per device), each device keeps a running best-k and
    passes its heap one hop around the ring for D-1 steps — O(k) payload per
    hop, the ring-parallel pattern the scaling-book recipe uses for
    long-sequence attention. Preferable when D·k·Q would pressure VMEM/HBM
    (large query batches on big meshes); distances are identical to the
    all_gather form (row choice may differ where k-th distances tie).
    ``impl`` selects the per-shard sweep shape, overriding
    ``GEOMESA_KNN_IMPL`` (``None`` = the env knob).
    """

    _check_knn_impl(impl)
    n_shards = data_shards(mesh)
    col_specs = (P(DATA_AXIS),) * (4 if with_ttl else 2)
    tail_specs = (P(QUERY_AXIS), P(QUERY_AXIS)) + ((P(),) if with_ttl else ())

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(*col_specs, P(), *tail_specs),
        out_specs=(P(QUERY_AXIS, None), P(QUERY_AXIS, None)),
        check_vma=False,
    )
    def step(*args):
        if with_ttl:
            x, y, bins, offs, true_n, qx, qy, cut = args
            ttl = (bins, offs, cut)
        else:
            x, y, true_n, qx, qy = args
            ttl = None
        dloc, iloc = _local_knn_heaps(x, y, true_n, qx, qy, k, ttl=ttl, impl=impl)
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

        def hop(carry, _):
            best_d, best_i, ring_d, ring_i = carry
            # receive the neighbor's heap, fold into the running best-k
            ring_d = jax.lax.ppermute(ring_d, DATA_AXIS, perm)
            ring_i = jax.lax.ppermute(ring_i, DATA_AXIS, perm)
            cat_d = jnp.concatenate([best_d, ring_d], axis=1)  # (Ql, 2k)
            cat_i = jnp.concatenate([best_i, ring_i], axis=1)
            nd, sel = jax.lax.top_k(-cat_d, k)
            best_d = -nd
            best_i = jnp.take_along_axis(cat_i, sel, axis=1)
            return (best_d, best_i, ring_d, ring_i), None

        (best_d, best_i, _, _), _ = jax.lax.scan(
            hop, (dloc, iloc, dloc, iloc), None, length=n_shards - 1
        )
        return jnp.sqrt(best_d), best_i

    return step


@lru_cache(maxsize=None)
def cached_ring_knn_step(mesh: Mesh, k: int, with_ttl: bool = False,
                         impl: str | None = None):
    return _observed(
        "ring_knn", make_ring_knn_step(mesh, k, with_ttl, impl=impl)
    )


@lru_cache(maxsize=None)
def cached_batched_density_step(mesh: Mesh, width: int, height: int):
    return _observed(
        "batched_density",
        make_batched_density_step(mesh, width=width, height=height),
    )


@device_band(certain=True)
def make_corridor_step(heading: bool, bidirectional: bool):
    """Fused corridor kernel: N candidate rows × Q corridors × S segments
    in ONE device pass (the trajectory plane's tube-select/route-search
    engine, :mod:`geomesa_tpu.trajectory.corridor`).

    fn(cx, cy (N,) f32, bins, offs (N,) int32, hdg (N,) f32,
       segs (Q, S, 4) f32 [x1, y1, x2, y2], tq (Q, S, 4) int32 time quads,
       brg (Q, S) f32 segment bearings (deg CW from N),
       buf2_lo, buf2_hi, tol_lo, tol_hi (Q,) f32)
    → (cand (Q, N) bool, sure (Q, N) bool).

    Per (corridor, segment, row): clamped point-to-segment distance² in
    f32 plus the EXACT int-domain (bin, offset) time-window test (the
    ``ops.refine`` comparisons — time semantics can never drift from the
    scan kernels). f32 cannot decide boundary rows the way the f64
    referee does, so the kernel answers in the repo's two-band contract:
    ``cand`` uses the WIDENED thresholds (``buf2_hi`` / ``tol_hi`` — a
    superset: a row outside it is f64-certainly out) and ``sure`` the
    NARROWED ones (f64-certainly in); callers refine only ``cand & ~sure``
    rows host-side in f64 (:func:`geomesa_tpu.trajectory.corridor.
    corridor_masks_f64`). NaN headings fail both bands (IEEE compares are
    False) — matching the host rule that an invalid heading is never
    aligned. Padded segments carry the unsatisfiable time quad; padded
    corridors carry negative ``buf2`` bands; padded rows are sliced off
    by the caller. ``jax.lax.map`` over corridors bounds the live mask to
    (S, N) — candidate sets are query results, far below store N, so the
    step is a plain jit (no mesh sharding), like the polygon-join kernels.
    """

    @jax.jit
    def step(cx, cy, bins, offs, hdg, segs, tq, brg,
             buf2_lo, buf2_hi, tol_lo, tol_hi):
        def one(args):
            sg, t, b, b2lo, b2hi, tlo, thi = args
            x1, y1 = sg[:, 0][:, None], sg[:, 1][:, None]
            x2, y2 = sg[:, 2][:, None], sg[:, 3][:, None]
            dx, dy = x2 - x1, y2 - y1
            len2 = dx * dx + dy * dy
            safe = jnp.where(len2 > 0, len2, 1.0)
            tp = ((cx[None, :] - x1) * dx + (cy[None, :] - y1) * dy) / safe
            tp = jnp.clip(jnp.where(len2 > 0, tp, 0.0), 0.0, 1.0)
            d2 = (cx[None, :] - (x1 + tp * dx)) ** 2 + (
                cy[None, :] - (y1 + tp * dy)) ** 2
            after = (bins[None, :] > t[:, 0:1]) | (
                (bins[None, :] == t[:, 0:1]) & (offs[None, :] >= t[:, 1:2]))
            before = (bins[None, :] < t[:, 2:3]) | (
                (bins[None, :] == t[:, 2:3]) & (offs[None, :] <= t[:, 3:4]))
            ok = after & before
            cand = ok & (d2 <= b2hi)
            sure = ok & (d2 <= b2lo)
            if heading:
                diff = jnp.abs(
                    jnp.mod(hdg[None, :] - b[:, None] + 180.0, 360.0) - 180.0)
                if bidirectional:
                    diff = jnp.minimum(diff, 180.0 - diff)
                # a >=360° tolerance means UNCONSTRAINED (the _pack
                # sentinel for corridors without a heading predicate in
                # a mixed batch): accept explicitly — `NaN <= 360` is
                # False, so relying on the numeric compare would drop
                # NaN-heading rows from corridors that never asked for
                # heading, diverging from the f64 semantics
                cand &= (diff <= thi) | (thi >= 360.0)
                sure &= (diff <= tlo) | (tlo >= 360.0)
            return cand.any(axis=0), sure.any(axis=0)

        return jax.lax.map(
            one, (segs, tq, brg, buf2_lo, buf2_hi, tol_lo, tol_hi))

    return step


@device_band(cand=True)
@lru_cache(maxsize=None)
def cached_corridor_step(n_cap: int, s_cap: int, q_cap: int,
                         heading: bool, bidirectional: bool):
    """Memoized corridor step, ONE observed identity per (row bucket,
    segment bucket, corridor bucket, heading/bidirectional variant) —
    the same J003 discipline as :func:`cached_matrix_scan_step`: crossing
    a bucket is a first compile on a fresh identity, and the steady
    corridor path (same buckets, new payloads) is pinned at ZERO
    recompiles by the jaxmon census (tests/test_trajectory.py)."""
    tag = ("_h" if heading else "") + ("_b" if bidirectional else "")
    return _observed(
        f"corridor_n{n_cap}_s{s_cap}_q{q_cap}{tag}",
        make_corridor_step(heading, bidirectional),
    )


# above this group cardinality the (chunk, G) one-hot's O(n·G) FLOPs and
# footprint lose to segment_sum's O(n) — "auto" falls back to segments
_MXU_BINCOUNT_MAX_GROUPS = 2048


def _onehot_bincount(ids, num_classes: int, chunk: int = 8192):
    """Exact bincount as chunked one-hot matmuls (the MXU histogram trick
    the density step uses for its 2-D variant at ``make_batched_density_
    step``): bf16 one-hot entries are exactly 0/1, each (1, chunk) ·
    (chunk, C) product accumulates in f32 — exact because a chunk partial
    is <= ``chunk`` — and the CROSS-chunk carry rides int32, so totals stay
    exact at ANY count (an f32 carry would silently round past 2**24).

    ``ids`` (N,) int32 in [0, num_classes); returns (num_classes,) int32.
    CONTRACT: class ``num_classes - 1`` is a DISCARD class (callers route
    non-matching rows there and slice it off) — chunk padding joins it, so
    pad lanes never pollute a real bucket.
    """
    n = ids.shape[0]
    k = -(-n // chunk)
    pad = k * chunk - n
    sp = jnp.pad(ids, (0, pad), constant_values=num_classes - 1)
    sp = sp.reshape(k, chunk)

    def body(acc, sc):
        oh = jax.nn.one_hot(sc, num_classes, dtype=jnp.bfloat16)
        ones = jnp.ones((1, chunk), dtype=jnp.bfloat16)
        part = jax.lax.dot_general(
            ones, oh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc + part[0].astype(jnp.int32), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros(num_classes, jnp.int32), sp
    )
    return acc


def make_grouped_agg_step(mesh: Mesh, n_groups: int, n_vals: int,
                          capacity: int, with_ttl: bool = False,
                          impl: str = "auto", overlap: bool = False):
    """Fused grouped-aggregation scan: the distributed SQL GROUP BY engine
    (the ``GeoMesaRelation.scala:94`` / Spark relational-aggregation role,
    SURVEY.md §2.14) as ONE mesh pass — per shard, a segment-reduce of every
    value column over the group-id column; partials merged across the data
    axis with ``psum`` (counts/sums) and an ``all_gather`` + local
    min/max (extrema).

    Point mode: fn(x, y, bins, offs, gid, rowid, vals, true_n, boxes,
    times). Overlap mode (``overlap=True``, the XZ2/XZ3 extended-geometry
    layout): fn(xmin, ymin, xmax, ymax, bins, offs, gid, rowid, vals,
    true_n, boxes, times) — the spatial test is int-bbox overlap, exact
    for the envelope-semantics BBOX predicate away from edge buckets.
    Either returns →
        (cnt (Q, G) int32      — filter-matching rows per group,
         first (Q, G) int32    — min ``rowid`` among matching rows
                                 (int32 max where empty) — callers order
                                 groups by first-matching-row for host-fold
                                 parity,
         vcnt (Q, V, G) int32  — non-null values per group,
         vsum (Q, V, G) f64,
         vmin (Q, V, G) f64 (+inf where empty),
         vmax (Q, V, G) f64 (-inf where empty),
         edge_pos (Q, D, capacity) int32 global positions (-1 pad),
         edge_hits (Q, D) int32 true per-shard edge-candidate counts)

    ``gid`` is the int32 group id per row (index-sorted order, same perm as
    the resident x/y columns); ``rowid`` is the ORIGINAL row index per lane
    (the perm value); ``vals`` is (V, N) f64 with NaN for nulls.
    The filter follows the exact-count contract
    (:func:`make_batched_edge_gather_step`): rows in spatial edge buckets or
    at quantized time-window endpoints — the only rows where the int-domain
    superset can diverge from the f64 predicate — are EXCLUDED from the
    device fold and returned compacted; the host tests them exactly and ADDS
    the passing ones, which (unlike subtracting false positives) is a sound
    correction for min/max too. ``hits > capacity`` on any shard means that
    query's correction set truncated — the caller falls back for it.

    ``with_ttl``: one extra input ``cut`` (2,) int32 — the age-off cutoff's
    quantized (bin, offset). Rows strictly BELOW the cutoff unit are
    genuinely expired (quantization floors) and drop entirely; rows
    strictly AFTER it are genuinely fresh; rows AT the unit are ambiguous
    at quantized granularity and route to the boundary gather for the
    host's exact-millisecond re-add — the same additive-exactness scheme
    as the spatial/temporal edges, so live TTL stores stay on the mesh
    (the AgeOffIterator-at-scan role on the aggregation path).

    ``impl``: how the integer folds (cnt / vcnt) compute. ``"mxu"`` uses
    the one-hot-matmul bincount (:func:`_onehot_bincount` — the density
    kernel's scatter-beating trick, exact at any count via an int32
    cross-chunk carry); ``"segment"`` uses XLA segment_sum; ``"auto"``
    picks mxu on TPU backends when the group cardinality is small enough
    that the (chunk, G) one-hot pays for itself — high-cardinality GROUP
    BY does O(n·G) matmul FLOPs vs segment_sum's O(n), so it falls back.
    f64 sums and extrema always ride segment ops — matmul accumulation
    would cost f64 exactness.
    """
    if impl == "auto":
        impl = (
            "mxu"
            if jax.default_backend() == "tpu"
            and n_groups <= _MXU_BINCOUNT_MAX_GROUPS
            else "segment"
        )
    if impl not in ("mxu", "segment"):
        raise ValueError(f"impl must be auto|mxu|segment: {impl!r}")
    n_spatial = 6 if overlap else 4

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            *(P(DATA_AXIS) for _ in range(n_spatial)),  # spatial+time cols
            P(DATA_AXIS),        # gid
            P(DATA_AXIS),        # rowid
            P(None, DATA_AXIS),  # vals (V, N)
            P(),                 # true_n
            P(QUERY_AXIS, None, None),  # boxes
            P(QUERY_AXIS, None, None),  # times
            *((P(),) if with_ttl else ()),  # cut (2,)
        ),
        out_specs=(
            P(QUERY_AXIS, None),
            P(QUERY_AXIS, None),
            P(QUERY_AXIS, None, None),
            P(QUERY_AXIS, None, None),
            P(QUERY_AXIS, None, None),
            P(QUERY_AXIS, None, None),
            P(QUERY_AXIS, DATA_AXIS, None),
            P(QUERY_AXIS, DATA_AXIS),
        ),
        check_vma=False,
    )
    def step(*args):
        cols = args[:n_spatial]
        (gid, rowid, vals, true_n, boxes, times, *ttl_args) = args[n_spatial:]
        if overlap:
            fxmin, fymin, fxmax, fymax, bins, offs = cols
            n = fxmin.shape[0]
        else:
            x, y, bins, offs = cols
            n = x.shape[0]
        base = jax.lax.axis_index(DATA_AXIS) * n
        rows_valid = (base + jnp.arange(n, dtype=jnp.int32)) < true_n
        ttl_fresh = ttl_edge = None
        if with_ttl:
            (cut,) = ttl_args
            ttl_fresh = (bins > cut[0]) | ((bins == cut[0]) & (offs > cut[1]))
            ttl_edge = (bins == cut[0]) & (offs == cut[1])
            rows_valid = rows_valid & (ttl_fresh | ttl_edge)

        def one(args_q):
            boxes_q, times_q = args_q  # (B, 4), (T, 4)
            in_box = jnp.zeros((n,), dtype=jnp.bool_)
            on_edge = jnp.zeros((n,), dtype=jnp.bool_)
            for k in range(boxes_q.shape[0]):
                if overlap:
                    ins, edg = _slot_overlap(
                        fxmin, fymin, fxmax, fymax, boxes_q[k]
                    )
                else:
                    ins, edg = _slot_point(x, y, boxes_q[k])
                in_box |= ins
                on_edge |= edg
            time_edge = jnp.zeros((n,), dtype=jnp.bool_)
            for k in range(times_q.shape[0]):
                time_edge |= _slot_time_edge(bins, offs, times_q[k])
            if with_ttl:
                time_edge |= ttl_edge
            in_all = (
                in_box
                & _batched_time_match(bins, offs, times_q[None])[0]
                & rows_valid
            )
            boundary = in_all & (on_edge | time_edge)
            fold = in_all & ~(on_edge | time_edge)

            def bincount(mask):
                """Rows-matching-``mask`` per group, exactly."""
                s = jnp.where(mask, gid, n_groups)
                if impl == "segment":
                    return jax.ops.segment_sum(
                        mask.astype(jnp.int32), s,
                        num_segments=n_groups + 1,
                    )[:n_groups]
                return _onehot_bincount(s, n_groups + 1)[:n_groups]

            # non-folding rows route to an overflow segment that is sliced
            # off — segment ids stay static-shape friendly
            seg = jnp.where(fold, gid, n_groups)
            cnt = bincount(fold)
            imax = jnp.int32(np.iinfo(np.int32).max)
            first = jax.ops.segment_min(
                jnp.where(fold, rowid, imax), seg,
                num_segments=n_groups + 1,
            )[:n_groups]
            if n_vals:
                vcnts, vsums, vmins, vmaxs = [], [], [], []
                for v in range(n_vals):
                    vv = vals[v]
                    ok = fold & ~jnp.isnan(vv)
                    segv = jnp.where(ok, gid, n_groups)
                    vcnts.append(bincount(ok))
                    vsums.append(jax.ops.segment_sum(
                        jnp.where(ok, vv, 0.0), segv,
                        num_segments=n_groups + 1)[:n_groups])
                    vmins.append(jax.ops.segment_min(
                        jnp.where(ok, vv, jnp.inf), segv,
                        num_segments=n_groups + 1)[:n_groups])
                    vmaxs.append(jax.ops.segment_max(
                        jnp.where(ok, vv, -jnp.inf), segv,
                        num_segments=n_groups + 1)[:n_groups])
                vcnt, vsum = jnp.stack(vcnts), jnp.stack(vsums)
                vmin, vmax = jnp.stack(vmins), jnp.stack(vmaxs)
            else:
                vcnt = jnp.zeros((0, n_groups), dtype=jnp.int32)
                vsum = jnp.zeros((0, n_groups))
                vmin = jnp.zeros((0, n_groups))
                vmax = jnp.zeros((0, n_groups))
            dest = jnp.where(
                boundary, jnp.cumsum(boundary.astype(jnp.int32)) - 1, capacity
            )
            pos = jnp.full((capacity,), -1, dtype=jnp.int32)
            pos = pos.at[dest].set(
                base + jnp.arange(n, dtype=jnp.int32), mode="drop"
            )
            return (cnt, first, vcnt, vsum, vmin, vmax, pos,
                    boundary.sum(dtype=jnp.int32))

        cnt, first, vcnt, vsum, vmin, vmax, pos, hits = jax.lax.map(
            one, (boxes, times)
        )
        # min/max merges: the TPU compiler lowers only SUM all-reduces of
        # f64 ("Supported lowering only of Sum all reduce"), so the
        # extrema gather every shard's (small) partial and reduce locally
        def gmin(v):
            return jnp.min(jax.lax.all_gather(v, DATA_AXIS), axis=0)

        def gmax(v):
            return jnp.max(jax.lax.all_gather(v, DATA_AXIS), axis=0)

        return (
            jax.lax.psum(cnt, DATA_AXIS),
            gmin(first),
            jax.lax.psum(vcnt, DATA_AXIS),
            jax.lax.psum(vsum, DATA_AXIS),
            gmin(vmin),
            gmax(vmax),
            pos[:, None, :],
            hits[:, None],
        )

    return step


@lru_cache(maxsize=None)
def cached_grouped_agg_step(mesh: Mesh, n_groups: int, n_vals: int,
                            capacity: int, with_ttl: bool = False,
                            impl: str = "auto", overlap: bool = False):
    return _observed(
        "grouped_agg",
        make_grouped_agg_step(
            mesh, n_groups, n_vals, capacity, with_ttl, impl, overlap
        ),
    )
