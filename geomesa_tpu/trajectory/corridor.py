"""Corridor engine: tube-select / route-search as ONE device problem.

``TubeSelectProcess`` and ``RouteSearchProcess`` (PAPER.md §1,
``geomesa-process``) survive in :mod:`geomesa_tpu.process` as host-side
per-query refines — each query pays a full planned scan plus a NumPy
``candidates × segments`` pass. This module re-casts Q concurrent
corridor queries as one ``(rows × corridors)`` device problem, the same
shape move the ISSUE-8 subscription matrix made for standing queries
(PAPERS.md: batch-parallel predicate evaluation is where accelerators
dominate):

- candidate pruning decomposes through the PLANNER: the union of every
  corridor's per-segment buffered bbox (+ time window) runs as one
  planned scan (``ds.query`` — Z/XZ range decomposition, residual,
  visibility, all for free);
- corridor segments pack into PADDED QUERY MATRICES in power-of-two
  buckets (rows / segments / corridors — tpulint J003, the subscription-
  matrix discipline), evaluated by the fused point-to-segment-distance +
  exact-int-time + heading kernel
  (:func:`geomesa_tpu.parallel.query.cached_corridor_step`);
- the kernel answers in two f32 bands (``cand`` widened superset,
  ``sure`` narrowed certain-in); only the sliver between them re-checks
  in f64 (:func:`corridor_masks_f64`) — results are EXACTLY the host
  f64 semantics, at device cost;
- the device-vs-host route rides the ISSUE-9 cost model under
  ``traj:corridor-dev`` / ``traj:corridor-host`` signatures, and sampled
  results shadow-compare against the DEMOTED host paths
  (``process.processes.tube_select`` / ``process.tracks.route_search``)
  through the ISSUE-13 audit plane (kind ``corridor``).

No locks of its own; no jax at module import (``GEOMESA_TPU_NO_JAX``
safe). See docs/trajectory.md for the corridor matrix grammar and the
exact-vs-superset semantics.
"""

from __future__ import annotations

import time as _time
from dataclasses import dataclass

import numpy as np

from geomesa_tpu.analysis.contracts import (
    device_band,
    dispatch_budget,
    host_sync_free,
)
from geomesa_tpu.filter import ast
from geomesa_tpu.planning.planner import Query

__all__ = [
    "CorridorSpec", "corridor_masks_f64", "route_search_device",
    "tube_select_device", "tube_select_many",
]

MIN_ROW_BUCKET = 1024  # row-padding floor (shared shape bucket discipline)
MIN_SEG_BUCKET = 4
# f32 distance band half-width (deg): the widened/narrowed thresholds
# must COVER worst-case f32 error of the point-to-segment computation at
# lon/lat magnitudes (|coord| ≤ 360 → projection + cancellation error
# ≲ 1e-4); 2e-3 gives >10× margin. Only perf rides the width — every
# band row re-checks in f64 — correctness rides it covering f32 error.
DIST_SLACK_DEG = 2e-3
HEADING_SLACK_DEG = 0.05  # f32 error of the mod-360 wrap is ≲ 1e-4 deg


@dataclass(frozen=True)
class CorridorSpec:
    """One corridor query: ordered waypoints, optional per-waypoint times.

    ``pts``: (P, 2) f64 (lon, lat) waypoints, P ≥ 2. ``ts``: (P,) int64
    epoch-ms (tube-select), or None (route-search: no time constraint).
    ``heading_tolerance_deg`` None = no heading constraint."""

    pts: tuple
    ts: tuple | None
    buffer_deg: float
    time_buffer_ms: int = 0
    heading_tolerance_deg: float | None = None
    bidirectional: bool = False

    @staticmethod
    def tube(track, buffer_deg: float, time_buffer_ms: int) -> "CorridorSpec":
        """From a tube-select track ``[(lon, lat, epoch_ms), ...]``."""
        if len(track) < 2:
            raise ValueError("tube requires at least 2 waypoints")
        return CorridorSpec(
            pts=tuple((float(x), float(y)) for x, y, _ in track),
            ts=tuple(int(t) for _, _, t in track),
            buffer_deg=float(buffer_deg),
            time_buffer_ms=int(time_buffer_ms),
        )

    @staticmethod
    def route(route, buffer_deg: float, heading_tolerance_deg=None,
              bidirectional: bool = False) -> "CorridorSpec":
        """From a route-search waypoint list ``[(lon, lat), ...]``."""
        if len(route) < 2:
            raise ValueError("route requires at least 2 waypoints")
        return CorridorSpec(
            pts=tuple((float(x), float(y)) for x, y in route),
            ts=None,
            buffer_deg=float(buffer_deg),
            heading_tolerance_deg=(
                None if heading_tolerance_deg is None
                else float(heading_tolerance_deg)),
            bidirectional=bool(bidirectional),
        )

    def segments(self):
        """(x1, y1, x2, y2 (S,) f64, t_lo, t_hi (S,) int64 | None)."""
        p = np.asarray(self.pts, dtype=np.float64)
        x1, y1 = p[:-1, 0], p[:-1, 1]
        x2, y2 = p[1:, 0], p[1:, 1]
        if self.ts is None:
            return x1, y1, x2, y2, None, None
        t = np.asarray(self.ts, dtype=np.int64)
        lo = np.minimum(t[:-1], t[1:]) - self.time_buffer_ms
        hi = np.maximum(t[:-1], t[1:]) + self.time_buffer_ms
        return x1, y1, x2, y2, lo, hi

    def bearings(self) -> np.ndarray:
        """Per-segment bearing (deg CW from N) — the route-search rule."""
        x1, y1, x2, y2, _, _ = self.segments()
        return np.degrees(np.arctan2(x2 - x1, y2 - y1)) % 360.0


from geomesa_tpu.trajectory.state import pow2_bucket as _pow2  # noqa: E402
# one shared bucket rule — a private copy here would let the corridor
# and track-state padding disciplines silently diverge


def prune_filter(sft, specs, base=None) -> ast.Filter:
    """The planner-facing candidate filter: OR over every corridor's
    per-segment buffered bbox (AND time window when timed) — the same
    primary bounds the demoted host paths used per query, now ONE planned
    scan for the whole batch. The query path's exact residual re-applies
    this OR, so candidates are a sound superset of every corridor's rows."""
    parts = []
    for spec in specs:
        x1, y1, x2, y2, lo, hi = spec.segments()
        b = spec.buffer_deg
        for i in range(len(x1)):
            box = ast.BBox(
                sft.geom_field,
                min(x1[i], x2[i]) - b, min(y1[i], y2[i]) - b,
                max(x1[i], x2[i]) + b, max(y1[i], y2[i]) + b)
            if lo is not None:
                if sft.dtg_field is None:
                    raise ValueError(
                        "timed corridor over a schema with no dtg field")
                box = ast.And([
                    box,
                    ast.During(sft.dtg_field, int(lo[i]) - 1, int(hi[i]) + 1),
                ])
            parts.append(box)
    f = parts[0] if len(parts) == 1 else ast.Or(parts)
    if base is not None:
        from geomesa_tpu.filter.cql import parse

        base = parse(base) if isinstance(base, str) else base
        f = ast.And([f, base])
    return f


@device_band(refine=True)
def corridor_masks_f64(xs, ys, tms, hdg, specs) -> np.ndarray:
    """EXACT f64 corridor membership: (Q, N) bool over the given rows.

    THE one semantic definition — the device route's band refine, the
    host route, and the parity tests all call it, so the three cannot
    drift. A row matches a corridor when SOME segment has point-to-
    segment distance ≤ buffer AND (if timed) the row's time inside the
    segment's buffered span AND (if heading-constrained) a finite heading
    within tolerance of the segment bearing (invalid/NaN headings are
    never aligned — the route-search rule)."""
    n = len(xs)
    out = np.zeros((len(specs), n), dtype=bool)
    if n == 0:
        return out
    cx, cy = xs[:, None], ys[:, None]
    for qi, spec in enumerate(specs):
        x1, y1, x2, y2, lo, hi = spec.segments()
        dx, dy = (x2 - x1)[None, :], (y2 - y1)[None, :]
        len2 = dx * dx + dy * dy
        with np.errstate(divide="ignore", invalid="ignore"):
            tp = np.where(
                len2 > 0,
                ((cx - x1[None, :]) * dx + (cy - y1[None, :]) * dy) / len2,
                0.0)
        tp = np.clip(tp, 0.0, 1.0)
        d2 = (cx - (x1[None, :] + tp * dx)) ** 2 + (
            cy - (y1[None, :] + tp * dy)) ** 2
        ok = d2 <= spec.buffer_deg ** 2
        if lo is not None:
            ct = tms[:, None]
            ok &= (ct >= lo[None, :]) & (ct <= hi[None, :])
        if spec.heading_tolerance_deg is not None:
            if hdg is None:
                raise ValueError("heading-constrained corridor without a "
                                 "heading column")
            brg = spec.bearings()[None, :]
            h = hdg[:, None]
            with np.errstate(invalid="ignore"):
                diff = np.abs((h - brg + 180.0) % 360.0 - 180.0)
            if spec.bidirectional:
                diff = np.minimum(diff, 180.0 - diff)
            aligned = np.isfinite(h) & (diff <= spec.heading_tolerance_deg)
            ok &= aligned
        out[qi] = ok.any(axis=1)
    return out


def _pack(specs, sft):
    """Corridor batch → padded device payloads (the corridor matrix).

    Returns (segs (Q, S, 4) f32, tq (Q, S, 4) int32, brg (Q, S) f32,
    buf2_lo, buf2_hi, tol_lo, tol_hi (Q,) f32, q_cap, s_cap). Padded
    segments hold the unsatisfiable time quad; padded corridors hold
    negative distance bands; corridors without a heading constraint hold
    the ≥360° unconstrained sentinel the kernel accepts outright (a
    finite stand-in would drop NaN-heading rows — NaN compares False)."""
    from geomesa_tpu.store.backends import time_quads

    q_cap = _pow2(len(specs))
    s_cap = _pow2(max(len(s.pts) - 1 for s in specs), MIN_SEG_BUCKET)
    segs = np.zeros((q_cap, s_cap, 4), dtype=np.float32)
    tq = np.tile(np.array([1, 0, 0, -1], dtype=np.int32), (q_cap, s_cap, 1))
    brg = np.zeros((q_cap, s_cap), dtype=np.float32)
    buf2_lo = np.full(q_cap, -1.0, dtype=np.float32)
    buf2_hi = np.full(q_cap, -1.0, dtype=np.float32)
    tol_lo = np.full(q_cap, -1.0, dtype=np.float32)
    tol_hi = np.full(q_cap, -1.0, dtype=np.float32)
    unconstrained = np.array([0, -1, 2**31 - 1, 2**31 - 1], dtype=np.int32)
    for qi, spec in enumerate(specs):
        x1, y1, x2, y2, lo, hi = spec.segments()
        s = len(x1)
        segs[qi, :s, 0] = x1
        segs[qi, :s, 1] = y1
        segs[qi, :s, 2] = x2
        segs[qi, :s, 3] = y2
        if lo is None:
            tq[qi, :s] = unconstrained
        else:
            for si in range(s):
                quads = time_quads(sft, [(int(lo[si]), int(hi[si]))])
                tq[qi, si] = quads[0] if quads is not None else unconstrained
        brg[qi, :s] = spec.bearings().astype(np.float32)
        b = spec.buffer_deg
        buf2_lo[qi] = max(b - DIST_SLACK_DEG, 0.0) ** 2
        buf2_hi[qi] = (b + DIST_SLACK_DEG) ** 2
        tol = spec.heading_tolerance_deg
        if tol is None:
            # unconstrained sentinel (>= 360): the kernel accepts these
            # corridors outright — a finite stand-in like 181 would
            # still drop NaN-heading rows (NaN compares False)
            tol_lo[qi] = tol_hi[qi] = 999.0
        else:
            tol_lo[qi] = max(tol - HEADING_SLACK_DEG, 0.0)
            tol_hi[qi] = tol + HEADING_SLACK_DEG
    return segs, tq, brg, buf2_lo, buf2_hi, tol_lo, tol_hi, q_cap, s_cap


def _choose_route(type_name: str) -> str:
    """Device corridor matrix vs. host f64 refine, via the adaptive cost
    model (``traj:corridor-dev`` / ``traj:corridor-host`` profiles; the
    device seed wins until both are trained, the probe schedule keeps the
    loser measured — the ISSUE-9 contract)."""
    from geomesa_tpu.planning.costmodel import Candidate, model

    win, _, _ = model().choose(type_name, "corridor", [
        Candidate("device", "traj:corridor-dev", seed_ms=1.0),
        Candidate("host", "traj:corridor-host", seed_ms=2.0),
    ])
    return win.name


@dispatch_budget(2)
def tube_select_many(ds, type_name: str, specs, filter=None,
                     heading_field: str | None = None,
                     route: str | None = None, auths=None):
    """Q corridor queries in one pass → per-corridor result tables.

    ONE planned candidate scan (the union prune filter), then either the
    fused device kernel + f64 band refine or the host f64 refine over
    the shared candidates (cost-model routed; ``route`` forces). Results
    are exactly :func:`corridor_masks_f64` semantics either way.
    ``auths``: record-level visibility for the candidate scan (the
    serving layer's restricted callers)."""
    specs = list(specs)
    if not specs:
        return []
    sft = ds.get_schema(type_name)
    if heading_field is None and any(
            s.heading_tolerance_deg is not None for s in specs):
        raise ValueError("heading-constrained specs need heading_field")
    r = ds.query(type_name, Query(
        filter=prune_filter(sft, specs, filter), auths=auths))
    t = r.table
    from geomesa_tpu.schema.columnar import representative_xy

    n = len(t)
    if n == 0:
        return [t for _ in specs]
    xs, ys = representative_xy(t)
    tms = t.dtg_millis() if sft.dtg_field else np.zeros(n, dtype=np.int64)
    hdg = None
    if heading_field is not None:
        col = t.columns[heading_field]
        raw = col.values.astype(np.float64)
        with np.errstate(invalid="ignore"):
            hdg = raw % 360.0  # NaN stays NaN (never aligned), warning-free
        if col.valid is not None:
            hdg = np.where(col.valid, hdg, np.nan)
    chosen = route or _choose_route(type_name)
    t0 = _time.perf_counter()
    if chosen == "device":
        masks = _device_masks(sft, specs, xs, ys, tms, hdg)
    else:
        masks = corridor_masks_f64(xs, ys, tms, hdg, specs)
    _observe_route(type_name, chosen, t0, int(masks.sum()))
    out = [t.take(np.nonzero(masks[qi])[0]) for qi in range(len(specs))]
    if auths is None:  # the demoted referee paths are auth-unaware
        _maybe_audit(ds, type_name, specs, filter, heading_field, out)
    return out


@dispatch_budget(2)
def _device_masks(sft, specs, xs, ys, tms, hdg) -> np.ndarray:
    """The device route. One fused kernel dispatch normally; a batch
    mixing uni- and bidirectional heading constraints compiles one
    kernel variant per directionality, so it splits into two
    homogeneous :func:`_corridor_kernel` calls — the worst case the
    dispatch budget declares."""
    heading = hdg is not None and any(
        s.heading_tolerance_deg is not None for s in specs)
    bidirectional = heading and any(
        s.bidirectional for s in specs
        if s.heading_tolerance_deg is not None)
    if bidirectional and not all(
            s.bidirectional for s in specs
            if s.heading_tolerance_deg is not None):
        # one kernel variant per batch: mixed directionality splits
        uni = [s for s in specs if not (s.heading_tolerance_deg is not None
                                        and s.bidirectional)]
        bi = [s for s in specs if s.heading_tolerance_deg is not None
              and s.bidirectional]
        m = np.zeros((len(specs), len(xs)), dtype=bool)
        mu = _corridor_kernel(sft, uni, xs, ys, tms, hdg)
        mb = _corridor_kernel(sft, bi, xs, ys, tms, hdg)
        iu = ib = 0
        for qi, s in enumerate(specs):
            if s.heading_tolerance_deg is not None and s.bidirectional:
                m[qi] = mb[ib]
                ib += 1
            else:
                m[qi] = mu[iu]
                iu += 1
        return m
    else:
        return _corridor_kernel(sft, specs, xs, ys, tms, hdg)


@dispatch_budget(1)
@host_sync_free
def _corridor_kernel(sft, specs, xs, ys, tms, hdg) -> np.ndarray:
    """One fused corridor dispatch over a directionality-homogeneous
    spec batch: padded corridor matrices through the fused kernel, then
    f64 re-check of the ``cand & ~sure`` band only. Sync-free up to the
    single retired readback of the two band masks — no hidden
    inter-stage await on the corridor path."""
    import jax.numpy as jnp

    from geomesa_tpu.curve.binned_time import BinnedTime
    from geomesa_tpu.obs.jaxmon import count_h2d
    from geomesa_tpu.parallel.query import cached_corridor_step

    n = len(xs)
    n_cap = _pow2(n, MIN_ROW_BUCKET)
    if sft.dtg_field:
        binned = BinnedTime(sft.z3_interval)
        bins, offs = binned.to_bin_and_offset(tms)
    else:
        bins = offs = np.zeros(n, dtype=np.int64)
    heading = hdg is not None and any(
        s.heading_tolerance_deg is not None for s in specs)
    bidirectional = heading and any(
        s.bidirectional for s in specs
        if s.heading_tolerance_deg is not None)

    def pad(a, dtype):
        out = np.zeros(n_cap, dtype=dtype)
        out[:n] = a
        return out

    cx = pad(xs.astype(np.float32), np.float32)
    cy = pad(ys.astype(np.float32), np.float32)
    pb = pad(np.asarray(bins, dtype=np.int32), np.int32)
    po = pad(np.asarray(offs, dtype=np.int32), np.int32)
    ph = pad(
        (hdg if hdg is not None else np.zeros(n)).astype(np.float32),
        np.float32)
    (segs, tq, brg, b2lo, b2hi, tlo, thi, q_cap, s_cap) = _pack(specs, sft)
    count_h2d(cx, cy, pb, po, ph, segs, tq, brg, label="tracks")
    step = cached_corridor_step(n_cap, s_cap, q_cap, heading, bidirectional)
    cand, sure = step(
        jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(pb), jnp.asarray(po),
        jnp.asarray(ph), jnp.asarray(segs), jnp.asarray(tq),
        jnp.asarray(brg), jnp.asarray(b2lo), jnp.asarray(b2hi),
        jnp.asarray(tlo), jnp.asarray(thi))
    cand = np.asarray(cand)[: len(specs), :n]  # tpusync: retire
    sure = np.asarray(sure)[: len(specs), :n]  # tpusync: retire
    out = sure.copy()
    band = cand & ~sure
    for qi in np.nonzero(band.any(axis=1))[0]:
        rows = np.nonzero(band[qi])[0]
        exact = corridor_masks_f64(
            xs[rows], ys[rows], tms[rows],
            None if hdg is None else hdg[rows], [specs[qi]])
        out[qi, rows] |= exact[0]
    return out


def _observe_route(type_name: str, route: str, t0: float, rows: int) -> None:
    from geomesa_tpu.obs import audit as _audit, devmon

    if _audit.in_shadow():
        return  # shadow re-executions must not train the traj profiles
    devmon.costs().observe_cost(
        type_name, f"traj:corridor-{'dev' if route == 'device' else 'host'}",
        wall_ms=(_time.perf_counter() - t0) * 1000.0, rows=rows)


def _maybe_audit(ds, type_name: str, specs, filter, heading_field,
                 results) -> None:
    """Sampled shadow comparison against the DEMOTED process paths
    (``tube_select`` / ``route_search``) — the ISSUE-13 contract for the
    corridor engine: the independent referee is the code this module
    replaced, run in ``audit.shadow()`` so it trains nothing."""
    from geomesa_tpu.obs import audit as _audit

    if not _audit.enabled() or _audit.in_shadow() or not _audit.sampled():
        return
    spec = specs[0]
    live = sorted(str(f) for f in results[0].fids)
    try:
        with _audit.shadow():
            ref_table = _referee_one(ds, type_name, spec, filter,
                                     heading_field)
        ref = sorted(str(f) for f in ref_table.fids)
    except Exception as e:  # noqa: BLE001 — referee trouble is counted, never raised
        _audit.get().note_check("corridor", True, type_name=type_name,
                                detail=f"abstain: {type(e).__name__}: {e}",
                                abstain=True)
        return
    from geomesa_tpu.ops.referee import fid_sets_equal

    ok, detail = fid_sets_equal(live, ref)
    _audit.get().note_check("corridor", ok, type_name=type_name,
                            detail=detail)


def _referee_one(ds, type_name: str, spec: CorridorSpec, filter,
                 heading_field):
    """One corridor through the demoted host process path."""
    if spec.ts is not None:
        from geomesa_tpu.process.processes import tube_select

        track = [(x, y, t) for (x, y), t in zip(spec.pts, spec.ts)]
        return tube_select(ds, type_name, track, spec.buffer_deg,
                           spec.time_buffer_ms, filter=filter)
    from geomesa_tpu.process.tracks import route_search

    return route_search(
        ds, type_name, list(spec.pts), spec.buffer_deg,
        heading_field=(heading_field
                       if spec.heading_tolerance_deg is not None else None),
        heading_tolerance_deg=(spec.heading_tolerance_deg or 45.0),
        bidirectional=spec.bidirectional, filter=filter)


def tube_select_device(ds, type_name: str, track, buffer_deg: float,
                       time_buffer_ms: int, filter=None, auths=None):
    """Single tube-select on the corridor engine (the product path; the
    old :func:`geomesa_tpu.process.processes.tube_select` is the audit
    referee)."""
    spec = CorridorSpec.tube(track, buffer_deg, time_buffer_ms)
    return tube_select_many(
        ds, type_name, [spec], filter=filter, auths=auths)[0]


def route_search_device(ds, type_name: str, route, buffer_deg: float,
                        heading_field: str | None = None,
                        heading_tolerance_deg: float = 45.0,
                        bidirectional: bool = False, filter=None,
                        auths=None):
    """Single route-search on the corridor engine (the product path; the
    old :func:`geomesa_tpu.process.tracks.route_search` is the audit
    referee)."""
    spec = CorridorSpec.route(
        route, buffer_deg,
        heading_tolerance_deg=(heading_tolerance_deg
                               if heading_field is not None else None),
        bidirectional=bidirectional)
    return tube_select_many(
        ds, type_name, [spec], filter=filter, heading_field=heading_field,
        auths=auths)[0]
