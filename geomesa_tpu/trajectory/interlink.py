"""Federated spatial interlinking: batched ST_* predicate links between
two stores.

The JedAI-spatial shape (PAPERS.md: *Three-dimensional Geospatial
Interlinking with JedAI-spatial*): given two datasets, emit every pair
``(left, right)`` satisfying an ST_* predicate — here the columnar
envelope predicates the tree evaluates exactly:

- ``intersects`` — the feature envelopes overlap (touching counts); for
  point features this is exact point-in-box / point-equality;
- ``dwithin`` — envelope-to-envelope distance ≤ ``distance`` (degrees);
- either predicate TIME-LIFTED to 3D (the XZ3 leg): additionally
  ``|t_left − t_right| ≤ time_buffer_ms``.

Candidate pairing is where the curves earn their keep: right-side
envelopes index into XZ sequence codes (:class:`geomesa_tpu.curve.xz.
XZSFC` — 2D, or dims=3 with the time axis lifted into the cube), and
each left envelope's buffered window covers via ``XZSFC.ranges`` →
``searchsorted`` over the sorted codes. The XZ cover is a SUPERSET
(property-pinned in tests/test_trajectory.py): every truly-linked pair
survives pruning, and the exact f64 refine keeps only real links — the
returned pair set is EXACTLY the nested-loop f64 referee's
(:func:`interlink_referee`), which is how the bench gate pins it.

For point right-stores with z2 device residency the candidate gather can
ride the blocked device join instead (``process.join.join_rows_device``
— the ops/join block-sparse kernels), cost-model routed under
``traj:link-xz`` / ``traj:link-block``. Two members of a federated /
sharded view link via :func:`link_members`.

No locks; no jax at module import (``GEOMESA_TPU_NO_JAX`` safe).
"""

from __future__ import annotations

import time as _time

import numpy as np

from geomesa_tpu.planning.planner import Query

__all__ = [
    "envelopes", "interlink", "interlink_referee", "link_members",
]

PREDICATES = ("intersects", "dwithin")
XZ_G = 12  # curve precision (the reference default)
MAX_RANGES_PER_LEFT = 64  # range budget per left window (coarse = superset)


def envelopes(table):
    """Per-row f64 envelopes ``(xmin, ymin, xmax, ymax, valid)`` — points
    degenerate, extended geometries their bounds, null/NaN rows invalid."""
    col = table.geom_column()
    n = len(table)
    if col.x is not None:
        x = np.asarray(col.x, dtype=np.float64)
        y = np.asarray(col.y, dtype=np.float64)
        b = np.stack([x, y, x, y], axis=1)
    elif col.bounds is not None:
        b = np.asarray(col.bounds, dtype=np.float64)
    else:
        return (np.zeros(n),) * 4 + (np.zeros(n, dtype=bool),)
    valid = np.isfinite(b).all(axis=1)
    if col.valid is not None:
        valid &= col.valid
    b = np.where(valid[:, None], b, 0.0)
    return b[:, 0], b[:, 1], b[:, 2], b[:, 3], valid


def _rect_dist2(lx1, ly1, lx2, ly2, rx1, ry1, rx2, ry2):
    """Squared envelope-to-envelope distance (0 when overlapping)."""
    dx = np.maximum(np.maximum(rx1 - lx2, lx1 - rx2), 0.0)
    dy = np.maximum(np.maximum(ry1 - ly2, ly1 - ry2), 0.0)
    return dx * dx + dy * dy


def interlink_referee(ltable, rtable, pred: str = "intersects",
                      distance: float = 0.0,
                      time_buffer_ms: int | None = None) -> list:
    """Nested-loop f64 referee: every (left fid, right fid) pair under
    the predicate, sorted — no XZ pruning, no device, no planner. The
    parity oracle for :func:`interlink` (the bench-gate leg compares the
    exact pair sets)."""
    lx1, ly1, lx2, ly2, lv = envelopes(ltable)
    rx1, ry1, rx2, ry2, rv = envelopes(rtable)
    d = float(distance) if pred == "dwithin" else 0.0
    lt = ltable.dtg_millis() if time_buffer_ms is not None else None
    rt = rtable.dtg_millis() if time_buffer_ms is not None else None
    out = []
    for i in range(len(ltable)):
        if not lv[i]:
            continue
        ok = rv & (_rect_dist2(lx1[i], ly1[i], lx2[i], ly2[i],
                               rx1, ry1, rx2, ry2) <= d * d)
        if time_buffer_ms is not None:
            ok &= np.abs(rt - lt[i]) <= int(time_buffer_ms)
        for j in np.nonzero(ok)[0]:
            out.append((str(ltable.fids[i]), str(rtable.fids[j])))
    out.sort()
    return out


def _xz_candidates(ltable, rtable, distance: float,
                   time_buffer_ms: int | None, lenv, renv):
    """XZ-range candidate pairing: right envelopes → sorted XZ codes;
    per left row, the buffered window's range cover → candidate right
    rows. 2D (:func:`geomesa_tpu.curve.xz.xz2_sfc`) untimed; dims=3 with
    the time axis lifted into the cube when ``time_buffer_ms`` is set.
    ``lenv``/``renv``: the tables' precomputed :func:`envelopes` tuples
    (computed ONCE in :func:`interlink`, shared with the refine stage).
    Yields ``(left_row, candidate_right_rows)`` for valid left rows."""
    from geomesa_tpu.curve.xz import XZSFC, xz2_sfc

    lx1, ly1, lx2, ly2, lv = lenv
    rx1, ry1, rx2, ry2, rv = renv
    rrows = np.nonzero(rv)[0]
    if len(rrows) == 0:
        return
    if time_buffer_ms is None:
        sfc = xz2_sfc(XZ_G)
        codes = sfc.index((rx1[rrows], ry1[rrows]), (rx2[rrows], ry2[rrows]))
        t_lo = t_hi = None
    else:
        lt = ltable.dtg_millis()
        rt = rtable.dtg_millis()
        buf = int(time_buffer_ms)
        tmin = float(min(lt.min() if len(lt) else 0,
                         rt[rrows].min()) - buf - 1)
        tmax = float(max(lt.max() if len(lt) else 1,
                         rt[rrows].max()) + buf + 1)
        sfc = XZSFC(g=XZ_G, dims=3, mins=(-180.0, -90.0, tmin),
                    maxs=(180.0, 90.0, tmax))
        t = rt[rrows].astype(np.float64)
        codes = sfc.index((rx1[rrows], ry1[rrows], t),
                          (rx2[rrows], ry2[rrows], t))
        t_lo, t_hi = lt.astype(np.float64) - buf, lt.astype(np.float64) + buf
    order = np.argsort(codes, kind="stable")
    sorted_codes = codes[order]
    sorted_rows = rrows[order]
    for i in np.nonzero(lv)[0]:
        lo = (lx1[i] - distance, ly1[i] - distance)
        hi = (lx2[i] + distance, ly2[i] + distance)
        if t_lo is not None:
            lo = lo + (t_lo[i],)
            hi = hi + (t_hi[i],)
        ranges = sfc.ranges([(lo, hi)], max_ranges=MAX_RANGES_PER_LEFT)
        if len(ranges) == 0:
            continue
        starts = np.searchsorted(sorted_codes, ranges[:, 0], side="left")
        ends = np.searchsorted(sorted_codes, ranges[:, 1], side="right")
        cand = np.concatenate(
            [sorted_rows[s:e] for s, e in zip(starts, ends)]
        ) if np.any(ends > starts) else np.empty(0, dtype=np.int64)
        if len(cand):
            yield i, np.unique(cand)


def _block_candidates(ltable, rds, rtype, distance: float):
    """The blocked-device-join pairing (ops/join block-sparse kernels via
    ``process.join.join_rows_device``): each left buffered envelope as a
    box polygon against the right store's z2-resident point layout — an
    int-domain SUPERSET gather with exact host refine inside the join,
    so refine below still decides the final pairs. Raises ValueError
    when the layout can't serve (caller falls back to XZ pairing)."""
    from geomesa_tpu.geometry.types import Polygon
    from geomesa_tpu.process.join import join_rows_device

    lx1, ly1, lx2, ly2, lv = envelopes(ltable)
    boxes = []
    rows_for = []
    for i in np.nonzero(lv)[0]:
        x1, y1 = lx1[i] - distance, ly1[i] - distance
        x2, y2 = lx2[i] + distance, ly2[i] + distance
        boxes.append(Polygon(np.array(
            [[x1, y1], [x2, y1], [x2, y2], [x1, y2], [x1, y1]])))
        rows_for.append(i)
    if not boxes:
        return None
    snap, pairs = join_rows_device(rds, rtype, boxes, pred="intersects")
    out = []
    for bi, rrows in pairs:
        if len(rrows):
            out.append((rows_for[bi], np.asarray(rrows, dtype=np.int64)))
    return snap, out


def _choose_pairing(rds, rtype: str) -> str:
    from geomesa_tpu.planning.costmodel import Candidate, model

    win, _, _ = model().choose(rtype, "link", [
        Candidate("xz", "traj:link-xz", seed_ms=1.0),
        Candidate("block", "traj:link-block", seed_ms=2.0),
    ])
    return win.name


def interlink(lds, ltype: str, rds, rtype: str, pred: str = "intersects",
              distance: float = 0.0, time_buffer_ms: int | None = None,
              lfilter=None, rfilter=None, route: str | None = None,
              auths=None) -> list:
    """Batched predicate linking between two stores → sorted
    ``[(left_fid, right_fid), ...]`` — the exact pair set of
    :func:`interlink_referee` over the same snapshots.

    ``pred``: ``intersects`` | ``dwithin`` (envelope semantics above).
    ``time_buffer_ms`` switches to the XZ3 time-lifted 3D leg. ``route``
    forces the candidate pairing (``"xz"`` | ``"block"``); by default the
    2D point case consults the cost model and everything else pairs via
    XZ ranges."""
    if pred not in PREDICATES:
        raise ValueError(f"unsupported predicate {pred!r} "
                         f"(supported: {PREDICATES})")
    d = float(distance) if pred == "dwithin" else 0.0
    if d < 0:
        raise ValueError("distance must be >= 0")
    ltable = lds.query(ltype, Query(filter=lfilter, auths=auths)).table
    t0 = _time.perf_counter()
    chosen = route
    if chosen is None:
        # the block route's device join runs auth-unaware — restricted
        # callers stay on the XZ pairing whose right scan applies auths
        chosen = ("xz" if (time_buffer_ms is not None or rfilter is not None
                           or auths is not None)
                  else _choose_pairing(rds, rtype))
    elif chosen == "block" and (time_buffer_ms is not None
                                or rfilter is not None or auths is not None):
        # a FORCED block route must not silently widen: the device join
        # cannot apply a right filter, auths, or the time lift
        raise ValueError(
            "route='block' cannot serve rfilter/auths/time_buffer_ms — "
            "use route='xz' (or let the router decide)")
    pairs: list = []
    if chosen == "block":
        try:
            got = _block_candidates(ltable, rds, rtype, d)
        except (ValueError, AttributeError):
            # layout can't serve — fall to XZ, and restart the clock so
            # the failed block attempt's wall never trains the xz
            # profile (a polluted xz p50 would skew every later route
            # choice against the path that actually ran)
            chosen = "xz"
            got = None
            t0 = _time.perf_counter()
        if chosen == "block":
            if got is not None:
                rtable, cands = got
                pairs = _refine(ltable, rtable, cands, pred, d,
                                time_buffer_ms)
            _observe_link(rtype, "block", t0, len(pairs))
            return pairs
    rtable = rds.query(rtype, Query(filter=rfilter, auths=auths)).table
    lenv = envelopes(ltable)
    renv = envelopes(rtable)
    cands = list(_xz_candidates(ltable, rtable, d, time_buffer_ms,
                                lenv, renv))
    pairs = _refine(ltable, rtable, cands, pred, d, time_buffer_ms,
                    lenv=lenv, renv=renv)
    _observe_link(rtype, "xz", t0, len(pairs))
    _maybe_audit(ltable, rtable, pred, d, time_buffer_ms, pairs)
    return pairs


def _refine(ltable, rtable, cands, pred: str, d: float,
            time_buffer_ms: int | None, lenv=None, renv=None) -> list:
    """Exact f64 refine of candidate pairs — THE predicate definition
    (shared envelope math with :func:`interlink_referee` via
    :func:`_rect_dist2`, so pruned and referee paths cannot drift).
    ``lenv``/``renv`` reuse the caller's :func:`envelopes` tuples."""
    lx1, ly1, lx2, ly2, _lv = lenv if lenv is not None else envelopes(ltable)
    rx1, ry1, rx2, ry2, rv = renv if renv is not None else envelopes(rtable)
    lt = ltable.dtg_millis() if time_buffer_ms is not None else None
    rt = rtable.dtg_millis() if time_buffer_ms is not None else None
    out = []
    for i, rrows in cands:
        ok = rv[rrows] & (
            _rect_dist2(lx1[i], ly1[i], lx2[i], ly2[i],
                        rx1[rrows], ry1[rrows], rx2[rrows], ry2[rrows])
            <= d * d)
        if time_buffer_ms is not None:
            ok &= np.abs(rt[rrows] - lt[i]) <= int(time_buffer_ms)
        for j in rrows[ok]:
            out.append((str(ltable.fids[i]), str(rtable.fids[j])))
    out.sort()
    return out


def _observe_link(rtype: str, route: str, t0: float, pairs: int) -> None:
    from geomesa_tpu.obs import audit as _audit, devmon

    if _audit.in_shadow():
        return
    devmon.costs().observe_cost(
        rtype, f"traj:link-{route}",
        wall_ms=(_time.perf_counter() - t0) * 1000.0, rows=pairs)


# referee cost is O(L·R): sampled audits only run it under this product
_AUDIT_MAX_CELLS = 512 * 512


def _maybe_audit(ltable, rtable, pred, d, time_buffer_ms, pairs) -> None:
    """Sampled shadow comparison of the pruned pair set against the
    nested-loop referee (audit kind ``interlink``); abstains (counted)
    when the L×R product makes the referee unaffordable."""
    from geomesa_tpu.obs import audit as _audit

    if not _audit.enabled() or _audit.in_shadow() or not _audit.sampled():
        return
    if len(ltable) * len(rtable) > _AUDIT_MAX_CELLS:
        _audit.get().note_check(
            "interlink", True, detail="abstain: referee too large",
            abstain=True)
        return
    with _audit.shadow():
        ref = interlink_referee(ltable, rtable, pred, d, time_buffer_ms)
    ok = pairs == ref
    detail = "" if ok else (
        f"live={len(pairs)} referee={len(ref)} pairs; "
        f"missing={sorted(set(ref) - set(pairs))[:3]} "
        f"extra={sorted(set(pairs) - set(ref))[:3]}")
    _audit.get().note_check("interlink", ok, detail=detail)


def link_members(view, left_member: int, ltype: str, right_member: int,
                 rtype: str | None = None, **kwargs) -> list:
    """Interlink two MEMBERS of a federated/sharded view
    (:class:`geomesa_tpu.store.merged.MergedDataStoreView` — ``stores``
    holds ``(store, scope)`` pairs): the JedAI-spatial cross-source case
    over this tree's federation."""
    stores = getattr(view, "stores", None)
    if stores is None:
        raise ValueError("link_members needs a merged/sharded view")
    if not (0 <= left_member < len(stores)
            and 0 <= right_member < len(stores)):
        raise IndexError("member index out of range")
    lds = stores[left_member][0]
    rds = stores[right_member][0]
    return interlink(lds, ltype, rds, rtype or ltype, **kwargs)
