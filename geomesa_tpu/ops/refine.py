"""Device-side candidate refinement: the ``Z3Iterator``/``Z2Iterator`` role.

Reference: the server-side push-down filters that decode z-cells and compare in
*normalized int space* (``geomesa-index-api/.../index/filters/Z3Filter.scala:24-55``,
``Z2Filter``; deployed in Accumulo iterators / HBase filters — SURVEY.md §2.9).
TPU re-design: one fused, fixed-shape jitted kernel over gathered candidate
slots — int32 compares on the VPU, no byte decoding, no per-range dispatch.

Int-domain compares are a *superset* test (normalization is monotone, so query
bounds normalized outward can only admit extra boundary-cell rows, never drop a
match); the exact f64 residual filter runs downstream on the survivors.

All inputs are explicitly int32 — this kernel must never silently widen under
x64 mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MAX_BOXES = 8  # padded box-slot count (static shape)
MAX_TIMES = 16  # padded time-window slot count

# sentinel rows make padded slots always-false (lo > hi)
_BOX_PAD = np.array([1, 0, 1, 0], dtype=np.int32)
# Padding for interval-overlap (XZ) queries: [1,0,1,0] is empty under point
# containment but a feature bbox spanning the origin corner would overlap it;
# [qxlo=MAX, qxhi=-1, ...] is unsatisfiable under `x1 <= qxhi & x2 >= qxlo`
# for non-negative normalized coords.
_BOX_PAD_OVERLAP = np.array([2**31 - 1, -1, 2**31 - 1, -1], dtype=np.int32)
_TIME_PAD = np.array([1, 0, 0, -1], dtype=np.int32)
# no spatial constraint: the whole normalized domain
_FULL_BOX = np.array([0, 2**31 - 1, 0, 2**31 - 1], dtype=np.int32)
# no temporal constraint: off_lo = -1 matches every row (offsets are >= 0)
# while its endpoints are unhittable, so the exact-mode edge test never
# flags rows of a time-unconstrained query (a real (0, 0) lo endpoint
# would mark EVERY row of a no-dtg store as a candidate)
_NO_TIME = np.array([0, -1, 2**31 - 1, 2**31 - 1], dtype=np.int32)


def _pack(rows, counts, slots, pad, widen) -> np.ndarray:
    """Each query's rows (consecutive in ``rows`` (N, 4), ``counts[q]`` of
    them) in the first slots of its (``slots``, 4) int32 payload, ``pad``
    after them; a query with more rows than slots gets ``widen(its rows,
    where each such query's rows start)`` in slot 0 instead."""
    out = np.empty((len(counts), slots, 4), dtype=np.int32)
    out[:] = pad
    if len(rows) == 0:
        return out
    q = np.repeat(np.arange(len(counts)), counts)
    slot = np.arange(len(rows)) - (np.cumsum(counts) - counts)[q]
    fits = counts <= slots
    if fits.all():
        out[q, slot] = rows
        return out
    put = fits[q]
    out[q[put], slot[put]] = rows[put]
    n = counts[~fits]
    out[~fits, 0] = widen(rows[~put], np.cumsum(n) - n)
    return out


def _box_envelope(rows: np.ndarray, at: np.ndarray) -> np.ndarray:
    return np.stack(
        [
            np.minimum.reduceat(rows[:, 0], at),
            np.maximum.reduceat(rows[:, 1], at),
            np.minimum.reduceat(rows[:, 2], at),
            np.maximum.reduceat(rows[:, 3], at),
        ],
        axis=1,
    )


def _time_envelope(rows: np.ndarray, at: np.ndarray) -> np.ndarray:
    # widened payloads are flagged non-exactable by the callers, so the
    # unhittable -1 lo-offset is safe here too
    lo = np.minimum.reduceat(rows[:, 0], at)
    hi = np.maximum.reduceat(rows[:, 2], at)
    return np.stack(
        [lo, np.full_like(lo, -1), hi, np.full_like(hi, 2**31 - 1)], axis=1
    )


def pack_box_rows(
    rows: np.ndarray, counts, slots: int = MAX_BOXES, overlap: bool = False
) -> np.ndarray:
    """The boxes of Q queries → their padded payloads (Q, ``slots``, 4)
    int32. ``rows`` (N, 4) [xlo, xhi, ylo, yhi] ints holds every query's
    boxes in query order, ``counts[q]`` of them for query ``q``.

    A query with no boxes → the full box. More boxes than slots →
    collapse to the bounding envelope (still a superset; residual
    recovers exactness). ``slots`` is a compile-time shape: single-box
    workloads pass ``slots=1`` so the device kernels skip the
    padded-slot evaluations entirely. ``overlap=True`` pads with the
    interval-overlap-unsatisfiable sentinel (for XZ bbox-overlap scans,
    where the containment pad is not empty).
    """
    counts = np.asarray(counts, dtype=np.int64)
    pad = _BOX_PAD_OVERLAP if overlap else _BOX_PAD
    out = _pack(rows, counts, slots, pad, _box_envelope)
    out[counts == 0, 0] = _FULL_BOX
    return out


def pack_time_rows(
    rows: np.ndarray, counts, slots: int = MAX_TIMES, unconstrained=None
) -> np.ndarray:
    """The time windows of Q queries → their padded payloads (Q,
    ``slots``, 4) int32. ``rows`` (N, 4) [bin_lo, off_lo, bin_hi, off_hi]
    ints holds every query's windows in query order, ``counts[q]`` of
    them for query ``q``.

    ``unconstrained`` (Q,) bool, by default the queries with no windows:
    no temporal constraint → the no-time sentinel. A query with no
    windows that is not unconstrained matches nothing: pad in every slot.
    More windows than slots → ``[min bin_lo, -1, max bin_hi, 2**31-1]``.
    """
    counts = np.asarray(counts, dtype=np.int64)
    out = _pack(rows, counts, slots, _TIME_PAD, _time_envelope)
    out[counts == 0 if unconstrained is None else unconstrained, 0] = _NO_TIME
    return out


def _one_query(payload_i32: np.ndarray | None) -> np.ndarray:
    if payload_i32 is None:
        return np.empty((0, 4), dtype=np.int32)
    return np.asarray(payload_i32, dtype=np.int32).reshape(-1, 4)


def pack_boxes(
    boxes_i32: np.ndarray | None, slots: int = MAX_BOXES, overlap: bool = False
) -> np.ndarray:
    """(B, 4) [xlo, xhi, ylo, yhi] int32 → padded (``slots``, 4):
    :func:`pack_box_rows` on one query."""
    b = _one_query(boxes_i32)
    return pack_box_rows(b, [len(b)], slots=slots, overlap=overlap)[0]


def unsat_rows(box_slots: int, time_slots: int) -> tuple[np.ndarray, np.ndarray]:
    """The fully-unsatisfiable payload pair: every box slot empty (lo > hi),
    every time window ending before it starts — a query that matches
    NOTHING while keeping the packed shapes. The one definition of the
    sentinel, shared by the planner's provably-disjoint branch and the
    subscription matrix's masked slots (if the encoding ever changes, both
    must move together or masked slots start matching rows)."""
    return (
        pack_boxes(_BOX_PAD[None], slots=box_slots),
        pack_times(_TIME_PAD[None], slots=time_slots),
    )


def pack_times(times_i32: np.ndarray | None, slots: int = MAX_TIMES) -> np.ndarray:
    """(T, 4) [bin_lo, off_lo, bin_hi, off_hi] int32 → padded (``slots``,
    4): :func:`pack_time_rows` on one query (none → unconstrained)."""
    t = _one_query(times_i32)
    return pack_time_rows(t, [len(t)], slots=slots)[0]


def _live_slots(payload: np.ndarray, pad: np.ndarray) -> np.ndarray:
    """(Q,) per query: slots up to and including its last non-pad slot."""
    real = (payload != pad).any(axis=2)  # (Q, S)
    last = payload.shape[1] - np.argmax(real[:, ::-1], axis=1)
    return np.where(real.any(axis=1), last, 0)


def trim_slots(
    boxes: np.ndarray, times: np.ndarray, overlap: bool = False
) -> tuple[np.ndarray, np.ndarray, int]:
    """Stacked batch payloads (Q, B, 4) / (Q, T, 4) → the same queries
    over only the slot columns some query uses: the trailing columns that
    are padding in every query are dropped, each axis kept at its live
    maximum rounded up to a power of two (at least 1, at most its width),
    so the kernels' static slot loops run the batch's real slots and the
    set of compiled shapes stays small.

    Exact: ``pack_*`` put padding after the real slots, a pad slot matches
    nothing, and a real slot equal to the pad matches nothing either.
    ``overlap`` selects the box pad as in :func:`pack_boxes`; the time pad
    is unsatisfiable in both modes, while the no-constraint time sentinel
    is not a pad and stays. Returns (boxes (Q, B', 4), times (Q, T', 4),
    the batch's live box+time slots)."""
    live_b = _live_slots(boxes, _BOX_PAD_OVERLAP if overlap else _BOX_PAD)
    live_t = _live_slots(times, _TIME_PAD)

    def width(live: np.ndarray, slots: int) -> int:
        n = int(live.max(initial=0))
        return min(slots, 1 << max(n - 1, 0).bit_length())

    return (
        boxes[:, : width(live_b, boxes.shape[1])],
        times[:, : width(live_t, times.shape[1])],
        int(live_b.sum() + live_t.sum()),
    )


@jax.jit
def refine_points(x, y, bins, offs, idx, count, boxes, times):
    """Fused gather + int-domain bbox/time refine over candidate slots.

    Args:
      x, y: (N,) int32 normalized coords, sorted in index order (device-resident).
      bins, offs: (N,) int32 time bin / offset-in-bin, same order.
      idx: (C,) int32 candidate slot → sorted-row position (host-planned).
      count: () int32 — number of real (non-padding) slots.
      boxes: (MAX_BOXES, 4) int32 [xlo, xhi, ylo, yhi] inclusive.
      times: (MAX_TIMES, 4) int32 [bin_lo, off_lo, bin_hi, off_hi] inclusive.

    Returns:
      (C,) bool mask of candidates passing the int-domain superset test.
    """
    xi = x[idx][:, None]  # (C, 1)
    yi = y[idx][:, None]
    bi = bins[idx][:, None]
    oi = offs[idx][:, None]

    in_box = (
        (xi >= boxes[None, :, 0])
        & (xi <= boxes[None, :, 1])
        & (yi >= boxes[None, :, 2])
        & (yi <= boxes[None, :, 3])
    ).any(axis=1)

    return in_box & _time_and_valid(bi, oi, times, idx, count)


def _time_and_valid(bi, oi, times, idx, count):
    """Shared (bin, offset) interval test + real-slot mask for both the
    point-containment and bbox-overlap refines (same time semantics)."""
    after_lo = (bi > times[None, :, 0]) | (
        (bi == times[None, :, 0]) & (oi >= times[None, :, 1])
    )
    before_hi = (bi < times[None, :, 2]) | (
        (bi == times[None, :, 2]) & (oi <= times[None, :, 3])
    )
    in_time = (after_lo & before_hi).any(axis=1)
    valid = jnp.arange(idx.shape[0], dtype=jnp.int32) < count
    return in_time & valid


def refine_bboxes(bxmin, bxmax, bymin, bymax, bins, offs, idx, count, boxes, times):
    """Fused gather + int-domain bbox-OVERLAP/time refine for extended
    geometries (linestrings/polygons): a candidate matches when its feature
    bbox intervals overlap any query box. ``boxes`` must be packed with
    ``pack_boxes(..., overlap=True)`` (the containment pad sentinel is not
    empty under interval overlap). The residual exact predicate recovers
    strictness on the host — this is the XZ scan's loose superset test
    (``XZ2IndexKeySpace`` role)."""
    lo_x = bxmin[idx][:, None]
    hi_x = bxmax[idx][:, None]
    lo_y = bymin[idx][:, None]
    hi_y = bymax[idx][:, None]
    bi = bins[idx][:, None]
    oi = offs[idx][:, None]

    overlaps = (
        (hi_x >= boxes[None, :, 0])
        & (lo_x <= boxes[None, :, 1])
        & (hi_y >= boxes[None, :, 2])
        & (lo_y <= boxes[None, :, 3])
    ).any(axis=1)

    return overlaps & _time_and_valid(bi, oi, times, idx, count)


@jax.jit
def count_points(x, y, bins, offs, idx, count, boxes, times):
    """Candidate count after refine — the aggregation fast path (no gather-out)."""
    return refine_points(x, y, bins, offs, idx, count, boxes, times).sum(
        dtype=jnp.int32
    )
