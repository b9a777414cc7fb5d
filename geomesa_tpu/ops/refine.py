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


def pack_boxes(
    boxes_i32: np.ndarray | None, slots: int = MAX_BOXES, overlap: bool = False
) -> np.ndarray:
    """(B, 4) [xlo, xhi, ylo, yhi] int32 → padded (``slots``, 4).

    More boxes than slots → collapse to the bounding envelope (still a
    superset; residual recovers exactness). ``slots`` is a compile-time
    shape: single-box workloads pass ``slots=1`` so the device kernels skip
    the padded-slot evaluations entirely. ``overlap=True`` pads with the
    interval-overlap-unsatisfiable sentinel (for XZ bbox-overlap scans,
    where the containment pad is not empty).
    """
    if boxes_i32 is None or len(boxes_i32) == 0:
        full = np.array([[0, 2**31 - 1, 0, 2**31 - 1]], dtype=np.int32)
        boxes_i32 = full
    b = np.asarray(boxes_i32, dtype=np.int32)
    if len(b) > slots:
        b = np.array(
            [[b[:, 0].min(), b[:, 1].max(), b[:, 2].min(), b[:, 3].max()]],
            dtype=np.int32,
        )
    pad = np.broadcast_to(_BOX_PAD_OVERLAP if overlap else _BOX_PAD, (slots - len(b), 4))
    return np.vstack([b, pad])


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
    """(T, 4) [bin_lo, off_lo, bin_hi, off_hi] int32 → padded (``slots``, 4)."""
    if times_i32 is None or len(times_i32) == 0:
        # unconstrained sentinel: off_lo = -1 matches every row (offsets are
        # >= 0) while its endpoints are unhittable, so the exact-mode edge
        # test never flags rows of a time-unconstrained query (a real (0, 0)
        # lo endpoint would mark EVERY row of a no-dtg store as a candidate)
        full = np.array([[0, -1, 2**31 - 1, 2**31 - 1]], dtype=np.int32)
        times_i32 = full
    t = np.asarray(times_i32, dtype=np.int32)
    if len(t) > slots:
        # widened payloads are flagged non-exactable by the callers, so the
        # unhittable -1 lo-offset is safe here too
        t = np.array(
            [[t[:, 0].min(), -1, t[:, 2].max(), 2**31 - 1]], dtype=np.int32
        )
    pad = np.broadcast_to(_TIME_PAD, (slots - len(t), 4))
    return np.vstack([t, pad])


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
