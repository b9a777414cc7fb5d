"""Index API: the middle seam (``GeoMesaFeatureIndex`` / ``IndexKeySpace`` role).

Reference contracts re-materialized TPU-first (SURVEY.md §1 seam 2,
``geomesa-index-api/.../api/GeoMesaFeatureIndex.scala:49``,
``IndexKeySpace.scala:23``): an index is (a) a permutation that sorts a feature
batch by its key order, and (b) a planner from extracted filter bounds to
**row intervals in that sort order**. Row intervals are this framework's
universal scan IR — the role byte ranges play in the reference
(``index/api/package.scala:276-330``) — because on a TPU the store is a set of
columnar device arrays sorted in index order, and a scan is a gather of
candidate slots, not a BatchScanner RPC.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import ClassVar

import numpy as np

from geomesa_tpu.filter.bounds import Extraction
from geomesa_tpu.schema.columnar import FeatureTable
from geomesa_tpu.schema.sft import FeatureType

DEFAULT_MAX_RANGES = 2000  # reference QueryProperties.ScanRangesTarget default


@dataclass
class IndexPlan:
    """Scan plan for one index over one snapshot: sorted-row intervals.

    ``intervals``: (R, 2) int64 ``[start, end)`` in sorted-row positions.
    ``exact``: True when interval membership alone implies a filter match for
    the *primary* predicate (no z false positives — e.g. full-domain scans);
    the full residual filter is applied downstream regardless.

    ``exec_cache``: backend-owned dispatch-payload memo. Plans live in the
    store's plan cache and repeat verbatim for repeated filters; the TPU
    backend stashes the derived per-shard split and the staged device
    payloads here (keyed by layout shape) so the cached-plan path pays
    ZERO host planning/staging per query. Entries are only valid for a
    layout with the same (rows_per_shard, kind) — the key carries both —
    and the plan cache itself is dropped on every state swap, so a stale
    payload can never pair with fresh residency. Excluded from equality.
    """

    intervals: np.ndarray
    exact: bool = False
    exec_cache: dict = field(default_factory=dict, compare=False, repr=False)

    @property
    def n_candidates(self) -> int:
        if len(self.intervals) == 0:
            return 0
        return int((self.intervals[:, 1] - self.intervals[:, 0]).sum())

    @staticmethod
    def empty() -> "IndexPlan":
        return IndexPlan(np.empty((0, 2), dtype=np.int64))

    @staticmethod
    def full(n: int) -> "IndexPlan":
        return IndexPlan(np.array([[0, n]], dtype=np.int64))


class FeatureIndex:
    """One configured index over a feature type. Subclasses define key order.

    Lifecycle: ``build(table)`` computes the sort permutation and retains the
    (host-side) sorted key arrays needed for planning; ``plan(extraction)``
    maps filter bounds to sorted-row intervals.
    """

    name: ClassVar[str] = "base"

    def __init__(self, sft: FeatureType):
        self.sft = sft
        self.perm: np.ndarray | None = None  # sorted position -> original row
        self.n = 0
        # the rows themselves in key order, for indexes that keep them
        # (z3): ``features`` row ``rank[r]`` is original row ``r``
        self.features: FeatureTable | None = None
        self.rank: np.ndarray | None = None  # original row -> sorted position

    # -- capability tests (StrategyDecider inputs) ---------------------------
    @classmethod
    def supports(cls, sft: FeatureType) -> bool:
        raise NotImplementedError

    def can_serve(self, e: Extraction) -> bool:
        raise NotImplementedError

    # -- build ---------------------------------------------------------------
    def build(self, table: FeatureTable, sorter=None) -> np.ndarray:
        """Compute and retain sort state; returns the permutation.

        ``sorter``: optional device sort — ``sorter(route_key_u64,
        tiebreak_i32_or_None) -> perm`` (the mesh sample-sort from
        :func:`geomesa_tpu.store.device_ingest.device_sort_perm`). Indexes
        whose keys map onto it use it in place of the host sort; others
        ignore it. Implementations must fall back to the host sort when the
        composite key cannot be expressed (e.g. out-of-range time bins).
        """
        raise NotImplementedError

    # -- plan ----------------------------------------------------------------
    def plan(self, e: Extraction, max_ranges: int = DEFAULT_MAX_RANGES) -> IndexPlan:
        raise NotImplementedError


def intervals_from_key_ranges(
    sorted_keys: np.ndarray, ranges: np.ndarray, offset: int = 0
) -> list[tuple[int, int]]:
    """Map inclusive key ranges to [start, end) positions via binary search.

    ``sorted_keys`` must be ascending; ``ranges`` is (R, 2) inclusive in key
    space. This is the host-side analog of the tablet server seeking each
    range: O(R log N), vectorized.
    """
    if len(ranges) == 0 or len(sorted_keys) == 0:
        return []
    starts = np.searchsorted(sorted_keys, ranges[:, 0], side="left") + offset
    ends = np.searchsorted(sorted_keys, ranges[:, 1], side="right") + offset
    keep = ends > starts
    return list(zip(starts[keep].tolist(), ends[keep].tolist()))


def merge_intervals(intervals: list[tuple[int, int]]) -> np.ndarray:
    """Sort + coalesce overlapping/adjacent [start, end) intervals."""
    if not intervals:
        return np.empty((0, 2), dtype=np.int64)
    intervals.sort()
    out = [list(intervals[0])]
    for s, e in intervals[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.int64)


def gather_indices(intervals: np.ndarray, pad_to: int | None = None):
    """Expand [start, end) intervals into a flat array of row positions.

    The host-side prelude to a device gather: candidate slots are contiguous
    spans of the sorted store. Returns (idx int64, count) where idx is padded
    with ``idx[0]`` (a harmless duplicate; padding slots are masked out by the
    kernel via ``count``).
    """
    if len(intervals) == 0:
        idx = np.zeros(pad_to or 0, dtype=np.int64)
        return idx, 0
    lens = intervals[:, 1] - intervals[:, 0]
    total = int(lens.sum())
    # vectorized concatenation of aranges
    idx = np.repeat(intervals[:, 0], lens) + (
        np.arange(total) - np.repeat(np.cumsum(lens) - lens, lens)
    )
    if pad_to is not None:
        if pad_to < total:
            raise ValueError(f"pad_to {pad_to} < candidate count {total}")
        pad = np.full(pad_to - total, idx[0] if total else 0, dtype=np.int64)
        idx = np.concatenate([idx, pad])
    return idx.astype(np.int64), total


def pad_bucket(n: int, minimum: int = 1024) -> int:
    """Round up to a power of two — bounds the jit compile cache."""
    b = minimum
    while b < n:
        b <<= 1
    return b
