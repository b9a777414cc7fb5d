"""Z3 (points + time) and XZ3 (extended geometries + time) indexes.

Reference: ``geomesa-index-api/.../index/z3/Z3Index.scala:19`` with key layout
``[shard][2B time-bin][8B z3][id]`` and ``Z3IndexKeySpace.scala`` (toIndexKey:64,
getIndexValues:98, getRanges:162) / ``XZ3IndexKeySpace.scala``. TPU re-design:
no byte rows — the sort order is ``(time-bin, z3)`` over the columnar snapshot,
bins are tracked as contiguous sorted-row spans (they double as the coarse
partition axis), and planning splits the range budget across bins exactly like
``Z3IndexKeySpace.scala:165-177``. Hash shards (``ShardStrategy.scala``) are
unnecessary on a device mesh — sharding happens by slicing the sorted store
(SURVEY.md §2.20 P1/P2).
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.curve.binned_time import BinnedTime
from geomesa_tpu.curve.sfc import z3_sfc
from geomesa_tpu.curve.xz import xz3_sfc
from geomesa_tpu.filter.bounds import Extraction
from geomesa_tpu.index.api import (
    DEFAULT_MAX_RANGES,
    FeatureIndex,
    IndexPlan,
    intervals_from_key_ranges,
    merge_intervals,
)
from geomesa_tpu.schema.columnar import FeatureTable
from geomesa_tpu.schema.sft import FeatureType

WORLD = (-180.0, -90.0, 180.0, 90.0)


def _lexsort_bin_key(bins: np.ndarray, key: np.ndarray, sorter) -> np.ndarray:
    """Sort rows by (time-bin, curve key), on device when a sorter is given.

    The 79-bit composite (16-bit bin, 63-bit key) rides the 64-bit device
    sample sort as ``route = bin<<48 | key>>15`` with the dropped low 15
    bits as the tiebreak column — exact because ``route`` is a monotone
    prefix of the wide key. Bins outside u16 (or a sorter failure the
    caller didn't catch) fall back to the host lexsort.
    """
    if (
        sorter is not None
        and len(bins)
        and 0 <= int(bins.min())
        and int(bins.max()) < (1 << 16)
        # the route packs key>>15 into 48 bits: keys >= 2^63 (e.g. XZ3 at
        # extreme precision) would overflow into the bin field — host sort
        and int(key.max()) < (1 << 63)
    ):
        route = (bins.astype(np.uint64) << np.uint64(48)) | (
            key.astype(np.uint64) >> np.uint64(15)
        )
        # all-ones route == the reshard padding sentinel; that row would be
        # silently dropped from the permutation — host sort handles it
        if int(route.max()) != 2**64 - 1:
            tie = (key.astype(np.uint64) & np.uint64(0x7FFF)).astype(np.int32)
            return sorter(route, tie)
    from geomesa_tpu import native

    return native.lexsort_bin_z(bins, key)


def time_windows(
    binned: BinnedTime, bin_values: np.ndarray, intervals
) -> list[tuple[int, int, int]]:
    """Expand temporal bounds into per-bin (bin, off_lo, off_hi) windows,
    clipped to bins actually present in the data (shared by Z3 and XZ3 —
    the per-bin budget split of ``Z3IndexKeySpace.scala:165-177``)."""
    if len(bin_values) == 0:
        return []
    max_off = int(binned.max_offset)
    if intervals is None:
        return [(int(b), 0, max_off) for b in bin_values]
    out = []
    for lo_ms, hi_ms in intervals:
        lo_ms = max(int(lo_ms), 0)
        # clamp to the last millisecond of the last bin present in the data
        hi_ms = min(
            int(hi_ms),
            int(binned.bin_start_millis(np.array([int(bin_values[-1]) + 1]))[0]) - 1,
        )
        if hi_ms < lo_ms:
            continue
        (blo,), (olo,) = binned.to_bin_and_offset(np.array([lo_ms]))
        (bhi,), (ohi,) = binned.to_bin_and_offset(np.array([hi_ms]))
        sel = (bin_values >= blo) & (bin_values <= bhi)
        for b in bin_values[sel]:
            w_lo = int(olo) if b == blo else 0
            w_hi = int(ohi) if b == bhi else max_off
            out.append((int(b), w_lo, w_hi))
    return out


class Z3Index(FeatureIndex):
    name = "z3"

    def __init__(self, sft: FeatureType):
        super().__init__(sft)
        self.period = sft.z3_interval
        self.binned = BinnedTime(self.period)
        if sft.index_layout == "legacy":
            from geomesa_tpu.curve.legacy import legacy_z3_sfc

            self.sfc = legacy_z3_sfc(self.period)
        else:
            self.sfc = z3_sfc(self.period)
        # build products
        self.bins: np.ndarray | None = None  # sorted (n,) int32
        self.zs: np.ndarray | None = None  # sorted (n,) uint64
        self.offsets: np.ndarray | None = None  # sorted (n,) int64 offsets
        self.bin_values: np.ndarray | None = None  # unique bins present
        self.bin_starts: np.ndarray | None = None  # row span starts per bin

    @classmethod
    def supports(cls, sft: FeatureType) -> bool:
        return sft.geom_is_points and sft.dtg_field is not None

    def can_serve(self, e: Extraction) -> bool:
        return True  # full-domain scan degrades gracefully

    def build(self, table: FeatureTable, sorter=None) -> np.ndarray:
        col = table.geom_column()
        t_ms = table.dtg_millis()
        bins, offs = self.binned.to_bin_and_offset(t_ms)
        z = self.sfc.index(col.x, col.y, offs)
        perm = _lexsort_bin_key(bins, z, sorter)
        self.perm = perm
        self.bins = bins[perm]
        self.offsets = offs[perm]
        self.zs = z[perm]
        self.n = len(table)
        self.bin_values, self.bin_starts = np.unique(self.bins, return_index=True)
        self._keep_rows(table, perm)
        return perm

    def _keep_rows(self, table: FeatureTable, perm: np.ndarray) -> None:
        """Keep the rows in key order, as GeoMesa stores each feature under
        its index key (SURVEY.md §2.3): a select's matches then lie in
        contiguous runs of ``features``, where they are scattered across
        the arrival-ordered table. String objects are shared, not copied."""
        self.features = table.take(perm)
        rank = np.empty(len(perm), dtype=np.int64)
        rank[perm] = np.arange(len(perm), dtype=np.int64)
        self.rank = rank

    def merge_build(self, table: FeatureTable, prev: "Z3Index", n_prev: int) -> np.ndarray:
        """LSM-style incremental build: ``table`` = [prev's rows | delta].

        The main tier is already (bin, z)-sorted in ``prev``; only the delta
        is sorted (small), then linearly merged (``native.merge_bin_z``) —
        O(n) instead of a full re-sort, the compaction pattern of SURVEY.md
        §2.11. Result is bit-identical to :meth:`build` on the whole table
        (stable ties: main rows precede delta rows, as in the full sort).
        """
        from geomesa_tpu import native

        n = len(table)
        if prev.n != n_prev or n_prev == 0 or prev.bins is None:
            return self.build(table)
        col = table.geom_column()
        sl = slice(n_prev, n)
        d_bins, d_offs = self.binned.to_bin_and_offset(table.dtg_millis()[sl])
        d_z = self.sfc.index(col.x[sl], col.y[sl], d_offs)
        d_perm = native.lexsort_bin_z(d_bins, d_z)
        d_bins_s = d_bins[d_perm]
        d_z_s = d_z[d_perm]
        merged = native.merge_bin_z(prev.bins, prev.zs, d_bins_s, d_z_s)
        in_main = merged < n_prev
        perm = np.where(
            in_main,
            prev.perm[np.minimum(merged, n_prev - 1)],
            n_prev + d_perm[np.maximum(merged - n_prev, 0)],
        )
        self.perm = perm
        self.bins = np.where(in_main, prev.bins[np.minimum(merged, n_prev - 1)],
                             d_bins_s[np.maximum(merged - n_prev, 0)])
        self.zs = np.where(in_main, prev.zs[np.minimum(merged, n_prev - 1)],
                           d_z_s[np.maximum(merged - n_prev, 0)])
        self.offsets = np.where(
            in_main, prev.offsets[np.minimum(merged, n_prev - 1)],
            d_offs[d_perm][np.maximum(merged - n_prev, 0)],
        )
        self.n = n
        self.bin_values, self.bin_starts = np.unique(self.bins, return_index=True)
        self._keep_rows(table, perm)
        return perm

    # -- planning ------------------------------------------------------------
    def _bin_span(self, b: int) -> tuple[int, int]:
        i = np.searchsorted(self.bin_values, b)
        if i == len(self.bin_values) or self.bin_values[i] != b:
            return (0, 0)
        start = int(self.bin_starts[i])
        end = int(self.bin_starts[i + 1]) if i + 1 < len(self.bin_starts) else self.n
        return (start, end)

    def plan(self, e: Extraction, max_ranges: int = DEFAULT_MAX_RANGES) -> IndexPlan:
        if e.disjoint or self.n == 0:
            return IndexPlan.empty()
        boxes = e.boxes if e.boxes is not None else [WORLD]
        windows = time_windows(self.binned, self.bin_values, e.intervals)
        if not windows:
            return IndexPlan.empty()
        budget = max(1, max_ranges // max(1, len(windows)))
        out: list[tuple[int, int]] = []
        for b, w_lo, w_hi in windows:
            start, end = self._bin_span(b)
            if end <= start:
                continue
            zranges = self.sfc.ranges(boxes, (float(w_lo), float(w_hi)), budget)
            out.extend(
                intervals_from_key_ranges(self.zs[start:end], zranges, offset=start)
            )
        return IndexPlan(merge_intervals(out))


class XZ3Index(FeatureIndex):
    """XZ3: bbox-of-geometry + time instant, for non-point default geometries."""

    name = "xz3"

    def __init__(self, sft: FeatureType):
        super().__init__(sft)
        self.period = sft.z3_interval
        self.binned = BinnedTime(self.period)
        self.sfc = xz3_sfc(self.period, sft.xz_precision)
        self.bins: np.ndarray | None = None
        self.codes: np.ndarray | None = None
        self.bin_values: np.ndarray | None = None
        self.bin_starts: np.ndarray | None = None

    @classmethod
    def supports(cls, sft: FeatureType) -> bool:
        return (
            sft.geom_field is not None
            and not sft.geom_is_points
            and sft.dtg_field is not None
        )

    def can_serve(self, e: Extraction) -> bool:
        return True

    def build(self, table: FeatureTable, sorter=None) -> np.ndarray:
        col = table.geom_column()
        b = col.bounds  # (n, 4)
        t_ms = table.dtg_millis()
        bins, offs = self.binned.to_bin_and_offset(t_ms)
        o = offs.astype(np.float64)
        codes = self.sfc.index(
            (b[:, 0], b[:, 1], o), (b[:, 2], b[:, 3], o)
        )
        perm = _lexsort_bin_key(bins, codes, sorter)
        self.perm = perm
        self.bins = bins[perm]
        self.codes = codes[perm]
        self.n = len(table)
        self.bin_values, self.bin_starts = np.unique(self.bins, return_index=True)
        return perm

    def merge_build(self, table: FeatureTable, prev: "XZ3Index", n_prev: int) -> np.ndarray:
        """Linear LSM merge of a sorted delta into the sorted main tier
        (same contract as :meth:`Z3Index.merge_build`)."""
        from geomesa_tpu import native

        n = len(table)
        if prev.n != n_prev or n_prev == 0 or prev.bins is None:
            return self.build(table)
        col = table.geom_column()
        b = col.bounds[n_prev:n]
        d_bins, d_offs = self.binned.to_bin_and_offset(table.dtg_millis()[n_prev:n])
        o = d_offs.astype(np.float64)
        d_codes = self.sfc.index((b[:, 0], b[:, 1], o), (b[:, 2], b[:, 3], o))
        d_perm = native.lexsort_bin_z(d_bins, d_codes)
        d_bins_s = d_bins[d_perm]
        d_codes_s = d_codes[d_perm]
        merged = native.merge_bin_z(prev.bins, prev.codes, d_bins_s, d_codes_s)
        in_main = merged < n_prev
        main_i = np.minimum(merged, n_prev - 1)
        delta_i = np.maximum(merged - n_prev, 0)
        self.perm = np.where(in_main, prev.perm[main_i], n_prev + d_perm[delta_i])
        self.bins = np.where(in_main, prev.bins[main_i], d_bins_s[delta_i])
        self.codes = np.where(in_main, prev.codes[main_i], d_codes_s[delta_i])
        self.n = n
        self.bin_values, self.bin_starts = np.unique(self.bins, return_index=True)
        return self.perm

    def _bin_span(self, b: int) -> tuple[int, int]:
        i = np.searchsorted(self.bin_values, b)
        if i == len(self.bin_values) or self.bin_values[i] != b:
            return (0, 0)
        start = int(self.bin_starts[i])
        end = int(self.bin_starts[i + 1]) if i + 1 < len(self.bin_starts) else self.n
        return (start, end)

    def plan(self, e: Extraction, max_ranges: int = DEFAULT_MAX_RANGES) -> IndexPlan:
        if e.disjoint or self.n == 0:
            return IndexPlan.empty()
        boxes = e.boxes if e.boxes is not None else [WORLD]
        windows = time_windows(self.binned, self.bin_values, e.intervals)
        if not windows:
            return IndexPlan.empty()
        budget = max(1, max_ranges // max(1, len(windows)))
        out: list[tuple[int, int]] = []
        for b, w_lo, w_hi in windows:
            start, end = self._bin_span(b)
            if end <= start:
                continue
            wins = [
                ((x1, y1, float(w_lo)), (x2, y2, float(w_hi)))
                for x1, y1, x2, y2 in boxes
            ]
            cranges = self.sfc.ranges(wins, budget)
            out.extend(
                intervals_from_key_ranges(self.codes[start:end], cranges, offset=start)
            )
        return IndexPlan(merge_intervals(out))
