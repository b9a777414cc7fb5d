"""Attribute index: per-attribute value order with a Z3 secondary tier.

Reference: ``geomesa-index-api/.../index/attribute/AttributeIndex.scala:61`` —
rows keyed ``[shard][attr idx][lexicoded value][tiered z3/date][id]`` with
values lexicoded so byte order = natural order (``AttributeIndexKey.scala``).
TPU re-design: **no lexicoding needed** — the index sorts the columnar
snapshot by (value, time-bin, z3) directly (numpy handles natural ordering),
value predicates map to row intervals via binary search over the sorted value
array, and the Z3 tier is realized by planning z-ranges *within* each
equal-value run (the ``GeoMesaFeatureIndex.getQueryStrategy`` tiering of
``GeoMesaFeatureIndex.scala:249-339``). Null values sort to the end and are
excluded from every planned range.
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.curve.binned_time import BinnedTime
from geomesa_tpu.curve.sfc import z3_sfc
from geomesa_tpu.filter.bounds import Extraction
from geomesa_tpu.index.api import (
    DEFAULT_MAX_RANGES,
    FeatureIndex,
    IndexPlan,
    intervals_from_key_ranges,
    merge_intervals,
)
from geomesa_tpu.index.z3 import time_windows
from geomesa_tpu.schema.columnar import FeatureTable
from geomesa_tpu.schema.sft import AttributeType, FeatureType


class AttributeIndex(FeatureIndex):
    """One instance per indexed attribute; named ``attr:<name>``."""

    name = "attr"

    def __init__(self, sft: FeatureType, attribute: str):
        super().__init__(sft)
        self.attribute = attribute
        self.name = f"attr:{attribute}"
        self.tiered = sft.geom_is_points and sft.dtg_field is not None
        if self.tiered:
            self.period = sft.z3_interval
            self.binned = BinnedTime(self.period)
            self.sfc = z3_sfc(self.period)
        self.values: np.ndarray | None = None  # sorted values (valid rows first)
        self.n_valid = 0
        self.bins: np.ndarray | None = None
        self.zs: np.ndarray | None = None

    @classmethod
    def supports(cls, sft: FeatureType) -> bool:  # pragma: no cover - factory
        return True

    def can_serve(self, e: Extraction) -> bool:
        return e.attr_bounded(self.attribute)

    @staticmethod
    def indexed_attributes(sft: FeatureType) -> list[str]:
        return [
            a.name
            for a in sft.attributes
            if a.indexed and not a.type.is_geometry
        ]

    def build(self, table: FeatureTable, sorter=None) -> np.ndarray:
        # attribute keys (strings etc.) don't map onto the u64 device sort
        col = table.columns[self.attribute]
        valid = col.is_valid()
        vals = col.values
        # a string column sorts by its dictionary codes, which rank the
        # set values exactly: the same stable order, with no string compare
        ranked = col.ranked()
        key = vals if ranked is None else ranked[1]
        # sortable surrogate: None -> pushed to end via the valid flag
        if self.tiered:
            tcol = table.geom_column()
            bins, offs = self.binned.to_bin_and_offset(table.dtg_millis())
            z = self.sfc.index(tcol.x, tcol.y, offs)
            order = stable_lexsort([z, bins, _sort_surrogate(key, valid), ~valid])
            self.bins = bins[order]
            self.zs = z[order]
        else:
            order = stable_lexsort([_sort_surrogate(key, valid), ~valid])
        self.perm = order
        self.values = vals[order]
        self.n = len(table)
        self.n_valid = int(valid.sum())
        return order

    # -- planning ------------------------------------------------------------
    def _value_span(self, lo, hi, lo_inc, hi_inc) -> tuple[int, int]:
        """Row span [start, end) of values within the interval (valid rows)."""
        vals = self.values[: self.n_valid]
        if lo is None:
            start = 0
        else:
            start = int(np.searchsorted(vals, lo, side="left" if lo_inc else "right"))
        if hi is None:
            end = self.n_valid
        else:
            end = int(np.searchsorted(vals, hi, side="right" if hi_inc else "left"))
        return start, max(end, start)

    def plan(self, e: Extraction, max_ranges: int = DEFAULT_MAX_RANGES) -> IndexPlan:
        bounds = e.attributes.get(self.attribute)
        if e.disjoint or self.n == 0:
            return IndexPlan.empty()
        if bounds is None:
            # full scan INCLUDING null-attribute rows (they sort to the end
            # and the residual filter decides their fate)
            return IndexPlan.full(self.n)
        out: list[tuple[int, int]] = []
        for lo, hi, li, ri in bounds:
            start, end = self._value_span(lo, hi, li, ri)
            if end <= start:
                continue
            # Z3 tier: for equality runs with temporal bounds, narrow by
            # (bin, z) within the run — the tiered-key-space trick.
            if (
                self.tiered
                and lo is not None
                and lo == hi
                and (e.intervals is not None or e.boxes is not None)
                and end - start > 64
            ):
                out.extend(self._tiered(start, end, e, max_ranges))
            else:
                out.append((start, end))
        return IndexPlan(merge_intervals(out))

    def _tiered(self, start: int, end: int, e: Extraction, max_ranges: int):
        from geomesa_tpu.index.z3 import WORLD

        boxes = e.boxes if e.boxes is not None else [WORLD]
        run_bins = self.bins[start:end]
        bin_values = np.unique(run_bins)
        windows = time_windows(self.binned, bin_values, e.intervals)
        if not windows:
            return []
        budget = max(1, max_ranges // max(1, len(windows)))
        out = []
        for b, w_lo, w_hi in windows:
            blo = start + int(np.searchsorted(run_bins, b, side="left"))
            bhi = start + int(np.searchsorted(run_bins, b, side="right"))
            if bhi <= blo:
                continue
            zr = self.sfc.ranges(boxes, (float(w_lo), float(w_hi)), budget)
            out.extend(
                intervals_from_key_ranges(self.zs[blo:bhi], zr, offset=blo)
            )
        return out


def stable_lexsort(keys: list[np.ndarray]) -> np.ndarray:
    """np.lexsort replacement that supports object (string) key arrays:
    chained stable argsorts, least-significant key first."""
    n = len(keys[0])
    order = np.arange(n)
    for k in keys:
        order = order[np.argsort(k[order], kind="stable")]
    return order


def _sort_surrogate(vals: np.ndarray, valid: np.ndarray) -> np.ndarray:
    """A sortable key array: invalid slots get the first valid value (their
    position is controlled by the ``~valid`` primary key in lexsort)."""
    if valid.all():
        return vals
    out = vals.copy()
    if valid.any():
        fill = vals[valid][0]
    else:
        fill = 0
    out[~valid] = fill
    return out
