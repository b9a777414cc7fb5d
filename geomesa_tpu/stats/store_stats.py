"""Per-feature-type statistics: maintenance + selectivity estimation.

The ``GeoMesaStats`` / ``StatsBasedEstimator`` / ``MetadataBackedStats`` roles
(``geomesa-index-api/.../stats/GeoMesaStats.scala:33``,
``StatsBasedEstimator.scala`` — SURVEY.md §2.3): sketches maintained at write
time feed cost-based index selection; the same sketches answer stats queries
(count/bounds/min-max/histogram) without scanning.

Recomputed per snapshot on write (our writes are bulk rebuilds), reusing the
Z3 index's build products for the spatio-temporal histogram.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from geomesa_tpu.filter.bounds import Extraction
from geomesa_tpu.schema.sft import AttributeType, FeatureType
from geomesa_tpu.stats.sketches import (
    Cardinality,
    CountStat,
    DescriptiveStats,
    Frequency,
    Histogram,
    MinMax,
    TopK,
    Z3Histogram,
    _hash_basis,
)

HIST_BINS = 1000


@dataclass
class AttributeStats:
    minmax: MinMax = field(default_factory=MinMax)
    histogram: Histogram | None = None  # numeric/date only
    frequency: Frequency = field(default_factory=Frequency)
    topk: TopK = field(default_factory=lambda: TopK(10))
    cardinality: Cardinality = field(default_factory=Cardinality)
    descriptive: DescriptiveStats | None = None


class StoreStats:
    """Sketch set for one feature type snapshot."""

    def __init__(self, sft: FeatureType):
        self.sft = sft
        self.count = 0
        self.attrs: dict[str, AttributeStats] = {}
        self.z3hist: Z3Histogram | None = None

    # -- maintenance ---------------------------------------------------------
    def rebuild(self, table, z3_index=None) -> None:
        self.count = len(table)
        self.attrs = {}
        for a in self.sft.attributes:
            if a.type.is_geometry:
                continue
            col = table.columns[a.name]
            valid = col.is_valid()
            st = AttributeStats()
            # a string column folds through its dictionary codes: a count
            # of each code at the set rows, and the distinct strings come
            # out sorted, as np.unique over the objects gives them
            ranked = col.ranked()
            vals = col.values[valid] if ranked is None else None
            if ranked is not None:
                distinct, codes = ranked
                counts = np.bincount(codes[valid], minlength=len(distinct))
                u, c = distinct[counts > 0], counts[counts > 0]
                if len(u):
                    st.minmax.min, st.minmax.max = u[0], u[-1]
            elif a.type.is_numeric or a.type == AttributeType.DATE:
                v = vals.astype(np.float64)
                st.minmax.observe(vals)
                if len(v) and st.minmax.min is not None:
                    lo = float(st.minmax.min)
                    hi = float(st.minmax.max)
                    st.histogram = Histogram(lo, max(hi, lo + 1e-9), HIST_BINS)
                    st.histogram.observe(v)
                st.descriptive = DescriptiveStats()
                st.descriptive.observe(v)
            else:
                st.minmax.observe(vals) if len(vals) else None
            # fold the column through np.unique ONCE: the CMS takes the
            # (value, count) pairs weighted, TopK only needs the heavy
            # slice, and the HLL only distinct values — per-value update
            # loops collapse to the unique count (bulk rebuilds were
            # spending ~20 s at 5M rows in exactly these three observes)
            if ranked is None:
                try:
                    u, c = np.unique(vals, return_counts=True)
                except TypeError:  # unorderable mixed objects
                    u = c = None
            if u is not None:
                # one hash a distinct value, shared by the CMS and the HLL
                basis = _hash_basis(u)
                st.frequency.observe_weighted(u, c, basis=basis)
                st.topk.observe_weighted(u, c)
                st.cardinality.observe(u, basis=basis)
            else:
                st.frequency.observe(vals)
                st.topk.observe(vals)
                st.cardinality.observe(vals)
            self.attrs[a.name] = st
        if z3_index is not None and z3_index.n and z3_index.zs is not None:
            self.z3hist = Z3Histogram()
            self.z3hist.observe_binned(z3_index.bins, z3_index.zs)
            self._z3_index = z3_index

    # -- estimation (StatsBasedEstimator role) --------------------------------
    def estimate_spatiotemporal(self, e: Extraction, sfc, binned) -> float:
        """Estimated rows matching spatial∩temporal bounds via Z3Histogram."""
        if self.z3hist is None:
            return float(self.count)
        if not e.spatially_bounded and not e.temporally_bounded:
            return float(self.count)
        from geomesa_tpu.index.z3 import WORLD, time_windows

        boxes = e.boxes if e.boxes is not None else [WORLD]
        bin_values = np.array(sorted(self.z3hist.counts), dtype=np.int64)
        windows = time_windows(binned, bin_values, e.intervals)
        est = 0.0
        for b, w_lo, w_hi in windows:
            # coarse cover is fine for estimation
            zr = sfc.ranges(boxes, (float(w_lo), float(w_hi)), max_ranges=64)
            est += self.z3hist.estimate_zranges(b, zr)
        return est

    def estimate_attr(self, name: str, bounds) -> float:
        """Estimated rows matching attribute value intervals."""
        if bounds is None:
            return float(self.count)
        st = self.attrs.get(name)
        if st is None:
            return float(self.count)
        try:
            a_type = self.sft.attr(name).type
        except KeyError:
            a_type = None
        est = 0.0
        for lo, hi, li, ri in bounds:
            if lo is not None and lo == hi:
                # coerce the CQL literal to the column's value type first:
                # the CMS hash basis is dtype-keyed (int 5 and float 5.0
                # hash differently), and the observed values carry the
                # column dtype — both literal directions need mapping
                q = lo
                if a_type is not None and a_type.is_numeric:
                    if isinstance(q, int) and not isinstance(q, bool) \
                            and a_type.value in ("Double", "Float"):
                        q = float(q)
                    elif isinstance(q, float) and q.is_integer() \
                            and a_type.value in ("Integer", "Long"):
                        q = int(q)
                est += st.frequency.count(q)
            elif st.histogram is not None:
                flo = float(st.histogram.lo if lo is None else lo)
                fhi = float(st.histogram.hi if hi is None else hi)
                est += st.histogram.estimate_range(flo, fhi)
            else:
                # string range: fall back to a fixed selectivity fraction
                est += self.count * 0.1
        return min(est, float(self.count))

    def estimate_filter_rows(self, f) -> float:
        """Composed row estimate for a filter or pre-extracted bounds —
        THE single estimation entry point (satellite of ROADMAP item 3):
        the spatio-temporal Z3Histogram estimate min'd with every bounded
        attribute's estimate (``Frequency`` point counts for equality,
        ``Histogram.estimate_range`` for ranges — composed inside
        :meth:`estimate_attr`), so the planner, the cost model, and
        ``stats_count`` all share one definition instead of reaching into
        individual sketches. Accepts a filter AST or an
        :class:`~geomesa_tpu.filter.bounds.Extraction`."""
        from geomesa_tpu.curve.binned_time import BinnedTime
        from geomesa_tpu.curve.sfc import z3_sfc
        from geomesa_tpu.filter.bounds import extract as _extract

        if isinstance(f, Extraction):
            e = f
        else:
            e = _extract(
                f, self.sft.geom_field, self.sft.dtg_field,
                attrs=tuple(self.attrs),
            )
        if e.disjoint:
            return 0.0
        est = self.estimate_spatiotemporal(
            e, z3_sfc(self.sft.z3_interval), BinnedTime(self.sft.z3_interval)
        )
        for name, bounds in e.attributes.items():
            if bounds is not None:
                est = min(est, self.estimate_attr(name, bounds))
        return float(min(max(est, 0.0), self.count))

    def selectivity(self, f) -> float:
        """Estimated matching fraction in [0, 1] for a filter AST /
        Extraction — :meth:`estimate_filter_rows` over the snapshot count
        (0.0 on an empty snapshot). The cost model's seed signal."""
        if self.count <= 0:
            return 0.0
        return self.estimate_filter_rows(f) / float(self.count)

    # -- public stats API (GeoMesaStats.getCount/getBounds/getMinMax) --------
    def min_max(self, attr: str) -> MinMax:
        return self.attrs[attr].minmax

    def top_k(self, attr: str, k: int = 10):
        return self.attrs[attr].topk.top(k)

    def histogram(self, attr: str) -> Histogram | None:
        return self.attrs[attr].histogram

    def cardinality(self, attr: str) -> float:
        return self.attrs[attr].cardinality.estimate()
