"""Mergeable statistical sketches (the ``geomesa-utils`` stats library role).

Reference: ``geomesa-utils/.../utils/stats/*.scala`` (SURVEY.md §2.18) —
``MinMax``, ``CountStat``, ``Histogram``/``BinnedArray``, ``Frequency``
(CountMinSketch), ``TopK``, ``Cardinality`` (HyperLogLog), ``Z3Histogram``,
``DescriptiveStats``, ``EnumerationStat``, ``GroupBy``, ``SeqStat``. All
sketches are **monoids** (associative ``merge``) so per-shard partials combine
with ``psum``-style reductions (reference merges them in ``StatsCombiner`` on
tablet servers — SURVEY.md §2.9).

Numpy-state implementations: every sketch's state is a small set of arrays, so
device-side update kernels (segment reductions) can share the layout.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


class Stat:
    """Base sketch: observe (vectorized), merge (monoid), to/from bytes."""

    def observe(self, values: np.ndarray) -> None:
        raise NotImplementedError

    def merge(self, other: "Stat") -> "Stat":
        raise NotImplementedError

    def __add__(self, other):
        return self.merge(other)


@dataclass
class CountStat(Stat):
    count: int = 0

    def observe(self, values):
        self.count += int(len(values))

    def merge(self, other):
        return CountStat(self.count + other.count)


@dataclass
class MinMax(Stat):
    """Min/max over a comparable attribute (``MinMax.scala``)."""

    min: object = None
    max: object = None

    def observe(self, values):
        if len(values) == 0:
            return
        lo, hi = np.min(values), np.max(values)
        lo = lo.item() if isinstance(lo, np.generic) else lo
        hi = hi.item() if isinstance(hi, np.generic) else hi
        self.min = lo if self.min is None else min(self.min, lo)
        self.max = hi if self.max is None else max(self.max, hi)

    def merge(self, other):
        out = MinMax(self.min, self.max)
        if other.min is not None:
            out.min = other.min if out.min is None else min(out.min, other.min)
            out.max = other.max if out.max is None else max(out.max, other.max)
        return out


@dataclass
class Histogram(Stat):
    """Equi-width binned counts over [lo, hi] (``Histogram``+``BinnedArray``)."""

    lo: float
    hi: float
    bins: int = 1000
    counts: np.ndarray = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.counts is None:
            self.counts = np.zeros(self.bins, dtype=np.int64)

    def _bin(self, values):
        v = np.asarray(values, dtype=np.float64)
        scaled = (v - self.lo) * (self.bins / max(self.hi - self.lo, 1e-300))
        return np.clip(scaled.astype(np.int64), 0, self.bins - 1)

    def observe(self, values):
        if len(values):
            np.add.at(self.counts, self._bin(values), 1)

    def merge(self, other):
        assert (self.lo, self.hi, self.bins) == (other.lo, other.hi, other.bins)
        return Histogram(self.lo, self.hi, self.bins, self.counts + other.counts)

    @property
    def total(self) -> int:
        return int(self.counts.sum())

    def estimate_range(self, lo: float, hi: float) -> float:
        """Estimated count in [lo, hi] with fractional edge bins."""
        if hi < lo:
            return 0.0
        w = (self.hi - self.lo) / self.bins
        if w <= 0:
            return float(self.total)
        b0 = (lo - self.lo) / w
        b1 = (hi - self.lo) / w
        i0 = int(np.clip(np.floor(b0), 0, self.bins - 1))
        i1 = int(np.clip(np.floor(b1), 0, self.bins - 1))
        if i0 == i1:
            return float(self.counts[i0]) * min(1.0, max(0.0, b1 - b0))
        est = self.counts[i0] * (i0 + 1 - b0) + self.counts[i1] * (b1 - i1)
        if i1 > i0 + 1:
            est += self.counts[i0 + 1 : i1].sum()
        return float(max(est, 0.0))



def _hash_basis(values) -> np.ndarray:
    """uint64 per-value hash basis shared by the CMS and HLL sketches.

    Numeric/bool/datetime arrays use their 64-bit patterns directly
    (vectorized — the splitmix-style mixers downstream do the avalanche
    work); object/string payloads take the per-value Python hash, one
    ``hash`` call a value, as the bits of its int64. Batch observe and
    single-value count both route through here, so the basis stays
    internally consistent."""
    a = np.asarray(values)
    if a.dtype.kind in "iu":
        return a.astype(np.int64, copy=False).view(np.uint64)
    if a.dtype.kind == "f":
        b = a.astype(np.float64, copy=False) + 0.0  # fold -0.0 into 0.0
        return b.view(np.uint64)
    if a.dtype.kind == "b":
        return a.astype(np.uint64)
    if a.dtype.kind == "M":
        return a.astype("datetime64[ms]").astype(np.int64).view(np.uint64)
    return np.fromiter(map(hash, values), np.int64,
                       count=len(values)).view(np.uint64)


@dataclass
class Frequency(Stat):
    """Count-min sketch for per-value frequency (``Frequency.scala`` /
    clearspring ``CountMinSketch``)."""

    depth: int = 4
    width: int = 1 << 12
    table: np.ndarray = None  # type: ignore[assignment]
    _seeds: np.ndarray = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.table is None:
            self.table = np.zeros((self.depth, self.width), dtype=np.int64)
        if self._seeds is None:
            self._seeds = np.arange(1, self.depth + 1, dtype=np.uint64) * np.uint64(
                0x9E3779B97F4A7C15
            )

    def _hashes(self, values, basis=None) -> np.ndarray:
        """(depth, n) bucket indices via splitmix-style mixing."""
        hv = _hash_basis(values) if basis is None else basis
        out = np.empty((self.depth, len(hv)), dtype=np.int64)
        for d in range(self.depth):
            x = hv * self._seeds[d]
            x ^= x >> np.uint64(31)
            x *= np.uint64(0xBF58476D1CE4E5B9)
            x ^= x >> np.uint64(27)
            out[d] = (x % np.uint64(self.width)).astype(np.int64)
        return out

    def observe(self, values):
        if len(values) == 0:
            return
        h = self._hashes(values)
        for d in range(self.depth):
            np.add.at(self.table[d], h[d], 1)

    def observe_weighted(self, values, counts, basis=None):
        """Observe pre-aggregated (unique value, count) pairs — the bulk
        rebuild path folds each column to its distinct values once and
        feeds the weights here, replacing n per-value updates with u.
        ``basis`` is ``_hash_basis(values)`` where the caller has it."""
        if len(values) == 0:
            return
        h = self._hashes(values, basis)
        w = np.asarray(counts, dtype=np.int64)
        for d in range(self.depth):
            np.add.at(self.table[d], h[d], w)

    def count(self, value) -> int:
        h = self._hashes([value])
        return int(min(self.table[d, h[d, 0]] for d in range(self.depth)))

    def merge(self, other):
        assert (self.depth, self.width) == (other.depth, other.width)
        return Frequency(self.depth, self.width, self.table + other.table, self._seeds)


@dataclass
class Cardinality(Stat):
    """HyperLogLog distinct-count (``Cardinality.scala`` / clearspring HLL)."""

    p: int = 12  # 2^p registers
    registers: np.ndarray = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.registers is None:
            self.registers = np.zeros(1 << self.p, dtype=np.uint8)

    def observe(self, values, basis=None):
        """``basis`` is ``_hash_basis(values)`` where the caller has it."""
        if len(values) == 0:
            return
        hv = _hash_basis(values) if basis is None else basis
        x = hv * np.uint64(0x9E3779B97F4A7C15)
        x ^= x >> np.uint64(29)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(32)
        idx = (x >> np.uint64(64 - self.p)).astype(np.int64)
        rest = x << np.uint64(self.p)
        # rank = leading zeros of the remaining bits + 1 (capped at 64-p+1)
        bl = np.zeros(len(x), dtype=np.int64)  # bit length via binary search
        r = rest.copy()
        for s in (32, 16, 8, 4, 2, 1):
            big = r >= (np.uint64(1) << np.uint64(s))
            bl += np.where(big, s, 0)
            r = np.where(big, r >> np.uint64(s), r)
        bl += (r > 0).astype(np.int64)
        rank = np.minimum(64 - bl, 64 - self.p) + 1
        np.maximum.at(self.registers, idx, rank.astype(np.uint8))

    def estimate(self) -> float:
        m = float(len(self.registers))
        alpha = 0.7213 / (1 + 1.079 / m)
        inv = np.power(2.0, -self.registers.astype(np.float64))
        e = alpha * m * m / inv.sum()
        zeros = int((self.registers == 0).sum())
        if e <= 2.5 * m and zeros:
            return m * np.log(m / zeros)  # linear counting
        return float(e)

    def merge(self, other):
        assert self.p == other.p
        return Cardinality(self.p, np.maximum(self.registers, other.registers))


@dataclass
class TopK(Stat):
    """Heavy hitters via space-saving-lite (``TopK.scala`` / StreamSummary).

    Exact-dict implementation with bounded pruning: capacity*10 tracked keys,
    pruned back to capacity*2 by count — adequate for planning hints.
    """

    capacity: int = 10
    counts: dict = field(default_factory=dict)

    def observe(self, values):
        for v in values:
            self.counts[v] = self.counts.get(v, 0) + 1
        if len(self.counts) > self.capacity * 10:
            keep = sorted(self.counts.items(), key=lambda kv: -kv[1])[: self.capacity * 2]
            self.counts = dict(keep)

    def observe_weighted(self, values, counts):
        """Pre-aggregated (unique value, count) pairs. Only the heaviest
        ``capacity * 10`` uniques can survive pruning, so the top slice is
        selected vectorized and the Python loop shrinks to that slice —
        EXACT for a whole-snapshot rebuild (every duplicate is already
        folded into its count)."""
        counts = np.asarray(counts)
        if len(values) > self.capacity * 10:
            top = np.argpartition(counts, -self.capacity * 10)[-self.capacity * 10:]
            values = np.asarray(values, dtype=object)[top]
            counts = counts[top]
        for v, c in zip(values, counts):
            self.counts[v] = self.counts.get(v, 0) + int(c)
        if len(self.counts) > self.capacity * 10:
            keep = sorted(self.counts.items(), key=lambda kv: -kv[1])[: self.capacity * 2]
            self.counts = dict(keep)

    def top(self, k: int | None = None):
        k = k or self.capacity
        return sorted(self.counts.items(), key=lambda kv: -kv[1])[:k]

    def merge(self, other):
        out = TopK(self.capacity, dict(self.counts))
        for v, c in other.counts.items():
            out.counts[v] = out.counts.get(v, 0) + c
        return out


@dataclass
class EnumerationStat(Stat):
    """Exact value → count enumeration (``EnumerationStat.scala``)."""

    counts: dict = field(default_factory=dict)

    def observe(self, values):
        vals, cnts = np.unique(np.asarray(values, dtype=object), return_counts=True)
        for v, c in zip(vals, cnts):
            self.counts[v] = self.counts.get(v, 0) + int(c)

    def merge(self, other):
        out = EnumerationStat(dict(self.counts))
        for v, c in other.counts.items():
            out.counts[v] = out.counts.get(v, 0) + c
        return out


@dataclass
class DescriptiveStats(Stat):
    """Streaming count/mean/M2 (variance) per Welford (``DescriptiveStats``)."""

    count: int = 0
    mean: float = 0.0
    m2: float = 0.0

    def observe(self, values):
        v = np.asarray(values, dtype=np.float64)
        v = v[np.isfinite(v)]
        if len(v) == 0:
            return
        n_b = len(v)
        mean_b = float(v.mean())
        m2_b = float(((v - mean_b) ** 2).sum())
        self._combine(n_b, mean_b, m2_b)

    def _combine(self, n_b, mean_b, m2_b):
        n_a = self.count
        delta = mean_b - self.mean
        n = n_a + n_b
        if n == 0:
            return
        self.mean += delta * n_b / n
        self.m2 += m2_b + delta * delta * n_a * n_b / n
        self.count = n

    @property
    def variance(self) -> float:
        return self.m2 / (self.count - 1) if self.count > 1 else 0.0

    def merge(self, other):
        out = DescriptiveStats(self.count, self.mean, self.m2)
        out._combine(other.count, other.mean, other.m2)
        return out


@dataclass
class Z3Histogram(Stat):
    """Counts per (time-bin, coarse z-cell): spatio-temporal selectivity
    (``Z3Histogram.scala``). z-cells are the top ``bits`` of the z3 code."""

    bits: int = 12  # 2^bits spatial cells per time bin
    counts: dict = field(default_factory=dict)  # bin -> np.ndarray(2^bits)

    def observe_binned(self, bins: np.ndarray, zs: np.ndarray) -> None:
        shift = np.uint64(63 - self.bits)
        cells = (zs.astype(np.uint64) >> shift).astype(np.int64)
        for b in np.unique(bins):
            sel = bins == b
            arr = self.counts.setdefault(int(b), np.zeros(1 << self.bits, np.int64))
            np.add.at(arr, cells[sel], 1)

    def observe(self, values):  # pragma: no cover - use observe_binned
        raise NotImplementedError("use observe_binned(bins, zs)")

    def estimate_cells(self, b: int, cell_lo: int, cell_hi: int) -> float:
        arr = self.counts.get(int(b))
        if arr is None:
            return 0.0
        return float(arr[cell_lo : cell_hi + 1].sum())

    def estimate_zranges(self, b: int, zranges: np.ndarray) -> float:
        """Estimated rows in a bin covered by inclusive z ranges (fractional
        cells at the edges)."""
        arr = self.counts.get(int(b))
        if arr is None or len(zranges) == 0:
            return 0.0
        shift = 63 - self.bits
        cell_span = 1 << shift
        est = 0.0
        for zlo, zhi in zranges:
            c0 = int(zlo) >> shift
            c1 = int(zhi) >> shift
            if c0 == c1:
                est += arr[c0] * (int(zhi) - int(zlo) + 1) / cell_span
            else:
                est += arr[c0] * ((c0 + 1) * cell_span - int(zlo)) / cell_span
                est += arr[c1] * (int(zhi) + 1 - c1 * cell_span) / cell_span
                if c1 > c0 + 1:
                    est += arr[c0 + 1 : c1].sum()
        return float(est)

    def merge(self, other):
        assert self.bits == other.bits
        out = Z3Histogram(self.bits, {k: v.copy() for k, v in self.counts.items()})
        for b, arr in other.counts.items():
            if b in out.counts:
                out.counts[b] = out.counts[b] + arr
            else:
                out.counts[b] = arr.copy()
        return out

    @property
    def total(self) -> int:
        return int(sum(arr.sum() for arr in self.counts.values()))


@dataclass
class Z3Frequency(Stat):
    """Count-min sketch over (time-bin, coarse z3-cell) keys — approximate
    spatio-temporal frequency in sublinear space (``Z3Frequency.scala``).

    Where :class:`Z3Histogram` stores one exact array per time bin (memory
    grows with bin count), this folds every (bin, cell) key into one fixed
    ``depth × width`` CMS, so long-lived stores can keep selectivity stats
    over unbounded time spans."""

    bits: int = 12  # coarse cell = top `bits` of the z3 code
    depth: int = 4
    width: int = 1 << 12
    table: np.ndarray = None  # type: ignore[assignment]
    _seeds: np.ndarray = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.table is None:
            self.table = np.zeros((self.depth, self.width), dtype=np.int64)
        if self._seeds is None:
            self._seeds = np.arange(1, self.depth + 1, dtype=np.uint64) * np.uint64(
                0x9E3779B97F4A7C15
            )

    def _keys(self, bins, zs) -> np.ndarray:
        shift = np.uint64(63 - self.bits)
        cells = zs.astype(np.uint64) >> shift
        return (bins.astype(np.uint64) << np.uint64(self.bits + 1)) | cells

    def _hashes(self, keys: np.ndarray) -> np.ndarray:
        out = np.empty((self.depth, len(keys)), dtype=np.int64)
        for d in range(self.depth):
            x = keys * self._seeds[d]
            x ^= x >> np.uint64(31)
            x *= np.uint64(0xBF58476D1CE4E5B9)
            x ^= x >> np.uint64(27)
            out[d] = (x % np.uint64(self.width)).astype(np.int64)
        return out

    def observe_binned(self, bins: np.ndarray, zs: np.ndarray) -> None:
        if len(bins) == 0:
            return
        h = self._hashes(self._keys(np.asarray(bins), np.asarray(zs)))
        for d in range(self.depth):
            np.add.at(self.table[d], h[d], 1)

    def observe(self, values):  # pragma: no cover - use observe_binned
        raise NotImplementedError("use observe_binned(bins, zs)")

    def count(self, b: int, cell: int) -> int:
        """Point estimate for one (bin, coarse-cell) key (CMS upper bound)."""
        key = (np.uint64(b) << np.uint64(self.bits + 1)) | np.uint64(cell)
        h = self._hashes(np.array([key], dtype=np.uint64))
        return int(min(self.table[d, h[d, 0]] for d in range(self.depth)))

    def estimate_zranges(self, b: int, zranges) -> float:
        """Estimated rows in a bin covered by inclusive z ranges."""
        shift = 63 - self.bits
        cells = set()
        for zlo, zhi in zranges:
            cells.update(range(int(zlo) >> shift, (int(zhi) >> shift) + 1))
        return float(sum(self.count(b, c) for c in cells))

    def merge(self, other):
        assert (self.depth, self.width, self.bits) == (
            other.depth, other.width, other.bits,
        )
        return Z3Frequency(
            self.bits, self.depth, self.width, self.table + other.table, self._seeds
        )


@dataclass
class GroupBy(Stat):
    """Per-group sub-sketches (``GroupBy.scala``): one sketch per distinct
    grouping value, each the same mergeable kind."""

    factory: object = None  # () -> Stat
    groups: dict = field(default_factory=dict)

    def observe_groups(self, keys, values) -> None:
        keys = np.asarray(keys, dtype=object)
        values = np.asarray(values)
        for k in set(keys.tolist()):
            sub = self.groups.get(k)
            if sub is None:
                sub = self.groups[k] = self.factory()
            sub.observe(values[keys == k])

    def observe(self, values):  # pragma: no cover - use observe_groups
        raise NotImplementedError("use observe_groups(keys, values)")

    def merge(self, other):
        assert type(self.factory()) is type(other.factory())  # noqa: E721
        import copy

        # deep-copy both sides: merged output must not alias live partials
        # (every other sketch's merge returns fully owned state)
        out = GroupBy(
            self.factory, {k: copy.deepcopy(v) for k, v in self.groups.items()}
        )
        for k, sub in other.groups.items():
            out.groups[k] = (
                copy.deepcopy(sub)
                if k not in out.groups
                else out.groups[k].merge(sub)
            )
        return out


@dataclass
class CovarianceStats(Stat):
    """Streaming multivariate mean/covariance (the reference
    ``DescriptiveStats`` tracks incremental covariance across attributes).

    State: count, d-vector mean, d×d comoment matrix; merged with the
    parallel (Chan et al.) update, so per-shard partials combine exactly."""

    dims: int = 2
    count: int = 0
    mean: np.ndarray = None  # type: ignore[assignment]
    comoment: np.ndarray = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.mean is None:
            self.mean = np.zeros(self.dims, dtype=np.float64)
        if self.comoment is None:
            self.comoment = np.zeros((self.dims, self.dims), dtype=np.float64)

    def observe(self, values):
        v = np.asarray(values, dtype=np.float64).reshape(-1, self.dims)
        v = v[np.isfinite(v).all(axis=1)]
        if len(v) == 0:
            return
        mean_b = v.mean(axis=0)
        dev = v - mean_b
        self._combine(len(v), mean_b, dev.T @ dev)

    def _combine(self, n_b: int, mean_b: np.ndarray, c_b: np.ndarray) -> None:
        n_a = self.count
        n = n_a + n_b
        if n == 0:
            return
        delta = mean_b - self.mean
        self.mean = self.mean + delta * (n_b / n)
        self.comoment = (
            self.comoment + c_b + np.outer(delta, delta) * (n_a * n_b / n)
        )
        self.count = n

    @property
    def covariance(self) -> np.ndarray:
        if self.count < 2:
            return np.zeros((self.dims, self.dims))
        return self.comoment / (self.count - 1)

    def merge(self, other):
        assert self.dims == other.dims
        out = CovarianceStats(
            self.dims, self.count, self.mean.copy(), self.comoment.copy()
        )
        out._combine(other.count, other.mean, other.comoment)
        return out
