"""Struct-of-arrays feature table: the host-side columnar store.

This wholesale replaces the reference's row-oriented feature serializers
(Kryo lazy features, ``geomesa-features`` — SURVEY.md §2.4): where the JVM
design fakes columnar access with per-attribute byte offsets
(``KryoBufferSimpleFeature``), we store features as real columns — numeric
arrays, epoch-millis dates, dictionary-encodable strings, and geometry columns
that always carry vectorized bbox arrays (plus x/y fast paths for points).
Device-side stores (:mod:`geomesa_tpu.store.tpu_backend`) are typed views of
these columns; Arrow IPC interchange is a zero-copy re-labeling
(:mod:`geomesa_tpu.io.arrow`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Iterable

import numpy as np

from geomesa_tpu.geometry.types import Point
from geomesa_tpu.schema.sft import AttributeType, FeatureType

_NUMERIC_DTYPES = {
    AttributeType.INT: np.int32,
    AttributeType.LONG: np.int64,
    AttributeType.FLOAT: np.float32,
    AttributeType.DOUBLE: np.float64,
    AttributeType.BOOLEAN: np.bool_,
}


@dataclass
class Column:
    """One attribute's storage; ``valid`` is None when all values are set."""

    type: AttributeType
    values: np.ndarray  # typed array; object array for strings/geoms
    valid: np.ndarray | None = None

    def __len__(self) -> int:
        return len(self.values)

    def take(self, idx: np.ndarray) -> "Column":
        return Column(
            self.type,
            self.values[idx],
            None if self.valid is None else self.valid[idx],
        )

    def is_valid(self) -> np.ndarray:
        if self.valid is None:
            return np.ones(len(self), dtype=bool)  # len() works for lazy geometry columns too
        return self.valid

    def dictionary(self):
        """Cached (sorted vocab, codes int32) for string columns — the
        ``ArrowDictionary`` role. Predicates evaluate against the (small)
        vocab once and compare int codes per row instead of strings
        (``ArrowFilterOptimizer.scala:1`` pushdown); None for non-strings.
        Invalid and non-str slots map to "".
        """
        if self.type not in (AttributeType.STRING, AttributeType.UUID):
            return None
        return self._factorized()[:2]

    def ranked(self):
        """``(distinct, codes)`` when the dictionary's codes rank the set
        values exactly: ``distinct`` holds the column's own sorted distinct
        strings (one object per vocab entry) and ``codes`` are
        :meth:`dictionary`'s, so counting or sorting codes at the set rows
        counts or sorts the strings themselves. None for non-string or
        non-object columns, a set value that is not a ``str``, or distinct
        strings that the unicode vocab merges (trailing NULs)."""
        if (self.type not in (AttributeType.STRING, AttributeType.UUID)
                or self.values.dtype != object):
            return None
        _, codes, distinct = self._factorized()
        return None if distinct is None else (distinct, codes)

    def _factorized(self):
        cached = self.__dict__.get("_dict")
        if cached is None:
            cached = self.__dict__["_dict"] = _factorize_strings(
                self.values, self.valid)
        return cached


def _factorize_strings(values, valid):
    """(vocab, codes, distinct) of a string column, built by hashing:
    factorize the values, sort only the distinct ones, remap the codes by
    rank. ``vocab`` and ``codes`` equal ``np.unique`` over the column's
    unicode cast (non-str values as ""); ``distinct`` is the sorted
    distinct objects when they align one to one with ``vocab`` and every
    set value is a str, else None."""
    import pandas as pd

    set_all_str = all_str = pd.api.types.infer_dtype(
        values, skipna=False) == "string"
    if all_str:
        obj = np.asarray(values, dtype=object)
    else:
        # a stray non-str value must keep mapping to "" (str(v) here
        # would change filter and grouping results)
        obj = np.array([v if isinstance(v, str) else "" for v in values],
                       dtype=object)
        if valid is not None:
            set_all_str = pd.api.types.infer_dtype(
                values[valid], skipna=False) in ("string", "empty")
    codes, uniq = pd.factorize(obj)
    if not (uniq[codes] == obj).all():
        # pandas' string hash table ends a string at its first NUL: fall
        # back to Python's own hashing and equality
        seen: dict = {}
        codes = np.fromiter((seen.setdefault(v, len(seen)) for v in obj),
                            np.int64, count=len(obj))
        uniq = np.empty(len(seen), dtype=object)
        uniq[:] = list(seen)
    uv = uniq.astype(str)
    # stable: timsort takes ids that arrive in order in one pass
    order = np.argsort(uv, kind="stable")
    ordered = uv[order]
    first = np.ones(len(ordered), dtype=bool)
    np.not_equal(ordered[1:], ordered[:-1], out=first[1:])
    rank = np.empty(len(ordered), dtype=np.int32)
    rank[order] = np.cumsum(first) - 1
    vocab = ordered[first]
    distinct = None
    if set_all_str and len(vocab) == len(uniq):
        distinct = uniq[order]
    return vocab, rank[codes], distinct


@dataclass
class GeometryColumn(Column):
    """Geometry storage: object array of Geometry + always-on bbox SoA.

    For Point columns ``x``/``y`` are the primary storage (values may be
    lazily materialized); bboxes degenerate to the points themselves.
    """

    x: np.ndarray | None = None  # f64, points only
    y: np.ndarray | None = None
    bounds: np.ndarray | None = None  # (N, 4) f64: xmin, ymin, xmax, ymax

    def take(self, idx: np.ndarray) -> "GeometryColumn":
        return GeometryColumn(
            self.type,
            self.values[idx] if self.values is not None else None,
            None if self.valid is None else self.valid[idx],
            x=None if self.x is None else self.x[idx],
            y=None if self.y is None else self.y[idx],
            bounds=None if self.bounds is None else self.bounds[idx],
        )

    def __len__(self) -> int:
        if self.values is not None:
            return len(self.values)
        return len(self.x)

    def geometries(self) -> np.ndarray:
        """Materialize the object array (lazily for point columns)."""
        if self.values is None:
            vals = np.empty(len(self.x), dtype=object)
            for i in range(len(self.x)):
                vals[i] = Point(float(self.x[i]), float(self.y[i]))
            self.values = vals
        return self.values


def _geometry_column(typ: AttributeType, geoms: Iterable[Any]) -> GeometryColumn:
    geoms = list(geoms)
    if any(isinstance(g, str) for g in geoms):
        # WKT strings accepted anywhere a geometry is (GeoTools convention)
        from geomesa_tpu.geometry.wkt import from_wkt

        geoms = [from_wkt(g) if isinstance(g, str) else g for g in geoms]
    n = len(geoms)
    if typ == AttributeType.POINT:
        x = np.empty(n, dtype=np.float64)
        y = np.empty(n, dtype=np.float64)
        valid = np.ones(n, dtype=bool)
        vals = np.empty(n, dtype=object)
        for i, g in enumerate(geoms):
            if g is None:
                valid[i] = False
                x[i] = np.nan
                y[i] = np.nan
            else:
                vals[i] = g
                x[i] = g.x
                y[i] = g.y
        bounds = np.stack([x, y, x, y], axis=1)
        return GeometryColumn(
            typ, vals, None if valid.all() else valid, x=x, y=y, bounds=bounds
        )
    vals = np.empty(n, dtype=object)
    bounds = np.full((n, 4), np.nan, dtype=np.float64)
    valid = np.ones(n, dtype=bool)
    for i, g in enumerate(geoms):
        vals[i] = g
        if g is None:
            valid[i] = False
        else:
            bounds[i] = g.bbox
    return GeometryColumn(typ, vals, None if valid.all() else valid, bounds=bounds)


def point_column(x: np.ndarray, y: np.ndarray, valid=None) -> GeometryColumn:
    """Fast-path Point column straight from coordinate arrays (bulk ingest)."""
    x = np.asarray(x, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    bounds = np.stack([x, y, x, y], axis=1)
    return GeometryColumn(AttributeType.POINT, None, valid, x=x, y=y, bounds=bounds)


def null_column(typ: AttributeType, n: int) -> Column:
    """An all-null column of length ``n`` (schema-evolution backfill)."""
    valid = np.zeros(n, dtype=bool)
    if typ in _NUMERIC_DTYPES:
        return Column(typ, np.zeros(n, dtype=_NUMERIC_DTYPES[typ]), valid)
    if typ == AttributeType.DATE:
        return Column(typ, np.zeros(n, dtype=np.int64), valid)
    return Column(typ, np.empty(n, dtype=object), valid)


def _scalar_column(typ: AttributeType, values: Iterable[Any]) -> Column:
    values = list(values)
    n = len(values)
    if typ in _NUMERIC_DTYPES:
        dtype = _NUMERIC_DTYPES[typ]
        arr = np.zeros(n, dtype=dtype)
        valid = np.ones(n, dtype=bool)
        for i, v in enumerate(values):
            if v is None:
                valid[i] = False
            else:
                arr[i] = v
        return Column(typ, arr, None if valid.all() else valid)
    if typ == AttributeType.DATE:
        arr = np.zeros(n, dtype=np.int64)
        valid = np.ones(n, dtype=bool)
        for i, v in enumerate(values):
            if v is None:
                valid[i] = False
            else:
                arr[i] = _to_millis(v)
        return Column(typ, arr, None if valid.all() else valid)
    # strings / uuid / bytes: object array
    arr = np.empty(n, dtype=object)
    valid = np.ones(n, dtype=bool)
    for i, v in enumerate(values):
        arr[i] = v
        if v is None:
            valid[i] = False
    return Column(typ, arr, None if valid.all() else valid)


def _to_millis(v) -> int:
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, np.datetime64):
        return int(v.astype("datetime64[ms]").astype(np.int64))
    if isinstance(v, str):
        return int(
            np.datetime64(v.rstrip("Z"), "ms").astype(np.int64)
        )
    import datetime

    if isinstance(v, datetime.datetime):
        if v.tzinfo is None:
            v = v.replace(tzinfo=datetime.timezone.utc)
        return int(v.timestamp() * 1000)
    raise TypeError(f"cannot convert to epoch millis: {v!r}")


@dataclass
class FeatureTable:
    """An ordered batch of features as columns; the unit of ingest/scan/result."""

    sft: FeatureType
    fids: np.ndarray  # object array of str
    columns: dict[str, Column]

    def __post_init__(self):
        n = len(self.fids)
        for name, col in self.columns.items():
            if len(col) != n:
                raise ValueError(
                    f"column {name!r} length {len(col)} != feature count {n}"
                )

    def __len__(self) -> int:
        return len(self.fids)

    @property
    def n(self) -> int:
        return len(self.fids)

    # -- constructors --------------------------------------------------------
    @staticmethod
    def from_records(
        sft: FeatureType, records: list[dict], fids: list[str] | None = None
    ) -> "FeatureTable":
        cols: dict[str, Column] = {}
        for a in sft.attributes:
            vals = [r.get(a.name) for r in records]
            if a.type.is_geometry:
                cols[a.name] = _geometry_column(a.type, vals)
            else:
                cols[a.name] = _scalar_column(a.type, vals)
        if fids is None:
            fids = [str(i) for i in range(len(records))]
        return FeatureTable(sft, np.asarray(fids, dtype=object), cols)

    @staticmethod
    def from_columns(
        sft: FeatureType, fids, columns: dict[str, Column]
    ) -> "FeatureTable":
        return FeatureTable(sft, np.asarray(fids, dtype=object), columns)

    # -- row access ----------------------------------------------------------
    def record(self, i: int) -> dict:
        out = {}
        for name, col in self.columns.items():
            if col.valid is not None and not col.valid[i]:
                out[name] = None
            elif isinstance(col, GeometryColumn):
                out[name] = col.geometries()[i]
            else:
                v = col.values[i]
                out[name] = v.item() if isinstance(v, np.generic) else v
        return out

    def take(self, idx) -> "FeatureTable":
        idx = np.asarray(idx)
        return FeatureTable(
            self.sft,
            self.fids[idx],
            {k: c.take(idx) for k, c in self.columns.items()},
        )

    # -- geometry / time accessors (the scan hot path) -----------------------
    def geom_column(self) -> GeometryColumn:
        if self.sft.geom_field is None:
            raise ValueError(f"schema {self.sft.name} has no geometry")
        return self.columns[self.sft.geom_field]  # type: ignore[return-value]

    def dtg_millis(self) -> np.ndarray:
        if self.sft.dtg_field is None:
            raise ValueError(f"schema {self.sft.name} has no date attribute")
        return self.columns[self.sft.dtg_field].values

    @staticmethod
    def concat(tables: list["FeatureTable"]) -> "FeatureTable":
        if not tables:
            raise ValueError("nothing to concat")
        sft = tables[0].sft
        fids = np.concatenate([t.fids for t in tables])
        cols: dict[str, Column] = {}
        for name in tables[0].columns:
            parts = [t.columns[name] for t in tables]
            if isinstance(parts[0], GeometryColumn):
                # mixed lazy (values=None) and materialized parts: keep lazy
                # only when ALL parts are lazy, else materialize everything
                if any(p.values is None for p in parts):
                    if all(p.values is None for p in parts):
                        vals = None
                    else:
                        vals = np.concatenate([p.geometries() for p in parts])
                else:
                    vals = np.concatenate([p.values for p in parts])
            else:
                vals = np.concatenate([p.values for p in parts])
            if any(p.valid is not None for p in parts):
                valid = np.concatenate([p.is_valid() for p in parts])
            else:
                valid = None
            if isinstance(parts[0], GeometryColumn):
                cols[name] = GeometryColumn(
                    parts[0].type,
                    vals,
                    valid,
                    x=_cat([p.x for p in parts]),
                    y=_cat([p.y for p in parts]),
                    bounds=_cat([p.bounds for p in parts]),
                )
            else:
                cols[name] = Column(parts[0].type, vals, valid)
        return FeatureTable(sft, fids, cols)


def _cat(arrs):
    if any(a is None for a in arrs):
        return None
    return np.concatenate(arrs)


def representative_xy(table: FeatureTable) -> tuple[np.ndarray, np.ndarray]:
    """Representative point coords for each feature: true point coords, or
    bbox centroids for extended geometries (shared by density/BIN aggregates
    and the track-oriented processes)."""
    col = table.geom_column()
    if col.x is not None:
        return col.x, col.y
    b = col.bounds
    return (b[:, 0] + b[:, 2]) * 0.5, (b[:, 1] + b[:, 3]) * 0.5
