"""The plain reference: brute force over the generated columns.

Independent of the program: it reads only the generated ``Dataset`` and
the request's box and window. Semantics as GeoMesa's CQL: ``BBOX`` is
inclusive on every edge, ``DURING`` exclusive at both ends. Copied from
``chip_smoke.py``'s ``referee_rows``/``loose_bounds`` (PR 21), with the
rows put in time order once so a request scans only its window's slice.

``dtype`` is the precision the comparison is computed in: float64 is the
reference; float32, the nearest precision below the configuration's
(f64 coordinates, int64 epoch milliseconds), is the control that has to
come out not correct.
"""

from __future__ import annotations

import numpy as np

# a loose (int-domain) count may count rows up to one 31-bit coordinate
# quantum (~1.7e-7 deg) outside the box and up to one z3 offset unit
# (1 s) outside the window; the widened bounds add more than either
LOOSE_EPS_DEG = 1e-6
LOOSE_EPS_MS = 1000


class Referee:
    def __init__(self, data, dtype=np.float64):
        t = data.t
        order = None if bool(np.all(t[1:] >= t[:-1])) else np.argsort(t, kind="stable")
        take = (lambda a: a) if order is None else (lambda a: a[order])
        self.dtype = np.dtype(dtype)
        self.row = np.arange(len(t)) if order is None else order
        self.t = take(t).astype(self.dtype) if self.dtype != np.float64 else take(t)
        self.lon = take(data.lon).astype(self.dtype)
        self.lat = take(data.lat).astype(self.dtype)

    def _slice(self, lo, hi):
        """Rows with ``lo < t < hi``, compared in the times' own type."""
        cast = self.t.dtype.type
        return (np.searchsorted(self.t, cast(lo), side="right"),
                np.searchsorted(self.t, cast(hi), side="left"))

    def _mask(self, a, b, box, eps=0.0):
        x1, y1, x2, y2 = (self.dtype.type(v) for v in box)
        x = self.lon[a:b]
        y = self.lat[a:b]
        return (x >= x1 - eps) & (x <= x2 + eps) & (y >= y1 - eps) & (y <= y2 + eps)

    def rows(self, box, window) -> np.ndarray:
        """Sorted row ids inside ``box`` (inclusive) and ``window``
        (exclusive)."""
        a, b = self._slice(*window)
        return np.sort(self.row[a:b][self._mask(a, b, box)])

    def count(self, box, window) -> int:
        a, b = self._slice(*window)
        return int(self._mask(a, b, box).sum())

    def loose_bounds(self, box, window) -> tuple[int, int]:
        """(exact, widened) counts: a loose answer lies between them."""
        lo, hi = window
        a, b = self._slice(lo - LOOSE_EPS_MS, hi + LOOSE_EPS_MS)
        wide = int(self._mask(a, b, box, LOOSE_EPS_DEG).sum())
        return self.count(box, window), wide
