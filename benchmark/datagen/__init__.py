"""Seeded data generators, one module per generator named by a
configuration's ``"generator"`` key.

Each module exposes ``generate(cfg, seed) -> Dataset``: columns built with
vectorised numpy from ``seed`` alone, the same seed giving the same data.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Dataset:
    """Generated rows. ``fids[i]`` is row ``i``'s feature id, a decimal
    string whose value less ``fid_base`` is ``i``.

    ``lon``/``lat`` f64 degrees, ``t`` int64 epoch milliseconds; ``attrs``
    holds every other attribute of the configuration's spec by name (numpy
    arrays; strings as object arrays drawn from a fixed vocabulary).
    """

    lon: np.ndarray
    lat: np.ndarray
    t: np.ndarray
    attrs: dict
    fids: np.ndarray
    fid_base: int = 0

    def __len__(self) -> int:
        return len(self.lon)


def ordinal_ids(n: int, base: int, width: int) -> np.ndarray:
    """Zero-padded decimal ids ``base .. base + n - 1``: fixed width, so
    string order is ingest order."""
    fmt = f"{{:0{width}d}}".format
    return np.fromiter(map(fmt, range(base, base + n)), dtype=object, count=n)


def fold(v: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Reflect values into ``[lo, hi]`` (a walk bouncing off the extent)."""
    w = hi - lo
    u = np.mod(v - lo, 2.0 * w)
    return lo + np.where(u <= w, u, 2.0 * w - u)


def stratified(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` values in (0, 1): the fixed quantiles ``(k + 0.5) / n`` in a
    seeded order, so every seed draws the same set of sizes."""
    return (rng.permutation(n) + 0.5) / n
