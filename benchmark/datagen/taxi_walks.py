"""T-Drive-shaped taxi tracks, from a seed.

Each taxi gets a share of the points (a lognormal weight, apportioned so
the total is exactly the configuration's), a home anchor around the city
centre, and a track: sampling gaps that are mostly short (lognormal) with
a heavy tail of long gaps (Pareto: the taxi parked or off), and a
mean-reverting walk about its anchor whose step is the speed times the
gap. Positions reflect off the configuration's extent. Everything is
vectorised over all points at once: the walk
``x_k = a x_{k-1} + e_k`` is ``a^k (x_0 + sum_i e_i a^-i)``, a segmented
cumulative sum.
"""

from __future__ import annotations

import numpy as np

from benchmark.datagen import Dataset, fold, ordinal_ids

_M_PER_DEG = 111_320.0


def _apportion(w: np.ndarray, total: int, minimum: int) -> np.ndarray:
    """Integer shares of ``total`` proportional to ``w``, each >= minimum."""
    free = total - minimum * len(w)
    raw = w / w.sum() * free
    out = np.floor(raw).astype(np.int64)
    rest = free - int(out.sum())
    out[np.argsort(out - raw)[:rest]] += 1
    return out + minimum


def _segment_cumsum(v: np.ndarray, starts: np.ndarray, seg: np.ndarray):
    c = np.cumsum(v)
    return c - (c[starts] - v[starts])[seg]


def generate(cfg: dict, seed: int) -> Dataset:
    g = cfg["generator_params"]
    n_taxi, n = int(cfg["taxis"]), int(cfg["points"])
    rng = np.random.default_rng([seed, 2])
    counts = _apportion(rng.lognormal(0.0, g["count_sigma"], n_taxi), n, 2)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    taxi = np.repeat(np.arange(n_taxi), counts)
    j = np.arange(n) - starts[taxi]  # step index within the taxi's track
    first = j == 0

    # sampling gaps (seconds): short lognormal, a Pareto tail of long ones
    dt = rng.lognormal(np.log(g["gap_median_s"]), g["gap_sigma"], n)
    long_gap = rng.random(n) < g["long_gap_share"]
    dt[long_gap] = np.minimum(
        g["long_gap_min_s"] * (1.0 + rng.pareto(g["long_gap_alpha"],
                                                int(long_gap.sum()))),
        g["long_gap_cap_s"])
    dt[first] = 0.0
    week_s = float(cfg["span_days"]) * 86_400.0
    tt = _segment_cumsum(dt, starts, taxi)
    span = tt[starts + counts - 1]
    squeeze = np.minimum(1.0, 0.999 * week_s / np.maximum(span, 1.0))
    tt *= squeeze[taxi]
    dt *= squeeze[taxi]
    begin = rng.random(n_taxi) * (week_s - span * squeeze)
    t = int(cfg["t0_ms"]) + np.round((begin[taxi] + tt) * 1000.0).astype(np.int64)

    # the walk, in degrees about each taxi's anchor
    a = float(g["reversion"])
    (x0, x1), (y0, y1) = cfg["extent_lon"], cfg["extent_lat"]
    cx, cy = g["centre"]
    lat_scale = _M_PER_DEG
    lon_scale = _M_PER_DEG * np.cos(np.radians(cy))
    speed = rng.lognormal(np.log(g["speed_median_mps"]), g["speed_sigma"], n)
    step = speed * np.minimum(dt, g["move_cap_s"])
    heading = rng.uniform(0.0, 2.0 * np.pi, n)
    ex = step * np.cos(heading) / lon_scale
    ey = step * np.sin(heading) / lat_scale
    ex[first] = 0.0
    ey[first] = 0.0
    ak = a ** j.astype(np.float64)
    sd_ratio = 1.0 / np.sqrt(1.0 - a * a)  # stationary spread / step spread
    e_sd = np.sqrt(np.mean(step * step) / 2.0)
    ax = cx + rng.normal(0.0, g["anchor_sd_deg"][0], n_taxi)
    ay = cy + rng.normal(0.0, g["anchor_sd_deg"][1], n_taxi)
    sx = rng.normal(0.0, e_sd * sd_ratio / lon_scale, n_taxi)
    sy = rng.normal(0.0, e_sd * sd_ratio / lat_scale, n_taxi)
    lon = ax[taxi] + ak * (sx[taxi] + _segment_cumsum(ex / ak, starts, taxi))
    lat = ay[taxi] + ak * (sy[taxi] + _segment_cumsum(ey / ak, starts, taxi))
    lon = fold(lon, x0, x1)
    lat = fold(lat, y0, y1)

    ids = np.array([str(i + 1) for i in range(n_taxi)], dtype=object)
    return Dataset(lon=lon, lat=lat, t=t, attrs={"taxiId": ids[taxi]},
                   fids=ordinal_ids(n, 0, 8))
