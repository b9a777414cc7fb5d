"""The one traffic generator. A mix is a data file, ``traffic/<mix>.json``.

Keys of a mix:

- ``call``: the client call, a module ``calls/<call>.py``.
- ``batch``: CQLs per request (a request is one client call).
- ``deck``: the stratification cycle. Every ``deck`` CQLs draw their box
  widths, heights, window lengths, window starts and centres from the
  same fixed quantiles ``(k + 0.5) / deck``, each in a seeded order: every
  seed sends the same set of sizes, in another order.
- ``centres``: ``"cities"`` (the configuration's ``generator_params.cities``
  in turn) or ``"data"`` (a row drawn from the data; the window then
  holds that row's instant).
- ``width_deg``, ``height_deg``: [min, max] of the box's sides, uniform.
- ``window_ms``: [min, max] of the window's length, uniform;
  ``window_whole_days``: round the length down to whole days.
- ``warm_window_share``: the warm-up replays the window's own requests,
  each CQL spelled anew (in parentheses: the same filter under another
  plan-cache key), until its requests that compiled nothing add up to
  this share of the window's seconds, so every shape the window uses is
  compiled first (0: only the harness's minimum of requests).
- ``check_requests``: compare a sample of this many requests, drawn from
  the seed, instead of every request of the window.

Each CQL is ``BBOX(<geom>, x1, y1, x2, y2) AND <dtg> DURING lo/hi``, boxes
clipped to the world. No CQL repeats within a run.
"""

from __future__ import annotations

import datetime
import json
from pathlib import Path

import numpy as np

from benchmark.datagen import stratified

HERE = Path(__file__).resolve().parent
_DAY_MS = 86_400_000
# independent random streams of one seed
STREAM_WINDOW, STREAM_SAMPLE = 7, 5


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def iso(ms: int) -> str:
    dt = datetime.datetime.fromtimestamp(ms // 1000, datetime.timezone.utc)
    return dt.strftime("%Y-%m-%dT%H:%M:%S.") + f"{int(ms) % 1000:03d}Z"


def spec_fields(spec: str) -> tuple[str, str]:
    """(geometry field, date field) of a GeoMesa spec string."""
    geom = dtg = None
    for part in spec.split(";")[0].split(","):
        name, typ = part.split(":")[:2]
        if name.startswith("*"):
            geom = name[1:]
        elif typ == "Date" and dtg is None:
            dtg = name
    return geom, dtg


class Traffic:
    """Requests of one mix over one configuration's data, from a seed
    (``stream`` keeps its draws apart from the seed's other uses)."""

    def __init__(self, mix: dict, cfg: dict, data, seed: int, stream: int):
        self.mix, self.cfg, self.data = mix, cfg, data
        self.rng = np.random.default_rng([seed, stream])
        self.geom, self.dtg = spec_fields(cfg["spec"])
        self.t0 = int(cfg["t0_ms"])
        self.span = int(cfg["span_days"]) * _DAY_MS
        self.batch = int(mix["batch"])
        self._pending: list = []
        self._seen: set = set()
        self._made: list = []

    def _deck(self) -> list:
        m, rng, n = self.mix, self.rng, int(self.mix["deck"])

        def between(lo_hi):
            lo, hi = lo_hi
            return lo + stratified(rng, n) * (hi - lo)

        w, h = between(m["width_deg"]), between(m["height_deg"])
        length = between(m["window_ms"])
        if m.get("window_whole_days"):
            length = np.floor(length / _DAY_MS) * _DAY_MS
        length = length.astype(np.int64)
        place = stratified(rng, n)
        if m["centres"] == "cities":
            cities = np.asarray(self.cfg["generator_params"]["cities"], float)
            pick = rng.permutation(np.arange(n) % len(cities))
            cx, cy = cities[pick, 0], cities[pick, 1]
            lo = self.t0 + (place * (self.span - length.max())).astype(np.int64)
        elif m["centres"] == "data":
            rows = rng.integers(0, len(self.data), n)
            cx, cy = self.data.lon[rows], self.data.lat[rows]
            lo = self.data.t[rows] - (place * length).astype(np.int64)
        else:
            raise ValueError(f"unknown centres {m['centres']!r}")
        out = []
        for k in range(n):
            box = (max(-180.0, float(cx[k] - w[k] / 2)),
                   max(-90.0, float(cy[k] - h[k] / 2)),
                   min(180.0, float(cx[k] + w[k] / 2)),
                   min(90.0, float(cy[k] + h[k] / 2)))
            window = (int(lo[k]), int(lo[k] + length[k]))
            out.append((box, window))
        return out

    def cql(self, box, window) -> str:
        x1, y1, x2, y2 = box
        return (f"BBOX({self.geom}, {x1!r}, {y1!r}, {x2!r}, {y2!r}) AND "
                f"{self.dtg} DURING {iso(window[0])}/{iso(window[1])}")

    def request(self, k: int) -> list:
        """The ``k``-th request of this stream (made on first use)."""
        while len(self._made) <= k:
            self._made.append(self._next())
        return self._made[k]

    def _next(self) -> list:
        """One request: ``batch`` (box, window, cql) triples, every CQL
        new in this run."""
        out = []
        while len(out) < self.batch:
            if not self._pending:
                self._pending = self._deck()
            box, window = self._pending.pop()
            q = self.cql(box, window)
            if q in self._seen:
                continue
            self._seen.add(q)
            out.append((box, window, q))
        return out
