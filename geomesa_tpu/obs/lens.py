"""query-lens: the shared latency histogram ring, its Prometheus
exposition, and the live regression sentinel.

- :class:`HistogramRing` — per-key series, each a bounded ring of
  TIME-BUCKETED latency histograms (fixed log-scale ``le`` bin edges,
  10 s buckets, 1 h retained by default — the same bucketed-deque shape
  as the SLO engine's burn-rate counters). Each bucket also accumulates
  rows/dispatches and keeps up to ``EXEMPLARS_PER_BUCKET`` *trace
  exemplars*: the (latency, trace_id) pairs of the bucket's slowest
  traced queries — so the tail (p99+) of every bucket is one lookup away
  from its stitched federated span tree (``trace.recent()`` → flight
  dumps). Two subclasses: the plan table
  (:class:`geomesa_tpu.obs.plantable.PlanTable`, one record per (type,
  plan signature), served at ``GET /api/obs/lens`` and ``geomesa-tpu obs
  lens``) and the stream delivery lens (:mod:`geomesa_tpu.obs.streamlens`).
- :func:`histogram_lines` — TRUE Prometheus histogram families —
  ``geomesa_lens_latency_ms_bucket`` with cumulative ``le`` labels plus
  ``_sum``/``_count`` under ``# TYPE ... histogram`` (the summary-style
  quantile emission in :mod:`obs.export` cannot be aggregated across
  instances; these can).
- :class:`RegressionSentinel` — a background comparator (the
  InvariantSweeper worker pattern, :mod:`obs.audit`) testing each plan
  signature's live window against a rolling reference window and
  committed BENCH baselines. Sustained p50/p99 regression raises an
  ``A_REGRESSION`` flight anomaly (rate-limited dump machinery rides for
  free) and a ``geomesa_lens_regression`` gauge.

No jax anywhere (``GEOMESA_TPU_NO_JAX=1`` safe).

Locking: a ring owns ONE leaf lock for its series table + buckets
(metrics tier, docs/concurrency.md) — quantile math and exposition copy
under the lock, format outside it. The sentinel's state is its own leaf.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from geomesa_tpu.analysis.contracts import shadow_plane

__all__ = [
    "HistogramRing", "RegressionSentinel", "BUCKET_EDGES_MS",
    "histogram_lines", "sentinel", "install_sentinel",
]

# fixed log-scale latency bin edges (ms). Fixed — not adaptive — so bucket
# histograms merge across time and across instances by plain addition,
# which is what makes the Prometheus histogram family and the sentinel's
# window quantiles possible. 0.25 ms..10 s covers a cached-jit dispatch
# through a pathological federated fan-out.
BUCKET_EDGES_MS: tuple = (
    0.25, 0.5, 1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0,
)
_N_BINS = len(BUCKET_EDGES_MS) + 1  # + the +Inf overflow bin

_BUCKET_S = 10.0  # time-bucket width (matches the SLO engine's counters)
_RING = 360  # buckets retained per series (1 h at 10 s)
_MAX_SERIES = 256  # default series cardinality valve
EXEMPLARS_PER_BUCKET = 4  # slowest traced queries kept per bucket


class _LensBucket:
    """One time bucket of one series: a latency histogram plus rollups and
    the bucket's slowest traced exemplars. Mutated only under the owning
    ring's lock."""

    __slots__ = ("start", "bins", "count", "sum_ms", "max_ms", "rows",
                 "dispatches", "exemplars")

    def __init__(self, start: float):
        self.start = start
        self.bins = [0] * _N_BINS
        self.count = 0
        self.sum_ms = 0.0
        self.max_ms = 0.0
        self.rows = 0
        self.dispatches = 0
        # [latency_ms, trace_id, ts] of the bucket's slowest traced
        # queries — replace-min keeps the tail (the p99+ sample IS the
        # bucket max), bounded at EXEMPLARS_PER_BUCKET
        self.exemplars: list = []


class _Series:
    __slots__ = ("buckets",)

    def __init__(self, ring: int = _RING):
        self.buckets: deque = deque(maxlen=ring)


def _quantile(bins: list, count: int, q: float) -> float:
    """Quantile estimate from merged histogram bins: find the bin holding
    the q-th observation, interpolate linearly inside its edge span (the
    overflow bin reports its lower edge — no upper bound to reach for)."""
    if count <= 0:
        return 0.0
    rank = q * count
    cum = 0
    for i, c in enumerate(bins):
        cum += c
        if cum >= rank and c:
            lo = BUCKET_EDGES_MS[i - 1] if i > 0 else 0.0
            if i >= len(BUCKET_EDGES_MS):
                return BUCKET_EDGES_MS[-1]
            hi = BUCKET_EDGES_MS[i]
            frac = (rank - (cum - c)) / c
            return lo + (hi - lo) * frac
    return BUCKET_EDGES_MS[-1]


def _esc(v: str) -> str:
    # text-exposition label escaping (signatures carry ':'s, types can
    # carry arbitrary user strings)
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_le(edge: float) -> str:
    # prometheus convention: integral edges render without the trailing
    # .0 ("le" values must parse as floats either way)
    return str(int(edge)) if float(edge).is_integer() else str(edge)


class HistogramRing:
    """The shared histogram-ring base: the per-key series table, the
    cardinality valve, the time-bucket ring append, the exemplar
    replace-min, and the merged-window histogram math.

    Both rings — the plan table (:mod:`geomesa_tpu.obs.plantable`) and
    the stream delivery lens (:mod:`geomesa_tpu.obs.streamlens`) — are
    subclasses, so the ring / exemplar semantics cannot drift between the
    two planes. Subclasses pick their bucket and series classes via
    ``_bucket_cls`` / ``_series_cls`` (extra ``__slots__`` on top of
    :class:`_LensBucket` / :class:`_Series`) and supply the valve,
    :meth:`_evict_locked` (the plan table drops the record written
    longest ago; the stream lens drops the cheapest series and folds it
    into an ``other`` rollup).

    Locking: ONE leaf lock for the series table + buckets (metrics tier,
    docs/concurrency.md) — every ``*_locked`` helper assumes it is held;
    nothing is called while holding it."""

    _bucket_cls = _LensBucket
    _series_cls = _Series

    def __init__(self, bucket_s: float = _BUCKET_S, ring: int = _RING,
                 max_series: int = _MAX_SERIES, clock=time.time):
        self.bucket_s = float(bucket_s)
        self._ring = ring
        self._max_series = max_series
        self._clock = clock
        self._lock = threading.Lock()  # leaf: series table + buckets
        self._series: dict[tuple, object] = {}
        self.observe_count = 0

    # -- shared machinery (caller holds self._lock) ---------------------------
    def _series_locked(self, key: tuple):
        """The key's series, created as needed (the subclass's valve,
        ``_evict_locked``, runs on creation)."""
        series = self._series.get(key)
        if series is None:
            if len(self._series) >= self._max_series:
                self._evict_locked()
            series = self._series[key] = self._series_cls(self._ring)
        return series

    def _bucket_locked(self, series, now: float):
        """``series``' bucket covering ``now``, appended as needed."""
        start = now - (now % self.bucket_s)
        if series.buckets and series.buckets[-1].start == start:
            return series.buckets[-1]
        b = self._bucket_cls(start)
        series.buckets.append(b)  # deque(maxlen) prunes the ring
        return b

    @staticmethod
    def _exemplar_locked(b, latency_ms: float, trace_id: str,
                         now: float) -> None:
        """Replace-min exemplar keep: the bucket retains its slowest
        ``EXEMPLARS_PER_BUCKET`` traced observations."""
        ex = b.exemplars
        if len(ex) < EXEMPLARS_PER_BUCKET:
            ex.append([latency_ms, trace_id, now])
        else:
            mi = min(range(len(ex)), key=lambda j: ex[j][0])
            if latency_ms > ex[mi][0]:
                ex[mi] = [latency_ms, trace_id, now]

    def _window_locked(self, key: tuple, start_s: float, end_s: float,
                       fold=None) -> tuple:
        """Merge buckets intersecting ``[start_s, end_s)`` →
        ``(bins, count, sum_ms, max_ms)``; ``fold(bucket)`` runs per
        merged bucket so subclasses accumulate their extra counters."""
        bins = [0] * _N_BINS
        count = 0
        sum_ms = 0.0
        max_ms = 0.0
        series = self._series.get(key)
        if series is not None:
            for b in series.buckets:
                if b.start + self.bucket_s > start_s and b.start < end_s:
                    for i, c in enumerate(b.bins):
                        bins[i] += c
                    count += b.count
                    sum_ms += b.sum_ms
                    max_ms = max(max_ms, b.max_ms)
                    if fold is not None:
                        fold(b)
        return bins, count, sum_ms, max_ms

    def _exemplar_rows_locked(self, key: tuple) -> list:
        series = self._series.get(key)
        rows = []
        if series is not None:
            for b in series.buckets:
                for ms, tid, ts in b.exemplars:
                    rows.append({"latency_ms": round(ms, 3),
                                 "trace_id": tid, "ts": ts,
                                 "bucket": b.start})
        return rows

    def series_keys(self) -> list:
        with self._lock:
            return list(self._series)


def histogram_lines(rows, prefix: str = "geomesa") -> list[str]:
    """TRUE histogram families over merged rings: per ``(type, signature,
    bins, count, sum_ms, dispatches)`` row, cumulative ``_bucket`` counts
    with ``le`` labels (``+Inf`` bucket equals ``_count``), plus
    ``_sum``/``_count`` — and a companion ``_dispatches_total`` counter.
    Empty when there are no rows."""
    if not rows:
        return []
    name = f"{prefix}_lens_latency_ms"
    hist = [f"# TYPE {name} histogram"]
    disp = [f"# TYPE {prefix}_lens_dispatches_total counter"]
    for t, sig, bins, count, sum_ms, dispatches in rows:
        labels = f'type="{_esc(t)}",signature="{_esc(sig)}"'
        cum = 0
        for i, edge in enumerate(BUCKET_EDGES_MS):
            cum += bins[i]
            hist.append(
                f'{name}_bucket{{{labels},le="{_fmt_le(edge)}"}} {cum}')
        hist.append(f'{name}_bucket{{{labels},le="+Inf"}} {count}')
        hist.append(f"{name}_sum{{{labels}}} {sum_ms:.6g}")
        hist.append(f"{name}_count{{{labels}}} {count}")
        disp.append(
            f"{prefix}_lens_dispatches_total{{{labels}}} {dispatches}")
    return hist + disp


# -- regression sentinel ------------------------------------------------------

@shadow_plane
class RegressionSentinel:
    """Background live-vs-reference latency comparator (the
    InvariantSweeper worker shape: ``start()``/``close()`` around a
    daemon thread, ``evaluate_once()`` for tests and the CLI).

    Per evaluation, for every plan signature of the plan table
    (:mod:`geomesa_tpu.obs.plantable`) with enough live traffic:

    - live window = the trailing ``live_window_s``;
    - reference = the ``ref_window_s`` immediately before it (rolling);
    - baseline = a committed per-signature p50 (``load_baselines`` — the
      BENCH rounds' per-config medians).

    Regression = live p50 or p99 above ``factor`` × reference (or
    ``factor`` × baseline). ``sustain`` consecutive regressed evaluations
    raise ONE ``A_REGRESSION`` flight anomaly per episode (the recorder's
    dump rate-limit rides along) and latch the
    ``geomesa_lens_regression`` gauge until the series recovers.

    Evaluations run in audit shadow: sentinel reads must never train the
    cost table, bill a tenant, or re-enter the plan table."""

    def __init__(self, table=None,
                 interval_s: float = 30.0, live_window_s: float = 60.0,
                 ref_window_s: float = 600.0, factor: float = 1.5,
                 min_live: int = 16, min_ref: int = 16, sustain: int = 1,
                 clock=time.time):
        self._table = table
        self.interval_s = interval_s
        self.live_window_s = live_window_s
        self.ref_window_s = ref_window_s
        self.factor = factor
        self.min_live = min_live
        self.min_ref = min_ref
        self.sustain = max(1, sustain)
        self._clock = clock
        self._lock = threading.Lock()  # leaf: streaks + alarms + baselines
        self._baselines: dict[tuple[str, str], float] = {}
        self._streaks: dict[tuple[str, str], int] = {}
        self._alarms: dict[tuple[str, str], dict] = {}
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.eval_count = 0
        self.regressions_total = 0

    @property
    def table(self):
        """The plan table judged (the process table unless injected)."""
        if self._table is not None:
            return self._table
        from geomesa_tpu.obs import plantable

        return plantable.table()

    def load_baselines(self, baselines: dict) -> int:
        """Install committed reference medians: ``{"type:signature":
        p50_ms}`` (or ``{"entries": [{"type", "signature", "p50_ms"}]}``,
        the BENCH sidecar shape). Returns the count installed."""
        rows: dict[tuple[str, str], float] = {}
        if "entries" in baselines and isinstance(baselines["entries"], list):
            for e in baselines["entries"]:
                rows[(str(e["type"]), str(e["signature"]))] = float(e["p50_ms"])
        else:
            for k, v in baselines.items():
                t, _, sig = str(k).partition(":")
                rows[(t, sig)] = float(v)
        with self._lock:
            self._baselines.update(rows)
        return len(rows)

    # -- evaluation -----------------------------------------------------------
    def evaluate_once(self, now: float | None = None) -> list[dict]:
        """One comparator pass; returns the alarms RAISED this pass (an
        already-latched alarm does not re-raise). Safe under any caller —
        wraps itself in audit shadow."""
        from geomesa_tpu.obs import audit as _audit

        with _audit.shadow():
            return self._evaluate(self._clock() if now is None else now)

    def _evaluate(self, now: float) -> list[dict]:
        tbl = self.table
        raised = []
        live_lo = now - self.live_window_s
        ref_lo = live_lo - self.ref_window_s
        for t, sig in tbl.series_keys():
            live = tbl.window_stats(t, sig, live_lo, now + 1.0)
            if live["count"] < self.min_live:
                continue  # not enough live traffic to judge — hold state
            ref = tbl.window_stats(t, sig, ref_lo, live_lo)
            with self._lock:
                base = self._baselines.get((t, sig))
            causes = []
            if ref["count"] >= self.min_ref:
                if live["p50_ms"] > self.factor * ref["p50_ms"] > 0:
                    causes.append(
                        ("p50_vs_ref", live["p50_ms"], ref["p50_ms"]))
                if live["p99_ms"] > self.factor * ref["p99_ms"] > 0:
                    causes.append(
                        ("p99_vs_ref", live["p99_ms"], ref["p99_ms"]))
            if base is not None and live["p50_ms"] > self.factor * base > 0:
                causes.append(("p50_vs_baseline", live["p50_ms"], base))
            key = (t, sig)
            if not causes:
                with self._lock:
                    self._streaks.pop(key, None)
                    self._alarms.pop(key, None)
                continue
            with self._lock:
                streak = self._streaks.get(key, 0) + 1
                self._streaks[key] = streak
                already = key in self._alarms
                fire = streak >= self.sustain and not already
                if fire:
                    kind, live_v, ref_v = causes[0]
                    alarm = {
                        "type": t, "signature": sig, "cause": kind,
                        "live_ms": round(live_v, 3),
                        "ref_ms": round(ref_v, 3),
                        "factor": round(live_v / ref_v, 3) if ref_v else 0.0,
                        "live_count": live["count"], "ts": now,
                    }
                    self._alarms[key] = alarm
                    self.regressions_total += 1
            if fire:
                raised.append(alarm)
                self._raise_anomaly(alarm)
        with self._lock:
            self.eval_count += 1
        return raised

    def _raise_anomaly(self, alarm: dict) -> None:
        # the alert path: one A_REGRESSION flight record per episode (the
        # recorder's dump throttle bounds file output under a storm).
        # flight.record is the operator surface, not a feedback sink — an
        # alert raised from shadow is the whole point.
        from geomesa_tpu.obs import flight as _flight

        _flight.record(
            "lens.sentinel", alarm["type"], source="sentinel",
            plan=(f"{alarm['cause']}: live {alarm['live_ms']:.3g} ms vs "
                  f"ref {alarm['ref_ms']:.3g} ms "
                  f"({alarm['factor']:.2f}x, n={alarm['live_count']})"),
            latency_ms=alarm["live_ms"],
            plan_signature=alarm["signature"],
            anomalies=(_flight.A_REGRESSION,),
        )

    # -- worker ---------------------------------------------------------------
    def start(self) -> None:
        with self._lock:
            if self._thread is not None:
                return
            self._thread = threading.Thread(
                target=self._run, name="geomesa-lens-sentinel", daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=10.0)

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.evaluate_once()
            except Exception:  # pragma: no cover — the sentinel must not die
                pass

    # -- read surfaces --------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            return {
                "alarms": list(self._alarms.values()),
                "eval_count": self.eval_count,
                "regressions_total": self.regressions_total,
                "baselines": len(self._baselines),
                "factor": self.factor,
                "live_window_s": self.live_window_s,
                "ref_window_s": self.ref_window_s,
                "running": self._thread is not None,
            }

    def prometheus_lines(self, prefix: str = "geomesa") -> list[str]:
        with self._lock:
            alarms = list(self._alarms.values())
            total = self.regressions_total
        out = [f"# TYPE {prefix}_lens_regression gauge"]
        for a in alarms:
            out.append(
                f'{prefix}_lens_regression{{type="{_esc(a["type"])}",'
                f'signature="{_esc(a["signature"])}",'
                f'cause="{_esc(a["cause"])}"}} 1')
        out.append(f"# TYPE {prefix}_lens_regressions_total counter")
        out.append(f"{prefix}_lens_regressions_total {total}")
        return out

    def prometheus_text(self, prefix: str = "geomesa") -> str:
        return "\n".join(self.prometheus_lines(prefix)) + "\n"


# process-wide singleton (tests swap with install_sentinel())
_sentinel = RegressionSentinel()


def sentinel() -> RegressionSentinel:
    """The process-wide regression sentinel (not started by default;
    servers opt in via ``start()``)."""
    return _sentinel


def install_sentinel(s: RegressionSentinel) -> RegressionSentinel:
    """Swap the process sentinel (tests); returns the previous one —
    callers own closing the outgoing worker."""
    global _sentinel
    prev, _sentinel = _sentinel, s
    return prev
