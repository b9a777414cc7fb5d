"""The plan table: one bounded record per (feature type, plan signature).

Every answered query ends in ``DataStore._audit``, which writes its
per-signature state here in one call (:meth:`PlanTable.record_query`):
one lock, one key lookup. A record holds

- the **cost profile** (count and the ``wall_ms`` / ``device_ms`` /
  ``rows`` / ``bytes_scanned`` reservoirs): the adaptive planner's
  training signal, ``GET /api/obs/costs``, the ``costs.json`` sidecar.
  The dispatch layers' route signatures (``sel:*``, ``gagg:*``,
  ``join:*``, corridor, interlink) write it through ``observe_cost``;
- the **calibration** sums of the profile's own p50 against each
  planned query's actual time;
- the **latency ring** (the :class:`~geomesa_tpu.obs.lens.HistogramRing`
  series it extends), with trace exemplars: ``GET /api/obs/lens``, the
  Prometheus histograms, the regression sentinel;
- the **roundtrip rollup** of each query's
  :class:`~geomesa_tpu.obs.ledger.QueryLedger`: the fusion report and
  the reconcile export.

The planner's per-(type, decision) consult ticks sit beside the records.
One valve bounds the table: a new record past ``max_records`` drops the
record whose last write of any kind is oldest (route signatures never get
a latency bucket, so the valve must not key on the ring). One ``forget``
purges a deleted or renamed type. One leaf lock guards it all
(docs/concurrency.md); nothing is called while it is held.
"""

from __future__ import annotations

import math
import random
import time
from bisect import bisect_left

from geomesa_tpu.analysis.contracts import cache_surface, feedback_sink
from geomesa_tpu.obs.ledger import EXPORT_KIND, EXPORT_SCHEMA_VERSION
from geomesa_tpu.obs.lens import (BUCKET_EDGES_MS, _BUCKET_S, _N_BINS, _RING,
                                  HistogramRing, _quantile, _Series,
                                  histogram_lines)

__all__ = ["PlanTable", "install", "table"]

MAX_RECORDS = 512


class _Quantiles:
    """Bounded reservoir (algorithm R) + count/sum — the same shape as
    :class:`geomesa_tpu.utils.metrics.Histogram` without the import (this
    module stays dependency-free for ``GEOMESA_TPU_NO_JAX`` processes).
    NOT thread-safe on its own: the owning :class:`PlanTable` lock guards
    every update/read."""

    __slots__ = ("count", "total", "_res", "_rng", "_qcache")
    SIZE = 256
    # above this many observations, quantiles serve from a cache refreshed
    # every CACHE_DELTA updates — predict() rides the per-query audit hot
    # path (the adaptive planner consults it every dispatch) and a fresh
    # reservoir sort per call would erode the <2% overhead bound. Below
    # the threshold quantiles stay exact (small-sample tests pin values).
    CACHE_MIN = 64
    CACHE_DELTA = 16

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self._res: list[float] = []
        self._rng = random.Random(0x5DEECE66D)
        self._qcache: dict[float, tuple[int, float]] = {}

    def to_state(self) -> dict:
        """JSON-able state (cost-profile persistence — the reservoir IS
        the learned distribution; the RNG restarts, which only changes
        which FUTURE samples replace which slots)."""
        return {"count": self.count, "total": self.total,
                "res": [round(v, 4) for v in self._res]}

    def load_state(self, state: dict) -> None:
        self.count = int(state.get("count", 0))
        self.total = float(state.get("total", 0.0))
        self._res = [float(v) for v in state.get("res", [])][:self.SIZE]
        self._qcache = {}

    def update(self, v: float) -> None:
        self.count += 1
        self.total += v
        if len(self._res) < self.SIZE:
            self._res.append(v)
        else:
            j = self._rng.randrange(self.count)
            if j < self.SIZE:
                self._res[j] = v

    def quantile(self, q: float) -> float:
        if not self._res:
            return 0.0
        hit = self._qcache.get(q)
        if (hit is not None and self.count > self.CACHE_MIN
                and self.count - hit[0] < self.CACHE_DELTA):
            return hit[1]
        s = sorted(self._res)
        pos = q * (len(s) - 1)
        lo = int(pos)
        hi = min(lo + 1, len(s) - 1)
        frac = pos - lo
        v = s[lo] * (1.0 - frac) + s[hi] * frac
        self._qcache[q] = (self.count, v)
        return v


class _Record(_Series):
    """Everything kept for one (type, plan signature). ``last`` stamps
    the newest write of any kind (the valve's key); ``cost_seq`` /
    ``cal_seq`` the newest cost observation / calibration, the order the
    cost and calibration documents list records in."""

    last = cost_seq = cal_seq = 0
    # calibration
    cal_count = 0
    abs_rel_err_sum = signed_rel_err_sum = last_predicted = last_actual = 0.0
    # roundtrip rollup
    queries = dispatches = compiles = syncs = h2d_bytes = d2h_bytes = 0
    dispatch_ms = sync_ms = host_gap_ms = wall_ms_sum = 0.0

    def __init__(self, ring: int = _RING):
        super().__init__(ring)
        self._reset_cost()

    def _reset_cost(self) -> None:
        self.count = 0
        self.profiled_count = 0
        self.wall_ms = _Quantiles()
        self.device_ms = _Quantiles()
        self.rows = _Quantiles()
        self.bytes_scanned = _Quantiles()

    def prediction(self) -> dict | None:
        """The cost profile's p50 read (None before the first cost
        observation) — what ``explain`` shows as *predicted* and the
        adaptive planner ranks strategies by."""
        if not self.count:
            return None
        return {
            "wall_ms_p50": round(self.wall_ms.quantile(0.5), 3),
            "wall_ms_p95": round(self.wall_ms.quantile(0.95), 3),
            "device_ms_p50": (
                round(self.device_ms.quantile(0.5), 3)
                if self.profiled_count else None
            ),
            "observations": self.count,
        }


def _finite(v: float) -> bool:
    # a NaN wall-ms (a clock anomaly) must never poison a reservoir
    return isinstance(v, (int, float)) and math.isfinite(v)


@cache_surface(name="plan-table", keyed_by="type_name", purge=("forget",))
class PlanTable(HistogramRing):
    """The bounded per-(type, plan-signature) record table."""

    _series_cls = _Record

    def __init__(self, max_records: int = MAX_RECORDS,
                 bucket_s: float = _BUCKET_S, ring: int = _RING,
                 clock=time.time):
        super().__init__(bucket_s=bucket_s, ring=ring,
                         max_series=max_records, clock=clock)
        self._ticks: dict[tuple, int] = {}
        self._seq = 0

    # -- shared machinery (caller holds self._lock) ---------------------------
    def _evict_locked(self) -> None:
        """The valve: drop the record whose last write is oldest."""
        del self._series[min(self._series,
                             key=lambda k: self._series[k].last)]

    def _record_locked(self, key: tuple) -> _Record:
        rec = self._series_locked(key)
        self._seq += 1
        rec.last = self._seq
        return rec

    def _observe_cost_locked(self, rec: _Record, wall_ms: float,
                             device_ms, rows: int,
                             bytes_scanned: int) -> None:
        rec.count += 1
        rec.wall_ms.update(float(wall_ms))
        rec.rows.update(float(rows))
        if bytes_scanned:
            rec.bytes_scanned.update(float(bytes_scanned))
        if device_ms is not None:
            rec.profiled_count += 1
            rec.device_ms.update(float(device_ms))
        rec.cost_seq = self._seq

    # -- the write surface ----------------------------------------------------
    @feedback_sink
    def record_query(self, type_name: str, signature: str, wall_ms: float,
                     *, rows: int = 0, planned: bool = False,
                     device_ms: float | None = None, bytes_scanned: int = 0,
                     trace_id: str = "", ledger=None,
                     now: float | None = None) -> dict | None:
        """One answered query: its latency point (and exemplar when
        traced), the charge of its ``ledger`` (a coalesced batch charges
        its shared ledger once per member), and, when ``planned`` (fully
        planned and individually timed), its cost observation and the
        calibration of the p50 read just before it. Returns that
        prediction (None when not planned or never observed)."""
        if now is None:
            now = self._clock()
        snap = ledger.snapshot() if ledger is not None else None
        bin_i = bisect_left(BUCKET_EDGES_MS, wall_ms)
        with self._lock:
            rec = self._record_locked((type_name, signature))
            predicted = None
            if planned:
                predicted = rec.prediction()
                if _finite(wall_ms):
                    self._observe_cost_locked(rec, wall_ms, device_ms, rows,
                                              bytes_scanned)
                # a p50 of fewer than 4 observations is no prediction
                if predicted is not None and predicted["observations"] >= 4:
                    p = predicted["wall_ms_p50"]
                    err = (p - wall_ms) / max(wall_ms, 1e-6)
                    rec.cal_count += 1
                    rec.abs_rel_err_sum += abs(err)
                    rec.signed_rel_err_sum += err
                    rec.last_predicted = float(p)
                    rec.last_actual = float(wall_ms)
                    rec.cal_seq = self._seq
            b = self._bucket_locked(rec, now)
            b.bins[bin_i] += 1
            b.count += 1
            b.sum_ms += wall_ms
            if wall_ms > b.max_ms:
                b.max_ms = wall_ms
            b.rows += rows
            if trace_id:
                self._exemplar_locked(b, wall_ms, trace_id, now)
            self.observe_count += 1
            if snap is not None:
                b.dispatches += snap["dispatches"]
                rec.queries += 1
                rec.dispatches += snap["dispatches"]
                rec.compiles += snap["compiles"]
                rec.dispatch_ms += snap["dispatch_ms"]
                rec.syncs += snap["syncs"]
                rec.sync_ms += snap["sync_ms"]
                rec.host_gap_ms += snap["host_gap_ms"]
                rec.wall_ms_sum += max(wall_ms, 0.0)
                rec.h2d_bytes += snap["h2d_bytes"]
                rec.d2h_bytes += snap["d2h_bytes"]
        return predicted

    @feedback_sink
    def observe_cost(self, type_name: str, signature: str, *,
                     wall_ms: float, device_ms: float | None = None,
                     rows: int = 0, bytes_scanned: int = 0) -> None:
        """A cost observation outside the audit: the dispatch layers'
        route signatures (``sel:*``, ``gagg:*``, ``join:*``, corridor and
        interlink rows) that train the planner's route decisions."""
        if not _finite(wall_ms):
            return
        with self._lock:
            self._observe_cost_locked(
                self._record_locked((type_name, signature)), wall_ms,
                device_ms, rows, bytes_scanned)

    def tick(self, type_name: str, name: str) -> int:
        """Monotonic per-(type, name) consult counter. Routing policies
        (``planner.choose_agg_path``) schedule periodic probes of the
        losing route off this — NOT off observation counts, which the
        winning route freezes by starving the loser of observations."""
        key = (type_name, name)
        with self._lock:
            n = self._ticks.get(key, 0) + 1
            self._ticks[key] = n
        return n

    def forget(self, type_name: str) -> None:
        """Drop every record and consult tick of one type. A deleted or
        renamed schema must not hand its cost profile, probe phase,
        latency history or fusion ranking to an unrelated future type of
        the same name (nor may the sentinel compare across them)."""
        with self._lock:
            for k in [k for k in self._series if k[0] == type_name]:
                del self._series[k]
            for k in [k for k in self._ticks if k[0] == type_name]:
                del self._ticks[k]

    # -- cost profile ---------------------------------------------------------
    def _trained_locked(self) -> list:
        return sorted(((k, r) for k, r in self._series.items() if r.count),
                      key=lambda kr: kr[1].cost_seq)

    def predict(self, type_name: str, signature: str) -> dict | None:
        """The current p50 cost of one plan shape (None when the shape
        has never been observed)."""
        with self._lock:
            rec = self._series.get((type_name, signature))
            return rec.prediction() if rec is not None else None

    def predict_prefix(self, type_name: str, prefix: str) -> dict | None:
        """Aggregated profile over every signature of one type starting
        with ``prefix`` — how the adaptive planner reads STRATEGY-level
        costs (audit signatures are ``index:ivN:agg``; the strategy
        decision keys by ``index:`` alone). Observation-weighted means of
        the per-signature p50/p95 (a strategy's profile is dominated by
        the shapes it actually serves); None when nothing matches."""
        with self._lock:
            matched = sorted(
                (r for (t, sig), r in self._series.items()
                 if r.count and t == type_name and sig.startswith(prefix)),
                key=lambda r: r.cost_seq)
            if not matched:
                return None
            n = sum(r.count for r in matched)
            p50 = sum(r.wall_ms.quantile(0.5) * r.count for r in matched) / n
            p95 = sum(r.wall_ms.quantile(0.95) * r.count for r in matched) / n
            return {
                "wall_ms_p50": round(p50, 3),
                "wall_ms_p95": round(p95, 3),
                "device_ms_p50": None,
                "observations": n,
                "signatures": len(matched),
            }

    def snapshot(self, limit: int = 256) -> dict:
        """The cost document (``GET /api/obs/costs``): the ``limit``
        most recently trained signatures."""
        with self._lock:
            rows = [{
                "type": t,
                "signature": sig,
                "count": r.count,
                "profiled": r.profiled_count,
                "wall_ms_p50": round(r.wall_ms.quantile(0.5), 3),
                "wall_ms_p95": round(r.wall_ms.quantile(0.95), 3),
                "device_ms_p50": round(r.device_ms.quantile(0.5), 3),
                "device_ms_p95": round(r.device_ms.quantile(0.95), 3),
                "rows_p50": round(r.rows.quantile(0.5), 1),
                "bytes_scanned_p50": round(r.bytes_scanned.quantile(0.5), 0),
            } for (t, sig), r in self._trained_locked()[-limit:]]
        rows.sort(key=lambda r: (r["type"], r["signature"]))
        return {"entries": rows, "entry_count": len(rows)}

    # -- persistence (docs/observability.md § Cost-model persistence) ---------
    def to_state(self) -> dict:
        """The cost profiles as JSON-able data: per-(type, signature)
        reservoirs + counts, plus the consult ticks (probe cadence must
        survive a restart too, or every reopened store re-probes from
        scratch)."""
        with self._lock:
            entries = [{
                "type": t, "signature": sig, "count": r.count,
                "profiled_count": r.profiled_count,
                "wall_ms": r.wall_ms.to_state(),
                "device_ms": r.device_ms.to_state(),
                "rows": r.rows.to_state(),
                "bytes_scanned": r.bytes_scanned.to_state(),
            } for (t, sig), r in self._trained_locked()]
            ticks = [[t, n, v] for (t, n), v in self._ticks.items()]
        return {"entries": entries, "ticks": ticks}

    def load_state(self, state: dict) -> None:
        """Restore a :meth:`to_state` snapshot. Merge by richness: a
        snapshot row only lands when it has MORE observations than the
        live profile — loading at store open must not wipe (or regress)
        profiles another open store already learned past the snapshot;
        the record's other views are never touched."""
        with self._lock:
            for row in state.get("entries", []):
                key = (row["type"], row["signature"])
                count = int(row.get("count", 0))
                live = self._series.get(key)
                if live is not None and live.count >= count:
                    continue
                rec = self._record_locked(key)
                rec._reset_cost()
                rec.count = count
                rec.profiled_count = int(row.get("profiled_count", 0))
                rec.wall_ms.load_state(row.get("wall_ms", {}))
                rec.device_ms.load_state(row.get("device_ms", {}))
                rec.rows.load_state(row.get("rows", {}))
                rec.bytes_scanned.load_state(row.get("bytes_scanned", {}))
                rec.cost_seq = self._seq
            for t, n, v in state.get("ticks", []):
                key = (t, n)
                self._ticks[key] = max(self._ticks.get(key, 0), int(v))

    # -- calibration ----------------------------------------------------------
    def _calibrated_locked(self) -> list:
        return sorted(((k, r) for k, r in self._series.items()
                       if r.cal_count),
                      key=lambda kr: kr[1].cal_seq)

    def calibration_state(self) -> dict:
        """JSON-able calibration state — saved with the cost profiles so
        predicted-vs-actual drift accounting survives restarts alongside
        the p50 rankings it judges."""
        with self._lock:
            entries = [
                {"type": t, "signature": sig, "count": r.cal_count,
                 "abs_rel_err_sum": round(r.abs_rel_err_sum, 6),
                 "signed_rel_err_sum": round(r.signed_rel_err_sum, 6),
                 "last_predicted": round(r.last_predicted, 4),
                 "last_actual": round(r.last_actual, 4)}
                for (t, sig), r in self._calibrated_locked()
            ]
        return {"entries": entries}

    def load_calibration_state(self, state: dict) -> None:
        """Restore :meth:`calibration_state` (the same merge-by-richness
        rule as :meth:`load_state`)."""
        with self._lock:
            for row in state.get("entries", []):
                key = (row["type"], row["signature"])
                count = int(row.get("count", 0))
                live = self._series.get(key)
                if live is not None and live.cal_count >= count:
                    continue
                rec = self._record_locked(key)
                rec.cal_count = count
                rec.abs_rel_err_sum = float(row.get("abs_rel_err_sum", 0.0))
                rec.signed_rel_err_sum = float(
                    row.get("signed_rel_err_sum", 0.0))
                rec.last_predicted = float(row.get("last_predicted", 0.0))
                rec.last_actual = float(row.get("last_actual", 0.0))
                rec.cal_seq = self._seq

    def calibration_report(self) -> dict:
        """The drift surface served with ``GET /api/obs/costs``: per-(type,
        signature) mean absolute relative error (MAPE vs actual), signed
        bias (positive = over-prediction), sample count, and the last
        predicted/actual pair; plus an overall observation-weighted MAPE."""
        with self._lock:
            items = [(k, r.cal_count, r.abs_rel_err_sum,
                      r.signed_rel_err_sum, r.last_predicted, r.last_actual)
                     for k, r in self._calibrated_locked()]
        rows = []
        tot_n = 0
        tot_err = 0.0
        for (t, sig), n, abs_sum, signed_sum, lp, la in items:
            rows.append({
                "type": t,
                "signature": sig,
                "count": n,
                "mean_abs_rel_err": round(abs_sum / n, 4),
                "mean_signed_rel_err": round(signed_sum / n, 4),
                "last_predicted_ms": round(lp, 3),
                "last_actual_ms": round(la, 3),
            })
            tot_n += n
            tot_err += abs_sum
        rows.sort(key=lambda r: (r["type"], r["signature"]))
        return {
            "entries": rows,
            "entry_count": len(rows),
            "overall_mean_abs_rel_err": (
                round(tot_err / tot_n, 4) if tot_n else None
            ),
            "samples": tot_n,
        }

    # -- latency history ------------------------------------------------------
    def series_keys(self) -> list:
        """The keys with latency history (the sentinel's iteration)."""
        with self._lock:
            return [k for k, r in self._series.items() if r.buckets]

    def window_stats(self, type_name: str, signature: str,
                     start_s: float, end_s: float) -> dict:
        """Merged stats over buckets intersecting ``[start_s, end_s)``:
        count / sum / p50 / p95 / p99 / max / rows / dispatches. The
        sentinel's comparison primitive."""
        extra = {"rows": 0, "dispatches": 0}

        def fold(b):
            extra["rows"] += b.rows
            extra["dispatches"] += b.dispatches

        with self._lock:
            bins, count, sum_ms, max_ms = self._window_locked(
                (type_name, signature), start_s, end_s, fold)
        return {
            "count": count,
            "sum_ms": sum_ms,
            "mean_ms": sum_ms / count if count else 0.0,
            "p50_ms": _quantile(bins, count, 0.5),
            "p95_ms": _quantile(bins, count, 0.95),
            "p99_ms": _quantile(bins, count, 0.99),
            "max_ms": max_ms,
            "rows": extra["rows"],
            "dispatches": extra["dispatches"],
        }

    def exemplars(self, type_name: str, signature: str,
                  limit: int = 16) -> list:
        """The series' retained exemplars, slowest first:
        ``{latency_ms, trace_id, ts, bucket}`` — each trace_id resolves
        against ``trace.recent()`` (and flight dumps) to the stitched
        span tree."""
        with self._lock:
            rows = self._exemplar_rows_locked((type_name, signature))
        rows.sort(key=lambda r: -r["latency_ms"])
        return rows[:limit]

    def lens_snapshot(self, limit: int = 50, window_s: float = 300.0,
                      type_name: str | None = None) -> dict:
        """The ``/api/obs/lens`` payload: per-series live-window quantiles,
        the retained bucket series (start/count/mean/max), and the top
        exemplars."""
        now = self._clock()
        keys = [k for k in self.series_keys()
                if type_name is None or k[0] == type_name]
        entries = []
        for t, sig in keys:
            win = self.window_stats(t, sig, now - window_s, now + 1.0)
            with self._lock:
                rec = self._series.get((t, sig))
                buckets = [
                    {"ts": b.start, "count": b.count,
                     "mean_ms": round(b.sum_ms / b.count, 3) if b.count else 0.0,
                     "max_ms": round(b.max_ms, 3),
                     "rows": b.rows, "dispatches": b.dispatches}
                    for b in (rec.buckets if rec is not None else ())
                ]
            entries.append({
                "type": t,
                "signature": sig,
                "window_s": window_s,
                "window": {k: (round(v, 3) if isinstance(v, float) else v)
                           for k, v in win.items()},
                "buckets": buckets[-64:],
                "exemplars": self.exemplars(t, sig, limit=8),
            })
        entries.sort(key=lambda e: -e["window"]["count"])
        return {
            "entries": entries[:limit],
            "series": len(keys),
            "bucket_s": self.bucket_s,
            "observe_count": self.observe_count,
        }

    def prometheus_lines(self, prefix: str = "geomesa") -> list[str]:
        """TRUE histogram families over the retained rings (see
        :func:`geomesa_tpu.obs.lens.histogram_lines`)."""
        with self._lock:
            rows = []
            for (t, sig), rec in self._series.items():
                if not rec.buckets:
                    continue
                bins = [0] * _N_BINS
                count = 0
                sum_ms = 0.0
                dispatches = 0
                for b in rec.buckets:
                    for i, c in enumerate(b.bins):
                        bins[i] += c
                    count += b.count
                    sum_ms += b.sum_ms
                    dispatches += b.dispatches
                rows.append((t, sig, bins, count, sum_ms, dispatches))
        return histogram_lines(rows, prefix)

    def prometheus_text(self, prefix: str = "geomesa") -> str:
        lines = self.prometheus_lines(prefix)
        return "\n".join(lines) + "\n" if lines else ""

    # -- roundtrip rollup -----------------------------------------------------
    def fusion_report(self, limit: int = 50) -> list[dict]:
        """Plan signatures ranked by host-choreography share — the fraction
        of wall time spent in inter-stage host gaps plus sync waits. High
        share + multiple dispatches per query = a fusion opportunity."""
        out = []
        with self._lock:
            for (type_name, sig), r in self._series.items():
                if not r.queries:
                    continue
                wall = max(r.wall_ms_sum,
                           r.dispatch_ms + r.sync_ms + r.host_gap_ms, 1e-9)
                share = min(1.0, (r.host_gap_ms + r.sync_ms) / wall)
                out.append({
                    "type": type_name,
                    "signature": sig,
                    "queries": r.queries,
                    "dispatches_per_query": r.dispatches / r.queries,
                    "syncs_per_query": r.syncs / r.queries,
                    "compiles": r.compiles,
                    "host_gap_ms": round(r.host_gap_ms, 3),
                    "sync_ms": round(r.sync_ms, 3),
                    "dispatch_ms": round(r.dispatch_ms, 3),
                    "wall_ms": round(r.wall_ms_sum, 3),
                    "host_share": round(share, 4),
                    "h2d_bytes": r.h2d_bytes,
                    "d2h_bytes": r.d2h_bytes,
                })
        out.sort(key=lambda r: (-r["host_share"], -r["wall_ms"]))
        return out[:limit]

    def export(self) -> dict:
        """The stable reconcile-export document (``obs ledger-export``,
        ``GET /api/obs/ledger?format=json``): one entry per (type, plan
        signature) with the raw rollup counters. Consumers key off
        ``kind`` + ``schema_version`` and must reject anything else."""
        with self._lock:
            entries = [
                {
                    "type": type_name,
                    "signature": sig,
                    "queries": r.queries,
                    "dispatches": r.dispatches,
                    "compiles": r.compiles,
                    "syncs": r.syncs,
                    "dispatch_ms": round(r.dispatch_ms, 3),
                    "sync_ms": round(r.sync_ms, 3),
                    "host_gap_ms": round(r.host_gap_ms, 3),
                    "wall_ms": round(r.wall_ms_sum, 3),
                    "h2d_bytes": r.h2d_bytes,
                    "d2h_bytes": r.d2h_bytes,
                }
                for (type_name, sig), r in sorted(self._series.items())
                if r.queries
            ]
        return {
            "kind": EXPORT_KIND,
            "schema_version": EXPORT_SCHEMA_VERSION,
            "entries": entries,
        }


_table = PlanTable()


def table() -> PlanTable:
    """The process-wide plan table."""
    return _table


def install(new: PlanTable | None = None) -> PlanTable:
    """Swap the process table (tests; None installs a fresh default);
    returns the previous one."""
    global _table
    prev = _table
    _table = new if new is not None else PlanTable()
    return prev
