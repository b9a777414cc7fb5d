"""stream-lens: per-(topic, subscription) delivery observability.

The query plane's retained lens (:mod:`geomesa_tpu.obs.lens`) answers
"since when is signature X slow, show me one trace"; this module is the
same retained plane for STANDING queries — what makes a 1M-subscription
registry operable (ROADMAP item 4):

- :class:`StreamLens` — per (topic, subscription) series on the shared
  :class:`~geomesa_tpu.obs.lens.HistogramRing` base (same ring / valve /
  exemplar machinery as the query lens, so the two planes cannot drift).
  Each delivery records the processing-time latency from bus append to
  ``HitBatch`` delivery, DECOMPOSED from the per-chunk stage stamps the
  scanner carries (:data:`STAGES`: queue-wait / pad-flush-wait / H2D
  staging / fused scan / host refine / fan-out), plus event-time
  on-time/late accounting per watermark advance and chunk trace-id
  exemplars that resolve to stitched span trees
  (``GET /api/obs/stream?trace=``).
- per-subscription COST attribution folded out of outputs the fused scan
  already computes: ``cost = hits + refine_rows + 0.01 × chunk_rows``
  (delivered hit rows and wide-row envelope-refine rows at full weight;
  the subscription's equal per-slot share of the fused ``rows × queries``
  pass down-weighted — occupancy is paid by every slot alike, matching
  is what differentiates subscriptions). The scale report ranks by the
  share of this.
- the capacity section: per-topic matrix occupancy / epoch churn rate /
  predicted next bucket-crossing recompile / HBM bytes-per-subscription
  extrapolated to 1M — fed by :meth:`StreamLens.note_matrix` once per
  scanned chunk.
- a ``stream.delivery`` SLO per topic on the lens's own
  :class:`~geomesa_tpu.obs.slo.SloEngine` (the usage-meter pattern: own
  engine, distinct metric names so ``# TYPE`` headers never collide with
  the store engine's), burned by late or slow deliveries.
- :class:`BacklogSentinel` — the ISSUE-17 ``RegressionSentinel`` shape:
  a shadow-plane comparator latching ONE ``A_BACKLOG`` flight anomaly
  per episode when a topic's watermark freshness, scanner queue depth,
  or delivery-SLO burn rate sustains past threshold.

Valve: unlike the query lens (longest-idle eviction), the stream lens
evicts the CHEAPEST series at the cardinality bound and folds it into a
per-topic ``other`` rollup, so totals stay reconcilable and the
Prometheus surface (``geomesa_stream_delivery_*``) stays bounded AND
representative at high subscription counts. The same top-K-by-cost
ranking bounds the watermark/freshness gauges in
:mod:`geomesa_tpu.stream.telemetry`.

Overhead discipline: ``observe_delivery`` is on the always-on scan path —
one leaf-lock acquisition per (subscription × chunk), a bisect into the
shared fixed edges, and a handful of increments (the ≤2% fused-scan bound
is pinned in tests/test_streamlens.py). No jax anywhere
(``GEOMESA_TPU_NO_JAX=1`` safe).

Locking (docs/concurrency.md): the lens lock (via HistogramRing) and the
sentinel's state lock are LEAVES — nothing is called while either is
held; the SLO engine's lock is its own leaf underneath.
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_left
from collections import deque

from geomesa_tpu.analysis.contracts import (cache_surface, feedback_sink,
                                            shadow_plane)
from geomesa_tpu.obs.lens import (BUCKET_EDGES_MS, _BUCKET_S, _MAX_SERIES,
                                  _N_BINS, _RING, _esc, _fmt_le, _quantile,
                                  HistogramRing, _LensBucket, _Series)
from geomesa_tpu.obs.slo import SloEngine

__all__ = [
    "StreamLens", "BacklogSentinel", "STAGES", "get", "install",
    "sentinel", "install_sentinel",
]

# the stage decomposition contract (docs/streaming.md § Stream lens):
# bus append → HitBatch delivery, in pipeline order. Stamped per CHUNK by
# the scanner, attributed per delivery.
STAGES = ("queue_wait", "pad_flush", "h2d", "scan", "refine", "fanout")
_N_STAGES = len(STAGES)

# cost-attribution weight of one fused-scan row-evaluation relative to
# one delivered/refined hit row (see module docstring)
SCAN_ROW_WEIGHT = 0.01

# exposition bound: series per topic emitted individually; the rest
# aggregate into the `other` rollup (also the watermark-gauge bound —
# stream/telemetry.py imports this)
TOP_K = 64


class _DeliveryBucket(_LensBucket):
    """One time bucket of one delivery series: the shared latency
    histogram plus the stream plane's extra counters. ``rows`` counts
    delivered hit rows, ``dispatches`` counts scanned chunks."""

    __slots__ = ("on_time", "late", "stage_ms", "cost")

    def __init__(self, start: float):
        super().__init__(start)
        self.on_time = 0  # watermark advances whose window was on-time
        self.late = 0  # advances containing rows behind the watermark
        self.stage_ms = [0.0] * _N_STAGES
        self.cost = 0.0


class _DeliverySeries(_Series):
    """Ring plus LIFETIME rollups (the cost ranking and the report read
    these without merging the ring)."""

    __slots__ = ("cost", "hit_rows", "chunks", "deliveries", "on_time",
                 "late", "stage_ms")

    def __init__(self, ring: int = _RING):
        super().__init__(ring)
        self.cost = 0.0
        self.hit_rows = 0
        self.chunks = 0
        self.deliveries = 0
        self.on_time = 0
        self.late = 0
        self.stage_ms = [0.0] * _N_STAGES


class _TopicState:
    """Per-topic capacity/churn observations + the valve's ``other``
    rollup + dropped-row accounting. Mutated under the lens lock."""

    __slots__ = ("ring", "slot_bytes", "dropped_rows", "dropped_chunks",
                 "other")

    def __init__(self):
        # (ts, epoch, active, capacity) — churn + growth trend source
        self.ring: deque = deque(maxlen=_RING)
        self.slot_bytes = 0
        self.dropped_rows = 0
        self.dropped_chunks = 0
        # valve rollup of evicted series: totals stay reconcilable
        self.other = {"series": 0, "cost": 0.0, "hit_rows": 0,
                      "deliveries": 0, "on_time": 0, "late": 0}


@cache_surface(name="stream-lens", keyed_by="topic", purge=("forget",))
class StreamLens(HistogramRing):
    """Per-(topic, subscription) delivery histograms with stage
    decomposition, lateness accounting, cost attribution, and the
    standing-query scale report."""

    _bucket_cls = _DeliveryBucket
    _series_cls = _DeliverySeries

    def __init__(self, bucket_s: float = _BUCKET_S, ring: int = _RING,
                 max_series: int = _MAX_SERIES, clock=time.time,
                 slo_target: float = 0.999,
                 slo_latency_ms: float = 2500.0):
        super().__init__(bucket_s=bucket_s, ring=ring,
                         max_series=max_series, clock=clock)
        self._topics: dict[str, _TopicState] = {}
        # own engine, usage-meter pattern: stream.delivery burn must not
        # share trackers (or # TYPE headers) with the store's engine
        self.slo = SloEngine()
        self.slo.objective("stream.delivery", target=slo_target,
                           latency_ms=slo_latency_ms)

    # -- valve ---------------------------------------------------------------
    def _evict_locked(self) -> None:
        """Top-K-by-cost valve: evict the CHEAPEST series and fold its
        lifetime totals into its topic's ``other`` rollup (the query
        lens's longest-idle policy would evict a quiet-but-expensive
        subscription the report must keep ranking)."""
        key = min(self._series, key=lambda k: self._series[k].cost)
        s = self._series.pop(key)
        o = self._topic_locked(key[0]).other
        o["series"] += 1
        o["cost"] += s.cost
        o["hit_rows"] += s.hit_rows
        o["deliveries"] += s.deliveries
        o["on_time"] += s.on_time
        o["late"] += s.late

    def _topic_locked(self, topic: str) -> _TopicState:
        st = self._topics.get(topic)
        if st is None:
            st = self._topics[topic] = _TopicState()
        return st

    # -- the hot path ---------------------------------------------------------
    @feedback_sink
    def observe_delivery(self, topic: str, subscription, *,
                         latency_ms: float | None = None,
                         stages: tuple | None = None, hit_rows: int = 0,
                         cost: float = 0.0, on_time: bool | None = None,
                         trace_id: str = "", now: float | None = None) -> None:
        """One (subscription × scanned chunk) observation. Always-on:
        one lock, one bisect, a few increments. ``latency_ms`` is None
        when the chunk matched nothing for this subscription (cost and
        watermark accounting still land; the histogram only ever holds
        real deliveries). ``on_time`` is None when the topic carries no
        event time (packed-payload matrices)."""
        if now is None:
            now = self._clock()
        key = (topic, str(subscription))
        bin_i = (bisect_left(BUCKET_EDGES_MS, latency_ms)
                 if latency_ms is not None else 0)
        with self._lock:
            if key not in self._series:
                # touch the topic table first so the valve's rollup
                # target exists before any eviction can need it
                self._topic_locked(topic)
            series = self._series_locked(key)
            b = self._bucket_locked(series, now)
            series.chunks += 1
            b.dispatches += 1
            series.cost += cost
            b.cost += cost
            if on_time is not None:
                if on_time:
                    series.on_time += 1
                    b.on_time += 1
                else:
                    series.late += 1
                    b.late += 1
            if latency_ms is not None:
                b.bins[bin_i] += 1
                b.count += 1
                b.sum_ms += latency_ms
                if latency_ms > b.max_ms:
                    b.max_ms = latency_ms
                b.rows += hit_rows
                series.hit_rows += hit_rows
                series.deliveries += 1
                if stages is not None:
                    sm = b.stage_ms
                    lm = series.stage_ms
                    for i in range(_N_STAGES):
                        sm[i] += stages[i]
                        lm[i] += stages[i]
                if trace_id:
                    self._exemplar_locked(b, latency_ms, trace_id, now)
            self.observe_count += 1
        if latency_ms is not None:
            # late or slow deliveries burn the topic's delivery SLO
            # (engine lock is its own leaf — acquired after ours released)
            self.slo.observe("stream.delivery", on_time is not False,
                             latency_ms=latency_ms, key=topic)

    def note_dropped(self, topic: str, rows: int, chunks: int = 1) -> None:
        """A poisoned chunk's rows: never evaluated for ANY subscription
        of the topic — the ``dropped`` leg of on-time/late/dropped."""
        with self._lock:
            st = self._topic_locked(topic)
            st.dropped_rows += int(rows)
            st.dropped_chunks += int(chunks)

    def note_matrix(self, topic: str, *, capacity: int, active: int,
                    epoch: int, slot_bytes: int,
                    now: float | None = None) -> None:
        """One per-chunk capacity observation (occupancy / churn /
        growth trend source for the scale report)."""
        if now is None:
            now = self._clock()
        with self._lock:
            st = self._topic_locked(topic)
            st.slot_bytes = int(slot_bytes)
            r = st.ring
            if r and r[-1][1] == epoch and r[-1][2] == active:
                r[-1] = (r[-1][0], epoch, active, capacity)
                return
            r.append((now, epoch, active, capacity))

    # -- maintenance ----------------------------------------------------------
    def forget(self, topic: str) -> None:
        """Purge every series and the capacity state for ``topic`` (hub
        closed / topic retired)."""
        with self._lock:
            for key in [k for k in self._series if k[0] == topic]:
                del self._series[key]
            self._topics.pop(topic, None)
        self.slo.forget("stream.delivery", topic)

    # -- read surfaces --------------------------------------------------------
    def cost_rank(self, topic: str) -> list:
        """``[(subscription, lifetime_cost), ...]`` most expensive first —
        the valve ranking the watermark gauges share
        (stream/telemetry.py)."""
        with self._lock:
            rows = [(k[1], s.cost) for k, s in self._series.items()
                    if k[0] == topic]
        rows.sort(key=lambda r: (-r[1], r[0]))
        return rows

    def window_stats(self, topic: str, subscription, start_s: float,
                     end_s: float) -> dict:
        """Merged delivery stats over ``[start_s, end_s)``: the shared
        histogram quantiles plus hit rows / chunks / on-time / late /
        cost / per-stage ms."""
        extra = {"rows": 0, "chunks": 0, "on_time": 0, "late": 0,
                 "cost": 0.0}
        stage_ms = [0.0] * _N_STAGES

        def fold(b):
            extra["rows"] += b.rows
            extra["chunks"] += b.dispatches
            extra["on_time"] += b.on_time
            extra["late"] += b.late
            extra["cost"] += b.cost
            for i in range(_N_STAGES):
                stage_ms[i] += b.stage_ms[i]

        with self._lock:
            bins, count, sum_ms, max_ms = self._window_locked(
                (topic, str(subscription)), start_s, end_s, fold)
        judged = extra["on_time"] + extra["late"]
        return {
            "count": count,
            "sum_ms": sum_ms,
            "mean_ms": sum_ms / count if count else 0.0,
            "p50_ms": _quantile(bins, count, 0.5),
            "p95_ms": _quantile(bins, count, 0.95),
            "p99_ms": _quantile(bins, count, 0.99),
            "max_ms": max_ms,
            "hit_rows": extra["rows"],
            "chunks": extra["chunks"],
            "on_time": extra["on_time"],
            "late": extra["late"],
            "on_time_fraction": (extra["on_time"] / judged if judged
                                 else None),
            "cost": extra["cost"],
            "stage_ms": {STAGES[i]: round(stage_ms[i], 3)
                         for i in range(_N_STAGES)},
        }

    def exemplars(self, topic: str, subscription, limit: int = 16) -> list:
        """The series' retained chunk-trace exemplars, slowest first —
        each ``trace_id`` resolves via ``trace.find_trace`` to the
        stitched poll → cut → stage → scan → deliver span tree."""
        with self._lock:
            rows = self._exemplar_rows_locked((topic, str(subscription)))
        rows.sort(key=lambda r: -r["latency_ms"])
        return rows[:limit]

    def _capacity_section(self, st: _TopicState, now: float) -> dict:
        """Occupancy, churn, the predicted next bucket-crossing
        recompile, and the 1M-subscription HBM extrapolation — computed
        from the note_matrix ring (caller holds the lock)."""
        ring = list(st.ring)
        if not ring:
            return {"observed": False}
        t0, e0, a0, _c0 = ring[0]
        t1, e1, a1, cap = ring[-1]
        dt = max(t1 - t0, 0.0)
        churn = (e1 - e0) / dt if dt > 0 else 0.0  # epoch advances / s
        grow = (a1 - a0) / dt if dt > 0 else 0.0  # net subscriptions / s
        headroom = cap - a1  # adds until the power-of-two bucket crosses
        eta_s = headroom / grow if grow > 0 else None
        return {
            "observed": True,
            "capacity": cap,
            "active": a1,
            "occupancy": round(a1 / cap, 4) if cap else 0.0,
            "epoch": e1,
            "churn_per_s": round(churn, 4),
            "growth_per_s": round(grow, 4),
            "next_bucket_crossing": {
                # crossing capacity compiles the next (cached, per-bucket)
                # executable — the one planned recompile left on this path
                "adds_until_grow": headroom + 1,
                "eta_s": round(eta_s, 1) if eta_s is not None else None,
            },
            "hbm_bytes_per_subscription": st.slot_bytes,
            "hbm_bytes_at_1m": st.slot_bytes * 1_000_000,
            "dropped_rows": st.dropped_rows,
            "dropped_chunks": st.dropped_chunks,
        }

    def report(self, window_s: float = 300.0, limit: int = 50,
               topic: str | None = None) -> dict:
        """The standing-query scale report (``GET /api/obs/stream``,
        ``geomesa-tpu obs stream-report``): per topic, subscriptions
        ranked by lifetime scan-cost SHARE (delivery p99 alongside), the
        capacity section, and the valve's ``other`` rollup."""
        now = self._clock()
        with self._lock:
            keys = [k for k in self._series
                    if topic is None or k[0] == topic]
            keyset = set(keys)
            lifetime = {k: {"cost": s.cost, "hit_rows": s.hit_rows,
                            "deliveries": s.deliveries, "chunks": s.chunks,
                            "on_time": s.on_time, "late": s.late}
                        for k, s in self._series.items() if k in keyset}
            topics = {t: (self._capacity_section(st, now),
                          dict(st.other))
                      for t, st in self._topics.items()
                      if topic is None or t == topic}
        by_topic: dict[str, list] = {}
        for t, sub in keys:
            by_topic.setdefault(t, []).append(sub)
        out_topics = []
        for t in sorted(set(by_topic) | set(topics)):
            subs = by_topic.get(t, [])
            total_cost = sum(lifetime[(t, s)]["cost"] for s in subs)
            cap, other = topics.get(t, ({"observed": False}, None))
            if other:
                total_cost += other["cost"]
            entries = []
            for s in subs:
                life = lifetime[(t, s)]
                win = self.window_stats(t, s, now - window_s, now + 1.0)
                entries.append({
                    "subscription": s,
                    "cost": round(life["cost"], 3),
                    "cost_share": (round(life["cost"] / total_cost, 4)
                                   if total_cost else 0.0),
                    "hit_rows": life["hit_rows"],
                    "deliveries": life["deliveries"],
                    "chunks": life["chunks"],
                    "on_time": life["on_time"],
                    "late": life["late"],
                    "window": {k: (round(v, 3) if isinstance(v, float)
                                   else v)
                               for k, v in win.items()},
                    "exemplars": self.exemplars(t, s, limit=4),
                })
            entries.sort(key=lambda e: (-e["cost"], -e["window"]["p99_ms"]))
            out_topics.append({
                "topic": t,
                "subscriptions": entries[:limit],
                "series": len(subs),
                "capacity": cap,
                "other": other if (other and other["series"]) else None,
            })
        return {
            "topics": out_topics,
            "window_s": window_s,
            "bucket_s": self.bucket_s,
            "observe_count": self.observe_count,
            "slo": self.slo.snapshot(),
        }

    # -- prometheus exposition ------------------------------------------------
    def prometheus_lines(self, prefix: str = "geomesa") -> list[str]:
        """The ``geomesa_stream_delivery_*`` families: a TRUE histogram
        (``_ms_bucket``/``_sum``/``_count``) plus on-time / late / hit-row
        / cost counters per (topic, subscription), bounded at
        :data:`TOP_K` series per topic by cost with an ``other`` rollup
        row — and the lens's own ``stream.delivery`` SLO gauges under the
        ``{prefix}_stream`` prefix (distinct names: the store engine
        already emits ``{prefix}_slo_*``)."""
        with self._lock:
            per_topic: dict[str, list] = {}
            for (t, sub), s in self._series.items():
                bins = [0] * _N_BINS
                count = 0
                sum_ms = 0.0
                for b in s.buckets:
                    for i, c in enumerate(b.bins):
                        bins[i] += c
                    count += b.count
                    sum_ms += b.sum_ms
                per_topic.setdefault(t, []).append(
                    (sub, s.cost, bins, count, sum_ms, s.hit_rows,
                     s.on_time, s.late))
            others = {t: dict(st.other) for t, st in self._topics.items()}
            dropped = {t: st.dropped_rows for t, st in self._topics.items()}
        rows = []
        for t in sorted(per_topic):
            ranked = sorted(per_topic[t], key=lambda r: (-r[1], r[0]))
            spill = ranked[TOP_K:]
            for sub, cost, bins, count, sum_ms, hits, on, late in \
                    ranked[:TOP_K]:
                rows.append((t, sub, cost, bins, count, sum_ms, hits, on,
                             late))
            o = dict(others.get(t) or
                     {"series": 0, "cost": 0.0, "hit_rows": 0,
                      "deliveries": 0, "on_time": 0, "late": 0})
            obins = [0] * _N_BINS
            ocount = 0
            osum = 0.0
            for sub, cost, bins, count, sum_ms, hits, on, late in spill:
                o["series"] += 1
                o["cost"] += cost
                o["hit_rows"] += hits
                o["on_time"] += on
                o["late"] += late
                for i, c in enumerate(bins):
                    obins[i] += c
                ocount += count
                osum += sum_ms
            if o["series"]:
                rows.append((t, "other", o["cost"], obins, ocount, osum,
                             o["hit_rows"], o["on_time"], o["late"]))
        if not rows and not dropped:
            return []
        name = f"{prefix}_stream_delivery_ms"
        hist = [f"# TYPE {name} histogram"]
        on_l = [f"# TYPE {prefix}_stream_delivery_on_time_total counter"]
        late_l = [f"# TYPE {prefix}_stream_delivery_late_total counter"]
        hit_l = [f"# TYPE {prefix}_stream_delivery_hit_rows_total counter"]
        cost_l = [f"# TYPE {prefix}_stream_delivery_cost_units_total counter"]
        for t, sub, cost, bins, count, sum_ms, hits, on, late in rows:
            labels = f'topic="{_esc(t)}",subscription="{_esc(sub)}"'
            cum = 0
            for i, edge in enumerate(BUCKET_EDGES_MS):
                cum += bins[i]
                hist.append(
                    f'{name}_bucket{{{labels},le="{_fmt_le(edge)}"}} {cum}')
            hist.append(f'{name}_bucket{{{labels},le="+Inf"}} {count}')
            hist.append(f"{name}_sum{{{labels}}} {sum_ms:.6g}")
            hist.append(f"{name}_count{{{labels}}} {count}")
            on_l.append(
                f"{prefix}_stream_delivery_on_time_total{{{labels}}} {on}")
            late_l.append(
                f"{prefix}_stream_delivery_late_total{{{labels}}} {late}")
            hit_l.append(
                f"{prefix}_stream_delivery_hit_rows_total{{{labels}}} {hits}")
            cost_l.append(
                f"{prefix}_stream_delivery_cost_units_total{{{labels}}} "
                f"{cost:.6g}")
        drop_l = [f"# TYPE {prefix}_stream_delivery_dropped_rows_total "
                  "counter"]
        for t in sorted(dropped):
            drop_l.append(
                f'{prefix}_stream_delivery_dropped_rows_total'
                f'{{topic="{_esc(t)}"}} {dropped[t]}')
        out = hist + on_l + late_l + hit_l + cost_l + drop_l
        out += self.slo.prometheus_lines(prefix=f"{prefix}_stream")
        return out

    def prometheus_text(self, prefix: str = "geomesa") -> str:
        lines = self.prometheus_lines(prefix)
        return "\n".join(lines) + "\n" if lines else ""


# -- backlog/freshness sentinel ----------------------------------------------

@shadow_plane
class BacklogSentinel:
    """Background backlog comparator (the ISSUE-17 sentinel shape:
    ``start()``/``close()`` around a daemon worker, ``evaluate_once()``
    for tests and the CLI).

    Per evaluation, per topic feeding the stream lens: watermark
    freshness (from the stream telemetry table — only meaningful while
    the scanner is actually behind, so freshness alone fires only with a
    nonzero queue), scanner queue depth, and the topic's
    ``stream.delivery`` burn rate. ``sustain`` consecutive burning
    evaluations latch ONE ``A_BACKLOG`` flight anomaly per episode (the
    recorder's dump rate-limit rides along) and the
    ``geomesa_stream_backlog`` gauge until the topic recovers.

    Evaluations run in audit shadow: sentinel reads must never meter a
    tenant or feed back into the lens."""

    def __init__(self, lens: StreamLens | None = None,
                 interval_s: float = 15.0, freshness_ms: float = 30_000.0,
                 max_scan_lag: int = 1_000_000, burn_factor: float = 2.0,
                 burn_window_s: float = 300.0, sustain: int = 1,
                 clock=time.time):
        self._lens = lens
        self.interval_s = interval_s
        self.freshness_ms = freshness_ms
        self.max_scan_lag = max_scan_lag
        self.burn_factor = burn_factor
        self.burn_window_s = burn_window_s
        self.sustain = max(1, sustain)
        self._clock = clock
        self._lock = threading.Lock()  # leaf: streaks + alarms
        self._streaks: dict[str, int] = {}
        self._alarms: dict[str, dict] = {}
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.eval_count = 0
        self.backlogs_total = 0

    @property
    def lens(self) -> StreamLens:
        return self._lens if self._lens is not None else get()

    # -- evaluation -----------------------------------------------------------
    def evaluate_once(self, now: float | None = None) -> list[dict]:
        """One comparator pass; returns the alarms RAISED this pass (an
        already-latched topic does not re-raise). Wraps itself in audit
        shadow."""
        from geomesa_tpu.obs import audit as _audit

        with _audit.shadow():
            return self._evaluate(self._clock() if now is None else now)

    def _evaluate(self, now: float) -> list[dict]:
        from geomesa_tpu.stream import telemetry as _telemetry

        lens = self.lens
        stream = _telemetry.report(now_ms=now * 1000.0)
        topics = set(stream) | {k[0] for k in lens.series_keys()}
        raised = []
        for t in sorted(topics):
            st = stream.get(t, {})
            scan_lag = int(st.get("scan_lag", 0))
            bus_lag = int(st.get("lag", 0))
            fresh = max(
                (wm["freshness_ms"]
                 for wm in (st.get("watermarks") or {}).values()),
                default=0.0,
            )
            causes = []
            if fresh > self.freshness_ms and (scan_lag > 0 or bus_lag > 0):
                causes.append(("freshness", fresh, self.freshness_ms))
            if scan_lag > self.max_scan_lag:
                causes.append(("queue_depth", float(scan_lag),
                               float(self.max_scan_lag)))
            burn = lens.slo.tracker("stream.delivery", t).burn_rate(
                self.burn_window_s)
            if burn >= self.burn_factor:
                causes.append(("slo_burn", burn, self.burn_factor))
            if not causes:
                with self._lock:
                    self._streaks.pop(t, None)
                    self._alarms.pop(t, None)
                continue
            with self._lock:
                streak = self._streaks.get(t, 0) + 1
                self._streaks[t] = streak
                fire = streak >= self.sustain and t not in self._alarms
                if fire:
                    kind, live_v, limit_v = causes[0]
                    alarm = {
                        "topic": t, "cause": kind,
                        "value": round(live_v, 3),
                        "threshold": round(limit_v, 3),
                        "scan_lag": scan_lag, "lag": bus_lag,
                        "freshness_ms": round(fresh, 1),
                        "burn_rate": round(burn, 3), "ts": now,
                    }
                    self._alarms[t] = alarm
                    self.backlogs_total += 1
            if fire:
                raised.append(alarm)
                self._raise_anomaly(alarm)
        with self._lock:
            self.eval_count += 1
        return raised

    def _raise_anomaly(self, alarm: dict) -> None:
        # one A_BACKLOG flight record per episode (the recorder's dump
        # throttle bounds file output under a storm). flight.record is
        # the operator surface — an alert raised from shadow is the point.
        from geomesa_tpu.obs import flight as _flight

        _flight.record(
            "stream.sentinel", alarm["topic"], source="sentinel",
            plan=(f"{alarm['cause']}: {alarm['value']:.6g} over "
                  f"{alarm['threshold']:.6g} (scan_lag={alarm['scan_lag']}, "
                  f"freshness={alarm['freshness_ms']:.6g} ms, "
                  f"burn={alarm['burn_rate']:.3g})"),
            latency_ms=alarm["freshness_ms"],
            plan_signature="stream.delivery",
            anomalies=(_flight.A_BACKLOG,),
        )

    # -- worker ---------------------------------------------------------------
    def start(self) -> None:
        with self._lock:
            if self._thread is not None:
                return
            self._thread = threading.Thread(
                target=self._run, name="geomesa-backlog-sentinel",
                daemon=True)
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
                "backlogs_total": self.backlogs_total,
                "freshness_ms": self.freshness_ms,
                "max_scan_lag": self.max_scan_lag,
                "burn_factor": self.burn_factor,
                "running": self._thread is not None,
            }

    def prometheus_lines(self, prefix: str = "geomesa") -> list[str]:
        with self._lock:
            alarms = list(self._alarms.values())
            total = self.backlogs_total
        out = [f"# TYPE {prefix}_stream_backlog gauge"]
        for a in alarms:
            out.append(
                f'{prefix}_stream_backlog{{topic="{_esc(a["topic"])}",'
                f'cause="{_esc(a["cause"])}"}} 1')
        out.append(f"# TYPE {prefix}_stream_backlogs_total counter")
        out.append(f"{prefix}_stream_backlogs_total {total}")
        return out

    def prometheus_text(self, prefix: str = "geomesa") -> str:
        return "\n".join(self.prometheus_lines(prefix)) + "\n"


# process-wide singletons (tests swap with install()/install_sentinel())
_lens = StreamLens()
_sentinel = BacklogSentinel()


def get() -> StreamLens:
    """The process-wide stream lens."""
    return _lens


def install(lens: StreamLens) -> StreamLens:
    """Swap the process stream lens (tests); returns the previous one."""
    global _lens
    prev, _lens = _lens, lens
    return prev


def sentinel() -> BacklogSentinel:
    """The process-wide backlog sentinel (not started by default;
    servers opt in via ``start()``)."""
    return _sentinel


def install_sentinel(s: BacklogSentinel) -> BacklogSentinel:
    """Swap the process sentinel (tests); returns the previous one —
    callers own closing the outgoing worker."""
    global _sentinel
    prev, _sentinel = _sentinel, s
    return prev
