"""Continuous correctness auditor: sampled shadow re-execution against an
independent referee, background invariant sweeps, and auto-captured
minimized repro bundles (docs/observability.md § Continuous correctness
auditing).

The platform's fast paths — device refine kernels, the exec-cache
memoized select, the cheap-select route, the GeoBlocks pyramid + query
cache, coalesced ``select_many`` batches, sharded fan-out — are parity-
asserted in bench legs on synthetic data, never against live traffic.
This module observes their correctness continuously, the way the obs
stack already observes latency, devices and tenants:

1. **Sampled shadow re-execution.** ``GEOMESA_TPU_AUDIT`` (a [0,1]
   rate) or ``hints={"audit": True}`` tags completed queries; their
   (filter, hints, auths, data-epoch) plus the LIVE answer are enqueued
   to a bounded low-priority worker that re-executes them on the
   independent referee path (:mod:`geomesa_tpu.ops.referee`: host-side
   f64 NumPy scan over the base snapshot — no Z-decomposition, no
   device kernels, no pyramid/cache/memo) and compares fid-set equality
   for selects, exact counts, and f64-tolerance grouped-agg values.
   When the live data epoch ``(rebuild_epoch, delta.version)`` has
   moved past the captured one the check ABSTAINS — counted, never
   alarming — so concurrent writes can only cost coverage, not produce
   a false alarm.

2. **Background invariant sweeps** (:class:`InvariantSweeper`):
   structural invariants shadow queries cannot see — pyramid partials
   reconcile against base per (bin, cell) on a rotating cell sample,
   devmon ledger vs ``TpuBackend.residency()`` agreement, query-cache
   entry epochs never ahead of the live epoch (and never outliving
   their schema), sharded-view Z-domain coverage disjoint and total,
   subscription-matrix unsat-sentinel slots matching nothing, and a
   standing query's cumulative delivered count cross-checked against
   ``DataStore.query`` at the same epoch.

3. **Divergence handling.** A confirmed mismatch becomes a typed
   :class:`DivergenceReport`: an ``A_DIVERGE`` flight anomaly,
   ``geomesa_audit_*`` prometheus counters (checked/passed/diverged/
   abstained per check kind), and a **repro bundle** under
   ``GEOMESA_TPU_AUDIT_DIR`` — the ISSUE-11-shaped workload event plus
   epoch, both answers, and a delta-debugged MINIMIZED predicate
   (conjuncts dropped / ranges halved while the divergence persists) —
   replayable via ``geomesa-tpu replay --bundle``.

Hygiene: every execution the auditor itself triggers (referee scans are
pure host code; the minimizer ALSO re-runs the live path) runs inside
:func:`shadow`, and the store's feedback planes — plan-table
observations, usage metering, SLO burn, workload capture — all consult
:func:`in_shadow` and skip shadow traffic (the same rule ISSUE 11's
replay applies to capture). The off path costs one module-global bool
plus one ContextVar read per query (<2% bound gated in scripts/lint.sh).

Locking (docs/concurrency.md): the auditor lock and the sweeper lock
are LEAVES guarding queue/counters/verdicts only; referee execution,
store snapshots, minimization and file I/O all run outside them. No jax
anywhere (``GEOMESA_TPU_NO_JAX=1`` safe).
"""

from __future__ import annotations

import contextvars
import json
import os
import threading
import time
import weakref
from collections import OrderedDict, deque
from contextlib import contextmanager
from dataclasses import asdict, dataclass, field

from geomesa_tpu.analysis.contracts import shadow_guard, shadow_plane

__all__ = [
    "AUDIT_DIR_ENV", "AUDIT_ENV", "ContinuousAuditor", "DivergenceReport",
    "InvariantSweeper", "enabled", "get", "in_shadow", "install",
    "minimize_predicate", "replay_bundle", "sampled", "shadow",
]

AUDIT_ENV = "GEOMESA_TPU_AUDIT"
AUDIT_DIR_ENV = "GEOMESA_TPU_AUDIT_DIR"

# hints that reshape the result into something the select referee cannot
# compare fid-for-fid (grids, sketches, byte streams, row subsets)
_INELIGIBLE_HINTS = ("density", "stats", "bin", "sample", "sample_by",
                    "knn")

_CHECK_KINDS = ("select", "count", "agg")


def _env_rate() -> float:
    raw = os.environ.get(AUDIT_ENV, "").strip()
    if not raw:
        return 0.0
    try:
        rate = float(raw)
    except ValueError:
        raise ValueError(
            f"{AUDIT_ENV} must be a sampling rate in [0, 1], got {raw!r}"
        ) from None
    return min(max(rate, 0.0), 1.0)


# THE one check the per-query hot path pays when auditing is off
# (module-global bool, same pattern as workload.ENABLED)
_rate = _env_rate()
ENABLED = _rate > 0.0
_sample_acc = 0.0


def enabled() -> bool:
    return ENABLED


def set_rate(rate: float) -> None:
    """Set the sampling rate (tests / install); 0 disables the env path
    (per-query ``hints={"audit": True}`` still audits)."""
    global _rate, ENABLED, _sample_acc
    _rate = min(max(float(rate), 0.0), 1.0)
    ENABLED = _rate > 0.0
    _sample_acc = 0.0


def sampled() -> bool:
    """Deterministic rate-accumulator sampling: at rate r, ~every 1/r-th
    completed query audits (rate 1.0 = every query). Racy increments
    under free threading can only LOSE ticks — sampling, not accounting."""
    global _sample_acc
    if _rate <= 0.0:
        return False
    _sample_acc += _rate
    if _sample_acc >= 1.0:
        _sample_acc -= 1.0
        return True
    return False


# -- shadow mode --------------------------------------------------------------
# ContextVar (not threading.local): it crosses into the watchdog's
# copy_context worker threads the same way trace spans do, so a shadow
# re-execution stays shadow end to end.
_shadow_var: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "geomesa_audit_shadow", default=False)


@shadow_guard
def in_shadow() -> bool:
    """True inside an auditor-triggered execution: the store's feedback
    planes (cost table, usage metering, SLO burn, workload capture)
    consult this and skip — audit traffic must not train the planner,
    bill a tenant, burn an SLO budget, or recapture itself."""
    return _shadow_var.get()


@shadow_guard
@contextmanager
def shadow():
    token = _shadow_var.set(True)
    try:
        yield
    finally:
        _shadow_var.reset(token)


def eligible_select(q) -> bool:
    """Can this query's answer be compared fid-for-fid against the
    referee? Paging/limits make the row subset plan-dependent, and
    aggregation/sampling hints reshape the result entirely."""
    if q.limit is not None or q.start_index is not None:
        return False
    return not any(k in q.hints for k in _INELIGIBLE_HINTS)


def eligible_agg(q) -> bool:
    return (q.limit is None and q.start_index is None
            and not any(k in q.hints for k in _INELIGIBLE_HINTS))


def filter_text(q) -> str:
    f = q.filter
    if f is None:
        return "INCLUDE"
    if isinstance(f, str):
        return f
    from geomesa_tpu.filter import ast as _ast

    try:
        return _ast.to_cql(f)
    except ValueError:
        return str(f)


# -- divergence reports -------------------------------------------------------

@dataclass
class DivergenceReport:
    """One confirmed live-vs-referee mismatch (or invariant violation)."""

    ts: float
    kind: str  # "select" | "count" | "agg" | "sweep:<check>"
    type_name: str
    filter_text: str
    epoch: tuple | None
    detail: str  # human-readable mismatch description
    minimized: str = ""  # delta-debugged predicate (query checks only)
    live_summary: str = ""
    referee_summary: str = ""
    bundle_path: str | None = None
    tenant: str = ""


# -- predicate minimization ---------------------------------------------------

def _narrowings(node):
    """Narrowed variants of one leaf: halved spatial boxes / time windows."""
    from dataclasses import replace as _replace

    from geomesa_tpu.filter import ast as _ast

    if isinstance(node, _ast.BBox) and node.xmin <= node.xmax:
        xm = (node.xmin + node.xmax) / 2.0
        ym = (node.ymin + node.ymax) / 2.0
        if node.xmax - node.xmin > 1e-9:
            yield _replace(node, xmax=xm)
            yield _replace(node, xmin=xm)
        if node.ymax - node.ymin > 1e-9:
            yield _replace(node, ymax=ym)
            yield _replace(node, ymin=ym)
    elif isinstance(node, _ast.During):
        if node.hi_millis - node.lo_millis > 2:
            mid = (node.lo_millis + node.hi_millis) // 2
            yield _replace(node, hi_millis=mid)
            yield _replace(node, lo_millis=mid)


def _rebuild(node, target, new):
    """``node`` with ``target`` (identity) replaced by ``new``."""
    from geomesa_tpu.filter import ast as _ast

    if node is target:
        return new
    if isinstance(node, _ast.And):
        return _ast.And(tuple(_rebuild(c, target, new)
                              for c in node.children))
    if isinstance(node, _ast.Or):
        return _ast.Or(tuple(_rebuild(c, target, new)
                             for c in node.children))
    if isinstance(node, _ast.Not):
        return _ast.Not(_rebuild(node.child, target, new))
    return node


def _leaves(node):
    from geomesa_tpu.filter import ast as _ast

    if isinstance(node, (_ast.And, _ast.Or)):
        for c in node.children:
            yield from _leaves(c)
    elif isinstance(node, _ast.Not):
        yield from _leaves(node.child)
    else:
        yield node


def minimize_predicate(f, diverges, max_checks: int = 48):
    """Delta-debug one diverging predicate: drop conjuncts and halve
    box/window ranges while ``diverges(candidate)`` stays True, bounded
    at ``max_checks`` re-executions. ``diverges`` must return False for
    candidates it cannot verify (epoch moved, execution error) — the
    minimizer then simply keeps the larger predicate, so a racing write
    can stall minimization but never yield a non-reproducing bundle."""
    from geomesa_tpu.filter import ast as _ast

    budget = [max_checks]

    def still(cand) -> bool:
        if budget[0] <= 0:
            return False
        budget[0] -= 1
        try:
            return bool(diverges(cand))
        except Exception:  # noqa: BLE001 — an unverifiable candidate is kept out
            return False

    cur = f
    changed = True
    while changed and budget[0] > 0:
        changed = False
        # 1-minimal conjunct drop (ddmin over the top-level AND)
        if isinstance(cur, _ast.And) and len(cur.children) > 1:
            for i in range(len(cur.children)):
                rest = cur.children[:i] + cur.children[i + 1:]
                cand = rest[0] if len(rest) == 1 else _ast.And(rest)
                if still(cand):
                    cur = cand
                    changed = True
                    break
            if changed:
                continue
        # range halving on the surviving leaves
        for leaf in list(_leaves(cur)):
            for narrowed in _narrowings(leaf):
                cand = _rebuild(cur, leaf, narrowed)
                if still(cand):
                    cur = cand
                    changed = True
                    break
            if changed:
                break
    return cur


# -- the auditor --------------------------------------------------------------

class _Check:
    __slots__ = ("store_ref", "type_name", "kind", "q", "epoch", "live",
                 "group_by", "value_cols", "cutoff_ms", "tenant", "ts")

    def __init__(self, store, type_name, kind, q, epoch, live,
                 group_by=None, value_cols=(), cutoff_ms=None,
                 tenant=""):
        self.store_ref = weakref.ref(store)
        self.type_name = type_name
        self.kind = kind
        self.q = q
        self.epoch = epoch
        self.live = live
        self.group_by = group_by
        self.value_cols = tuple(value_cols or ())
        self.cutoff_ms = cutoff_ms
        self.tenant = tenant
        self.ts = time.time()


@shadow_plane
class ContinuousAuditor:
    """Bounded low-priority shadow-re-execution worker.

    ``enqueue_*`` is the hot-path side: build a check item, append under
    the leaf lock, drop-and-count when the queue is full (audit coverage
    degrades before the serving path does). The worker thread (lazily
    started; deterministic idempotent ``close``) pops one item at a
    time and runs the referee comparison OUTSIDE the lock. ``drain()``
    runs every queued check on the calling thread — the synchronous
    surface tests and ``explain(analyze=True)`` use."""

    def __init__(self, rate: float | None = None,
                 bundle_dir: str | None = None,
                 max_queue: int = 256, minimize_steps: int = 48,
                 autostart: bool = True, clock=time.time):
        if rate is not None:
            set_rate(rate)
        # the rate THIS auditor runs at: install() re-applies it, so a
        # swap-back (install(prev)) restores the previous sampling rate
        # instead of leaving the swapped-in auditor's rate behind
        self.rate = rate if rate is not None else _rate
        if bundle_dir is None:
            bundle_dir = os.environ.get(AUDIT_DIR_ENV) or None
        self.bundle_dir = bundle_dir
        self.max_queue = max_queue
        self.minimize_steps = minimize_steps
        self.autostart = autostart
        self._clock = clock
        self._lock = threading.Lock()  # leaf: queue + counters + verdicts
        self._cv = threading.Condition(self._lock)
        self._queue: deque = deque()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # per-kind counters (the geomesa_audit_* series)
        self.checked: dict[str, int] = {}
        self.passed: dict[str, int] = {}
        self.diverged: dict[str, int] = {}
        self.abstained: dict[str, int] = {}
        self.dropped = 0  # queue-full drops
        self.errors = 0  # referee execution errors (counted, never raised)
        self.bundles_written = 0
        self.divergences: deque = deque(maxlen=64)
        self._sweeps: dict[str, dict] = {}  # last result per sweep check
        # (type, filter text) -> verdict dict, for explain's Audit: line
        self._verdicts: OrderedDict = OrderedDict()

    # -- hot-path side --------------------------------------------------------
    def enqueue_select(self, store, type_name: str, q, epoch,
                       table) -> bool:
        fids = tuple(str(f) for f in table.fids)
        return self._enqueue(_Check(store, type_name, "select", q, epoch,
                                    fids, tenant=self._tenant(q)))

    def enqueue_count(self, store, type_name: str, q, epoch,
                      count: int) -> bool:
        return self._enqueue(_Check(store, type_name, "count", q, epoch,
                                    int(count), tenant=self._tenant(q)))

    def enqueue_agg(self, store, type_name: str, q, epoch, result,
                    group_by, value_cols, cutoff_ms=None) -> bool:
        from geomesa_tpu.ops.referee import live_agg_map

        live = live_agg_map(result, list(value_cols or ()))
        return self._enqueue(_Check(
            store, type_name, "agg", q, epoch, live, group_by=group_by,
            value_cols=value_cols, cutoff_ms=cutoff_ms,
            tenant=self._tenant(q)))

    @staticmethod
    def _tenant(q) -> str:
        from geomesa_tpu.obs import usage as _usage

        return q.hints.get("tenant") or _usage.current_tenant() or ""

    def _enqueue(self, item: _Check) -> bool:
        start = False
        with self._lock:
            if len(self._queue) >= self.max_queue:
                self.dropped += 1
                return False
            self._queue.append(item)
            self._cv.notify()
            if (self.autostart and self._thread is None
                    and not self._stop.is_set()):
                start = True
                self._thread = threading.Thread(
                    target=self._run, name="geomesa-audit", daemon=True)
        if start:
            self._thread.start()
        return True

    # -- worker side ----------------------------------------------------------
    def _run(self) -> None:
        while not self._stop.is_set():
            with self._cv:
                while not self._queue and not self._stop.is_set():
                    # CV wait releases the lock (worker parks when idle)
                    # tpurace: disable-next-line=R003
                    self._cv.wait(0.25)
                if self._stop.is_set():
                    return
                # _cv is Condition(self._lock): the auditor lock IS held
                # here — the lockset analyzer can't see through Condition
                # tpulint: disable-next-line=R001
                item = self._queue.popleft()
            self._execute(item)

    def drain(self) -> int:
        """Run every queued check on the calling thread; returns the
        number executed (tests / explain / CLI)."""
        n = 0
        while True:
            with self._lock:
                if not self._queue:
                    return n
                item = self._queue.popleft()
            self._execute(item)
            n += 1

    def close(self) -> None:
        """Deterministic idempotent shutdown of the worker thread."""
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=10.0)

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    # -- check execution ------------------------------------------------------
    def _count(self, table: dict, kind: str) -> None:
        table[kind] = table.get(kind, 0) + 1

    def _note_verdict(self, item: _Check, verdict: str, detail: str = ""):
        key = (item.type_name, filter_text(item.q))
        with self._lock:
            self._verdicts[key] = {
                "verdict": verdict, "kind": item.kind, "detail": detail,
                "ts": self._clock(),
            }
            self._verdicts.move_to_end(key)
            while len(self._verdicts) > 128:
                self._verdicts.popitem(last=False)

    def last_verdict(self, type_name: str, text: str | None = None):
        """The newest verdict for (type, filter) — or for the type alone
        when the exact text is absent (TTL stores rewrite the filter
        between the caller and the audit hook)."""
        with self._lock:
            if text is not None:
                hit = self._verdicts.get((type_name, text))
                if hit is not None:
                    return dict(hit)
            for (t, _txt), v in reversed(self._verdicts.items()):
                if t == type_name:
                    return dict(v)
        return None

    @staticmethod
    def _snapshot_at_epoch(store, type_name: str, epoch):
        """(sft, main, delta) when the live epoch still equals ``epoch``,
        else None (→ abstain). Epoch is re-read AFTER the snapshot:
        equality means no mutation landed in between, so the snapshot IS
        the captured-epoch data."""
        st = store._types.get(type_name)
        if st is None:
            return None
        main, _idx, _bs, _stats, delta = st.snapshot()
        if st.data_epoch() != tuple(epoch):
            return None
        return st.sft, main, delta

    def _execute(self, item: _Check) -> None:
        with self._lock:
            self._count(self.checked, item.kind)
        store = item.store_ref()
        if store is None:
            with self._lock:
                self._count(self.abstained, item.kind)
            return
        try:
            with shadow():
                self._execute_inner(store, item)
        except Exception:  # noqa: BLE001 — the auditor must never take down its host
            with self._lock:
                self.errors += 1

    def _execute_inner(self, store, item: _Check) -> None:
        from geomesa_tpu.ops import referee as _referee

        snap = self._snapshot_at_epoch(store, item.type_name, item.epoch)
        if snap is None:
            with self._lock:
                self._count(self.abstained, item.kind)
            self._note_verdict(item, "abstained", "epoch moved")
            return
        sft, main, delta = snap
        if item.kind == "agg":
            ref = _referee.referee_agg(
                sft, main, delta, item.q, item.group_by, item.value_cols,
                cutoff_ms=item.cutoff_ms)
            ok, detail = _referee.agg_equal(item.live, ref)
            live_s = f"{len(item.live)} groups"
            ref_s = f"{len(ref)} groups"
        else:
            ref_fids = _referee.referee_select(sft, main, delta, item.q)
            if item.kind == "count":
                ok = int(item.live) == len(ref_fids)
                detail = (f"count live={item.live} "
                          f"referee={len(ref_fids)}") if not ok else ""
                live_s = str(item.live)
                ref_s = str(len(ref_fids))
            else:
                # live fids arrive in result-table order; the referee
                # sorts — compare as multisets
                ok, detail = _referee.fid_sets_equal(
                    sorted(item.live), ref_fids)
                live_s = f"{len(item.live)} fids"
                ref_s = f"{len(ref_fids)} fids"
        if ok:
            with self._lock:
                self._count(self.passed, item.kind)
            self._note_verdict(item, "pass")
            return
        self._handle_divergence(store, item, detail, live_s, ref_s)

    # -- divergence path ------------------------------------------------------
    def _diverges_fn(self, store, item: _Check):
        """Predicate-level divergence oracle for the minimizer: re-run
        the LIVE path (in shadow — the feedback planes must not see it)
        and the referee with a candidate filter; True only when they
        still disagree AND the epoch held for both executions."""
        from dataclasses import replace as _replace

        from geomesa_tpu.ops import referee as _referee

        def diverges(cand) -> bool:
            q = _replace(item.q, filter=cand, hints={
                k: v for k, v in item.q.hints.items() if k != "audit"
            })
            if store._types.get(item.type_name) is None:
                return False
            st = store._types[item.type_name]
            if st.data_epoch() != tuple(item.epoch):
                return False
            # re-run the SAME live lane that produced the divergence: a
            # batched-count bug must be verified through count_many, not
            # through the (possibly correct) single-select path
            if item.kind == "agg":
                out = store.aggregate_many(
                    item.type_name, [q], group_by=item.group_by,
                    value_cols=item.value_cols)
                live_val = out[0]
            elif item.kind == "count":
                live_val = store.count_many(
                    item.type_name, [q], loose=False)[0]
            else:
                live_val = store.query(item.type_name, q)
            snap = self._snapshot_at_epoch(
                store, item.type_name, item.epoch)
            if snap is None:
                return False
            sft, main, delta = snap
            if item.kind == "agg":
                if live_val is None:
                    return False
                lm = _referee.live_agg_map(live_val, list(item.value_cols))
                ref = _referee.referee_agg(
                    sft, main, delta, q, item.group_by, item.value_cols,
                    cutoff_ms=item.cutoff_ms)
                return not _referee.agg_equal(lm, ref)[0]
            ref_fids = _referee.referee_select(sft, main, delta, q)
            if item.kind == "count":
                return int(live_val) != len(ref_fids)
            live_fids = sorted(str(f) for f in live_val.table.fids)
            return live_fids != ref_fids

        return diverges

    def _handle_divergence(self, store, item: _Check, detail: str,
                           live_s: str, ref_s: str) -> None:
        from geomesa_tpu.filter import ast as _ast
        from geomesa_tpu.obs import flight as _flight

        f = item.q.resolved_filter()
        minimized = f
        if not isinstance(f, _ast.Include) and self.minimize_steps > 0:
            minimized = minimize_predicate(
                f, self._diverges_fn(store, item),
                max_checks=self.minimize_steps)
        try:
            min_text = _ast.to_cql(minimized)
        except ValueError:
            min_text = str(minimized)
        report = DivergenceReport(
            ts=self._clock(), kind=item.kind, type_name=item.type_name,
            filter_text=filter_text(item.q), epoch=tuple(item.epoch),
            detail=detail, minimized=min_text,
            live_summary=live_s, referee_summary=ref_s,
            tenant=item.tenant,
        )
        report.bundle_path = self._write_bundle(item, report)
        with self._lock:
            self._count(self.diverged, item.kind)
            if report.bundle_path is not None:
                self.bundles_written += 1
            self.divergences.append(report)
        self._note_verdict(item, "diverged", detail)
        # A_DIVERGE flight anomaly: the record lands in the always-on
        # ring (and triggers a throttled Perfetto dump when a flight
        # dir is configured) so "what diverged and when" is answerable
        # from the flight surfaces alone
        _flight.record(
            op=f"audit.{item.kind}", type_name=item.type_name,
            source="audit", plan=report.filter_text,
            rows=0, anomalies=(_flight.A_DIVERGE,),
            tenant=item.tenant,
        )

    def _bundle_event(self, item: _Check) -> dict:
        """The ISSUE 11 workload wide-event shape for the diverging
        query — what ``geomesa-tpu replay --bundle`` re-issues."""
        from geomesa_tpu.obs.workload import _REPLAYABLE_HINTS, _json_safe

        return {
            "ts_arrival": round(item.ts, 6),
            "ts": round(item.ts, 6),
            "op": "query" if item.kind != "agg" else "aggregate",
            "type": item.type_name,
            "source": "audit",
            "filter": filter_text(item.q),
            "hints": {k: _json_safe(v) for k, v in item.q.hints.items()
                      if k in _REPLAYABLE_HINTS} or None,
            "tenant": item.tenant,
            "auths": (list(item.q.auths)
                      if item.q.auths is not None else None),
            "plan_signature": "", "predicted_ms": None,
            "latency_ms": 0.0, "rows": 0, "bytes_out": 0,
            "trace_id": "", "device_ms": 0.0, "degraded": False,
        }

    def _live_payload(self, item: _Check):
        if item.kind == "agg":
            return {str(k): v for k, v in item.live.items()}
        if item.kind == "count":
            return int(item.live)
        return list(item.live)

    def _write_bundle(self, item: _Check, report: DivergenceReport):
        if not self.bundle_dir:
            return None
        doc = {
            "kind": "geomesa-audit-repro-bundle",
            "version": 1,
            "check": item.kind,
            "event": self._bundle_event(item),
            "epoch": list(item.epoch),
            "group_by": list(item.group_by or []),
            "value_cols": list(item.value_cols),
            "cutoff_ms": item.cutoff_ms,
            "live": self._live_payload(item),
            "detail": report.detail,
            "minimized": report.minimized,
        }
        path = os.path.join(
            self.bundle_dir,
            f"repro-{int(report.ts * 1000)}-{item.kind}-"
            f"{self.bundles_written}.json")
        try:
            os.makedirs(self.bundle_dir, exist_ok=True)
            with open(path, "w", encoding="utf-8") as fh:
                json.dump(doc, fh, indent=1, default=str)
        except OSError:
            return None  # a full disk must not fail the audit path
        return path

    # -- external-plane feed (trajectory corridor/interlink shadow checks) ----
    def note_check(self, kind: str, ok: bool, type_name: str = "",
                   detail: str = "", abstain: bool = False) -> None:
        """Fold one externally-executed shadow comparison into the audit
        counters (``geomesa_audit_*{kind=...}``) — the trajectory plane's
        corridor/interlink engines compare their device results against
        the demoted host referees themselves (inside ``shadow()``) and
        report the verdict here; divergences raise the same ``A_DIVERGE``
        flight anomaly as query divergences."""
        with self._lock:
            self._count(self.checked, kind)
            if abstain:
                self._count(self.abstained, kind)
            elif ok:
                self._count(self.passed, kind)
            else:
                self._count(self.diverged, kind)
        if not ok and not abstain:
            from geomesa_tpu.obs import flight as _flight

            report = DivergenceReport(
                ts=self._clock(), kind=kind, type_name=type_name,
                filter_text="", epoch=None, detail=detail)
            with self._lock:
                self.divergences.append(report)
            _flight.record(
                op=kind, type_name=type_name, source="audit",
                plan=detail[:200], rows=0, anomalies=(_flight.A_DIVERGE,))

    # -- sweeper feed ---------------------------------------------------------
    def note_sweep(self, name: str, result: dict) -> None:
        kind = f"sweep:{name}"
        with self._lock:
            self._count(self.checked, kind)
            if result.get("abstained"):
                self._count(self.abstained, kind)
            elif result.get("violations"):
                self._count(self.diverged, kind)
            else:
                self._count(self.passed, kind)
            self._sweeps[name] = result
        if result.get("violations"):
            from geomesa_tpu.obs import flight as _flight

            report = DivergenceReport(
                ts=self._clock(), kind=kind,
                type_name=result.get("type_name", ""),
                filter_text="", epoch=None,
                detail="; ".join(str(v) for v in result["violations"][:4]),
            )
            with self._lock:
                self.divergences.append(report)
            _flight.record(
                op=kind, type_name=report.type_name, source="audit",
                plan=report.detail[:200], rows=0,
                anomalies=(_flight.A_DIVERGE,),
            )

    # -- read surface ---------------------------------------------------------
    def snapshot(self, limit: int = 32) -> dict:
        """The ``GET /api/obs/audit`` payload."""
        with self._lock:
            kinds = sorted(set(self.checked) | set(_CHECK_KINDS))
            counters = {
                k: {
                    "checked": self.checked.get(k, 0),
                    "passed": self.passed.get(k, 0),
                    "diverged": self.diverged.get(k, 0),
                    "abstained": self.abstained.get(k, 0),
                }
                for k in kinds
            }
            div = [asdict(d) for d in list(self.divergences)[-limit:]]
            sweeps = {k: dict(v) for k, v in self._sweeps.items()}
            out = {
                "rate": _rate,
                "enabled": ENABLED,
                "queue_depth": len(self._queue),
                "dropped": self.dropped,
                "errors": self.errors,
                "bundles_written": self.bundles_written,
                "bundle_dir": self.bundle_dir,
                "checks": counters,
                "divergences": div,
                "sweeps": sweeps,
            }
        return out

    def prometheus_lines(self, prefix: str = "geomesa") -> list[str]:
        with self._lock:
            kinds = sorted(set(self.checked) | set(_CHECK_KINDS))
            tables = (("checked", self.checked), ("passed", self.passed),
                      ("diverged", self.diverged),
                      ("abstained", self.abstained))
            lines: list[str] = []
            for name, table in tables:
                metric = f"{prefix}_audit_{name}_total"
                lines.append(f"# TYPE {metric} counter")
                for k in kinds:
                    lines.append(f'{metric}{{kind="{k}"}} {table.get(k, 0)}')
            lines.append(f"# TYPE {prefix}_audit_dropped_total counter")
            lines.append(f"{prefix}_audit_dropped_total {self.dropped}")
            lines.append(f"# TYPE {prefix}_audit_bundles_total counter")
            lines.append(
                f"{prefix}_audit_bundles_total {self.bundles_written}")
        return lines

    def prometheus_text(self, prefix: str = "geomesa") -> str:
        return "\n".join(self.prometheus_lines(prefix)) + "\n"


# -- invariant sweeps ---------------------------------------------------------

@shadow_plane
class InvariantSweeper:
    """Periodic validator of structural invariants shadow queries cannot
    see. Attach surfaces (``attach_store`` / ``attach_view`` /
    ``attach_stream`` / ``attach_matrix``), then either run
    :meth:`sweep_once` explicitly (tests, CLI) or :meth:`start` the
    background thread. Every check result feeds the auditor's
    ``sweep:<name>`` counters; violations raise ``A_DIVERGE`` flight
    anomalies through the same path as query divergences."""

    # (bin, cell, group) partials below this compare in ONE vectorized
    # recount per sweep (deterministic full coverage); above it the
    # rotating cell sample bounds per-sweep cost
    FULL_COMPARE_CELLS = 1 << 22

    def __init__(self, auditor: "ContinuousAuditor | None" = None,
                 interval_s: float = 10.0, cell_sample: int = 16):
        self._auditor = auditor
        self.interval_s = interval_s
        self.cell_sample = cell_sample
        self._lock = threading.Lock()  # leaf: target lists + cursors
        self._stores: list = []  # weakrefs to DataStore
        self._views: list = []  # weakrefs to ShardedDataStoreView
        self._streams: list = []  # weakrefs to streaming stores
        self._matrices: list = []  # weakrefs to SubscriptionMatrix
        self._tracks: list = []  # weakrefs to trajectory TrackState
        self._pools: list = []  # weakrefs to BufferPool (tier coherence)
        self._pyr_cursor = 0  # rotating cell-sample cursor
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.sweep_count = 0

    @property
    def auditor(self) -> ContinuousAuditor:
        return self._auditor if self._auditor is not None else get()

    def _attach(self, bucket: list, obj) -> None:
        with self._lock:
            bucket[:] = [r for r in bucket if r() is not None]
            if not any(r() is obj for r in bucket):
                bucket.append(weakref.ref(obj))

    def attach_store(self, store) -> None:
        self._attach(self._stores, store)

    def attach_view(self, view) -> None:
        self._attach(self._views, view)

    def attach_stream(self, store) -> None:
        self._attach(self._streams, store)

    def attach_matrix(self, matrix) -> None:
        self._attach(self._matrices, matrix)

    def attach_track_state(self, state) -> None:
        self._attach(self._tracks, state)

    def attach_pool(self, pool) -> None:
        self._attach(self._pools, pool)

    def start(self) -> None:
        with self._lock:
            if self._thread is not None:
                return
            self._thread = threading.Thread(
                target=self._run, name="geomesa-audit-sweeper", daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=10.0)

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.sweep_once()
            except Exception:  # noqa: BLE001 — a sweep bug must not kill the thread
                pass

    def _targets(self, bucket: list) -> list:
        with self._lock:
            objs = [r() for r in bucket]
            bucket[:] = [r for r in bucket if r() is not None]
        return [o for o in objs if o is not None]

    def sweep_once(self) -> list[dict]:
        """One pass over every attached surface; returns the per-check
        results (also folded into the auditor counters). Runs in audit
        shadow: the standing-count check issues real ``store.query``
        calls, and sweep traffic must stay invisible to the feedback
        planes (and must never be sampled into fresh audit checks)."""
        out: list[dict] = []
        with shadow():
            for store in self._targets(self._stores):
                out.append(self.check_pyramids(store))
                out.append(self.check_ledger(store))
                out.append(self.check_query_cache(store))
                out.append(self.check_wal(store))
            for view in self._targets(self._views):
                out.append(self.check_shard_coverage(view))
            for m in self._targets(self._matrices):
                out.append(self.check_matrix_sentinels(m))
            for s in self._targets(self._streams):
                out.append(self.check_standing_counts(s))
            for ts in self._targets(self._tracks):
                out.append(self.check_track_state(ts))
            for pool in self._targets(self._pools):
                out.append(self.check_tiering(pool))
        for r in out:
            self.auditor.note_sweep(r["check"], r)
        with self._lock:
            self.sweep_count += 1
        return out

    # -- individual checks ----------------------------------------------------
    def check_wal(self, store) -> dict:
        """WAL/checkpoint invariants for durability-plane stores
        (docs/operations.md § Durability & recovery): every type's applied
        seq is at/below the WAL's seq high-water (an applied mutation the
        journal never issued a seq for cannot exist); each topic's trimmed
        head never passes its commit offset (a trim past the commit would
        have destroyed un-checkpointed records); and the manifest's replay
        floors never exceed the live applied seqs (a stamp ahead of the
        state would make recovery skip acked records). No-WAL stores
        report zero checks."""
        result = {"check": "wal", "checked": 0, "violations": [],
                  "abstained": 0}
        wal = getattr(store, "_wal", None)
        if wal is None:
            return result
        live: dict[str, int] = {}
        for name, st in list(store._types.items()):
            with st.lock:
                live[name] = st.wal_seq
        # high-water read AFTER the applied seqs: a write landing between
        # the two reads makes the (stale) seq <= the (fresh) high-water —
        # the reverse order false-alarmed on every concurrent write
        high = wal.seq_highwater()
        for name, seq in live.items():
            result["checked"] += 1
            if seq > high:
                result["violations"].append(
                    f"{name}: applied wal_seq {seq} > seq high-water {high}")
        try:
            for topic in wal.topics():
                result["checked"] += 1
                head = wal.bus.head_offset(topic)
                # the RAW sidecar value: committed_offset() clamps to
                # max(commit, head), which would make this check
                # unfalsifiable
                raw = wal.bus._read_commit(topic)
                if raw is None:
                    result["abstained"] += 1
                elif head > raw:
                    result["violations"].append(
                        f"{topic}: trimmed head {head} > commit {raw}")
        except OSError:
            result["abstained"] += 1
        catalog = getattr(store, "_wal_catalog", None)
        if catalog:
            import json as _json
            import os as _os

            from geomesa_tpu.store import persistence as _persist
            from geomesa_tpu.store import wal as _walmod

            mpath = _os.path.join(catalog, _persist.MANIFEST)
            try:
                stamps = (_json.loads(open(mpath).read())
                          .get("wal", {}).get("topics", {}))
            except (OSError, ValueError):
                stamps = {}
            for topic, stamp in stamps.items():
                name = _walmod.type_for(topic)
                if name is None or name not in live:
                    continue
                result["checked"] += 1
                if int(stamp) > live[name]:
                    # a concurrent checkpoint can stamp between our two
                    # reads — re-read before concluding (and the schema
                    # may have been deleted meanwhile: abstain, the next
                    # checkpoint drops its stamp)
                    st2 = store._types.get(name)
                    if st2 is None:
                        result["abstained"] += 1
                        continue
                    with st2.lock:
                        now = st2.wal_seq
                    if int(stamp) > now:
                        result["violations"].append(
                            f"{topic}: manifest stamp {stamp} > live "
                            f"applied seq {now}")
        return result

    def check_pyramids(self, store) -> dict:
        """Pyramid partials reconcile against base per (bin, cell) on a
        rotating cell sample: the finest level's per-group counts for K
        sampled (bin, cell) buckets must equal a fresh recount from the
        main tier (the same normalization the build used). Abstains when
        the epoch moves mid-check or no pyramid is live."""
        import numpy as np

        result = {"check": "pyramid", "checked": 0, "violations": [],
                  "abstained": 0}
        for name, st in list(store._types.items()):
            epoch = st.data_epoch()
            with st.lock:
                pyrs = dict(st.pyramids)
                main = st.table
            if main is None or not pyrs:
                continue
            for pkey, (pyr, stamp) in pyrs.items():
                if pyr is None:
                    continue
                if stamp != epoch[0]:
                    result["abstained"] += 1
                    continue
                try:
                    from geomesa_tpu.curve.binned_time import BinnedTime
                    from geomesa_tpu.curve.normalize import (
                        lat as norm_lat,
                        lon as norm_lon,
                    )
                    from geomesa_tpu.ops.geoblocks import COORD_BITS
                    from geomesa_tpu.store.backends import REFINE_PRECISION

                    col = main.geom_column()
                    xi = norm_lon(REFINE_PRECISION).normalize(
                        col.x).astype(np.int64)
                    yi = norm_lat(REFINE_PRECISION).normalize(
                        col.y).astype(np.int64)
                    if st.sft.dtg_field:
                        bins, _ = BinnedTime(
                            st.sft.z3_interval
                        ).to_bin_and_offset(main.dtg_millis())
                    else:
                        bins = np.zeros(len(main), dtype=np.int64)
                    fine = pyr.levels[-1]
                    nx = fine.nx
                    c = nx * nx
                    shift = COORD_BITS - fine.k
                    cell = (yi >> shift) * nx + (xi >> shift)
                    ti = np.searchsorted(pyr.bins_present,
                                         np.asarray(bins, np.int64))
                    t_n = len(pyr.bins_present)
                    total = t_n * c
                    g = max(len(pyr.keys), 1)
                    bucket = ti * c + cell
                    if total * g <= self.FULL_COMPARE_CELLS:
                        # small pyramid: one vectorized full recount —
                        # every (bin, cell, group) partial compared
                        expect = np.bincount(
                            bucket * g + pyr.gid.astype(np.int64),
                            minlength=total * g).astype(np.int64)
                        got = fine.cnt.reshape(-1).astype(np.int64)
                        bad = np.nonzero(expect != got)[0]
                        result["checked"] += total
                        for b in bad[:4]:
                            tb = int(b) // (c * g)
                            cb = (int(b) // g) % c
                            result["violations"].append(
                                f"{name}{list(pkey)}: (bin {tb}, cell "
                                f"{cb}) pyramid={int(got[b])} "
                                f"base={int(expect[b])}")
                    else:
                        # big pyramid: rotating (bin, cell) sample — the
                        # sweep covers the grid over successive passes
                        k = min(self.cell_sample, total)
                        with self._lock:
                            base_cur = self._pyr_cursor
                            self._pyr_cursor = (
                                (base_cur + k) % max(total, 1))
                        sample = (base_cur + np.arange(k)) % total
                        for b in sample:
                            tb, cb = int(b) // c, int(b) % c
                            rows = np.nonzero(bucket == b)[0]
                            expect = np.bincount(
                                pyr.gid[rows], minlength=g,
                            ).astype(np.int64)
                            got = fine.cnt[tb, cb, :].astype(np.int64)
                            result["checked"] += 1
                            if not np.array_equal(expect, got):
                                result["violations"].append(
                                    f"{name}{list(pkey)}: (bin {tb}, "
                                    f"cell {cb}) pyramid={got.sum()} "
                                    f"base={expect.sum()}")
                except (TypeError, ValueError):
                    result["abstained"] += 1
                    continue
                if st.data_epoch() != epoch:
                    # a mutation landed mid-recount: the comparison read
                    # torn state — retract anything it concluded
                    result["violations"] = [
                        v for v in result["violations"]
                        if not v.startswith(f"{name}[")]
                    result["abstained"] += 1
        result["abstained"] = int(result["abstained"])
        return result

    def check_ledger(self, store) -> dict:
        """Devmon-ledger vs ``TpuBackend.residency()`` agreement: every
        byte the live backend state holds must be registered (spatial/
        bbox groups), and the ledger must not exceed residency by more
        than the pool's reclaimable donation stash."""
        from geomesa_tpu.obs import devmon
        from geomesa_tpu.store.backends import TpuBackend

        result = {"check": "ledger", "checked": 0, "violations": [],
                  "abstained": 0}
        if not isinstance(store.backend, TpuBackend):
            return result
        res = devmon.ledger().resident()
        pool = getattr(store.backend, "pool", None)
        for name, st in list(store._types.items()):
            epoch = st.data_epoch()
            per_index = store.device_residency(name)["indices"]
            led = res.get(name, {})
            donated = 0
            if pool is not None:
                with pool._lock:
                    donated = sum(
                        e.nbytes for (t, _i, _f), e in
                        pool._donated.items() if t == name)
            for idx, nbytes in per_index.items():
                result["checked"] += 1
                groups = led.get(idx, {})
                reg = (groups.get(devmon.GROUP_SPATIAL, 0)
                       + groups.get(devmon.GROUP_BBOX, 0))
                if reg < nbytes:
                    if st.data_epoch() != epoch:
                        result["abstained"] += 1
                        continue
                    result["violations"].append(
                        f"{name}.{idx}: resident {nbytes} B but ledger "
                        f"registers {reg} B (unaccounted residency)")
                elif reg > nbytes + donated:
                    if st.data_epoch() != epoch:
                        result["abstained"] += 1
                        continue
                    result["violations"].append(
                        f"{name}.{idx}: ledger {reg} B exceeds resident "
                        f"{nbytes} B + donated {donated} B (leak)")
        return result

    def check_query_cache(self, store) -> dict:
        """Query-cache entry epochs still valid: an entry may be stale
        (it will miss and drop) but must never be stamped AHEAD of the
        live epoch (a future stamp would serve a dead table's answer
        once the epoch catches up) and must never outlive its schema
        (the delete/rename purge discipline)."""
        result = {"check": "query_cache", "checked": 0, "violations": [],
                  "abstained": 0}
        entries = store.agg_cache.entries_snapshot()
        for type_name, _key, epoch in entries:
            result["checked"] += 1
            st = store._types.get(type_name)
            if st is None:
                result["violations"].append(
                    f"cache entry for deleted/renamed schema "
                    f"{type_name!r} (epoch {epoch})")
                continue
            live = st.data_epoch()
            if tuple(epoch) > tuple(live):
                result["violations"].append(
                    f"{type_name}: entry epoch {tuple(epoch)} ahead of "
                    f"live {tuple(live)}")
        return result

    def check_shard_coverage(self, view) -> dict:
        """Sharded-view Z-domain coverage: the shard cuts partition the
        62-bit Z2 domain (disjoint and total) and every shard is owned
        by exactly one live member."""
        result = {"check": "shard_coverage", "checked": 1,
                  "violations": [], "abstained": 0}
        router = getattr(view, "router", None)
        if router is None:
            result["checked"] = 0
            return result
        result["violations"] = router.coverage_violations()
        return result

    def check_tiering(self, pool) -> dict:
        """Buffer-pool tier coherence (serving/elastic.py): a demoted
        (type, index) lives in exactly one lower tier, the warm tier
        respects its RAM budget, cold entries have their on-disk file,
        and demoted bytes are not still ledgered as device-resident —
        a two-tier copy or a stale ledger row would make the ops surface
        report HBM the device freed long ago."""
        result = {"check": "tiering", "checked": 1,
                  "violations": [], "abstained": 0}
        tier = getattr(pool, "_tiering", None)
        if tier is None:
            result["checked"] = 0
            return result
        result["violations"] = tier.coherence_violations()
        return result

    def check_matrix_sentinels(self, matrix) -> dict:
        """Subscription-matrix masked slots hold the unsatisfiable
        sentinel payload — a freed slot that could still match would
        deliver ghost hits to a dead subscription's successor."""
        result = {"check": "matrix_sentinels", "checked": 1,
                  "violations": [], "abstained": 0}
        result["violations"] = matrix.validate_sentinels()
        return result

    def check_track_state(self, state) -> dict:
        """Trajectory track-state CSR invariants (trajectory/state.py):
        entity offsets start at 0, never decrease, and sum to the row
        count; every entity's timestamps are nondecreasing in layout
        order — a violated CSR silently mis-aggregates EVERY per-entity
        statistic, which no query-level shadow check can see."""
        result = {"check": "track_state", "checked": 1,
                  "violations": [], "abstained": 0,
                  "type_name": getattr(state, "type_name", "")}
        result["violations"] = state.validate()
        return result

    def check_standing_counts(self, store) -> dict:
        """A standing query's cumulative delivered count cross-checked
        against ``store.query`` at the same epoch: delivered < exact is
        a missed delivery (contract violation); delivered > exact is the
        documented quantization superset (recorded, passing). Abstains
        unless the hub is fully drained and quiet around the check, and
        only audits subscriptions that observed the whole stream
        (registered before any ingest, or first-with-backlog-replay)."""
        result = {"check": "standing_counts", "checked": 0,
                  "violations": [], "abstained": 0, "loose_extra": 0}
        hubs = getattr(store, "_hubs", None)
        if hubs is None:
            return result
        for type_name, hub in hubs.items():
            if hub.lag() != 0:
                result["abstained"] += 1
                continue
            before = hub.rows_ingested()
            for sid, predicate in hub.matrix.standing():
                if predicate is None:
                    continue
                if hub.sub_base(sid) != 0:
                    result["abstained"] += 1
                    continue
                delivered = hub.scanner.total(sid)
                try:
                    exact = store.query(type_name, predicate).count
                except Exception:  # noqa: BLE001 — abstain on any query trouble
                    result["abstained"] += 1
                    continue
                if hub.rows_ingested() != before or hub.lag() != 0:
                    result["abstained"] += 1
                    continue
                result["checked"] += 1
                if delivered < exact:
                    result["violations"].append(
                        f"{type_name} sid={sid}: delivered {delivered} "
                        f"< exact {exact} (missed deliveries)")
                elif delivered > exact:
                    result["loose_extra"] += 1
        return result


# -- repro-bundle replay ------------------------------------------------------

def load_bundle(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    if doc.get("kind") != "geomesa-audit-repro-bundle":
        raise ValueError(f"{path!r} is not an audit repro bundle")
    return doc


def replay_bundle(store, path_or_doc) -> dict:
    """Re-execute a repro bundle against ``store``: run the live path
    and the referee for both the original and the minimized predicate
    (all in shadow — replay must not pollute the feedback planes) and
    report whether the divergence still reproduces."""
    from geomesa_tpu.ops import referee as _referee
    from geomesa_tpu.planning.planner import Query

    doc = (path_or_doc if isinstance(path_or_doc, dict)
           else load_bundle(path_or_doc))
    ev = doc["event"]
    type_name = ev["type"]
    check = doc.get("check", "select")

    def run_one(filt_text: str) -> dict:
        q = Query(filter=None if filt_text == "INCLUDE" else filt_text,
                  hints=dict(ev.get("hints") or {}),
                  auths=(list(ev["auths"])
                         if ev.get("auths") is not None else None))
        with shadow():
            st = store._types[type_name]
            if check == "agg":
                out = store.aggregate_many(
                    type_name, [q], group_by=doc.get("group_by"),
                    value_cols=doc.get("value_cols") or ())
                main, _i, _b, _s, delta = st.snapshot()
                ref = _referee.referee_agg(
                    st.sft, main, delta, q, doc.get("group_by"),
                    doc.get("value_cols") or (),
                    cutoff_ms=doc.get("cutoff_ms"))
                if out[0] is None:
                    # the live engine declined the batched path: the
                    # caller-side host fold IS the referee — no divergence
                    return {"filter": filt_text, "diverged": False,
                            "declined": True}
                lm = _referee.live_agg_map(
                    out[0], list(doc.get("value_cols") or ()))
                ok, detail = _referee.agg_equal(lm, ref)
                return {"filter": filt_text, "diverged": not ok,
                        "detail": detail}
            if check == "count":
                # the divergence came from the BATCHED exact-count lane:
                # replay it through count_many, not the select path
                live_n = int(store.count_many(
                    type_name, [q], loose=False)[0])
            else:
                live = store.query(type_name, q)
                live_n = live.count
            main, _i, _b, _s, delta = st.snapshot()
            ref_fids = _referee.referee_select(
                st.sft, main, delta, q)
            if check == "count":
                ok = live_n == len(ref_fids)
                detail = "" if ok else (
                    f"count live={live_n} referee={len(ref_fids)}")
            else:
                ok, detail = _referee.fid_sets_equal(
                    sorted(str(f) for f in live.table.fids), ref_fids)
            return {"filter": filt_text, "diverged": not ok,
                    "detail": detail,
                    "live_rows": live_n,
                    "referee_rows": len(ref_fids)}

    original = run_one(ev.get("filter") or "INCLUDE")
    minimized = None
    if doc.get("minimized") and doc["minimized"] != ev.get("filter"):
        minimized = run_one(doc["minimized"])
    return {
        "kind": "audit-bundle-replay",
        "check": check,
        "type": type_name,
        "recorded_detail": doc.get("detail", ""),
        "original": original,
        "minimized": minimized,
        "reproduced": bool(
            original["diverged"]
            or (minimized is not None and minimized["diverged"])),
    }


# -- process-wide singletons --------------------------------------------------

_auditor = ContinuousAuditor()


def get() -> ContinuousAuditor:
    return _auditor


def install(auditor: "ContinuousAuditor | None") -> ContinuousAuditor:
    """Swap the process auditor (tests / reconfiguration); returns the
    previous one. ``install(None)`` resets to a fresh env-configured
    auditor. The outgoing auditor's worker stops; installing an auditor
    that was previously swapped OUT (``install(old)``) revives it —
    its worker restarts on the next enqueue and ITS sampling rate is
    re-applied, so a swap-back restores coverage instead of silently
    enqueueing into a dead worker at the swapped-in rate."""
    global _auditor
    prev = _auditor
    if auditor is None:
        set_rate(_env_rate())
        auditor = ContinuousAuditor()
    else:
        set_rate(auditor.rate)
        with auditor._lock:
            if auditor._stop.is_set():  # closed by a prior swap-out
                auditor._stop = threading.Event()
                auditor._thread = None
    _auditor = auditor
    prev.close()
    return prev
