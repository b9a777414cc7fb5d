"""Host-roundtrip ledger — per-query device-dispatch choreography accounting.

ROADMAP item 1 (whole-plan device compilation) needs evidence: WHICH plan
signatures pay for staged execution — multiple device dispatches per query
with host code (``np.asarray`` materializations, bound computations,
padding decisions) running between them — and which already run fused.
This module is that evidence plane:

- :class:`QueryLedger` — a per-query accumulator opened by the datastore
  around each query/select-many execution (:func:`roundtrip`). The jaxmon
  dispatch wrapper (:func:`geomesa_tpu.obs.jaxmon.observed`) reports every
  device dispatch into the live ledger via :func:`note_dispatch`; backend
  call sites report host sync points (``np.asarray`` on a device result —
  a ``block_until_ready`` in disguise) via :func:`materialize` /
  ``QueryLedger.note_sync``. Between consecutive device activities the
  ledger derives the INTER-STAGE HOST GAP: wall time where the device sat
  idle while host code choreographed the next dispatch.
- :class:`LedgerTable` — the bounded per-(type, plan-signature) rollup.
  ``fusion_report()`` ranks signatures by host-choreography share
  ``(host_gap_ms + sync_ms) / wall_ms`` — the work list for whole-plan
  compilation, served at ``GET /api/obs/fusion`` and
  ``geomesa-tpu obs fusion-report``.

Propagation is a ContextVar, exactly like devprof's profile context: the
context survives into the planner/backend call stack of the same logical
query, and a NESTED :func:`roundtrip` (a select-many fallback re-entering
``DataStore.query``) gets a FRESH inner ledger so the inner query's counts
are attributed to its own signature, not double-charged to the batch.

Overhead discipline: the off path (no roundtrip open — internal scans,
audit shadow traffic) costs one ContextVar read per dispatch. The on path
adds one leaf-lock acquisition per dispatch/sync against device calls that
cost milliseconds. No jax anywhere (``GEOMESA_TPU_NO_JAX=1`` safe).

Locking: ``QueryLedger`` and ``LedgerTable`` each own one leaf lock
(metrics tier, docs/concurrency.md); nothing blocking runs under either.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar

from geomesa_tpu.analysis.contracts import cache_surface, feedback_sink
from geomesa_tpu.obs import trace as _trace

__all__ = [
    "QueryLedger", "LedgerTable", "roundtrip", "current", "note_dispatch",
    "materialize", "table", "install",
    "EXPORT_KIND", "EXPORT_SCHEMA_VERSION",
]

# stable export schema consumed by `python -m geomesa_tpu.analysis --sync
# --reconcile` (analysis/sync/rules.py mirrors both constants; a version
# bump there must land together with one here). The export is raw rollup
# counters, NOT the derived fusion_report ranking — reconciliation needs
# exact dispatch totals, not shares.
EXPORT_KIND = "geomesa-tpu-roundtrip-ledger"
EXPORT_SCHEMA_VERSION = 1

_led_var: ContextVar[QueryLedger | None] = ContextVar(
    "geomesa_roundtrip_ledger", default=None)

# rollup-table cardinality cap: (type, signature) keys are bounded in
# practice (few types x few plan shapes), the cap is a safety valve against
# a pathological filter stream minting unbounded signatures
_MAX_ENTRIES = 256


class QueryLedger:
    """Per-query roundtrip accumulator. One instance per :func:`roundtrip`
    context; mutated from the query's own call stack (and, for federated
    members, pool threads carrying the copied context) — guarded by its
    own leaf lock."""

    __slots__ = ("dispatches", "compiles", "dispatch_ms", "syncs", "sync_ms",
                 "host_gap_ms", "h2d_bytes", "d2h_bytes", "_last_end",
                 "_lock")

    def __init__(self) -> None:
        self.dispatches = 0
        self.compiles = 0
        self.dispatch_ms = 0.0
        self.syncs = 0
        self.sync_ms = 0.0
        self.host_gap_ms = 0.0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        # perf_counter stamp of the last device activity END (dispatch
        # return or sync completion); the next dispatch's start minus this
        # is the inter-stage host gap
        self._last_end = 0.0
        self._lock = threading.Lock()  # leaf: accumulator fields

    def note_dispatch(self, t0: float, t1: float, *, compiled: bool = False,
                      h2d_bytes: int = 0, d2h_bytes: int = 0) -> None:
        """One device dispatch spanning ``[t0, t1]`` (perf_counter secs)."""
        with self._lock:
            self.dispatches += 1
            if compiled:
                self.compiles += 1
            self.dispatch_ms += (t1 - t0) * 1000.0
            if self._last_end and t0 > self._last_end:
                self.host_gap_ms += (t0 - self._last_end) * 1000.0
            if t1 > self._last_end:
                self._last_end = t1
            self.h2d_bytes += h2d_bytes
            self.d2h_bytes += d2h_bytes

    def note_sync(self, t0: float, t1: float) -> None:
        """One host sync point (``np.asarray`` / ``block_until_ready`` on a
        device result) spanning ``[t0, t1]``. The wait itself is device
        drain, not host choreography — but its END restarts the gap clock:
        host code after the sync up to the next dispatch is choreography."""
        with self._lock:
            self.syncs += 1
            self.sync_ms += (t1 - t0) * 1000.0
            if self._last_end and t0 > self._last_end:
                self.host_gap_ms += (t0 - self._last_end) * 1000.0
            if t1 > self._last_end:
                self._last_end = t1

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "dispatches": self.dispatches,
                "compiles": self.compiles,
                "dispatch_ms": self.dispatch_ms,
                "syncs": self.syncs,
                "sync_ms": self.sync_ms,
                "host_gap_ms": self.host_gap_ms,
                "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes,
            }


def current() -> QueryLedger | None:
    """The live ledger, if a roundtrip context is open on this
    context-propagation chain (None on the off path)."""
    return _led_var.get()


@contextmanager
def roundtrip():
    """Open a per-query ledger for the enclosed execution. Always a FRESH
    ledger: a nested roundtrip (select-many fallback re-entering
    ``DataStore.query``) attributes to its own signature. Yields the
    :class:`QueryLedger` so the closer can charge it to the rollup."""
    ql = QueryLedger()
    tok = _led_var.set(ql)
    try:
        yield ql
    finally:
        _led_var.reset(tok)


def note_dispatch(t0: float, t1: float, *, compiled: bool = False,
                  h2d_bytes: int = 0, d2h_bytes: int = 0) -> None:
    """Module-level dispatch hook for the jaxmon wrapper: one ContextVar
    read on the off path, one locked accumulate on the on path."""
    ql = _led_var.get()
    if ql is not None:
        ql.note_dispatch(t0, t1, compiled=compiled,
                         h2d_bytes=h2d_bytes, d2h_bytes=d2h_bytes)


def materialize(obj):
    """``np.asarray`` with sync-point accounting: the canonical way for a
    backend to pull a device result to host. Off path (no ledger open)
    degrades to a bare ``np.asarray``. Runs inside a ``sync`` span, so a
    traced query shows the wait for the device (and the copy back) as
    its own stage."""
    import numpy as np

    ql = _led_var.get()
    with _trace.span("sync"):
        if ql is None:
            return np.asarray(obj)
        t0 = time.perf_counter()
        out = np.asarray(obj)
        ql.note_sync(t0, time.perf_counter())
    return out


class _Rollup:
    """One (type, plan-signature) rollup row."""

    __slots__ = ("queries", "dispatches", "compiles", "dispatch_ms", "syncs",
                 "sync_ms", "host_gap_ms", "wall_ms", "h2d_bytes",
                 "d2h_bytes")

    def __init__(self) -> None:
        self.queries = 0
        self.dispatches = 0
        self.compiles = 0
        self.dispatch_ms = 0.0
        self.syncs = 0
        self.sync_ms = 0.0
        self.host_gap_ms = 0.0
        self.wall_ms = 0.0
        self.h2d_bytes = 0
        self.d2h_bytes = 0


@cache_surface(name="roundtrip-ledger", keyed_by="type_name",
               purge=("forget",))
class LedgerTable:
    """Bounded per-(type, plan-signature) roundtrip rollup. Entries for a
    dropped/renamed type are purged via :meth:`forget` alongside the cost
    table (``DataStore._purge_type_name``) — stale signatures must not
    keep ranking in the fusion report after their schema is gone."""

    def __init__(self, max_entries: int = _MAX_ENTRIES):
        self._lock = threading.Lock()  # leaf: rollup table
        self._max = max_entries
        self._rows: dict[tuple[str, str], _Rollup] = {}

    @feedback_sink
    def charge(self, type_name: str, signature: str, ql: QueryLedger,
               wall_ms: float) -> None:
        """Fold one query's ledger into the (type, signature) rollup. A
        coalesced batch charges the SHARED ledger once per member query —
        every signature served by the batched dispatch sees its counts."""
        snap = ql.snapshot()
        key = (type_name, signature)
        with self._lock:
            row = self._rows.get(key)
            if row is None:
                if len(self._rows) >= self._max:
                    # drop the coldest row (fewest queries) — cardinality
                    # valve, not an accuracy surface
                    coldest = min(self._rows, key=lambda k: self._rows[k].queries)
                    del self._rows[coldest]
                row = self._rows[key] = _Rollup()
            row.queries += 1
            row.dispatches += snap["dispatches"]
            row.compiles += snap["compiles"]
            row.dispatch_ms += snap["dispatch_ms"]
            row.syncs += snap["syncs"]
            row.sync_ms += snap["sync_ms"]
            row.host_gap_ms += snap["host_gap_ms"]
            row.wall_ms += max(wall_ms, 0.0)
            row.h2d_bytes += snap["h2d_bytes"]
            row.d2h_bytes += snap["d2h_bytes"]

    def forget(self, type_name: str) -> None:
        """Purge every rollup row for ``type_name`` (schema delete/rename)."""
        with self._lock:
            for key in [k for k in self._rows if k[0] == type_name]:
                del self._rows[key]

    def fusion_report(self, limit: int = 50) -> list[dict]:
        """Plan signatures ranked by host-choreography share — the fraction
        of wall time spent in inter-stage host gaps plus sync waits. High
        share + multiple dispatches per query = a fusion opportunity
        (ROADMAP item 1 work list)."""
        with self._lock:
            items = list(self._rows.items())
        out = []
        for (type_name, sig), row in items:
            if row.queries == 0:
                continue
            wall = max(row.wall_ms, row.dispatch_ms + row.sync_ms
                       + row.host_gap_ms, 1e-9)
            share = min(1.0, (row.host_gap_ms + row.sync_ms) / wall)
            out.append({
                "type": type_name,
                "signature": sig,
                "queries": row.queries,
                "dispatches_per_query": row.dispatches / row.queries,
                "syncs_per_query": row.syncs / row.queries,
                "compiles": row.compiles,
                "host_gap_ms": round(row.host_gap_ms, 3),
                "sync_ms": round(row.sync_ms, 3),
                "dispatch_ms": round(row.dispatch_ms, 3),
                "wall_ms": round(row.wall_ms, 3),
                "host_share": round(share, 4),
                "h2d_bytes": row.h2d_bytes,
                "d2h_bytes": row.d2h_bytes,
            })
        out.sort(key=lambda r: (-r["host_share"], -r["wall_ms"]))
        return out[:limit]

    def snapshot(self) -> dict:
        return {"entries": self.fusion_report(limit=_MAX_ENTRIES)}

    def export(self) -> dict:
        """The stable reconcile-export document (``obs ledger-export``,
        ``GET /api/obs/ledger?format=json``): one entry per (type, plan
        signature) with the raw rollup counters. Consumers key off
        ``kind`` + ``schema_version`` and must reject anything else."""
        with self._lock:
            items = sorted(self._rows.items())
        return {
            "kind": EXPORT_KIND,
            "schema_version": EXPORT_SCHEMA_VERSION,
            "entries": [
                {
                    "type": type_name,
                    "signature": sig,
                    "queries": row.queries,
                    "dispatches": row.dispatches,
                    "compiles": row.compiles,
                    "syncs": row.syncs,
                    "dispatch_ms": round(row.dispatch_ms, 3),
                    "sync_ms": round(row.sync_ms, 3),
                    "host_gap_ms": round(row.host_gap_ms, 3),
                    "wall_ms": round(row.wall_ms, 3),
                    "h2d_bytes": row.h2d_bytes,
                    "d2h_bytes": row.d2h_bytes,
                }
                for (type_name, sig), row in items
            ],
        }


_table = LedgerTable()


def table() -> LedgerTable:
    """The process-wide rollup table."""
    return _table


def install(tbl: LedgerTable) -> LedgerTable:
    """Swap the process-wide table (tests); returns the previous one."""
    global _table
    prev = _table
    _table = tbl
    return prev
