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
- the per-(type, plan-signature) rollup lives in the plan table
  (:mod:`geomesa_tpu.obs.plantable`, :func:`table`), which ``_audit``
  charges with each query's ledger. Its ``fusion_report()`` ranks
  signatures by host-choreography share
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

Locking: ``QueryLedger`` owns one leaf lock (metrics tier,
docs/concurrency.md); nothing blocking runs under it.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar

from geomesa_tpu.obs import trace as _trace

__all__ = [
    "QueryLedger", "roundtrip", "current", "note_dispatch",
    "materialize", "table",
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


def table():
    """The process-wide rollup: the plan table
    (:func:`geomesa_tpu.obs.plantable.table`), whose ``export()`` and
    ``fusion_report()`` read the charged rollup sums."""
    from geomesa_tpu.obs import plantable

    return plantable.table()
