"""Device telemetry: HBM residency ledger, per-query device-time
attribution, and the cost-profile accessor and sidecar.

The rest of the obs stack sees the *host* pipeline end to end; this module
opens the device black box with three substrates (docs/observability.md
§ Device telemetry & cost profiles):

- :class:`ResidencyLedger` — every device allocation the backend makes
  (:meth:`geomesa_tpu.store.backends.TpuBackend.load`, the grouped-agg
  staging cache) registers here as (type, index, column group, bytes),
  and unregisters automatically when the owning state object is dropped
  (eviction, reload, compaction — a ``weakref.finalize`` per entry, so no
  invalidation protocol can be forgotten). Exposes live
  ``geomesa_device_resident_bytes{type,index,group}`` gauges, headroom
  against the backend's ``max_device_bytes`` budget, and the
  host-resident-spill report — the accounting layer a buffer-pool
  eviction policy (ROADMAP item 1) sits on.

- :func:`profiled` / :class:`DevProfile` — the sampled per-query
  device-time attribution mode (``GEOMESA_TPU_DEVPROF`` env or the
  ``devprof`` query hint). While a profile is active on the context,
  :func:`geomesa_tpu.obs.jaxmon.observed` brackets each cached-jit
  dispatch with ``block_until_ready`` timing so the query's wall time
  splits into compile / dispatch / device-compute / h2d / d2h. The
  OFF path costs one module-global flag check per dispatch (the <2%
  bound on the cached-jit select path is asserted in
  ``tests/test_devmon.py`` and gated in ``scripts/lint.sh``).

- :func:`costs` — the online per-(type, plan-signature) cost profiles
  (p50/p95 device-ms and wall-ms, bytes scanned, rows returned) kept in
  the plan table (:mod:`geomesa_tpu.obs.plantable`), served at
  ``GET /api/obs/costs``, rendered by ``explain(analyze=True)`` as
  predicted-vs-actual, consumed by the adaptive planner, and persisted
  to the ``costs.json`` sidecar (:func:`save_cost_snapshot`).

Locking: the ledger owns one leaf lock (same tier as the metrics-registry
locks — docs/concurrency.md); no blocking calls run under it. No jax at
module level (``GEOMESA_TPU_NO_JAX=1`` safe).
"""

from __future__ import annotations

import os
import random
import threading
import weakref
from contextlib import contextmanager
from contextvars import ContextVar

from geomesa_tpu.analysis.contracts import cache_surface
from geomesa_tpu.obs import plantable
from geomesa_tpu.obs.plantable import PlanTable

__all__ = [
    "DEVPROF_ENV", "DevProfile", "ResidencyLedger",
    "cost_sidecar_path", "costs", "current_profile", "device_report",
    "install", "ledger", "load_cost_snapshot", "plan_signature",
    "profiled", "prometheus_text", "purge_persisted_costs",
    "sampled", "save_cost_snapshot",
]

DEVPROF_ENV = "GEOMESA_TPU_DEVPROF"

# canonical column-group names (the residency unit ROADMAP item 1's
# eviction policy will reason about)
GROUP_SPATIAL = "spatial"  # x/y/bins/offs point layout
GROUP_BBOX = "bbox"  # xmin/ymin/xmax/ymax/bins/offs overlap layout
GROUP_AGG = "agg"  # grouped-aggregation staging (gid/rowid/value cols)
GROUP_PYRAMID = "pyramid"  # GeoBlocks pre-aggregation pyramid levels


# -- HBM residency ledger -----------------------------------------------------

@cache_surface(name="spill-ledger", keyed_by="type_name",
               purge=("clear_spills",))
class ResidencyLedger:
    """Process-wide registry of live device allocations.

    Entries are (type, index, group, bytes), keyed by an opaque token;
    when an ``owner`` object is supplied at registration the entry
    auto-unregisters when that object is garbage collected — the drop /
    donate / reload paths need no explicit bookkeeping, they just stop
    referencing the old state. One leaf lock; every method is O(entries)
    or better and never blocks under it."""

    def __init__(self):
        self._lock = threading.Lock()  # leaf: entries + spills + budget
        self._seq = 0
        self._entries: dict[int, tuple] = {}  # token -> (type, index, group, bytes)
        self._finalizers: dict[int, object] = {}
        # host-resident spill report: (type, index) -> estimated bytes the
        # budget refused (the index serves from the host path instead)
        self._spills: dict[tuple, int] = {}
        self._budget: int | None = None
        self.register_count = 0  # lifetime registrations (ops surface)

    # -- write surface (the backend's side) -----------------------------------
    def set_budget(self, budget_bytes: int | None) -> None:
        with self._lock:
            self._budget = budget_bytes

    def begin_load(self, type_name: str) -> None:
        """A fresh load for ``type_name`` is starting: clear its spill
        report (the load re-records any indexes that still don't fit)."""
        self.clear_spills(type_name)

    def register(self, type_name: str, index: str, group: str,
                 nbytes: int, owner=None) -> int:
        """Record one live device allocation; returns the entry token.
        With ``owner``, the entry unregisters itself when ``owner`` is
        garbage collected (the state-object lifetime IS the allocation
        lifetime for every backend path)."""
        with self._lock:
            self._seq += 1
            token = self._seq
            self._entries[token] = (type_name, index, group, int(nbytes))
            self.register_count += 1
        if owner is not None:
            fin = weakref.finalize(owner, self.unregister, token)
            fin.atexit = False  # telemetry: never delay interpreter exit
            with self._lock:
                self._finalizers[token] = fin
        return token

    def unregister(self, token: int) -> None:
        with self._lock:
            self._entries.pop(token, None)
            self._finalizers.pop(token, None)

    def unregister_matching(self, type_name: str, index: str) -> int:
        """Drop every live entry of one ``(type, index)`` — the tiering
        policy's demotion path (serving/elastic.py): the owner object
        stays ALIVE holding host/disk copies, so its GC finalizer cannot
        fire, yet the bytes have left the device and must leave the
        ledger with them (the ledger-vs-residency agreement pinned in
        tests). The orphaned finalizers later no-op against the already-
        removed tokens. Returns the bytes unregistered."""
        with self._lock:
            tokens = [
                t for t, e in self._entries.items()
                if e[0] == type_name and e[1] == index
            ]
            freed = 0
            for t in tokens:
                freed += self._entries.pop(t)[3]
                self._finalizers.pop(t, None)
            return freed

    def record_spill(self, type_name: str, index: str, est_bytes: int) -> None:
        with self._lock:
            self._spills[(type_name, index)] = int(est_bytes)

    def clear_spills(self, type_name: str) -> None:
        with self._lock:
            for k in [k for k in self._spills if k[0] == type_name]:
                del self._spills[k]

    # -- read surface ---------------------------------------------------------
    def total_bytes(self) -> int:
        with self._lock:
            return sum(e[3] for e in self._entries.values())

    def type_bytes(self, type_name: str) -> int:
        with self._lock:
            return sum(
                e[3] for e in self._entries.values() if e[0] == type_name
            )

    def index_bytes(self, type_name: str, index: str) -> int:
        """Live device bytes held by one (type, index) across groups —
        the bytes-scanned denominator the cost table records."""
        with self._lock:
            return sum(
                e[3] for e in self._entries.values()
                if e[0] == type_name and e[1] == index
            )

    def resident(self) -> dict:
        """``{type: {index: {group: bytes}}}`` for every live entry
        (entries sharing a key sum — reload overlap windows show both)."""
        out: dict = {}
        with self._lock:
            entries = list(self._entries.values())
        for t, i, g, b in entries:
            grp = out.setdefault(t, {}).setdefault(i, {})
            grp[g] = grp.get(g, 0) + b
        return out

    def snapshot(self) -> dict:
        """The ``device`` section of ``/api/metrics``: per-(type, index,
        group) resident bytes, budget headroom, and the spill report.

        ``budget_bytes`` applies PER TYPE (the ``TpuBackend`` contract —
        a store holding T types can reach T × budget), so
        ``headroom_bytes`` reports the MOST CONSTRAINED type: budget
        minus the largest per-type total. A single-type process reads it
        as plain budget-minus-resident."""
        with self._lock:
            entries = list(self._entries.values())
            spills = dict(self._spills)
            budget = self._budget
            registered = self.register_count
        resident: dict = {}
        total = 0
        per_type: dict = {}
        for t, i, g, b in entries:
            grp = resident.setdefault(t, {}).setdefault(i, {})
            grp[g] = grp.get(g, 0) + b
            per_type[t] = per_type.get(t, 0) + b
            total += b
        return {
            "resident": resident,
            "total_bytes": total,
            "budget_bytes": budget,
            "headroom_bytes": (
                budget - max(per_type.values(), default=0)
                if budget is not None else None
            ),
            "spilled": {f"{t}.{i}": b for (t, i), b in spills.items()},
            "spilled_bytes": sum(spills.values()),
            "register_count": registered,
        }

    def prometheus_lines(self, prefix: str = "geomesa") -> list[str]:
        """Labeled residency gauges (the exposition
        :func:`geomesa_tpu.obs.export.prometheus_text` cannot emit —
        appended to the scrape the way the SLO engine's lines are)."""
        snap = self.snapshot()
        lines = [f"# TYPE {prefix}_device_resident_bytes gauge"]
        for t, per_index in sorted(snap["resident"].items()):
            for i, per_group in sorted(per_index.items()):
                for g, b in sorted(per_group.items()):
                    lines.append(
                        f'{prefix}_device_resident_bytes'
                        f'{{type="{t}",index="{i}",group="{g}"}} {b}'
                    )
        lines.append(f"# TYPE {prefix}_device_resident_bytes_total gauge")
        lines.append(
            f"{prefix}_device_resident_bytes_total {snap['total_bytes']}")
        if snap["budget_bytes"] is not None:
            lines.append(f"# TYPE {prefix}_device_budget_bytes gauge")
            lines.append(
                f"{prefix}_device_budget_bytes {snap['budget_bytes']}")
            lines.append(f"# TYPE {prefix}_device_headroom_bytes gauge")
            lines.append(
                f"{prefix}_device_headroom_bytes {snap['headroom_bytes']}")
        if snap["spilled"]:
            lines.append(f"# TYPE {prefix}_device_spilled_bytes gauge")
            for key, b in sorted(snap["spilled"].items()):
                t, _, i = key.rpartition(".")
                lines.append(
                    f'{prefix}_device_spilled_bytes'
                    f'{{type="{t}",index="{i}"}} {b}'
                )
        return lines


# -- per-query device-time attribution ---------------------------------------

class DevProfile:
    """Accumulator for one profiled query's device-time attribution.

    Stage totals (ms): ``compile`` (cold jit trace+lower+compile),
    ``dispatch`` (warm host-side dispatch until the async call returns),
    ``device_compute`` (``block_until_ready`` wait), ``h2d`` (timed
    host→device staging of numpy arguments), ``d2h`` (timed
    materialization of results back to host). Byte counters ride along.
    Locked: the watchdog may run the scan on a worker thread while the
    caller's thread owns the context (contexts are copied into workers)."""

    __slots__ = ("_lock", "compile_ms", "dispatch_ms", "device_ms",
                 "h2d_ms", "d2h_ms", "h2d_bytes", "d2h_bytes",
                 "dispatches", "compiles", "steps")

    def __init__(self):
        self._lock = threading.Lock()
        self.compile_ms = 0.0
        self.dispatch_ms = 0.0
        self.device_ms = 0.0
        self.h2d_ms = 0.0
        self.d2h_ms = 0.0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.dispatches = 0
        self.compiles = 0
        self.steps: dict[str, dict] = {}

    def note_h2d(self, nbytes: int) -> None:
        """Attribute pre-staged payload bytes (``jaxmon.count_h2d`` with a
        query-side label) to THIS query, without counting a dispatch.
        Pool-labeled staging (a buffer-pool warm-up the query merely
        triggered) never lands here — per-query h2d splits stay truthful."""
        with self._lock:
            self.h2d_bytes += int(nbytes)

    def add(self, step: str, *, compile_ms=0.0, dispatch_ms=0.0,
            device_ms=0.0, h2d_ms=0.0, d2h_ms=0.0,
            h2d_bytes=0, d2h_bytes=0) -> None:
        with self._lock:
            self.compile_ms += compile_ms
            self.dispatch_ms += dispatch_ms
            self.device_ms += device_ms
            self.h2d_ms += h2d_ms
            self.d2h_ms += d2h_ms
            self.h2d_bytes += h2d_bytes
            self.d2h_bytes += d2h_bytes
            self.dispatches += 1
            if compile_ms:
                self.compiles += 1
            s = self.steps.setdefault(
                step, {"calls": 0, "ms": 0.0, "device_ms": 0.0})
            s["calls"] += 1
            s["ms"] += compile_ms + dispatch_ms + device_ms
            s["device_ms"] += device_ms

    @property
    def total_ms(self) -> float:
        return (self.compile_ms + self.dispatch_ms + self.device_ms
                + self.h2d_ms + self.d2h_ms)

    def breakdown(self) -> dict:
        """The flight-record / explain payload: stage → ms splits plus
        transfer bytes and dispatch counts."""
        with self._lock:
            return {
                "compile": round(self.compile_ms, 3),
                "dispatch": round(self.dispatch_ms, 3),
                "device_compute": round(self.device_ms, 3),
                "h2d": round(self.h2d_ms, 3),
                "d2h": round(self.d2h_ms, 3),
                "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes,
                "dispatches": self.dispatches,
                "compiles": self.compiles,
                "steps": {k: dict(v) for k, v in self.steps.items()},
            }


_prof_var: ContextVar["DevProfile | None"] = ContextVar(
    "geomesa_devprof", default=None)
_active_lock = threading.Lock()
_active_count = 0
# THE one check jaxmon.observed pays per dispatch when profiling is off:
# a module-global bool, flipped only while >=1 profiled() context is live
PROFILING = False

# deterministic-enough per-process sampler stream (independent of the
# global random state so tests that seed random stay unperturbed)
_sampler = random.Random()
_sampler_lock = threading.Lock()


def env_rate() -> float:
    """The ``GEOMESA_TPU_DEVPROF`` sampling rate: unset/0 → off, ``1`` /
    ``true`` → every query, a float in (0, 1] → that fraction. Read per
    call so operators (and tests) can flip it live."""
    raw = os.environ.get(DEVPROF_ENV, "").strip().lower()
    if not raw or raw in ("0", "false", "off", "no"):
        return 0.0
    if raw in ("1", "true", "on", "yes"):
        return 1.0
    try:
        rate = float(raw)
    except ValueError:
        return 0.0
    return min(max(rate, 0.0), 1.0)


def sampled(hint=None) -> bool:
    """Should THIS query be device-profiled? An explicit per-query hint
    (``hints={"devprof": True/False}``) always wins; otherwise sample at
    the env rate."""
    if hint is not None:
        return bool(hint)
    rate = env_rate()
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    with _sampler_lock:
        return _sampler.random() < rate


def current_profile() -> "DevProfile | None":
    """The live profile on this context (None when this query is not
    being profiled). Callers gate on :data:`PROFILING` first so the off
    path never pays the ContextVar read."""
    return _prof_var.get()


@contextmanager
def profiled():
    """Activate device-time attribution for this call tree. Nested
    activations share the OUTER profile (``explain(analyze=True)``
    wraps ``query()``, which may itself sample — the records must land
    in one accumulator, not split across two)."""
    global PROFILING
    existing = _prof_var.get()
    if existing is not None:
        yield existing
        return
    prof = DevProfile()
    with _active_lock:
        _active_count_inc()
    tok = _prof_var.set(prof)
    try:
        yield prof
    finally:
        _prof_var.reset(tok)
        with _active_lock:
            _active_count_dec()


def _active_count_inc():
    global _active_count, PROFILING
    _active_count += 1
    PROFILING = True


def _active_count_dec():
    global _active_count, PROFILING
    _active_count -= 1
    PROFILING = _active_count > 0


# -- plan signatures ----------------------------------------------------------

def plan_signature(info, q=None) -> str:
    """The cost-table key for one executed plan: index choice, union arm
    count, aggregation kind, and a log2 bucket of the interval count —
    the plan *shape*, not the literal predicate, so repeated queries of
    the same shape share one cost profile (what the adaptive planner
    needs: costs per strategy, not per filter string)."""
    agg = "rows"
    if q is not None:
        hints = getattr(q, "hints", None) or {}
        for kind in ("density", "stats", "bin"):
            if hints.get(kind):
                agg = kind
                break
    if info is None:
        return f"scan:{agg}"
    parts = [getattr(info, "index_name", None) or "none"]
    n_iv = getattr(info, "n_intervals", 0)
    if n_iv:
        # next-power-of-two bucket: plan WIDTH matters, exact count is noise
        parts.append(f"iv{1 << max(int(n_iv) - 1, 0).bit_length()}")
    parts.append(agg)
    return ":".join(parts)


# -- process-wide singletons --------------------------------------------------

_ledger = ResidencyLedger()


def ledger() -> ResidencyLedger:
    return _ledger


def costs() -> PlanTable:
    """The cost profiles: the process-wide plan table."""
    return plantable.table()


def install(new_ledger: ResidencyLedger) -> ResidencyLedger:
    """Swap the process residency ledger (test isolation); returns the
    previous one. Entries registered against the OLD ledger keep
    unregistering against it — their finalizers captured the instance."""
    global _ledger
    prev, _ledger = _ledger, new_ledger
    return prev


def device_report() -> dict:
    """The ``device`` section of ``/api/metrics``: the residency snapshot
    plus process-wide transfer totals from the jax telemetry registry."""
    out = _ledger.snapshot()
    transfers = {"h2d_bytes": 0, "d2h_bytes": 0}
    from geomesa_tpu.obs import jaxmon

    if jaxmon.GLOBAL is not None:
        snap = jaxmon.GLOBAL.snapshot()
        for k, short in (("jax.transfer.h2d_bytes", "h2d_bytes"),
                         ("jax.transfer.d2h_bytes", "d2h_bytes"),
                         ("jax.transfer.h2d_bytes.pool", "h2d_bytes_pool")):
            if k in snap:
                transfers[short] = snap[k].get("count", 0)
    out["transfers"] = transfers
    out["devprof_rate"] = env_rate()
    return out


def prometheus_text(prefix: str = "geomesa") -> str:
    lines = _ledger.prometheus_lines(prefix)
    return "\n".join(lines) + "\n" if lines else ""


# -- cost-profile persistence (the GEOMESA_TPU_WORKLOAD_DIR sidecar) ----------
# Learned p50 rankings and calibration survive restarts: the plan table's
# cost profiles and calibration entries snapshot to costs.json next to
# the workload capture, loaded at store open (store.persistence.load) and
# saved at catalog save. Schema delete/rename purges the persisted rows
# along with the live ones (DataStore._purge_type_name).

COSTS_SIDECAR = "costs.json"


def cost_sidecar_path(path: str | None = None) -> "str | None":
    """The sidecar file path: explicit, or derived from
    ``GEOMESA_TPU_WORKLOAD_DIR`` (None when neither is set)."""
    if path is not None:
        return path
    d = os.environ.get("GEOMESA_TPU_WORKLOAD_DIR") or None
    return os.path.join(d, COSTS_SIDECAR) if d else None


@cache_surface(name="persisted-cost-sidecar", keyed_by="type_name",
               purge=("purge_persisted_costs",))
def save_cost_snapshot(path: str | None = None) -> "str | None":
    """Persist the live cost profiles + calibration state; returns the path
    written (None when no sidecar location is configured). Atomic
    (tmp + replace): a crash mid-save must not truncate the previous
    snapshot."""
    import json

    p = cost_sidecar_path(path)
    if p is None:
        return None
    tbl = plantable.table()
    doc = {
        "kind": "geomesa-cost-snapshot",
        "costs": tbl.to_state(),
        "calibration": tbl.calibration_state(),
    }
    os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
    tmp = p + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)
    os.replace(tmp, p)
    return p


def load_cost_snapshot(path: str | None = None) -> bool:
    """Load a persisted snapshot into the live plan table (no-op
    when the sidecar is absent/unreadable — a missing or corrupt snapshot
    must never fail a store open). Returns True when state loaded."""
    import json

    p = cost_sidecar_path(path)
    if p is None or not os.path.exists(p):
        return False
    try:
        with open(p, encoding="utf-8") as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        return False
    if doc.get("kind") != "geomesa-cost-snapshot":
        return False
    tbl = plantable.table()
    tbl.load_state(doc.get("costs", {}))
    tbl.load_calibration_state(doc.get("calibration", {}))
    return True


def purge_persisted_costs(type_name: str, path: str | None = None) -> None:
    """Drop one type's rows from the persisted sidecar (schema delete/
    rename: the successor type must not inherit the dead type's learned
    profile across a restart). Best-effort — a read-only sidecar never
    fails the schema operation."""
    import json

    p = cost_sidecar_path(path)
    if p is None or not os.path.exists(p):
        return
    try:
        with open(p, encoding="utf-8") as fh:
            doc = json.load(fh)
        costs_state = doc.get("costs", {})
        costs_state["entries"] = [
            e for e in costs_state.get("entries", [])
            if e.get("type") != type_name
        ]
        costs_state["ticks"] = [
            t for t in costs_state.get("ticks", []) if t[0] != type_name
        ]
        cal = doc.get("calibration", {})
        cal["entries"] = [
            e for e in cal.get("entries", [])
            if e.get("type") != type_name
        ]
        tmp = p + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
        os.replace(tmp, p)
    except (OSError, ValueError):
        return
