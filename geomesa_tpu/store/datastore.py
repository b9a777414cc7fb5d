"""DataStore: the top seam — schema CRUD, writes, queries (GeoTools role).

Reference: ``GeoMesaDataStore`` (``geomesa-index-api/.../geotools/
GeoMesaDataStore.scala:49``) + ``QueryPlanner.runQuery`` (SURVEY.md §3.3).
Host-side orchestration: schemas and the canonical columnar tables live here;
each write rebuilds index permutations and backend device state (bulk-load
semantics v1 — the streaming LSM delta tier is the lambda-pattern follow-up,
SURVEY.md §2.11).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any

import numpy as np

from geomesa_tpu import obs
from geomesa_tpu.analysis.contracts import (
    cache_surface,
    choreography_boundary,
    dispatch_budget,
    feedback_sink,
    mutation,
)
from geomesa_tpu.filter import ast
from geomesa_tpu.index.api import FeatureIndex
from geomesa_tpu.planning.planner import Query, QueryPlanner, build_indices
from geomesa_tpu.schema.columnar import FeatureTable
from geomesa_tpu.schema.sft import FeatureType, parse_spec
from geomesa_tpu.store.backends import ExecutionBackend, OracleBackend, TpuBackend

if TYPE_CHECKING:
    from geomesa_tpu.store.bufferpool import BufferPool

_BACKENDS = {"oracle": OracleBackend, "tpu": TpuBackend}


def _ttl_cutoff_ms(ttl_ms: int, now_ms: int | None = None) -> int:
    """THE age-off cutoff: rows with dtg >= cutoff are live. One definition
    shared by the query-time mask, the mesh aggregation mask, and physical
    age_off(), so the three can never drift."""
    import time as _time

    return (int(_time.time() * 1000) if now_ms is None else now_ms) - ttl_ms


# hints that are pure execution METADATA (attribution, never semantics):
# they change neither results nor the execution contract, so the batched
# and cached paths must not decline on them — the serving coalescer
# (serving/coalesce.py) stamps "tenant" on every query it batches so a
# shared dispatch meters each member query against ITS tenant
_METADATA_HINTS = frozenset({"tenant"})


def _semantic_hints(q) -> bool:
    """True when the query carries hints that can alter results or the
    execution contract (everything except the metadata set above)."""
    return any(k not in _METADATA_HINTS for k in q.hints)


def _pure_bbox_time(f: ast.Filter, sft: FeatureType) -> bool:
    """True when the filter is a conjunction of spatial-box/temporal
    primaries on the schema's DEFAULT geometry/date fields — fully
    expressible as int-domain (boxes, windows) with no residual, so the
    batched loose count covers it. Predicates on other attributes (which
    ``bounds.extract`` would silently treat as unconstrained) disqualify."""
    if isinstance(f, ast.Include):
        return True
    if isinstance(f, ast.BBox):
        return f.prop == sft.geom_field
    if isinstance(f, (ast.During, ast.TempOp)):
        return f.prop == sft.dtg_field
    if isinstance(f, ast.And):
        return all(_pure_bbox_time(c, sft) for c in f.children)
    return False


@dataclass
class QueryResult:
    """Materialized query result + plan trace + optional aggregates.

    Aggregates mirror the reference's push-down scan flavors (SURVEY.md §2.3):
    ``density`` (DensityScan grid), ``stats`` (StatsScan sketches), and
    ``bin_data`` (BinAggregatingScan byte stream).
    """

    table: FeatureTable
    row_ids: np.ndarray
    plan_info: Any = None
    density: np.ndarray | None = None  # (height, width) f64 weighted counts
    stats: dict | None = None  # label -> sketch
    bin_data: bytes | None = None
    # federation partial-results marker (MergedDataStoreView in `partial`
    # mode): True when one or more members were skipped; member_errors
    # carries (member_index, exception_type, message) per skipped member
    degraded: bool = False
    member_errors: list | None = None

    @property
    def count(self) -> int:
        return len(self.table)

    def records(self) -> list[dict]:
        return [self.table.record(i) for i in range(len(self.table))]


@dataclass
class ExplainAnalyze:
    """``EXPLAIN ANALYZE`` output: the static plan text plus the measured
    per-stage timeline (:class:`geomesa_tpu.obs.StageTimeline`) of one real
    execution. ``stages`` durations sum to ``wall_ms`` by construction (an
    ``other`` residual stage absorbs untraced time). ``device`` is the
    devprof attribution of the analyzed run (compile / dispatch /
    device-compute / h2d / d2h, ms + bytes); ``cost`` is the cost table's
    predicted-vs-actual for this plan shape (predicted is the table's p50
    BEFORE this run observed into it)."""

    plan: str
    timeline: Any
    hits: int
    device: "dict | None" = None
    cost: "dict | None" = None
    # buffer-pool / query-cache gauges at analyze time (hit/miss/eviction
    # counters + pyramid bytes — DataStore.cache_report)
    cache: "dict | None" = None
    # correctness-audit verdict for the analyzed execution (obs/audit.py:
    # pass / diverged / abstained), present when auditing is enabled —
    # the analyzed query runs with the "audit" hint and the auditor
    # drains synchronously so the verdict is available here
    audit: "dict | None" = None

    @property
    def stages(self) -> list:
        return self.timeline.stages

    @property
    def wall_ms(self) -> float:
        return self.timeline.wall_ms

    def __str__(self) -> str:
        out = f"{self.plan}\n{self.timeline.render()}"
        if self.device:
            out += "\n  Device time:"
            for k in ("compile", "dispatch", "device_compute", "h2d", "d2h"):
                out += f"\n    {k:<15s} {self.device.get(k, 0.0):10.3f} ms"
            out += (f"\n    transfers       h2d {self.device.get('h2d_bytes', 0)} B"
                    f" / d2h {self.device.get('d2h_bytes', 0)} B"
                    f" ({self.device.get('dispatches', 0)} dispatches)")
        if self.cost:
            pred = self.cost.get("predicted")
            pred_ms = pred.get("wall_ms_p50") if pred else None
            out += (
                f"\n  Cost profile [{self.cost.get('signature')}]: "
                + (f"predicted {pred_ms} ms p50 "
                   f"(n={pred.get('observations')})" if pred
                   else "no prior observations")
                + f", actual {self.cost.get('actual_ms')} ms"
            )
            cal = self.cost.get("calibration_error")
            if cal is not None:
                out += f" (calibration error {cal:.1%})"
            src = self.cost.get("strategy_source")
            if src:
                out += f"\n  Strategy source: {src}"
            for alt in self.cost.get("alternatives") or []:
                obs_txt = (
                    f"observed {alt['observed_ms_p50']} ms p50"
                    f" (n={alt['observations']})"
                    if alt.get("observed_ms_p50") is not None
                    else "no observations"
                )
                out += (
                    f"\n  Rejected: {alt['name']} ≈ "
                    f"{alt['est_rows']:.0f} rows, {obs_txt}"
                )
        if self.cache:
            ac = self.cache.get("agg_cache") or {}
            pool = self.cache.get("pool") or {}
            out += (
                f"\n  Cache: agg hits {ac.get('hits', 0)} / misses "
                f"{ac.get('misses', 0)} / evictions "
                f"{ac.get('evictions', 0)}; pool hits "
                f"{pool.get('hits', 0)} / misses {pool.get('misses', 0)}"
                f" / evictions {pool.get('evictions', 0)}"
            )
            pb = self.cache.get("pyramid_bytes") or {}
            if pb:
                out += "; pyramid bytes " + ", ".join(
                    f"{t}={b}" for t, b in sorted(pb.items()))
        if self.audit:
            out += (f"\n  Audit: {self.audit.get('verdict')} "
                    f"({self.audit.get('kind')}"
                    + (f": {self.audit['detail']}"
                       if self.audit.get("detail") else "")
                    + ")")
        return out + f"\n  Hits: {self.hits}"


# both derived-data caches ride this state object: the plan cache is
# valid only for the current `indices` object (identity-checked on every
# lookup/insert), the pyramids only for the current data epoch — and the
# epoch IS monotonic within one _TypeState lifetime (both caches die
# with the object, so the delete+recreate restart cannot serve them)
@cache_surface(name="plan-cache", keyed_by="indices-identity",
               purge=("purge_derived",))
@cache_surface(name="agg-pyramids", keyed_by="epoch", epoch="monotonic",
               purge=("purge_derived",))
@dataclass
class _TypeState:
    sft: FeatureType
    table: FeatureTable | None = None  # main (sorted, device-resident) tier
    indices: dict[str, FeatureIndex] = field(default_factory=dict)
    backend_state: Any = None
    stats: Any = None  # StoreStats
    delta: Any = None  # DeltaTier (hot append buffer)
    fid_seq: int = 0  # monotonic sequential-fid allocator (under `lock`)
    # main-tier rebuild epoch: bumps on every state swap (compact, delete,
    # age-off, evolution). With the delta tier's mutation version it forms
    # the DATA EPOCH stamped on cached aggregates (ops/geoblocks.py) and
    # the buffer pool's donation fingerprint — delta-only writes bump the
    # version but not this, so donated main-tier buffers stay reusable
    epoch: int = 0
    # durability plane (store/wal.py): seq of the last WAL record whose
    # effect is in this in-memory state (updated under `lock` with the
    # apply; checkpoint stamps read it under `wal_lock`), and a per-state
    # identity so an incremental checkpoint can never reuse a manifest
    # entry across a delete+recreate of the same type name (the epoch
    # tuple restarts at the same values there)
    wal_seq: int = 0
    ident: str = ""

    def __post_init__(self):
        if self.delta is None:
            from geomesa_tpu.store.delta import DeltaTier

            self.delta = DeltaTier()
        # plan cache (the reference's SoftThreadLocal plan caches,
        # QueryPlanner.scala:160): (filter text, forced index) → planned
        # (IndexPlan, residual AST, info). Entries are valid for the
        # CURRENT `indices` object only — every state swap clears it, and
        # both lookup and insert verify `st.indices is <snapshot indices>`
        # under `lock`, so a stale plan can never pair with fresh indices
        from collections import OrderedDict

        self.plan_cache: OrderedDict = OrderedDict()
        # GeoBlocks pre-aggregation pyramids, one per (group_by tuple,
        # value_cols tuple): immutable, stamped with the data epoch at
        # build time, dropped wholesale on every rebuild (under `lock`)
        self.pyramids: dict = {}
        import threading

        # `lock` guards the coherent (table, indices, backend_state, stats,
        # delta) swap vs concurrent readers — a background persister (lambda
        # role) compacts while queries run, and a reader must never pair a
        # new table with old index permutations. `mutate_lock` serializes the
        # MUTATION pipelines end-to-end (compact / delete / age-off / schema
        # evolution / recover): last-writer-wins swaps between concurrent
        # mutators would otherwise lose updates.
        self.lock = threading.RLock()
        self.mutate_lock = threading.RLock()
        # WAL ordering guard — held across (apply + WAL append) so the
        # per-type journal's seq order always equals the apply order, and
        # by the checkpointer while stamping this type's applied seq.
        # Hierarchy: wal_lock > mutate_lock > lock (docs/concurrency.md).
        self.wal_lock = threading.RLock()
        if not self.ident:
            import uuid

            self.ident = uuid.uuid4().hex

    def purge_derived(self) -> None:
        """Drop BOTH derived-data caches (plan cache + GeoBlocks
        pyramids) — the one invalidation point every state swap calls
        under ``lock``. The declared purge target of the ``plan-cache``
        and ``agg-pyramids`` cache surfaces above: keeping the two
        ``clear()`` calls in one place is what lets the ``--flow`` F001
        pass prove every mutation path reaches them."""
        # every caller swaps state under `lock`; the helper exists so the
        # two clears cannot drift apart, not to introduce a lock scope
        # tpurace: disable-next-line=R001
        self.plan_cache.clear()
        # tpurace: disable-next-line=R001
        self.pyramids.clear()

    def snapshot(self):
        """Coherent read of the query-relevant state (one lock hold)."""
        with self.lock:
            return (
                self.table,
                self.indices,
                self.backend_state,
                self.stats,
                self.delta.merged(),
            )

    def data_epoch(self) -> tuple:
        """The (rebuild epoch, delta version) pair every mutation advances
        monotonically. Cache users MUST read this BEFORE taking the data
        snapshot they compute from: a mutation racing the computation then
        stamps the entry with a pair that never recurs — a guaranteed
        future MISS, never a stale hit."""
        with self.lock:
            return (self.epoch, self.delta.version)

    def consume_snapshot(self):
        """Mutator-side snapshot: state + the number of delta tables the
        mutation will consume (call ONLY with ``mutate_lock`` held)."""
        with self.lock:
            return (
                self.table,
                self.indices,
                self.delta.merged(),
                len(self.delta.tables),
            )

    @property
    def main_rows(self) -> int:
        return 0 if self.table is None else len(self.table)

    @property
    def total_rows(self) -> int:
        return self.main_rows + self.delta.rows


@choreography_boundary
class DataStore:
    """An in-process spatio-temporal datastore over a pluggable backend.

    ``audit_writer`` (an :class:`~geomesa_tpu.utils.audit.AuditWriter`) records
    a ``QueryEvent`` per query; ``metrics`` (a
    :class:`~geomesa_tpu.utils.metrics.MetricsRegistry`) accumulates
    query/write counters and timings; ``user`` tags audit records.

    The facade is the sanctioned stage-orchestration layer
    (``@choreography_boundary``, tpusync): per-query routing and
    fallback loops in here are host choreography BY DESIGN, and callers
    are charged zero static dispatch cost for calling in. The batched
    entry points below carry their own ``@dispatch_budget`` contracts,
    which opt them back into the S001 worst-case check — those bounds
    (and the runtime ledger's measured rates, via ``--sync
    --reconcile``) are where the fusion guarantees live.
    """

    def __init__(
        self,
        backend: str | ExecutionBackend = "tpu",
        audit_writer=None,
        metrics=None,
        user: str = "unknown",
        wal_dir: str | None = None,
    ):
        if isinstance(backend, str):
            backend = _BACKENDS[backend]()
        self.backend = backend
        self._types: dict[str, _TypeState] = {}
        self.audit_writer = audit_writer
        self.user = user
        if metrics is None:
            from geomesa_tpu.utils.metrics import MetricsRegistry

            metrics = MetricsRegistry()
        self.metrics = metrics
        # SLO engine (docs/observability.md § SLOs): one availability/
        # latency observation per completed or timed-out query, exposed
        # as burn-rate gauges on GET /api/metrics?format=prometheus
        from geomesa_tpu.obs.slo import SloEngine

        self.slo = SloEngine()
        self.slo.objective("store.query", target=0.999)
        # GeoBlocks query cache (ops/geoblocks.py): exact-repeat grouped
        # aggregations served straight from cache, epoch-validated so a
        # write can never leave a stale answer servable
        from geomesa_tpu.ops.geoblocks import QueryCache

        self.agg_cache = QueryCache()
        from geomesa_tpu.utils import timeouts as _timeouts
        from geomesa_tpu.utils.timeouts import Watchdog

        self.watchdog = Watchdog()
        # thread-exhaustion signal, sampled live at metrics snapshot time
        self.metrics.gauge("store.query.abandoned_running").fn = (
            _timeouts.abandoned_running
        )
        import threading

        # atomic schema-catalog mutation (create/delete/rename): a threaded
        # REST server creates schemas concurrently
        self._schema_lock = threading.Lock()
        # (scope type-name | None, fn(sft, query) -> query) pairs
        self._interceptors: list[tuple[str | None, Any]] = []
        # device-failure circuit breaker (failure detection/recovery, SURVEY
        # §5: the reference delegates to the backing store's failover; here
        # the host columnar table IS the replica, so a dead device degrades
        # to exact host scans instead of failing queries)
        self._device_down_until: float = 0.0
        # durability plane (store/wal.py; docs/operations.md § Durability
        # & recovery): with GEOMESA_TPU_WAL (or wal_dir=) every mutating
        # op journals before it acks; DataStore.open(recover=True) replays
        # the tail over the last checkpoint
        self._wal = None
        self._wal_replay = False  # recovery/load applies without journaling
        self._wal_schema_seq = 0  # last APPLIED schema-op seq (schema_lock)
        self._wal_catalog: str | None = None
        self._wal_ckpt = None
        self._wal_unreplayed = False
        if wal_dir is None:
            wal_dir = os.environ.get("GEOMESA_TPU_WAL") or None
        if wal_dir:
            from geomesa_tpu.store.wal import WriteAheadLog

            self._wal = WriteAheadLog(wal_dir)
            # attaching over a journal with RETAINED records without
            # replaying them (DataStore.open does; a plain construct —
            # e.g. GEOMESA_TPU_WAL ambient on a CLI load — does not)
            # must not mutate or checkpoint: a save would trim, and new
            # stamps would shadow, acked history that was never applied.
            # open() clears the flag once the tail is accounted for.
            self._wal_unreplayed = self._wal.has_records()

    # -- failure detection / recovery -----------------------------------------
    DEVICE_BACKOFF_S = 30.0  # circuit stays open this long after a failure

    # XLA status codes a RUNNING device program fails with when the
    # accelerator (or its link) dies; INVALID_ARGUMENT, UNIMPLEMENTED & co.
    # describe the program and stay loud
    _OUTAGE_CODES = (
        "UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED", "CANCELLED",
        "DATA_LOSS", "INTERNAL", "UNKNOWN", "RESOURCE_EXHAUSTED",
        "FAILED_PRECONDITION",
    )
    # what a refusal by the compiler says whatever its status code (Mosaic
    # "failed to compile", "XLA:TPU compile permanent error", a VMEM
    # window that does not fit)
    _COMPILE_MARKERS = ("compil", "mosaic", "lowering", "pallas", "vmem")

    @classmethod
    def _is_device_error(cls, e: BaseException) -> bool:
        """Errors that mean 'the accelerator failed at run time', not 'the
        program is wrong'.

        Only a runtime ``JaxRuntimeError`` with an outage status, a
        connection failure, or a bare RuntimeError/OSError with a
        device-flavored message qualifies. Errors raised while tracing,
        lowering or compiling (tracer/type errors, Pallas block-shape
        refusals, Mosaic and VMEM compile failures) are program bugs:
        they re-raise instead of hiding behind the (correct but slow)
        host fallback.
        """
        import jax

        msg = str(e)
        if isinstance(e, jax.errors.JaxRuntimeError):
            low = msg.lower()
            if any(m in low for m in cls._COMPILE_MARKERS):
                return False
            return msg.lstrip().startswith(cls._OUTAGE_CODES)
        if (type(e).__module__ or "").startswith(("jax", "jaxlib")):
            return False  # tracer / lowering / sharding errors
        if isinstance(e, (ConnectionError, TimeoutError)):
            return True
        if isinstance(e, (RuntimeError, OSError)):
            low = msg.lower()
            return any(
                s in low
                for s in (
                    "unavailable", "deadline", "backend", "device", "tpu",
                    "transfer", "connection", "socket",
                )
            )
        return False

    def _device_available(self) -> bool:
        import time as _time

        return _time.monotonic() >= self._device_down_until

    def _trip_device_circuit(self, e: BaseException) -> None:
        import time as _time

        self._device_down_until = _time.monotonic() + self.DEVICE_BACKOFF_S
        self.metrics.gauge("store.device.circuit_open").set(1.0)

    def _note_device_ok(self) -> None:
        """A device path just succeeded: close a half-open circuit."""
        if self._device_down_until:
            self._device_down_until = 0.0
            self.metrics.gauge("store.device.circuit_open").set(0.0)

    def recover(self, type_name: str | None = None) -> bool:
        """Close the device circuit and rebuild device-resident state.

        Call after an accelerator outage (or let the circuit's backoff probe
        recover lazily). Returns True when device state reloaded cleanly.
        """
        self._device_down_until = 0.0
        self.metrics.gauge("store.device.circuit_open").set(0.0)
        names = [type_name] if type_name else list(self._types)
        ok = True
        for name in names:
            st = self._types[name]
            # mutate_lock: a compaction swapping state mid-load would leave
            # residency for a table that is no longer current
            with st.mutate_lock:
                with st.lock:
                    table, indices, epoch = st.table, st.indices, st.epoch
                if table is None:
                    continue
                try:
                    # same main tier → same fingerprint: buffers a pool-
                    # pressure eviction donated re-admit without staging
                    loaded = self.backend.load(
                        st.sft, table, indices, fingerprint=epoch)
                    with st.lock:
                        st.backend_state = loaded
                except Exception as e:  # noqa: BLE001 — degrade, don't fail
                    if not self._is_device_error(e):
                        raise
                    self._trip_device_circuit(e)
                    ok = False
        return ok

    # -- schema CRUD (MetadataBackedDataStore role) --------------------------
    def create_schema(self, sft: FeatureType | str, spec: str | None = None) -> FeatureType:
        if isinstance(sft, str):
            if spec is None:
                raise ValueError("create_schema('name', 'spec string') requires a spec")
            sft = parse_spec(sft, spec)
        vis_field = sft.user_data.get("geomesa.vis.field")
        if vis_field and vis_field not in {a.name for a in sft.attributes}:
            raise ValueError(
                f"geomesa.vis.field names unknown attribute {vis_field!r}"
            )
        state = _TypeState(sft=sft, indices=build_indices(sft))
        ticket = None
        if self._wal_active():
            from geomesa_tpu.store import wal as _walmod

            with self._wal.schema_lock:
                with self._schema_lock:  # atomic exists-check + insert
                    if sft.name in self._types:
                        raise ValueError(f"schema already exists: {sft.name}")
                    self._types[sft.name] = state
                # schema-topic appends order under wal.schema_lock (held
                # here), not the per-type wal_lock the data ops use
                # tpurace: disable-next-line=R001
                ticket = self._wal.append(
                    _walmod.SCHEMA_TOPIC,
                    {"op": "create_schema", "name": sft.name,
                     "spec": sft.to_spec(),
                     "index_layout": sft.index_layout})
                self._wal_schema_seq = ticket.seq
        else:
            with self._schema_lock:  # atomic exists-check + insert
                if sft.name in self._types:
                    raise ValueError(f"schema already exists: {sft.name}")
                self._types[sft.name] = state
        if ticket is not None:
            self._wal.commit(ticket)
        return sft

    @mutation(kind="evolve", invalidates=("plan-cache", "agg-pyramids"))
    @mutation(kind="rename", invalidates=(
        "geoblocks-query-cache", "buffer-pool", "device-cost-table",
        "spill-ledger", "planner-calibration-table",
        "persisted-cost-sidecar", "track-state-cache",
        "query-lens", "roundtrip-ledger", "stream-lens"))
    def update_schema(
        self,
        type_name: str,
        add: str | list[str] | None = None,
        keywords: list[str] | None = None,
        rename_to: str | None = None,
    ) -> FeatureType:
        """Schema evolution (``GeoMesaDataStore.updateSchema`` role,
        ``MetadataBackedDataStore.scala``): append attributes (all-null for
        existing rows), set keyword user-data, rename the type. Reference
        semantics are preserved: existing attributes cannot be removed or
        retyped, and the default geometry cannot change.

        ``add``: attribute spec string(s) in the SFT DSL, e.g.
        ``"severity:Integer:index=true"``.
        """
        if self._wal_active():
            from geomesa_tpu.store import wal as _walmod

            st = self._state(type_name)
            with self._wal.schema_lock, st.wal_lock:
                new_sft = self._apply_update_schema(
                    type_name, add, keywords, rename_to)
                ticket = self._wal.append(
                    _walmod.SCHEMA_TOPIC,
                    {"op": "update_schema", "type": type_name,
                     "add": ([add] if isinstance(add, str) else
                             list(add) if add else None),
                     "keywords": keywords, "rename_to": rename_to})
                self._wal_schema_seq = ticket.seq
            self._wal.commit(ticket)
            return new_sft
        return self._apply_update_schema(type_name, add, keywords, rename_to)

    def _apply_update_schema(
        self,
        type_name: str,
        add: str | list[str] | None = None,
        keywords: list[str] | None = None,
        rename_to: str | None = None,
    ) -> FeatureType:
        st = self._state(type_name)
        sft = st.sft
        new_attrs = list(sft.attributes)
        have = {a.name for a in new_attrs}
        appended = []
        if add:
            specs = [add] if isinstance(add, str) else list(add)
            for spec in specs:
                tmp = parse_spec("_tmp", spec)
                for a in tmp.attributes:
                    if a.type.is_geometry:
                        raise ValueError(
                            "cannot add geometry attributes (reference "
                            "updateSchema restriction)"
                        )
                    if a.name in have:
                        raise ValueError(f"attribute already exists: {a.name!r}")
                    new_attrs.append(a)
                    appended.append(a)
                    have.add(a.name)
        user_data = dict(sft.user_data)
        if keywords is not None:
            # comma-joined so the value survives the to_spec round-trip
            user_data["geomesa.keywords"] = ",".join(keywords)
        from geomesa_tpu.schema.sft import AttributeType as _AT

        if (
            any(a.type == _AT.DATE for a in appended)
            and "geomesa.index.dtg" not in user_data
        ):
            # pin the pre-evolution dtg ("" = none): an appended all-null
            # Date column must not become the store's temporal axis
            user_data["geomesa.index.dtg"] = sft.dtg_field or ""
        new_name = rename_to or sft.name
        if rename_to and rename_to != type_name:
            if rename_to in self._types:
                raise ValueError(f"schema already exists: {rename_to!r}")
        new_sft = FeatureType(
            name=new_name,
            attributes=new_attrs,
            default_geom=sft.geom_field,
            user_data=user_data,
        )

        # build the evolved table OUTSIDE the swap: main + delta merged in
        # host code, appended attributes backfilled as null columns — one
        # rebuild, and any failure leaves the old state fully intact
        from geomesa_tpu.schema.columnar import null_column

        with st.mutate_lock:
            main, _, delta_table, n_tables = st.consume_snapshot()
            parts = [t for t in (main, delta_table) if t is not None and len(t)]
            base = FeatureTable.concat(parts) if len(parts) > 1 else (
                parts[0] if parts else None
            )
            if base is not None:
                cols = dict(base.columns)
                for a in appended:
                    cols[a.name] = null_column(a.type, len(base))
                # sft swaps atomically WITH the rebuilt state: a concurrent
                # query never pairs the evolved schema with old indices
                self._rebuild(
                    st, FeatureTable(new_sft, base.fids, cols),
                    consumed_tables=n_tables, new_sft=new_sft,
                )
            else:
                with st.lock:
                    st.sft = new_sft
                    st.table = None
                    st.indices = build_indices(new_sft)
                    st.backend_state = None
                    st.delta.drop_first(n_tables)
                    st.purge_derived()
                    st.epoch += 1
        if rename_to and rename_to != type_name:
            with self._schema_lock:
                self._types[rename_to] = self._types.pop(type_name)
                # interceptors scoped to the old name follow the rename
                self._interceptors = [
                    (rename_to if scope == type_name else scope, fn)
                    for scope, fn in self._interceptors
                ]
            # device residency, cached aggregates, cost rows are all keyed
            # by type NAME: a rebuild above registered them under the OLD
            # name, where they would leak forever (and poison a future
            # schema reusing that name). Drop the device state so the next
            # query rebuilds under the new name, then purge the old key.
            st = self._types[rename_to]
            with st.mutate_lock:
                with st.lock:
                    st.backend_state = None
                    st.pyramids.clear()
            self._purge_type_name(type_name)
        return new_sft

    def get_schema(self, name: str) -> FeatureType:
        return self._state(name).sft

    def list_schemas(self) -> list[str]:
        return sorted(self._types)

    @mutation(kind="delete_schema", invalidates=(
        "geoblocks-query-cache", "buffer-pool", "device-cost-table",
        "spill-ledger", "planner-calibration-table",
        "persisted-cost-sidecar", "track-state-cache",
        "query-lens", "roundtrip-ledger", "stream-lens"))
    def delete_schema(self, name: str) -> None:
        if self._wal_active():
            from geomesa_tpu.store import wal as _walmod

            with self._wal.schema_lock:
                self._apply_delete_schema(name)
                # schema-topic appends order under wal.schema_lock (held
                # here), not the per-type wal_lock the data ops use
                # tpurace: disable-next-line=R001
                ticket = self._wal.append(
                    _walmod.SCHEMA_TOPIC,
                    {"op": "delete_schema", "name": name})
                self._wal_schema_seq = ticket.seq
            self._wal.commit(ticket)
            return
        self._apply_delete_schema(name)

    def _apply_delete_schema(self, name: str) -> None:
        with self._schema_lock:
            del self._types[name]
        # a recreated same-name type RESTARTS its rebuild epoch and delta
        # version at the same values, so everything keyed by type name
        # must die with the schema: cached aggregates (the epoch tuple
        # recurs — the successor would read the dead table's answers as
        # current), pool entries/donations (a fingerprint collision would
        # re-admit the dead table's device columns as the new state), the
        # spill report, and the observed cost profile + probe phase
        self._purge_type_name(name)

    def _purge_type_name(self, name: str) -> None:
        """Drop every store/pool/telemetry artifact keyed by a type NAME
        whose schema no longer answers for it (delete, rename)."""
        self.agg_cache.invalidate(name)
        pool: "BufferPool | None" = getattr(self.backend, "pool", None)
        if pool is not None:
            pool.purge(name)
        from geomesa_tpu.obs import devmon
        from geomesa_tpu.planning import costmodel

        devmon.ledger().clear_spills(name)
        devmon.costs().forget(name)
        costmodel.model().forget(name)
        # the retained profiling plane (obs.lens) and the roundtrip rollup
        # (obs.ledger) key series by type name too: a recreated same-name
        # type must not inherit its predecessor's latency history or
        # fusion ranking (and the sentinel must not compare across them)
        from geomesa_tpu.obs import lens as _lensmod
        from geomesa_tpu.obs import ledger as _rtledger

        _lensmod.get().forget(name)
        _rtledger.table().forget(name)
        # the stream lens keys delivery history by TOPIC, and the topic
        # convention is type-name-derived — a recreated same-name type's
        # standing subscriptions must not inherit the dead type's
        # delivery histograms, lateness counters, or capacity history
        from geomesa_tpu.obs import streamlens as _streamlens

        _streamlens.get().forget(f"geomesa-{name}")
        # the PERSISTED cost sidecar too: a restart must not resurrect a
        # deleted/renamed type's profile for an unrelated successor
        devmon.purge_persisted_costs(name)
        # cached trajectory track states are epoch-fingerprinted by the
        # SAME restarting (rebuild epoch, delta version) tuple — a
        # recreated same-name type could collide and serve the dead
        # table's per-entity aggregates as current
        from geomesa_tpu.trajectory import state as _traj_state

        _traj_state.invalidate(self, name)

    def _state(self, name: str) -> _TypeState:
        if name not in self._types:
            raise KeyError(f"no such schema: {name!r}")
        return self._types[name]

    # -- writes (GeoMesaFeatureWriter + lambda hot-tier roles) ---------------
    @mutation(kind="write", invalidates=("plan-cache", "agg-pyramids"))
    def write(self, type_name: str, data, fids=None) -> int:
        """Append features (FeatureTable or list of record dicts).

        Writes land in the hot delta tier (immediately queryable, scanned
        brute-force) and are merged into the sorted main tier when the delta
        passes the compaction threshold — the lambda-architecture pattern
        (SURVEY.md §2.11). Validation before commit (the reference's
        all-indices-validate-before-write pattern, ``IndexAdapter.scala:
        139-149``): rows with a null default geometry or null dtg are
        rejected, and main-tier state only swaps in after every index builds,
        so a failed write never leaves the store half-applied.

        With the durability plane attached (``GEOMESA_TPU_WAL`` /
        ``wal_dir=``) the write is journaled under the type's WAL order
        lock and the return — the ACK — waits for the record's
        group-commit durability: a SIGKILL after return can never lose it
        (docs/operations.md § Durability & recovery).
        """
        st = self._state(type_name)
        with obs.span("write", type_name=type_name):
            if isinstance(data, list):
                if fids is None:
                    fids = self._generate_fids(st, len(data), data)
                data = FeatureTable.from_records(st.sft, data, fids)
            self._validate(st.sft, data)
            ticket = None
            if self._wal_active():
                from geomesa_tpu.io.arrow import to_ipc_bytes
                from geomesa_tpu.store import wal as _walmod

                payload = to_ipc_bytes(data)
                with st.wal_lock:
                    compact_now = self._apply_write(st, data)
                    ticket = self._wal.append(
                        _walmod.topic_for(type_name), {"op": "write"}, payload)
                    with st.lock:
                        st.wal_seq = ticket.seq
            else:
                compact_now = self._apply_write(st, data)
            if ticket is not None:
                self._wal.commit(ticket)  # durability before the ack
            if compact_now:
                self.compact(type_name)
            return len(data)

    def _apply_write(self, st: _TypeState, data) -> bool:
        self.metrics.counter("store.writes").inc(len(data))
        with st.lock:
            st.delta.append(data)
            return st.delta.should_compact(st.main_rows)

    def _wal_active(self) -> bool:
        """Journal this mutation? False on the WAL-off path (one attribute
        check — the <2% write-overhead bound) and during recovery replay /
        checkpoint load (the records being applied ARE the journal).
        Raises if the attached journal still holds an unreplayed tail —
        mutating over un-recovered acked history must fail loudly, not
        shadow it (open with ``DataStore.open(catalog, recover=True)``)."""
        if self._wal is None or self._wal_replay:
            return False
        if self._wal_unreplayed:
            from geomesa_tpu.store.wal import WalTailError

            raise WalTailError(
                f"WAL {self._wal.path!r} holds un-replayed acked records; "
                f"this store was attached without recovery — open the "
                f"catalog with DataStore.open(..., recover=True)")
        return True

    def _generate_fids(self, st, n: int, records: list) -> list:
        """Default feature ids. Schemas opting in via user-data
        ``geomesa.fid.uuid='z3'`` get z3-prefixed ids (the reference writer's
        Z3 time-UUID default, ``GeoMesaFeatureWriter.scala:81``); otherwise
        sequential ``<type>.<n>`` ids."""
        sft = st.sft
        if (
            str(sft.user_data.get("geomesa.fid.uuid", "")).lower() == "z3"
            and sft.geom_field is not None
            and sft.dtg_field is not None
        ):
            from geomesa_tpu.schema.columnar import _to_millis
            from geomesa_tpu.utils.fid import z3_fids

            lons = np.empty(n)
            lats = np.empty(n)
            ts = np.empty(n, dtype=np.int64)
            ok = True
            for i, r in enumerate(records):
                g = r.get(sft.geom_field)
                t = r.get(sft.dtg_field)
                if g is None or t is None or not hasattr(g, "bbox"):
                    ok = False
                    break
                x1, y1, x2, y2 = g.bbox
                lons[i] = (x1 + x2) / 2
                lats[i] = (y1 + y2) / 2
                ts[i] = _to_millis(t)
            if ok:
                return list(z3_fids(lons, lats, ts, sft.z3_interval))
        with st.lock:
            # monotonic per-type sequence: concurrent writers must never
            # mint the same id (total_rows alone is a check-then-act race)
            st.fid_seq = max(st.fid_seq, st.total_rows)
            base = st.fid_seq
            st.fid_seq += n
        return [f"{st.sft.name}.{base + i}" for i in range(n)]

    # -- query interceptors (QueryInterceptor.scala:27 role) ------------------
    def register_interceptor(self, type_name: str | None, fn) -> None:
        """Register ``fn(sft, query) -> query`` rewriting queries before
        planning; ``type_name`` None applies to every schema."""
        # under the schema lock: the rename path REPLACES the list wholesale
        # while holding it, and an append racing that swap would land on the
        # discarded list (registration silently lost)
        with self._schema_lock:
            self._interceptors.append((type_name, fn))

    def _intercept(self, type_name: str, sft, q: Query) -> Query:
        for scope, fn in self._interceptors:
            if scope is None or scope == type_name:
                out = fn(sft, q)
                if out is not None:
                    q = out
        return q

    @mutation(kind="delete", invalidates=("plan-cache", "agg-pyramids"))
    def delete_features(self, type_name: str, fids, visible_to=None) -> int:
        """Remove features by id (the ``GeoMesaFeatureWriter`` remove role).

        Rebuilds the main tier without the targeted rows (columnar stores
        delete by rewrite, like the reference's LSM deletes compact away);
        returns the number of rows removed. ``visible_to`` (a list of
        authorizations) enforces record visibility UNDER the mutation lock:
        targeting any row the auths cannot see raises ``PermissionError`` —
        the race-proof backstop for the serving layer's pre-check.
        """
        st = self._state(type_name)
        want = {str(f) for f in fids}
        if self._wal_active():
            from geomesa_tpu.store import wal as _walmod

            ticket = None
            with st.wal_lock:
                removed = self._apply_delete(st, want, visible_to)
                if removed:  # no state change → nothing to journal
                    ticket = self._wal.append(
                        _walmod.topic_for(type_name),
                        {"op": "delete", "fids": sorted(want),
                         "visible_to": (None if visible_to is None
                                        else list(visible_to))})
                    with st.lock:
                        st.wal_seq = ticket.seq
            if ticket is not None:
                self._wal.commit(ticket)
            return removed
        return self._apply_delete(st, want, visible_to)

    def _apply_delete(self, st: _TypeState, want: set, visible_to) -> int:
        with st.mutate_lock:
            main, _, delta, n_tables = st.consume_snapshot()
            tables = [t for t in (main, delta) if t is not None and len(t)]
            if not tables:
                return 0
            combined = (
                tables[0] if len(tables) == 1 else FeatureTable.concat(tables)
            )
            keep = np.array(
                [str(f) not in want for f in combined.fids], dtype=bool
            )
            removed = int((~keep).sum())
            if visible_to is not None and removed:
                vis_field = (st.sft.user_data or {}).get("geomesa.vis.field")
                if vis_field:
                    from geomesa_tpu.security.visibility import parse_visibility

                    auths = frozenset(visible_to)
                    vvals = combined.columns[vis_field].values
                    for i in np.nonzero(~keep)[0]:
                        expr = vvals[i] if vvals[i] else ""
                        if not parse_visibility(expr).evaluate(auths):
                            raise PermissionError(
                                "target features not visible"
                            )
            if removed == 0:
                return 0
            # the delta drops only after the new state swaps in — a failed
            # rebuild must not lose hot-tier rows
            self._rebuild(
                st, combined.take(np.nonzero(keep)[0]), consumed_tables=n_tables
            )
            return removed

    def update_features(self, type_name: str, data, fids, visible_to=None) -> int:
        """Replace the features with the given ids (the
        ``GeoMesaFeatureWriter`` MODIFY flavor): delete + append under the
        mutation lock. Like the reference (no cross-index transactions,
        ``IndexAdapter.scala:139`` validates-then-writes), the replacement
        is not atomic for concurrent readers — a query racing the update may
        briefly miss the row; it never sees both versions after return.
        ``visible_to``: see :meth:`delete_features`.

        Every target fid must already exist — a missing id raises
        ``KeyError`` (no silent upsert; WFS-T Update's replace contract),
        checked under the mutation lock for both restricted and
        unrestricted callers."""
        fids = [str(f) for f in fids]
        if len(set(fids)) != len(fids):
            raise ValueError("update_features: duplicate fids")
        if isinstance(data, list):
            if len(data) != len(fids):
                raise ValueError(
                    f"update_features: {len(data)} records for {len(fids)} fids"
                )
        elif [str(f) for f in data.fids] != fids:
            # a table carries its own fids; they must BE the replaced ids or
            # the delete and the append would target different features
            raise ValueError("update_features: table fids != fids argument")
        st = self._state(type_name)
        # wal_lock OUTSIDE mutate_lock: the inner delete/write journal
        # under wal_lock, and wal_lock > mutate_lock is the canonical
        # order (docs/concurrency.md) — taking mutate first would invert
        with st.wal_lock, st.mutate_lock:
            # validate the replacement BEFORE deleting: a malformed update
            # must fail without destroying the original rows (the reference's
            # validates-then-writes pattern)
            table = (
                FeatureTable.from_records(st.sft, data, fids)
                if isinstance(data, list)
                else data
            )
            self._validate(st.sft, table)
            # every target must exist (no silent upsert — WFS-T Update is
            # replace). Fid sets are read per-tier (no delta concat; the
            # delete below builds the merged view once). Restricted callers
            # get PermissionError for missing ids — the same error hidden
            # rows raise — so a 403/404 split cannot become an existence
            # oracle for rows their auths cannot see.
            existing: set[str] = set()
            with st.lock:
                tiers = [st.table, *st.delta.tables]
            for t in tiers:
                if t is not None and len(t):
                    existing.update(str(f) for f in t.fids)
            missing = [f for f in fids if f not in existing]
            if missing:
                if visible_to is not None:
                    raise PermissionError("target features not visible")
                raise KeyError(
                    f"update_features: no such feature id(s) {missing[:5]}"
                    + ("..." if len(missing) > 5 else "")
                )
            # wal_lock is ALREADY HELD (outer, reentrant) — the inner
            # delete/write re-acquire it, so the static mutate->wal edge
            # seen here cannot deadlock against the canonical wal->mutate
            # order
            # tpurace: disable-next-line=R002
            self.delete_features(type_name, fids, visible_to=visible_to)
            return self.write(type_name, table)

    @mutation(kind="clear", invalidates=("plan-cache", "agg-pyramids"))
    def clear(self, type_name: str) -> int:
        """Drop every row of a type, keeping the schema (the bus tier's
        ``Clear`` barrier as a store op; WFS-T "delete all" role). Returns
        the rows removed. Journaled like every other mutation — a
        recovered store is empty exactly when the acked state was."""
        st = self._state(type_name)
        if self._wal_active():
            from geomesa_tpu.store import wal as _walmod

            ticket = None
            with st.wal_lock:
                removed = self._apply_clear(st)
                if removed:
                    ticket = self._wal.append(
                        _walmod.topic_for(type_name), {"op": "clear"})
                    with st.lock:
                        st.wal_seq = ticket.seq
            if ticket is not None:
                self._wal.commit(ticket)
            return removed
        return self._apply_clear(st)

    def _apply_clear(self, st: _TypeState) -> int:
        with st.mutate_lock:
            with st.lock:
                removed = st.total_rows
                if removed == 0:
                    return 0
                n_tables = len(st.delta.tables)
                st.table = None
                st.indices = build_indices(st.sft)
                st.backend_state = None
                st.stats = None
                st.delta.drop_first(n_tables)
                st.purge_derived()
                st.epoch += 1
            return removed

    @mutation(kind="write", invalidates=("plan-cache", "agg-pyramids"))
    def compact(self, type_name: str) -> None:
        """Merge the delta tier into the sorted main tier (re-sort + device
        reload + stats rebuild). Atomic: state swaps only on success, and
        writes landing mid-compaction stay in the hot tier."""
        st = self._state(type_name)
        with st.mutate_lock:
            main, prev_indices, delta, n_tables = st.consume_snapshot()
            if delta is None:
                return
            n_prev = 0 if main is None else len(main)
            table = delta if main is None else FeatureTable.concat([main, delta])
            self._rebuild(
                st, table, prev_indices=prev_indices, n_prev=n_prev,
                consumed_tables=n_tables,
            )

    # bulk builds below this many rows host-sort (device round-trip beats
    # the sort only at scale); env-tunable so tests can force the mesh path
    DEVICE_SORT_MIN_ROWS = 2_000_000

    def _device_sorter(self, n_rows: int):
        """The mesh sample-sort for index builds, when it applies.

        The ``DefaultSplitter`` role wired into the store lifecycle (VERDICT
        r2 item 4): bulk ingest/compaction on the TPU backend routes
        arrival-order keys through stats-driven splits + ``all_to_all``
        (``device_ingest.device_sort_perm``) instead of the host sort.
        Returns None (→ host sort) for small tables, non-TPU backends, or
        an open device circuit.
        """
        if self.backend.name != "tpu" or not self._device_available():
            return None
        env_thresh = os.environ.get("GEOMESA_DEVICE_SORT_MIN_ROWS")
        if env_thresh is None:
            import jax

            if jax.default_backend() != "tpu":
                # without an explicit opt-in the mesh sample sort only pays
                # on a real accelerator: on the CPU test mesh its
                # all_to_all materializations cost ~20x the native host
                # radix sort (the env knob stays an opt-in anywhere — the
                # sharding tests set it to exercise the device path)
                return None
        threshold = int(
            env_thresh if env_thresh is not None else self.DEVICE_SORT_MIN_ROWS
        )
        if n_rows < max(threshold, 1):
            return None
        from geomesa_tpu.store.device_ingest import device_sort_perm

        try:
            mesh = self.backend._get_mesh()
        except Exception as e:  # noqa: BLE001 — degrade, don't die
            if not self._is_device_error(e):
                raise
            self._trip_device_circuit(e)
            return None

        def sorter(route_key, tiebreak):
            return device_sort_perm(mesh, route_key, tiebreak)

        return sorter

    def _rebuild(self, st: _TypeState, table: FeatureTable, prev_indices=None,
                 n_prev: int = 0, consumed_tables: int = 0, new_sft=None) -> None:
        """Swap in a new main tier built from ``table`` (delta folded in).

        Indexes exposing ``merge_build`` fold a sorted delta into the
        already-sorted previous state linearly (LSM compaction, SURVEY.md
        §2.11) instead of re-sorting everything. ``consumed_tables`` is the
        delta-table count the caller folded into ``table`` (from
        :meth:`_TypeState.consume_snapshot`); only those first tables drop
        from the hot tier, so writes landing during the rebuild survive.
        ``new_sft`` swaps the schema atomically with the state (evolution).
        Callers must hold ``st.mutate_lock``.
        """
        sft = new_sft if new_sft is not None else st.sft
        indices = build_indices(sft)
        # the NEXT rebuild epoch (mutate_lock serializes mutators, so the
        # increment is race-free): the backend load's donation fingerprint
        # and, at swap, the new data-epoch component
        next_epoch = st.epoch + 1
        sorter = self._device_sorter(len(table))
        for name, index in indices.items():
            prev = (prev_indices or {}).get(name)
            if prev is not None and n_prev > 0 and hasattr(index, "merge_build"):
                index.merge_build(table, prev, n_prev)
            elif sorter is not None:
                try:
                    index.build(table, sorter=sorter)
                    self._note_device_ok()  # half-open circuit closes
                except Exception as e:  # noqa: BLE001 — degrade, don't die
                    if not self._is_device_error(e):
                        raise
                    self._trip_device_circuit(e)
                    self.metrics.counter("store.device.sort_failures").inc()
                    sorter = None  # host sorts for the remaining indexes too
                    index.build(table)
            else:
                index.build(table)
        try:
            backend_state = self.backend.load(
                sft, table, indices, fingerprint=next_epoch)
        except Exception as e:  # noqa: BLE001 — write must not die with the device
            if not self._is_device_error(e):
                raise
            self._trip_device_circuit(e)
            self.metrics.counter("store.device.load_failures").inc()
            backend_state = None  # host paths serve until recover()
        from geomesa_tpu.stats.store_stats import StoreStats

        stats = StoreStats(sft)
        stats.rebuild(table, indices.get("z3"))
        with st.lock:
            if new_sft is not None:
                st.sft = new_sft
            st.table = table
            st.indices = indices
            st.backend_state = backend_state
            st.stats = stats
            st.delta.drop_first(consumed_tables)
            st.purge_derived()  # pyramids were built from the OLD main tier
            st.epoch = next_epoch

    # -- age-off (AgeOffIterator / DtgAgeOffIterator role) --------------------
    @staticmethod
    def _age_off_ttl_ms(sft: FeatureType) -> int | None:
        """TTL from schema user-data ``geomesa.age.off`` (milliseconds)."""
        v = sft.user_data.get("geomesa.age.off")
        return None if v is None else int(v)

    @mutation(kind="age_off", invalidates=("plan-cache", "agg-pyramids"))
    def age_off(self, type_name: str, now_ms: int | None = None) -> int:
        """Physically drop rows older than the schema's TTL; returns count.

        The query path also masks expired rows on the fly, so ``age_off`` is a
        maintenance compaction (the reference runs the same logic as a
        server-side iterator at scan AND at major compaction —
        ``AgeOffIterator``, SURVEY.md §2.3/§2.6).
        """
        st = self._state(type_name)
        ttl = self._age_off_ttl_ms(st.sft)
        if ttl is None or st.sft.dtg_field is None or st.total_rows == 0:
            return 0
        if now_ms is None:
            # resolve the clock BEFORE journaling: a replayed age-off must
            # drop exactly the rows the live one did
            import time as _time

            now_ms = int(_time.time() * 1000)
        if self._wal_active():
            from geomesa_tpu.store import wal as _walmod

            ticket = None
            with st.wal_lock:
                removed = self._apply_age_off(st, now_ms, ttl)
                if removed:
                    ticket = self._wal.append(
                        _walmod.topic_for(type_name),
                        {"op": "age_off", "now_ms": int(now_ms)})
                    with st.lock:
                        st.wal_seq = ticket.seq
            if ticket is not None:
                self._wal.commit(ticket)
            return removed
        return self._apply_age_off(st, now_ms, ttl)

    def _apply_age_off(self, st: _TypeState, now_ms: int, ttl: int) -> int:
        cutoff = _ttl_cutoff_ms(ttl, now_ms)
        with st.mutate_lock:
            main, _, delta, n_tables = st.consume_snapshot()
            parts = [t for t in (main, delta) if t is not None]
            if not parts:  # raced another maintenance pass that emptied it
                return 0
            table = FeatureTable.concat(parts) if len(parts) > 1 else parts[0]
            keep = table.columns[st.sft.dtg_field].values >= cutoff
            removed = int((~keep).sum())
            if removed == 0:
                return 0
            if keep.any():
                self._rebuild(
                    st, table.take(np.nonzero(keep)[0]), consumed_tables=n_tables
                )
            else:  # everything expired: reset to empty
                with st.lock:
                    st.table = None
                    st.indices = build_indices(st.sft)
                    st.backend_state = None
                    st.stats = None
                    st.delta.drop_first(n_tables)
                    st.purge_derived()
                    st.epoch += 1
            return removed

    @staticmethod
    def _validate(sft: FeatureType, table: FeatureTable) -> None:
        if sft.geom_field is not None:
            col = table.columns[sft.geom_field]
            if not col.is_valid().all():
                bad = int((~col.is_valid()).sum())
                raise ValueError(
                    f"{bad} feature(s) with null geometry {sft.geom_field!r}: "
                    "indexed geometries must be non-null"
                )
        if sft.dtg_field is not None:
            col = table.columns[sft.dtg_field]
            if not col.is_valid().all():
                bad = int((~col.is_valid()).sum())
                raise ValueError(
                    f"{bad} feature(s) with null date {sft.dtg_field!r}: "
                    "indexed dates must be non-null"
                )
        # visibility expressions must parse at write time so one malformed row
        # can never poison every subsequent auth-filtered read
        vis_field = sft.user_data.get("geomesa.vis.field")
        if vis_field:
            from geomesa_tpu.security.visibility import parse_visibility

            for v in set(
                "" if v is None else str(v) for v in table.columns[vis_field].values
            ):
                # comma lists are per-ATTRIBUTE expressions (attribute-level
                # visibility); each part must parse on its own
                for part in v.split(","):
                    parse_visibility(part.strip())  # raises on bad input

    # -- queries (QueryPlanner.runQuery role) --------------------------------
    def query(
        self, type_name: str, q: "Query | str | ast.Filter | None" = None, **kwargs
    ) -> QueryResult:
        st = self._state(type_name)
        if isinstance(q, (str, ast.Filter)) or q is None:
            q = Query(filter=q, **kwargs)
        elif kwargs:
            raise ValueError(
                "pass query options inside the Query object, not as kwargs: "
                f"{sorted(kwargs)}"
            )
        # the per-query trace root (child when already inside a request or
        # batch span); every stage below opens a child span, so EXPLAIN
        # ANALYZE and the Perfetto export read straight off this tree
        with obs.span("query", type_name=type_name):
            # sampled device-time attribution (GEOMESA_TPU_DEVPROF env or
            # the per-query "devprof" hint): every cached-jit dispatch in
            # this tree brackets with block_until_ready timing, and the
            # breakdown lands in the flight record + cost table (_audit)
            from geomesa_tpu.obs import devmon
            from geomesa_tpu.obs import ledger as _rtledger

            # host-roundtrip ledger (obs.ledger): every device dispatch /
            # host sync under this query charges the per-query ledger;
            # _audit folds it into the per-signature fusion rollup
            with _rtledger.roundtrip():
                if devmon.sampled(q.hints.get("devprof")):
                    with devmon.profiled():
                        return self._run_query(st, type_name, q)
                return self._run_query(st, type_name, q)

    def _run_query(self, st: _TypeState, type_name: str, q: Query) -> QueryResult:
        import time as _time

        # user query-rewrite hooks run before anything else sees the query
        # (QueryPlanner.scala:178 configureQuery → interceptors)
        if self._interceptors:
            q = self._intercept(type_name, st.sft, q)

        self.metrics.counter("store.queries").inc()
        if st.total_rows == 0:
            # still runs the shared reduce pipeline so aggregation hints
            # produce empty aggregates (not None) — callers index res.stats
            from geomesa_tpu.store.reduce import reduce_result

            empty = FeatureTable.from_records(st.sft, [])
            with obs.span("reduce", rows=0):
                table, rows, density, stats_out, bin_data = reduce_result(
                    st.sft, empty, np.empty(0, dtype=np.int64), q
                )
            with obs.span("telemetry"):
                self._audit(type_name, q, 0.0, 0.0, 0)
            return QueryResult(
                table, rows, density=density, stats=stats_out, bin_data=bin_data
            )

        # query-time age-off (AgeOffIterator-at-scan role): expired rows are
        # masked even before a physical age_off() compaction runs
        ttl = self._age_off_ttl_ms(st.sft)
        if ttl is not None and st.sft.dtg_field is not None:
            from dataclasses import replace as _replace

            cut = ast.Compare(
                ">=", st.sft.dtg_field,
                _ttl_cutoff_ms(ttl, q.hints.get("now_ms")),
            )
            q = _replace(q, filter=ast.And((q.resolved_filter(), cut)))

        # correctness-audit tagging (obs/audit.py): the off path is one
        # module-global bool plus a dict lookup. A sampled (or
        # hint-tagged) query captures the DATA EPOCH *before* the scan
        # snapshot — a write landing in between moves the live epoch
        # past the captured one, and the shadow re-check then abstains
        # instead of alarming (the capture-order rule cached aggregates
        # already follow)
        from geomesa_tpu.obs import audit as _obsaudit

        audit_epoch = None
        if (not _obsaudit.in_shadow()
                and (_obsaudit.ENABLED or q.hints.get("audit"))
                and _obsaudit.eligible_select(q)
                # eligibility FIRST: an ineligible (density/limit) query
                # must not burn a sampling tick — the configured rate
                # applies to auditable traffic
                and (q.hints.get("audit") or _obsaudit.sampled())):
            audit_epoch = st.data_epoch()

        t_start = _time.perf_counter()
        plan_box = {"info": None, "plan_ms": 0.0}

        def _scan_and_reduce():
            f = q.resolved_filter()
            # COHERENT state snapshot: a background compaction (lambda
            # persister) must never let this query pair a new table with old
            # index permutations or a stale device residency
            main, indices, backend_state, stats, delta_table = st.snapshot()
            main_n = 0 if main is None else len(main)
            if main_n == 0:
                rows = np.empty(0, dtype=np.int64)
            elif isinstance(self.backend, OracleBackend):
                # referee path: no planning, brute force
                rows = self.backend.select(None, None, None, None, f, main)
            else:
                t0 = _time.perf_counter()
                # TTL stores rewrite the filter with a now_ms cut per call —
                # the key would never repeat, so don't pay the cache overhead
                cache_key = None if ttl is not None else self._plan_cache_key(q)
                with obs.span("plan") as _plan_sp:
                    cached = self._plan_lookup(st, indices, cache_key)
                    if cached is not None:
                        plan, f, plan_box["info"] = cached
                        _plan_sp.set(cache="hit")
                    else:
                        planner = QueryPlanner(st.sft, indices, stats)
                        plan, f, plan_box["info"] = planner.plan(
                            q, under_burn=self._under_burn(type_name))
                        self._plan_store(
                            st, indices, cache_key, (plan, f, plan_box["info"])
                        )
                    _plan_sp.set(index=plan_box["info"].index_name)
                plan_box["plan_ms"] = (_time.perf_counter() - t0) * 1000.0
                info = plan_box["info"]
                # circuit open → don't touch the device; exact host scan
                state = backend_state if self._device_available() else None
                try:
                    if info.sub_plans:
                        # FilterSplitter union: scan each arm on its own index
                        # (full filter as residual keeps each arm exact), union
                        parts = [
                            self.backend.select(
                                state, indices[n], p, e_c, f, main
                            )
                            for n, p, e_c in info.sub_plans
                        ]
                        rows = np.unique(np.concatenate(parts))
                    else:
                        index = indices[info.index_name]
                        rows = self.backend.select(
                            state, index, plan, info.extraction, f, main,
                        )
                except Exception as e:  # noqa: BLE001 — failover, re-raise rest
                    if state is None or not self._is_device_error(e):
                        raise
                    self._trip_device_circuit(e)
                    self.metrics.counter("store.query.device_failovers").inc()
                    with obs.span("refine", mode="failover"):
                        rows = np.nonzero(f.mask(main))[0]
                else:
                    if state is not None:
                        self._note_device_ok()
            rows = np.sort(rows)

            # hot-tier merge (LambdaQueryRunner role): brute-force the small
            # unsorted delta and append, row ids offset past the main tier
            if delta_table is not None:
                with obs.span("delta", rows=len(delta_table)):
                    dmask = f.mask(delta_table)
                    drows = np.nonzero(dmask)[0]
                    rows = np.concatenate([rows, drows + main_n])

            with obs.span("reduce", rows=len(rows)):
                table = _take_combined(st.sft, main, main_n, delta_table, rows)

                # shared post-scan pipeline: visibility, sampling, aggregation
                # hints, sort/limit/projection/CRS (LocalQueryRunner shape)
                from geomesa_tpu.store.reduce import reduce_result

                return reduce_result(st.sft, table, rows, q)

        # query watchdog (ThreadManagement role): per-query ``timeout`` hint
        # in seconds; timed-out scans are abandoned and counted
        from geomesa_tpu.utils.timeouts import QueryTimeout, run_with_timeout

        timeout_s = q.hints.get("timeout")
        # end-to-end deadline (hints["deadline"]: utils.timeouts.Deadline):
        # the remaining budget CAPS any per-query timeout, and a budget
        # already spent upstream sheds the scan before any device work —
        # no worker thread is spawned, so nothing lands in the abandoned
        # gauge for work that never started
        deadline = q.hints.get("deadline")
        if deadline is not None:
            rem = deadline.remaining_s()
            if rem <= 0:
                self.metrics.counter("store.query.timeouts").inc()
                self.metrics.counter("store.query.deadline_shed").inc()
                if not _obsaudit.in_shadow():
                    self.slo.observe("store.query", ok=False, key=type_name)
                self._meter_failed(type_name, q, 0.0)
                raise QueryTimeout(
                    f"deadline spent before scan of {type_name!r} started")
            timeout_s = rem if timeout_s is None else min(timeout_s, rem)
        token = self.watchdog.register(f"{type_name}: {q.filter!r}")
        timed_out = False
        try:
            table, rows, density, stats_out, bin_data = run_with_timeout(
                _scan_and_reduce, timeout_s
            )
        except QueryTimeout:
            timed_out = True
            wall = (_time.perf_counter() - t_start) * 1000.0
            self.metrics.counter("store.query.timeouts").inc()
            if not _obsaudit.in_shadow():
                self.slo.observe(
                    "store.query", ok=False, key=type_name, latency_ms=wall)
            self._meter_failed(type_name, q, wall)
            raise
        finally:
            # finally: scan errors (not just timeouts) must release the
            # registration instead of leaking it in the active set
            self.watchdog.complete(token, timed_out=timed_out)
        info = plan_box["info"]
        plan_ms = plan_box["plan_ms"]
        scan_ms = (_time.perf_counter() - t_start) * 1000.0 - plan_ms
        with obs.span("telemetry"):
            self._audit(type_name, q, plan_ms, scan_ms, len(table), info=info)
        if audit_epoch is not None:
            # shadow re-execution against the independent referee: the
            # LIVE answer (post-reduce fids) rides along so the check
            # compares without re-running this path
            _obsaudit.get().enqueue_select(
                self, type_name, q, audit_epoch, table)
        return QueryResult(
            table, rows, info, density=density, stats=stats_out, bin_data=bin_data
        )

    _PLAN_CACHE_MAX = 128

    def _under_burn(self, type_name: str) -> bool:
        """Is this type burning its error budget? Fed to the planner's
        SLO-aware tie-breaking: under burn, near-tied strategies resolve
        to the lower-variance plan. Computed only on plan-cache misses."""
        try:
            return (
                self.slo.tracker("store.query", type_name).burn_rate(300.0)
                > 1.0
            )
        except Exception:  # noqa: BLE001 — telemetry must never fail a plan
            return False

    @staticmethod
    def _plan_cache_key(q: "Query"):
        """Cache key for a query's PLANNING inputs, or None if uncacheable.
        Planning reads only the filter and the forced-index hint."""
        f = q.filter
        if f is None:
            text = "INCLUDE"
        elif isinstance(f, str):
            text = f
        else:
            try:
                text = ast.to_cql(f)
            except ValueError:
                return None
        return (text, q.hints.get("index"))

    def _plan_lookup(self, st: _TypeState, indices, key):
        if key is None:
            return None
        with st.lock:
            if st.indices is not indices:
                return None  # our snapshot is older than the live state
            hit = st.plan_cache.get(key)
            if hit is not None:
                st.plan_cache.move_to_end(key)
                self.metrics.counter("store.plan_cache.hits").inc()
            return hit

    @feedback_sink
    def _plan_store(self, st: _TypeState, indices, key, value) -> None:
        if key is None:
            return
        # a probe-tick plan deliberately took the LOSING strategy so its
        # cost profile stays fresh — caching it would replay the loser
        # for every later identical query, turning a bounded 1-in-16
        # exploration into a permanent per-filter regression. The next
        # identical query replans (a non-probe tick) and caches normally.
        if getattr(value[2], "strategy_source", "") == "probe":
            return
        with st.lock:
            if st.indices is not indices:
                return  # state swapped since our snapshot: plan is stale
            st.plan_cache[key] = value
            while len(st.plan_cache) > self._PLAN_CACHE_MAX:
                st.plan_cache.popitem(last=False)

    def cache_report(self) -> dict:
        """The buffer-pool / query-cache / pyramid gauge block
        (docs/observability.md § Buffer pool & query cache): served by
        ``GET /api/metrics`` and rendered by ``explain(analyze=True)``."""
        pool = getattr(self.backend, "pool", None)
        pyramid_bytes = {}
        for name, st in list(self._types.items()):
            with st.lock:
                total = sum(
                    p.nbytes for p, _stamp in st.pyramids.values()
                    if p is not None
                )
            if total:
                pyramid_bytes[name] = total
        return {
            "agg_cache": self.agg_cache.snapshot(),
            "pyramid_bytes": pyramid_bytes,
            "pool": pool.snapshot() if pool is not None else None,
        }

    def cache_prometheus_lines(self, prefix: str = "geomesa") -> list[str]:
        lines = self.agg_cache.prometheus_lines(prefix)
        rep = self.cache_report()
        lines.append(f"# TYPE {prefix}_pyramid_bytes gauge")
        for t, b in sorted(rep["pyramid_bytes"].items()):
            lines.append(f'{prefix}_pyramid_bytes{{type="{t}"}} {b}')
        pool = getattr(self.backend, "pool", None)
        if pool is not None:
            lines += pool.prometheus_lines(prefix)
        return lines

    def device_residency(self, type_name: str) -> dict:
        """HBM residency report for one type: per-index device bytes, total,
        and the backend's budget (the managed hot-tier view of SURVEY.md
        §2.20 P9 — indexes over budget serve from the host path instead)."""
        st = self._state(type_name)
        with st.lock:
            state = st.backend_state
        per_index = (
            TpuBackend.residency(state)
            if isinstance(self.backend, TpuBackend)
            else {}
        )
        return {
            "indices": per_index,
            "total_bytes": int(sum(per_index.values())),
            "budget_bytes": getattr(self.backend, "max_device_bytes", None),
            "resident": bool(per_index),
        }

    def evict_device(self, type_name: str) -> None:
        """Drop one type's device-resident arrays (host stays authoritative;
        queries fall back to exact host scans). ``recover(type_name)``
        re-uploads — together the explicit HBM tier controls."""
        st = self._state(type_name)
        # mutate_lock: a concurrent rebuild/recover mid backend.load() would
        # otherwise re-install device state right after this eviction
        with st.mutate_lock:
            with st.lock:
                st.backend_state = None
                # pyramids hold the device count mirrors (ledger group
                # "pyramid"): they must not outlive an explicit eviction
                st.pyramids.clear()
        # explicit eviction is operator intent to free the HBM NOW: the
        # pool's pins AND its donation stash for this type both drop (a
        # stashed copy would silently keep the bytes resident)
        pool = getattr(self.backend, "pool", None)
        if pool is not None:
            pool.purge(type_name)
        # the ledger entries unregister themselves when the dropped state
        # is collected; the spill report is explicit bookkeeping, clear it
        from geomesa_tpu.obs import devmon

        devmon.ledger().clear_spills(type_name)
        self.metrics.counter("store.device.evictions").inc()

    def query_iter(
        self,
        type_name: str,
        q: "Query | str | ast.Filter | None" = None,
        batch_rows: int = 65536,
        **kwargs,
    ):
        """Stream query results as bounded ``FeatureTable`` batches — the
        GeoTools feature-reader / ``CloseableIterator`` role
        (``GeoMesaDataStore.scala:390``): exports and clients page through
        results without holding one giant formatted payload."""
        if batch_rows < 1:
            raise ValueError("batch_rows must be >= 1")
        # run the query eagerly so schema/filter errors raise at the call
        # site, not at the consumer's first next()
        t = self.query(type_name, q, **kwargs).table

        def _gen():
            for lo in range(0, len(t), batch_rows):
                yield t.take(np.arange(lo, min(lo + batch_rows, len(t))))

        return _gen()

    def _batch_gate(self, st: _TypeState, want_bbox: bool):
        """Shared gate for the batched device fan-outs (count_many /
        density_many): coherent snapshot, device residency, and the
        conditions under which loose batched execution is NOT equivalent
        (hot-tier rows, TTL masking, no resident columns). Returns
        (main, main_n, point_state, bbox_state, batchable, perm) — ``perm``
        maps the point state's sorted positions to original rows (the
        exact-count correction path needs it)."""
        main, indices, backend_state, _stats, delta_table = st.snapshot()
        main_n = 0 if main is None else len(main)
        dev = bbox_dev = None
        perm = None
        if isinstance(self.backend, TpuBackend) and self._device_available():
            dev, dev_name = TpuBackend.point_state(backend_state)
            if dev is not None and dev_name in (indices or {}):
                perm = indices[dev_name].perm
            if dev is None and want_bbox:
                # extended-geometry store: loose tests are bbox overlaps
                bbox_dev, bbox_name = TpuBackend.bbox_state(backend_state)
                if bbox_dev is not None and bbox_name in (indices or {}):
                    perm = indices[bbox_name].perm
        batchable = not (
            (dev is None and bbox_dev is None)
            or delta_table is not None
            or main_n == 0
            # TTL masking is injected per-query in query(); loose batched
            # passes would include expired rows — take the exact path
            or self._age_off_ttl_ms(st.sft) is not None
        )
        return main, main_n, dev, bbox_dev, batchable, perm

    def _batch_payloads(self, st: _TypeState, qs, overlap: bool, viewport=None):
        """Shared batchability loop: which queries are pure bbox+time
        conjunctions on the default geom/date fields (anything else has
        residual semantics the loose kernels can't honor) → their int-domain
        payloads. ``viewport``: intersect every query's spatial bounds with
        this (xmin, ymin, xmax, ymax) box — rows outside it must not match
        (the density viewport). Returns [(query idx, payload | None,
        exactable)] — ``exactable`` is False when packing WIDENED the
        payload (more boxes/intervals than the kernel slots), i.e. the int
        result is a superset even beyond edge-bucket quantization and
        cannot be corrected to exact."""
        from dataclasses import replace as _replace

        from geomesa_tpu.filter.bounds import extract as _extract
        from geomesa_tpu.ops.refine import MAX_BOXES, MAX_TIMES

        pending: list[tuple[int, tuple | None, bool]] = []
        # the batch's planning: every query's parse, extraction and
        # payload, under ONE span for the whole batch
        with obs.span("plan", queries=len(qs)):
            for i, q in enumerate(qs):
                f = q.resolved_filter()
                if (
                    not _pure_bbox_time(f, st.sft)
                    or _semantic_hints(q)
                    or q.auths is not None
                    or q.limit is not None
                    or q.start_index is not None
                ):
                    continue
                e = _extract(f, st.sft.geom_field, st.sft.dtg_field)
                if viewport is not None and not e.disjoint:
                    vx1, vy1, vx2, vy2 = viewport
                    boxes = e.boxes if e.boxes is not None else [
                        (-180.0, -90.0, 180.0, 90.0)
                    ]
                    clipped = []
                    for x1, y1, x2, y2 in boxes:
                        nx1, ny1 = max(x1, vx1), max(y1, vy1)
                        nx2, ny2 = min(x2, vx2), min(y2, vy2)
                        if nx1 <= nx2 and ny1 <= ny2:
                            clipped.append((nx1, ny1, nx2, ny2))
                    if not clipped:
                        pending.append((i, None, True))
                        continue
                    e = _replace(e, boxes=clipped)
                payload = (
                    None
                    if e.disjoint
                    else self.backend._payload(st.sft, e, overlap=overlap)
                )
                n_boxes = len(e.boxes) if e.boxes is not None else 1
                n_times = len(e.intervals) if e.intervals is not None else 1
                exactable = n_boxes <= MAX_BOXES and n_times <= MAX_TIMES
                pending.append((i, payload, exactable))
        return pending

    def _stage_batch(self, live, *extra):
        """Host staging of a batched fan-out before its one dispatch: the
        live queries' payloads stacked, the query axis padded to divide
        the mesh query axis (with duplicates the caller discards), the
        transfer accounted, and every array put on the device. ``live``:
        [(query idx, (boxes, times))]; ``extra``: further per-query
        arrays padded alongside. Returns (mesh, device arrays: boxes,
        times, *extra)."""
        import jax.numpy as jnp

        from geomesa_tpu.obs.jaxmon import count_h2d
        from geomesa_tpu.parallel.mesh import pad_query_axis

        with obs.span("stage", queries=len(live)):
            mesh = self.backend._get_mesh()
            arrays, _ = pad_query_axis(
                mesh,
                np.stack([p[0] for _, p in live]),
                np.stack([p[1] for _, p in live]),
                *extra,
            )
            count_h2d(*arrays)  # per-batch payload staging
            return mesh, tuple(jnp.asarray(a) for a in arrays)

    @dispatch_budget(2, signatures=("*:rows",))
    def select_many(self, type_name: str, queries) -> list:
        """Batched row retrieval: results identical to
        ``[self.query(type_name, q) for q in queries]`` with the whole
        batch's device work in TWO dispatches — a planned pair-count pass
        that sizes the gather exactly, then one block gather serving every
        query (``TpuBackend.select_many_positions``). Dispatch RTT
        amortizes across the batch the way the fused count/density paths
        do (SURVEY.md §2.20 P4; the reference's BatchScanner multi-range
        scan, ``AccumuloQueryPlan.scala:136`` role). Queries that don't
        fit the batched shape — sub-plan unions, non-resident indexes, an
        open device circuit, the oracle backend, per-query timeouts —
        transparently run per-query instead, same results either way.
        Point AND extended-geometry (XZ bbox-layout) stores both batch.
        """
        queries = list(queries)
        # ONE batch span; every query lands a per-query child span (the
        # fallback path through query() and the batched tail both open one)
        with obs.span("select_many", n_queries=len(queries)):
            # one SHARED roundtrip ledger for the whole batch: the batched
            # dispatches charge every member query's signature (the
            # coalescer attribution contract); per-query fallbacks open
            # their own nested ledger inside query()
            from geomesa_tpu.obs import ledger as _rtledger

            with _rtledger.roundtrip():
                return self._run_select_many(type_name, queries)

    def _run_select_many(self, type_name: str, queries) -> list:
        import time as _time

        st = self._state(type_name)
        qs_raw = [
            Query(filter=q) if isinstance(q, (str, ast.Filter)) or q is None
            else q
            for q in queries
        ]
        qs = (
            [self._intercept(type_name, st.sft, q) for q in qs_raw]
            if self._interceptors
            else list(qs_raw)
        )
        ttl = self._age_off_ttl_ms(st.sft)
        if ttl is not None and st.sft.dtg_field is not None:
            from dataclasses import replace as _replace

            qs = [
                _replace(
                    q,
                    filter=ast.And((
                        q.resolved_filter(),
                        ast.Compare(
                            ">=", st.sft.dtg_field,
                            _ttl_cutoff_ms(ttl, q.hints.get("now_ms")),
                        ),
                    )),
                )
                for q in qs
            ]

        def _fallback(i):
            # the ORIGINAL query object: query() runs interceptors itself,
            # so handing it the intercepted copy would intercept twice
            return self.query(type_name, qs_raw[i])

        if (
            st.total_rows == 0
            or isinstance(self.backend, OracleBackend)
            or not self._device_available()
        ):
            return [_fallback(i) for i in range(len(qs))]
        # audit epoch for the batched tail (the coalescer's shared
        # dispatches and the sharded view's per-member batches both land
        # here): read BEFORE the snapshot so a racing write abstains
        from geomesa_tpu.obs import audit as _obsaudit

        audit_epoch = None
        if not _obsaudit.in_shadow() and (
                _obsaudit.ENABLED
                or any(q.hints.get("audit") for q in qs)):
            audit_epoch = st.data_epoch()

        t_start = _time.perf_counter()
        main, indices, backend_state, stats, delta_table = st.snapshot()
        main_n = 0 if main is None else len(main)
        if main_n == 0 or not backend_state:
            return [_fallback(i) for i in range(len(qs))]

        planned = []
        with obs.span("plan", queries=len(qs)):
            for q in qs:
                cache_key = None if ttl is not None else self._plan_cache_key(q)
                cached = self._plan_lookup(st, indices, cache_key)
                if cached is None:
                    planner = QueryPlanner(st.sft, indices, stats)
                    cached = planner.plan(
                        q, under_burn=self._under_burn(type_name))
                    self._plan_store(st, indices, cache_key, cached)
                planned.append((q, *cached))  # (q, plan, f, info)
        plan_ms = (_time.perf_counter() - t_start) * 1000.0

        results: list = [None] * len(qs)
        groups: dict[str, list[int]] = {}
        for i, (q, plan, f, info) in enumerate(planned):
            dev = backend_state.get(info.index_name)
            if (
                info.sub_plans
                or dev is None
                or getattr(dev, "kind", None) not in ("points", "bboxes")
                or q.hints.get("timeout") is not None
                or q.hints.get("deadline") is not None
            ):
                results[i] = _fallback(i)
            else:
                groups.setdefault(info.index_name, []).append(i)

        from geomesa_tpu.store.reduce import reduce_result

        for index_name, idxs in groups.items():
            dev = backend_state[index_name]
            index = indices[index_name]
            try:
                pos_lists = self.backend.select_many_positions(
                    dev, index,
                    [planned[i][3].extraction for i in idxs],
                    [planned[i][1].intervals for i in idxs],
                )
            except Exception as e:  # noqa: BLE001 — failover, re-raise rest
                if not self._is_device_error(e):
                    raise
                self._trip_device_circuit(e)
                self.metrics.counter("store.query.device_failovers").inc()
                for i in idxs:
                    results[i] = _fallback(i)
                continue
            self._note_device_ok()
            # audit decomposition: the shared device dispatches split
            # evenly across the batch; each query's host tail (residual +
            # reduce) is timed individually — a later query's audit row
            # must not absorb earlier queries' reduce time
            shared_ms = (_time.perf_counter() - t_start) * 1000.0 - plan_ms
            for i, positions in zip(idxs, pos_lists):
                q, plan, f, info = planned[i]
                tq0 = _time.perf_counter()
                # per-query child span: the host tail each batched query
                # pays individually (residual refine + reduce); the shared
                # dispatch spans above cover the device half
                with obs.span("query", batch_index=i, batched=True):
                    with obs.span("refine", candidates=len(positions)):
                        rows = index.perm[positions]
                        # exact residual: same contract as backend.select
                        # (int superset culled on device, f64 filter
                        # settles the rest)
                        if len(rows) and not isinstance(f, ast.Include):
                            rows = rows[ast.residual_mask(f, main, rows)]
                        rows = np.sort(rows)
                        if delta_table is not None:
                            drows = np.nonzero(f.mask(delta_table))[0]
                            rows = np.concatenate([rows, drows + main_n])
                    with obs.span("reduce", rows=len(rows)):
                        table = _take_combined(st.sft, main, main_n,
                                               delta_table, rows)
                        tbl, rws, density, stats_out, bin_data = reduce_result(
                            st.sft, table, rows, q)
                    tail_ms = (_time.perf_counter() - tq0) * 1000.0
                    from geomesa_tpu.obs import devmon as _devmon

                    with obs.span("telemetry"):
                        self._audit(type_name, q, plan_ms / len(qs),
                                    shared_ms / len(idxs) + tail_ms, len(tbl),
                                    sig=_devmon.plan_signature(info, q))
                results[i] = QueryResult(
                    tbl, rws, info, density=density, stats=stats_out,
                    bin_data=bin_data,
                )
                if (audit_epoch is not None
                        and _obsaudit.eligible_select(q)
                        and (q.hints.get("audit")
                             or (_obsaudit.ENABLED and _obsaudit.sampled()))):
                    _obsaudit.get().enqueue_select(
                        self, type_name, q, audit_epoch, tbl)
        return results

    def count_many(self, type_name: str, queries, loose: bool = True):
        """Batched counts for many queries in ONE device pass.

        The multi-query fan-out path (SURVEY.md §2.20 P4): all bbox+time
        queries are evaluated against the resident columns in a single fused
        scan (``ops.pallas_kernels.batched_count``). ``loose`` counts in the
        int key domain without the exact residual refine — the reference's
        loose-bbox hint semantics (``QueryHints`` ``geomesa.loose.bbox``).

        ``loose=False`` STAYS batched on both store kinds: the fused int
        count plus a device gather of the spatial edge-bucket candidates
        (the only rows where the int superset can diverge from f64 —
        interior buckets of a closed box are f64-certain, and strict int
        inequality on an overlap axis implies the f64 inequality)
        re-tested host-side against the full filter AST. Mixed-filter
        queries, widened payloads, truncated candidate lanes, or a
        non-empty hot tier fall back to exact per-query execution.
        """
        queries = list(queries)
        # ONE batch span (plan, stage, jit, sync and telemetry children)
        # and one SHARED roundtrip ledger: the batched dispatch and sync
        # charge every member query's signature, as in select_many;
        # per-query fallbacks open their own nested ledger inside query()
        with obs.span("count_many", n_queries=len(queries)):
            from geomesa_tpu.obs import ledger as _rtledger

            with _rtledger.roundtrip():
                return self._run_count_many(type_name, queries, loose)

    def _run_count_many(self, type_name: str, queries, loose: bool):
        from geomesa_tpu.obs import ledger as _rtledger

        st = self._state(type_name)
        qs = [
            Query(filter=q) if isinstance(q, (str, ast.Filter)) or q is None else q
            for q in queries
        ]
        # interceptors see every query exactly as query() would show them
        if self._interceptors:
            qs = [self._intercept(type_name, st.sft, q) for q in qs]

        def _exact(q):
            return self.query(type_name, q).count

        # audit epoch for batched EXACT counts (loose counts are a
        # documented int-domain superset — comparing them to the exact
        # referee would alarm by design, so only loose=False audits);
        # read BEFORE the _batch_gate snapshot so racing writes abstain
        from geomesa_tpu.obs import audit as _obsaudit

        audit_epoch = None
        if (not loose and not _obsaudit.in_shadow()
                and (_obsaudit.ENABLED
                     or any(q.hints.get("audit") for q in qs))):
            audit_epoch = st.data_epoch()

        main, main_n, dev, bbox_dev, batchable, perm = self._batch_gate(
            st, want_bbox=True
        )
        # exact batched mode needs resident columns + a position→row map
        # for the edge-candidate residual; anything else goes per-query
        if not batchable or (
            not loose and (perm is None or main is None)
        ):
            return [_exact(q) for q in qs]
        pending = self._batch_payloads(
            st, qs, overlap=bbox_dev is not None
        )

        out: list = [None] * len(qs)
        live = [
            (i, p) for i, p, ok in pending
            if p is not None and (loose or ok)
        ]
        for i, p, ok in pending:
            if p is None:
                out[i] = 0  # disjoint filter: exactly zero either mode
        if live:
            import jax.numpy as jnp

            from geomesa_tpu.parallel.query import (
                cached_batched_count_step,
                cached_batched_overlap_step,
            )

            edge_pos = edge_hits = None
            cap = 512
            try:
                # one fused scan over the mesh-sharded columns, counts
                # psum-merged over the data axis (P4 + P6); the query batch
                # is padded to divide the mesh query axis
                mesh, (boxes, times) = self._stage_batch(live)
                if not loose:
                    # ONE fused pass returns counts AND the boundary
                    # candidates — exact mode costs the same device scan
                    from geomesa_tpu.parallel.query import (
                        cached_batched_edge_gather_step,
                    )

                    gather = cached_batched_edge_gather_step(
                        mesh, cap, overlap=bbox_dev is not None
                    )
                    col_args = (bbox_dev or dev).spatial_cols()
                    counts, edge_pos, edge_hits = gather(
                        *col_args, jnp.int32(main_n), boxes, times,
                    )
                    counts = _rtledger.materialize(counts)
                    edge_pos = _rtledger.materialize(edge_pos)  # (Qp, D, cap)
                    edge_hits = _rtledger.materialize(edge_hits)  # (Qp, D)
                elif bbox_dev is not None:
                    step = cached_batched_overlap_step(mesh, with_time=True)
                    counts = _rtledger.materialize(
                        step(
                            *bbox_dev.spatial_cols(),
                            jnp.int32(main_n), boxes, times,
                        )
                    )
                else:
                    c = dev.cols
                    step = cached_batched_count_step(mesh)
                    counts = _rtledger.materialize(
                        step(
                            c["x"], c["y"], c["bins"], c["offs"],
                            jnp.int32(main_n), boxes, times,
                        )
                    )
            except Exception as e:  # noqa: BLE001 — failover to exact host path
                if not self._is_device_error(e):
                    raise
                self._trip_device_circuit(e)
                self.metrics.counter("store.query.device_failovers").inc()
                counts = None
            if counts is not None:
                self._note_device_ok()
                if loose:
                    for k, (i, _) in enumerate(live):
                        out[i] = int(counts[k])
                else:
                    # exact mode: subtract edge-bucket candidates failing
                    # the full f64 filter AST (a handful of rows per query)
                    cap = edge_pos.shape[2]
                    for k, (i, _) in enumerate(live):
                        if (edge_hits[k] > cap).any():
                            continue  # truncated lanes → per-query exact
                        cand = np.concatenate([
                            edge_pos[k, d, : edge_hits[k, d]]
                            for d in range(edge_pos.shape[1])
                        ]).astype(np.int64)
                        corr = 0
                        if len(cand):
                            rows = perm[cand]
                            f = qs[i].resolved_filter()
                            m = ast.residual_mask(f, main, rows)
                            corr = int((~m).sum())
                        out[i] = int(counts[k]) - corr
        # batched queries still hit metrics + the audit trail (one
        # telemetry span for the whole batch)
        with obs.span("telemetry", queries=len(pending)):
            for i, _p, _ok in pending:
                if out[i] is None:
                    continue  # device failover: the exact path audits these
                self.metrics.counter("store.queries").inc()
                self._audit(type_name, qs[i], 0.0, 0.0, out[i])
                if (audit_epoch is not None
                        and _obsaudit.eligible_select(qs[i])
                        and (qs[i].hints.get("audit")
                             or (_obsaudit.ENABLED and _obsaudit.sampled()))):
                    _obsaudit.get().enqueue_count(
                        self, type_name, qs[i], audit_epoch, int(out[i]))
        for i, q in enumerate(qs):
            if out[i] is None:
                out[i] = _exact(q)
        return out

    # -- distributed SQL aggregation (GROUP BY on the mesh) ------------------

    _AGG_MAX_GROUPS = 65536  # beyond this the host fold is the better engine

    def _agg_group_ids(self, main, group_by):
        """Factorize the GROUP BY key columns over ``main`` → (int32 group id
        per row, group keys as tuples in first-occurrence row order — the
        order the host fold produces, so results are order-identical)."""
        n = len(main)
        if not group_by:
            return np.zeros(n, dtype=np.int32), [()]
        ids: list[np.ndarray] = []
        vocabs: list[list] = []
        for g in group_by:
            col = main.columns[g]
            vals = col.values
            if (
                isinstance(vals, np.ndarray)
                and vals.dtype.kind == "f"
                and np.isnan(vals).any()
            ):
                # host parity is impossible: the host fold's per-object dict
                # makes EVERY NaN key its own group (nan != nan), while
                # np.unique collapses them — decline the device path
                raise ValueError("NaN GROUP BY keys take the host fold")
            # string columns: the cached dictionary codes (ArrowDictionary
            # role) replace an O(n log n) OBJECT-array sort with int32 work —
            # the dominant cost of cold aggregation staging at 10M+ rows.
            # Only when every value is a SET STRING: the dictionary maps
            # invalid AND stray non-str values to "", which would collide
            # with a real "" / diverge from the host fold's raw-value keys
            d = None
            if col.valid is None and all(type(v) is str for v in vals):
                d = col.dictionary()
            if d is not None:
                vocab, codes = d
                vocabs.append(list(vocab))
                ids.append(codes.astype(np.int64))
                continue
            try:
                uniq, inv = np.unique(vals, return_inverse=True)
                vocabs.append(list(uniq))
                ids.append(inv.astype(np.int64))
            except TypeError:
                # object column with None/mixed values: dict factorize
                seen: dict = {}
                inv = np.empty(n, dtype=np.int64)
                vocab: list = []
                for i, v in enumerate(vals):
                    j = seen.get(v)
                    if j is None:
                        j = seen[v] = len(vocab)
                        vocab.append(v)
                    inv[i] = j
                vocabs.append(vocab)
                ids.append(inv)
        code = ids[0]
        for k in range(1, len(ids)):
            base = len(vocabs[k]) + 1
            if int(code.max(initial=0)) > (2**62) // base:
                raise ValueError("group key space overflows the device path")
            code = code * base + ids[k]
        uniq_codes, first, inv = np.unique(
            code, return_index=True, return_inverse=True
        )
        order = np.argsort(first, kind="stable")
        rank = np.empty(len(order), dtype=np.int64)
        rank[order] = np.arange(len(order))
        gid = rank[inv].astype(np.int32)
        keys = []
        for ci in uniq_codes[order]:
            c = int(ci)
            parts = []
            for k in range(len(ids) - 1, 0, -1):
                base = len(vocabs[k]) + 1
                parts.append(vocabs[k][c % base])
                c //= base
            parts.append(vocabs[0][c])
            keys.append(tuple(reversed(parts)))
        return gid, keys

    def _agg_residency(self, dev, main, perm, group_by, value_cols,
                       type_name: str = "?", index_name: str = "?"):
        """Stage (or fetch from ``dev.agg_cache``) the group-id column and a
        stacked (V, N) f64 value matrix into the mesh layout, aligned with
        ``dev``'s sharded x/y columns (same perm, same padding). The cache
        lives on the state object, so compactions that rebuild the layout
        drop it automatically. Raises TypeError/ValueError for columns the
        f64 device fold cannot carry (strings, geometries) — callers fall
        back to the host fold."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from geomesa_tpu.parallel.mesh import (
            DATA_AXIS,
            data_shards,
            pad_rows,
            shard_columns,
        )
        from geomesa_tpu.store.backends import JOIN_BLOCK

        mesh = self.backend._get_mesh()
        # the process-level pool budget covers agg staging too: every
        # device allocation below asks for room first (evicting colder
        # buffers), and a refusal raises ValueError → the host fold
        padded_est = pad_rows(
            max(len(main), data_shards(mesh)), data_shards(mesh), JOIN_BLOCK
        )

        def _room(nbytes: int, what: str) -> None:
            if not self.backend.pool.ensure_room(int(nbytes)):
                raise ValueError(f"device budget refuses agg {what}")

        gkey = ("gid", tuple(group_by or ()))
        cached = dev.agg_cache.get(gkey)
        if cached is None:
            _room(padded_est * 4, "group-id staging")
            gid_orig, keys = self._agg_group_ids(main, group_by)
            if len(keys) > self._AGG_MAX_GROUPS:
                raise ValueError("group cardinality beyond the device path")
            cols, _, _ = shard_columns(
                mesh, {"gid": gid_orig[perm].astype(np.int32)},
                multiple=JOIN_BLOCK,
            )
            cached = (cols["gid"], gid_orig, keys)
            dev.agg_cache[gkey] = cached
            # agg staging is device residency too: ledger it under the
            # "agg" column group (dies with `dev`, so unregistration rides
            # the same finalizer as the spatial columns); the pool entry
            # for (type, index) absorbs the bytes — same owner, same pins
            from geomesa_tpu.obs import devmon
            from geomesa_tpu.store.bufferpool import register_residency

            register_residency(
                self.backend.pool, type_name, index_name, devmon.GROUP_AGG,
                int(cols["gid"].nbytes), owner=dev)
        rowid = dev.agg_cache.get(("rowid",))
        if rowid is None:
            _room(padded_est * 4, "row-id staging")
            # original row index per lane: the device computes each group's
            # first MATCHING row (segment_min), which orders the output
            # groups exactly as the host fold's first-occurrence-over-
            # filtered-rows construction does
            rcols, _, _ = shard_columns(
                mesh, {"rowid": np.asarray(perm, dtype=np.int32)},
                multiple=JOIN_BLOCK, pad_value=np.iinfo(np.int32).max,
            )
            rowid = rcols["rowid"]
            dev.agg_cache[("rowid",)] = rowid
            from geomesa_tpu.obs import devmon
            from geomesa_tpu.store.bufferpool import register_residency

            register_residency(
                self.backend.pool, type_name, index_name, devmon.GROUP_AGG,
                int(rowid.nbytes), owner=dev)
        # value columns cache PER COLUMN (one device + one host copy each,
        # however many SELECT-list combinations arrive); the per-request
        # (V, N) matrix is a device-side concat — no host↔device transfer
        sharding = NamedSharding(mesh, PartitionSpec(None, DATA_AXIS))
        shards = data_shards(mesh)
        padded = pad_rows(max(len(main), shards), shards, JOIN_BLOCK)
        per_dev, per_host = [], []
        for c in value_cols:
            got = dev.agg_cache.get(("val", c))
            if got is None:
                _room(padded * 8, "value-column staging")
                col = main.columns[c]
                v = np.asarray(col.values, dtype=np.float64).copy()
                if col.valid is not None:
                    v[~col.valid] = np.nan
                pv = np.zeros((1, padded), dtype=np.float64)
                pv[0, : len(main)] = v[perm]
                got = (jax.device_put(pv, sharding), v)
                dev.agg_cache[("val", c)] = got
                from geomesa_tpu.obs import devmon
                from geomesa_tpu.obs.jaxmon import count_h2d
                from geomesa_tpu.store.bufferpool import register_residency

                # pool warm-up staging, attributed to the POOL: the query
                # that happened to trigger the miss must not absorb these
                # bytes in its devprof h2d split (satellite red/green in
                # tests/test_geoblocks.py)
                count_h2d(pv, label="pool")
                register_residency(
                    self.backend.pool, type_name, index_name,
                    devmon.GROUP_AGG, int(got[0].nbytes), owner=dev)
            per_dev.append(got[0])
            per_host.append(got[1])
        if per_dev:
            import jax.numpy as jnp

            dv = jax.device_put(jnp.concatenate(per_dev, axis=0), sharding)
        else:
            dv = jax.device_put(
                np.zeros((0, padded), dtype=np.float64), sharding
            )
        hv = (
            np.stack(per_host)
            if per_host
            else np.zeros((0, len(main)), dtype=np.float64)
        )
        return cached, rowid, dv, hv

    # -- GeoBlocks helpers (ops/geoblocks.py) --------------------------------

    @staticmethod
    def _agg_cache_key(q, group_by, value_cols):
        """Exact-repeat aggregation cache key: the literal predicate text
        plus GROUP BY and value columns. None = uncacheable (hints, auths,
        paging, or an un-serializable filter)."""
        if (_semantic_hints(q) or q.auths is not None
                or q.limit is not None or q.start_index is not None):
            return None
        base = DataStore._plan_cache_key(q)
        if base is None:
            return None
        return (base[0], tuple(group_by or ()), tuple(value_cols or ()))

    def _pyramid_extraction(self, st, q):
        """The query's Extraction when it can ride the pyramid: a pure
        bbox+time conjunction over the default geom/date fields with at
        most ONE box and ONE interval (the interior/boundary decomposition
        is per-rectangle). None = take the fused or host path."""
        f = q.resolved_filter()
        if (
            not _pure_bbox_time(f, st.sft)
            or _semantic_hints(q)
            or q.auths is not None
            or q.limit is not None
            or q.start_index is not None
        ):
            return None
        from geomesa_tpu.filter.bounds import extract as _extract

        e = _extract(f, st.sft.geom_field, st.sft.dtg_field)
        if e.boxes is not None and len(e.boxes) != 1:
            return None
        if e.intervals is not None and len(e.intervals) != 1:
            return None
        return e

    def _pyramid(self, st: _TypeState, type_name: str, main, group_by,
                 value_cols, main_epoch: int):
        """The (group_by, value_cols) pre-aggregation pyramid for the
        CURRENT main tier, built lazily once per rebuild epoch (an O(n)
        host pass — one stable sort — amortized over every subsequent
        aggregate). None when the shape can't ride: non-point geometries,
        string/geometry value columns, group cardinality or byte cap
        exceeded — the failure is remembered per epoch so it isn't
        retried per query."""
        pkey = (tuple(group_by or ()), tuple(value_cols))
        with st.lock:
            cached = st.pyramids.get(pkey)
        if cached is not None:
            pyr, stamp = cached
            if stamp == main_epoch:
                if pyr is not None and pyr.device.get("cnt") is not None:
                    # a recover()-path backend.load parked this pyramid's
                    # pool entry in the donation stash (release keeps
                    # same-fingerprint entries) while the mirror kept
                    # serving from st.pyramids — re-admit it: stash bytes
                    # are reclaimable spare capacity, these are working
                    # set and must stay budget-accounted and evictable
                    pool = getattr(self.backend, "pool", None)
                    if pool is not None:
                        pool.take_donated(
                            type_name, _pyramid_index_name(pkey),
                            main_epoch,
                            on_evict=_pyramid_evictor(st, pkey, pyr))
                return pyr  # pyr may be None: the remembered failure
        from geomesa_tpu.ops.geoblocks import AggPyramid

        import time as _time

        t0 = _time.perf_counter()
        pyr = None
        try:
            col = main.geom_column() if st.sft.geom_field else None
            if col is None or col.x is None:
                raise ValueError("pyramid needs point geometries")
            gid_orig, keys = self._agg_group_ids(main, group_by)
            if len(keys) > self._AGG_MAX_GROUPS:
                raise ValueError("group cardinality beyond the pyramid")
            from geomesa_tpu.curve.binned_time import BinnedTime
            from geomesa_tpu.curve.normalize import (
                lat as norm_lat,
                lon as norm_lon,
            )
            from geomesa_tpu.store.backends import REFINE_PRECISION

            xi = norm_lon(REFINE_PRECISION).normalize(col.x).astype(np.int64)
            yi = norm_lat(REFINE_PRECISION).normalize(col.y).astype(np.int64)
            if st.sft.dtg_field:
                bins, _offs = BinnedTime(
                    st.sft.z3_interval).to_bin_and_offset(main.dtg_millis())
            else:
                bins = np.zeros(len(main), dtype=np.int64)
            vals = []
            for c in value_cols:
                cv = main.columns[c]
                v = np.asarray(cv.values, dtype=np.float64).copy()
                if cv.valid is not None:
                    v[~cv.valid] = np.nan
                vals.append(v)
            vmat = (np.stack(vals) if vals
                    else np.zeros((0, len(main)), dtype=np.float64))
            pyr = AggPyramid(xi, yi, bins, gid_orig, keys, vmat,
                             epoch=main_epoch)
        except (TypeError, ValueError):
            pyr = None
        if pyr is not None:
            self._pyramid_mirror(st, type_name, pkey, pyr, main_epoch)
            self.metrics.histogram("store.agg.pyramid_build_ms").update(
                (_time.perf_counter() - t0) * 1000.0)
        with st.lock:
            if st.epoch == main_epoch:
                st.pyramids[pkey] = (pyr, main_epoch)
        return pyr

    def _pyramid_mirror(self, st, type_name, pkey, pyr, main_epoch) -> None:
        """Device mirror of the finest level's count partials — the layout
        a fused device kernel reads — registered with the residency ledger
        and pinned/evictable through the buffer pool. The staging bytes
        are POOL traffic, not any query's (jaxmon ``label="pool"``).
        Best-effort: an open device circuit just skips the mirror."""
        pool = getattr(self.backend, "pool", None)
        if pool is None or not self._device_available():
            return
        try:
            import jax

            from geomesa_tpu.obs import devmon
            from geomesa_tpu.obs.jaxmon import count_h2d

            host = pyr.levels[-1].cnt.astype(np.int32)
            if not pool.ensure_room(int(host.nbytes)):
                return  # pool budget refuses the mirror: host-only pyramid
            count_h2d(host, label="pool")
            dev = jax.device_put(host)
            pyr.device["cnt"] = dev
            devmon.ledger().register(
                type_name, "geoblocks", devmon.GROUP_PYRAMID,
                int(dev.nbytes), owner=pyr)
            # pool key is per-PYRAMID (pkey = group_by + value_cols), not
            # per-type: a second aggregation shape registering under the
            # same key would REPLACE the first's entry (bufferpool
            # register semantics) while st.pyramids still held its mirror
            # resident — bytes in HBM invisible to the budget, evictor
            # lost
            pool.register(
                type_name, _pyramid_index_name(pkey), devmon.GROUP_PYRAMID,
                int(dev.nbytes), owner=pyr, fingerprint=main_epoch,
                on_evict=_pyramid_evictor(st, pkey, pyr))
        except Exception as e:  # noqa: BLE001 — mirror is optional
            if not self._is_device_error(e):
                raise
            self._trip_device_circuit(e)

    def _pyramid_answer(self, q, st, main, delta, pyr, e, value_cols,
                        group_by):
        """One exact grouped aggregate from the pyramid: interior partials
        + boundary rows refined against the full f64 filter AST + the
        delta fold — the same correction machinery the fused device path
        feeds (:meth:`_assemble_agg`)."""
        from geomesa_tpu.curve.normalize import lat as norm_lat, lon as norm_lon
        from geomesa_tpu.store.backends import REFINE_PRECISION, time_quads

        box = None
        if e.boxes is not None:
            nlon = norm_lon(REFINE_PRECISION)
            nlat = norm_lat(REFINE_PRECISION)
            x1, y1, x2, y2 = e.boxes[0]
            box = (int(nlon.normalize(x1)), int(nlon.normalize(x2)),
                   int(nlat.normalize(y1)), int(nlat.normalize(y2)))
        window = None
        quads = time_quads(st.sft, e.intervals)
        if quads is not None:
            blo, olo, bhi, ohi = (int(v) for v in quads[0])
            if (blo, olo) > (bhi, ohi):  # clamped to unsatisfiable
                return self._assemble_agg_empty(value_cols)
            window = (blo, olo, bhi, ohi)
        cnt, first, vcnt, vsum, vmin, vmax, brows = pyr.answer(box, window)
        return self._assemble_agg(
            q, main, delta, pyr.keys, value_cols,
            cnt, first, vcnt, vsum, vmin, vmax,
            np.sort(brows), pyr.gid, pyr.host_vals, group_by,
        )

    @dispatch_budget(1, signatures=("*:stats",))
    def aggregate_many(self, type_name: str, queries, group_by=None,
                       value_cols=(), now_ms: int | None = None):
        """See :meth:`_aggregate_many_impl` (the engine). This wrapper
        adds the correctness-audit hook: sampled (or hint-tagged)
        answered lanes enqueue a shadow grouped-agg comparison against
        the independent referee, stamped with the data epoch the engine
        read BEFORE its snapshot (abstain-on-write semantics)."""
        from geomesa_tpu.obs import audit as _obsaudit

        box: dict = {}
        queries = list(queries)
        # ONE batch span: plan, stage, jit, sync and telemetry children
        with obs.span("aggregate_many", n_queries=len(queries)):
            out = self._aggregate_many_impl(
                type_name, queries, group_by=group_by, value_cols=value_cols,
                now_ms=now_ms, audit_box=box)
        if "epoch" in box and not _obsaudit.in_shadow():
            qs = box["qs"]
            for i, r in enumerate(out):
                if r is None:
                    continue  # declined: the caller's host fold answers
                q = qs[i]
                if (_obsaudit.eligible_agg(q)
                        and (q.hints.get("audit")
                             or (_obsaudit.ENABLED and _obsaudit.sampled()))):
                    _obsaudit.get().enqueue_agg(
                        self, type_name, q, box["epoch"], r,
                        group_by, value_cols,
                        cutoff_ms=box.get("cutoff_ms"))
        return out

    def _aggregate_many_impl(self, type_name: str, queries, group_by=None,
                             value_cols=(), now_ms: int | None = None,
                             audit_box: dict | None = None):
        """Batched grouped aggregation on the mesh: ONE fused pass computes,
        per query, COUNT(*) plus per-value-column count/sum/min/max for
        every GROUP BY key — a per-shard segment-reduce merged across the
        data axis with psum (counts/sums) and all_gather + min/max
        (extrema). The
        distributed relational-aggregation role the reference delegates to
        Spark (``geomesa-spark-sql/.../GeoMesaRelation.scala:47,94``,
        SURVEY.md §2.14).

        Returns one entry per query: ``None`` when that query cannot ride
        the mesh (residual filters beyond bbox+time, hints/auths/limits,
        truncated edge lanes, non-numeric value columns, TTL stores, device
        trouble) — callers run their host fold for those — else
        ``{"groups": [key tuples], "count": (G,) int64, "cols": {col:
        {"count": (G,) int64, "sum"/"min"/"max": (G,) f64 (NaN = empty)}}}``
        with groups in first-occurrence row order (host-fold parity) and
        only groups with at least one matching row included.

        Exactness: the device folds the int-domain interior; edge-bucket
        rows (the only int/f64 divergence sites) are EXCLUDED on device,
        re-tested host-side against the full f64 filter AST, and ADDED —
        sound for min/max, unlike subtracting false positives. Pending
        hot-tier (delta) rows are folded host-side, so live stores stay on
        the mesh path. TTL stores stay too: rows strictly below the
        cutoff's quantized unit drop on device, rows AT the ambiguous unit
        ride the boundary gather for an exact-millisecond host re-add
        (``now_ms`` pins the clock for tests). Value sums ride f64 (ints
        beyond 2**53 lose precision — the documented Spark-parity caveat).
        """
        st = self._state(type_name)
        qs = [
            Query(filter=q)
            if isinstance(q, (str, ast.Filter)) or q is None
            else q
            for q in queries
        ]
        if self._interceptors:
            qs = [self._intercept(type_name, st.sft, q) for q in qs]
        out: list = [None] * len(qs)
        group_by = list(group_by) if group_by else None
        value_cols = list(value_cols)
        ttl = self._age_off_ttl_ms(st.sft)
        if ttl is not None and st.sft.dtg_field is None:
            return out
        cutoff_ms = None
        if ttl is not None:
            cutoff_ms = _ttl_cutoff_ms(ttl, now_ms)
        # DATA EPOCH first, snapshot second (_TypeState.data_epoch): a
        # mutation landing between the two leaves cache entries stamped
        # with a pair that never recurs — a miss, never a stale hit
        epoch = st.data_epoch()
        if audit_box is not None:
            # the audit wrapper stamps its shadow checks with the SAME
            # pre-snapshot epoch (and the normalized/intercepted queries)
            audit_box["epoch"] = epoch
            audit_box["qs"] = qs
            audit_box["cutoff_ms"] = cutoff_ms
        main, indices, backend_state, _stats, delta = st.snapshot()
        main_n = 0 if main is None else len(main)
        if main_n == 0:
            return out
        for c in (group_by or []) + value_cols:
            if c not in main.columns:
                return out

        # -- GeoBlocks tier (ops/geoblocks.py): the epoch-validated query
        # cache serves exact repeats outright; eligible misses route to
        # the pre-aggregation pyramid when the cost-table consult agrees.
        # The oracle backend stays a pure brute-force referee, and TTL
        # stores stay on the fused path (their answers are clock-relative).
        import time as _time

        from geomesa_tpu.obs import audit as _obsaudit
        from geomesa_tpu.obs import devmon as _devmon

        devmon_costs = _devmon.costs()
        # audit-shadow re-executions must not train the gagg route
        # verdict (the same hygiene _audit applies to the cost table)
        _observe_gagg = (
            (lambda *a, **k: None) if _obsaudit.in_shadow()
            else devmon_costs.observe
        )
        cache_ctx = None
        if isinstance(self.backend, TpuBackend) and ttl is None:
            cache_ctx = {"epoch": epoch, "keys": {}}
            for i, q in enumerate(qs):
                key = self._agg_cache_key(q, group_by, value_cols)
                if key is None:
                    continue
                cache_ctx["keys"][i] = key
                hit = self.agg_cache.get(type_name, key, epoch)
                if hit is not None:
                    out[i] = hit
                    self.metrics.counter("store.queries").inc()
                    self.metrics.counter("store.agg.cache_hits").inc()
                    with obs.span("telemetry"):
                        self._audit(type_name, q, 0.0, 0.0,
                                    int(hit["count"].sum()))
            from geomesa_tpu.ops import geoblocks as _geoblocks
            from geomesa_tpu.planning.planner import choose_agg_path

            if _geoblocks.enabled() and choose_agg_path(
                    devmon_costs, type_name) == "pyramid":
                pyr = None
                for i, q in enumerate(qs):
                    if out[i] is not None:
                        continue
                    e = self._pyramid_extraction(st, q)
                    if e is None:
                        continue
                    if e.disjoint:
                        out[i] = self._assemble_agg_empty(value_cols)
                        continue
                    if pyr is None:
                        pyr = self._pyramid(st, type_name, main, group_by,
                                            value_cols, epoch[0])
                        if pyr is None:
                            break  # shape can't ride: fused/host paths
                    t0 = _time.perf_counter()
                    res = self._pyramid_answer(
                        q, st, main, delta, pyr, e, value_cols, group_by)
                    if res is None:
                        continue
                    out[i] = res
                    wall = (_time.perf_counter() - t0) * 1000.0
                    total = int(res["count"].sum())
                    self.metrics.counter("store.queries").inc()
                    self.metrics.counter("store.agg.pyramid_served").inc()
                    _observe_gagg(type_name, "gagg:pyramid",
                                  wall_ms=wall, rows=total)
                    with obs.span("telemetry"):
                        self._audit(type_name, q, 0.0, wall, total)
                    key = cache_ctx["keys"].get(i)
                    if key is not None:
                        self.agg_cache.put(type_name, key, epoch, res)
            if all(o is not None for o in out):
                return out

        dev = dev_name = None
        overlap = False
        if isinstance(self.backend, TpuBackend) and self._device_available():
            dev, dev_name = TpuBackend.point_state(backend_state)
            if dev is None:
                # extended-geometry store (XZ layout): the spatial fold is
                # int-bbox OVERLAP — exact for the envelope-semantics BBOX
                # predicate away from edge buckets
                dev, dev_name = TpuBackend.bbox_state(backend_state)
                overlap = dev is not None
        perm = None
        if dev is not None and dev_name in (indices or {}):
            perm = indices[dev_name].perm
        if dev is None or perm is None:
            return out
        try:
            (dev_gid, gid_orig, keys), dev_rowid, dev_vals, host_vals = (
                self._agg_residency(dev, main, perm, group_by, value_cols,
                                    type_name=type_name,
                                    index_name=dev_name or "?")
            )
        except (TypeError, ValueError):
            return out
        G = len(keys)
        # only unanswered lanes pay extraction/packing (cache- and
        # pyramid-served queries are done)
        todo = [i for i in range(len(qs)) if out[i] is None]
        pending = [
            (todo[j], p, ok)
            for j, p, ok in self._batch_payloads(
                st, [qs[i] for i in todo], overlap=overlap)
        ]
        live = [(i, p) for i, p, ok in pending if p is not None and ok]
        for i, p, ok in pending:
            if p is None:
                # provably-disjoint filter: zero rows, no groups
                out[i] = self._assemble_agg_empty(value_cols)
        if not live:
            return out
        t_scan0 = _time.perf_counter()
        import jax.numpy as jnp

        from geomesa_tpu.obs import ledger as _rtledger
        from geomesa_tpu.parallel.query import cached_grouped_agg_step

        G_pad = 1 << max(0, (G - 1).bit_length())
        cap = 512
        try:
            mesh, (boxes, times) = self._stage_batch(live)
            step = cached_grouped_agg_step(
                mesh, G_pad, len(value_cols), cap,
                with_ttl=cutoff_ms is not None, overlap=overlap,
            )
            ttl_args = ()
            if cutoff_ms is not None:
                from geomesa_tpu.curve.binned_time import BinnedTime

                (cb,), (co,) = BinnedTime(
                    st.sft.z3_interval
                ).to_bin_and_offset(np.array([cutoff_ms]))
                ttl_args = (
                    jnp.asarray(np.array([cb, co], dtype=np.int32)),
                )
            # pool pin: the fused pass reads the resident columns — a
            # pinned buffer is never an eviction victim mid-dispatch
            self.backend.pool.touch(type_name, dev_name)
            with self.backend.pool.pinned(type_name, dev_name):
                res = step(
                    *dev.spatial_cols(), dev_gid, dev_rowid,
                    dev_vals, jnp.int32(main_n), boxes, times, *ttl_args,
                )
                cnt, first, vcnt, vsum, vmin, vmax, epos, ehits = map(
                    _rtledger.materialize, res
                )
        except Exception as e:  # noqa: BLE001 — failover to the host fold
            if not self._is_device_error(e):
                raise
            self._trip_device_circuit(e)
            self.metrics.counter("store.query.device_failovers").inc()
            return out
        self._note_device_ok()
        # cost decomposition: the shared device dispatch splits evenly
        # across the batch; each lane's host assembly is timed on its own
        # (a later lane's observation must not absorb earlier assemblies)
        shared_ms = (_time.perf_counter() - t_scan0) * 1000.0 / len(live)
        for k, (i, _) in enumerate(live):
            if (ehits[k] > cap).any():
                continue  # truncated correction lanes: host fold
            tq0 = _time.perf_counter()
            ecand = np.concatenate(
                [epos[k, d, : ehits[k, d]] for d in range(epos.shape[1])]
            ).astype(np.int64)
            out[i] = self._assemble_agg(
                qs[i], main, delta, keys, value_cols,
                cnt[k, :G].astype(np.int64).copy(),
                first[k, :G].astype(np.int64).copy(),
                vcnt[k, :, :G].astype(np.int64).copy(),
                vsum[k, :, :G].copy(),
                vmin[k, :, :G].copy(),
                vmax[k, :, :G].copy(),
                perm[ecand] if len(ecand) else ecand,
                gid_orig, host_vals, group_by,
                cutoff_ms,
            )
            self.metrics.counter("store.queries").inc()
            # audit the POST-correction total (edge + delta rows included),
            # matching what count_many/density_many record
            with obs.span("telemetry"):
                self._audit(
                    type_name, qs[i], 0.0, 0.0, int(out[i]["count"].sum())
                )
            if cache_ctx is not None:
                key = cache_ctx["keys"].get(i)
                if key is not None:
                    self.agg_cache.put(
                        type_name, key, cache_ctx["epoch"], out[i])
                _observe_gagg(
                    type_name, "gagg:scan",
                    wall_ms=shared_ms
                    + (_time.perf_counter() - tq0) * 1000.0,
                    rows=int(out[i]["count"].sum()))
        return out

    @staticmethod
    def _assemble_agg_empty(value_cols):
        z64 = np.zeros(0, dtype=np.int64)
        zf = np.zeros(0, dtype=np.float64)
        return {
            "groups": [],
            "count": z64,
            "cols": {
                c: {"count": z64, "sum": zf, "min": zf, "max": zf}
                for c in value_cols
            },
        }

    def _assemble_agg(self, q, main, delta, keys, value_cols, cnt, first,
                      vcnt, vsum, vmin, vmax, cand_rows, gid_orig,
                      host_vals, group_by, cutoff_ms=None):
        """Fold the host-side corrections into the pre-aggregated partials
        (device interior OR pyramid interior — both feed this): boundary/
        edge candidate rows re-tested exactly (added, never subtracted;
        ``cutoff_ms`` adds the exact-millisecond TTL check a quantized
        mask cannot make) and pending delta rows (which may introduce new
        group keys). Groups are ordered by their first MATCHING row index
        — identical to the host fold's first-occurrence-over-filtered-rows
        construction (delta rows order after the main tier at
        ``main_n + delta_row``, as in query())."""
        f = q.resolved_filter()
        V = len(value_cols)
        main_n = len(main)

        def _fold_row(g: int, row_order: int, vals_at):
            cnt[g] += 1
            first[g] = min(first[g], row_order)
            for v in range(V):
                x = vals_at(v)
                if x is not None and not np.isnan(x):
                    vcnt[v][g] += 1
                    vsum[v][g] += x
                    vmin[v][g] = min(vmin[v][g], x)
                    vmax[v][g] = max(vmax[v][g], x)

        if len(cand_rows):
            rows = cand_rows
            if f is not None:
                rows = rows[ast.residual_mask(f, main, rows)]
            if cutoff_ms is not None and len(rows):
                rows = rows[main.dtg_millis()[rows] >= cutoff_ms]
            for r in rows:
                _fold_row(int(gid_orig[r]), int(r), lambda v: host_vals[v][r])

        keys = list(keys)
        if delta is not None and len(delta):
            dm = (
                np.ones(len(delta), dtype=bool)
                if f is None
                else np.asarray(f.mask(delta), dtype=bool)
            )
            if cutoff_ms is not None:
                dm &= delta.dtg_millis() >= cutoff_ms
            drows = np.nonzero(dm)[0]
            if len(drows):
                key_pos = {kk: i for i, kk in enumerate(keys)}
                extra_n = 0
                dvals = [delta.columns[c] for c in value_cols]
                gcols = [delta.columns[g].values for g in (group_by or [])]
                for r in drows:
                    kk = tuple(gc[r] for gc in gcols)
                    g = key_pos.get(kk)
                    if g is None:
                        g = key_pos[kk] = len(keys)
                        keys.append(kk)
                        extra_n += 1
                    if g >= len(cnt):
                        grow = g + 1 - len(cnt)
                        cnt = np.concatenate([cnt, np.zeros(grow, np.int64)])
                        first = np.concatenate(
                            [first, np.full(grow, np.iinfo(np.int64).max)]
                        )
                        vcnt = np.concatenate(
                            [vcnt, np.zeros((V, grow), np.int64)], axis=1
                        ) if V else vcnt
                        vsum = np.concatenate(
                            [vsum, np.zeros((V, grow))], axis=1
                        ) if V else vsum
                        vmin = np.concatenate(
                            [vmin, np.full((V, grow), np.inf)], axis=1
                        ) if V else vmin
                        vmax = np.concatenate(
                            [vmax, np.full((V, grow), -np.inf)], axis=1
                        ) if V else vmax
                    _fold_row(
                        g, main_n + int(r),
                        lambda v: (
                            None
                            if dvals[v].valid is not None
                            and not dvals[v].valid[r]
                            else float(dvals[v].values[r])
                        ),
                    )
        # keep only groups with matching rows (host parity: groups are
        # formed FROM the matched rows), ordered by first matching row —
        # the host fold's first-occurrence order; no-GROUP-BY keeps its
        # single group
        if group_by:
            alive = np.nonzero(cnt > 0)[0]
            alive = alive[np.argsort(first[alive], kind="stable")]
        else:
            alive = np.arange(len(cnt))
        cols = {}
        for v, c in enumerate(value_cols):
            mn = vmin[v][alive].astype(np.float64)
            mx = vmax[v][alive].astype(np.float64)
            empty = vcnt[v][alive] == 0
            mn[empty] = np.nan
            mx[empty] = np.nan
            cols[c] = {
                "count": vcnt[v][alive],
                "sum": vsum[v][alive].astype(np.float64),
                "min": mn,
                "max": mx,
            }
        return {
            "groups": [keys[int(i)] for i in alive],
            "count": cnt[alive],
            "cols": cols,
        }

    def density_many(
        self,
        type_name: str,
        queries,
        bbox,
        width: int = 256,
        height: int = 256,
        loose: bool = True,
    ):
        """Batched density grids for many queries in ONE device pass: the
        ``DensityScan`` multi-query fan-out (SURVEY.md §2.20 P4 + P6).
        Every query rasterizes into the SHARED ``bbox`` viewport at
        ``width×height``; returns one (height, width) float64 grid per
        query. Pure bbox+time queries ride the fused device step with grids
        ``psum``-merged over the data axis (query bounds are intersected
        with the viewport, so out-of-viewport rows never count); anything
        else (residual filters, hints incl. ``weight_by``, auths, hot-tier
        rows, extended geometries) falls back to the exact per-query density
        hint path. Like :meth:`count_many`, the batched pass tests in the
        31-bit int key domain (the loose-bbox semantics — boundary-epsilon
        rows may differ from the exact float path); ``loose=False`` forces
        the exact path for every query.
        """
        queries = list(queries)
        # ONE batch span: plan, stage, jit, sync and telemetry children
        with obs.span("density_many", n_queries=len(queries)):
            return self._run_density_many(
                type_name, queries, bbox, width, height, loose)

    def _run_density_many(self, type_name: str, queries, bbox, width: int,
                          height: int, loose: bool):
        st = self._state(type_name)
        qs = [
            Query(filter=q) if isinstance(q, (str, ast.Filter)) or q is None else q
            for q in queries
        ]
        if self._interceptors:
            qs = [self._intercept(type_name, st.sft, q) for q in qs]
        width, height = int(width), int(height)  # one coercion for ALL uses
        opts = {"bbox": tuple(bbox), "width": width, "height": height}

        def _exact(q):
            from dataclasses import replace as _replace

            # the shared viewport wins, caller density options (weight_by,
            # ...) survive
            caller = q.hints.get("density")
            merged = {
                **(caller if isinstance(caller, dict) else {}),
                **opts,
            }
            return self.query(
                type_name, _replace(q, hints={**q.hints, "density": merged})
            ).density

        _main, main_n, dev, _bbox_dev, batchable, _perm = self._batch_gate(
            st, want_bbox=False
        )
        if not loose or not batchable or dev is None:
            return [_exact(q) for q in qs]
        pending = self._batch_payloads(
            st, qs, overlap=False, viewport=opts["bbox"]
        )

        out: list = [None] * len(qs)
        empty_grid = np.zeros((height, width))
        live = [(i, p) for i, p, _ok in pending if p is not None]
        for i, p, _ok in pending:
            if p is None:
                out[i] = empty_grid.copy()
        if live:
            import jax.numpy as jnp

            from geomesa_tpu.curve.normalize import lat as norm_lat, lon as norm_lon
            from geomesa_tpu.obs import ledger as _rtledger
            from geomesa_tpu.parallel.query import cached_batched_density_step
            from geomesa_tpu.store.backends import REFINE_PRECISION

            nlon = norm_lon(REFINE_PRECISION)
            nlat = norm_lat(REFINE_PRECISION)
            x1, y1, x2, y2 = opts["bbox"]
            gb = np.array(
                [int(nlon.normalize(x1)), int(nlon.normalize(x2)),
                 int(nlat.normalize(y1)), int(nlat.normalize(y2))],
                dtype=np.int32,
            )
            gbs = np.broadcast_to(gb, (len(live), 4)).copy()
            c = dev.cols
            try:
                mesh, (boxes, times, gbs) = self._stage_batch(live, gbs)
                grids = _rtledger.materialize(
                    cached_batched_density_step(mesh, width, height)(
                        c["x"], c["y"], c["bins"], c["offs"],
                        jnp.int32(main_n), boxes, times, gbs,
                    )
                )
            except Exception as e:  # noqa: BLE001 — failover to exact path
                if not self._is_device_error(e):
                    raise
                self._trip_device_circuit(e)
                self.metrics.counter("store.query.device_failovers").inc()
                grids = None
            if grids is not None:
                self._note_device_ok()
                for k, (i, _) in enumerate(live):
                    out[i] = grids[k].astype(np.float64)
        with obs.span("telemetry", queries=len(pending)):
            for i, _p, _ok in pending:
                if out[i] is None:
                    continue
                self.metrics.counter("store.queries").inc()
                self._audit(type_name, qs[i], 0.0, 0.0, int(out[i].sum()))
        for i, q in enumerate(qs):
            if out[i] is None:
                out[i] = _exact(q)
        return out

    def _meter_failed(self, type_name: str, q: Query, wall_ms: float) -> None:
        """Tenant accounting for queries that never reach ``_audit``
        (deadline shed, watchdog timeout): the heaviest tenants are
        exactly the ones that time out, and an admission controller
        metering only SUCCESSES would never shed them. Burns the
        tenant's SLO budget (ok=False) and accrues the wall time spent.
        Audit-shadow executions are excluded (same hygiene as
        :meth:`_audit`)."""
        from geomesa_tpu.obs import audit as _obsaudit
        from geomesa_tpu.obs import usage

        if _obsaudit.in_shadow():
            return
        tenant = q.hints.get("tenant") or usage.current_tenant()
        usage.observe(tenant, type_name, "timeout", wall_ms=wall_ms,
                      ok=False)

    def _audit(self, type_name: str, q: Query, plan_ms: float, scan_ms: float,
               hits: int, info=None, sig: str | None = None) -> None:
        # audit-shadow executions (obs/audit.py: referee comparisons,
        # the divergence minimizer, bundle replay) are invisible to the
        # feedback planes — cost table, usage metering, SLO burn,
        # workload capture — the same rule ISSUE 11's replay applies to
        # capture: the auditor must never train the planner it audits,
        # bill a tenant for verification, or recapture itself
        from geomesa_tpu.obs import audit as _obsaudit

        if _obsaudit.in_shadow():
            return
        self.metrics.histogram("store.query.hits").update(hits)
        self.metrics.histogram("store.query.scan_ms").update(scan_ms)
        filt = q.filter if isinstance(q.filter, str) else str(q.filter or "INCLUDE")
        # always-on observability: one flight-recorder audit record + one
        # SLO availability observation + one cost-table observation per
        # completed query (all leaf-lock appends — the <2% cached-jit
        # bound is gated in scripts/lint.sh). A query that ran under
        # devprof additionally carries its device-time breakdown.
        from geomesa_tpu.obs import devmon, usage, workload
        from geomesa_tpu.obs import flight as _flight

        prof = devmon.current_profile() if devmon.PROFILING else None
        device = prof.breakdown() if prof is not None else None
        # tenant attribution (obs.usage): an explicit per-query hint wins
        # (the web layer sets it from X-Geomesa-Tenant); otherwise the
        # request-scoped context the web layer / replay harness bound —
        # anonymous embedded callers land on the default tenant
        tenant = q.hints.get("tenant") or usage.current_tenant()
        # batched paths (select_many) pass their planned signature
        # explicitly: they audit with info=None (amortized timings must
        # not train the cost table) but their lens/ledger attribution
        # still keys on the REAL plan signature
        sig = sig if sig is not None else devmon.plan_signature(info, q)
        predicted = None
        # only FULLY PLANNED, individually timed executions feed the cost
        # table: batched paths audit with amortized-zero timings and no
        # plan info, and an empty store audits 0 ms — letting those in
        # would pull every p50 toward zero under the wrong signature
        # (the table is the adaptive planner's training signal)
        if info is not None:
            index_name = getattr(info, "index_name", None) or ""
            costs = devmon.costs()
            # predicted-vs-actual calibration: read the table's p50 BEFORE
            # this run observes into it (what the planner would have
            # predicted), then feed the error into the cost model's drift
            # report (/api/obs/costs "calibration" section)
            predicted = costs.predict(type_name, sig)
            costs.observe(
                type_name, sig,
                wall_ms=plan_ms + scan_ms,
                device_ms=(device["device_compute"] + device["dispatch"]
                           + device["compile"]) if device else None,
                rows=hits,
                bytes_scanned=(
                    devmon.ledger().index_bytes(type_name, index_name)
                    if index_name and "union" not in index_name else 0
                ),
            )
            if predicted is not None and predicted.get("observations", 0) >= 4:
                from geomesa_tpu.planning import costmodel

                costmodel.model().record_calibration(
                    type_name, sig,
                    predicted["wall_ms_p50"], plan_ms + scan_ms,
                )
        predicted_ms = predicted["wall_ms_p50"] if predicted else None
        device_ms = (device["device_compute"] + device["dispatch"]
                     + device["compile"]) if device else 0.0
        _flight.record(
            op="query", type_name=type_name, source="store", plan=filt,
            latency_ms=plan_ms + scan_ms, rows=hits,
            breakdown={"plan": plan_ms, "scan": scan_ms},
            device=device or {},
            tenant=tenant or "", auths=q.auths,
            plan_signature=sig, predicted_ms=predicted_ms,
        )
        self.slo.observe("store.query", ok=True, key=type_name,
                         latency_ms=plan_ms + scan_ms)
        # retained profiling plane (obs.lens) + roundtrip rollup
        # (obs.ledger): the lens takes the latency histogram point with
        # the submitter's trace exemplar (a coalesced follower's stamped
        # trace_id hint wins over the leader's batch span, so exemplars
        # resolve to DISJOINT stitched trees); the rollup charges this
        # query's dispatch/sync/host-gap ledger to its plan signature —
        # every member of a coalesced batch charges the SHARED ledger once
        from geomesa_tpu.obs import ledger as _rtledger
        from geomesa_tpu.obs import lens as _lensmod

        ql = _rtledger.current()
        trace_id = q.hints.get("trace_id") or ""
        if not trace_id:
            sp = obs.current()
            trace_id = sp.trace_id if sp is not None else ""
        _lensmod.get().observe(
            type_name, sig, latency_ms=plan_ms + scan_ms, rows=hits,
            dispatches=ql.dispatches if ql is not None else 0,
            trace_id=trace_id)
        if ql is not None:
            _rtledger.table().charge(type_name, sig, ql,
                                     wall_ms=plan_ms + scan_ms)
        # per-tenant usage metering (obs.usage): one leaf-lock append, the
        # same cost class as the flight record — the accounting substrate
        # ROADMAP item 4's admission controller consumes
        usage.observe(
            tenant, type_name, sig, rows=hits,
            wall_ms=plan_ms + scan_ms, device_ms=device_ms,
        )
        # workload capture (obs.workload): one wide event per query when
        # GEOMESA_TPU_WORKLOAD_DIR is set; the off path is one bool check
        if workload.ENABLED:
            import time as _time

            workload.record(
                ts=_time.time(), op="query", type_name=type_name,
                source="store", filter_text=filt, hints=q.hints,
                tenant=tenant or "", auths=q.auths, plan_signature=sig,
                predicted_ms=predicted_ms,
                latency_ms=plan_ms + scan_ms, rows=hits,
                device_ms=device_ms,
            )
        # SLO → buffer-pool feedback, sampled (1/32 queries): a type
        # burning its error budget weighs heavier in eviction scoring, so
        # its buffers stay resident while an idle type's go first
        pool = getattr(self.backend, "pool", None)
        if pool is not None:
            self._slo_feed = getattr(self, "_slo_feed", 0) + 1
            if self._slo_feed % 32 == 1:
                pool.note_slo(
                    type_name,
                    self.slo.tracker("store.query", type_name)
                    .budget_remaining(300.0),
                )
        if self.audit_writer is None:
            return
        from geomesa_tpu.utils.audit import QueryEvent, now_millis
        hints = ", ".join(f"{k}={v!r}" for k, v in sorted(q.hints.items()))
        # audit↔trace join: the event names this query's span (the "query"
        # span in query()/select_many), the parent of the "telemetry" span
        # this call runs in; empty when tracing is off
        sp = obs.current()
        span_id = ""
        if sp is not None:
            span_id = (sp.parent_id if sp.name == "telemetry" and sp.parent_id
                       else sp.span_id)
        self.audit_writer.write_event(
            QueryEvent(
                store_type=type(self.backend).__name__,
                type_name=type_name,
                date=now_millis(),
                user=self.user,
                filter=filt,
                hints=hints,
                plan_time_ms=plan_ms,
                scan_time_ms=scan_ms,
                hits=hits,
                trace_id=sp.trace_id if sp is not None else "",
                span_id=span_id,
            )
        )

    def explain(
        self,
        type_name: str,
        q: "Query | str | ast.Filter",
        analyze: bool = False,
    ) -> "str | ExplainAnalyze":
        """Static plan explain; ``analyze=True`` additionally EXECUTES the
        query under a collected trace and returns an :class:`ExplainAnalyze`
        whose stage timeline (plan → dispatch → refine → reduce, plus an
        ``other`` residual) sums to the measured wall time. Range
        decomposition shows as a ``decompose`` span NESTED under ``plan``
        in the full trace tree (``timeline.root``), not as a top-level
        stage."""
        st = self._state(type_name)
        if isinstance(q, (str, ast.Filter)):
            q = Query(filter=q)
        planner = QueryPlanner(st.sft, st.indices, st.stats)
        _, _, info = planner.plan(q)
        out = info.explain()
        if st.delta.rows:
            # intervals above cover the SORTED main tier only; pending hot-
            # tier rows are brute-forced at query time until compact()
            out += f"\n  Hot tier (unsorted, merged at query time): {st.delta.rows} rows"
        if not analyze:
            return out
        from geomesa_tpu.obs import devmon
        from geomesa_tpu.obs import trace as _trace

        # predicted cost BEFORE the run (query() observes into the table);
        # the analyzed execution always runs under devprof so the stage
        # rows split into compile / dispatch / device-compute / h2d / d2h
        sig = devmon.plan_signature(info, q)
        predicted = devmon.costs().predict(type_name, sig)
        import time as _time

        # under active auditing the analyzed execution is audit-tagged
        # and the auditor drains synchronously, so the verdict renders
        # as the `Audit:` line of this ExplainAnalyze
        from dataclasses import replace as _q_replace

        from geomesa_tpu.obs import audit as _obsaudit

        q_run = q
        if _obsaudit.enabled():
            q_run = _q_replace(q, hints={**q.hints, "audit": True})
        with _trace.collect("explain.analyze", type_name=type_name) as root:
            with devmon.profiled() as prof:
                t0 = _time.perf_counter()
                res = self.query(type_name, q_run)
                actual_ms = (_time.perf_counter() - t0) * 1000.0
        audit_verdict = None
        if _obsaudit.enabled():
            aud = _obsaudit.get()
            aud.drain()
            audit_verdict = aud.last_verdict(
                type_name, _obsaudit.filter_text(q_run))
        qspans = root.find("query")
        from geomesa_tpu.planning.costmodel import calibration_error

        return ExplainAnalyze(
            plan=out,
            timeline=_trace.StageTimeline(qspans[0] if qspans else root),
            hits=res.count,
            device=prof.breakdown(),
            cost={
                "signature": sig,
                "predicted": predicted,
                "actual_ms": round(actual_ms, 3),
                # relative prediction error for THIS run (None before the
                # table has a prediction) — the per-query view of the
                # /api/obs/costs calibration report
                "calibration_error": (
                    round(calibration_error(
                        predicted["wall_ms_p50"], actual_ms), 4)
                    if predicted else None
                ),
                "strategy_source": getattr(info, "strategy_source", ""),
                # the decider's rejected alternatives with their estimates
                "alternatives": getattr(info, "alternatives", None) or [],
            },
            cache=self.cache_report(),
            audit=audit_verdict,
        )

    # -- stats API (GeoMesaStats role: exact or estimated) -------------------
    def stats_count(self, type_name: str, cql=None, exact: bool = False):
        """Row count: stored total, sketch estimate, or exact via query.

        ``cql`` may be a CQL string or a pre-built filter AST (the merged
        view passes ASTs so per-store scope filters compose exactly)."""
        st = self._state(type_name)
        if st.total_rows == 0:
            return 0
        if cql is None:
            return st.total_rows
        if exact:
            return self.query(type_name, Query(filter=cql)).count
        if st.stats is None:  # only delta-tier data so far: count it exactly
            return self.query(type_name, Query(filter=cql)).count
        from geomesa_tpu.filter.cql import parse as _parse

        f_ast = _parse(cql) if isinstance(cql, str) else cql
        # the composed sketch estimate (StoreStats.estimate_filter_rows):
        # one definition shared with the planner's cheap-path gate and the
        # cost model's seeds
        est = st.stats.estimate_filter_rows(f_ast)
        # stats cover the main tier only; the hot delta is small enough to
        # count exactly so fresh writes stay visible to estimates
        delta_table = st.delta.merged()
        if delta_table is not None:
            est += float(f_ast.mask(delta_table).sum())
        return est

    # -- persistence (checkpoint/resume) -------------------------------------
    def save(self, path: str, file_format: str = "parquet") -> dict:
        from geomesa_tpu.store import persistence

        return persistence.save(self, path, file_format=file_format)

    @staticmethod
    def load(
        path: str,
        backend: str = "tpu",
        column_group: str | None = None,
        filter=None,
    ) -> "DataStore":
        from geomesa_tpu.store import persistence

        return persistence.load(
            path, backend=backend, column_group=column_group, filter=filter
        )

    # -- durability plane (checkpoint + WAL recovery) -------------------------
    @classmethod
    def open(
        cls,
        path: str,
        backend: str = "tpu",
        recover: bool = False,
        wal_dir: str | None = None,
        checkpointer: bool = True,
        ckpt_bytes: int | None = None,
        ckpt_interval_s: float | None = None,
    ) -> "DataStore":
        """Open a durable catalog: WAL lock → checkpoint load → WAL-tail
        replay (docs/operations.md § Durability & recovery).

        ``wal_dir`` defaults to ``GEOMESA_TPU_WAL`` or ``<path>/wal``. The
        cross-process catalog lock is taken FIRST, so a double-open fails
        fast with :class:`~geomesa_tpu.store.wal.WalLockedError` before
        any state loads. With ``recover=True`` the WAL tail past the
        manifest stamps replays exactly-once in global seq order (typed
        records are idempotent by seq; schema ops interleave in order);
        without it, an unreplayed tail raises
        :class:`~geomesa_tpu.store.wal.WalTailError` instead of being
        silently dropped. ``checkpointer=True`` starts the background
        incremental checkpointer (WAL-bytes / interval triggers,
        deterministic shutdown via :meth:`close`)."""
        import json as _json
        import time as _time

        from geomesa_tpu.resilience import faults as _faults
        from geomesa_tpu.store import persistence
        from geomesa_tpu.store import wal as _walmod
        from pathlib import Path as _Path

        if wal_dir is None:
            wal_dir = os.environ.get("GEOMESA_TPU_WAL") or os.path.join(
                path, "wal")
        ds = cls(backend=backend, wal_dir=wal_dir)
        ds._wal_catalog = path
        wal = ds._wal
        try:
            # a SIGKILLed checkpoint leaves its catalog-lease claim behind
            # and every later save would wait out the full TTL on it; we
            # hold the exclusive WAL lock, so dead local claims are safe
            # to reap now
            from geomesa_tpu.utils.locks import reap_dead_claims

            reap_dead_claims(path)
            stamps: dict[str, int] = {}
            global_floor = 0
            ds._wal_replay = True
            try:
                mpath = _Path(path) / persistence.MANIFEST
                manifest = None
                if mpath.exists():
                    manifest = _json.loads(mpath.read_text())
                    persistence.load(path, backend=backend, into=ds)
                if manifest and manifest.get("wal"):
                    wstamp = manifest["wal"]
                    global_floor = int(wstamp.get("seq", 0))
                    stamps = {str(k): int(v)
                              for k, v in (wstamp.get("topics") or {}).items()}
                    # re-issuing a stamped seq would make the NEXT replay
                    # skip the acked write that reused it
                    wal.ensure_seq_floor(
                        max([global_floor, *stamps.values()] or [0]))
                    ds._wal_schema_seq = stamps.get(_walmod.SCHEMA_TOPIC, 0)
                    for name in ds.list_schemas():
                        st = ds._state(name)
                        st.wal_seq = stamps.get(_walmod.topic_for(name), 0)
                        ident = (manifest.get("types", {})
                                 .get(name, {}).get("ident"))
                        if ident:
                            st.ident = ident
                tail = wal.records_after(stamps, default_floor=global_floor)
                if tail and not recover:
                    raise _walmod.WalTailError(
                        f"catalog {path!r} has {len(tail)} acked WAL "
                        f"record(s) past the last checkpoint; open with "
                        f"recover=True to replay them")
                if tail:
                    t0 = _time.perf_counter()
                    with obs.span("store.recover", catalog=path,
                                  records=len(tail)):
                        for seq, topic, hdr, payload in tail:
                            _faults.crash_point("recover.mid_replay")
                            ds._wal_apply(seq, topic, hdr, payload)
                    _walmod._note(
                        recoveries=1, replayed_records=len(tail),
                        replay_ms_total=(_time.perf_counter() - t0) * 1000.0)
            finally:
                ds._wal_replay = False
            # the tail (if any) is replayed and the stamps are live:
            # mutation/checkpointing can no longer shadow acked history
            ds._wal_unreplayed = False
            if checkpointer:
                ds._wal_ckpt = _walmod.WalCheckpointer(
                    ds, path, bytes_trigger=ckpt_bytes,
                    interval_s=ckpt_interval_s)
            return ds
        except BaseException:
            wal.close()
            raise

    def _wal_apply(self, seq: int, topic: str, hdr: dict,
                   payload: bytes) -> None:
        """Apply one replayed WAL record to the in-memory state. Data ops
        are exact (same pre-state → same effect); schema ops are
        EFFECT-IDEMPOTENT — a checkpoint staged mid-save can already
        reflect a schema op whose seq is above the schema stamp, so an
        already-applied create/evolve/rename/delete skips (counted)."""
        from geomesa_tpu.io.arrow import from_ipc_bytes
        from geomesa_tpu.store import wal as _walmod

        op = hdr.get("op")
        if topic == _walmod.SCHEMA_TOPIC:
            try:
                if op == "create_schema":
                    if hdr["name"] in self._types:
                        _walmod._note(replay_skipped=1)
                    else:
                        sft = parse_spec(hdr["name"], hdr["spec"])
                        if hdr.get("index_layout") == "legacy":
                            sft.user_data["geomesa.index.layout"] = "legacy"
                        self.create_schema(sft)
                elif op == "delete_schema":
                    if hdr["name"] not in self._types:
                        _walmod._note(replay_skipped=1)
                    else:
                        self.delete_schema(hdr["name"])
                elif op == "update_schema":
                    tname = hdr["type"]
                    if tname not in self._types:
                        _walmod._note(replay_skipped=1)  # renamed/gone: done
                    else:
                        self.update_schema(
                            tname, add=hdr.get("add"),
                            keywords=hdr.get("keywords"),
                            rename_to=hdr.get("rename_to"))
            except ValueError:
                # already-applied evolution (attribute exists / rename
                # target exists): the checkpoint was newer than the stamp
                _walmod._note(replay_skipped=1)
            # recovery replay is single-threaded and runs before the
            # store is shared with any other thread
            # tpurace: disable-next-line=R001
            self._wal_schema_seq = max(self._wal_schema_seq, seq)
            return
        name = _walmod.type_for(topic)
        if name is None or name not in self._types:
            # stale incarnation (type deleted before the checkpoint) or a
            # topic whose create never acked: nothing to apply to
            _walmod._note(replay_skipped=1)
            return
        st = self._state(name)
        if op == "write":
            self.write(name, from_ipc_bytes(st.sft, payload))
        elif op == "delete":
            self.delete_features(name, hdr["fids"],
                                 visible_to=hdr.get("visible_to"))
        elif op == "clear":
            self.clear(name)
        elif op == "age_off":
            self.age_off(name, now_ms=hdr["now_ms"])
        else:
            _walmod._note(replay_skipped=1)
            return
        with st.lock:
            st.wal_seq = max(st.wal_seq, seq)

    def close(self) -> None:
        """Deterministic shutdown of the durability plane: stop the
        background checkpointer, flush pending group commits, release the
        cross-process catalog lock. Idempotent; a plain (WAL-less) store
        is a no-op."""
        ck = self._wal_ckpt
        if ck is not None:
            self._wal_ckpt = None
            ck.close()
        if self._wal is not None:
            self._wal.close()

    def _stats(self, type_name: str):
        st = self._state(type_name)
        if st.stats is None and st.delta.rows > 0:
            # delta-only data: fold the hot tier in so sketches exist (writes
            # below the compaction threshold don't build stats eagerly)
            self.compact(type_name)
        if st.stats is None:
            raise ValueError(f"no statistics for {type_name!r}: no data written yet")
        return st.stats

    def stats_bounds(self, type_name: str, attr: str):
        """(min, max) of an attribute from sketches."""
        mm = self._stats(type_name).min_max(attr)
        return (mm.min, mm.max)

    def stats_top_k(self, type_name: str, attr: str, k: int = 10):
        return self._stats(type_name).top_k(attr, k)

    def stats_histogram(self, type_name: str, attr: str):
        return self._stats(type_name).histogram(attr)

    def stats_cardinality(self, type_name: str, attr: str) -> float:
        return self._stats(type_name).cardinality(attr)


def _pyramid_index_name(pkey) -> str:
    """Pool entry name for one pyramid's device mirror — unique per
    aggregation shape so two shapes on a type never share (and clobber)
    one pool entry. Shows in the spill report as ``geoblocks[...]``."""
    group_by, value_cols = pkey
    return "geoblocks[%s;%s]" % (",".join(group_by), ",".join(value_cols))


def _pyramid_evictor(st: "_TypeState", pkey, pyr):
    """Pool-eviction callback for a pyramid's device mirror: drop the
    whole pyramid from the type state (it rebuilds lazily on the next
    eligible aggregate). Runs outside every pool lock."""

    def _evict():
        with st.lock:
            cached = st.pyramids.get(pkey)
            if cached is not None and cached[0] is pyr:
                del st.pyramids[pkey]

    return _evict


def _take_combined(sft, main, main_n: int, delta_table, rows: np.ndarray) -> FeatureTable:
    """Materialize rows addressed in the virtual (main ++ delta) row space."""
    parts = []
    main_sel = rows[rows < main_n]
    delta_sel = rows[rows >= main_n] - main_n
    if len(main_sel):
        parts.append(main.take(main_sel))
    if len(delta_sel):
        parts.append(delta_table.take(delta_sel))
    if not parts:
        return FeatureTable.from_records(sft, [])
    return parts[0] if len(parts) == 1 else FeatureTable.concat(parts)


