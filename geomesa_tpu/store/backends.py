"""Execution backends: the bottom seam (``IndexAdapter`` role, SURVEY.md §1).

Two implementations, mirroring the reference's two-tier test architecture
(SURVEY.md §4 "lesson"):

- :class:`OracleBackend` — brute-force vectorized filter evaluation over the
  host columnar table. The result-set parity referee (the ``GeoCQEngine`` /
  ``TestGeoMesaDataStore`` role).
- :class:`TpuBackend` — device-resident int32 columns per index order; scans
  gather host-planned candidate slots and run the fused jit refine kernel
  (:mod:`geomesa_tpu.ops.refine`), then apply the exact f64 residual filter to
  the survivors on the host (the coprocessor/iterator stack role).
"""

from __future__ import annotations

import os
from contextlib import ExitStack
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from geomesa_tpu import obs
from geomesa_tpu.analysis.contracts import dispatch_budget
from geomesa_tpu.obs import ledger as _rtledger
from geomesa_tpu.curve.binned_time import BinnedTime
from geomesa_tpu.curve.normalize import lat as norm_lat, lon as norm_lon
from geomesa_tpu.filter import ast
from geomesa_tpu.filter.bounds import Extraction
from geomesa_tpu.index.api import FeatureIndex, IndexPlan, gather_indices, pad_bucket
from geomesa_tpu.schema.columnar import FeatureTable
from geomesa_tpu.schema.sft import FeatureType

REFINE_PRECISION = 31  # device coords are 31-bit fixed point (Z2 resolution)
JOIN_BLOCK = 4096  # block-sparse join granularity; shards pad to multiples
# per-plan dispatch-payload memo cap (IndexPlan.exec_cache): total idx/count
# slots above this re-derive per query instead of pinning device arrays the
# ledger/pool don't account for (128 cached plans x wide-scan splits would
# silently hold MBs of HBM outside the budget)
_EXEC_MEMO_MAX_SLOTS = 1 << 18  # 256k slots ≈ 2 MB of int32 per plan
# row-select one-pass threshold: total gather slots (shards x per-shard
# capacity) below which the count pass is skipped and the gather runs
# straight at the planner's candidate bound — one device dispatch instead
# of two (each dispatch is a full host->device round trip). 4M int32
# slots = 16 MB of pos transfer worst-case.
try:
    _ONE_PASS_MAX_SLOTS = int(
        os.environ.get("GEOMESA_SELECT_ONE_PASS_SLOTS", str(4 * 1024 * 1024))
    )
except ValueError as _e:
    raise ValueError(
        "GEOMESA_SELECT_ONE_PASS_SLOTS must be an integer slot count: "
        f"{os.environ['GEOMESA_SELECT_ONE_PASS_SLOTS']!r}"
    ) from _e


class ExecutionBackend:
    name = "base"

    def load(self, sft: FeatureType, table: FeatureTable, indices: dict,
             fingerprint=None) -> Any:
        """(Re)build backend state for a snapshot of the data.

        ``fingerprint`` identifies the MAIN-TIER snapshot (the owning
        type's rebuild epoch): backends with a buffer pool use it to
        re-admit donated buffers from an identical prior load without
        re-staging (delta-only writes keep the fingerprint stable)."""
        raise NotImplementedError

    def select(
        self,
        state: Any,
        index: FeatureIndex,
        plan: IndexPlan,
        extraction: Extraction,
        residual: ast.Filter,
        table: FeatureTable,
    ) -> np.ndarray:
        """Execute a scan plan → matching global row indices (unsorted)."""
        raise NotImplementedError


class OracleBackend(ExecutionBackend):
    """Brute force: evaluate the full filter over every row (referee)."""

    name = "oracle"

    def load(self, sft, table, indices, fingerprint=None):
        return None

    def select(self, state, index, plan, extraction, residual, table):
        with obs.span("refine", mode="oracle", rows=len(table)):
            return np.nonzero(residual.mask(table))[0]


@dataclass
class _MeshIndexState:
    """Per-index mesh-sharded device columns, sorted in index order.

    ``cols`` holds the device jnp arrays sharded contiguously over the mesh
    ``data`` axis (curve order = shard order, SURVEY.md §2.20 P1); padding
    rows live past ``n`` and never appear in scan intervals. ``kind`` is
    ``"points"`` (x/y/bins/offs — containment refine) or ``"bboxes"``
    (xmin/xmax/ymin/ymax/bins/offs — overlap refine for extended
    geometries, the XZ2/XZ3 device path).
    """

    cols: dict[str, Any]
    rows_per_shard: int
    n: int
    kind: str = "points"
    # lazily-staged grouped-aggregation residency (DataStore.aggregate_many):
    # group-id/value columns keyed by their column tuple. Lives and dies with
    # this state object, so a compact/ingest that rebuilds the layout also
    # drops the cache — no invalidation protocol needed.
    agg_cache: dict = field(default_factory=dict)

    @property
    def nbytes(self) -> int:
        """Device bytes held by this index's sharded columns (incl. padding)."""
        return int(sum(int(c.nbytes) for c in self.cols.values()))

    def spatial_cols(self) -> tuple:
        """THE ordered spatial+time column tuple every kernel expects:
        (x, y, bins, offs) for point layouts, (xmin, ymin, xmax, ymax,
        bins, offs) for bbox layouts — one definition so the positional
        contract cannot drift per call site (the kernels accept any int32
        arrays, so a mis-ordered tuple is silently wrong, not an error)."""
        c = self.cols
        if self.kind == "bboxes":
            return (c["xmin"], c["ymin"], c["xmax"], c["ymax"],
                    c["bins"], c["offs"])
        return (c["x"], c["y"], c["bins"], c["offs"])


def _slot_clearer(state: dict, name: str):
    """Pool-eviction callback: clear the index's slot in the backend-state
    dict so subsequent snapshots take the exact host path. Queries that
    already snapshotted the state keep their reference — the arrays stay
    alive until the last reader drops them, so eviction never invalidates
    an in-flight dispatch."""

    def _clear():
        state[name] = None

    return _clear


def time_quads(sft: FeatureType, intervals) -> "np.ndarray | None":
    """Interval list → (T, 4) [bin_lo, off_lo, bin_hi, off_hi] int32 quads
    (the kernels' and the GeoBlocks pyramid's shared time payload), or
    None for no temporal constraint. Every interval clamping away yields
    the unsatisfiable quad — a temporally-impossible predicate must not
    become a full-window scan."""
    if intervals is None or not sft.dtg_field:
        return None
    binned = BinnedTime(sft.z3_interval)
    from geomesa_tpu.curve.binned_time import MAX_BIN

    quads = []
    for lo, hi in intervals:
        lo = max(int(lo), 0)
        # last indexable millisecond: one before the start of bin MAX_BIN+1
        hi_cap = int(binned.bin_start_millis(np.array([MAX_BIN + 1]))[0]) - 1
        hi = min(int(hi), hi_cap)
        if hi < lo:
            continue
        (blo,), (olo,) = binned.to_bin_and_offset(np.array([lo]))
        (bhi,), (ohi,) = binned.to_bin_and_offset(np.array([hi]))
        quads.append([int(blo), int(olo), int(bhi), int(ohi)])
    if quads:
        return np.array(quads, dtype=np.int32)
    return np.array([[1, 0, 0, -1]], dtype=np.int32)


class TpuBackend(ExecutionBackend):
    """Mesh-sharded columnar execution: the distributed-scan role of the
    tablet-server fleet. Row retrieval is two-pass — per-shard refine counts
    size the capacity lanes, then an on-device compaction gathers matching
    global row positions per shard (``ArrowScan.scala:37`` /
    ``QueryPlan.scala:106`` role, collectives instead of scan RPC)."""

    name = "tpu"

    def __init__(self, mesh=None, max_device_bytes: int | None = None,
                 pool=None):
        self._mesh = mesh
        # shared HBM buffer pool (store/bufferpool.py): pins hot buffers
        # across queries, evicts by SLO-weighted access frequency under
        # the GEOMESA_TPU_HBM process budget, and re-admits donated
        # buffers on fingerprint-stable reloads. One pool per backend so
        # test stores never fight over same-named types.
        if pool is None:
            from geomesa_tpu.store.bufferpool import BufferPool

            pool = BufferPool()
        self.pool = pool
        # PER-TYPE HBM residency budget, enforced on each load() (the
        # hot-tier half of SURVEY.md §2.20 P9 at device granularity):
        # indexes past the budget stay host-resident — select() already
        # falls back per index. The budget counts TOTAL bytes across the
        # mesh (all shards summed), not per device; a store holding T types
        # can reach T × budget — size accordingly. Env default so operators
        # can set it without code.
        if max_device_bytes is None:
            env = os.environ.get("GEOMESA_DEVICE_BUDGET_BYTES")
            if env:
                try:
                    max_device_bytes = int(env)
                except ValueError:
                    raise ValueError(
                        "GEOMESA_DEVICE_BUDGET_BYTES must be an integer "
                        f"byte count, got {env!r}"
                    ) from None
        self.max_device_bytes = max_device_bytes

    def _get_mesh(self):
        if self._mesh is None:
            from geomesa_tpu.parallel.mesh import default_mesh

            self._mesh = default_mesh()
        return self._mesh

    @staticmethod
    def point_state(state) -> tuple["_MeshIndexState | None", str | None]:
        """The preferred point-index device state: (state, index name).

        Shared by every batched device fast path (count_many, knn_many) so
        index preference stays in one place.
        """
        if not state:
            return None, None
        for name in ("z3", "z2"):
            dev = state.get(name)
            if dev is not None:
                return dev, name
        return None, None

    @staticmethod
    def bbox_state(state) -> tuple["_MeshIndexState | None", str | None]:
        """The preferred extended-geometry device state (xz3/xz2): feature
        bbox SoA for overlap-mode batched fast paths."""
        if not state:
            return None, None
        for name in ("xz3", "xz2"):
            dev = state.get(name)
            if dev is not None and dev.kind == "bboxes":
                return dev, name
        return None, None

    # residency priority when a device-byte budget applies: the batched
    # fast paths prefer z3/z2 (point containment) then xz3/xz2 (overlap)
    _LOAD_PRIORITY = ("z3", "z2", "xz3", "xz2")

    @classmethod
    def residency(cls, state) -> dict[str, int]:
        """Per-index device bytes for a backend-state snapshot."""
        if not state:
            return {}
        return {
            name: dev.nbytes
            for name, dev in state.items()
            if isinstance(dev, _MeshIndexState)
        }

    def load(self, sft, table, indices, fingerprint=None):
        from geomesa_tpu.obs import devmon
        from geomesa_tpu.store.bufferpool import register_residency
        from geomesa_tpu.parallel.mesh import shard_columns

        # HBM residency ledger: every device allocation this load makes is
        # registered (type, index, column group, bytes) and auto-unregisters
        # when the state object is dropped (evict/reload/compact); indexes
        # the budget refuses land in the host-resident spill report instead
        ledger = devmon.ledger()
        type_name = getattr(sft, "name", "?")
        ledger.begin_load(type_name)
        ledger.set_budget(self.max_device_bytes)
        # retire this type's live pool entries: fingerprint-stable states
        # (same main tier — recover() after a pressure eviction, reloads
        # across delta-only writes) park in the donation stash for
        # zero-copy re-admission below; anything stale is freed
        self.pool.release(type_name, keep_fingerprint=fingerprint)
        state: dict[str, _MeshIndexState | None] = {}
        nlon = norm_lon(REFINE_PRECISION)
        nlat = norm_lat(REFINE_PRECISION)
        binned = BinnedTime(sft.z3_interval) if sft.dtg_field else None
        mesh = None
        ordered = sorted(
            indices.items(),
            key=lambda kv: (
                self._LOAD_PRIORITY.index(kv[0])
                if kv[0] in self._LOAD_PRIORITY
                else len(self._LOAD_PRIORITY)
            ),
        )
        used_bytes = 0
        est = 0
        if (self.max_device_bytes is not None
                or self.pool.max_total_bytes is not None):
            # admission estimate: int32 columns at the REAL padded row count
            # (block-aligned shards — parallel/mesh.pad_rows with the
            # JOIN_BLOCK multiple — can round small tables up substantially)
            from geomesa_tpu.parallel.mesh import data_shards, pad_rows

            mesh = self._get_mesh()
            n_cols = (
                4 if (sft.geom_field and table.geom_column().x is not None) else 6
            )
            shards = data_shards(mesh)
            est = n_cols * 4 * pad_rows(
                max(len(table), shards), shards, JOIN_BLOCK
            )
        # buffers admitted by THIS load stay pinned until the loop ends:
        # a later (lower-priority) index's ensure_room must not evict the
        # just-staged higher-priority one — fresh entries have hits=0 and
        # would otherwise be the coldest eviction candidates, inverting
        # _LOAD_PRIORITY and wasting the h2d staging just paid for. With
        # release() having retired this type's prior entries above, load-
        # pressure evictions can only fall on OTHER types' cold buffers.
        load_pins = ExitStack()
        try:
            for name, index in ordered:
                col = table.geom_column() if sft.geom_field else None
                if col is None or len(table) == 0 or name in ("id",):
                    state[name] = None  # host path BY DESIGN — never a spill
                    continue
                if col.x is None and col.bounds is None:
                    state[name] = None
                    continue
                if self.max_device_bytes is not None:
                    if used_bytes + est > self.max_device_bytes:
                        state[name] = None  # host path serves this index
                        ledger.record_spill(type_name, name, est)
                        # a donated state for a refused index would hold the
                        # very bytes the budget just declined — free it
                        self.pool.drop_donated(type_name, name)
                        continue
                # donation fast path: an identical prior load of this index
                # (same fingerprint = same main tier) parked in the pool's
                # stash — re-admit it without staging a byte host→device.
                # The evictor rebinds to THIS load's state dict.
                donated = self.pool.take_donated(
                    type_name, name, fingerprint,
                    on_evict=_slot_clearer(state, name))
                if donated is not None:
                    state[name] = donated
                    used_bytes += donated.nbytes
                    load_pins.enter_context(self.pool.pinned(type_name, name))
                    continue
                # process-level pool budget (GEOMESA_TPU_HBM): make room by
                # evicting the coldest unpinned buffers (other types' first,
                # by SLO-weighted access frequency); an immovable working set
                # spills this index to the host path, same as per-type
                if not self.pool.ensure_room(est or 0):
                    state[name] = None
                    ledger.record_spill(type_name, name, est)
                    continue
                if mesh is None:
                    mesh = self._get_mesh()
                perm = index.perm
                if binned is not None:
                    bins, offs = binned.to_bin_and_offset(table.dtg_millis()[perm])
                    bins = bins.astype(np.int32)
                    offs = offs.astype(np.int32)
                else:
                    bins = np.zeros(len(table), dtype=np.int32)
                    offs = np.zeros(len(table), dtype=np.int32)
                if col.x is not None:
                    xi = nlon.normalize(col.x[perm]).astype(np.int32)
                    yi = nlat.normalize(col.y[perm]).astype(np.int32)
                    # block-aligned shards so block-granular kernels (the
                    # block-sparse join over the z2 layout) divide evenly
                    cols, padded, rows_per_shard = shard_columns(
                        mesh, {"x": xi, "y": yi, "bins": bins, "offs": offs},
                        multiple=JOIN_BLOCK,
                    )
                    state[name] = _MeshIndexState(
                        cols=cols, rows_per_shard=rows_per_shard, n=len(table)
                    )
                    used_bytes += state[name].nbytes
                    register_residency(
                        self.pool, type_name, name, devmon.GROUP_SPATIAL,
                        state[name].nbytes, owner=state[name],
                        fingerprint=fingerprint,
                        on_evict=_slot_clearer(state, name))
                    load_pins.enter_context(self.pool.pinned(type_name, name))
                else:
                    # extended geometries: shard the bbox SoA for overlap refine.
                    # Null geometries leave NaN bounds — normalize a dummy, then
                    # stamp an unsatisfiable interval so they never match (the
                    # residual filter already excludes them on the host path)
                    b = col.bounds[perm]
                    invalid = (
                        np.zeros(len(b), dtype=bool)
                        if col.valid is None
                        else ~col.valid[perm]
                    )
                    invalid |= ~np.isfinite(b).all(axis=1)
                    if invalid.any():
                        b = np.where(invalid[:, None], 0.0, b)
                    xmin = nlon.normalize(b[:, 0]).astype(np.int32)
                    ymin = nlat.normalize(b[:, 1]).astype(np.int32)
                    xmax = nlon.normalize(b[:, 2]).astype(np.int32)
                    ymax = nlat.normalize(b[:, 3]).astype(np.int32)
                    if invalid.any():
                        imax = np.iinfo(np.int32).max
                        xmin[invalid] = imax
                        xmax[invalid] = -1  # hi < 0 <= qlo: overlap always false
                        ymin[invalid] = imax
                        ymax[invalid] = -1
                    cols, padded, rows_per_shard = shard_columns(
                        mesh,
                        {
                            "xmin": xmin, "ymin": ymin, "xmax": xmax, "ymax": ymax,
                            "bins": bins, "offs": offs,
                        },
                        multiple=JOIN_BLOCK,
                    )
                    state[name] = _MeshIndexState(
                        cols=cols, rows_per_shard=rows_per_shard, n=len(table),
                        kind="bboxes",
                    )
                    used_bytes += state[name].nbytes
                    register_residency(
                        self.pool, type_name, name, devmon.GROUP_BBOX,
                        state[name].nbytes, owner=state[name],
                        fingerprint=fingerprint,
                        on_evict=_slot_clearer(state, name))
                    load_pins.enter_context(self.pool.pinned(type_name, name))
        finally:
            load_pins.close()
        # deterministic device-corruption fault injection (resilience/
        # faults.py kind="flip"): flips ONE staged device-column value so
        # the correctness auditor's red legs have a real silent-wrong-
        # answer to catch. Consulted only when an injector is active —
        # the fault-free path is one module-global read.
        from geomesa_tpu.resilience import faults as _faults

        inj = _faults.active()
        if inj is not None:
            self._apply_device_flips(inj, type_name, state)
        return state

    @staticmethod
    def _apply_device_flips(inj, type_name: str, state: dict) -> None:
        """Apply fired ``kind=flip`` rules: XOR bit 30 into row ``at``
        of the x/xmin column of EVERY resident index layout — a large
        silent coordinate corruption the host table does NOT share, so
        whichever index the planner scans diverges from the referee on
        exactly the rows the flipped coordinate moves across a query
        boundary (one flipped value per resident layout; the strategy
        decider picks the layout freely, so a single-index flip would
        make the red leg depend on planner mood)."""
        rules = inj.device_flips(type_name)
        if not rules:
            return
        import jax

        for r in rules:
            for dev in state.values():
                if not isinstance(dev, _MeshIndexState):
                    continue
                col = "x" if dev.kind == "points" else "xmin"
                arr = dev.cols[col]
                host = np.asarray(arr).copy()
                flat = host.reshape(-1)
                row = (r.truncate_at or 0) % max(len(flat), 1)
                flat[row] = np.int32(int(flat[row]) ^ (1 << 30))
                sharding = getattr(arr, "sharding", None)
                dev.cols[col] = (
                    jax.device_put(host, sharding) if sharding is not None
                    else jax.device_put(host)
                )

    # -- refine payload (int-domain superset bounds) -------------------------
    def _payload(self, sft: FeatureType, e: Extraction, overlap: bool = False):
        from geomesa_tpu.ops.refine import pack_boxes, pack_times

        nlon = norm_lon(REFINE_PRECISION)
        nlat = norm_lat(REFINE_PRECISION)
        boxes = None
        if e.boxes is not None:
            boxes = np.array(
                [
                    [
                        int(nlon.normalize(x1)),
                        int(nlon.normalize(x2)),
                        int(nlat.normalize(y1)),
                        int(nlat.normalize(y2)),
                    ]
                    for x1, y1, x2, y2 in e.boxes
                ],
                dtype=np.int32,
            )
        times = time_quads(sft, e.intervals)
        return pack_boxes(boxes, overlap=overlap), pack_times(times)

    @dispatch_budget(2, signatures=("*:rows",))
    def select(self, state, index, plan, extraction, residual, table):
        import time as _time

        intervals = plan.intervals
        if len(intervals) == 0:
            return np.empty(0, dtype=np.int64)
        dev = state.get(index.name) if state else None
        type_name = getattr(index.sft, "name", "?")
        if dev is None:
            # host path: expand + residual. A pool MISS only when this
            # index COULD have been resident (a device-servable layout
            # over a non-empty geometry table — i.e. it was evicted or
            # budget-spilled); host-by-design indexes (id, geometry-less
            # types) must not drown the hit rate in noise
            if (
                state
                and index.name in self._LOAD_PRIORITY
                and len(table)
                and index.sft.geom_field is not None
            ):
                self.pool.note_miss(type_name, index.name)
            with obs.span("refine", mode="host", index=index.name):
                positions, total = gather_indices(intervals)
                rows = index.perm[positions[:total]]
                return rows[ast.residual_mask(residual, table, rows)]

        # adaptive dispatch route (planning/costmodel.py): "twopass" is the
        # per-query candidate-slot count+gather; "planned" runs the batched
        # block-pair steps with a singleton batch — the SAME compiled
        # executables select_many uses, so both modes share one jit cache
        # (the bench-6 fast path). Observed wall per route feeds the cost
        # table under sel:twopass / sel:planned, and the model's probe
        # schedule keeps the losing route measured so the verdict can flip
        # with hardware (dispatch-RTT-bound links favor stable shapes;
        # local backends favor the tighter candidate gather).
        from geomesa_tpu.obs import devmon
        from geomesa_tpu.planning import costmodel

        route = "twopass"
        if dev.rows_per_shard % JOIN_BLOCK == 0:
            route = costmodel.model().choose_select_route(type_name)
        # access-frequency accounting + dispatch pin: a pinned buffer is
        # never an eviction victim, so the scan below cannot lose its
        # columns mid-flight
        self.pool.touch(type_name, index.name)
        t0 = _time.perf_counter()
        with self.pool.pinned(type_name, index.name), \
                obs.span("dispatch", index=index.name,
                         intervals=len(intervals), route=route):
            if route == "planned":
                positions = self.select_many_positions(
                    dev, index, [extraction], [intervals])[0]
            else:
                positions = self._mesh_select_positions(
                    dev, index, extraction, intervals, plan=plan
                )
        from geomesa_tpu.obs import audit as _obsaudit

        if not _obsaudit.in_shadow():
            # audit-shadow re-executions (the divergence minimizer runs
            # the live path repeatedly) must not train the sel:* route
            # profiles — same hygiene as the _audit-level exclusions
            devmon.costs().observe(
                type_name, f"sel:{route}",
                wall_ms=(_time.perf_counter() - t0) * 1000.0,
                rows=len(positions),
            )
        rows = index.perm[positions]
        if isinstance(residual, ast.Include):
            return rows
        with obs.span("refine", candidates=len(rows)):
            return rows[ast.residual_mask(residual, table, rows)]

    @dispatch_budget(2)
    def select_many_positions(
        self, dev: "_MeshIndexState", index, extractions, intervals_list
    ) -> list[np.ndarray]:
        """Matching sorted-order positions for MANY queries in TWO device
        dispatches total (VERDICT r4 item 2 — the multi-query row-retrieval
        path): a planned pair-count pass sizes the gather EXACTLY, then one
        block gather serves every query. Dispatch RTT amortizes across the
        batch the way the fused count/density steps do, and (query, block)
        pair ids ship host→device in KBs where the per-row candidate slots
        of :meth:`_mesh_select_positions` ship MBs per query.

        Both residency layouts serve: point containment
        (``dev.kind == "points"``) and bbox overlap (``"bboxes"`` — the
        XZ extended-geometry layout); the block grid rides the
        JOIN_BLOCK-aligned residency either way. Counts and gather
        evaluate the same int-domain predicate, so gather overflow is
        impossible.
        """
        import jax.numpy as jnp

        from geomesa_tpu.parallel.mesh import data_shards
        from geomesa_tpu.parallel.query import (
            cached_planned_count_step,
            cached_planned_gather_step,
            intervals_to_block_pairs,
            pad_block_pairs,
        )

        mesh = self._get_mesh()
        nq = len(intervals_list)
        B = JOIN_BLOCK
        if dev.rows_per_shard % B != 0:
            raise ValueError(
                f"residency not block-aligned: {dev.rows_per_shard} % {B}")
        empty = [np.empty(0, dtype=np.int64) for _ in range(nq)]
        # host staging before the first dispatch: (query, block) pairs,
        # their padding, the payloads and the stacked batch
        with obs.span("stage", queries=nq):
            pair_q, pair_blk = intervals_to_block_pairs(intervals_list, B)
            if len(pair_q) == 0:
                return empty
            # pairs processed per scan step: larger chunks amortize
            # per-step overhead on accelerators (live gather memory =
            # chunk × JOIN_BLOCK × 24 B); power of two so the padded
            # budget always divides
            chunk = int(os.environ.get("GEOMESA_SELECT_BLOCK_CHUNK", "8"))
            if chunk < 1 or chunk & (chunk - 1):
                raise ValueError(
                    "GEOMESA_SELECT_BLOCK_CHUNK must be a power of two: "
                    f"{chunk}")
            budget = pad_bucket(len(pair_q), minimum=chunk)
            pq, pb = pad_block_pairs(pair_q, pair_blk, budget)
            overlap = dev.kind == "bboxes"
            payloads = [
                self._payload(index.sft, e, overlap=overlap)
                for e in extractions
            ]
            # bucket the query-batch dimension too: every compile-time
            # shape (nqp, budget, capacity) is a bucket, so naturally
            # varying batch sizes reuse cached executables instead of
            # recompiling per size. Padded query slots are never
            # referenced by any pair. The planned steps split this axis
            # over the mesh query axis, so the bucket must also divide by
            # it (the pad_query_axis contract) — a pure power-of-two
            # bucket fails dispatch on query_parallel=3 etc.
            import math

            from geomesa_tpu.parallel.mesh import QUERY_AXIS

            nqp = math.lcm(pad_bucket(nq, minimum=4), mesh.shape[QUERY_AXIS])
            boxes = np.stack(
                [p[0] for p in payloads]
                + [np.zeros_like(payloads[0][0])] * (nqp - nq)
            )
            times = np.stack(
                [p[1] for p in payloads]
                + [np.zeros_like(payloads[0][1])] * (nqp - nq)
            )
            from geomesa_tpu.obs.jaxmon import count_h2d

            count_h2d(pq, pb, boxes, times)  # per-batch payload staging
        args = (
            *dev.spatial_cols(), jnp.int32(dev.n),
        )
        # pool accounting + pin: the batch's two dispatches read the same
        # resident columns; pinned buffers are never eviction victims
        type_name = getattr(index.sft, "name", "?")
        self.pool.touch(type_name, index.name)
        with self.pool.pinned(type_name, index.name):
            with obs.span("dispatch.count", queries=nq, pairs=len(pair_q)):
                # inter-stage host sync: the pair counts size the gather
                counts = _rtledger.materialize(
                    cached_planned_count_step(mesh, nqp, B, budget, chunk,
                                              overlap=overlap)(
                        *args, jnp.asarray(pq[None]), jnp.asarray(pb[None]),
                        jnp.asarray(boxes[None]), jnp.asarray(times[None]),
                    )
                )[0]
            total = int(counts.sum())
            if total == 0:
                return empty
            capacity = pad_bucket(total, minimum=128)
            with obs.span("dispatch.gather", capacity=capacity):
                buf, hits = cached_planned_gather_step(
                    mesh, B, budget, capacity, chunk, overlap=overlap)(
                    *args, jnp.asarray(pq), jnp.asarray(pb),
                    jnp.asarray(boxes), jnp.asarray(times),
                )
                buf = _rtledger.materialize(buf)
                hits = _rtledger.materialize(hits)
        # per-pair spans: a pair's rows sit in its OWNER shard's buffer,
        # consecutively in pair-index order (the device scan's write order)
        with obs.span("unpack", pairs=len(pair_q)):
            blocks_per_shard = dev.rows_per_shard // B
            out: list[list[np.ndarray]] = [[] for _ in range(nq)]
            off = np.zeros(data_shards(mesh), dtype=np.int64)
            for p in range(budget):
                qid = int(pq[p])
                if qid < 0:
                    continue
                d = int(pb[p]) // blocks_per_shard
                h = int(hits[p])
                if h:
                    out[qid].append(buf[d, off[d]: off[d] + h])
                off[d] += h
            return [
                np.concatenate(o).astype(np.int64) if o
                else np.empty(0, dtype=np.int64)
                for o in out
            ]

    @dispatch_budget(2)
    def _mesh_select_positions(
        self, dev: _MeshIndexState, index, extraction, intervals, plan=None
    ) -> np.ndarray:
        """Distributed two-pass refine → matching sorted-order positions.

        ``plan``: the owning :class:`~geomesa_tpu.index.api.IndexPlan`,
        when the caller has one — its ``exec_cache`` memoizes the derived
        per-shard interval split and the staged device payloads, so a plan
        served from the store's plan cache dispatches with ZERO host
        re-derivation or re-staging (the dominant host cost of the steady
        per-query select path). The memo key carries the layout shape; a
        reload with a different shape misses instead of mis-pairing.
        """
        import jax.numpy as jnp

        from geomesa_tpu.parallel.mesh import data_shards
        from geomesa_tpu.parallel.query import (
            cached_select_count_step,
            cached_select_count_step_bbox,
            cached_select_gather_step,
            cached_select_gather_step_bbox,
            max_shard_candidates,
            split_intervals_by_shard,
        )

        mesh = self._get_mesh()
        n_shards = data_shards(mesh)
        bbox_mode = dev.kind == "bboxes"
        memo_key = ("twopass", id(mesh), dev.rows_per_shard, dev.kind)
        memo = plan.exec_cache.get(memo_key) if plan is not None else None
        if memo is None:
            # host staging before the first dispatch: the per-shard
            # candidate split, the payload, and their puts to the device
            with obs.span("stage", intervals=len(intervals)):
                mx = max_shard_candidates(
                    intervals, dev.rows_per_shard, n_shards)
                if mx == 0:
                    memo = (0, None, None, None, None)
                    if plan is not None:
                        plan.exec_cache[memo_key] = memo
                    return np.empty(0, dtype=np.int64)
                bucket = pad_bucket(mx)
                idx, counts = split_intervals_by_shard(
                    intervals, dev.rows_per_shard, n_shards, bucket
                )
                boxes, times = self._payload(
                    index.sft, extraction, overlap=bbox_mode)
                from geomesa_tpu.obs.jaxmon import count_h2d

                count_h2d(idx, counts, boxes, times)  # per-query staging
                memo = (
                    mx,
                    jnp.asarray(idx), jnp.asarray(counts),
                    jnp.asarray(boxes), jnp.asarray(times),
                )
                # memoize only payloads under the per-plan slot cap — a
                # wide scan's (n_shards, bucket) split can reach MBs per
                # plan and those re-derive per query (their cost is
                # scan-dominated anyway). Memoized bytes ARE device
                # residency: register them in the ledger under the
                # "planmemo" group with the PLAN as owner, so the footprint
                # shows in the residency gauges / budget headroom and
                # unregisters itself when the plan cache drops the plan
                # (LRU or state swap). Not pool-evictable by design: the
                # per-plan cap bounds each entry and the plan cache's
                # 128-entry LRU bounds the aggregate.
                if (plan is not None
                        and n_shards * bucket <= _EXEC_MEMO_MAX_SLOTS):
                    plan.exec_cache[memo_key] = memo
                    from geomesa_tpu.obs import devmon

                    devmon.ledger().register(
                        getattr(index.sft, "name", "?"), index.name,
                        "planmemo",
                        sum(int(a.nbytes) for a in memo[1:]),
                        owner=plan,
                    )
        mx, d_idx, d_counts, d_boxes, d_times = memo
        if mx == 0:
            return np.empty(0, dtype=np.int64)
        c = dev.cols
        if bbox_mode:
            col_args = (
                c["xmin"], c["xmax"], c["ymin"], c["ymax"], c["bins"], c["offs"]
            )
        else:
            col_args = (c["x"], c["y"], c["bins"], c["offs"])
        gather = (cached_select_gather_step_bbox if bbox_mode
                  else cached_select_gather_step)
        # single-dispatch route: the count pass exists only to TIGHTEN the
        # gather capacity (matches <= planner candidates), but each extra
        # dispatch pays a full host->device round trip, against the few ms
        # the tighter transfer saves. When the
        # planner's candidate bound is already small, gather straight at
        # that bound; the two-pass stays for wide scans where an untamed
        # capacity would dominate transfer and pos-buffer memory.
        # compare the PADDED capacity (what the gather actually allocates
        # and transfers), not the raw candidate bound
        if n_shards * pad_bucket(mx, minimum=128) <= _ONE_PASS_MAX_SLOTS:
            capacity = pad_bucket(mx, minimum=128)
        else:
            count_step = (cached_select_count_step_bbox if bbox_mode
                          else cached_select_count_step)(mesh)
            # the inter-stage host sync of the two-pass route: the count
            # result must land on host before the gather capacity exists
            # (ledger.materialize = np.asarray + roundtrip sync accounting)
            per_shard = _rtledger.materialize(
                count_step(*col_args, d_idx, d_counts, d_boxes, d_times)
            )
            top = int(per_shard.max())
            if top == 0:
                return np.empty(0, dtype=np.int64)
            capacity = pad_bucket(top, minimum=128)
        pos, hits = gather(mesh, capacity)(
            *col_args, d_idx, d_counts, d_boxes, d_times
        )
        pos = _rtledger.materialize(pos)
        hits = _rtledger.materialize(hits)
        with obs.span("unpack", shards=n_shards):
            return np.concatenate(
                [pos[d, : hits[d]] for d in range(n_shards)]
            ).astype(np.int64)
