"""Query planner: configure → decide strategy → plan ranges → execute → reduce.

The ``QueryPlanner`` / ``StrategyDecider`` / ``FilterSplitter`` roles
(``geomesa-index-api/.../planning/QueryPlanner.scala:43,63``,
``StrategyDecider.scala:41-67``; call stack SURVEY.md §3.3). Planning is
host-side Python; execution is a backend call (brute-force oracle or TPU
kernels). The residual ("secondary") filter is always the full original filter
— cheap to re-apply vectorized, and it makes every scan plan trivially sound.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

from geomesa_tpu import obs
from geomesa_tpu.curve.binned_time import BinnedTime
from geomesa_tpu.filter import ast
from geomesa_tpu.filter.bounds import Extraction, extract
from geomesa_tpu.filter.cql import parse as parse_cql
from geomesa_tpu.index.api import DEFAULT_MAX_RANGES, FeatureIndex, IndexPlan
from geomesa_tpu.index.attribute import AttributeIndex
from geomesa_tpu.index.z2 import IdIndex, XZ2Index, Z2Index
from geomesa_tpu.index.z3 import XZ3Index, Z3Index
from geomesa_tpu.schema.sft import FeatureType

ALL_INDEX_TYPES = [Z3Index, XZ3Index, Z2Index, XZ2Index, IdIndex]
INDEX_BY_NAME = {c.name: c for c in ALL_INDEX_TYPES}


@dataclass
class Query:
    """A query against one feature type (OGC ``Query`` role).

    ``filter``: CQL string or AST node. ``properties``: projection (None = all).
    ``hints``: QueryHints analog (``index/conf/QueryHints.scala``) — e.g.
    ``{"index": "z2"}`` to force an index, ``{"loose_bbox": True}``,
    aggregation hints (``density``, ``stats``, ``bin``...).
    """

    filter: Any = None
    properties: list[str] | None = None
    sort_by: tuple[str, bool] | None = None  # (field, descending)
    limit: int | None = None
    # OGC Query.startIndex paging offset: rows skipped after sort, before limit
    start_index: int | None = None
    hints: dict = field(default_factory=dict)
    # authorizations for record-level visibility filtering (geomesa-security
    # role); None = unrestricted, [] = only unlabeled records visible
    auths: list[str] | None = None

    def resolved_filter(self) -> ast.Filter:
        if self.filter is None:
            return ast.Include()
        if isinstance(self.filter, str):
            return parse_cql(self.filter)
        return self.filter


@dataclass
class QueryPlanInfo:
    """Explain output (``Explainer`` role, ``index/utils/Explainer.scala:16``)."""

    type_name: str
    filter_str: str
    index_name: str
    extraction: Extraction
    n_intervals: int
    n_candidates: int
    plan_ms: float
    notes: list[str] = field(default_factory=list)
    # multi-plan union (FilterSplitter role): [(index_name, IndexPlan,
    # Extraction)] — when set, the scan is the union of these sub-scans and
    # ``index_name`` reads "union(...)"
    sub_plans: list = None
    # adaptive-planner decision record (planning/costmodel.py): how the
    # strategy was chosen ("cost-model" | "cost-model/slo" | "probe" |
    # "stats" | "heuristic" | "forced" | "fid"), the model's predicted
    # wall-ms for the winner (None before training), the stats row
    # estimate, and every REJECTED alternative as {name, est_rows,
    # observed_ms_p50, observations} — what explain() renders
    strategy_source: str = ""
    predicted_ms: float | None = None
    est_rows: float | None = None
    alternatives: list = None
    # high-selectivity fast path: decomposition ran with the reduced range
    # budget and the union search was skipped (CHEAP_SELECT_ROWS)
    cheap: bool = False

    def signature(self, q=None) -> str:
        """The shared plan-shape key (``devmon.plan_signature``): what the
        adaptive cost table, the query lens, and the roundtrip ledger all
        key their per-plan profiles by. Exposed here so explain output and
        lens/fusion-report entries cross-reference without re-deriving."""
        from geomesa_tpu.obs import devmon as _devmon
        return _devmon.plan_signature(self, q)

    def explain(self) -> str:
        lines = [
            f"Planning '{self.type_name}' {self.filter_str}",
            f"  Index: {self.index_name}",
            f"  Signature: {self.signature()}",
            f"  Spatial bounds: {self.extraction.boxes}",
            f"  Temporal bounds: {self.extraction.intervals}",
            f"  Scan intervals: {self.n_intervals} covering {self.n_candidates} rows",
            f"  Planning time: {self.plan_ms:.2f} ms",
        ]
        if self.strategy_source:
            head = f"  Strategy: {self.strategy_source}"
            if self.est_rows is not None:
                head += f", estimated {self.est_rows:.0f} rows"
            if self.predicted_ms is not None:
                head += f", predicted {self.predicted_ms} ms p50"
            if self.cheap:
                head += " [cheap fast path]"
            lines.append(head)
        for alt in self.alternatives or []:
            obs_txt = (
                f"observed {alt['observed_ms_p50']} ms p50"
                f" (n={alt['observations']})"
                if alt.get("observed_ms_p50") is not None
                else "no observations"
            )
            lines.append(
                f"  Rejected: {alt['name']} ≈ {alt['est_rows']:.0f} rows, "
                + obs_txt
            )
        lines += [f"  Note: {n}" for n in self.notes]
        return "\n".join(lines)


def _extract_fids(f: ast.Filter):
    """Top-level fid filter (possibly AND'd): the ID-index fast path."""
    if isinstance(f, ast.FidIn):
        return f.fids
    if isinstance(f, ast.And):
        for c in f.children:
            if isinstance(c, ast.FidIn):
                return c.fids
    return None


class StrategyDecider:
    """Pick the best index for an extraction.

    Reference: ``StrategyDecider.scala:41-140`` — cost-based via stats
    estimates when available (``CostBasedStrategyDecider``), falling back to a
    specificity heuristic (id > attr-equality > z3 > z2 > full scan) without
    stats. Attribute-index costs get a residual-work multiplier (the
    reference's join-cost penalty for reduced attribute indexes).

    The static estimate is only the SEED: pass ``type_name`` and a
    ``cost_model`` (:mod:`geomesa_tpu.planning.costmodel`) and the decision
    upgrades to learned per-(type, index) observed costs once every
    competing strategy is trained, with bounded probes of losing strategies
    and SLO-aware tie-breaking (see docs/planning.md).
    """

    ATTR_COST_MULTIPLIER = 2.0

    @staticmethod
    def choose(
        indices: dict[str, FeatureIndex],
        e: Extraction,
        f: ast.Filter,
        hints: dict,
        stats=None,
        trace: list | None = None,
        type_name: str | None = None,
        cost_model=None,
        under_burn: bool = False,
        decision: dict | None = None,
    ) -> tuple[str, Any]:
        notes = trace if trace is not None else []
        dec = decision if decision is not None else {}
        dec.setdefault("alternatives", [])
        forced = hints.get("index")
        if forced:
            if forced not in indices:
                raise ValueError(f"forced index {forced!r} not available")
            notes.append(f"index forced by hint: {forced}")
            dec["source"] = "forced"
            return forced, None
        fids = _extract_fids(f)
        if fids is not None and "id" in indices:
            dec["source"] = "fid"
            return "id", fids
        if stats is not None and stats.count > 0:
            est = StrategyDecider.estimate_rows(indices, e, stats)
            if est:
                if cost_model is not None and type_name and len(est) > 1:
                    name = StrategyDecider._model_based(
                        est, type_name, cost_model, under_burn, notes, dec
                    )
                else:
                    name = min(est.items(), key=lambda kv: kv[1])[0]
                    ranked = ", ".join(
                        f"{n}≈{c:.0f}"
                        for n, c in sorted(est.items(), key=lambda kv: kv[1])
                    )
                    notes.append(
                        f"cost-based (estimated rows): {ranked} → {name}")
                    dec["source"] = "stats"
                    dec["alternatives"] = [
                        {"name": n, "est_rows": c, "observed_ms_p50": None,
                         "observations": 0}
                        for n, c in sorted(
                            est.items(), key=lambda kv: kv[1])
                        if n != name
                    ]
                dec["est_rows"] = est[name]
                return name, None
        name = StrategyDecider._heuristic(indices, e)
        notes.append(f"heuristic choice (no usable stats): {name}")
        dec["source"] = "heuristic"
        return name, None

    @staticmethod
    def _model_based(est: dict, type_name: str, cost_model, under_burn: bool,
                     notes: list, dec: dict) -> str:
        """Rank strategies through the cost model: stats row estimates
        seed the candidates (signature prefix = the index name, matching
        every audit signature the strategy produced), learned p50 wall-ms
        takes over once all candidates are trained, and the probe schedule
        re-measures losers so no profile freezes."""
        from geomesa_tpu.planning.costmodel import Candidate

        cands = [
            Candidate(n, f"{n}:", est_rows=r, prefix=True)
            for n, r in est.items()
        ]
        win, ranked, source = cost_model.choose(
            type_name, "strategy", cands, under_burn=under_burn
        )
        rank_txt = ", ".join(
            f"{c.name}≈{c.predicted_ms}ms" if c.predicted_ms is not None
            else f"{c.name}≈{c.est_rows:.0f}rows"
            for c in ranked
        )
        notes.append(f"adaptive ({source}): {rank_txt} → {win.name}")
        dec["source"] = source
        dec["predicted_ms"] = win.predicted_ms
        dec["alternatives"] = [
            {
                "name": c.name,
                "est_rows": c.est_rows,
                "observed_ms_p50": (
                    c.observed.get("wall_ms_p50") if c.observed else None
                ),
                "observations": (
                    c.observed.get("observations", 0) if c.observed else 0
                ),
            }
            for c in ranked[1:]
        ]
        return win.name

    @staticmethod
    def estimate_rows(indices, e: Extraction, stats) -> dict[str, float]:
        """Per-strategy stats row estimates (the ``CostBasedStrategyDecider``
        table): every servable index → estimated matching rows, attribute
        indexes penalized by :data:`ATTR_COST_MULTIPLIER`. Empty when no
        index can be costed (caller falls back to the heuristic)."""
        costs: dict[str, float] = {}
        for name, index in indices.items():
            if name == "id":
                continue  # only via fid fast path
            if name in ("z3", "xz3"):
                # z3 competes only when the filter has temporal bounds — a
                # spatial-only query would pay a per-time-bin range
                # decomposition for the same selectivity z2 gets in one pass
                # (the reference offers z3 strategies only for dtg-bounded
                # filters, Z3IndexKeySpace.getIndexValues)
                if e.spatially_bounded and not e.temporally_bounded and (
                    "z2" in indices or "xz2" in indices
                ):
                    continue
                if not (e.spatially_bounded or e.temporally_bounded):
                    costs[name] = float(stats.count)
                else:
                    # estimation always uses the point z3 curve against the
                    # Z3Histogram (built only for point schemas; otherwise
                    # falls back to total count inside the estimator)
                    costs[name] = stats.estimate_spatiotemporal(
                        e, _z3_est_sfc(index), index.binned
                    )
            elif name in ("z2", "xz2"):
                if not e.spatially_bounded:
                    costs[name] = float(stats.count)
                else:
                    # spatial-only estimate: all bins, coarse cover
                    e_sp = Extraction(e.boxes, None, {})
                    costs[name] = stats.estimate_spatiotemporal(
                        e_sp, _z3_est_sfc(index), BinnedTime(index.sft.z3_interval)
                    )
            elif name.startswith("attr:"):
                attr = name.split(":", 1)[1]
                bounds = e.attributes.get(attr)
                if bounds is None:
                    continue  # can't serve
                est = stats.estimate_attr(attr, bounds)
                costs[name] = est * StrategyDecider.ATTR_COST_MULTIPLIER
        return costs

    @staticmethod
    def _heuristic(indices, e: Extraction) -> str:
        for name in indices:
            if name.startswith("attr:") and e.attr_bounded(name.split(":", 1)[1]):
                bounds = e.attributes[name.split(":", 1)[1]]
                if all(lo is not None and lo == hi for lo, hi, _, _ in bounds):
                    return name  # equality on an indexed attribute
        temporal = e.temporally_bounded
        spatial = e.spatially_bounded
        if temporal and ("z3" in indices or "xz3" in indices):
            return "z3" if "z3" in indices else "xz3"
        if spatial and ("z2" in indices or "xz2" in indices):
            return "z2" if "z2" in indices else "xz2"
        for name in ("z3", "xz3", "z2", "xz2", "id"):
            if name in indices:
                return name
        return next(iter(indices))  # whatever is configured (full scan)


def _z3_est_sfc(index):
    """The point z3 curve used for selectivity estimation (shared by the z3
    and z2 costing branches)."""
    from geomesa_tpu.curve.sfc import z3_sfc

    return z3_sfc(index.sft.z3_interval)


# high-selectivity fast path (the bench-6 regression fix): when stats
# estimate at most this many matching rows, decomposition runs with the
# reduced range budget below and the union search is skipped outright —
# planning cost scales with range count, and a query returning a few
# thousand rows must not pay a 2000-range decomposition to save device
# work it doesn't have (results are identical either way: coarser ranges
# only widen the int-domain candidate superset the exact residual culls)
CHEAP_SELECT_ROWS = 4096
CHEAP_MAX_RANGES = 64


class QueryPlanner:
    """Plans one query over one feature type's built indexes.

    ``cost_model``: the adaptive cost model consulted for strategy choice
    (default: the process singleton, :func:`geomesa_tpu.planning.costmodel.
    model`); pass ``False`` to force the static stats-only decider (the
    union-arm sub-planner does — per-arm probes would make union plans
    nondeterministic)."""

    def __init__(
        self, sft: FeatureType, indices: dict[str, FeatureIndex], stats=None,
        cost_model=None,
    ):
        self.sft = sft
        self.indices = indices
        self.stats = stats
        if cost_model is None:
            from geomesa_tpu.planning import costmodel

            cost_model = costmodel.model()
        self.cost_model = cost_model or None  # False → None (static)
        self.indexed_attrs = tuple(
            name.split(":", 1)[1] for name in indices if name.startswith("attr:")
        )

    def plan(
        self, q: Query, max_ranges: int = DEFAULT_MAX_RANGES,
        under_burn: bool = False,
    ) -> tuple[IndexPlan, ast.Filter, QueryPlanInfo]:
        t0 = time.perf_counter()
        f = q.resolved_filter()
        from geomesa_tpu.filter.bounds import coerce_attr_bounds

        e = extract(
            f, self.sft.geom_field, self.sft.dtg_field, attrs=self.indexed_attrs
        )
        e = coerce_attr_bounds(self.sft, e)
        notes: list[str] = []
        dec: dict = {}
        name, fids = StrategyDecider.choose(
            self.indices, e, f, q.hints, self.stats, trace=notes,
            type_name=self.sft.name, cost_model=self.cost_model,
            under_burn=under_burn, decision=dec,
        )
        index = self.indices[name]
        for attr, bounds in e.attributes.items():
            if bounds is not None:
                notes.append(f"attribute bounds: {attr} in {bounds}")
        est_rows = dec.get("est_rows")
        # cheap means SELECTIVE, not small-absolute: a tiny store's full
        # scan estimates under the row threshold but deserves the whole
        # machinery; and a top-level OR keeps the union search — that IS
        # the machinery built for it (a cross-attribute OR's single-index
        # plan can be a full scan the union beats by orders of magnitude)
        cheap = (
            fids is None
            and not isinstance(f, ast.Or)
            and est_rows is not None
            and est_rows <= CHEAP_SELECT_ROWS
            and self.stats is not None
            and est_rows <= 0.25 * max(self.stats.count, 1)
        )
        if cheap:
            max_ranges = min(max_ranges, CHEAP_MAX_RANGES)
            notes.append(
                f"cheap fast path: ≈{est_rows:.0f} rows ≤ "
                f"{CHEAP_SELECT_ROWS} — range budget {max_ranges}, "
                "union search skipped"
            )
        with obs.span("decompose", index=name):
            if fids is not None and isinstance(index, IdIndex):
                plan = index.plan_fids(fids)
                notes.append(f"id lookup on {len(fids)} fids")
            else:
                plan = index.plan(e, max_ranges)

        # FilterSplitter role (FilterSplitter.scala:25): a top-level OR whose
        # arms each bind a DIFFERENT index (e.g. cross-attribute ORs) can run
        # as a union of tight scans instead of one loose/full scan — taken
        # when the combined sub-scan candidates undercut the single plan
        if "index" not in q.hints and not cheap:
            union = self._union_plans(f, max_ranges, notes)
            if union is not None:
                union_cand = sum(p.n_candidates for _, p, _ in union)
                if union_cand < plan.n_candidates:
                    notes.append(
                        "union plan: "
                        + " + ".join(
                            f"{n}({p.n_candidates})" for n, p, _ in union
                        )
                        + f" = {union_cand} candidates vs {name}"
                        f"({plan.n_candidates}) single-index"
                    )
                    info = QueryPlanInfo(
                        type_name=self.sft.name,
                        filter_str=str(q.filter) if q.filter is not None else "INCLUDE",
                        index_name="union(" + ",".join(n for n, _, _ in union) + ")",
                        extraction=e,
                        n_intervals=sum(len(p.intervals) for _, p, _ in union),
                        n_candidates=union_cand,
                        plan_ms=(time.perf_counter() - t0) * 1e3,
                        notes=notes,
                        sub_plans=union,
                        strategy_source="union",
                        est_rows=dec.get("est_rows"),
                        alternatives=dec.get("alternatives"),
                    )
                    return plan, f, info

        info = QueryPlanInfo(
            type_name=self.sft.name,
            filter_str=str(q.filter) if q.filter is not None else "INCLUDE",
            index_name=name,
            extraction=e,
            n_intervals=len(plan.intervals),
            n_candidates=plan.n_candidates,
            plan_ms=(time.perf_counter() - t0) * 1e3,
            notes=notes,
            strategy_source=dec.get("source", ""),
            predicted_ms=dec.get("predicted_ms"),
            est_rows=dec.get("est_rows"),
            alternatives=dec.get("alternatives"),
            cheap=cheap,
        )
        return plan, f, info

    def _union_plans(self, f: ast.Filter, max_ranges: int, notes: list):
        """CNF alternative: top-level OR → per-arm index plans, or None.

        Every arm must be bounded under SOME index (spatial, temporal,
        indexed-attribute, or fid bounds) — one unbounded arm makes the union
        a full scan and the single-plan path is strictly better.
        """
        if not isinstance(f, ast.Or) or not (2 <= len(f.children) <= 8):
            return None
        from geomesa_tpu.filter.bounds import coerce_attr_bounds

        budget = max(1, max_ranges // len(f.children))
        subs = []
        for child in f.children:
            e_c = extract(
                child, self.sft.geom_field, self.sft.dtg_field,
                attrs=self.indexed_attrs,
            )
            e_c = coerce_attr_bounds(self.sft, e_c)
            fids = _extract_fids(child) or (
                child.fids if isinstance(child, ast.FidIn) else None
            )
            bounded = (
                e_c.spatially_bounded
                or e_c.temporally_bounded
                or any(b is not None for b in e_c.attributes.values())
                or fids is not None
            )
            if not bounded:
                return None
            name, _ = StrategyDecider.choose(
                self.indices, e_c, child, {}, self.stats
            )
            index = self.indices[name]
            if fids is not None and isinstance(index, IdIndex):
                plan = index.plan_fids(list(fids))
            else:
                plan = index.plan(e_c, budget)
            subs.append((name, plan, e_c))
        return subs


def _standing_dimension(f, geom: str | None, dtg: str | None) -> str:
    """Dimension tag (``space``/``time``/``both``/``all``/``none``) for a
    filter the subscription matrix evaluates EXACTLY in the int domain.

    The matrix runs NO residual filter after the device scan — unlike the
    store's query path, where extraction only has to be a sound superset
    because the full predicate re-applies afterwards. Any clause that
    extraction would widen or drop (attribute predicates, NOT, fid
    filters, non-BBOX spatial ops whose envelope over-covers, ORs that
    mix dimensions — the matrix evaluates ``(any box) AND (any window)``)
    therefore raises instead of silently over-delivering."""
    if isinstance(f, ast.Include):
        return "all"
    if isinstance(f, ast.Exclude):
        return "none"
    if isinstance(f, ast.BBox) and f.prop == geom:
        return "space"
    if isinstance(f, (ast.During, ast.TempOp)) and f.prop == dtg:
        return "time"
    if isinstance(f, ast.Between) and f.prop == dtg:
        return "time"
    if (isinstance(f, ast.Compare) and f.prop == dtg
            and f.op in ("=", "<", "<=", ">", ">=")):
        return "time"
    if isinstance(f, ast.And):
        tags = [_standing_dimension(c, geom, dtg) for c in f.children]
        if "none" in tags:
            return "none"
        tags = [t for t in tags if t != "all"]
        if not tags:
            return "all"
        if all(t == "space" for t in tags):
            return "space"
        if all(t == "time" for t in tags):
            return "time"
        return "both"
    if isinstance(f, ast.Or):
        tags = [_standing_dimension(c, geom, dtg) for c in f.children]
        if "all" in tags:
            return "all"
        tags = [t for t in tags if t != "none"]
        if not tags:
            return "none"
        if all(t == "space" for t in tags):
            return "space"
        if all(t == "time" for t in tags):
            return "time"
        raise ValueError(
            "standing queries cannot OR spatial with temporal clauses — "
            "the matrix evaluates (any box) AND (any window), a strict "
            f"superset of such a predicate: {f!r}"
        )
    raise ValueError(
        "standing queries evaluate bbox + time-window predicates only; no "
        f"residual filter runs after the device scan: unsupported clause {f!r}"
    )


def standing_query_payload(sft: FeatureType, predicate,
                           box_slots: int = 2, time_slots: int = 2):
    """Decompose a STANDING query (bbox + time-window predicate) into one
    subscription-matrix row: packed int-domain box and time-range payloads,
    the exact encoding the batched count kernels consume
    (``store.backends.pack_payloads`` on a batch of one, over the
    planner's bounds extraction, at this matrix's slot counts).

    ``predicate`` is a CQL string, a filter AST node, or a
    :class:`Query`. Returns ``(boxes (box_slots, 4) int32, times
    (time_slots, 4) int32)``. Like every int-domain payload this is a
    SUPERSET test at quantization boundaries — standing-query deliveries
    are int-domain matches (docs/streaming.md § Semantics). A provably
    disjoint predicate packs to the unsatisfiable sentinel (matches
    nothing) instead of a full scan. Raises ``ValueError`` for any clause
    the matrix cannot evaluate exactly (attribute predicates, ``NOT``,
    fid filters, non-BBOX spatial ops, ORs mixing space with time): no
    residual filter runs after the device scan, so accepting one would
    deliver rows the predicate rejects.
    """
    # lazy: backends imports planner — the payload helpers live there
    from geomesa_tpu.ops.refine import unsat_rows
    from geomesa_tpu.store.backends import pack_payloads

    q = predicate if isinstance(predicate, Query) else Query(filter=predicate)
    f = q.resolved_filter()
    # reject predicates the matrix cannot evaluate exactly (raises) —
    # deliveries would otherwise be an UNBOUNDED superset, not the
    # documented quantization-boundary one
    _standing_dimension(f, sft.geom_field, sft.dtg_field)
    e = extract(f, sft.geom_field, sft.dtg_field)
    if e.disjoint:
        return unsat_rows(box_slots, time_slots)
    boxes, times = pack_payloads(sft, [e], box_slots=box_slots,
                                 time_slots=time_slots)
    return boxes[0], times[0]


# routing consults between probes of the loser — now THE shared probe
# cadence of every cost-model decision (planning/costmodel.PROBE_EVERY);
# re-exported here because the agg path defined it first
from geomesa_tpu.planning.costmodel import PROBE_EVERY as AGG_PROBE_EVERY  # noqa: E402


def choose_agg_path(cost_table, type_name: str,
                    min_observations: int = 8) -> str:
    """Route one eligible grouped aggregation: the GeoBlocks pyramid
    (``"pyramid"``) or the fused device scan (``"scan"``).

    Delegates to the generalized cost-model decision engine
    (:meth:`geomesa_tpu.planning.costmodel.CostModel.choose_agg_path`)
    over the given observed-cost table — the original tick/probe
    mechanism, now shared by the strategy decider, the select dispatch
    route, and the join kernel choice: once BOTH routes have enough
    observations under this type the lower p50 wins (until then the
    pyramid is the seeded default — repeated polygon/bbox aggregation is
    exactly its regime), and every ``AGG_PROBE_EVERY``-th consult routes
    to the LOSING path so neither profile freezes. The probe schedule
    rides the cost table's per-type consult counter
    (:meth:`PlanTable.tick`) — never observation counts, which the
    winner freezes by starving the loser of observations."""
    from geomesa_tpu.planning.costmodel import CostModel

    return CostModel(table=cost_table).choose_agg_path(
        type_name, min_observations)


def build_indices(sft: FeatureType) -> dict[str, FeatureIndex]:
    """Instantiate the index set for a schema (``IndexManager`` role).

    Respects ``geomesa.indices`` user-data override; defaults to every index
    whose ``supports`` matches (reference default: z3+z2[+attr]+id for points,
    xz3+xz2+id for extended geometries).
    """
    configured = sft.configured_indices
    out: dict[str, FeatureIndex] = {}
    for cls in ALL_INDEX_TYPES:
        if configured is not None and cls.name not in configured:
            continue
        if cls.supports(sft):
            out[cls.name] = cls(sft)
    for attr in AttributeIndex.indexed_attributes(sft):
        if configured is None or "attr" in configured or f"attr:{attr}" in configured:
            idx = AttributeIndex(sft, attr)
            out[idx.name] = idx
    if not out:
        out["id"] = IdIndex(sft)
    return out
