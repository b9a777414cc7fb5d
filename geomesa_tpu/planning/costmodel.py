"""Adaptive cost model: live-stats seeds corrected by observed costs.

ROADMAP item 3. The reference's ``StrategyDecider`` is cost-based but
STATIC — stats sketches estimate rows, a fixed multiplier penalizes
attribute joins, and the estimate is never compared with what executions
actually cost. *Adaptive Geospatial Joins for Modern Hardware* (PAPERS.md)
shows the winning strategy flips with selectivity AND hardware; GeoBlocks
shows cached/pre-aggregated answers beat rescans only in the right regime.
This module closes the loop:

- **Seeds** come from the stats sketches
  (:meth:`geomesa_tpu.stats.store_stats.StoreStats.selectivity`): a row
  estimate converted to a synthetic cost, used only for RELATIVE ranking
  until real observations exist.
- **Observations** come from the cost profiles of the plan table
  (:mod:`geomesa_tpu.obs.plantable`, ``/api/obs/costs``), fed by every
  fully-planned query audit and by the per-route ``sel:*`` / ``gagg:*`` /
  ``join:*`` signatures the dispatch layers record. Once every candidate
  of a decision has enough observations, measured p50 wall-ms replaces
  the seed ranking outright.
- **Bounded exploration** (the generalized ``choose_agg_path`` tick/probe
  mechanism): every ``PROBE_EVERY``-th consult of a decision routes to the
  LOSING candidate so no profile freezes — the winner can never starve the
  loser of observations, and the verdict can flip when data or hardware
  shifts. Probes are bounded: a candidate whose seed estimate is more than
  ``PROBE_MAX_RATIO`` worse than the best is never probed (re-measuring a
  full scan against an id lookup would be pure regression).
- **SLO-aware tie-breaking**: when the caller reports error-budget burn,
  near-tied candidates (within ``TIE_BAND``) resolve to the LOWER-VARIANCE
  plan (smallest p95/p50 spread) — under burn, predictability beats a thin
  median win.
- **Calibration**: every (predicted, actual) pair of a planned query
  lands in the plan table's per-(type, signature) calibration sums — mean
  absolute relative error, signed bias — served with ``/api/obs/costs``
  and rendered by ``explain(analyze=True)``, so model drift is observable
  before it costs latency.

The model keeps no state of its own: every decision reads the plan
table. No jax at module level (``GEOMESA_TPU_NO_JAX=1`` safe).
"""

from __future__ import annotations

from dataclasses import dataclass, field

__all__ = [
    "Candidate", "CostModel", "MIN_OBSERVATIONS", "PROBE_EVERY",
    "PROBE_MAX_RATIO", "TIE_BAND", "model",
]

# observations a signature needs before its measured p50 outranks seeds
MIN_OBSERVATIONS = 8
# consults between probes of the losing candidate (choose_agg_path legacy
# name AGG_PROBE_EVERY re-exports from planner)
PROBE_EVERY = 16
# never probe a candidate whose seed estimate is worse than the best by
# more than this ratio (bounded exploration: a 10M-row full scan must not
# be re-measured against a 100-row id lookup)
PROBE_MAX_RATIO = 32.0
# candidates within this relative band of the best are "near ties" —
# under SLO burn the lower-variance one wins
TIE_BAND = 0.25

# synthetic ms per estimated row: converts a stats row estimate into a
# seed cost. Only RELATIVE ordering among seeds matters (seeds never
# compare against measured ms — learned mode requires every candidate
# observed), so the constant is arbitrary but keeps explain output legible
SEED_MS_PER_ROW = 0.001


@dataclass
class Candidate:
    """One strategy/route option inside a decision."""

    name: str
    signature: str  # cost-table signature ("sel:planned", "z3:" prefix...)
    est_rows: float | None = None  # stats seed (rows)
    seed_ms: float | None = None  # synthetic seed cost (relative only)
    prefix: bool = False  # signature is a prefix over audit signatures
    observed: dict | None = field(default=None, repr=False)
    predicted_ms: float | None = None  # measured p50 when trained

    def seed(self) -> float:
        if self.seed_ms is not None:
            return float(self.seed_ms)
        if self.est_rows is not None:
            return float(self.est_rows) * SEED_MS_PER_ROW
        return float("inf")


def calibration_error(predicted_ms: float, actual_ms: float) -> float:
    """Relative prediction error vs the ACTUAL cost: |pred - actual| /
    max(actual, epsilon). 0.0 = perfect; 1.0 = off by the full actual."""
    return abs(predicted_ms - actual_ms) / max(actual_ms, 1e-6)


class CostModel:
    """The decision engine: rank candidates by learned cost when every
    candidate is trained, by stats seeds otherwise; probe the loser on a
    bounded schedule."""

    def __init__(self, table=None, min_observations: int = MIN_OBSERVATIONS,
                 probe_every: int = PROBE_EVERY):
        self._table = table
        self.min_observations = min_observations
        self.probe_every = probe_every

    def table(self):
        """The live plan table (the process table unless one was
        injected for tests) — resolved per call so test installs via
        ``plantable.install`` are honored."""
        if self._table is not None:
            return self._table
        from geomesa_tpu.obs import plantable

        return plantable.table()

    # -- prediction ----------------------------------------------------------
    def predict(self, type_name: str, signature: str,
                prefix: bool = False) -> dict | None:
        """Current cost profile for one signature — exact, or aggregated
        over every audit signature starting with ``signature`` (strategy
        decisions key by index name, audits append interval-bucket/agg)."""
        t = self.table()
        if not prefix:
            return t.predict(type_name, signature)
        agg = getattr(t, "predict_prefix", None)
        return agg(type_name, signature) if agg is not None else None

    def _fill(self, type_name: str, c: Candidate,
              min_obs: int | None = None) -> None:
        need = self.min_observations if min_obs is None else min_obs
        obs = self.predict(type_name, c.signature, prefix=c.prefix)
        c.observed = obs
        if obs is not None and obs.get("observations", 0) >= need:
            c.predicted_ms = obs["wall_ms_p50"]

    # -- the decision --------------------------------------------------------
    def choose(self, type_name: str, decision: str,
               candidates: list[Candidate], *, under_burn: bool = False,
               probe: bool = True,
               min_observations: int | None = None,
               ) -> tuple[Candidate, list[Candidate], str]:
        """Pick one candidate. Returns (winner, ranked, source) where
        ``ranked`` is best-first and ``source`` is one of ``cost-model``
        (every candidate trained — measured p50 ranking), ``stats``
        (seed ranking), or ``probe`` (scheduled re-measure of the loser).

        The probe schedule rides the cost table's per-(type, decision)
        consult counter — never observation counts, which the winner
        freezes by starving the loser (see ``choose_agg_path``)."""
        if not candidates:
            raise ValueError(f"decision {decision!r}: no candidates")
        for c in candidates:
            self._fill(type_name, c, min_observations)
        trained = all(c.predicted_ms is not None for c in candidates)
        if trained:
            ranked = sorted(candidates, key=lambda c: c.predicted_ms)
            source = "cost-model"
            if under_burn and len(ranked) > 1:
                best = ranked[0].predicted_ms
                near = [c for c in ranked
                        if c.predicted_ms <= best * (1.0 + TIE_BAND)]
                if len(near) > 1:
                    near.sort(key=lambda c: _spread(c.observed))
                    if near[0] is not ranked[0]:
                        ranked.remove(near[0])
                        ranked.insert(0, near[0])
                        source = "cost-model/slo"
        else:
            ranked = sorted(candidates, key=lambda c: c.seed())
            source = "stats"
        if probe and len(ranked) > 1:
            tick = self.table().tick(type_name, f"route:{decision}")
            if tick % self.probe_every == 0:
                # bounded exploration: re-measure the best LOSER whose seed
                # isn't catastrophically worse than the winner's. A zero
                # seed (a 0-row stats estimate) gives the bound nothing to
                # anchor on — skip the probe rather than waive the bound
                # (probing a full scan against a 0-row estimate is exactly
                # what PROBE_MAX_RATIO exists to prevent); fixed route
                # seeds (selects/agg/join) are always positive, so those
                # decisions keep their probe cadence.
                floor = ranked[0].seed()
                if floor > 0:
                    for loser in ranked[1:]:
                        if loser.seed() <= floor * PROBE_MAX_RATIO:
                            ranked = [loser] + [
                                c for c in ranked if c is not loser]
                            return ranked[0], ranked, "probe"
        return ranked[0], ranked, source

    # -- canned decisions (the dispatch layers' entry points) ----------------
    def choose_select_route(self, type_name: str) -> str:
        """Row-retrieval dispatch route for ONE planned select:
        ``"twopass"`` (per-query candidate-slot count+gather,
        ``TpuBackend._mesh_select_positions``) or ``"planned"`` (the
        batched block-pair steps run with a singleton batch — the same
        compiled executables ``select_many`` uses, so both modes share one
        jit cache). Observed costs land under ``sel:twopass`` /
        ``sel:planned`` in the dispatch layer — ONE pooled profile per
        type across plan widths (not per interval bucket: a width-aware
        split would multiply each type's training time). Until both
        routes are trained the twopass seed wins (it gathers only
        candidate slots where the planned route reads whole blocks) and
        the probe schedule measures the planned route anyway."""
        win, _, _ = self.choose(type_name, "select", [
            Candidate("twopass", "sel:twopass", seed_ms=1.0),
            Candidate("planned", "sel:planned", seed_ms=2.0),
        ])
        return win.name

    def choose_agg_path(self, type_name: str,
                        min_observations: int | None = None) -> str:
        """Grouped-aggregation route: GeoBlocks ``"pyramid"`` or fused
        device ``"scan"`` (the decision ``ops/geoblocks.py`` consults).
        Pyramid is the seeded default — repeated polygon/bbox aggregation
        is exactly its regime and boundary refinement is O(perimeter)
        where the scan is O(n)."""
        win, _, _ = self.choose(
            type_name, "gagg",
            [
                Candidate("pyramid", "gagg:pyramid", seed_ms=1.0),
                Candidate("scan", "gagg:scan", seed_ms=2.0),
            ],
            min_observations=min_observations,
        )
        return win.name

    def choose_join_path(self, type_name: str, pair_density: float) -> str:
        """Spatial-join kernel choice: ``"block"`` (index-pruned
        block-sparse join — wins when polygon bboxes touch few blocks) or
        ``"dense"`` (full ``points_in_polygons`` pass — wins when measured
        pair density is high enough that block planning + gather overhead
        buys nothing). ``pair_density`` = planned candidate pairs /
        (points x polygons), measured from the block plan."""
        dense_seed = 1.0 if pair_density >= 0.25 else 2.0
        win, _, _ = self.choose(type_name, "join", [
            Candidate("block", "join:block", seed_ms=3.0 - dense_seed),
            Candidate("dense", "join:dense", seed_ms=dense_seed),
        ])
        return win.name


def _spread(observed: dict | None) -> float:
    """p95/p50 dispersion — the variance proxy SLO tie-breaking minimizes
    (a plan with a fat tail loses a near tie under burn)."""
    if not observed:
        return float("inf")
    p50 = observed.get("wall_ms_p50") or 0.0
    p95 = observed.get("wall_ms_p95")
    if p95 is None or p50 <= 0:
        return float("inf")
    return p95 / p50


# -- process-wide singleton ---------------------------------------------------

_model = CostModel()


def model() -> CostModel:
    return _model
