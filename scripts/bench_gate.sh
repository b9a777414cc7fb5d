#!/usr/bin/env bash
# Continuous perf-regression gate — the CI smoke variant.
#
# Nine legs, all cheap (tiny-N CPU mesh, ~seconds each):
#
#   1. capture   — REAL median-of-K measurement of the smoke config in
#                  isolated subprocesses (exercises the whole bench
#                  harness: child fan-out, parity flags, JSON emission).
#   2. green     — `bench.py --regress` against that capture must exit 0.
#   3. red       — the same comparison with a synthetically injected 20%
#                  slowdown (GEOMESA_BENCH_INJECT_SLOWDOWN=1.2) must exit
#                  non-zero at the default 15% threshold.
#
# Legs 2-3 reuse recorded measurements (GEOMESA_BENCH_REGRESS_MEASURED)
# instead of re-measuring, so the red/green contract is DETERMINISTIC: CI
# containers on shared hosts show >2x wall-clock jitter between identical
# runs, and a 15% absolute-time gate on fresh measurements there flakes by
# construction. Real rounds on real hardware run the full re-measuring
# gate instead, against a --regress-capture file taken on the chip
# (see docs/operations.md § Benchmarks).
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export GEOMESA_BENCH_N="${GEOMESA_BENCH_N:-20000}"
export GEOMESA_BENCH_Q="${GEOMESA_BENCH_Q:-8}"
export GEOMESA_BENCH_ITERS="${GEOMESA_BENCH_ITERS:-4}"
export GEOMESA_BENCH_REGRESS_K="${GEOMESA_BENCH_REGRESS_K:-2}"
# config 9 rides the gate as the grouped-aggregation PARITY leg: its
# pyramid-vs-f64-fold, warm-cache-byte-identity, and fused-step parity
# flags all gate (a parity loss on a fresh run always fails, regardless
# of speed) — the 0.16x path of BENCH_r05 can never silently regress again.
# Config 8 rides it as the STREAMING parity leg (ISSUE 8): the
# subscription-matrix product path's straight-XLA referee parity and the
# journal-tier delivery parity both gate every run. Its detail also
# carries the stream-lens delivery profile (ISSUE 20) —
# delivery_p50_ms/p99_ms + on_time_fraction from the journal leg — so
# a delivery-latency regression is visible in the same capture the
# parity legs gate.
# Config 6 rides it as the SELECT parity leg (ISSUE 9): per-query and
# batched row-set parity plus the plan-overhead bound (host planning <5%
# of query wall on the cached path) gate every run — the adaptive
# planner's fast path can never silently regress select again.
# Config 10 rides it as the TRAJECTORY parity leg (ISSUE 15): tube-select
# row-set parity of the device corridor path vs the demoted host referee
# (zero steady-state recompiles pinned), and interlink exact pair-set
# parity vs the nested-loop f64 referee on the 2D and XZ3 legs.
export GEOMESA_BENCH_REGRESS_CONFIGS="${GEOMESA_BENCH_REGRESS_CONFIGS:-2,6,8,9,10}"

tmp="$(mktemp -d)"
trap 'rm -rf "$tmp"' EXIT

echo "[bench-gate] 1/9 capture (real measurement, K=$GEOMESA_BENCH_REGRESS_K)"
python bench.py --regress-capture "$tmp/baseline.json"

echo "[bench-gate] 2/9 green: regress vs capture must pass"
GEOMESA_BENCH_REGRESS_MEASURED="$tmp/baseline.json" \
    python bench.py --regress "$tmp/baseline.json" \
    --regress-report "$tmp/report.json"

echo "[bench-gate] 3/9 red: injected 20% slowdown must FAIL the gate"
if GEOMESA_BENCH_INJECT_SLOWDOWN=1.2 \
    GEOMESA_BENCH_REGRESS_MEASURED="$tmp/baseline.json" \
    python bench.py --regress "$tmp/baseline.json" >/dev/null; then
    echo "[bench-gate] FAIL: injected 20% regression was not caught" >&2
    exit 1
fi

# capture → replay → parity smoke (ISSUE 11): a tiny two-tenant workload
# captured with GEOMESA_TPU_WORKLOAD_DIR, replayed closed-loop, must
# reproduce byte-identical per-query row counts, emit a per-signature
# recorded-vs-replayed report loadable as a --regress baseline, and hold
# the K+1 tenant label-cardinality bound on the prometheus exposition.
echo "[bench-gate] 4/9 workload capture -> replay -> parity smoke"
python scripts/replay_smoke.py

# serving-plane smoke (ISSUE 12): replay a tiny captured two-tenant
# workload through the web tier with admission control + request
# coalescing ON — row-count parity vs uncoalesced execution, observed
# coalesce width > 1 (fewer device dispatches than queries), and shed
# correctness (the over-budget tenant answers 429 + Retry-After while
# the healthy tenant keeps answering 200). See docs/serving.md.
echo "[bench-gate] 5/9 serving: admission + coalescing replay parity smoke"
python scripts/serving_smoke.py

# correctness-auditor smoke (ISSUE 13): green leg — a clean mixed
# workload (selects, exact batched counts, grouped aggs, concurrent
# writer) at GEOMESA_TPU_AUDIT=1.0 audits with ZERO divergences (epoch
# races may only abstain) and clean invariant sweeps; red leg — an
# injected one-row device corruption (FaultInjector kind=flip) must
# produce >= 1 divergence with a repro bundle that replays to the same
# divergence. The gate fails if the auditor stays silent.
echo "[bench-gate] 6/9 correctness auditor: green + red (injected corruption)"
python scripts/audit_smoke.py

# durability crash harness (ISSUE 14): green leg — N SIGKILL/recover
# cycles across the named crash points (WAL group commit, checkpoint
# commit order, recovery replay) plus random kills, each restart must
# show zero acked-write loss, no half-applied unacked write, referee
# parity, and clean invariant sweeps; red leg — GEOMESA_TPU_WAL_UNSAFE
# acks before durability with a crash injected in that window, and the
# harness MUST detect the loss (the gate fails if it stays silent).
echo "[bench-gate] 7/9 durability: kill-and-recover crash harness (green + red)"
python scripts/crash_smoke.py --cycles "${GEOMESA_CRASH_CYCLES:-8}" --rows 24
python scripts/crash_smoke.py --red --cycles 3 --rows 24

# tpusync reconcile smoke (ISSUE 18): green leg — a roundtrip ledger
# exported from a REAL staged-select run (forced two-phase: count ->
# host sizing -> gather) must reconcile clean against the dispatch
# budgets declared on the select/select-many paths; red leg — the same
# export with 5x the measured dispatch rate must flag the declaration
# (the gate fails if the divergence stays silent). The static half of
# the fusion work list: docs/tpulint.md § Sync rules.
echo "[bench-gate] 8/9 tpusync: static budgets vs measured ledger (green + red)"
python scripts/sync_reconcile_smoke.py

# elastic-federation rebalance harness (ISSUE 19): green leg — live
# shard migrations under write load with SIGKILLs at the elastic.*
# crash points, zero acked-write loss/duplication and clean coverage at
# every generation; red leg — the dual-apply state is DISABLED, opening
# a real loss window the referee must detect (silence fails the gate).
echo "[bench-gate] 9/9 elastic: live-rebalance crash harness (green + red)"
python scripts/rebalance_smoke.py --cycles "${GEOMESA_REBALANCE_CYCLES:-8}"
python scripts/rebalance_smoke.py --red --cycles 3

echo "[bench-gate] OK"
