"""Bench harness regressions: the official record must never read
parity-false for HARNESS reasons (VERDICT r2 weak #2 — `iso()` truncated
query windows to whole seconds while the f64 referee used exact
milliseconds, so one sub-second-boundary row went "missing")."""

import json
import subprocess
import sys

import numpy as np
import pytest

import geomesa_tpu  # noqa: F401
from geomesa_tpu.filter.cql import parse as parse_cql
from geomesa_tpu.geometry.types import Point
from geomesa_tpu.planning.planner import Query
from geomesa_tpu.schema.columnar import Column, FeatureTable, point_column
from geomesa_tpu.schema.sft import AttributeType, parse_spec
from geomesa_tpu.store.datastore import DataStore


def _iso_ms(ms: int) -> str:
    """The bench's millisecond-precision ISO formatter, reproduced here so a
    drift in either copy fails the parity sweep below."""
    import datetime

    dt = datetime.datetime.fromtimestamp(ms / 1000, datetime.timezone.utc)
    return dt.strftime("%Y-%m-%dT%H:%M:%S.") + f"{int(ms) % 1000:03d}Z"


def test_bench_iso_matches_local():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_mod", "bench.py")
    # bench.py imports jax at module load; the conftest already pinned cpu.
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # grab the closure-free equivalent by calling bench_select's inner iso
    # indirectly: format a few stamps both ways through the CQL parser
    for ms in (1_499_481_020_001, 1_500_000_000_999, 1_500_000_000_000):
        ast = parse_cql(f"dtg DURING {_iso_ms(ms)}/{_iso_ms(ms + 86_400_000)}")
        assert ast.lo_millis == ms and ast.hi_millis == ms + 86_400_000


class TestSubSecondBoundaryParity:
    """Row-set parity between DataStore CQL select and the exact-ms f64
    referee, fuzzing timestamps ONTO window boundaries at ms offsets."""

    def _parity_sweep(self, seed: int):
        rng = np.random.default_rng(seed)
        n = 4_000
        t0 = 1_499_000_000_000
        span = 10 * 86_400_000
        t = t0 + rng.integers(0, span, n)
        # windows with sub-second endpoints, then rows planted EXACTLY on
        # and ±1 ms around both endpoints (the r02 failure was one row at
        # t=...020001 just inside a truncated window edge)
        windows = []
        for _ in range(8):
            lo = int(t0 + rng.integers(0, span - 86_400_000))
            hi = lo + int(rng.integers(3_600_000, 86_400_000))
            windows.append((lo, hi))
        planted = []
        for lo, hi in windows:
            planted += [lo - 1, lo, lo + 1, hi - 1, hi, hi + 1]
        t = np.concatenate([t, np.array(planted, dtype=np.int64)])
        n = len(t)
        lon = rng.uniform(-60, 60, n)
        lat = rng.uniform(-30, 30, n)

        sft = parse_spec("evt", "dtg:Date,*geom:Point")
        table = FeatureTable.from_columns(
            sft,
            np.arange(n).astype(str).astype(object),
            {"dtg": Column(AttributeType.DATE, t.astype(np.int64)),
             "geom": point_column(lon, lat)},
        )
        for backend in ("oracle", "tpu"):
            ds = DataStore(backend=backend)
            ds.create_schema(sft)
            ds.write("evt", table)
            ds.compact("evt")
            for lo, hi in windows:
                cql = (
                    f"BBOX(geom, -50, -25, 50, 25) AND "
                    f"dtg DURING {_iso_ms(lo)}/{_iso_ms(hi)}"
                )
                got = set(ds.query("evt", cql).table.fids.tolist())
                # referee: exact-ms f64 semantics (DURING is exclusive)
                m = (
                    (lon >= -50) & (lon <= 50) & (lat >= -25) & (lat <= 25)
                    & (t > lo) & (t < hi)
                )
                want = set(np.nonzero(m)[0].astype(str).tolist())
                assert got == want, (
                    backend, lo, hi,
                    sorted(want - got)[:3], sorted(got - want)[:3],
                )

    def test_boundary_rows_fuzz(self):
        for seed in (0, 1, 2):
            self._parity_sweep(seed)


def _bench_mod(name="bench_units_mod"):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_config_units_round_trip_through_compact():
    """Every canonical config unit must survive the driver's compact
    emission intact (config 8's old prose unit truncated to
    'Grows/s/chip (each row m' in BENCH_DETAIL/BENCH_rNN records)."""
    mod = _bench_mod()
    assert set(mod.BENCHES) <= set(mod.UNITS)
    for cfg, unit in mod.UNITS.items():
        r = {"metric": f"m_{cfg}", "value": 1.0, "unit": unit,
             "vs_baseline": 1.0, "detail": {"row_set_parity": True}}
        c = mod._compact(r)
        assert c["u"] == unit, (cfg, unit, c["u"])
        # and through a full JSON round trip
        assert json.loads(json.dumps(c))["u"] == unit


class TestRegressGate:
    """Pure-function coverage of the perf-regression gate (the live
    red/green smoke runs in scripts/bench_gate.sh)."""

    def test_unit_direction(self):
        mod = _bench_mod()
        assert mod._unit_direction("ms/query") == "lower"
        assert mod._unit_direction("ms p99") == "lower"
        assert mod._unit_direction("Gpairs/s") == "higher"
        assert mod._unit_direction("Grows/s/chip") == "higher"

    def test_compare_lower_is_better(self):
        mod = _bench_mod()
        # 10% slower: inside the 15% threshold
        v = mod._regress_compare(10.0, 11.0, "ms/query", 15.0)
        assert not v["regressed"] and v["delta_pct"] == 10.0
        # 20% slower: regression
        v = mod._regress_compare(10.0, 12.0, "ms/query", 15.0)
        assert v["regressed"] and v["delta_pct"] == 20.0
        # faster is never a regression
        v = mod._regress_compare(10.0, 5.0, "ms/query", 15.0)
        assert not v["regressed"] and v["delta_pct"] < 0

    def test_compare_higher_is_better(self):
        mod = _bench_mod()
        v = mod._regress_compare(1.0, 0.8, "Grows/s/chip", 15.0)
        assert v["regressed"] and v["delta_pct"] == pytest.approx(20.0)
        v = mod._regress_compare(1.0, 1.2, "Grows/s/chip", 15.0)
        assert not v["regressed"]

    def test_injected_slowdown_trips_threshold(self):
        """The gate's self-test contract: identical measurements plus a
        synthetic 20% slowdown must regress at the 15% threshold, in
        BOTH unit directions."""
        mod = _bench_mod()
        v = mod._regress_compare(10.0, 10.0, "ms/query", 15.0, slowdown=1.2)
        assert v["regressed"] and v["delta_pct"] == pytest.approx(20.0)
        assert v["injected_slowdown"] == 1.2
        v = mod._regress_compare(2.0, 2.0, "Grows/s/chip", 15.0, slowdown=1.2)
        assert v["regressed"]
        # and must NOT trip without the injection
        v = mod._regress_compare(10.0, 10.0, "ms/query", 15.0)
        assert not v["regressed"]

    def test_parity_loss_always_gates(self):
        """Losing result-set parity on the fresh run fails the gate even
        at unchanged speed, and even on a config whose baseline had no
        parity referee (a wrong answer is worse than a slow one)."""
        mod = _bench_mod()
        b = {"value": 10.0, "unit": "ms/query", "parity": True}
        v = mod._regress_verdict(b, {"value": 10.0, "parity": False}, 15.0)
        assert v["regressed"] and v["gating"] and v["parity_failure"]
        b_noref = {"value": 10.0, "unit": "ms/query", "parity": None}
        v = mod._regress_verdict(b_noref, {"value": 10.0, "parity": False},
                                 15.0)
        assert v["regressed"] and v["gating"]
        # speed noise on a no-referee config reports but does not gate
        v = mod._regress_verdict(b_noref, {"value": 20.0, "parity": None},
                                 15.0)
        assert v["regressed"] and not v["gating"]
        # the ordinary case: parity config, speed regression, gates
        v = mod._regress_verdict(b, {"value": 20.0, "parity": True}, 15.0)
        assert v["regressed"] and v["gating"] and "parity_failure" not in v

    def test_baseline_loader_accepts_all_three_shapes(self, tmp_path):
        mod = _bench_mod()
        # 1. a --regress-capture file
        cap = tmp_path / "cap.json"
        cap.write_text(json.dumps({
            "kind": "bench-regress-baseline",
            "configs": {"2": {"value": 5.0, "unit": "ms/query",
                              "parity": True}},
        }))
        base = mod._load_regress_baseline(str(cap))
        assert base["2"] == {"value": 5.0, "unit": "ms/query", "parity": True}
        # 2. a BENCH_DETAIL.json sweep record (parity from detail flags)
        det = tmp_path / "detail.json"
        det.write_text(json.dumps({
            "backend": "tpu",
            "configs": {
                "2": {"value": 5.4, "unit": "ms/query",
                      "detail": {"int_domain_parity": True,
                                 "row_set_parity": True}},
                "8": {"value": None, "unit": "error"},
            },
        }))
        base = mod._load_regress_baseline(str(det))
        assert base["2"]["parity"] is True
        assert "8" not in base  # value-less configs never become baselines
        # 3. a --regress-report file: measured values become the baseline
        rep = tmp_path / "report.json"
        rep.write_text(json.dumps({
            "kind": "bench-regress-report",
            "configs": {"2": {"baseline": 5.0, "measured": 5.5,
                              "unit": "ms/query", "parity": True}},
        }))
        base = mod._load_regress_baseline(str(rep))
        assert base["2"]["value"] == 5.5


def test_driver_line_compact_and_parseable(tmp_path):
    """Driver-mode emission contract: the LAST stdout line parses as JSON,
    stays under the driver's ~4 KB tail capture, and carries per-config
    summaries (r02's parsed was null purely from overflow)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_mod2", "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # a worst-case configs dict: 8 configs with max-width fields + errors
    configs = {}
    for i in range(1, 9):
        configs[str(i)] = {
            "metric": "m" * 40, "value": 123.4567, "unit": "u" * 30,
            "vs_baseline": 99999.99,
            "error": "x" * 500,
            "detail": {"n_points": 10**9, "int_domain_parity": True,
                       "row_set_parity": True, "blob": "y" * 2000},
        }
    compact = {k: mod._compact(r) for k, r in configs.items()}
    line = json.dumps({
        "metric": "m" * 60, "value": 1.0, "unit": "ms/query",
        "vs_baseline": 12.3,
        "detail": {"backend": "tpu", "devices": 8, "configs_ok": 8,
                   "configs_total": 8, "configs": compact,
                   "full_detail": "BENCH_DETAIL.json"},
    })
    assert len(line) < 3500, len(line)
    parsed = json.loads(line)
    assert parsed["detail"]["configs"]["1"]["parity"] is True
    # errors truncate, parity flags AND together
    assert len(parsed["detail"]["configs"]["1"]["error"]) <= 120
    bad = dict(configs["2"])
    bad["detail"] = {"int_domain_parity": True, "row_set_parity": False}
    assert mod._compact(bad)["parity"] is False


class TestBackendProbe:
    """No TPU is a failure, never a silent CPU fallback: only an explicit
    JAX_PLATFORMS=cpu makes a run a rehearsal, labelled "cpu"."""

    @staticmethod
    def _probe(monkeypatch, stdout, platforms=None, rc=0):
        mod = _bench_mod("bench_probe_mod")
        done = subprocess.CompletedProcess([], rc, stdout, "boom")
        monkeypatch.setattr(mod, "_run_with_graceful_timeout",
                            lambda *a, **k: done)
        if platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        return mod._probe_backend()

    def test_tpu_passes(self, monkeypatch):
        assert self._probe(monkeypatch, "tpu 1\n")[:2] == ("tpu", 1)

    def test_cpu_rehearsal_labels_itself(self, monkeypatch):
        assert self._probe(monkeypatch, "cpu 8\n", "cpu")[:2] == ("cpu", 8)

    @pytest.mark.parametrize("stdout,platforms,rc", [
        ("cpu 8\n", None, 0),       # no TPU found, CPU not asked for
        ("cpu 8\n", "tpu,cpu", 0),  # asked for the TPU, got the CPU
        ("", None, 1),              # backend failed to start
    ])
    def test_no_tpu_exits(self, monkeypatch, stdout, platforms, rc):
        with pytest.raises(SystemExit):
            self._probe(monkeypatch, stdout, platforms, rc)


class TestHbmPeak:
    class _Dev:
        def __init__(self, kind):
            self.device_kind = kind

    def test_v5e_from_published_table(self):
        mod = _bench_mod()
        assert mod.hbm_peak_gbps(self._Dev("TPU v5 lite")) == 819.0

    def test_unknown_kind_is_an_error(self):
        mod = _bench_mod()
        with pytest.raises(KeyError, match="TPU v99"):
            mod.hbm_peak_gbps(self._Dev("TPU v99"))
