"""The plan table (geomesa_tpu.obs.plantable): one bounded record per
(type, plan signature) holding the cost profile, its calibration, the
latency ring and the roundtrip rollup.

- parity: a fixed observation sequence (planned and batched audits,
  ``sel:`` route observations, exemplars, a coalesced shared ledger, an
  audit outside any ledger, a schema purge) yields exactly the documents
  the four separate tables it replaced produced — recorded below as
  literals — and a ``costs.json`` sidecar of that format loads;
- one query is one write: ``_audit`` takes the table lock once per
  query, for a planned select and for each CQL of a batched count, and a
  schema purge is one ``forget`` that empties every view;
- the one valve and the one ``forget``, pinned per view.
"""

import json
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from geomesa_tpu.geometry import Point
from geomesa_tpu.obs import devmon, ledger, plantable
from geomesa_tpu.obs.ledger import QueryLedger
from geomesa_tpu.obs.plantable import PlanTable
from geomesa_tpu.planning import costmodel
from geomesa_tpu.planning.planner import Query
from geomesa_tpu.schema.sft import parse_spec
from geomesa_tpu.store.datastore import DataStore

# ---------------------------------------------------------------------------
# parity with the documents of the four tables the plan table replaced
# ---------------------------------------------------------------------------

Z3 = SimpleNamespace(index_name="z3", n_intervals=5)  # z3:iv8:rows
Z2 = SimpleNamespace(index_name="z2", n_intervals=2)  # z2:iv2:rows


def _charge(ql, k):
    base = 10.0 * k
    ql.note_dispatch(base, base + 0.002, compiled=(k == 0),
                     h2d_bytes=64 * (k + 1), d2h_bytes=8 * (k + 1))
    ql.note_sync(base + 0.003, base + 0.0045)
    ql.note_dispatch(base + 0.005, base + 0.0058)


def _q(trace=""):
    return Query(filter="INCLUDE", hints={"trace_id": trace} if trace else {})


def _parity_sequence():
    """Drive ``DataStore._audit`` and the route observations through a
    fixed sequence on a fixed clock; return every read document."""
    clock = [1000.0]
    devmon.install(devmon.ResidencyLedger())
    plantable.install(PlanTable(clock=lambda: clock[0]))
    devmon.ledger().register("pt", "z3", "spatial", 4096)
    ds = DataStore()

    def planned(t, info, plan_ms, scan_ms, hits, trace="", k=0,
                device=None):
        with ledger.roundtrip() as ql:
            _charge(ql, k)
            if device is not None:
                with devmon.profiled() as prof:
                    prof.add("step", dispatch_ms=device[0],
                             device_ms=device[1])
                    ds._audit(t, _q(trace), plan_ms, scan_ms, hits,
                              info=info)
            else:
                ds._audit(t, _q(trace), plan_ms, scan_ms, hits, info=info)
        clock[0] += 4.0

    ct = devmon.costs()
    for i in range(9):
        ct.observe_cost("pt", "sel:twopass", wall_ms=1.0 + 0.25 * i,
                        rows=10 + i)
        ct.observe_cost("pt", "sel:planned", wall_ms=2.5 - 0.125 * i,
                        rows=10 + i, bytes_scanned=1000 * i)
    for _ in range(3):
        ct.tick("pt", "route:select")
    walls = [(0.5, 3.0), (0.4, 2.2), (0.6, 4.1), (0.5, 2.9), (0.7, 3.3),
             (0.3, 6.5), (0.5, 2.0)]
    for i, (p, s) in enumerate(walls):
        planned("pt", Z3, p, s, 100 + 7 * i,
                trace=f"tr{i:02d}" if i % 2 else "", k=i,
                device=(0.25, 1.5 + i * 0.1) if i == 3 else None)
    planned("pt", Z2, 0.2, 12.0, 900, trace="trz2", k=1)
    planned("pt", Z2, 0.2, 30.0, 950, k=2)
    # a coalesced batch: one shared ledger charged by every member query
    with ledger.roundtrip() as ql:
        _charge(ql, 3)
        for j, sig in enumerate(["z3:iv8:rows", "z2:iv2:rows",
                                 "z3:iv8:rows"]):
            ds._audit("pt", _q(f"bt{j}"), 0.1, 1.0 + j, 50 + j, sig=sig)
        ds._audit("pt", _q(), 0.0, 0.0, 17)  # count_many-style: scan:rows
    clock[0] += 11.0
    # an audit outside any roundtrip: latency only, no rollup charge
    ds._audit("pt", _q("tr_out"), 0.0, 0.0, 3)
    # a second type, then its purge
    planned("gone", Z3, 1.0, 1.0, 5, trace="g0", k=0)
    ct.observe_cost("gone", "sel:twopass", wall_ms=5.0, rows=1)
    ct.tick("gone", "route:select")
    for i in range(6):
        planned("gone", Z2, 0.1, 1.0 + i, 5, k=i)
    ds._purge_type_name("gone")

    decisions = [costmodel.model().choose_select_route("pt")
                 for _ in range(20)]
    tbl = ledger.table()
    keys = sorted(tbl.series_keys())
    return {
        "decisions": decisions,
        "cost_snapshot": ct.snapshot(),
        "predict": {s: ct.predict("pt", s) for s in
                    ["z3:iv8:rows", "z2:iv2:rows", "sel:twopass",
                     "sel:planned", "scan:rows", "nope"]},
        "predict_prefix": {p: ct.predict_prefix("pt", p)
                           for p in ["z3:", "z2:", "sel:", "", "nope"]},
        "calibration_report": ct.calibration_report(),
        "calibration_state": ct.calibration_state(),
        "to_state": ct.to_state(),
        "export": tbl.export(),
        "fusion_report": tbl.fusion_report(),
        "window_stats": {f"{t}|{s}": tbl.window_stats(t, s, 0.0, 1e12)
                         for t, s in keys},
        "exemplars": {f"{t}|{s}": tbl.exemplars(t, s) for t, s in keys},
    }


# what the four tables (cost table, calibration table, latency lens,
# roundtrip rollup) returned for _parity_sequence, recorded from them
EXPECTED = {'calibration_report': {'entries': [{'count': 3,
                                                'last_actual_ms': 2.5,
                                                'last_predicted_ms': 3.75,
                                                'mean_abs_rel_err': 0.3743,
                                                'mean_signed_rel_err': -0.0409,
                                                'signature': 'z3:iv8:rows',
                                                'type': 'pt'}],
                                   'entry_count': 1,
                                   'overall_mean_abs_rel_err': 0.3743,
                                   'samples': 3},
            'calibration_state': {'entries': [{'abs_rel_err_sum': 1.122794,
                                               'count': 3,
                                               'last_actual': 2.5,
                                               'last_predicted': 3.75,
                                               'signature': 'z3:iv8:rows',
                                               'signed_rel_err_sum': -0.122794,
                                               'type': 'pt'}]},
            'cost_snapshot': {'entries': [{'bytes_scanned_p50': 4500.0,
                                           'count': 9,
                                           'device_ms_p50': 0.0,
                                           'device_ms_p95': 0.0,
                                           'profiled': 0,
                                           'rows_p50': 14.0,
                                           'signature': 'sel:planned',
                                           'type': 'pt',
                                           'wall_ms_p50': 2.0,
                                           'wall_ms_p95': 2.45},
                                          {'bytes_scanned_p50': 0.0,
                                           'count': 9,
                                           'device_ms_p50': 0.0,
                                           'device_ms_p95': 0.0,
                                           'profiled': 0,
                                           'rows_p50': 14.0,
                                           'signature': 'sel:twopass',
                                           'type': 'pt',
                                           'wall_ms_p50': 2.0,
                                           'wall_ms_p95': 2.9},
                                          {'bytes_scanned_p50': 0.0,
                                           'count': 2,
                                           'device_ms_p50': 0.0,
                                           'device_ms_p95': 0.0,
                                           'profiled': 0,
                                           'rows_p50': 925.0,
                                           'signature': 'z2:iv2:rows',
                                           'type': 'pt',
                                           'wall_ms_p50': 21.2,
                                           'wall_ms_p95': 29.3},
                                          {'bytes_scanned_p50': 4096.0,
                                           'count': 7,
                                           'device_ms_p50': 2.05,
                                           'device_ms_p95': 2.05,
                                           'profiled': 1,
                                           'rows_p50': 121.0,
                                           'signature': 'z3:iv8:rows',
                                           'type': 'pt',
                                           'wall_ms_p50': 3.5,
                                           'wall_ms_p95': 6.17}],
                              'entry_count': 4},
            'decisions': ['twopass', 'twopass', 'twopass', 'twopass',
                          'twopass', 'twopass', 'twopass', 'twopass',
                          'twopass', 'twopass', 'twopass', 'twopass',
                          'planned', 'twopass', 'twopass', 'twopass',
                          'twopass', 'twopass', 'twopass', 'twopass'],
            'exemplars': {'pt|scan:rows': [{'bucket': 1040.0,
                                            'latency_ms': 0.0,
                                            'trace_id': 'tr_out',
                                            'ts': 1047.0}],
                          'pt|z2:iv2:rows': [{'bucket': 1020.0,
                                              'latency_ms': 12.2,
                                              'trace_id': 'trz2',
                                              'ts': 1028.0},
                                             {'bucket': 1030.0,
                                              'latency_ms': 2.1,
                                              'trace_id': 'bt1',
                                              'ts': 1036.0}],
                          'pt|z3:iv8:rows': [{'bucket': 1020.0,
                                              'latency_ms': 6.8,
                                              'trace_id': 'tr05',
                                              'ts': 1020.0},
                                             {'bucket': 1010.0,
                                              'latency_ms': 3.4,
                                              'trace_id': 'tr03',
                                              'ts': 1012.0},
                                             {'bucket': 1030.0,
                                              'latency_ms': 3.1,
                                              'trace_id': 'bt2',
                                              'ts': 1036.0},
                                             {'bucket': 1000.0,
                                              'latency_ms': 2.6,
                                              'trace_id': 'tr01',
                                              'ts': 1004.0},
                                             {'bucket': 1030.0,
                                              'latency_ms': 1.1,
                                              'trace_id': 'bt0',
                                              'ts': 1036.0}]},
            'export': {'entries': [{'compiles': 0,
                                    'd2h_bytes': 32,
                                    'dispatch_ms': 2.8,
                                    'dispatches': 2,
                                    'h2d_bytes': 256,
                                    'host_gap_ms': 1.5,
                                    'queries': 1,
                                    'signature': 'scan:rows',
                                    'sync_ms': 1.5,
                                    'syncs': 1,
                                    'type': 'pt',
                                    'wall_ms': 0.0},
                                   {'compiles': 0,
                                    'd2h_bytes': 72,
                                    'dispatch_ms': 8.4,
                                    'dispatches': 6,
                                    'h2d_bytes': 576,
                                    'host_gap_ms': 4.5,
                                    'queries': 3,
                                    'signature': 'z2:iv2:rows',
                                    'sync_ms': 4.5,
                                    'syncs': 3,
                                    'type': 'pt',
                                    'wall_ms': 44.5},
                                   {'compiles': 1,
                                    'd2h_bytes': 288,
                                    'dispatch_ms': 25.2,
                                    'dispatches': 18,
                                    'h2d_bytes': 2304,
                                    'host_gap_ms': 13.5,
                                    'queries': 9,
                                    'signature': 'z3:iv8:rows',
                                    'sync_ms': 13.5,
                                    'syncs': 9,
                                    'type': 'pt',
                                    'wall_ms': 31.7}],
                       'kind': 'geomesa-tpu-roundtrip-ledger',
                       'schema_version': 1},
            'fusion_report': [{'compiles': 1,
                               'd2h_bytes': 288,
                               'dispatch_ms': 25.2,
                               'dispatches_per_query': 2.0,
                               'h2d_bytes': 2304,
                               'host_gap_ms': 13.5,
                               'host_share': 0.5172,
                               'queries': 9,
                               'signature': 'z3:iv8:rows',
                               'sync_ms': 13.5,
                               'syncs_per_query': 1.0,
                               'type': 'pt',
                               'wall_ms': 31.7},
                              {'compiles': 0,
                               'd2h_bytes': 32,
                               'dispatch_ms': 2.8,
                               'dispatches_per_query': 2.0,
                               'h2d_bytes': 256,
                               'host_gap_ms': 1.5,
                               'host_share': 0.5172,
                               'queries': 1,
                               'signature': 'scan:rows',
                               'sync_ms': 1.5,
                               'syncs_per_query': 1.0,
                               'type': 'pt',
                               'wall_ms': 0.0},
                              {'compiles': 0,
                               'd2h_bytes': 72,
                               'dispatch_ms': 8.4,
                               'dispatches_per_query': 2.0,
                               'h2d_bytes': 576,
                               'host_gap_ms': 4.5,
                               'host_share': 0.2022,
                               'queries': 3,
                               'signature': 'z2:iv2:rows',
                               'sync_ms': 4.5,
                               'syncs_per_query': 1.0,
                               'type': 'pt',
                               'wall_ms': 44.5}],
            'predict': {'nope': None,
                        'scan:rows': None,
                        'sel:planned': {'device_ms_p50': None,
                                        'observations': 9,
                                        'wall_ms_p50': 2.0,
                                        'wall_ms_p95': 2.45},
                        'sel:twopass': {'device_ms_p50': None,
                                        'observations': 9,
                                        'wall_ms_p50': 2.0,
                                        'wall_ms_p95': 2.9},
                        'z2:iv2:rows': {'device_ms_p50': None,
                                        'observations': 2,
                                        'wall_ms_p50': 21.2,
                                        'wall_ms_p95': 29.3},
                        'z3:iv8:rows': {'device_ms_p50': 2.05,
                                        'observations': 7,
                                        'wall_ms_p50': 3.5,
                                        'wall_ms_p95': 6.17}},
            'predict_prefix': {'': {'device_ms_p50': None,
                                    'observations': 27,
                                    'signatures': 4,
                                    'wall_ms_p50': 3.811,
                                    'wall_ms_p95': 5.553},
                               'nope': None,
                               'sel:': {'device_ms_p50': None,
                                        'observations': 18,
                                        'signatures': 2,
                                        'wall_ms_p50': 2.0,
                                        'wall_ms_p95': 2.675},
                               'z2:': {'device_ms_p50': None,
                                       'observations': 2,
                                       'signatures': 1,
                                       'wall_ms_p50': 21.2,
                                       'wall_ms_p95': 29.3},
                               'z3:': {'device_ms_p50': None,
                                       'observations': 7,
                                       'signatures': 1,
                                       'wall_ms_p50': 3.5,
                                       'wall_ms_p95': 6.17}},
            'to_state': {'entries': [{'bytes_scanned': {'count': 0,
                                                        'res': [],
                                                        'total': 0.0},
                                      'count': 9,
                                      'device_ms': {'count': 0,
                                                    'res': [],
                                                    'total': 0.0},
                                      'profiled_count': 0,
                                      'rows': {'count': 9,
                                               'res': [10.0, 11.0, 12.0, 13.0,
                                                       14.0, 15.0, 16.0, 17.0,
                                                       18.0],
                                               'total': 126.0},
                                      'signature': 'sel:twopass',
                                      'type': 'pt',
                                      'wall_ms': {'count': 9,
                                                  'res': [1.0, 1.25, 1.5, 1.75,
                                                          2.0, 2.25, 2.5, 2.75,
                                                          3.0],
                                                  'total': 18.0}},
                                     {'bytes_scanned': {'count': 8,
                                                        'res': [1000.0, 2000.0,
                                                                3000.0, 4000.0,
                                                                5000.0, 6000.0,
                                                                7000.0,
                                                                8000.0],
                                                        'total': 36000.0},
                                      'count': 9,
                                      'device_ms': {'count': 0,
                                                    'res': [],
                                                    'total': 0.0},
                                      'profiled_count': 0,
                                      'rows': {'count': 9,
                                               'res': [10.0, 11.0, 12.0, 13.0,
                                                       14.0, 15.0, 16.0, 17.0,
                                                       18.0],
                                               'total': 126.0},
                                      'signature': 'sel:planned',
                                      'type': 'pt',
                                      'wall_ms': {'count': 9,
                                                  'res': [2.5, 2.375, 2.25,
                                                          2.125, 2.0, 1.875,
                                                          1.75, 1.625, 1.5],
                                                  'total': 18.0}},
                                     {'bytes_scanned': {'count': 7,
                                                        'res': [4096.0, 4096.0,
                                                                4096.0, 4096.0,
                                                                4096.0, 4096.0,
                                                                4096.0],
                                                        'total': 28672.0},
                                      'count': 7,
                                      'device_ms': {'count': 1,
                                                    'res': [2.05],
                                                    'total': 2.05},
                                      'profiled_count': 1,
                                      'rows': {'count': 7,
                                               'res': [100.0, 107.0, 114.0,
                                                       121.0, 128.0, 135.0,
                                                       142.0],
                                               'total': 847.0},
                                      'signature': 'z3:iv8:rows',
                                      'type': 'pt',
                                      'wall_ms': {'count': 7,
                                                  'res': [3.5, 2.6, 4.7, 3.4,
                                                          4.0, 6.8, 2.5],
                                                  'total': 27.5}},
                                     {'bytes_scanned': {'count': 0,
                                                        'res': [],
                                                        'total': 0.0},
                                      'count': 2,
                                      'device_ms': {'count': 0,
                                                    'res': [],
                                                    'total': 0.0},
                                      'profiled_count': 0,
                                      'rows': {'count': 2,
                                               'res': [900.0, 950.0],
                                               'total': 1850.0},
                                      'signature': 'z2:iv2:rows',
                                      'type': 'pt',
                                      'wall_ms': {'count': 2,
                                                  'res': [12.2, 30.2],
                                                  'total': 42.4}}],
                         'ticks': [['pt', 'route:select', 23]]},
            'window_stats': {'pt|scan:rows': {'count': 2,
                                              'dispatches': 2,
                                              'max_ms': 0.0,
                                              'mean_ms': 0.0,
                                              'p50_ms': 0.125,
                                              'p95_ms': 0.2375,
                                              'p99_ms': 0.2475,
                                              'rows': 20,
                                              'sum_ms': 0.0},
                             'pt|z2:iv2:rows': {'count': 3,
                                                'dispatches': 6,
                                                'max_ms': 30.2,
                                                'mean_ms': 14.833333333333334,
                                                'p50_ms': 17.5,
                                                'p95_ms': 46.24999999999999,
                                                'p99_ms': 49.24999999999999,
                                                'rows': 1901,
                                                'sum_ms': 44.5},
                             'pt|z3:iv8:rows': {'count': 9,
                                                'dispatches': 18,
                                                'max_ms': 6.8,
                                                'mean_ms': 3.522222222222222,
                                                'p50_ms': 3.5,
                                                'p95_ms': 7.749999999999995,
                                                'p99_ms': 9.55,
                                                'rows': 949,
                                                'sum_ms': 31.7}}}


@pytest.fixture()
def parity_docs():
    prev_ledger = devmon.install(devmon.ResidencyLedger())
    prev = plantable.install()
    try:
        yield _parity_sequence()
    finally:
        devmon.install(prev_ledger)
        plantable.install(prev)


@pytest.mark.parametrize("doc", sorted(EXPECTED))
def test_parity_with_the_replaced_tables(parity_docs, doc):
    assert parity_docs[doc] == EXPECTED[doc]


# costs.json as the replaced tables wrote it for _parity_sequence
SIDECAR = {
    "kind": "geomesa-cost-snapshot",
    "costs": EXPECTED["to_state"],
    "calibration": EXPECTED["calibration_state"],
}


def test_sidecar_round_trips_in_its_format(tmp_path):
    path = tmp_path / "costs.json"
    path.write_text(json.dumps(SIDECAR))
    assert devmon.load_cost_snapshot(str(path))
    tbl = devmon.costs()
    assert tbl.to_state() == SIDECAR["costs"]
    assert tbl.calibration_state() == SIDECAR["calibration"]
    assert tbl.predict("pt", "z3:iv8:rows") == EXPECTED["predict"][
        "z3:iv8:rows"]
    out = tmp_path / "again.json"
    assert devmon.save_cost_snapshot(str(out)) == str(out)
    assert json.loads(out.read_text()) == SIDECAR


# ---------------------------------------------------------------------------
# one query, one write
# ---------------------------------------------------------------------------

T0 = 1_498_867_200_000


def _cql(i: int) -> str:
    x = -45.0 + i
    return (f"BBOX(geom,{x},-40,{x + 30},40) AND dtg DURING "
            "2017-07-01T00:00:00Z/2017-07-01T00:05:00Z")


@pytest.fixture(scope="module")
def ds():
    rng = np.random.default_rng(11)
    store = DataStore(backend="tpu")
    store.create_schema(parse_spec("pts", "name:String,dtg:Date,*geom:Point"))
    store.write("pts", [
        {"name": f"n{i % 3}", "dtg": T0 + i * 700,
         "geom": Point(float(rng.uniform(-50, 50)),
                       float(rng.uniform(-50, 50)))}
        for i in range(600)
    ])
    store.compact("pts")
    return store


class _CountingLock:
    def __init__(self):
        self._lock = threading.Lock()
        self.acquired = 0

    def __enter__(self):
        self.acquired += 1
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


@pytest.fixture()
def counted(ds, monkeypatch):
    """A fresh table whose lock counts acquisitions, and ``_audit``
    wrapped to record how many each audit took."""
    tbl = plantable.table()
    tbl._lock = _CountingLock()
    per_audit = []
    orig = ds._audit

    def audit(*a, **k):
        n0 = tbl._lock.acquired
        orig(*a, **k)
        per_audit.append(tbl._lock.acquired - n0)

    monkeypatch.setattr(ds, "_audit", audit)
    return tbl, per_audit


def test_planned_query_is_one_write(ds, counted):
    tbl, per_audit = counted
    ds.query("pts", _cql(1))
    assert per_audit == [1]
    sig = next(k[1] for k in tbl.series_keys() if k[0] == "pts")
    assert tbl.window_stats("pts", sig, 0.0, 1e12)["count"] == 1
    assert tbl.predict("pts", sig)["observations"] == 1
    (row,) = [e for e in tbl.export()["entries"] if e["signature"] == sig]
    assert row["queries"] == 1 and row["dispatches"] >= 1


def test_count_many_is_one_write_per_cql(ds, counted):
    tbl, per_audit = counted
    ds.count_many("pts", [_cql(10 + i) for i in range(4)])
    assert per_audit == [1, 1, 1, 1]
    assert sum(tbl.window_stats(t, s, 0.0, 1e12)["count"]
               for t, s in tbl.series_keys()) == 4
    # batched audits carry amortized timings: they never train the cost
    # profile of their signature
    assert not [e for e in tbl.snapshot()["entries"]
                if not e["signature"].startswith("sel:")]
    assert sum(e["queries"] for e in tbl.export()["entries"]) == 4


def test_purge_is_one_forget_that_empties_every_view(ds, counted,
                                                    monkeypatch):
    tbl, _per_audit = counted
    for _ in range(6):  # enough repeats to calibrate the plan shape
        ds.query("pts", _cql(20))
    ds.count_many("pts", [_cql(30 + i) for i in range(4)])
    assert tbl.calibration_report()["entries"]
    forgets = []
    orig = tbl.forget
    monkeypatch.setattr(tbl, "forget",
                        lambda name: (forgets.append(name), orig(name)))
    ds._purge_type_name("pts")
    assert forgets == ["pts"]
    assert tbl.series_keys() == []
    assert tbl.snapshot()["entries"] == []
    assert tbl.to_state() == {"entries": [], "ticks": []}
    assert tbl.calibration_report()["entries"] == []
    assert tbl.fusion_report() == []
    assert tbl.export()["entries"] == []
    assert tbl.lens_snapshot()["series"] == 0


# ---------------------------------------------------------------------------
# the one valve and the one forget, per view
# ---------------------------------------------------------------------------

def _ledger():
    ql = QueryLedger()
    ql.note_dispatch(0.0, 0.001)
    return ql


_WRITE = {
    "lens": lambda tbl, t, sig, now: tbl.record_query(t, sig, 1.0, now=now),
    "rollup": lambda tbl, t, sig, now: tbl.record_query(
        t, sig, 1.0, ledger=_ledger(), now=now),
    "cost": lambda tbl, t, sig, now: tbl.observe_cost(t, sig, wall_ms=1.0),
}
_READ = {
    "lens": lambda tbl: [k for k in tbl.series_keys()],
    "rollup": lambda tbl: [(r["type"], r["signature"])
                           for r in tbl.fusion_report()],
    "cost": lambda tbl: [(e["type"], e["signature"])
                         for e in tbl.snapshot()["entries"]],
    "calibration": lambda tbl: [(e["type"], e["signature"])
                                for e in tbl.calibration_report()["entries"]],
}


@pytest.mark.parametrize("view", ["lens", "rollup", "cost"])
def test_valve_drops_the_record_written_longest_ago(view):
    tbl = PlanTable(max_records=3)
    write = _WRITE[view]
    for i, sig in enumerate(["a", "b", "c"]):
        write(tbl, "pts", sig, 100.0 + i)
    write(tbl, "pts", "a", 103.0)  # "a" written again: "b" is the oldest
    write(tbl, "pts", "d", 104.0)
    assert sorted(s for _t, s in _READ[view](tbl)) == ["a", "c", "d"]


def test_valve_keeps_route_records_without_latency_history():
    """A route signature is written by observe_cost alone and never gets
    a latency bucket: the valve keys on writes, so a recent route record
    outlives older audited ones."""
    tbl = PlanTable(max_records=3)
    tbl.record_query("pts", "a", 1.0, now=100.0)
    tbl.record_query("pts", "b", 1.0, now=101.0)
    tbl.observe_cost("pts", "sel:twopass", wall_ms=1.0)
    tbl.record_query("pts", "c", 1.0, now=102.0)
    assert tbl.predict("pts", "sel:twopass")["observations"] == 1
    assert sorted(s for _t, s in tbl.series_keys()) == ["b", "c"]


@pytest.mark.parametrize("view", ["lens", "rollup", "calibration"])
def test_forget_purges_type(view):
    tbl = PlanTable()
    for t in ("pts", "other"):
        for _ in range(4):  # the prior the calibration needs
            tbl.observe_cost(t, "a", wall_ms=1.0)
        tbl.record_query(t, "a", 2.0, planned=True, ledger=_ledger(),
                         trace_id="tr", now=1.0)
        tbl.tick(t, "route:select")
    tbl.forget("pts")
    assert _READ[view](tbl) == [("other", "a")]
    assert [e["type"] for e in tbl.to_state()["entries"]] == ["other"]
    assert [t[0] for t in tbl.to_state()["ticks"]] == ["other"]
