"""One-pass batch packing of the int-domain refine payloads
(``store.backends.pack_payloads``): bit-identical to the per-query
packing it replaced, and the batched fan-outs that use it answer as the
per-query path does."""

import numpy as np
import pytest

from geomesa_tpu.curve.binned_time import MAX_BIN, BinnedTime
from geomesa_tpu.curve.normalize import lat as norm_lat, lon as norm_lon
from geomesa_tpu.filter.bounds import Extraction
from geomesa_tpu.geometry.types import Point
from geomesa_tpu.ops.refine import MAX_BOXES, MAX_TIMES, pack_boxes, pack_times
from geomesa_tpu.planning.planner import Query, standing_query_payload
from geomesa_tpu.schema.sft import parse_spec
from geomesa_tpu.store.backends import (
    REFINE_PRECISION,
    TpuBackend,
    pack_payloads,
    time_quads,
)
from geomesa_tpu.store.datastore import DataStore

T0 = 1_498_867_200_000  # 2017-07-01
_DAY = 86_400_000
SFT = parse_spec("e", "dtg:Date,*geom:Point")


# -- the reference: the per-query packing, one query and one scalar at a time


def _ref_pack_boxes(boxes_i32, slots=MAX_BOXES, overlap=False):
    if boxes_i32 is None or len(boxes_i32) == 0:
        boxes_i32 = np.array([[0, 2**31 - 1, 0, 2**31 - 1]], dtype=np.int32)
    b = np.asarray(boxes_i32, dtype=np.int32)
    if len(b) > slots:
        b = np.array(
            [[b[:, 0].min(), b[:, 1].max(), b[:, 2].min(), b[:, 3].max()]],
            dtype=np.int32,
        )
    pad = (np.array([2**31 - 1, -1, 2**31 - 1, -1], dtype=np.int32) if overlap
           else np.array([1, 0, 1, 0], dtype=np.int32))
    return np.vstack([b, np.broadcast_to(pad, (slots - len(b), 4))])


def _ref_pack_times(times_i32, slots=MAX_TIMES):
    if times_i32 is None or len(times_i32) == 0:
        times_i32 = np.array([[0, -1, 2**31 - 1, 2**31 - 1]], dtype=np.int32)
    t = np.asarray(times_i32, dtype=np.int32)
    if len(t) > slots:
        t = np.array([[t[:, 0].min(), -1, t[:, 2].max(), 2**31 - 1]],
                     dtype=np.int32)
    pad = np.array([1, 0, 0, -1], dtype=np.int32)
    return np.vstack([t, np.broadcast_to(pad, (slots - len(t), 4))])


def _ref_time_quads(sft, intervals):
    if intervals is None or not sft.dtg_field:
        return None
    binned = BinnedTime(sft.z3_interval)
    quads = []
    for lo, hi in intervals:
        lo = max(int(lo), 0)
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


def _ref_payload_boxes(boxes):
    nlon = norm_lon(REFINE_PRECISION)
    nlat = norm_lat(REFINE_PRECISION)
    return np.array(
        [[int(nlon.normalize(x1)), int(nlon.normalize(x2)),
          int(nlat.normalize(y1)), int(nlat.normalize(y2))]
         for x1, y1, x2, y2 in boxes],
        dtype=np.int32,
    )


def _ref_payload(sft, e, overlap=False):
    boxes = None if e.boxes is None else _ref_payload_boxes(e.boxes)
    return (_ref_pack_boxes(boxes, overlap=overlap),
            _ref_pack_times(_ref_time_quads(sft, e.intervals)))


def _assert_parity(sft, extractions, overlap=False):
    boxes, times = pack_payloads(sft, extractions, overlap=overlap)
    assert boxes.dtype == np.int32 and times.dtype == np.int32
    assert boxes.shape == (len(extractions), MAX_BOXES, 4)
    assert times.shape == (len(extractions), MAX_TIMES, 4)
    for q, e in enumerate(extractions):
        rb, rt = _ref_payload(sft, e, overlap=overlap)
        assert np.array_equal(boxes[q], rb), (q, e.boxes)
        assert np.array_equal(times[q], rt), (q, e.intervals)
    return boxes, times


def _boxes(rng, n):
    if n is None:
        return None
    x = rng.uniform(-180, 170, n)
    y = rng.uniform(-90, 80, n)
    return [(float(a), float(b), float(a + w), float(b + h))
            for a, b, w, h in zip(x, y, rng.uniform(0, 10, n),
                                  rng.uniform(0, 10, n))]


def _intervals(rng, n):
    if n is None:
        return None
    lo = T0 + rng.integers(0, 60 * _DAY, n)
    return [(int(a), int(a + d)) for a, d in zip(lo, rng.integers(0, 3 * _DAY, n))]


_COUNTS = [None, 0, 1, 3, 8, 9, 20]
_INTERVAL_COUNTS = [None, 0, 1, 5, 16, 17, 20]


@pytest.mark.parametrize("overlap", [False, True])
@pytest.mark.parametrize("n_intervals", _INTERVAL_COUNTS)
@pytest.mark.parametrize("n_boxes", _COUNTS)
def test_one_shape_matches_reference(n_boxes, n_intervals, overlap):
    """A batch of queries of one shape, and each query alone."""
    rng = np.random.default_rng([n_boxes or 0, n_intervals or 0, overlap])
    es = [Extraction(_boxes(rng, n_boxes), _intervals(rng, n_intervals))
          for _ in range(3)]
    _assert_parity(SFT, es, overlap=overlap)
    for e in es:
        _assert_parity(SFT, [e], overlap=overlap)


# intervals the clamp moves or drops: before the epoch, past the last bin,
# ending before they start, and every one of a query's intervals dropped
_LATE = int(BinnedTime(SFT.z3_interval).bin_start_millis(MAX_BIN + 1))
_CLAMPED = {
    "negative_lo": [(-5 * _DAY, T0)],
    "negative_both": [(-5 * _DAY, -_DAY), (T0, T0 + _DAY)],
    "hi_past_max_bin": [(T0, _LATE + 7 * _DAY)],
    "lo_past_max_bin": [(_LATE + _DAY, _LATE + 2 * _DAY), (T0, T0 + 1)],
    "all_clamp_away": [(-9 * _DAY, -_DAY), (_LATE, _LATE + _DAY)],
    "reversed": [(T0 + _DAY, T0)],
    "one_instant": [(T0, T0)],
    "open_ends": [(-(2**62), 2**62)],
    "float_ends": [(T0 + 0.7, T0 + _DAY + 0.2)],
    "many_some_dropped": [(-3 * _DAY, -_DAY)] * 4
    + [(T0 + k * _DAY, T0 + k * _DAY + 5) for k in range(17)],
}


@pytest.mark.parametrize("case", sorted(_CLAMPED))
@pytest.mark.parametrize("period", ["day", "week", "month", "year"])
def test_clamped_intervals_match_reference(case, period):
    sft = parse_spec("e", f"dtg:Date,*geom:Point;geomesa.z3.interval='{period}'")
    ivs = _CLAMPED[case]
    e = Extraction([(0.0, 0.0, 1.0, 1.0)], ivs)
    _assert_parity(sft, [e, Extraction(None, [(T0, T0 + _DAY)]), e])
    want = _ref_time_quads(sft, ivs)
    got = time_quads(sft, ivs)
    assert got.dtype == np.int32 and np.array_equal(got, want)


def test_time_quads_matches_reference():
    rng = np.random.default_rng(5)
    for n in (1, 2, 7, 30):
        ivs = _intervals(rng, n)
        assert np.array_equal(time_quads(SFT, ivs), _ref_time_quads(SFT, ivs))
    assert time_quads(SFT, None) is None
    assert np.array_equal(time_quads(SFT, []), [[1, 0, 0, -1]])
    no_dtg = parse_spec("g", "*geom:Point")
    assert time_quads(no_dtg, [(T0, T0 + 1)]) is None


def test_no_date_field_is_unconstrained():
    sft = parse_spec("g", "name:String,*geom:Point")
    rng = np.random.default_rng(9)
    es = [Extraction(_boxes(rng, n), _intervals(rng, k))
          for n, k in ((1, 1), (None, 5), (9, None), (3, 20))]
    _, times = _assert_parity(sft, es)
    assert (times[:, 0] == [0, -1, 2**31 - 1, 2**31 - 1]).all()


@pytest.mark.parametrize("overlap", [False, True])
def test_mixed_batch_matches_reference(overlap):
    rng = np.random.default_rng(17)
    es = [
        Extraction(_boxes(rng, nb), _intervals(rng, ni))
        for nb, ni in ((1, 1), (None, None), (20, 17), (8, 16), (0, 1),
                       (9, None), (1, 0), (3, 5), (None, 20), (1, 1))
    ]
    es.append(Extraction([(-180.0, -90.0, 180.0, 90.0)], _CLAMPED["all_clamp_away"]))
    es.append(Extraction(_boxes(rng, 2), _CLAMPED["many_some_dropped"]))
    es.append(Extraction([(170.0, 80.0, 180.0, 90.0), (-180.0, -90.0, -179.0, -89.0)],
                         _CLAMPED["negative_lo"]))
    _assert_parity(SFT, es, overlap=overlap)
    _assert_parity(SFT, es[::-1], overlap=overlap)


def test_empty_batch():
    boxes, times = pack_payloads(SFT, [])
    assert boxes.shape == (0, MAX_BOXES, 4) and times.shape == (0, MAX_TIMES, 4)


@pytest.mark.parametrize("at", [0, 1, 3])
def test_nan_coordinate_raises(at):
    box = [1.0, 2.0, 3.0, 4.0]
    box[at] = float("nan")
    es = [Extraction([(0.0, 0.0, 1.0, 1.0)], None),
          Extraction([(5.0, 5.0, 6.0, 6.0), tuple(box)], None)]
    with pytest.raises(ValueError):
        pack_payloads(SFT, es)
    with pytest.raises(ValueError):
        _ref_payload(SFT, es[1])


@pytest.mark.parametrize("overlap", [False, True])
def test_payload_is_row_of_batch_of_one(overlap):
    backend = TpuBackend.__new__(TpuBackend)  # _payload reads no state
    rng = np.random.default_rng(23)
    for nb, ni in ((1, 1), (None, None), (9, 17), (0, 3)):
        e = Extraction(_boxes(rng, nb), _intervals(rng, ni))
        boxes, times = pack_payloads(SFT, [e], overlap=overlap)
        got = backend._payload(SFT, e, overlap=overlap)
        assert np.array_equal(got[0], boxes[0])
        assert np.array_equal(got[1], times[0])
        rb, rt = _ref_payload(SFT, e, overlap=overlap)
        assert np.array_equal(got[0], rb) and np.array_equal(got[1], rt)


_INT_ROWS = {
    "none": None,
    "empty": np.zeros((0, 4), np.int32),
    "one": [[5, 9, 2, 7]],
    "pad_row": [[1, 0, 1, 0]],
    "time_pad_row": [[1, 0, 0, -1]],
    "two": [[5, 9, 2, 7], [0, 3, 8, 8]],
    "three": [[5, 9, 2, 7], [0, 3, 8, 8], [-4, 20, 1, 30]],
}


@pytest.mark.parametrize("slots", [1, 2, 3, 8])
@pytest.mark.parametrize("case", sorted(_INT_ROWS))
def test_pack_boxes_and_times_match_reference(case, slots):
    """The one-query wrappers of the batch core, at any slot count."""
    rows = _INT_ROWS[case]
    for overlap in (False, True):
        assert np.array_equal(pack_boxes(rows, slots=slots, overlap=overlap),
                              _ref_pack_boxes(rows, slots=slots, overlap=overlap))
    got = pack_times(rows, slots=slots)
    assert got.dtype == np.int32
    assert np.array_equal(got, _ref_pack_times(rows, slots=slots))


@pytest.mark.parametrize("slots", [(1, 1), (2, 2), (3, 5)])
@pytest.mark.parametrize("cql", [
    "BBOX(geom, -60, -30, 0, 30) AND dtg DURING 2017-07-01T00:00:00Z/2017-07-05T00:00:00Z",
    "BBOX(geom, 150, -40, -150, 40)",
    "(BBOX(geom, 0, 0, 5, 5) OR BBOX(geom, 10, 10, 15, 15) OR BBOX(geom, 20, 20, 25, 25))"
    " AND dtg DURING 2017-07-01T00:00:00Z/2017-07-02T00:00:00Z",
    "dtg DURING 1960-01-01T00:00:00Z/1965-01-01T00:00:00Z",
    "dtg DURING 2017-07-01T00:00:00Z/2017-07-02T00:00:00Z"
    " OR dtg DURING 2017-07-04T00:00:00Z/2017-07-05T00:00:00Z"
    " OR dtg DURING 2017-07-08T00:00:00Z/2017-07-09T00:00:00Z",
])
def test_standing_query_payload_matches_reference(cql, slots):
    """The subscription matrix's rows: a batch of one at its slot counts."""
    from geomesa_tpu.filter.bounds import extract
    from geomesa_tpu.filter.cql import parse

    box_slots, time_slots = slots
    e = extract(parse(cql), SFT.geom_field, SFT.dtg_field)
    boxes, times = standing_query_payload(SFT, cql, box_slots, time_slots)
    want_b = _ref_pack_boxes(
        None if e.boxes is None else _ref_payload_boxes(e.boxes), slots=box_slots)
    want_t = _ref_pack_times(_ref_time_quads(SFT, e.intervals), slots=time_slots)
    assert np.array_equal(boxes, want_b) and np.array_equal(times, want_t)


# -- the batched fan-outs answer as before

_D = "2017-07-0{}T00:00:00Z"
_BATCH = [
    # one box, one window: batched
    f"BBOX(geom, -60, -30, 0, 30) AND dtg DURING {_D.format(1)}/{_D.format(5)}",
    f"BBOX(geom, 10, -20, 70, 40) AND dtg DURING {_D.format(2)}/{_D.format(6)}",
    # an antimeridian box (two boxes), and no time constraint: batched
    f"BBOX(geom, 150, -40, -150, 40) AND dtg DURING {_D.format(2)}/{_D.format(8)}",
    "BBOX(geom, 20, -10, 80, 60)",
    f"dtg DURING {_D.format(3)}/{_D.format(4)}",
    # disjoint: two boxes that do not meet, two windows that do not meet
    "BBOX(geom, 0, 0, 10, 10) AND BBOX(geom, 20, 20, 30, 30)",
    f"dtg DURING {_D.format(1)}/{_D.format(2)} AND dtg DURING {_D.format(4)}/{_D.format(5)}",
    # not batchable: an OR of boxes, an attribute predicate
    "BBOX(geom, -100, -40, -60, 0) OR BBOX(geom, 40, 0, 90, 50)",
    "BBOX(geom, -30, -30, 30, 30) AND name = 'n1'",
]
_PACKED = 5  # the batched, non-disjoint CQLs of _BATCH


@pytest.fixture(scope="module")
def store():
    rng = np.random.default_rng(29)
    n = 3_000
    lon = rng.uniform(-175, 175, n)
    lat = rng.uniform(-80, 80, n)
    # half a second off every whole second: never on a window's
    # quantized endpoint, so loose counts equal exact ones
    dtg = T0 + rng.integers(0, 10 * 86_400, n) * 1000 + 500
    ds = DataStore(backend="tpu")
    ds.create_schema("b", "name:String,age:Integer,dtg:Date,*geom:Point")
    ds.write("b", [{"name": f"n{i % 3}", "age": int(i % 7), "dtg": int(t),
                    "geom": Point(float(x), float(y))}
                   for i, (x, y, t) in enumerate(zip(lon, lat, dtg))],
             fids=[str(i) for i in range(n)])
    ds.compact("b")
    return ds


def _deltas(ds, run):
    queries = ds.metrics.counter("store.batch_plan.queries")
    packed = ds.metrics.counter("store.batch_plan.packed")
    before = (queries.count, packed.count)
    out = run()
    return out, (queries.count - before[0], packed.count - before[1])


@pytest.mark.parametrize("loose", [True, False])
def test_count_many_mixed_batch(store, monkeypatch, loose):
    from geomesa_tpu.ops import refine

    qs = [Query(filter=q) for q in _BATCH] + [Query(filter=_BATCH[0], limit=5)]
    want = [store.query("b", q).count for q in qs]
    got, deltas = _deltas(store, lambda: store.count_many("b", qs, loose=loose))
    assert got == want, (got, want)
    assert got[5] == got[6] == 0 and sum(got) > 0
    assert deltas == (len(qs), _PACKED)
    # the untrimmed full-width scan answers the same
    monkeypatch.setattr(refine, "trim_slots",
                        lambda b, t, overlap=False: (b, t, 0))
    assert store.count_many("b", qs, loose=loose) == got


def test_density_many_viewport_batch(store):
    from geomesa_tpu.store.datastore import DataStore as DS

    oracle = DS(backend="oracle")
    oracle.create_schema("b", "name:String,age:Integer,dtg:Date,*geom:Point")
    main = store._state("b").snapshot()[0]
    oracle.write("b", main)
    oracle.compact("b")
    viewport = (-40.0, -30.0, 40.0, 30.0)
    qs = [
        _BATCH[0],  # partly inside the viewport
        "BBOX(geom, 100, 40, 120, 60)",  # clipped away: empty grid
        _BATCH[4],  # no box: the viewport itself
        _BATCH[5],  # disjoint
        _BATCH[8],  # residual: the exact path
    ]
    grids, deltas = _deltas(store, lambda: store.density_many(
        "b", qs, viewport, width=32, height=16))
    assert deltas == (len(qs), 2)
    for q, g in zip(qs, grids):
        exact = oracle.query("b", Query(filter=q, hints={"density": {
            "bbox": viewport, "width": 32, "height": 16}})).density
        assert g.shape == (16, 32)
        assert np.array_equal(g, exact), q
    assert grids[1].sum() == 0 and grids[2].sum() > 0


def test_aggregate_many_mixed_batch(store):
    from geomesa_tpu.ops import referee

    qs = [Query(filter=q) for q in _BATCH]
    out, deltas = _deltas(store, lambda: store.aggregate_many(
        "b", qs, group_by=["name"], value_cols=["age"]))
    assert deltas[0] >= 1 and deltas[1] <= deltas[0]
    main, _i, _b, _s, delta = store._state("b").snapshot()
    served = 0
    for q, res in zip(qs, out):
        if res is None:
            continue  # the caller's host fold answers it
        served += 1
        want = referee.referee_agg(store._state("b").sft, main, delta, q,
                                   ["name"], ["age"])
        ok, detail = referee.agg_equal(referee.live_agg_map(res, ["age"]), want)
        assert ok, (q.filter, detail)
    assert served >= _PACKED
