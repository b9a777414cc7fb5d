"""The trace reduction on small traces recorded on TPU v5 lite chips
(``data/tpu_tiny.xplane.pb``, one chip: three ``bench.request``
annotations, each around a jitted reduction and a jitted sort of 2**20
floats; ``data/tpu4_tiny.xplane.pb``, four chips: the same with the
floats sharded over the chips, so the reduction ends in an all-reduce
and the sort gathers), and on synthetic traces of one and two device
planes."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import trace_reduce

TRACE = str(Path(__file__).parent / "data" / "tpu_tiny.xplane.pb")
TRACE4 = str(Path(__file__).parent / "data" / "tpu4_tiny.xplane.pb")


def _union_by_hand(intervals, lo, hi):
    """Busy nanoseconds by marking every covered nanosecond offset."""
    covered = set()
    for s, e in intervals:
        covered.update(range(int(max(s, lo)), int(min(e, hi))))
    return len(covered)


def test_union_of_intervals():
    iv = [(0, 10), (5, 15), (20, 30), (25, 26), (40, 50)]
    assert trace_reduce.union_ns(iv, 0, 100) == 35
    assert trace_reduce.union_ns(iv, 8, 45) == _union_by_hand(iv, 8, 45)
    assert trace_reduce.idle_gaps(iv, 0, 60) == [(15, 20), (30, 40), (50, 60)]


def test_recorded_trace():
    import jax

    out = trace_reduce.reduce(TRACE)
    assert out is not None
    pd = jax.profiler.ProfileData.from_file(TRACE)
    marks = trace_reduce.anchors(pd)
    assert len(marks) == 3
    lo, hi = marks[0][0], marks[-1][1]
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    ops = trace_reduce.device_ops(pd)
    # busy is the union of the device op intervals inside the window
    clipped = [(max(s, lo), min(e, hi)) for _, s, e, _ in ops if min(e, hi) > max(s, lo)]
    assert out["busy_s"] == pytest.approx(_union_by_hand(clipped, lo, hi) / 1e9)
    assert 0.0 < out["busy_s"] < out["window_s"]
    idle = 1.0 - out["busy_s"] / out["window_s"]
    assert 0.0 <= idle <= 1.0
    names = {k for k, _ in out["breakdown"]["device_ops"]}
    assert any(trace_reduce.op_kind(n) == "sort" for n in names)


def test_idle_gaps_split_by_innermost_span():
    # (start, end, depth, name): one request [0, 100) with plan [10, 30)
    # and reduce [60, 90) inside it; a second request [120, 150)
    spans = [(0, 100, 0, "req"), (10, 30, 1, "plan"), (60, 90, 1, "reduce"),
             (120, 150, 0, "req")]
    seg = trace_reduce.label_segments(spans)
    assert seg == [(0, 10, "req"), (10, 30, "plan"), (30, 60, "req"),
                   (60, 90, "reduce"), (90, 100, "req"), (100, 120, "client"),
                   (120, 150, "req")]
    got = trace_reduce.gaps_by_label([(20, 70), (95, 130)], seg)
    want = {"plan": 10, "req": 30 + 5 + 10, "reduce": 10, "client": 20}
    assert {k: round(v * 1e9) for k, v in got.items()} == want


def test_idle_gaps_add_up_to_idle_time():
    from geomesa_tpu.obs.trace import Span
    import jax

    marks = trace_reduce.anchors(jax.profiler.ProfileData.from_file(TRACE))
    roots, starts = [], []
    for i, (s, e) in enumerate(marks):  # one span tree a request, any clock
        root = Span("bench.request", {}, None)
        root.t0_ns, root.t1_ns = 10**6 + i * 10**8, 10**6 + i * 10**8 + (e - s)
        plan = Span("plan", {}, None)
        plan.t0_ns, plan.t1_ns = root.t0_ns, root.t0_ns + (e - s) // 3
        root.children.append(plan)
        roots.append(root)
        starts.append(root.t0_ns)
    out = trace_reduce.reduce(TRACE, starts, roots)
    idle = sum(v for _, v in out["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"])


def test_op_names():
    ev = "%batched_count.1 = s32[64,128]{1,0} custom-call(s32[1,3]{1,0} %a)"
    assert trace_reduce.op_name(ev) == "batched_count.1"
    assert trace_reduce.op_kind(ev) == "batched_count"
    assert trace_reduce.op_kind("%copy-start") == "copy-start"


def test_cpu_trace_has_no_device_plane(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    assert trace_reduce.reduce(trace_reduce.find_xplane(tmp_path)) is None


def _span(name, t0, t1, children=()):
    return SimpleNamespace(name=name, t0_ns=t0, t1_ns=t1, children=list(children))


def _plane(name, line, events):
    evs = [SimpleNamespace(name=n, start_ns=s, end_ns=e) for n, s, e in events]
    return SimpleNamespace(name=name, lines=[SimpleNamespace(name=line, events=evs)])


def _roots(marks):
    """One span tree a request, on the trace's own clock: the request,
    with `plan` over its first third."""
    return [_span("bench.request", s, e, [_span("plan", s, s + (e - s) // 3)])
            for s, e in marks]


def _labels_of_one_plane(pd, starts, roots):
    """The idle labels as read from the first device plane alone."""
    marks = trace_reduce.anchors(pd)
    lo, hi = marks[0][0], marks[-1][1]
    ops = trace_reduce.device_ops(pd)
    first = sorted({o[3] for o in ops})[0]
    iv = [(s, e) for _, s, e, plane in ops if plane == first]
    offset = marks[0][0] - starts[0]
    seg = trace_reduce.label_segments(trace_reduce.span_intervals(roots, offset))
    return dict(trace_reduce.gaps_by_label(trace_reduce.idle_gaps(iv, lo, hi), seg))


def test_one_recorded_plane_labels_as_before():
    import jax

    pd = jax.profiler.ProfileData.from_file(TRACE)
    marks = trace_reduce.anchors(pd)
    roots, starts = _roots(marks), [s for s, _ in marks]
    out = trace_reduce.reduce(TRACE, starts, roots)
    assert dict(out["breakdown"]["idle_gaps"]) == _labels_of_one_plane(pd, starts, roots)


# two requests, [0, 100) under `plan` for its first half and [100, 200)
# under `reduce` for its second half; chip 0 runs [50, 150), chip 1 runs
# [0, 50) and [150, 200), so chip 0 idles under plan and reduce, chip 1
# under the requests' own spans
HOST = _plane("/host:CPU", "python", [("bench.request", 0, 100),
                                      ("bench.request", 100, 200)])
CHIP0 = _plane("/device:TPU:0", trace_reduce.OPS_LINE,
               [("%fusion.1 = f32[8]", 50, 100), ("%fusion.1 = f32[8]", 100, 150)])
CHIP1 = _plane("/device:TPU:1", trace_reduce.OPS_LINE,
               [("%fusion.1 = f32[8]", 0, 50), ("%all-reduce.2 = f32[8]", 150, 200)])
ROOTS = [_span("bench.request", 0, 100, [_span("plan", 0, 50)]),
         _span("bench.request", 100, 200, [_span("reduce", 150, 200)])]
STARTS = [0, 100]


@pytest.mark.parametrize("planes, want", [
    ([CHIP0], {"plan": 50, "reduce": 50}),
    ([CHIP0, CHIP1], {"plan": 25, "reduce": 25, "bench.request": 50}),
])
def test_idle_labels_are_means_over_planes(monkeypatch, planes, want):
    import jax

    pd = SimpleNamespace(planes=[HOST, *planes])
    monkeypatch.setattr(jax.profiler, "ProfileData",
                        SimpleNamespace(from_file=lambda path: pd))
    out = trace_reduce.reduce("synthetic", STARTS, ROOTS)
    labels = dict(out["breakdown"]["idle_gaps"])
    assert {k: round(v * 1e9) for k, v in labels.items()} == want
    # busy is the mean per chip, and the labels fill the rest of the window
    assert out["window_s"] == pytest.approx(200e-9)
    assert out["busy_s"] == pytest.approx(100e-9)
    assert sum(labels.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    # op seconds are summed over the chips
    assert sum(out["op_seconds"].values()) == pytest.approx(100e-9 * len(planes))
    if len(planes) == 1:  # one plane reads exactly what the first plane alone did
        assert labels == _labels_of_one_plane(pd, STARTS, ROOTS)


def test_recorded_four_chip_trace():
    import jax

    pd = jax.profiler.ProfileData.from_file(TRACE4)
    marks = trace_reduce.anchors(pd)
    lo, hi = marks[0][0], marks[-1][1]
    ops = trace_reduce.device_ops(pd)
    planes = sorted({o[3] for o in ops})
    assert planes == [f"/device:TPU:{i}" for i in range(4)]
    out = trace_reduce.reduce(TRACE4, [s for s, _ in marks], _roots(marks))
    # busy is the mean over the chips of each chip's union of op intervals
    per_chip = [trace_reduce.union_ns([(s, e) for _, s, e, p in ops if p == plane], lo, hi)
                for plane in planes]
    assert out["busy_s"] == pytest.approx(sum(per_chip) / 4 / 1e9)
    idle = sum(v for _, v in out["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"])
    # op seconds are chip-seconds: at least the busy time of all four chips
    assert sum(out["op_seconds"].values()) >= 4 * out["busy_s"] * (1 - 1e-9)
    kinds = {trace_reduce.op_kind(n) for n, _ in out["breakdown"]["device_ops"]}
    assert {"all-reduce", "all-gather", "sort"} <= kinds
