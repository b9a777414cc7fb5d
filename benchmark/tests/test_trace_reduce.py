"""The trace reduction on a small trace recorded on one TPU v5 lite
(``data/tpu_tiny.xplane.pb``: three ``bench.request`` annotations, each
around a jitted reduction and a jitted sort of 2**20 floats)."""

from pathlib import Path

import pytest

from benchmark import trace_reduce

TRACE = str(Path(__file__).parent / "data" / "tpu_tiny.xplane.pb")


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
