"""The span readers ``stage_ms``, ``batch_plan_ms`` and ``telemetry_ms``
on hand-built span trees: totals per CQL, only the spans each one names,
and nothing where the program opens no such span."""

from types import SimpleNamespace

import pytest

from benchmark.run import _metric_reader
from geomesa_tpu.obs.trace import Span

MS = 1_000_000  # ns


def _span(name, t0_ms, t1_ms, *children):
    sp = Span(name, {}, None)
    sp.t0_ns, sp.t1_ns = int(t0_ms * MS), int(t1_ms * MS)
    sp.children.extend(children)
    return sp


def _select_request(at):
    """One query: plan 2 ms, dispatch with stage 1.5 / jit / sync / unpack,
    telemetry 0.25 ms."""
    return _span(
        "bench.request", at, at + 10,
        _span("query", at, at + 9.5,
              _span("plan", at, at + 2),
              _span("dispatch", at + 2, at + 7,
                    _span("stage", at + 2, at + 3.5),
                    _span("jit", at + 3.5, at + 4),
                    _span("sync", at + 4, at + 6),
                    _span("unpack", at + 6, at + 6.5)),
              _span("refine", at + 7, at + 8),
              _span("reduce", at + 8, at + 9),
              _span("telemetry", at + 9, at + 9.25)))


def _count_request(at, n=4):
    """One count_many call of ``n`` CQLs: plan 3 ms, stage 0.5, jit, sync,
    telemetry 1 ms."""
    return _span(
        "bench.request", at, at + 20,
        _span("count_many", at, at + 19,
              _span("plan", at, at + 3),
              _span("stage", at + 3, at + 3.5),
              _span("jit", at + 3.5, at + 4),
              _span("sync", at + 4, at + 18),
              _span("telemetry", at + 18, at + 19)))


def _ctx(roots, queries):
    return SimpleNamespace(roots=roots, queries=queries,
                           requests=len(roots))


def _read(name, ctx):
    return _metric_reader(name)(ctx)


def test_select_cell():
    ctx = _ctx([_select_request(0), _select_request(20)], 2)
    assert _read("stage_ms", ctx) == pytest.approx(1.5)
    assert _read("telemetry_ms", ctx) == pytest.approx(0.25)
    # no count_many span: the batch planner's metric has nothing to read
    assert _read("batch_plan_ms", ctx) is None


def test_count_cell():
    ctx = _ctx([_count_request(0), _count_request(30)], 8)
    assert _read("batch_plan_ms", ctx) == pytest.approx(2 * 3 / 8)
    assert _read("stage_ms", ctx) == pytest.approx(2 * 0.5 / 8)
    assert _read("telemetry_ms", ctx) == pytest.approx(2 * 1 / 8)


def test_plan_under_query_and_count_many():
    """A tree holding both kinds of ``plan``: batch_plan_ms reads only the
    one under ``count_many``, plan_ms reads both."""
    mixed = _span(
        "bench.request", 0, 40,
        _select_request(0).children[0],
        _count_request(15).children[0])
    ctx = _ctx([mixed], 5)
    assert _read("batch_plan_ms", ctx) == pytest.approx(3 / 5)
    assert _read("plan_ms", ctx) == pytest.approx((2 + 3) / 5)
    assert _read("stage_ms", ctx) == pytest.approx((1.5 + 0.5) / 5)
    assert _read("telemetry_ms", ctx) == pytest.approx((0.25 + 1) / 5)


@pytest.mark.parametrize("name", ["stage_ms", "batch_plan_ms", "telemetry_ms"])
def test_nothing_to_read(name):
    """A program that opens none of the spans (the parent commit's), or
    a window that answered nothing, gives no value and raises nothing."""
    bare = _span("bench.request", 0, 5, _span("query", 0, 5,
                                              _span("plan", 0, 1),
                                              _span("dispatch", 1, 4)))
    assert _read(name, _ctx([bare], 1)) is None
    assert _read(name, _ctx([_count_request(0)], 0)) is None
    assert _read(name, _ctx([], 0)) is None
