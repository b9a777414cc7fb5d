"""Compile the store's device steps for a described TPU v5e, at real widths.

Nothing runs: each step is lowered and compiled by the TPU compiler for
chips that are described, not attached (``jax.experimental.topologies``),
which refuses what interpret mode on the CPU cannot see — tile-misaligned
blocks, VMEM over-use, a program that does not fit HBM, an op the chip
cannot lower. The topology is described inside a module fixture (never at
import: only one process may load the TPU library, and the suite runs with
several workers); all such compiles live in this one file.

The step factories choose their Pallas/MXU branches from
``jax.default_backend()``, which is the CPU here; the ``on_tpu`` fixture
steers that per test.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from geomesa_tpu.parallel.mesh import DATA_AXIS, QUERY_AXIS

ROWS = 1 << 22  # 4M rows per chip
Q = 64
BOXES, TIMES = 8, 16  # pack_boxes / pack_times slot counts of the store


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one:
    # keep these out of any persistent cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _mesh(topo, chips: int) -> Mesh:
    devs = np.array(topo.devices[:chips]).reshape(chips, 1)
    return Mesh(devs, (DATA_AXIS, QUERY_AXIS))


def _cols(mesh, n_cols: int, chips: int, dtype=jnp.int32):
    sh = NamedSharding(mesh, P(DATA_AXIS))
    return [jax.ShapeDtypeStruct((ROWS * chips,), dtype, sharding=sh)
            for _ in range(n_cols)]


def _rep(mesh, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, P()))


def _queries(mesh, q: int = Q, lead=()):
    return (_rep(mesh, (*lead, q, BOXES, 4)), _rep(mesh, (*lead, q, TIMES, 4)))


def _compile(step, *args) -> str:
    return step.lower(*args).compile().as_text()


def _reducers(hlo: str) -> set:
    """The combiner ops of every all-reduce in compiled HLO text."""
    bodies = {}
    name = None
    for line in hlo.splitlines():
        s = line.strip()
        if s.endswith("{") and ("(" in s) and not s.startswith("ROOT"):
            name = s.split()[0].lstrip("%")
            if name == "ENTRY":
                name = s.split()[1].lstrip("%")
            bodies[name] = []
        elif name is not None:
            bodies[name].append(s)
    ops = set()
    for line in hlo.splitlines():
        if "all-reduce(" not in line and "all-reduce-start(" not in line:
            continue
        if "to_apply=" not in line:
            continue
        comp = line.split("to_apply=")[1].split(",")[0].split()[0].lstrip("%")
        for b in bodies.get(comp, []):
            m = re.match(r"ROOT\s+\S+\s+=\s+.*?\s([a-z][a-z0-9-]*)\(", b)
            if m:
                ops.add(m.group(1))
    return ops


# -- the four Pallas kernels, one chip ---------------------------------------

def _kernel_args(one, n_q: int, boxes: int = BOXES, times: int = TIMES):
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731
    col = i32((ROWS,))
    return (col, col, col, col, i32(()), i32(()),
            i32((n_q, boxes, 4)), i32((n_q, times, 4)))


@pytest.mark.parametrize("kernel", ["batched_count", "batched_count_hits",
                                    "z3_encode", "z2_encode"])
def test_pallas_kernel_compiles(topo, kernel):
    from jax.sharding import SingleDeviceSharding

    from geomesa_tpu.ops import pallas_kernels as pk

    one = SingleDeviceSharding(topo.devices[0])
    fn = getattr(pk, kernel)
    if kernel.startswith("batched"):
        args = _kernel_args(one, Q)
    else:
        u32 = jax.ShapeDtypeStruct((ROWS,), jnp.uint32, sharding=one)
        args = (u32,) * (3 if kernel == "z3_encode" else 2)
    assert "tpu_custom_call" in _compile(fn, *args)


@pytest.mark.parametrize("boxes,times", [(BOXES, TIMES), (1, 1)])
def test_batched_count_slot_shapes_compile(topo, boxes, times):
    """``count_many`` trims its payloads to the batch's live slots: the
    one-box, one-window shape compiles beside the full width."""
    from jax.sharding import SingleDeviceSharding

    from geomesa_tpu.ops.pallas_kernels import batched_count

    one = SingleDeviceSharding(topo.devices[0])
    args = _kernel_args(one, Q, boxes, times)
    assert "tpu_custom_call" in _compile(batched_count, *args)


# -- sharded steps of the store's query path ---------------------------------

@pytest.mark.parametrize("chips", [1, 4])
def test_batched_count_step_compiles(topo, on_tpu, chips):
    from geomesa_tpu.parallel.query import make_batched_count_step

    mesh = _mesh(topo, chips)
    step = make_batched_count_step(mesh, impl="auto")
    hlo = _compile(step, *_cols(mesh, 4, chips), _rep(mesh, ()),
                   *_queries(mesh))
    assert "tpu_custom_call" in hlo
    if chips > 1:
        assert "add" in _reducers(hlo)  # the psum over data shards


@pytest.mark.parametrize("chips", [1, 4])
def test_grouped_agg_step_compiles(topo, on_tpu, chips):
    """Segment-reduce per shard; counts and sums merge with psum, the f64
    extrema through an all_gather (the TPU compiler lowers only SUM
    all-reduces of f64)."""
    from geomesa_tpu.parallel.query import make_grouped_agg_step

    mesh = _mesh(topo, chips)
    step = make_grouped_agg_step(mesh, n_groups=32, n_vals=1, capacity=512)
    vals = jax.ShapeDtypeStruct((1, ROWS * chips), jnp.float64,
                                sharding=NamedSharding(mesh, P(None, DATA_AXIS)))
    hlo = _compile(step, *_cols(mesh, 6, chips), vals, _rep(mesh, ()),
                   *_queries(mesh, 4))
    if chips > 1:
        assert "add" in _reducers(hlo) and "all-gather" in hlo


@pytest.mark.parametrize("kind", ["count", "gather"])
def test_select_steps_compile(topo, on_tpu, kind):
    from geomesa_tpu.parallel.query import (
        make_select_count_step,
        make_select_gather_step,
    )

    mesh = _mesh(topo, 1)
    step = (make_select_count_step(mesh) if kind == "count"
            else make_select_gather_step(mesh, capacity=1 << 15))
    sh = NamedSharding(mesh, P(DATA_AXIS, None))
    idx = jax.ShapeDtypeStruct((1, 1 << 15), jnp.int32, sharding=sh)
    cnt = jax.ShapeDtypeStruct((1,), jnp.int32,
                               sharding=NamedSharding(mesh, P(DATA_AXIS)))
    _compile(step, *_cols(mesh, 4, 1), idx, cnt,
             _rep(mesh, (BOXES, 4)), _rep(mesh, (TIMES, 4)))


@pytest.mark.parametrize("chips", [1, 4])
def test_planned_select_pair_compiles(topo, on_tpu, chips):
    """The count/gather pair behind ``select_many``; the gather's
    positions come back through an all_gather on four chips."""
    from geomesa_tpu.parallel.query import (
        make_planned_count_step,
        make_planned_gather_step,
    )

    mesh = _mesh(topo, chips)
    pairs = 512
    count = make_planned_count_step(mesh, 32, 4096, pairs)
    _compile(count, *_cols(mesh, 4, chips), _rep(mesh, ()),
             _rep(mesh, (1, pairs)), _rep(mesh, (1, pairs)),
             *_queries(mesh, 32, lead=(1,)))
    gather = make_planned_gather_step(mesh, 4096, pairs, capacity=1 << 19)
    _compile(gather, *_cols(mesh, 4, chips), _rep(mesh, ()),
             _rep(mesh, (pairs,)), _rep(mesh, (pairs,)), *_queries(mesh, 32))


def test_edge_gather_step_compiles(topo, on_tpu):
    from geomesa_tpu.parallel.query import make_batched_edge_gather_step

    mesh = _mesh(topo, 1)
    step = make_batched_edge_gather_step(mesh, capacity=512)
    _compile(step, *_cols(mesh, 4, 1), _rep(mesh, ()), *_queries(mesh))


def test_density_step_compiles(topo, on_tpu):
    """The MXU one-hot density (the TPU branch of the factory)."""
    from geomesa_tpu.parallel.query import make_batched_density_step

    mesh = _mesh(topo, 1)
    step = make_batched_density_step(mesh, 256, 256)
    _compile(step, *_cols(mesh, 4, 1), _rep(mesh, ()), *_queries(mesh),
             _rep(mesh, (Q, 4)))


def test_matrix_scan_step_compiles(topo, on_tpu):
    """The stream's standing-query scan over one 64K-row chunk."""
    from geomesa_tpu.parallel.query import make_matrix_scan_step

    mesh = _mesh(topo, 1)
    step = make_matrix_scan_step(mesh, topk=64)
    sh = NamedSharding(mesh, P(DATA_AXIS))
    chunk = [jax.ShapeDtypeStruct((1 << 16,), jnp.int32, sharding=sh)] * 4
    hlo = _compile(step, *chunk, _rep(mesh, ()),
                   _rep(mesh, (8, 2, 4)), _rep(mesh, (8, 2, 4)))
    assert "tpu_custom_call" in hlo


def test_device_sort_reshard_step_compiles(topo, on_tpu):
    """``device_sort_perm``'s sample-sort shuffle on four chips: u64 keys
    routed by ``all_to_all``, then a local lexsort with one tiebreak
    column. Its sorts take about a minute to compile whatever the width,
    so this one runs at 64K rows per chip."""
    from geomesa_tpu.parallel.reshard import make_reshard_step

    chips, rows = 4, 1 << 16
    mesh = _mesh(topo, chips)
    step = make_reshard_step(mesh, 2, (2 * rows) // chips + 8, 1)
    sh = NamedSharding(mesh, P(DATA_AXIS))
    key = jax.ShapeDtypeStruct((rows * chips,), jnp.uint64, sharding=sh)
    col = jax.ShapeDtypeStruct((rows * chips,), jnp.int32, sharding=sh)
    hlo = _compile(step, key, _rep(mesh, ()), _rep(mesh, (chips,), jnp.uint64),
                   col, col)
    assert "all-to-all" in hlo
