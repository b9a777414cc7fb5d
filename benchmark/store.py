"""Set-up and health of the system under test.

Copied from ``chip_smoke.py`` (PR 21) so that no later PR moves them:
the store build (``FeatureTable.from_columns`` → ``DataStore`` on an
explicit one-device mesh → ``write``, which compacts past its threshold,
→ ``compact``), the compile clock, the device-health counters and the
peak-HBM reading.
"""

from __future__ import annotations

_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}


class CompileClock:
    """Sums JAX's own compile-phase durations and counts backend compiles
    (a persistent-cache hit is a short one) and cache hits/misses."""

    def __init__(self):
        import jax.monitoring as jm

        self.secs = dict.fromkeys(_COMPILE_EVENTS.values(), 0.0)
        self.backend_compiles = 0
        self.events = {"cache_hits": 0, "cache_misses": 0}
        jm.register_event_duration_secs_listener(self._on_duration)
        jm.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_kw):
        key = _COMPILE_EVENTS.get(name)
        if key:
            self.secs[key] += secs
            if key == "backend_compile_s":
                self.backend_compiles += 1

    def _on_event(self, name, **_kw):
        tail = name.rsplit("/", 1)[-1]
        if tail in self.events:
            self.events[tail] += 1

    def report(self) -> dict:
        return {**self.secs, "backend_compiles": self.backend_compiles,
                **self.events}


def build_store(cfg: dict, data):
    """A one-chip ``DataStore`` holding ``data`` under the configuration's
    spec, written and compacted as a bulk ingest is."""
    from geomesa_tpu.parallel.mesh import make_mesh
    from geomesa_tpu.schema.columnar import Column, FeatureTable, point_column
    from geomesa_tpu.schema.sft import parse_spec
    from geomesa_tpu.store.backends import TpuBackend
    from geomesa_tpu.store.datastore import DataStore

    sft = parse_spec(cfg["type_name"], cfg["spec"])
    cols = {}
    for a in sft.attributes:
        if a.name == sft.geom_field:
            cols[a.name] = point_column(data.lon, data.lat)
        elif a.name == sft.dtg_field:
            cols[a.name] = Column(a.type, data.t)
        else:
            cols[a.name] = Column(a.type, data.attrs[a.name])
    table = FeatureTable.from_columns(sft, data.fids, cols)
    ds = DataStore(backend=TpuBackend(mesh=make_mesh(1)))
    ds.create_schema(sft)
    ds.write(cfg["type_name"], table)
    ds.compact(cfg["type_name"])
    return ds


def resident_columns(ds, type_name: str, index: str = "z3") -> dict:
    """The device arrays of one resident index, by column name."""
    state = ds._state(type_name).backend_state or {}
    dev = state.get(index)
    return {} if dev is None else dict(dev.cols)


def device_health(ds) -> dict:
    m = ds.metrics
    return {
        "device_failovers": m.counter("store.query.device_failovers").count,
        "sort_failures": m.counter("store.device.sort_failures").count,
        "load_failures": m.counter("store.device.load_failures").count,
        "circuit_open": int(bool(m.gauge("store.device.circuit_open").value)),
    }


def peak_hbm(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")
